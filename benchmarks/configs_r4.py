"""Candidate bench configs — ONE definition shared by the quality
sweep (sweep_quality.py, CPU-runnable, multi-seed, orders configs by
held-out AUC) and the speed sweep (sweep_speed_r4.py, TPU), so the two
sweeps can never silently measure different configs under one name.
(The r4 single-seed harness sweep_quality_r4.py is retired: single-seed
orderings at these scales are seed noise — PROFILE.md r4 addendum.)"""

BASE = {"objective": "binary", "num_leaves": 31, "max_bin": 255,
        "learning_rate": 0.1, "verbosity": -1}

# the SHIPPED bench config (bench.py + bench_families.py derive theirs
# from this name, so the headline bench, the quality sweep, and the
# family rows can never silently measure different "shipped" configs).
# r5 decider: W8 + strict tail 16 + no gain floor — best wave mean AND
# most seed-stable at both 500k and 2M (PROFILE.md r5).
SHIPPED = "wave_w8_tail16"

QUANT = {"use_quantized_grad": True, "num_grad_quant_bins": 15}

CONFIGS = {
    # ordered most-important-first (the speed sweep runs them in order
    # so a run cut short loses the least-important tail)
    "wave_w8_tail_auto+quant": {"tree_grow_policy": "wave",
                                "tpu_wave_width": 8,
                                "tpu_wave_gain_ratio": 0, **QUANT},
    "wave_w8_tail_auto": {"tree_grow_policy": "wave", "tpu_wave_width": 8,
                          "tpu_wave_gain_ratio": 0},
    "wave_r3bench": {"tree_grow_policy": "wave", "tpu_wave_width": 8,
                     "tpu_wave_gain_ratio": 0.8, "tpu_wave_strict_tail": 0},
    "strict": {},
    "wave_w8_tail6+quant": {"tree_grow_policy": "wave",
                            "tpu_wave_width": 8, "tpu_wave_gain_ratio": 0,
                            "tpu_wave_strict_tail": 6, **QUANT},
    "wave_r3bench+quant": {"tree_grow_policy": "wave", "tpu_wave_width": 8,
                           "tpu_wave_gain_ratio": 0.8,
                           "tpu_wave_strict_tail": 0, **QUANT},
    "strict+quant": dict(QUANT),
    # quality-sweep extras (cheap on CPU, skipped by the speed sweep's
    # default ordering unless explicitly named)
    "wave_r3bench+tail": {"tree_grow_policy": "wave", "tpu_wave_width": 8,
                          "tpu_wave_gain_ratio": 0.8},
    "wave_w6_tail_auto": {"tree_grow_policy": "wave", "tpu_wave_width": 6,
                          "tpu_wave_gain_ratio": 0},
    "wave_w8_tail16": {"tree_grow_policy": "wave", "tpu_wave_width": 8,
                       "tpu_wave_gain_ratio": 0, "tpu_wave_strict_tail": 16},
    # r5: wide-wave quantized challengers — the int8 lattice fits 42 leaf
    # slots per MXU pass vs f32's 14 (PROFILE r3c kernel economics), so
    # IF the kernel width curve holds end-to-end these trade a known
    # small AUC cost for many fewer passes per tree.  The capacity-aware
    # floor keeps depth; tail16 keeps the strict endgame.
    "wave_w16_tail16+quant": {"tree_grow_policy": "wave",
                              "tpu_wave_width": 16,
                              "tpu_wave_gain_ratio": 0.8,
                              "tpu_wave_strict_tail": 16, **QUANT},
    "wave_w28_tail16+quant": {"tree_grow_policy": "wave",
                              "tpu_wave_width": 28,
                              "tpu_wave_gain_ratio": 0.8,
                              "tpu_wave_strict_tail": 16, **QUANT},
}
