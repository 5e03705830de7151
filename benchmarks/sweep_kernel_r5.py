"""Round-5 histogram-kernel floor attack (VERDICT r4 #2).

Sweeps the two levers the 4-bit decision note left standing at the bench
shape (default 1M x 28 x 256):

  (a) row_tile x feat_tile grid of the production f32 multi kernel at
      the bench wave width (14 f32 leaf slots = 126 LHS rows) — no swept
      tile table was ever recorded; PROFILE r3 only fixed row_tile=2048.
  (b) the padded-M axis under the int8 lattice: quantized waves fit 42
      leaf slots (3 rows each) where f32 fits 14 — W in {8, 14, 28, 42}
      prices the histograms-per-pass curve that decides whether
      use_quantized_grad + wider waves beat the ~15 ms bf16 floor.

Timing: dependency-chained fori_loop slope (k=1 vs k=K), the only
honest method on the r3 backend (PROFILE.md r3b — block_until_ready
returned early there).  Each config prints as it lands so a sweep cut
short keeps the prefix.  Budget-aware: SWEEP_KERNEL_BUDGET seconds (default
900) — most-important configs first.

Usage: python benchmarks/sweep_kernel_r5.py [N] [F] [MB]
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
F = int(sys.argv[2]) if len(sys.argv) > 2 else 28
MB = int(sys.argv[3]) if len(sys.argv) > 3 else 256
BUDGET = float(os.environ.get("SWEEP_KERNEL_BUDGET", 900))
# CPU smoke-testing of the harness mechanics (the kernels are TPU-only)
INTERPRET = os.environ.get("SWEEP_KERNEL_INTERPRET") == "1"
T0 = time.time()


def main():
    import numpy as np

    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops.fused import quantize_gradients
    from lightgbm_tpu.ops.pallas_hist import (_run_kernel_multi,
                                              _run_kernel_multi_i8,
                                              _split_payload9)

    plat = jax.devices()[0].platform
    print(f"backend={plat} n={N} f={F} mb={MB} budget={BUDGET:.0f}s",
          flush=True)
    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, MB, (F, N)).astype(
        np.uint8 if MB <= 256 else np.uint16))
    payload = jnp.asarray(rng.randn(N, 3).astype(np.float32))
    leaf_id = jnp.asarray(rng.randint(0, 48, N).astype(np.int32))
    pw9 = _split_payload9(payload)

    gq, hq, (sg, sh) = quantize_gradients(
        payload[:, 0], jnp.abs(payload[:, 1]) + 0.1, 8, return_scales=True)
    pw3 = jnp.stack([gq, hq, jnp.ones_like(gq)]).astype(jnp.int8)

    def timed(fn, out_shape):
        """ms/call by fori_loop slope; None on failure."""
        k = 6

        @jax.jit
        def chain(k_):
            def body(i, acc):
                return fn(acc[0, 0, 0])
            return jax.lax.fori_loop(0, k_, body,
                                     jnp.zeros(out_shape, jnp.float32))

        np.asarray(chain(1))          # compile + warmup
        t0 = time.perf_counter()
        np.asarray(chain(1))
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.asarray(chain(k))
        tk = time.perf_counter() - t0
        return (tk - t1) / (k - 1) * 1e3

    results = []

    def run(tag, builder, out_shape, n_hists):
        if time.time() - T0 > BUDGET:
            print(f"[kernel-sweep] budget exhausted before {tag}",
                  flush=True)
            return
        try:
            ms = timed(builder, out_shape)
            per_leaf = ms / n_hists
            results.append({"config": tag, "ms_per_call": round(ms, 2),
                            "n_hists": n_hists,
                            "ms_per_hist": round(per_leaf, 3)})
            print(f"{tag:<36} {ms:8.2f} ms/call  "
                  f"{per_leaf:7.3f} ms/hist", flush=True)
        except Exception as e:
            print(f"{tag:<36} FAILED: {type(e).__name__}: "
                  f"{str(e)[:160]}", flush=True)

    # ---- (b) int8 width curve first (the decision the bench needs) ----
    for W in (8, 14, 28, 42):
        slots = jnp.arange(W, dtype=jnp.int32)

        def fn(eps, slots=slots):
            lid = leaf_id + (eps * 1e-20).astype(jnp.int32)
            return _run_kernel_multi_i8(bins, pw3, lid, slots, MB,
                                        2048, 0, INTERPRET)\
                .astype(jnp.float32)
        run(f"int8 W={W} rt=2048", fn, (F, W * 3, MB), W)

    # ---- (a) f32 tile grid at the production width 14 ----
    slots14 = jnp.arange(14, dtype=jnp.int32)
    for rt in (1024, 2048, 4096):
        for ft in (0, 7, 14):
            def fn(eps, rt=rt, ft=ft):
                lid = leaf_id + (eps * 1e-20).astype(jnp.int32)
                return _run_kernel_multi(bins, pw9, lid, slots14, MB,
                                         rt, ft, INTERPRET)
            run(f"f32 W=14 rt={rt} ft={ft or F}", fn, (F, 14 * 9, MB), 14)

    # ---- int8 tile spots at the best width (42) ----
    slots42 = jnp.arange(42, dtype=jnp.int32)
    for rt in (1024, 4096):
        def fn(eps, rt=rt):
            lid = leaf_id + (eps * 1e-20).astype(jnp.int32)
            return _run_kernel_multi_i8(bins, pw3, lid, slots42, MB,
                                        rt, 0, INTERPRET).astype(jnp.float32)
        run(f"int8 W=42 rt={rt}", fn, (F, 42 * 3, MB), 42)

    print("KERNELS " + json.dumps({"backend": plat, "n": N, "f": F,
                                   "mb": MB, "results": results}),
          flush=True)


if __name__ == "__main__":
    main()
