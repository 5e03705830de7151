"""Shard-streamed wave grower — training without the [F, N] device matrix.

The in-memory growers hold the whole binned matrix in device memory, so
HBM — not `datastore_budget_mb` — is the real training ceiling even when
PR 9's datastore spills the host copy.  This engine decomposes ONE tree
of wave growth (ops/grow_wave.py) into per-shard device programs driven
by a host loop, so only the per-row training state stays resident:

  resident:   payload [N, 3] f32, leaf_id [N] i32 (plus the booster's
              score vectors) — O(N), independent of F
  transient:  at most TWO shard bin blocks [F, shard_rows] at a time
              (the double-buffered staging the budget math sizes)

Per round the datastore shards flow through `ShardPrefetcher` in PINNED
ascending shard order and a per-shard jitted program folds each block
into the wave's per-(leaf, feature) histogram carry
(ops/histogram.py `hist_stream_*`); the completed histograms then run
the UNCHANGED split scan (`find_best_split`) and state update.

Byte-identity to in-memory training is the hard invariant, and it holds
by construction, not by tolerance:

  * integer bin codes — a shard slice of the bin matrix is the same
    integers the assembled matrix holds;
  * accumulation order — the f32 carry applies each shard's rows with
    the same in-order scatter-add `segment_sum` lowers to, and shards
    arrive in pinned row order, so every (leaf, bin) cell sees the
    exact same sequence of float adds as the one-pass builder; the
    packed family carries int32 sums, associative under any grouping;
  * split math — the pick loop, the sibling-subtraction trick, the
    vmapped child search, and the finalize/prune are the SAME
    expressions as `make_wave_grower`, evaluated on bit-equal inputs.

The wave structure is what makes the decomposition possible: within one
wave every pick targets a pre-wave READY leaf and fresh children are
never re-picked, so the wave's row partitions are row-disjoint and can
be replayed per shard from the wave-start `leaf_id` (the pick loop
itself never reads bins — it only consumes cached per-leaf best splits).
A leaf-wise booster streams through the same engine as a width-1 wave
(`wave_strict_tail >= num_leaves` IS strict best-first order —
tests/test_wave.py `test_full_strict_tail_matches_strict`).

Cost model (the honest part): every wave re-reads the full datastore
once, so a tree costs ~ceil((L-1)/W) + 1 shard passes instead of one
matrix residency — leaf-wise (width 1) pays ~L passes per tree.  That
is the classic out-of-core trade (arXiv:2005.09148): disk/host
bandwidth buys back device memory.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from ..datastore.prefetch import PrefetchRunStats, ShardPrefetcher
from ..mesh.placement import stream_shard_plan
from ..ops.grow import (DeviceTree, GrowerSpec, _split_to_arrays,
                        child_bounds_basic, ic_allowed_from_used,
                        make_cegb_penalty, make_node_samplers,
                        split_go_left)
from ..ops.grow_wave import prune_wave_tail, wave_sizes
from ..ops.histogram import (hist_stream_finalize, hist_stream_init,
                             hist_stream_packed_finalize,
                             hist_stream_packed_init,
                             hist_stream_packed_update, hist_stream_update,
                             hist_sub, hist_value)
from ..ops.split import (NEG_INF, find_best_split, leaf_output,
                         refine_child_sums, smooth_output)
from ..telemetry import REGISTRY
from .. import telemetry

Array = jax.Array

INF = jnp.inf

#: per-leaf cached-best-split state keys — MUST mirror ops/grow_wave.py
LEAF_KEYS = ("leaf_gain", "leaf_feat", "leaf_thr", "leaf_dl",
             "leaf_lg", "leaf_lh", "leaf_lc", "leaf_rg", "leaf_rh",
             "leaf_rc", "leaf_iscat", "leaf_catmask")


def streaming_downgrade_reasons(spec: GrowerSpec, store) -> List[str]:
    """Why this spec cannot stream (empty list = streamable).

    The engine implements the wave feature scope MINUS the modes whose
    state is not shard-decomposable; the booster prices the downgrade
    with a warning (same contract as the wave→strict downgrade).
    """
    reasons = []
    if store is None:
        reasons.append("no datastore (external_memory off)")
    if spec.bundled:
        reasons.append("EFB bundling (bundle expansion needs the "
                       "assembled bundle columns)")
    if spec.forced_splits:
        reasons.append("forced splits")
    if spec.monotone_intermediate:
        reasons.append("monotone_constraints_method=intermediate")
    if spec.hist_pool_slots > 0:
        reasons.append("bounded histogram pool")
    return reasons


def streaming_spec(spec: GrowerSpec, policy: str) -> GrowerSpec:
    """The engine's wave spec for a resolved grow policy.

    `leafwise` streams as a width-1 wave: a full strict tail with the
    wave heuristics off IS strict best-first order (the equivalence the
    wave tests pin), so one engine covers both policies byte-exactly.
    """
    if policy == "wave":
        return spec
    return spec._replace(wave_width=1,
                         wave_strict_tail=spec.num_leaves,
                         wave_gain_ratio=0.0, wave_overgrow=0.0)


class StreamingWaveGrower:
    """Grower-compatible callable: `(bins_fm, grad, hess, sample_weight,
    feat, allowed) -> DeviceTree`, with `bins_fm=None` — bins stream
    from the datastore instead.  One instance per training run; it owns
    the run's prefetch accounting (`PrefetchRunStats`) and the
    `stream.*` telemetry."""

    def __init__(self, spec: GrowerSpec, store, *, prefetch_depth: int = 2,
                 run_stats: Optional[PrefetchRunStats] = None,
                 payload: str = "bins", budget_mb: float = 0.0):
        reasons = streaming_downgrade_reasons(spec, store)
        if reasons:
            raise ValueError("spec cannot stream: " + "; ".join(reasons))
        self.spec = spec
        self.store = store
        self.payload_name = payload
        self.depth = max(1, int(prefetch_depth))
        self.stats = run_stats if run_stats is not None \
            else PrefetchRunStats()
        self.plan = stream_shard_plan(store)
        self.L = spec.num_leaves
        self.MB = spec.max_bin
        self.LB, self.W = wave_sizes(spec)
        # the Pallas kernels are probe-gated bitwise-equal to their XLA
        # base family, so streaming the base family preserves identity
        # with any resolved impl
        self.packed = spec.hist_impl in ("packed", "pallas_q")
        self.chl = spec.packed_const_hess_level if self.packed else 0
        cegb_on = spec.cegb_tradeoff > 0.0 and \
            (spec.cegb_penalty_split > 0.0 or spec.cegb_coupled
             or spec.cegb_lazy)
        self.track_used = spec.n_ic_groups > 0 or \
            (cegb_on and spec.cegb_lazy)
        self._carry_keys = ("step", "nl", "nodes", "leaf_g", "leaf_h",
                            "leaf_c", "leaf_lb", "leaf_ub", "leaf_out",
                            "leaf_depth") + \
            (("leaf_used",) if self.track_used else ())
        # resolved wave geometry — same gauges the in-memory factory
        # records (this body runs host-side, never under jit)
        REGISTRY.gauge("wave.width").set(self.W)
        REGISTRY.gauge("wave.grow_leaves").set(self.LB)
        REGISTRY.gauge("wave.shards").set(1)
        REGISTRY.gauge("stream.shards").set(store.n_shards)
        # two watermarks (memledger satellite): `peak_staging_bytes` is
        # what `datastore_budget_mb` sizes — at most the current +
        # previous staged shard blocks; `peak_device_bytes` is the
        # HONEST device footprint: staging PLUS the resident O(N) state
        # (payload, leaf_id, grad/hess) and the live histogram carries.
        # The old gauge counted only staging and therefore lied.
        self.peak_device_bytes = 0
        self.peak_staging_bytes = 0
        self._resident_bytes = 0  # recomputed per pass, host arithmetic
        self.budget_mb = float(budget_mb)
        self._tree_idx = -1     # bumped per __call__ (one call = one tree)
        self._build_programs()

    # ------------------------------------------------------------ programs
    def _split_ctx(self, feat: Dict[str, Array]):
        """Per-program split context over TRACED feat — the same shared
        derivations (and the same node-indexed RNG draws) as the
        in-memory growers, rebuilt inside each jitted body."""
        spec = self.spec
        F = feat["nb"].shape[0]
        mono = feat.get("mono")
        if mono is None:
            mono = jnp.zeros((F,), jnp.int32)
        bynode_mask, extra_mask = make_node_samplers(spec, feat, F)
        _, cegb_penalty = make_cegb_penalty(spec, feat, F)
        find = functools.partial(
            find_best_split,
            l1=spec.lambda_l1, l2=spec.lambda_l2,
            min_data_in_leaf=spec.min_data_in_leaf,
            min_sum_hessian=spec.min_sum_hessian_in_leaf,
            min_gain_to_split=spec.min_gain_to_split,
            max_delta_step=spec.max_delta_step,
            cat_smooth=spec.cat_smooth, cat_l2=spec.cat_l2,
            max_cat_threshold=spec.max_cat_threshold,
            max_cat_to_onehot=spec.max_cat_to_onehot,
            min_data_per_group=spec.min_data_per_group,
            path_smooth=spec.path_smooth, has_cat=spec.has_cat)

        def split_of(hist, g, h, c, node_allowed, lb, ub, p_out, nid,
                     penalty=None):
            na = node_allowed & bynode_mask(nid)
            cm = extra_mask(nid)
            s = find(hist_value(hist), g, h, c, feat["nb"],
                     feat["missing"], feat["default"], na, feat["is_cat"],
                     mono=mono, out_lb=lb, out_ub=ub, parent_output=p_out,
                     cand_mask=cm, gain_penalty=penalty)
            return refine_child_sums(s, hist, feat["nb"], feat["missing"])

        return F, mono, split_of, cegb_penalty

    def _clamp_output(self, g, h):
        spec = self.spec
        return leaf_output(g, h, spec.lambda_l1, spec.lambda_l2,
                           spec.max_delta_step)

    def _acc_init(self):
        F = self.store.n_features
        if self.packed:
            return hist_stream_packed_init(F, self.W, self.MB,
                                           const_hess_level=self.chl)
        return hist_stream_init(F, self.W, self.MB)

    def _acc_update(self, acc, bins, pl, lid, slots, qs):
        if self.packed:
            return hist_stream_packed_update(
                acc, bins, pl, lid, slots, self.MB, qs[0], qs[1],
                const_hess_level=self.chl)
        return hist_stream_update(acc, bins, pl, lid, slots, self.MB)

    def _acc_finalize(self, acc, qs):
        F = self.store.n_features
        if self.packed:
            return hist_stream_packed_finalize(
                acc, F, self.W, self.MB, qs[0], qs[1],
                const_hess_level=self.chl)
        return hist_stream_finalize(acc, F, self.W, self.MB)

    def _build_programs(self):
        spec = self.spec
        L, LB, W, MB = self.L, self.LB, self.W, self.MB
        track_used = self.track_used
        carry_keys = self._carry_keys
        clamp_output = self._clamp_output

        @jax.jit
        def prep(grad, hess, sample_weight):
            payload = jnp.stack([grad * sample_weight,
                                 hess * sample_weight,
                                 sample_weight], axis=1)
            # same reduce expressions as the in-memory root sums
            return (payload, payload[:, 0].sum(), payload[:, 1].sum(),
                    payload[:, 2].sum())

        self._prep = prep

        @functools.lru_cache(maxsize=8)
        def accum_prog(rows: int):
            """Fold one shard (root pass / already-partitioned rows)."""
            def run(acc, bins, payload, leaf_id, row0, slots, qs):
                pl = jax.lax.dynamic_slice(payload, (row0, 0), (rows, 3))
                lid = jax.lax.dynamic_slice(leaf_id, (row0,), (rows,))
                return self._acc_update(acc, bins, pl, lid, slots, qs)
            return jax.jit(run)

        self._accum_prog = accum_prog

        @functools.lru_cache(maxsize=8)
        def wave_prog(rows: int):
            """Apply one wave's partitions to a shard's rows, then fold
            the shard into the smaller-children histogram carry."""
            def run(acc, bins, payload, leaf_id, row0, desc, feat, qs):
                pl = jax.lax.dynamic_slice(payload, (row0, 0), (rows, 3))
                lid = jax.lax.dynamic_slice(leaf_id, (row0,), (rows,))
                lid = _apply_partitions(lid, bins, desc, feat)
                acc = self._acc_update(acc, bins, pl, lid,
                                       desc["small"], qs)
                leaf_id = jax.lax.dynamic_update_slice(leaf_id, lid,
                                                       (row0,))
                return acc, leaf_id
            return jax.jit(run)

        self._wave_prog = wave_prog

        @functools.lru_cache(maxsize=8)
        def part_prog(rows: int):
            """Partition-only shard pass (tree-full wave: the picks
            were committed but no new histograms are needed)."""
            def run(bins, leaf_id, row0, desc, feat):
                lid = jax.lax.dynamic_slice(leaf_id, (row0,), (rows,))
                lid = _apply_partitions(lid, bins, desc, feat)
                return jax.lax.dynamic_update_slice(leaf_id, lid, (row0,))
            return jax.jit(run)

        self._part_prog = part_prog

        def _apply_partitions(lid, bins, desc, feat):
            # the wave's picks are row-disjoint (each targets a distinct
            # pre-wave ready leaf), so replaying the W descriptors in
            # pick order from the wave-start leaf_id reproduces the
            # in-memory loop's assignment exactly; pad descriptors
            # (best == LB) match no rows and drop out of the where
            for w in range(W):
                gl = split_go_left(spec, feat, bins, None,
                                   desc["f"][w], desc["t"][w],
                                   desc["dl"][w], desc["cat"][w],
                                   desc["mask"][w])
                in_leaf = lid == desc["best"][w]
                lid = jnp.where(in_leaf & ~gl, desc["new"][w], lid)
            return lid

        @jax.jit
        def root_find(hist0, root_g, root_h, root_c, feat, allowed):
            F, mono, split_of, cegb_penalty = self._split_ctx(feat)
            root_out = clamp_output(root_g, root_h)
            if spec.n_ic_groups:
                allowed = allowed & jnp.any(feat["ic_groups"], axis=0)
            root_pen = cegb_penalty(root_c, jnp.zeros((F,), bool))
            s0 = split_of(hist0, root_g, root_h, root_c, allowed,
                          jnp.float32(-INF), jnp.float32(INF), root_out,
                          0, penalty=root_pen)

            hist = jnp.zeros((LB,) + hist0.shape, dtype=jnp.float32)\
                .at[0].set(hist0)
            leaf_best = [jnp.zeros((LB,) + a.shape, dtype=a.dtype)
                         .at[0].set(a) for a in _split_to_arrays(s0)]
            leaf_best[0] = jnp.full((LB,), NEG_INF, dtype=jnp.float32)\
                .at[0].set(s0.gain)

            nodes = dict(
                split_leaf=jnp.zeros((LB - 1,), jnp.int32),
                split_feature=jnp.zeros((LB - 1,), jnp.int32),
                threshold_bin=jnp.zeros((LB - 1,), jnp.int32),
                default_left=jnp.zeros((LB - 1,), bool),
                split_is_cat=jnp.zeros((LB - 1,), bool),
                split_cat_mask=jnp.zeros((LB - 1, MB), bool),
                split_gain=jnp.zeros((LB - 1,), jnp.float32),
                internal_g=jnp.zeros((LB - 1,), jnp.float32),
                internal_h=jnp.zeros((LB - 1,), jnp.float32),
                internal_cnt=jnp.zeros((LB - 1,), jnp.float32),
            )
            state = dict(
                step=jnp.int32(0), nl=jnp.int32(1), hist=hist,
                leaf_gain=leaf_best[0], leaf_feat=leaf_best[1],
                leaf_thr=leaf_best[2], leaf_dl=leaf_best[3],
                leaf_lg=leaf_best[4], leaf_lh=leaf_best[5],
                leaf_lc=leaf_best[6], leaf_rg=leaf_best[7],
                leaf_rh=leaf_best[8], leaf_rc=leaf_best[9],
                leaf_iscat=leaf_best[10], leaf_catmask=leaf_best[11],
                leaf_g=jnp.zeros((LB,), jnp.float32).at[0].set(root_g),
                leaf_h=jnp.zeros((LB,), jnp.float32).at[0].set(root_h),
                leaf_c=jnp.zeros((LB,), jnp.float32).at[0].set(root_c),
                leaf_lb=jnp.full((LB,), -INF, jnp.float32),
                leaf_ub=jnp.full((LB,), INF, jnp.float32),
                leaf_out=jnp.zeros((LB,), jnp.float32).at[0]
                .set(root_out),
                leaf_depth=jnp.zeros((LB,), jnp.int32),
                nodes=nodes,
            )
            if track_used:
                state["leaf_used"] = jnp.zeros((LB, F), bool)
            return state, allowed

        self._root_find = root_find

        @jax.jit
        def pick(st, feat):
            """The wave's pick loop — the SAME while_loop as the
            in-memory body minus the row partition (deferred to the
            per-shard programs) and minus the forced-split ride
            (streaming downgrades on forced splits)."""
            F = feat["nb"].shape[0]
            mono = feat.get("mono")
            if mono is None:
                mono = jnp.zeros((F,), jnp.int32)
            istate = {k: st[k] for k in carry_keys + LEAF_KEYS}
            istate["ready"] = jnp.arange(LB) < st["nl"]
            istate["w"] = jnp.int32(0)
            if spec.wave_strict_tail > 0:
                tail = min(spec.wave_strict_tail, LB - 1)
                remaining = LB - st["nl"]
                istate["wcap"] = jnp.where(
                    remaining <= tail, jnp.int32(1),
                    jnp.minimum(jnp.int32(W),
                                (remaining - tail).astype(jnp.int32)))
            else:
                istate["wcap"] = jnp.int32(W)
            istate["p_small"] = jnp.full((W,), LB, jnp.int32)
            istate["p_left"] = jnp.full((W,), LB, jnp.int32)
            istate["p_new"] = jnp.full((W,), LB, jnp.int32)
            istate["p_step"] = jnp.zeros((W,), jnp.int32)
            istate["g_floor"] = jnp.float32(0.0)
            fullness = st["nl"].astype(jnp.float32) / LB

            def icond(s):
                rg = jnp.where(s["ready"], s["leaf_gain"], NEG_INF)
                go = jnp.max(rg) > jnp.maximum(s["g_floor"], 0.0)
                return (s["w"] < s["wcap"]) & (s["step"] < LB - 1) & go

            def ibody(s):
                step = s["step"]
                new = step + 1
                rg = jnp.where(s["ready"], s["leaf_gain"], NEG_INF)
                best = jnp.argmax(rg).astype(jnp.int32)
                chosen = tuple(s[k][best] for k in LEAF_KEYS)
                (gain_s, f, t, dl, lg, lh, lc, rg_, rh, rc, node_cat,
                 node_mask) = chosen

                nodes = s["nodes"]
                nodes = dict(
                    split_leaf=nodes["split_leaf"].at[step].set(best),
                    split_feature=nodes["split_feature"].at[step].set(f),
                    threshold_bin=nodes["threshold_bin"].at[step].set(t),
                    default_left=nodes["default_left"].at[step].set(dl),
                    split_is_cat=nodes["split_is_cat"].at[step]
                    .set(node_cat),
                    split_cat_mask=nodes["split_cat_mask"].at[step]
                    .set(node_mask),
                    split_gain=nodes["split_gain"].at[step].set(gain_s),
                    internal_g=nodes["internal_g"].at[step]
                    .set(s["leaf_g"][best]),
                    internal_h=nodes["internal_h"].at[step]
                    .set(s["leaf_h"][best]),
                    internal_cnt=nodes["internal_cnt"].at[step]
                    .set(s["leaf_c"][best]),
                )

                def put2(arr, a, b):
                    return arr.at[best].set(a).at[new].set(b)

                lb, ub = s["leaf_lb"][best], s["leaf_ub"][best]
                parent_out = s["leaf_out"][best]
                mc_f = jnp.where(node_cat, 0, mono[f])
                l_sm = smooth_output(clamp_output(lg, lh), lc,
                                     parent_out, spec.path_smooth)
                r_sm = smooth_output(clamp_output(rg_, rh), rc,
                                     parent_out, spec.path_smooth)
                (l_fin, r_fin, l_lb, l_ub, r_lb, r_ub) = \
                    child_bounds_basic(mc_f, l_sm, r_sm, lb, ub)

                left_smaller = lc <= rc
                small = jnp.where(left_smaller, best, new)
                depth = s["leaf_depth"][best] + 1
                floor_w0 = jnp.float32(spec.wave_gain_ratio) * gain_s \
                    * fullness

                out = dict(s)
                if track_used:
                    child_used = s["leaf_used"][best].at[f].set(True)
                    out["leaf_used"] = s["leaf_used"].at[best]\
                        .set(child_used).at[new].set(child_used)
                out.update(
                    step=step + 1, nl=new + 1,
                    nodes=nodes, w=s["w"] + 1,
                    g_floor=jnp.where(s["w"] == 0, floor_w0,
                                      s["g_floor"]),
                    ready=s["ready"].at[best].set(False)
                    .at[new].set(False),
                    p_small=s["p_small"].at[s["w"]].set(small),
                    p_left=s["p_left"].at[s["w"]].set(best),
                    p_new=s["p_new"].at[s["w"]].set(new),
                    p_step=s["p_step"].at[s["w"]].set(step),
                    leaf_gain=put2(s["leaf_gain"], NEG_INF, NEG_INF),
                    leaf_g=put2(s["leaf_g"], lg, rg_),
                    leaf_h=put2(s["leaf_h"], lh, rh),
                    leaf_c=put2(s["leaf_c"], lc, rc),
                    leaf_lb=put2(s["leaf_lb"], l_lb, r_lb),
                    leaf_ub=put2(s["leaf_ub"], l_ub, r_ub),
                    leaf_out=put2(s["leaf_out"], l_fin, r_fin),
                    leaf_depth=put2(s["leaf_depth"], depth, depth),
                )
                return out

            s1 = jax.lax.while_loop(icond, ibody, istate)
            nd = s1["nodes"]
            ps = s1["p_step"]
            # the wave's partition descriptors, replayed per shard; pad
            # entries gather step-0's record but best == LB routes no rows
            desc = dict(best=s1["p_left"], new=s1["p_new"],
                        small=s1["p_small"],
                        f=nd["split_feature"][ps],
                        t=nd["threshold_bin"][ps],
                        dl=nd["default_left"][ps],
                        cat=nd["split_is_cat"][ps],
                        mask=nd["split_cat_mask"][ps])
            return s1, desc

        self._pick = pick

        @jax.jit
        def find_children(hist_st, s1, small_h, feat, allowed):
            """Sibling subtraction + vmapped child search — the SAME
            expressions as the in-memory `hist_and_find` on the
            streamed smaller-children histograms."""
            F, mono, split_of, cegb_penalty = self._split_ctx(feat)
            parents = hist_st[jnp.clip(s1["p_left"], 0, LB - 1)]
            large_h = hist_sub(parents, small_h)
            p_large = jnp.where(s1["p_small"] == s1["p_left"],
                                s1["p_new"], s1["p_left"])
            hist = hist_st.at[s1["p_small"]].set(small_h, mode="drop")
            hist = hist.at[p_large].set(large_h, mode="drop")

            child_slots = jnp.concatenate([s1["p_left"], s1["p_new"]])
            node_ids = jnp.concatenate([2 * s1["p_step"] + 1,
                                        2 * s1["p_step"] + 2])

            def eval_child(slot, nid):
                sl = jnp.clip(slot, 0, LB - 1)
                g, h, c = s1["leaf_g"][sl], s1["leaf_h"][sl], \
                    s1["leaf_c"][sl]
                deep_ok = (spec.max_depth <= 0) | \
                    (s1["leaf_depth"][sl] < spec.max_depth)
                lu = s1["leaf_used"][sl] if track_used \
                    else jnp.zeros((F,), bool)
                a = allowed & deep_ok
                if spec.n_ic_groups:
                    a = a & ic_allowed_from_used(feat, lu)
                sr = split_of(hist[sl], g, h, c, a,
                              s1["leaf_lb"][sl], s1["leaf_ub"][sl],
                              s1["leaf_out"][sl], nid,
                              penalty=cegb_penalty(c, lu))
                return _split_to_arrays(sr)

            res = jax.vmap(eval_child)(child_slots, node_ids)
            leaf_upd = tuple(
                s1[k].at[child_slots].set(r, mode="drop")
                for k, r in zip(LEAF_KEYS, res))
            return hist, leaf_upd

        self._find_children = find_children

        @jax.jit
        def finalize(st):
            if LB > L:
                nodes_f, leaves_f, leaf_id_f, n_splits = prune_wave_tail(
                    st, LB=LB, L=L, n_forced=0,
                    clamp_output=clamp_output)
                nl_f = n_splits + 1
                slot = jnp.arange(L)
                active = slot < nl_f
                values = jnp.where(active & (nl_f > 1),
                                   leaves_f["out"], 0.0)
                return DeviceTree(
                    n_splits=n_splits,
                    leaf_value=values,
                    leaf_g=leaves_f["g"], leaf_h=leaves_f["h"],
                    leaf_cnt=leaves_f["c"],
                    leaf_id=leaf_id_f,
                    **nodes_f,
                )
            n_splits = st["step"]
            slot = jnp.arange(L)
            active = slot < st["nl"]
            values = jnp.where(active & (st["nl"] > 1),
                               st["leaf_out"], 0.0)
            return DeviceTree(
                n_splits=n_splits,
                split_leaf=st["nodes"]["split_leaf"],
                split_feature=st["nodes"]["split_feature"],
                threshold_bin=st["nodes"]["threshold_bin"],
                default_left=st["nodes"]["default_left"],
                split_is_cat=st["nodes"]["split_is_cat"],
                split_cat_mask=st["nodes"]["split_cat_mask"],
                split_gain=st["nodes"]["split_gain"],
                internal_g=st["nodes"]["internal_g"],
                internal_h=st["nodes"]["internal_h"],
                internal_cnt=st["nodes"]["internal_cnt"],
                leaf_value=values,
                leaf_g=st["leaf_g"], leaf_h=st["leaf_h"],
                leaf_cnt=st["leaf_c"],
                leaf_id=st["leaf_id"],
            )

        self._finalize = finalize

    # ------------------------------------------------------------ streaming
    def _stream(self, prof=None):
        """Yield (rows, row0, device_block) over the pinned shard plan
        with double-buffered staging accounting: at most the current +
        previous blocks are device-resident at once.

        `prof` (the per-pass profile dict, see `_pass`) accumulates the
        two producer-side stall stages: `prefetch_wait_s` is the host
        time blocked in the prefetcher's `next()` (disk + decode behind
        the bounded queue), `h2d_s` the `jnp.asarray` staging call.
        Generator timing is exact by construction: the interval between
        our `yield` and the consumer's next `next()` — the device-fold
        dispatch — never lands in either bucket.
        """
        self.stats.start_pass()
        REGISTRY.counter("stream.shard_passes").inc()

        def on_hit():
            self.stats.hit()
            REGISTRY.counter("datastore.prefetch.hit").inc()

        def on_stall():
            self.stats.stall()
            REGISTRY.counter("datastore.prefetch.stall").inc()
            REGISTRY.counter("stream.stalls").inc()

        pf = ShardPrefetcher(self.store, payload=self.payload_name,
                             depth=self.depth, plan=self.plan,
                             on_hit=on_hit, on_stall=on_stall)
        shards_read = REGISTRY.counter("stream.shards_read")
        prev_bytes = 0
        it = iter(pf)
        try:
            while True:
                t0 = time.perf_counter()
                try:
                    _k, row0, block = next(it)
                except StopIteration:
                    break
                t1 = time.perf_counter()
                dev = jnp.asarray(block)
                t2 = time.perf_counter()
                if prof is not None:
                    prof["prefetch_wait_s"] += t1 - t0
                    prof["h2d_s"] += t2 - t1
                staged = block.nbytes + prev_bytes
                if staged > self.peak_staging_bytes:
                    self.peak_staging_bytes = staged
                total = staged + self._resident_bytes
                if total > self.peak_device_bytes:
                    self.peak_device_bytes = total
                prev_bytes = block.nbytes
                # weakref-tracked: the free is observed when the
                # double-buffer rotates, no release bookkeeping here
                telemetry.MEMLEDGER.register("stream.staging", dev)
                shards_read.inc()
                yield block.shape[1], row0, dev
        finally:
            pf.close()
            self.stats.absorb(pf)
            REGISTRY.gauge("stream.peak_staging_mb").set(
                round(self.peak_staging_bytes / 2**20, 3))
            REGISTRY.gauge("stream.peak_device_mb").set(
                round(self.peak_device_bytes / 2**20, 3))
            # the staging double-buffer is the part the budget sizes —
            # audited per pass against the declared contract
            telemetry.MEMLEDGER.audit(
                "datastore_budget_mb", self.budget_mb * 2**20,
                self.peak_staging_bytes, site="stream.pass",
                peak_staging_mb=round(self.peak_staging_bytes / 2**20, 3))
            # run-max (not per-pass) host residency: the accounting
            # satellite — short-lived per-pass prefetchers must not
            # reset the published steady state
            REGISTRY.gauge("datastore.peak_resident_mb").set(
                round(self.stats.peak_resident_bytes / 2**20, 3))

    # ------------------------------------------------------------ residency
    @staticmethod
    def _tree_nbytes(tree_) -> int:
        """Host-side byte total of a pytree of device arrays (metadata
        only — never a device sync)."""
        if tree_ is None:
            return 0
        return sum(int(getattr(a, "nbytes", 0))
                   for a in jax.tree_util.tree_leaves(tree_))

    # ------------------------------------------------------------ profiler
    @staticmethod
    def _pass_profile():
        return {"prefetch_wait_s": 0.0, "h2d_s": 0.0,
                "device_fold_s": 0.0, "host_harvest_s": 0.0}

    @staticmethod
    def _pass_close(sp, prof, t_start, **ids) -> None:
        """Stamp one pass's stall attribution onto its `stream.pass`
        span and the always-on `stream.pass.*` histograms.

        The four stages are DISJOINT host-side sub-intervals of the pass
        (prefetch-wait and H2D inside `_stream`, device-fold around each
        per-shard program dispatch, host-harvest around the accumulator
        finalize), so their sum is ≤ the pass wall time by construction
        — the invariant the CI spool smoke asserts.  Timing wraps the
        ASYNC dispatch calls, never a device sync (graft-lint R005 /
        zero-added-syncs): on a real accelerator device-fold is dispatch
        time and the tail of device work drains into whichever stage
        blocks next, which is exactly the host's-eye stall view the
        timeline renders.
        """
        wall = time.perf_counter() - t_start
        sp.set(wall_s=round(wall, 6),
               **{k: round(v, 6) for k, v in prof.items()}, **ids)
        REGISTRY.histogram("stream.pass.wall").observe(wall)
        for k, v in prof.items():
            REGISTRY.histogram("stream.pass." + k[:-2]).observe(v)

    # ------------------------------------------------------------ __call__
    def __call__(self, bins_fm, grad, hess, sample_weight, feat, allowed
                 ) -> DeviceTree:
        del bins_fm  # streamed — never materialized
        spec = self.spec
        LB, W = self.LB, self.W
        qs = feat.get("qscales")
        payload, root_g, root_h, root_c = self._prep(
            grad, hess, sample_weight)
        N = payload.shape[0]
        leaf_id = jnp.zeros((N,), jnp.int32)
        # resident O(N) state the old gauge ignored: the [N, 3] payload,
        # the partition vector, and the caller's grad/hess (alive for
        # the whole tree).  `buf=stream` keeps these handles disjoint
        # from the booster's own `train.scores` assignment.
        base_resident = self._tree_nbytes(
            (payload, leaf_id, grad, hess, sample_weight))
        telemetry.MEMLEDGER.register("train.scores", payload, buf="stream")
        telemetry.MEMLEDGER.register("train.scores", leaf_id, buf="stream")
        self._tree_idx += 1
        tree = self._tree_idx
        wave_idx = 0
        shards = len(self.plan)

        # ---- root pass: one full-datastore sweep at wave call shape ----
        with telemetry.span("stream.pass", phase="root") as sp:
            prof, t_pass = self._pass_profile(), time.perf_counter()
            root_slots = jnp.full((W,), LB, jnp.int32).at[0].set(0)
            acc = self._acc_init()
            self._resident_bytes = base_resident + self._tree_nbytes(acc)
            with telemetry.MEMLEDGER.oom_guard("stream.fold"):
                for rows, row0, dev in self._stream(prof):
                    t_f = time.perf_counter()
                    acc = self._accum_prog(rows)(
                        acc, dev, payload, leaf_id, row0, root_slots, qs)
                    prof["device_fold_s"] += time.perf_counter() - t_f
            t_h = time.perf_counter()
            hist0 = self._acc_finalize(acc, qs)[0]
            prof["host_harvest_s"] += time.perf_counter() - t_h
            self._pass_close(sp, prof, t_pass, tree=tree, wave=0,
                             shards=shards)
        state, allowed_eff = self._root_find(hist0, root_g, root_h,
                                             root_c, feat, allowed)
        if state.get("hist") is not None:
            telemetry.MEMLEDGER.register("train.hist_carry", state["hist"])

        # ---- wave loop (host-driven; cond mirrors the in-memory one) ----
        while (int(state["step"]) < LB - 1
               and float(jnp.max(state["leaf_gain"])) > 0.0):
            wave_idx += 1
            s1, desc = self._pick(
                {k: state[k] for k in self._carry_keys + LEAF_KEYS},
                feat)
            if int(s1["step"]) >= LB - 1:
                # capacity reached mid-wave: the committed picks still
                # partition rows (leaf_id feeds the score update), but
                # no new histograms are needed — partition-only pass
                with telemetry.span("stream.pass",
                                    phase="partition") as sp:
                    prof, t_pass = self._pass_profile(), \
                        time.perf_counter()
                    self._resident_bytes = base_resident + \
                        self._tree_nbytes(s1.get("hist"))
                    with telemetry.MEMLEDGER.oom_guard("stream.fold"):
                        for rows, row0, dev in self._stream(prof):
                            t_f = time.perf_counter()
                            leaf_id = self._part_prog(rows)(
                                dev, leaf_id, row0, desc, feat)
                            prof["device_fold_s"] += \
                                time.perf_counter() - t_f
                    self._pass_close(sp, prof, t_pass, tree=tree,
                                     wave=wave_idx, shards=shards)
                state = {k: s1[k] for k in
                         self._carry_keys + LEAF_KEYS}
                break
            with telemetry.span("stream.pass", phase="wave") as sp:
                prof, t_pass = self._pass_profile(), time.perf_counter()
                acc = self._acc_init()
                self._resident_bytes = base_resident + \
                    self._tree_nbytes(acc) + \
                    self._tree_nbytes(state.get("hist"))
                with telemetry.MEMLEDGER.oom_guard("stream.fold"):
                    for rows, row0, dev in self._stream(prof):
                        t_f = time.perf_counter()
                        acc, leaf_id = self._wave_prog(rows)(
                            acc, dev, payload, leaf_id, row0, desc,
                            feat, qs)
                        prof["device_fold_s"] += \
                            time.perf_counter() - t_f
                t_h = time.perf_counter()
                small_h = self._acc_finalize(acc, qs)
                prof["host_harvest_s"] += time.perf_counter() - t_h
                self._pass_close(sp, prof, t_pass, tree=tree,
                                 wave=wave_idx, shards=shards)
            hist, leaf_upd = self._find_children(
                state["hist"], s1, small_h, feat, allowed_eff)
            state = {k: s1[k] for k in self._carry_keys}
            state["hist"] = hist
            telemetry.MEMLEDGER.register("train.hist_carry", hist)
            for k, v in zip(LEAF_KEYS, leaf_upd):
                state[k] = v

        state = dict(state)
        state.pop("hist", None)
        state["leaf_id"] = leaf_id
        return self._finalize(state)
