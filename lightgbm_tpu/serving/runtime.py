"""Device-resident serving runtime: bucketed jit programs, exact sums.

The booster exports once (`Booster.export_predict_arrays`) into stacked
traversal arrays; every request is padded to a power-of-two row bucket,
so each module-level jitted program compiles at most once per bucket —
total compiles are bounded by the bucket count (log2(cap)+1) per
program no matter how ragged the request-size distribution is.  The
bound is asserted through the PR 3 `jax.monitoring` recompile listener
in tests/test_serving.py.

Above the exact ladder sits an OPT-IN declared-error tier
(`serve_precision=bounded`, default "exact"): leaf values quantize to
per-tile-scaled int8/int16 codes (`compiler.quantize.pack_bounded`) and
accumulate in int32, with only the final per-tile scale combine in f32
— routing stays the exact `_leaf_slots` walk, so the ONLY deviation
from the exact rungs is the leaf-value representation, and it is
covered by a worst-case bound computed at pack time and PUBLISHED per
model (registry status / healthz / fleet snapshot).  The refresh-time
probe measures the real max-abs-error against the exact-f64 reference
and hard-disables the rung (cause="bound",
`serve.bounded_disabled{cause=}`) whenever measurement exceeds the
published bound — the same probe-gated discipline as the rungs below.
The full exact ladder stays live beneath it for fallback, and the
exact rungs' bytes are untouched (asserted in
tests/test_bounded_serving.py).

Fallback ladder (every rung byte-identical to `booster.predict`):

  b. bounded     — opt-in, see above: exact routing + int32-accumulated
     quantized leaf values, f32 scores inside the published max-abs
     error bound (NOT byte-identical — the one deliberate exception on
     this ladder).  Uses the tiled Pallas traversal when the compiled
     planes are live, the stacked XLA scan otherwise; both share
     `accumulate_slots_bounded`, so the bytes are identical either way.
  0. compiled    — `compiler/`: the export is compiled into quantized
     VMEM-sized tree tiles and traversed by the fused Pallas kernel
     (`compiler.kernel.compiled_predict`); the tile slots gather back
     to boosting order and run through the same software-f64
     accumulation as the device-sum rung.  Gated by its own
     refresh-time parity probe (`serve.compiled_disabled{cause=}` on
     any refusal) and by `serve_compiled` ("auto" enables on TPU only
     — CPU backends keep the cheaper XLA rungs unless forced).
  1. device-sum  — `ops.predict.predict_raw_ensemble_exact`: traversal
     AND f64 leaf accumulation on device (software binary64 over u32
     bit planes), `convert_output` folded into the program.  D2H is
     N*K scores (8 B raw / 4 B converted each), not T*N slots.  Gated
     by an export-time parity probe: the device sum must bit-match the
     host f64 reference on the probe batch or the model degrades one
     rung and `serve.device_sum_disabled` counts it.
  2. slot path   — `ops.predict.predict_leaf_ensemble` returns [T, N]
     i32 leaf slots; leaf values are gathered on host from the
     export's f64 table and accumulated tree-by-tree in boosting
     order, then passed through the identical eager `convert_output`.
  3. host walk   — tree.py f64 walk (device errors, linear trees, X
     narrower than the stacked arrays).

Rows are independent under the per-row `while_loop` traversal, so a
padded batch's real-row results are bitwise equal to the unpadded
batch's.

f32 routing caveat (same as `booster._predict_raw_device`): features
and thresholds are cast to f32 on device, so a row lying within f32
epsilon of a split threshold can route differently from the f64 host
walk.  Thresholds are bin-edge midpoints, so real data essentially
never sits there; the host fallback walk remains the exact-f64
reference path.  Both device rungs share `_leaf_slots`, so they route
identically — the probe therefore isolates ACCUMULATION parity, which
is exactly the property the device-sum rung adds.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import telemetry
from ..analysis import make_lock
from ..compiler import PlanNotCompilable, build_plan
from ..compiler.kernel import ROW_BLOCK, compiled_predict, \
    compiled_predict_bounded
from ..compiler.quantize import pack_bounded
from ..ops.predict import predict_leaf_ensemble, \
    predict_raw_ensemble_bounded, predict_raw_ensemble_exact
from ..resilience import FAULTS, OPEN, CircuitBreaker, Supervisor

#: padding cap (and the micro-batcher's default flush threshold): with
#: power-of-two buckets this caps the compile count at log2(4096)+1 = 13
DEFAULT_MAX_BATCH_ROWS = 4096

# ONE process-wide jitted program each: their shape-keyed compile
# caches ARE the bucket bound.  A per-runtime `jax.jit` would re-own
# the cache per model load and re-trip graft-lint R002's
# factory-per-call trap.  `convert` is a bound method of the booster's
# objective — stable hash/eq per booster instance, so it keys the
# cache without recompiling per call.
_LEAF_JIT = jax.jit(predict_leaf_ensemble)
_EXACT_JIT = jax.jit(predict_raw_ensemble_exact,
                     static_argnames=("n_class", "convert"))
_BOUNDED_JIT = jax.jit(predict_raw_ensemble_bounded,
                       static_argnames=("n_class", "convert"))


class _ServeState:
    """Everything `predict` reads, published as ONE reference.

    `refresh()` / `demote()` build a complete bundle off to the side —
    export planes, compiled tile planes, rung gates, probe verdicts —
    and assign it to `ServingRuntime._state` in a single store.  A
    request that snapshots the state mid-refresh therefore computes
    with the whole old model or the whole new one, never the old plan's
    tiles over the new export's leaf values: the background
    auto-refresh (registry._background_refresh) runs seconds of device
    probes concurrently with live predict callers."""

    __slots__ = ("export", "device_sum_ok", "compiled_ok", "plan",
                 "plan_planes", "plan_meta", "plan_gidx", "probe_failed",
                 "demoted", "bounded_ok", "bounded_planes",
                 "bounded_bound", "bounded_measured")

    def __init__(self, export: Dict):
        self.export = export
        self.device_sum_ok = False
        self.compiled_ok = False
        self.plan = None
        self.plan_planes = None
        self.plan_meta = None
        self.plan_gidx = None
        self.probe_failed = False
        self.demoted = False
        # bounded tier: (qval, tile_of_tree, scales) device planes, the
        # published worst-case bound, the probe-measured max-abs error
        self.bounded_ok = False
        self.bounded_planes = None
        self.bounded_bound = None
        self.bounded_measured = None

    def clone(self) -> "_ServeState":
        """Field-for-field copy — the single-rung republish sites
        (`_publish_rung`, `_drop_compiled`, `_drop_bounded`) start from
        a full copy so a new rung's fields can never be silently
        dropped by a manual copy list going stale."""
        new = _ServeState(self.export)
        for f in self.__slots__:
            setattr(new, f, getattr(self, f))
        return new


def _compile_refusal(e: BaseException) -> bool:
    """Whether `e` is the compiler refusing a program — Pallas has no
    lowering rule (NotImplementedError / LoweringException) or Mosaic
    rejects the kernel (MosaicError) — rather than a device failure a
    retry could clear.  A refusal is permanent for this model on this
    installation, so it is published as `cause="compile"` with the
    compiler's message instead of opening a breaker that re-probes."""
    names = {c.__name__ for c in type(e).__mro__}
    return bool(names & {"MosaicError", "LoweringException",
                         "NotImplementedError"})


def bucket_rows(n: int, max_rows: int = DEFAULT_MAX_BATCH_ROWS) -> int:
    """Smallest power of two >= n, clamped to [1, max_rows].

    Requests larger than `max_rows` are chunked by the caller, so every
    device shape the runtime ever presents is one of the
    log2(max_rows)+1 bucket sizes.
    """
    if n <= 1:
        return 1
    return min(1 << int(n - 1).bit_length(), max_rows)


class ServingRuntime:
    """Serves one exported model through bucket-padded device programs.

    Thread-safe: `predict` snapshots the published `_ServeState` once
    per call, and `refresh`/`demote` build a complete replacement
    bundle and publish it in a single assignment — concurrent requests
    either see the whole old model (export AND compiled plan) or the
    whole new one, never a mix.

    `device_sum` selects the device-sum rung: "auto" (default) enables
    the exact device-sum program only after the export-time parity
    probe bit-matches, "force" skips the probe (tests/benches of the
    machinery), "off" pins the slot path.  `compiled` gates the tiled
    Pallas rung above it the same way, with one extra wrinkle: "auto"
    additionally requires a TPU backend (on CPU the kernel would run
    interpreted — strictly slower than the XLA device-sum program — so
    auto quietly keeps the existing ladder; "on"/"force" override for
    tests and benches).  `tile_vmem_kb` is the compiler's per-tile
    plane budget.
    """

    def __init__(self, booster, *,
                 max_batch_rows: int = DEFAULT_MAX_BATCH_ROWS,
                 start_iteration: int = 0,
                 num_iteration: Optional[int] = None,
                 name: str = "default",
                 device_sum: str = "auto",
                 compiled: str = "auto",
                 tile_vmem_kb: float = 512.0,
                 precision: str = "exact",
                 quant_bits: int = 8,
                 device=None,
                 dispatch_timeout_ms: float = 0.0,
                 breaker_backoff_s: float = 30.0,
                 breaker_backoff_max_s: float = 600.0):
        self._booster = booster
        self.name = name
        self.max_batch_rows = max(int(max_batch_rows), 1)
        self._start = start_iteration
        self._num = num_iteration
        self._device_sum_mode = str(device_sum).lower()
        self._compiled_mode = str(compiled).lower()
        self._tile_vmem_kb = float(tile_vmem_kb)
        self._precision = str(precision).lower()
        if self._precision not in ("exact", "bounded"):
            raise ValueError(
                f"serve_precision must be 'exact' or 'bounded', "
                f"got {precision!r}")
        self._quant_bits = int(quant_bits)
        self._state = _ServeState({})
        # resilience plane: one watchdog lane + one circuit breaker per
        # device rung.  `dispatch_timeout_ms <= 0` (the default) makes
        # the supervisors transparent direct calls; breakers replace the
        # old disable-until-refresh behavior for transient failures —
        # open on error, half-open background re-probe after backoff,
        # permanent only on a CONTENT mismatch.
        self._supervisors = {
            "bounded": Supervisor("serve.dispatch.bounded",
                                  dispatch_timeout_ms),
            "compiled": Supervisor("compiled.traverse",
                                   dispatch_timeout_ms),
            "device_sum": Supervisor("serve.dispatch.device_sum",
                                     dispatch_timeout_ms),
            "slot_path": Supervisor("serve.dispatch.slot_path",
                                    dispatch_timeout_ms),
        }
        self._breakers = {
            rung: CircuitBreaker(f"{name}.{rung}",
                                 backoff_s=breaker_backoff_s,
                                 backoff_max_s=breaker_backoff_max_s)
            for rung in ("bounded", "compiled", "device_sum",
                         "slot_path")}
        #: rung -> {"cause", "detail"} of the last time it was switched
        #: off; an entry goes when the rung next comes up.  Read by
        #: `rung_status()` (`/healthz`), so WHY a rung is off is visible
        #: without the event stream.
        self._rung_lock = make_lock("serving.runtime._rung_lock")
        self._rung_off: Dict[str, Dict[str, str]] = {}  # guarded-by: _rung_lock
        self._reprobe_lock = make_lock("serving.runtime._reprobe_lock")
        self._reprobe_threads: Dict[str, threading.Thread] = {}  # guarded-by: _reprobe_lock
        #: pin every device array (export planes + staged inputs) to one
        #: device — the sharded serving plane builds one pinned runtime
        #: per mesh device (serving/sharded.py).  None = default device,
        #: the pre-existing behavior.
        self.device = device
        self._refresh_lock = make_lock("serving.runtime._refresh_lock")
        self._staging_lock = make_lock("serving.runtime._staging_lock")
        self._staging: Dict = {}  # guarded-by: _staging_lock
        #: memory-ledger handles THIS runtime registered (plane handles
        #: under _refresh_lock, staging handles under _staging_lock);
        #: release is by handle, never by owner prefix — two runtimes
        #: for the same model name (a load() swap overlap) must not
        #: wipe each other's attribution
        self._ledger_handles: List = []  # guarded-by: _refresh_lock
        self._ledger_staging: List = []  # guarded-by: _staging_lock
        self.refresh()

    # ------------------------------------------------------------ export
    def refresh(self) -> None:
        """(Re-)export the booster — picks up continued training,
        `rollback_one_iter`, and `refit`-style in-place mutations (the
        export cache is `_model_version`-keyed, so an unchanged model
        costs one dict lookup).  Re-runs the device-sum and compiled
        parity probes against the new export and re-promotes a demoted
        runtime."""
        with self._refresh_lock:
            # a refresh is a new export whose probes re-derive every
            # rung verdict — including the PERMANENT ones (that is the
            # documented way out of a content mismatch)
            for br in self._breakers.values():
                br.reset()
            ex = self._pin_export(
                self._booster.export_predict_arrays(self._start,
                                                    self._num))
            # two-phase publish, each phase a complete self-consistent
            # bundle (a request snapshots exactly one of them — never
            # the OLD plan's tiles over the NEW export's leaf values):
            #  1. the new export with no device rungs — fresh bytes are
            #     visible immediately via the exact slot path while the
            #     probes below run seconds of device work;
            #  2. the same export with its probed rungs attached.
            self._state = _ServeState(ex)
            st = _ServeState(ex)
            st.device_sum_ok = self._device_sum_enable(ex, st)
            st.compiled_ok = self._compiled_enable(ex, st)
            st.bounded_ok = self._bounded_enable(ex, st)
            self._state = st
            self._ledger_register(st)

    def _pin_export(self, ex: Dict) -> Dict:
        """Copy the export's device arrays onto this runtime's pinned
        device (replication H2D/D2D traffic, one `mesh.collective.
        replicate` span per refresh).  The booster's shared export cache
        is left untouched — same copy-not-mutate discipline as
        `demote()` — so co-resident replicas and unpinned runtimes keep
        their own placement."""
        if self.device is None:
            return ex
        from ..mesh.placement import collective_span
        with collective_span("replicate", model=self.name,
                             device=int(self.device.id)):
            ex = dict(ex)
            st = ex.get("stacked")
            if st:
                ex["stacked"] = {
                    k: jax.device_put(v, self.device)
                    if isinstance(v, jax.Array) else v
                    for k, v in st.items()}
            for k in ("value_hi", "value_lo"):
                if ex.get(k) is not None:
                    ex[k] = jax.device_put(ex[k], self.device)
        return ex

    # Read-only views of the published state — tests and the ops
    # surface peek at these; the serving path itself snapshots `_state`
    # once per call and never reads them as separate live attributes.
    @property
    def _export(self) -> Dict:
        return self._state.export

    @property
    def _plan(self):
        return self._state.plan

    @property
    def _plan_planes(self):
        return self._state.plan_planes

    @property
    def demoted(self) -> bool:
        """Is the published bundle host-resident (post LRU demotion)?"""
        return self._state.demoted

    def stale(self) -> bool:
        """Has the booster mutated since the last refresh()?"""
        return self._export["version"] != getattr(
            self._booster, "_model_version", 0)

    @property
    def booster(self):
        """The served booster — the fleet autoscaler reloads from the
        LIVE model when resizing a replica set (fleet/tenancy.py)."""
        return self._booster

    @property
    def device_sum_active(self) -> bool:
        """Is the device-sum rung serving (probe passed, not off)?"""
        return self._state.device_sum_ok

    @property
    def compiled_active(self) -> bool:
        """Is the compiled tile rung serving (plan built, probe passed)?"""
        return self._state.compiled_ok

    def rung_status(self) -> Dict:
        """Which device rungs the published bundle serves from and, for
        each rung that is off, the cause and detail of its last
        disable."""
        st = self._state
        with self._rung_lock:
            off = {r: dict(v) for r, v in self._rung_off.items()}
        return {"bounded": bool(st.bounded_ok),
                "compiled": bool(st.compiled_ok),
                "device_sum": bool(st.device_sum_ok),
                "disabled": off}

    def _note_rung(self, rung: str, cause: str = "",
                   detail: str = "") -> None:
        """Record why `rung` went off (or, with no cause, that it is
        back) for `rung_status()`."""
        with self._rung_lock:
            if cause:
                self._rung_off[rung] = {"cause": cause,
                                        "detail": detail[:500]}
            else:
                self._rung_off.pop(rung, None)

    @property
    def precision(self) -> str:
        """The runtime's configured precision tier ('exact'/'bounded')."""
        return self._precision

    @property
    def bounded_active(self) -> bool:
        """Is the bounded quantized rung serving (opt-in, probe passed,
        measured error within the published bound)?"""
        return self._state.bounded_ok

    @property
    def bounded_bound(self) -> Optional[float]:
        """The published worst-case max-abs-error bound on RAW scores
        (None when the bounded tier is off or disqualified)."""
        return self._state.bounded_bound

    @property
    def bounded_measured_error(self) -> Optional[float]:
        """The probe-measured max-abs error vs the exact-f64 reference
        on the refresh probe batch (None before any bounded probe)."""
        return self._state.bounded_measured

    @property
    def num_class(self) -> int:
        return self._export["num_class"]

    def num_feature(self) -> int:
        return int(self._booster.num_feature())

    def staging_bytes(self) -> int:
        """Bytes of the reused per-(bucket, width) staging buffers —
        each sizes the transient device copy `_stage32` uploads per
        call, so this is the runtime's worst-case per-call staging
        footprint on top of the pinned planes."""
        with self._staging_lock:
            return sum(int(buf.nbytes)
                       for buf in self._staging.values())

    def _plane_bytes(self) -> int:
        """Pinned plane bytes only (stacked traversal planes +
        leaf-value bit planes + compiled tile planes) — what
        `demote()` actually frees.  0 after `demote()`."""
        st = self._state
        ex = st.export
        if st.demoted or not ex:
            return 0
        total = 0
        stacked = ex.get("stacked")
        if stacked:
            total += sum(int(v.nbytes) for v in stacked.values()
                         if hasattr(v, "nbytes"))
        for k in ("value_hi", "value_lo"):
            if ex.get(k) is not None:
                total += int(ex[k].nbytes)
        if st.plan_planes is not None:
            total += sum(int(a.nbytes) for bucket in st.plan_planes
                         for a in bucket if a is not None)
        if st.bounded_planes is not None:
            total += sum(int(a.nbytes) for a in st.bounded_planes)
        return total

    def device_bytes(self) -> int:
        """Accelerator-resident bytes of this runtime's export (stacked
        traversal planes + leaf-value bit planes + compiled tile
        planes) — the registry's `serve_vram_budget_mb` accounting unit
        and exactly what the memory ledger attributes under
        `serve.<name>.planes{rung=}`, so `_admit` and the budget
        auditor agree on one number.  The reused per-(bucket, width)
        staging buffers are host-side scratch that survives `demote()`
        and exists regardless of admission — attributed separately
        under `serve.<name>.staging{bucket=,width=}` and reported by
        `staging_bytes()`, deliberately excluded here so workload shape
        (which buckets a traffic mix touched) can never flip an admit
        decision."""
        return self._plane_bytes()

    def _ledger_register(self, st: _ServeState) -> None:
        """(Re-)attribute the published bundle's planes in the memory
        ledger.  `assign` releases the previous bundle's handles for
        the same owner+labels first, so refresh/demote/rung swaps never
        double-count; a demoted bundle assigns empty lists, which IS
        the release."""
        led = telemetry.MEMLEDGER
        if not led.enabled:
            return
        ex = st.export
        owner = f"serve.{self.name}.planes"
        stacked_arrays: list = []
        tile_planes: list = []
        bounded_planes: list = []
        if ex and not st.demoted:
            stacked = ex.get("stacked")
            if stacked:
                stacked_arrays += [v for v in stacked.values()
                                   if hasattr(v, "nbytes")]
            stacked_arrays += [ex[k] for k in ("value_hi", "value_lo")
                               if ex.get(k) is not None]
            if st.plan_planes is not None:
                tile_planes = [a for bucket in st.plan_planes
                               for a in bucket if a is not None]
            if st.bounded_planes is not None:
                bounded_planes = list(st.bounded_planes)
        self._ledger_handles = (
            led.assign(owner, stacked_arrays, rung="stacked")
            + led.assign(owner, tile_planes, rung="compiled")
            + led.assign(owner, bounded_planes, rung="bounded"))

    def _ledger_release(self) -> None:
        """Drop every ledger handle this runtime owns (planes AND
        staging) — `ServingModel.close()` calls this so an unloaded
        model stops being attributed.  Handle-wise (idempotent), not
        owner-prefix-wise: during a load() swap the old and new
        runtimes briefly share owner keys."""
        led = telemetry.MEMLEDGER
        with self._refresh_lock:
            handles, self._ledger_handles = self._ledger_handles, []
        with self._staging_lock:
            handles += self._ledger_staging
            self._ledger_staging = []
        for h in handles:
            led.release(h)

    def demote(self) -> int:
        """Move the export's device arrays to host copies (the
        registry's LRU budget demotion).  The runtime keeps serving
        bit-identical results — the jitted programs re-upload per call
        — at reduced throughput until the next `refresh()` promotes it
        back.  Returns the device bytes freed."""
        with self._refresh_lock:
            freed = self._plane_bytes()  # staging survives demotion
            if freed == 0:
                return 0
            cur = self._state
            ex = dict(cur.export)
            stacked = ex.get("stacked")
            if stacked:
                ex["stacked"] = {
                    k: np.asarray(v) if isinstance(v, jax.Array) else v
                    for k, v in stacked.items()}
            for k in ("value_hi", "value_lo"):
                if ex.get(k) is not None:
                    ex[k] = np.asarray(ex[k])
            # the compiled planes exist ONLY on device — the demoted
            # bundle drops that rung entirely (the next refresh()
            # rebuilds and re-probes it); the device-sum rung survives,
            # re-uploading the host copies per call
            st = _ServeState(ex)
            st.device_sum_ok = cur.device_sum_ok
            st.probe_failed = cur.probe_failed
            st.demoted = True
            # the booster-side export cache pins the same device
            # buffers — drop it so they can actually free
            if getattr(self._booster, "_serving_export_cache",
                       None) is not None:
                self._booster._serving_export_cache = None
            self._state = st
            self._ledger_register(st)
            if cur.bounded_ok:
                # the bounded planes are device arrays — a demoted
                # bundle drops the rung (next refresh() repacks it)
                telemetry.REGISTRY.gauge("serve.bounded.active",
                                         model=self.name).set(0)
        telemetry.REGISTRY.counter("serve.demotions").inc()
        return freed

    # -------------------------------------------------- device-sum gate
    def _device_sum_enable(self, ex: Dict, st: _ServeState) -> bool:
        """Decide the top ladder rung for this export (refresh-time);
        probe verdicts land on the in-construction bundle `st`, which
        refresh() publishes whole."""
        if self._device_sum_mode == "off":
            return False
        if ex["stacked"] is None or not ex["trees"] \
                or ex.get("value_hi") is None:
            return False
        if ex["average_factor"] != 1:
            # RF averaging would need f64 division on device — the
            # slot path serves these models exactly instead
            return False
        if self._device_sum_mode == "force":
            self._breakers["device_sum"].record_success()
            self._note_rung("device_sum")
            return True
        verdict, detail = self._probe_device_sum(ex)
        if verdict == "ok":
            self._breakers["device_sum"].record_success()
            self._note_rung("device_sum")
            return True
        if verdict == "mismatch":
            # wrong CONTENT: permanent until a refresh re-probes a new
            # export — no amount of waiting fixes wrong bits
            st.probe_failed = True
            self._breakers["device_sum"].record_mismatch()
        elif verdict == "compile":
            # the compiler refuses the program: waiting fixes nothing
            self._breakers["device_sum"].record_mismatch()
        else:
            # transient device exception: the breaker's half-open
            # re-probe can recover the rung without a manual refresh
            self._breakers["device_sum"].record_failure()
        self._disable_device_sum(verdict, detail)
        return False

    def _disable_device_sum(self, cause: str, detail: str = "") -> None:
        telemetry.REGISTRY.counter("serve.device_sum_disabled").inc()
        telemetry.event("serve.device_sum_disabled", model=self.name,
                        cause=cause, detail=detail[:200])
        self._note_rung("device_sum", cause, detail)

    def _probe_device_sum(self, ex: Dict) -> Tuple[str, str]:
        """Export-time exact-parity gate: the device-sum program must
        bit-match the host f64 gather/sum over the SAME device slots —
        raw and converted — on a threshold-clustered probe batch, or
        the model degrades to the slot path.  Returns (verdict,
        detail); verdict is "ok", "mismatch" (wrong bits — permanent),
        "compile" (the compiler refused the program — permanent, detail
        is its message) or "error" (device exception —
        breaker-recoverable); a broken rung always degrades, never
        raises."""
        try:
            # single-chunk probe: stay within the bucket cap so the
            # staging buffer fits (small-bucket runtimes probe small)
            X = self._probe_batch(ex, rows=min(256, self.max_batch_rows))
            slots = self._device_slots_chunk(X, ex["stacked"])
            K = ex["num_class"]
            leaf_values = ex["leaf_values"]
            want = np.zeros((X.shape[0], K), np.float64)
            for i in range(slots.shape[0]):
                want[:, i % K] += leaf_values[i, slots[i]]
            if K == 1:
                want = want[:, 0]
            got = self._device_sum_chunk(X, ex, want_raw=True)
            if got.shape != want.shape or not np.array_equal(
                    got.view(np.uint64), want.view(np.uint64)):
                return "mismatch", "raw scores differ"
            obj = self._booster.objective_
            if obj is not None:
                got_c = self._device_sum_chunk(X, ex, want_raw=False)
                want_c = self._convert(want)
                if got_c.shape != want_c.shape \
                        or got_c.dtype != want_c.dtype \
                        or not np.array_equal(got_c.view(np.uint32),
                                              want_c.view(np.uint32)):
                    return "mismatch", "converted scores differ"
            return "ok", ""
        except Exception as e:
            return self._probe_exception("device_sum", e)

    def _probe_exception(self, rung: str, e: Exception) -> Tuple[str, str]:
        """(verdict, detail) for an exception out of a rung's probe:
        "compile" when the compiler refused the program, else the
        transient "error" (with its `serve.<rung>_probe_error`
        event)."""
        detail = f"{type(e).__name__}: {e}"
        if _compile_refusal(e):
            return "compile", detail
        telemetry.event(f"serve.{rung}_probe_error", model=self.name,
                        error=str(e)[:200])
        return "error", detail

    def _probe_batch(self, ex: Dict, rows: int = 256) -> np.ndarray:
        """Deterministic adversarial probe batch: feature values
        clustered at the model's own split thresholds (maximum routing
        and accumulation diversity), NaN/zero sprinkles for the
        missing-value paths, plus plain gaussian noise so large
        exponent gaps and cancellations in the adder all fire."""
        nf = max(self.num_feature(), int(ex["stacked"]["min_features"]), 1)
        rng = np.random.RandomState(0)
        X = rng.randn(rows, nf)
        thr, feats = [], []
        for t in ex["trees"]:
            k = max(t.num_leaves - 1, 0)
            thr.append(np.asarray(t.threshold[:k], np.float64))
            feats.append(np.asarray(t.split_feature[:k], np.int64))
        if thr:
            thr = np.concatenate(thr)
            feats = np.concatenate(feats)
            for f in np.unique(feats):
                v = thr[feats == f]
                pick = v[rng.randint(len(v), size=rows)]
                noise = rng.randn(rows) * (np.std(v) + 1e-3)
                X[:, f] = np.where(rng.rand(rows) < 0.5, pick,
                                   pick + noise)
        X[rng.rand(rows, nf) < 0.03] = np.nan
        X[rng.rand(rows, nf) < 0.03] = 0.0
        return np.ascontiguousarray(X)

    # ---------------------------------------------------- compiled gate
    def _disable_compiled(self, cause: str, detail: str = "") -> None:
        telemetry.REGISTRY.counter("serve.compiled_disabled",
                                   cause=cause).inc()
        telemetry.event("serve.compiled_disabled", model=self.name,
                        cause=cause, detail=detail[:200])
        self._note_rung("compiled", cause, detail)

    def _compiled_enable(self, ex: Dict, st: _ServeState) -> bool:
        """Decide the compiled tile rung for this export (refresh-time):
        build the plan, pin its planes onto the in-construction bundle
        `st`, then demand byte parity on the probe batch.  ANY refusal
        lands in `serve.compiled_disabled{cause=}` and the ladder below
        serves — a model that cannot compile is a degradation, never an
        error."""
        mode = self._compiled_mode
        if mode == "off":
            return False
        backend = jax.default_backend()
        if mode == "auto" and backend != "tpu":
            # interpreted Pallas on CPU is strictly slower than the XLA
            # device-sum program — auto keeps the existing ladder
            self._disable_compiled("platform", backend)
            return False
        if ex["stacked"] is None or not ex["trees"] \
                or ex.get("value_hi") is None or ex["average_factor"] != 1:
            self._disable_compiled("model")
            return False
        try:
            plan = build_plan(ex, tile_vmem_kb=self._tile_vmem_kb,
                              name=self.name)
        except PlanNotCompilable as e:
            self._disable_compiled("not_compilable", str(e))
            return False
        # declared-vs-measured tile contract: the packer promised every
        # tile fits serve_tile_vmem_kb — hold it to that (counts
        # mem.budget_violation{contract=serve_tile_vmem_kb} on breach)
        if plan.tile_stats:
            telemetry.MEMLEDGER.audit(
                "serve_tile_vmem_kb", self._tile_vmem_kb * 1024,
                max(int(s.get("bytes", 0)) for s in plan.tile_stats),
                model=self.name, site="serve.compiled_enable",
                tiles=len(plan.tile_stats))
        planes = []
        for p in plan.planes:
            arrs = [jnp.asarray(p["words"]), jnp.asarray(p["kids"]),
                    jnp.asarray(p["pal"]),
                    jnp.asarray(p["catw"]) if "catw" in p else None]
            if self.device is not None:
                arrs = [jax.device_put(a, self.device)
                        if a is not None else None for a in arrs]
            planes.append(tuple(arrs))
        gidx = jnp.asarray(plan.gather_idx)
        if self.device is not None:
            gidx = jax.device_put(gidx, self.device)
        st.plan = plan
        st.plan_planes = tuple(planes)
        st.plan_meta = tuple(
            (p["depth"], p["catw"].shape[-1] if "catw" in p else 0)
            for p in plan.planes)
        st.plan_gidx = gidx
        if mode == "force":
            self._breakers["compiled"].record_success()
            self._note_rung("compiled")
            return True
        verdict, detail = self._probe_compiled(st)
        if verdict == "ok":
            self._breakers["compiled"].record_success()
            self._note_rung("compiled")
            return True
        if verdict in ("mismatch", "compile"):
            # wrong bits, or a kernel the compiler refuses: permanent
            # until a refresh — only wrong bits taint `probe_failed`
            st.probe_failed = st.probe_failed or verdict == "mismatch"
            self._breakers["compiled"].record_mismatch()
            self._disable_compiled(
                "probe" if verdict == "mismatch" else "compile", detail)
            st.plan = None
            st.plan_planes = None
            st.plan_meta = None
            st.plan_gidx = None
        else:
            # transient probe exception: KEEP the built planes so the
            # half-open re-probe can retry without a rebuild — the open
            # breaker (plus compiled_ok=False) gates serving meanwhile
            self._breakers["compiled"].record_failure()
            self._disable_compiled("probe_error", detail)
        return False

    def _probe_compiled(self, st: _ServeState) -> Tuple[str, str]:
        """Refresh-time exact-parity gate for the compiled rung: the
        tiled kernel's accumulated bits — raw AND converted — must
        match the host f64 gather/sum over the slot program's device
        slots on the threshold-clustered probe batch (the same
        reference `_probe_device_sum` holds the device-sum rung to).
        Same (verdict, detail) split: "ok" | "mismatch" | "compile" |
        "error"."""
        try:
            ex = st.export
            X = self._probe_batch(ex, rows=min(256, self.max_batch_rows))
            slots = self._device_slots_chunk(X, ex["stacked"])
            K = ex["num_class"]
            leaf_values = ex["leaf_values"]
            want = np.zeros((X.shape[0], K), np.float64)
            for i in range(slots.shape[0]):
                want[:, i % K] += leaf_values[i, slots[i]]
            if K == 1:
                want = want[:, 0]
            got = self._compiled_chunk(X, st, want_raw=True)
            if got.shape != want.shape or not np.array_equal(
                    got.view(np.uint64), want.view(np.uint64)):
                return "mismatch", "raw scores differ"
            obj = self._booster.objective_
            if obj is not None:
                got_c = self._compiled_chunk(X, st, want_raw=False)
                want_c = self._convert(want)
                if got_c.shape != want_c.shape \
                        or got_c.dtype != want_c.dtype \
                        or not np.array_equal(got_c.view(np.uint32),
                                              want_c.view(np.uint32)):
                    return "mismatch", "converted scores differ"
            return "ok", ""
        except Exception as e:
            return self._probe_exception("compiled", e)

    # ----------------------------------------------------- bounded gate
    def _disable_bounded(self, cause: str, detail: str = "") -> None:
        telemetry.REGISTRY.counter("serve.bounded_disabled",
                                   cause=cause).inc()
        telemetry.event("serve.bounded_disabled", model=self.name,
                        cause=cause, detail=detail[:200])
        telemetry.REGISTRY.gauge("serve.bounded.active",
                                 model=self.name).set(0)
        self._note_rung("bounded", cause, detail)

    def _bounded_gauges(self, st: _ServeState, active: bool) -> None:
        """Publish the per-model bound/measured gauges the fleet
        snapshot and sentinel read (`/debug/fleet` renders them)."""
        g = telemetry.REGISTRY.gauge
        g("serve.bounded.active", model=self.name).set(1 if active else 0)
        if st.bounded_bound is not None:
            g("serve.bounded.bound", model=self.name).set(st.bounded_bound)
        if st.bounded_measured is not None:
            g("serve.bounded.measured_error", model=self.name).set(
                st.bounded_measured)

    def _bounded_enable(self, ex: Dict, st: _ServeState) -> bool:
        """Decide the bounded quantized rung for this export
        (refresh-time): quantize the leaf-value table against the tile
        plan, pin the planes onto the in-construction bundle `st`, then
        demand the probe-measured max-abs error stay within the
        published bound.  ANY refusal lands in
        `serve.bounded_disabled{cause=}` and the exact ladder serves —
        a model that cannot be bounded-quantized is a degradation,
        never an error."""
        if self._precision != "bounded":
            return False
        if ex["stacked"] is None or not ex["trees"] \
                or ex.get("value_hi") is None or ex["average_factor"] != 1:
            self._disable_bounded("model")
            return False
        # the quantizer's per-tile scales come from the SAME tile plan
        # the compiled rung traverses; when that rung is off (CPU auto /
        # serve_compiled=off) the plan is built here host-side only —
        # its tile membership prices the scales, no device planes pinned
        plan = st.plan
        if plan is None:
            try:
                plan = build_plan(ex, tile_vmem_kb=self._tile_vmem_kb,
                                  name=self.name)
            except PlanNotCompilable as e:
                self._disable_bounded("not_compilable", str(e))
                return False
        try:
            packed = pack_bounded(ex["trees"], plan, ex["leaf_values"],
                                  ex["num_class"], bits=self._quant_bits)
        except PlanNotCompilable as e:
            self._disable_bounded("not_quantizable", str(e))
            return False
        arrs = [jnp.asarray(packed["qval"]),
                jnp.asarray(packed["tile_of_tree"]),
                jnp.asarray(packed["scales"])]
        if self.device is not None:
            arrs = [jax.device_put(a, self.device) for a in arrs]
        st.bounded_planes = tuple(arrs)
        st.bounded_bound = float(packed["bound"])
        verdict, detail = self._probe_bounded(st)
        if verdict == "ok":
            self._breakers["bounded"].record_success()
            self._bounded_gauges(st, True)
            self._note_rung("bounded")
            return True
        if verdict == "compile":
            # the compiler refuses the program: permanent until a
            # refresh, like a bound breach, and nothing to re-probe
            self._breakers["bounded"].record_mismatch()
            self._disable_bounded("compile", detail)
            st.bounded_planes = None
            st.bounded_bound = None
        elif verdict == "bound":
            # measured error past the published bound is wrong CONTENT
            # (a doctored/rotted plane, not a transient): permanent
            # until a refresh re-quantizes — same class as a parity
            # mismatch on the exact rungs, but it does NOT taint
            # `probe_failed` (the exact ladder beneath is untouched)
            self._breakers["bounded"].record_mismatch()
            self._disable_bounded(
                "bound", f"measured {st.bounded_measured!r} > "
                         f"published {st.bounded_bound!r}")
            st.bounded_planes = None
            st.bounded_bound = None
        else:
            # transient device exception: KEEP the quantized planes so
            # the half-open re-probe can retry without a repack
            self._breakers["bounded"].record_failure()
            self._disable_bounded("probe_error", detail)
        return False

    def _probe_bounded(self, st: _ServeState) -> Tuple[str, str]:
        """Refresh-time bound-enforcement gate: measure the bounded
        program's max-abs error against the host f64 gather/sum over
        the slot program's device slots (the same exact reference the
        parity probes use) on the threshold-clustered probe batch.
        Returns (verdict, detail): "ok" (measured <= published bound,
        measurement stored for publication), "bound" (measured exceeds
        the bound — the contract would be violated, permanent),
        "compile" (the compiler refused the program — permanent) or
        "error" (device exception — breaker-recoverable)."""
        try:
            ex = st.export
            X = self._probe_batch(ex, rows=min(256, self.max_batch_rows))
            slots = self._device_slots_chunk(X, ex["stacked"])
            K = ex["num_class"]
            leaf_values = ex["leaf_values"]
            want = np.zeros((X.shape[0], K), np.float64)
            for i in range(slots.shape[0]):
                want[:, i % K] += leaf_values[i, slots[i]]
            if K == 1:
                want = want[:, 0]
            got = self._bounded_chunk(X, st, want_raw=True)
            if got.shape != want.shape:
                st.bounded_measured = float("inf")
                return "bound", f"shape {got.shape} != {want.shape}"
            err = float(np.max(np.abs(got.astype(np.float64) - want)))
            st.bounded_measured = err
            if not np.isfinite(err) or err > st.bounded_bound:
                return "bound", f"measured {err!r}"
            return "ok", ""
        except Exception as e:
            return self._probe_exception("bounded", e)

    def _drop_bounded(self, st: _ServeState, cause: str,
                      detail: str = "") -> None:
        """Retire the bounded rung from the PUBLISHED bundle (warmup
        failures) — the bounded analog of `_drop_compiled`."""
        self._disable_bounded(cause, detail)
        with self._refresh_lock:
            cur = self._state
            if cur is not st or not cur.bounded_ok:
                return
            new = cur.clone()
            new.bounded_ok = False
            new.bounded_planes = None
            new.bounded_bound = None
            self._state = new
            self._ledger_register(new)

    def buckets(self) -> List[int]:
        """Every padding bucket this runtime can present to the device."""
        out = []
        b = 1
        while b < self.max_batch_rows:
            out.append(b)
            b <<= 1
        out.append(self.max_batch_rows)
        return out

    def warmup(self) -> int:
        """Compile every padding bucket up front (warm-up-on-load), so
        no live request ever pays a device compile: the slot program,
        the device-sum programs (raw + converted) when active, AND the
        eager `convert_output` per-bucket compiles — the eager path
        stays live on the fallback ladder, so a degradation must not
        reintroduce a first-request compile.  Uses the model's full
        feature width — the jit caches are keyed on [bucket, F], so
        warming a narrower matrix would not count.  Returns the number
        of buckets warmed (0 when the model is host-walk only)."""
        st = self._state
        ex = st.export
        if ex["stacked"] is None or not ex["trees"]:
            return 0
        nf = max(self.num_feature(), int(ex["stacked"]["min_features"]))
        sizes = self.buckets()
        obj = self._booster.objective_
        K = ex["num_class"]
        compiled_ok = st.compiled_ok
        with telemetry.span("serve.warmup", model=self.name,
                            buckets=len(sizes)):
            t0 = time.perf_counter()
            device_sum_warm = st.device_sum_ok
            bounded_warm = st.bounded_ok
            slot_warm = True
            for b in sizes:
                Z = np.zeros((b, nf), np.float64)
                if bounded_warm:
                    try:
                        self._bounded_chunk(Z, st, want_raw=True)
                        if obj is not None:
                            self._bounded_chunk(Z, st, want_raw=False)
                    except Exception as e:
                        # the bounded rung degrades to the exact ladder
                        # exactly like a compiled warmup failure
                        bounded_warm = False
                        self._drop_bounded(
                            st, "compile" if _compile_refusal(e)
                            else "warmup_error", str(e))
                if slot_warm:
                    try:
                        self._device_slots_chunk(Z, ex["stacked"])
                    except Exception as e:
                        # degrade-don't-error, same contract as the
                        # predict path: warmup must never fail the model
                        # load — open the rung's breaker (the half-open
                        # re-probe recovers it) and keep warming the
                        # surviving ladder
                        slot_warm = False
                        self._breakers["slot_path"].record_failure()
                        telemetry.REGISTRY.counter(
                            "serve.device_errors").inc()
                        telemetry.event("serve.device_error",
                                        model=self.name,
                                        path="slot_warmup",
                                        error=str(e)[:200])
                if compiled_ok:
                    try:
                        self._compiled_chunk(Z, st, want_raw=True)
                        if obj is not None:
                            self._compiled_chunk(Z, st, want_raw=False)
                    except Exception as e:
                        # a rung that cannot even warm must not fail the
                        # model load — retire it and keep warming the
                        # surviving ladder
                        compiled_ok = False
                        self._drop_compiled(
                            st, "compile" if _compile_refusal(e)
                            else "warmup_error", str(e))
                if device_sum_warm:
                    try:
                        self._device_sum_chunk(Z, ex, want_raw=True)
                        if obj is not None:
                            self._device_sum_chunk(Z, ex, want_raw=False)
                    except Exception as e:
                        device_sum_warm = False
                        self._breakers["device_sum"].record_failure()
                        telemetry.REGISTRY.counter(
                            "serve.device_errors").inc()
                        telemetry.event("serve.device_error",
                                        model=self.name,
                                        path="device_sum_warmup",
                                        error=str(e)[:200])
                if obj is not None:
                    shape = (b,) if K == 1 else (b, K)
                    self._convert(np.zeros(shape, np.float64))
            telemetry.REGISTRY.timing("serve.warmup").observe(
                time.perf_counter() - t0)
        return len(sizes)

    def _drop_compiled(self, st: _ServeState, cause: str,
                       detail: str = "") -> None:
        """Retire the compiled rung from the PUBLISHED bundle (warmup
        failures): republish the same export minus the plan — unless a
        concurrent refresh/demote already swapped a newer bundle in, in
        which case theirs wins."""
        self._disable_compiled(cause, detail)
        with self._refresh_lock:
            cur = self._state
            if cur is not st or not cur.compiled_ok:
                return
            new = cur.clone()
            new.compiled_ok = False
            new.plan = None
            new.plan_planes = None
            new.plan_meta = None
            new.plan_gidx = None
            self._state = new
            self._ledger_register(new)

    # ----------------------------------------- breaker-gated recovery
    def _maybe_reprobe(self, st: _ServeState) -> None:
        """Request-path hook: promote any OPEN breaker whose backoff
        has elapsed to half_open and kick ONE background re-probe for
        it.  The request itself never probes — the `.state` read is a
        lock-free attribute load, so the closed/hot path pays one
        string compare per rung."""
        if st.demoted:
            return
        for rung, br in self._breakers.items():
            if br.state == OPEN and br.begin_probe():
                self._kick_reprobe(rung)

    def _kick_reprobe(self, rung: str) -> None:
        # begin_probe() hands out exactly one half-open claim per open
        # period, so this can never double-spawn for a rung
        t = threading.Thread(
            target=self._reprobe, args=(rung,), daemon=True,
            name=f"lgbm-serve-reprobe-{self.name}-{rung}")
        with self._reprobe_lock:
            self._reprobe_threads[rung] = t
        t.start()

    def _reprobe(self, rung: str) -> None:
        """Half-open background re-probe: re-run the rung's parity
        probe against the LIVE bundle and close / re-open (backoff
        doubled) / permanent the breaker on the verdict.  Runs under
        the refresh lock — a concurrent refresh() either waits or has
        already republished, and its fresh probes win either way."""
        br = self._breakers[rung]
        telemetry.REGISTRY.counter("serve.breaker.reprobe",
                                   rung=rung).inc()
        try:
            with self._refresh_lock:
                cur = self._state
                ex = cur.export
                if cur.demoted or not ex or ex.get("stacked") is None \
                        or not ex.get("trees"):
                    br.record_failure()
                    return
                verdict, detail = "error", ""
                if rung == "device_sum":
                    verdict, detail = (
                        ("ok", "") if self._device_sum_mode == "force"
                        else self._probe_device_sum(ex))
                elif rung == "compiled":
                    # no planes: a mismatch dropped them (permanent) or
                    # a demote did — only a refresh rebuilds them
                    if cur.plan_planes is not None:
                        verdict, detail = (
                            ("ok", "") if self._compiled_mode == "force"
                            else self._probe_compiled(cur))
                elif rung == "bounded":
                    # no planes: a bound breach dropped them (permanent)
                    # or a demote did — only a refresh repacks
                    if cur.bounded_planes is not None:
                        verdict, detail = self._probe_bounded(cur)
                else:
                    verdict, detail = self._probe_slot_path(ex)
                if verdict == "ok":
                    br.record_success()
                    telemetry.REGISTRY.counter("serve.breaker.recovered",
                                               rung=rung).inc()
                    telemetry.event("serve.breaker.recovered",
                                    model=self.name, rung=rung)
                    if rung == "bounded":
                        self._bounded_gauges(cur, True)
                    self._note_rung(rung)
                    self._publish_rung(cur, rung, True)
                elif verdict in ("mismatch", "bound", "compile"):
                    br.record_mismatch()
                    if rung == "compiled":
                        self._disable_compiled(
                            "probe" if verdict == "mismatch" else verdict,
                            detail)
                    elif rung == "bounded":
                        self._disable_bounded(verdict, detail)
                    elif rung == "device_sum":
                        self._disable_device_sum(verdict, detail)
                    # only wrong BITS label the ladder `probe_fail`
                    self._publish_rung(cur, rung, False, mismatch=True,
                                       taint=verdict != "compile")
                else:
                    br.record_failure()
        except Exception as e:  # a failed re-probe must never propagate
            br.record_failure()
            telemetry.event("serve.breaker.reprobe_error",
                            model=self.name, rung=rung,
                            error=str(e)[:200])

    def _probe_slot_path(self, ex: Dict) -> Tuple[str, str]:
        """Re-probe gate for the slot rung: the slot path is exact by
        construction (device slots + host f64 gather), so recovery only
        needs the device program to answer again."""
        try:
            X = self._probe_batch(ex, rows=min(64, self.max_batch_rows))
            self._device_slots_chunk(X, ex["stacked"])
            return "ok", ""
        except Exception as e:
            telemetry.event("serve.slot_probe_error", model=self.name,
                            error=str(e)[:200])
            return "error", f"{type(e).__name__}: {e}"

    def _publish_rung(self, cur: _ServeState, rung: str, ok: bool,
                      mismatch: bool = False, taint: bool = True) -> None:
        """Republish the live bundle with one rung verdict flipped
        (caller holds `_refresh_lock`).  The slot rung has no state
        flag — its breaker is the only gate — and a no-op flip is not
        republished (predict-time failures leave the flag True; the
        breaker alone gated the rung, so closing it suffices).
        `mismatch` marks a permanent verdict (the planes go even when
        the flag is already down); `taint=False` keeps it from labelling
        the ladder `probe_fail` (a compile refusal is not wrong bits)."""
        if rung == "slot_path":
            return
        flag = {"device_sum": "device_sum_ok", "compiled": "compiled_ok",
                "bounded": "bounded_ok"}[rung]
        if getattr(cur, flag) == ok and not mismatch:
            return
        new = cur.clone()
        # a bounded bound-breach does NOT taint probe_failed: that flag
        # labels the EXACT ladder's host-walk cause, and the exact
        # rungs beneath the bounded tier are untouched by its verdict
        new.probe_failed = cur.probe_failed or (mismatch and taint
                                                and rung != "bounded")
        setattr(new, flag, ok)
        if rung == "compiled" and not ok:
            new.plan = None
            new.plan_planes = None
            new.plan_meta = None
            new.plan_gidx = None
        if rung == "bounded" and not ok:
            new.bounded_planes = None
            new.bounded_bound = None
        self._state = new
        self._ledger_register(new)

    # ----------------------------------------------------------- predict
    def predict(self, X, raw_score: bool = False,
                clock: Optional[telemetry.StageClock] = None) -> np.ndarray:
        """Bucket-padded device prediction, byte-identical to
        `booster.predict(X, raw_score=...)` (ladder rungs degrade
        transparently on device errors).

        `clock` (ISSUE 8 tracing) collects per-stage wall-clock deltas —
        staging copy, device dispatch, D2H — and the ladder rung that
        produced the bytes; the remainder of the call (host gather/sum,
        output conversion, slicing) lands in the `convert` stage.  All
        stamps are `perf_counter` reads around boundaries the call
        already crosses: tracing adds no device syncs and never touches
        the data, so traced and untraced outputs are byte-identical.
        """
        if clock is None:
            clock = telemetry.StageClock()
        if not (isinstance(X, np.ndarray) and X.dtype == np.float64
                and X.flags["C_CONTIGUOUS"]):
            # the micro-batcher hands over already-normalized arrays —
            # don't copy a contiguous f64 matrix a second time
            X = np.ascontiguousarray(np.asarray(X, dtype=np.float64))
        if X.ndim == 1:
            X = X.reshape(1, -1)
        n = X.shape[0]
        # ONE snapshot of the published bundle: every rung below reads
        # export and plan from the same `st`, so a concurrent refresh
        # can never mix this request across model versions
        st = self._state
        ex = st.export
        self._maybe_reprobe(st)
        with telemetry.span("serve.predict", model=self.name, rows=n):
            t0 = time.perf_counter()
            want_raw = raw_score or self._booster.objective_ is None
            out = None
            # the bounded tier sits ABOVE the exact ladder: opt-in,
            # probe-passed, breaker-closed — any refusal falls through
            # to the exact rungs unchanged
            if st.bounded_ok and ex["trees"] \
                    and self._breakers["bounded"].allow_request():
                out = self._bounded(X, st, want_raw, clock)
                if out is not None:
                    clock.rung = "bounded"
            if out is None and st.compiled_ok and ex["trees"] \
                    and self._breakers["compiled"].allow_request():
                out = self._compiled(X, st, want_raw, clock)
            if out is not None:
                if clock.rung != "bounded":
                    clock.rung = "compiled"
            else:
                if st.device_sum_ok and ex["trees"] \
                        and self._breakers["device_sum"].allow_request():
                    out = self._device_sum(X, ex, want_raw, clock)
                if out is not None:
                    clock.rung = "device_sum"
                else:
                    raw = self._raw(X, st, clock)
                    out = raw if want_raw else self._convert(raw)
            total = time.perf_counter() - t0
            telemetry.REGISTRY.timing("serve.predict").observe(total)
            accounted = sum(clock.stages.get(s, 0.0)
                            for s in ("stage_copy", "dispatch", "d2h",
                                      "convert"))
            clock.add("convert", max(total - accounted, 0.0))
        telemetry.REGISTRY.counter("serve.rows").inc(n)
        return out

    # -------------------------------------- rung b: bounded quantized
    def _bounded(self, X: np.ndarray, st: _ServeState, want_raw: bool,
                 clock: Optional[telemetry.StageClock] = None,
                 ) -> Optional[np.ndarray]:
        """f32 scores inside the published error bound, or None when
        the exact ladder must take over (same chunk/degrade shape as
        `_compiled`)."""
        stacked = st.export["stacked"]
        if X.shape[1] < stacked["min_features"] or X.shape[0] == 0:
            return None
        try:
            outs = [self._bounded_chunk(
                        X[lo:lo + self.max_batch_rows], st, want_raw,
                        clock)
                    for lo in range(0, X.shape[0], self.max_batch_rows)]
        except Exception as e:
            if _compile_refusal(e):
                # a bucket the probe did not compile: retire the rung
                # with the compiler's message, nothing to re-probe
                self._breakers["bounded"].record_mismatch()
                self._drop_bounded(st, "compile", str(e))
                return None
            self._breakers["bounded"].record_failure()
            telemetry.REGISTRY.counter("serve.device_errors").inc()
            telemetry.event("serve.device_error", model=self.name,
                            path="bounded", error=str(e)[:200])
            return None
        telemetry.REGISTRY.counter("serve.bounded").inc()
        return outs[0] if len(outs) == 1 else np.concatenate(outs, axis=0)

    def _bounded_chunk(self, Xc: np.ndarray, st: _ServeState,
                       want_raw: bool,
                       clock: Optional[telemetry.StageClock] = None,
                       ) -> np.ndarray:
        """One bucket-padded bounded dispatch.  Traversal comes from
        the tiled Pallas program when the compiled planes are live on
        this bundle, the stacked XLA scan otherwise — both route
        bit-identically and share `accumulate_slots_bounded`, so the
        choice never changes the bytes (a compiled-rung drop mid-flight
        just switches the next chunk's traversal)."""
        if clock is None:
            clock = telemetry.StageClock()
        ex = st.export
        use_kernel = st.plan_planes is not None
        b = bucket_rows(Xc.shape[0], self.max_batch_rows)
        if use_kernel and b > ROW_BLOCK and b % ROW_BLOCK:
            # the kernel grid tiles rows in ROW_BLOCK blocks (see
            # `_compiled_chunk`) — pad up so the block spec divides
            b += ROW_BLOCK - b % ROW_BLOCK
        t = time.perf_counter()
        Xd = self._stage32(Xc, b)
        clock.add("stage_copy", time.perf_counter() - t)
        K = ex["num_class"]
        conv = None if want_raw else self._booster.objective_.convert_output
        qval, tidx, scales = st.bounded_planes
        n = Xc.shape[0]

        def _device():
            with telemetry.MEMLEDGER.oom_guard("serve.dispatch.bounded",
                                               model=self.name):
                FAULTS.inject("serve.dispatch.bounded")
                t = time.perf_counter()
                if use_kernel:
                    cls = ex["stacked"].get("cls") if K > 1 else None
                    interp = jax.default_backend() != "tpu"
                    out = compiled_predict_bounded(
                        Xd, st.plan_planes, st.plan_gidx, qval, tidx,
                        scales, cls, meta=st.plan_meta, n_class=K,
                        convert=conv, interpret=interp)
                else:
                    arrays = {k: v for k, v in ex["stacked"].items()
                              if k not in ("min_features", "value")}
                    out = _BOUNDED_JIT(arrays, Xd, qval, tidx, scales,
                                       n_class=K, convert=conv)
                clock.add("dispatch", time.perf_counter() - t)
                t = time.perf_counter()
                o = np.asarray(jax.device_get(out))
                clock.add("d2h", time.perf_counter() - t)
                telemetry.REGISTRY.counter("serve.d2h_bytes").inc(
                    o.nbytes)
                return FAULTS.inject("serve.d2h.bounded", o)

        return self._supervisors["bounded"].call(_device)[:n]

    # ------------------------------------------- rung 0: compiled tiles
    def _compiled(self, X: np.ndarray, st: _ServeState, want_raw: bool,
                  clock: Optional[telemetry.StageClock] = None,
                  ) -> Optional[np.ndarray]:
        """Finished scores from the tiled Pallas program, or None when
        the device-sum rung must take over (same chunk/degrade shape as
        `_device_sum`)."""
        stacked = st.export["stacked"]
        if X.shape[1] < stacked["min_features"] or X.shape[0] == 0:
            return None
        try:
            outs = [self._compiled_chunk(
                        X[lo:lo + self.max_batch_rows], st, want_raw,
                        clock)
                    for lo in range(0, X.shape[0], self.max_batch_rows)]
        except Exception as e:
            if _compile_refusal(e):
                # a bucket the probe did not compile: retire the rung
                # with the compiler's message, nothing to re-probe
                self._breakers["compiled"].record_mismatch()
                self._drop_compiled(st, "compile", str(e))
                return None
            self._breakers["compiled"].record_failure()
            telemetry.REGISTRY.counter("serve.device_errors").inc()
            telemetry.event("serve.device_error", model=self.name,
                            path="compiled", error=str(e)[:200])
            return None
        telemetry.REGISTRY.counter("serve.compiled").inc()
        return outs[0] if len(outs) == 1 else np.concatenate(outs, axis=0)

    def _compiled_chunk(self, Xc: np.ndarray, st: _ServeState,
                        want_raw: bool,
                        clock: Optional[telemetry.StageClock] = None,
                        ) -> np.ndarray:
        if clock is None:
            clock = telemetry.StageClock()
        ex = st.export
        b = bucket_rows(Xc.shape[0], self.max_batch_rows)
        if b > ROW_BLOCK and b % ROW_BLOCK:
            # the kernel grid tiles rows in ROW_BLOCK blocks; an odd
            # user cap (serve_max_batch_rows=3000) clamps the top
            # bucket to a non-multiple — pad on up so the block spec
            # divides.  Padding rows are zero and sliced away below;
            # rows are independent, so the real rows' bytes are
            # untouched.
            b += ROW_BLOCK - b % ROW_BLOCK
        t = time.perf_counter()
        Xd = self._stage32(Xc, b)
        clock.add("stage_copy", time.perf_counter() - t)
        K = ex["num_class"]
        cls = ex["stacked"].get("cls") if K > 1 else None
        conv = None if want_raw else self._booster.objective_.convert_output
        # interpret off-TPU: parity machinery stays testable everywhere
        interp = jax.default_backend() != "tpu"
        n = Xc.shape[0]

        def _device():
            # dispatch + D2H under one watchdog deadline: a wedged
            # kernel is abandoned and surfaces as DeviceTimeoutError,
            # which the except in `_compiled` treats like any device
            # failure (degrade + open the breaker).  The oom_guard dumps
            # the attributed snapshot on RESOURCE_EXHAUSTED, re-raises,
            # and the same except degrades the rung.
            with telemetry.MEMLEDGER.oom_guard("serve.dispatch.compiled",
                                               model=self.name):
                FAULTS.inject("compiled.traverse")
                t = time.perf_counter()
                out = compiled_predict(Xd, st.plan_planes, st.plan_gidx,
                                       ex["value_hi"], ex["value_lo"],
                                       cls, meta=st.plan_meta, n_class=K,
                                       convert=conv, interpret=interp)
                clock.add("dispatch", time.perf_counter() - t)
                if want_raw:
                    t = time.perf_counter()
                    hi = np.asarray(jax.device_get(out[0]))
                    lo = np.asarray(jax.device_get(out[1]))
                    clock.add("d2h", time.perf_counter() - t)
                    telemetry.REGISTRY.counter("serve.d2h_bytes").inc(
                        hi.nbytes + lo.nbytes)
                    raw = ((hi.astype(np.uint64) << np.uint64(32))
                           | lo).view(np.float64)
                    return FAULTS.inject("serve.d2h.compiled", raw)
                t = time.perf_counter()
                o = np.asarray(jax.device_get(out))
                clock.add("d2h", time.perf_counter() - t)
                telemetry.REGISTRY.counter("serve.d2h_bytes").inc(
                    o.nbytes)
                return FAULTS.inject("serve.d2h.compiled", o)

        return self._supervisors["compiled"].call(_device)[:n]

    # ----------------------------------------------- rung 1: device sum
    def _device_sum(self, X: np.ndarray, ex: Dict, want_raw: bool,
                    clock: Optional[telemetry.StageClock] = None,
                    ) -> Optional[np.ndarray]:
        """Finished scores straight off the device, or None when the
        next rung (slot path) must take over."""
        stacked = ex["stacked"]
        if X.shape[1] < stacked["min_features"] or X.shape[0] == 0:
            return None
        try:
            outs = [self._device_sum_chunk(
                        X[lo:lo + self.max_batch_rows], ex, want_raw,
                        clock)
                    for lo in range(0, X.shape[0], self.max_batch_rows)]
        except Exception as e:
            self._breakers["device_sum"].record_failure()
            telemetry.REGISTRY.counter("serve.device_errors").inc()
            telemetry.event("serve.device_error", model=self.name,
                            path="device_sum", error=str(e)[:200])
            return None
        telemetry.REGISTRY.counter("serve.device_sum").inc()
        return outs[0] if len(outs) == 1 else np.concatenate(outs, axis=0)

    def _device_sum_chunk(self, Xc: np.ndarray, ex: Dict, want_raw: bool,
                          clock: Optional[telemetry.StageClock] = None,
                          ) -> np.ndarray:
        if clock is None:
            clock = telemetry.StageClock()
        b = bucket_rows(Xc.shape[0], self.max_batch_rows)
        t = time.perf_counter()
        Xd = self._stage32(Xc, b)
        clock.add("stage_copy", time.perf_counter() - t)
        stacked = ex["stacked"]
        arrays = {k: v for k, v in stacked.items()
                  if k not in ("min_features", "value")}
        arrays["value_hi"] = ex["value_hi"]
        arrays["value_lo"] = ex["value_lo"]
        K = ex["num_class"]
        conv = None if want_raw else self._booster.objective_.convert_output
        n = Xc.shape[0]

        def _device():
            with telemetry.MEMLEDGER.oom_guard(
                    "serve.dispatch.device_sum", model=self.name):
                FAULTS.inject("serve.dispatch.device_sum")
                t = time.perf_counter()
                out = _EXACT_JIT(arrays, Xd, n_class=K, convert=conv)
                clock.add("dispatch", time.perf_counter() - t)
                if want_raw:
                    t = time.perf_counter()
                    hi = np.asarray(jax.device_get(out[0]))
                    lo = np.asarray(jax.device_get(out[1]))
                    clock.add("d2h", time.perf_counter() - t)
                    telemetry.REGISTRY.counter("serve.d2h_bytes").inc(
                        hi.nbytes + lo.nbytes)
                    raw = ((hi.astype(np.uint64) << np.uint64(32))
                           | lo).view(np.float64)
                    return FAULTS.inject("serve.d2h.device_sum", raw)
                t = time.perf_counter()
                o = np.asarray(jax.device_get(out))
                clock.add("d2h", time.perf_counter() - t)
                telemetry.REGISTRY.counter("serve.d2h_bytes").inc(
                    o.nbytes)
                return FAULTS.inject("serve.d2h.device_sum", o)

        return self._supervisors["device_sum"].call(_device)[:n]

    # ------------------------------------------- rungs 2+3: slots, host
    def _raw(self, X: np.ndarray, st: _ServeState,
             clock: Optional[telemetry.StageClock] = None) -> np.ndarray:
        """Exact f64 raw scores: device leaf slots (bucketed) + host
        gather/sum in tree order — the host walk's summation, verbatim."""
        ex = st.export
        trees = ex["trees"]
        K = ex["num_class"]
        n = X.shape[0]
        raw = np.zeros((n, K), np.float64)
        slots = None
        slot_skipped = False
        if trees:
            if self._breakers["slot_path"].allow_request():
                slots = self._device_slots(X, ex, clock)
            else:
                # open breaker: the rung already failed recently — skip
                # it outright (nobody pays a wedged device's deadline
                # twice) until the half-open re-probe closes it
                slot_skipped = True
        if clock is not None:
            clock.rung = "slot_path" if slots is not None else "host_walk"
        if trees and slots is None:
            # host fallback (tree.py walk, exact f64) — the labeled
            # counter makes the WHY diagnosable from /metrics alone:
            # linear_tree (no stacked planes), forced (X too narrow /
            # empty), probe_fail (device errors on a runtime whose
            # refresh-time parity probes already failed — the smoking
            # gun for a silently miscompiling device), breaker_open
            # (skipped without an attempt), device_error
            stacked = ex["stacked"]
            if stacked is None:
                cause = "linear_tree"
            elif X.shape[1] < stacked["min_features"] or n == 0:
                cause = "forced"
            elif st.probe_failed:
                cause = "probe_fail"
            elif slot_skipped:
                cause = "breaker_open"
            else:
                cause = "device_error"
            telemetry.REGISTRY.counter("serve.host_walk",
                                       cause=cause).inc()
            with telemetry.span("serve.fallback", model=self.name,
                                rows=n):
                for i, t in enumerate(trees):
                    raw[:, i % K] += t.predict(X)
        elif trees:
            telemetry.REGISTRY.counter("serve.slot_path").inc()
            leaf_values = ex["leaf_values"]
            for i in range(len(trees)):
                raw[:, i % K] += leaf_values[i, slots[i]]
        if ex["average_factor"] != 1:
            raw /= ex["average_factor"]
        if K == 1:
            raw = raw[:, 0]
        return raw

    def _device_slots(self, X: np.ndarray, ex: Dict,
                      clock: Optional[telemetry.StageClock] = None,
                      ) -> Optional[np.ndarray]:
        """[T, N] i32 leaf slots via the bucketed device program, or
        None when the host walk must take over."""
        stacked = ex["stacked"]
        if stacked is None or X.shape[1] < stacked["min_features"] \
                or X.shape[0] == 0:
            return None
        try:
            outs = [self._device_slots_chunk(
                        X[lo:lo + self.max_batch_rows], stacked, clock)
                    for lo in range(0, X.shape[0], self.max_batch_rows)]
        except Exception as e:
            # probe-wedge lesson: a dead/wedged device must degrade, not
            # 500 — count it and serve from the host walk
            self._breakers["slot_path"].record_failure()
            telemetry.REGISTRY.counter("serve.device_errors").inc()
            telemetry.event("serve.device_error", model=self.name,
                            error=str(e)[:200])
            return None
        return outs[0] if len(outs) == 1 else np.concatenate(outs, axis=1)

    def _device_slots_chunk(self, Xc: np.ndarray, stacked: Dict,
                            clock: Optional[telemetry.StageClock] = None,
                            ) -> np.ndarray:
        if clock is None:
            clock = telemetry.StageClock()
        n = Xc.shape[0]
        b = bucket_rows(n, self.max_batch_rows)
        t = time.perf_counter()
        Xd = self._stage32(Xc, b)
        clock.add("stage_copy", time.perf_counter() - t)
        arrays = {k: v for k, v in stacked.items()
                  if k not in ("min_features", "value")}

        def _device():
            with telemetry.MEMLEDGER.oom_guard(
                    "serve.dispatch.slot_path", model=self.name):
                FAULTS.inject("serve.dispatch.slot_path")
                t = time.perf_counter()
                out = _LEAF_JIT(arrays, Xd)
                clock.add("dispatch", time.perf_counter() - t)
                t = time.perf_counter()
                slots = np.asarray(jax.device_get(out))
                clock.add("d2h", time.perf_counter() - t)
                telemetry.REGISTRY.counter("serve.d2h_bytes").inc(
                    slots.nbytes)
                return FAULTS.inject("serve.d2h.slot_path", slots)

        return self._supervisors["slot_path"].call(_device)[:, :n]

    def _stage32(self, Xc: np.ndarray, b: int):
        """Pad `Xc` into a reused per-(bucket, width) f32 staging
        buffer and hand the device a COPY (`jnp.array` copies by
        default), so the buffer is reusable the moment this returns.
        f64 -> f32 saturates huge values to inf — the routing we want
        (same errstate rationale as booster._predict_raw_device); the
        padding rows stay 0.0 and are sliced away by the callers.  The
        lock covers concurrent `predict` callers sharing a bucket."""
        n = Xc.shape[0]
        key = (b, Xc.shape[1])
        with self._staging_lock:
            buf = self._staging.get(key)
            if buf is None:
                buf = np.empty((b, Xc.shape[1]), np.float32)
                self._staging[key] = buf
                # once per (bucket, width), NOT per call: the reused
                # buffer is the worst-case per-call device staging, so
                # it is the thing worth attributing (the per-call
                # device copy is transient and weakref churn on the
                # hot path buys nothing)
                self._ledger_staging.append(
                    telemetry.MEMLEDGER.register(
                        f"serve.{self.name}.staging", buf,
                        bucket=str(b), width=str(Xc.shape[1])))
            with np.errstate(over="ignore"):
                buf[:n] = Xc
            buf[n:] = 0.0
            if self.device is not None:
                return jnp.array(buf, device=self.device)
            return jnp.array(buf)

    def _convert(self, raw: np.ndarray) -> np.ndarray:
        """`objective_.convert_output`, bucket-padded: conversions are
        row-independent (sigmoid / per-row softmax / ...), so padding to
        the same power-of-two buckets keeps eager-op compiles bounded
        while producing bitwise the values `booster.predict` returns."""
        obj = self._booster.objective_
        n = raw.shape[0]
        outs = []
        for lo in range(0, n, self.max_batch_rows):
            chunk = raw[lo:lo + self.max_batch_rows]
            b = bucket_rows(chunk.shape[0], self.max_batch_rows)
            pad = np.zeros((b,) + chunk.shape[1:], chunk.dtype)
            pad[:chunk.shape[0]] = chunk
            conv = np.asarray(jax.device_get(
                obj.convert_output(jnp.asarray(pad))))
            outs.append(conv[:chunk.shape[0]])
        return outs[0] if len(outs) == 1 else np.concatenate(outs, axis=0)
