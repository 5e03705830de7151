"""Multi-model registry: warm-up-on-load, atomic hot-swap, budgeting.

`load()` builds the full serving stack for a model — export, optional
all-bucket warm-up, micro-batcher — **before** the name becomes
visible, then swaps it in under the registry lock.  A hot-swap
therefore never serves a cold model: readers resolve either the whole
old entry or the whole new one, and the old entry's batcher is closed
only after the swap (in-flight requests on it complete).

Co-residency budgeting (`serve_vram_budget_mb`, 0 = unlimited): each
entry accounts its export's device bytes (stacked traversal planes +
leaf-value bit planes, `ServingRuntime.device_bytes`).  A load that
would exceed the budget first DEMOTES least-recently-used entries
(their device arrays move to host copies — they keep serving
bit-identical results, re-uploading per call, until a `refresh()`
re-promotes them) and, if still over, is rejected with a clear
`LightGBMError` while every already-loaded model keeps serving —
budget pressure degrades throughput, never availability or
correctness.

Staleness: `status()` reports entries whose booster mutated since
their last export (`ServingRuntime.stale`) — surfaced in `/healthz`
and the `serve.stale` gauge; with `serve_auto_refresh` the first
predict that notices the staleness kicks a BACKGROUND re-export (the
stale export keeps serving until the refreshed one swaps in) — the
request thread never pays the export, so p99 stays flat through a
refresh (tests/test_fleet.py pins this).
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Optional, Union

from .. import telemetry
from ..analysis import enable_lock_witness, make_lock
from ..resilience import FAULTS
from ..utils import log
from ..utils.config import Config
from ..utils.log import LightGBMError
from .batcher import MicroBatcher, ServingClosedError
from .runtime import ServingRuntime
from .sharded import ShardedServingRuntime

#: bound on back-to-back hot-swap retries in `predict` — each retry
#: requires ANOTHER swap to have landed mid-dispatch, so a healthy
#: registry never comes close; the bound turns a pathological
#: swap-storm into a clean error instead of an unbounded loop
_SWAP_RETRIES = 8

# process-wide count of build-then-swap loads currently in flight,
# published as the `serve.swap_windows` gauge.  The batcher reads the
# gauge on every shed to attribute it (`serve.shed.swap_window`), and
# the soak harness uses it to prove hot-swap windows never shed
# silently — a plain gauge, so the split is observable cross-module
# without an import cycle.
_swap_window_lock = threading.Lock()
_swap_window_count = 0


def _note_swap_window(delta: int) -> None:
    global _swap_window_count
    with _swap_window_lock:
        _swap_window_count = max(0, _swap_window_count + delta)
        count = _swap_window_count
    telemetry.REGISTRY.gauge("serve.swap_windows").set(count)


@contextlib.contextmanager
def _swap_window():
    """Marks one build-then-swap window (runtime build, warmup, swap):
    the phase whose device/CPU contention makes concurrent sheds
    swap-cost rather than steady-state load."""
    _note_swap_window(1)
    try:
        yield
    finally:
        _note_swap_window(-1)


class ServingModel:
    """One registered model: its runtime + micro-batcher."""

    def __init__(self, name: str, runtime: ServingRuntime,
                 batcher: MicroBatcher, auto_refresh: bool = False):
        self.name = name
        self.runtime = runtime
        self.batcher = batcher
        self.auto_refresh = auto_refresh
        self.last_used = time.monotonic()
        self._refresh_kick = make_lock("serving.registry._refresh_kick")
        self._refresh_thread: Optional[threading.Thread] = None  # guarded-by: _refresh_kick

    def predict(self, X, raw_score: bool = False,
                timeout: Optional[float] = None,
                trace: Optional[telemetry.RequestTrace] = None):
        self.last_used = time.monotonic()
        if self.auto_refresh and self.runtime.stale():
            # OFF the request thread: a re-export costs device uploads +
            # a parity probe, which must never land in a request's p99.
            # The stale export keeps serving (bit-exact for the model
            # version it captured) until the background refresh() swaps
            # the new export in atomically under the runtime's
            # refresh lock.
            self._kick_refresh()
        return self.batcher.predict(X, raw_score=raw_score,
                                    timeout=timeout, trace=trace)

    def _kick_refresh(self) -> None:
        """Start (at most) one background refresh; callers never wait."""
        with self._refresh_kick:
            t = self._refresh_thread
            if t is not None and t.is_alive():
                return
            telemetry.REGISTRY.counter("serve.auto_refresh").inc()
            t = threading.Thread(
                target=self._background_refresh,
                name=f"lgbm-tpu-refresh-{self.name}", daemon=True)
            self._refresh_thread = t
            t.start()

    def _background_refresh(self) -> None:
        try:
            self.runtime.refresh()
        except Exception as e:  # a failed refresh must not kill serving
            telemetry.REGISTRY.counter("serve.auto_refresh_errors").inc()
            telemetry.event("serve.auto_refresh_error", model=self.name,
                            error=str(e)[:200])

    def close(self) -> None:
        self.batcher.close()
        t = self._refresh_thread
        if t is not None and t.is_alive():
            t.join(timeout=30.0)
        # un-attribute the runtime's buffers in the memory ledger — an
        # unloaded model must stop counting against serve.<name>.*
        rel = getattr(self.runtime, "_ledger_release", None)
        if rel is not None:
            rel()


class ModelRegistry:
    """Thread-safe name -> ServingModel map (serving/ tentpole layer 3).

    `params` takes the serving knobs (`serve_max_batch_rows`,
    `serve_max_wait_ms`, `serve_queue_depth`, `serve_deadline_ms`,
    `serve_warmup`, `serve_device_sum`, `serve_vram_budget_mb`,
    `serve_auto_refresh`, plus the `serve_trace*` flight-recorder knobs
    — aliases resolve through utils/config.py like every other param).

    Constructing a registry configures the process-global
    `telemetry.SERVE_RECORDER` from its `serve_trace*` params (the
    recorder is a process singleton like REGISTRY/TRACER, so
    `/debug/requests` and bench can read it without plumbing; the last
    registry constructed wins, which is the one serving).
    """

    def __init__(self, params: Optional[dict] = None):
        self._config = Config(dict(params or {}))
        self._lock = make_lock("serving.registry._lock")
        # serializes the budget decision (_admit) WITH the swap it
        # admits: a demotion decided from a pre-swap LRU snapshot could
        # otherwise demote the entry a concurrent load() just made live
        self._swap_lock = make_lock("serving.registry._swap_lock")
        self._models: Dict[str, ServingModel] = {}  # guarded-by: _lock
        # per-model traffic sampler hooks (fleet/shadow.py TrafficSampler
        # and fleet/drift.py DriftMonitor attach here): each is called
        # with every request's row block, outside the serving data path
        # — sampling never touches the bytes served
        self._samplers: Dict[str, List[object]] = {}  # guarded-by: _lock
        # load observers (soak byte-oracle, lineage tooling): each is
        # called with (name, booster, entry) after a load goes live —
        # the only way an external checker can hold a reference to
        # every booster VERSION a name has served, not just the latest
        self._load_listeners: List[object] = []  # guarded-by: _lock
        if self._config.debug_locks:
            # runtime half of graft-race R006 — see booster.py for the
            # matching training-side switch; sticky process-global
            enable_lock_witness(True)
            log.warning("debug_locks=true: lock-order witness armed "
                        "for this process")
        cfg = self._config
        telemetry.SERVE_RECORDER.configure(
            enabled=cfg.serve_trace, capacity=cfg.serve_trace_ring,
            slow_ms=cfg.serve_trace_slow_ms,
            sample_every=cfg.serve_trace_sample)
        # resilience plane: `fault_spec` arms the process-global fault
        # registry (chaos tests / CI chaos smoke; see resilience/faults.py
        # for the grammar) — the $LGBM_FAULTS env var arms at import
        if cfg.fault_spec:
            FAULTS.arm(cfg.fault_spec)

    # -------------------------------------------------------------- load
    def load(self, name: str, model: Union[str, object], *,
             warmup: Optional[bool] = None,
             shard_devices: Optional[int] = None) -> ServingModel:
        """Register `model` (a Booster or a model-file path) under
        `name`, warmed up, replacing any previous holder atomically.
        Raises `LightGBMError` without touching the registry when the
        export would not fit `serve_vram_budget_mb` even after LRU
        demotion of the other entries.

        `shard_devices` overrides the config's `serve_shard_devices`
        for THIS load only — the fleet replica autoscaler resizes a
        model by reloading it through this same build-then-swap path,
        so a resize is just another hot-swap: the old replica set keeps
        serving until the new one is warm.
        """
        from ..booster import Booster
        booster = model if isinstance(model, Booster) \
            else Booster(model_file=str(model))
        cfg = self._config
        if shard_devices is None:
            shard_devices = int(cfg.serve_shard_devices)
        with _swap_window(), telemetry.span("serve.load", model=name):
            if shard_devices != 1:
                # replicated sharded plane: one pinned runtime per mesh
                # device, striped by least-outstanding-work (sharded.py)
                runtime = ShardedServingRuntime(
                    booster, shard_devices=shard_devices,
                    max_batch_rows=cfg.serve_max_batch_rows,
                    name=name, device_sum=cfg.serve_device_sum,
                    compiled=cfg.serve_compiled,
                    precision=cfg.serve_precision,
                    quant_bits=cfg.serve_quant_bits,
                    tile_vmem_kb=cfg.serve_tile_vmem_kb,
                    dispatch_timeout_ms=cfg.serve_dispatch_timeout_ms,
                    breaker_backoff_s=cfg.serve_breaker_backoff_s,
                    breaker_backoff_max_s=cfg.serve_breaker_backoff_max_s)
            else:
                runtime = ServingRuntime(
                    booster, max_batch_rows=cfg.serve_max_batch_rows,
                    name=name, device_sum=cfg.serve_device_sum,
                    compiled=cfg.serve_compiled,
                    precision=cfg.serve_precision,
                    quant_bits=cfg.serve_quant_bits,
                    tile_vmem_kb=cfg.serve_tile_vmem_kb,
                    dispatch_timeout_ms=cfg.serve_dispatch_timeout_ms,
                    breaker_backoff_s=cfg.serve_breaker_backoff_s,
                    breaker_backoff_max_s=cfg.serve_breaker_backoff_max_s)
            # the swap lock spans admit -> swap: the LRU demotion
            # decision and the swap it admits are one atomic step, so a
            # concurrent load can neither demote this entry the instant
            # it becomes live nor admit against a stale snapshot
            with self._swap_lock:
                self._admit(name, runtime)
                if cfg.serve_warmup if warmup is None else warmup:
                    runtime.warmup()
                batcher = MicroBatcher(
                    runtime, max_batch_rows=cfg.serve_max_batch_rows,
                    max_wait_ms=cfg.serve_max_wait_ms,
                    queue_depth=cfg.serve_queue_depth,
                    deadline_ms=cfg.serve_deadline_ms)
                entry = ServingModel(name, runtime, batcher,
                                     auto_refresh=cfg.serve_auto_refresh)
                with self._lock:
                    old = self._models.get(name)
                    self._models[name] = entry
                    telemetry.REGISTRY.gauge("serve.models").set(
                        len(self._models))
        telemetry.REGISTRY.counter("serve.model_loads").inc()
        # lineage: record the swap the serving plane actually performed
        # (the daemon records the DECISION; this is the apply).  Never
        # let accounting fail a completed load.
        try:
            telemetry.LEDGER.record(
                "registry.swap", model=name,
                fingerprint=booster.model_fingerprint(),
                replicas=getattr(runtime, "num_replicas", 1),
                replaced=old is not None)
        except Exception:
            pass
        self._update_vram_gauge()
        # notify load observers BEFORE the predecessor drains: a
        # byte-consistency oracle must learn the successor is live while
        # in-flight requests on the old version can still complete, so
        # both versions' windows overlap the swap instant.  Observer
        # exceptions never fail a completed load.
        with self._lock:
            listeners = list(self._load_listeners)
        for hook in listeners:
            try:
                hook(name, booster, entry)
            except Exception:
                telemetry.REGISTRY.counter("serve.load_listener_errors").inc()
        if old is not None:
            old.close()
        return entry

    def _admit(self, name: str, runtime: ServingRuntime) -> None:
        """Budget gate for a new export: demote LRU entries until the
        newcomer fits, else reject it — loaded models keep serving
        either way.  Caller holds `_swap_lock`, so the decision is
        taken against the registry state the admitted swap will join."""
        budget = int(self._config.serve_vram_budget_mb * (1 << 20))
        if budget <= 0:
            return
        # the budget is PER DEVICE; a sharded runtime spreads its
        # byte-identical copies over num_replicas devices, so the
        # process-wide ceiling scales with the replica count
        budget *= getattr(runtime, "num_replicas", 1)
        need = runtime.device_bytes()
        with self._lock:
            others = [e for n, e in self._models.items() if n != name]
        used = sum(e.runtime.device_bytes() for e in others)
        if used + need > budget:
            for e in sorted(others, key=lambda e: e.last_used):
                if used + need <= budget:
                    break
                freed = e.runtime.demote()
                if freed:
                    telemetry.event("serve.demote", model=e.name,
                                    freed_bytes=freed)
                    telemetry.LEDGER.record("registry.demote",
                                            model=e.name,
                                            freed_bytes=freed)
                    used -= freed
        self._update_vram_gauge()
        # declared-vs-measured check at the swap boundary: a normal
        # admit (possibly after demotions) lands under the ceiling, so
        # a counted violation here means the accounting drifted or the
        # demotion math stopped freeing what it claims
        telemetry.MEMLEDGER.audit(
            "serve_vram_budget_mb", budget, used + need, model=name,
            site="registry.admit", need_bytes=need, used_bytes=used,
            replicas=getattr(runtime, "num_replicas", 1))
        if used + need > budget:
            raise LightGBMError(
                f"serving model {name!r} needs {need} device bytes but "
                f"only {max(budget - used, 0)} of the "
                f"serve_vram_budget_mb={self._config.serve_vram_budget_mb:g}"
                f" budget remain ({used} in use); raise the budget or "
                f"unload a model — already-loaded models keep serving")

    def _update_vram_gauge(self) -> None:
        with self._lock:
            total = sum(e.runtime.device_bytes()
                        for e in self._models.values())
        telemetry.REGISTRY.gauge("serve.vram_bytes").set(total)

    def unload(self, name: str) -> None:
        with self._lock:
            entry = self._models.pop(name, None)
            telemetry.REGISTRY.gauge("serve.models").set(
                len(self._models))
        if entry is not None:
            entry.close()
        self._update_vram_gauge()

    # ------------------------------------------------------------ lookup
    def get(self, name: str = "default") -> ServingModel:
        with self._lock:
            entry = self._models.get(name)
        if entry is None:
            raise LightGBMError(f"no model {name!r} loaded "
                                f"(loaded: {self.names() or 'none'})")
        return entry

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._models)

    def status(self) -> Dict:
        """Registry health snapshot (the `/healthz` payload body):
        model names, entries whose booster mutated since export
        (`stale`), demoted entries, per-entry device bytes, per-entry
        rung status (`rungs`: live rungs + why the others are off), and
        — once any request has completed — all-rung server-side latency
        percentiles from the `serve.stage.e2e` histograms
        (`latency_ms`: count/p50/p90/p99/p999).  Also refreshes the
        `serve.stale` gauge."""
        with self._lock:
            entries = dict(self._models)
        stale = sorted(n for n, e in entries.items()
                       if e.runtime.stale())
        telemetry.REGISTRY.gauge("serve.stale").set(len(stale))
        out = {"models": sorted(entries),
               "stale": stale,
               "demoted": sorted(n for n, e in entries.items()
                                 if e.runtime.demoted),
               "device_bytes": {n: e.runtime.device_bytes()
                                for n, e in sorted(entries.items())},
               # which device rungs each model serves from and, for a
               # rung that is off, why (cause + the compiler's message
               # or the probe's numbers)
               "rungs": {n: e.runtime.rung_status()
                         for n, e in sorted(entries.items())}}
        # bounded precision tier: publish each bounded-tier model's
        # contract (the worst-case bound) next to what the probe actually
        # measured, so /healthz is where operators audit the promise
        bounded = {}
        for n, e in sorted(entries.items()):
            rt = e.runtime
            if getattr(rt, "precision", "exact") != "bounded":
                continue
            bounded[n] = {
                "active": bool(rt.bounded_active),
                "bound": rt.bounded_bound,
                "measured_max_abs_error": rt.bounded_measured_error,
            }
        if bounded:
            out["bounded"] = bounded
        lat = telemetry.e2e_latency_summary()
        if lat is not None:
            out["latency_ms"] = lat
        return out

    # --------------------------------------------------- traffic sampling
    def attach_sampler(self, name: str, sampler) -> None:
        """Attach a per-model traffic sampler (any callable taking the
        request's row block).  The fleet shadow gate and the drift
        monitor sample live traffic this way — several samplers may
        coexist per model; sampling happens before dispatch on a COPY-
        free read of X, and a sampler exception never fails a request."""
        with self._lock:
            self._samplers.setdefault(name, []).append(sampler)

    def add_load_listener(self, hook) -> None:
        """Register a load observer: `hook(name, booster, entry)` runs
        after every successful `load` goes live (and before the
        replaced entry drains).  The soak harness's byte-consistency
        oracle attaches here to track every live model VERSION."""
        with self._lock:
            self._load_listeners.append(hook)

    def remove_load_listener(self, hook=None) -> None:
        """Detach one observer (by identity) or, with `hook=None`, all."""
        with self._lock:
            if hook is None:
                self._load_listeners.clear()
            else:
                self._load_listeners = [
                    h for h in self._load_listeners if h is not hook]

    def detach_sampler(self, name: str, sampler=None) -> None:
        """Detach one sampler (by identity) or, with `sampler=None`,
        every sampler registered for the model."""
        with self._lock:
            if sampler is None:
                self._samplers.pop(name, None)
                return
            hooks = self._samplers.get(name)
            if hooks is None:
                return
            self._samplers[name] = [s for s in hooks if s is not sampler]
            if not self._samplers[name]:
                self._samplers.pop(name, None)

    def predict(self, X, model: str = "default", raw_score: bool = False,
                timeout: Optional[float] = None,
                trace: Optional[telemetry.RequestTrace] = None):
        with self._lock:
            samplers = list(self._samplers.get(model, ()))
        for sampler in samplers:
            try:
                sampler(X)
            except Exception:  # sampling is best-effort observability
                telemetry.REGISTRY.counter("fleet.sampler_errors").inc()
        for _ in range(_SWAP_RETRIES):
            entry = self.get(model)
            try:
                return entry.predict(X, raw_score=raw_score,
                                     timeout=timeout, trace=trace)
            except ServingClosedError:
                # a hot-swap closed this entry's batcher between the
                # name lookup and the dispatch — the successor entry is
                # already live, so the swap stays invisible to callers.
                # Re-raise when the name is gone or unchanged (a real
                # close, not a swap); each retry requires another swap
                # landed mid-dispatch, and the bound above turns a
                # pathological swap-storm into a clean error.
                with self._lock:
                    cur = self._models.get(model)
                if cur is None or cur is entry:
                    raise
        telemetry.REGISTRY.counter("serve.swap_retry_exhausted").inc()
        # per-cause attribution next to the aggregate: `swap_window`
        # when a build-then-swap is STILL in flight (the storm is live —
        # a retry after backoff will land), `swap_storm` when the churn
        # already settled (the caller raced a burst that is over)
        cause = "swap_window" \
            if telemetry.REGISTRY.gauge("serve.swap_windows").value > 0 \
            else "swap_storm"
        telemetry.REGISTRY.counter("serve.swap_retry_exhausted",
                                   cause=cause).inc()
        raise ServingClosedError(
            f"model {model!r} was hot-swapped {_SWAP_RETRIES} times "
            "mid-dispatch; giving up — retry the request")

    # ------------------------------------------------------------- close
    def close(self) -> None:
        with self._lock:
            entries = list(self._models.values())
            self._models.clear()
            telemetry.REGISTRY.gauge("serve.models").set(0)
        for e in entries:
            e.close()
        telemetry.REGISTRY.gauge("serve.vram_bytes").set(0)
