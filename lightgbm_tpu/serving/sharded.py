"""Sharded serving plane: replicate the export, stripe the traffic.

Data-parallel serving over the mesh runtime (``lightgbm_tpu/mesh/``):
the booster exports ONCE (`export_predict_arrays` is version-cached),
then each mesh device gets a pinned `ServingRuntime` replica holding
its own copy of the traversal planes + hi/lo leaf bit planes
(``mesh.collective.replicate`` spans).  Flushed row-buckets are striped
over the replicas by a least-outstanding-work scheduler whose
assignment is computed — deterministically — BEFORE dispatch: snapshot
the outstanding-rows vector, greedily give each ``max_batch_rows``
chunk to the least-loaded replica (ties break on the lowest replica
index), then dispatch the per-replica chunk lists concurrently.  Under
fixed scheduling (quiesced replicas) the same input always takes the
same stripes.

Every replica serves through the unchanged 4-rung fallback ladder
(compiled tiles included — each replica compiles and pins its own
plan), so each stripe is byte-identical to ``booster.predict`` no
matter which device ran it — and a wedged device degrades ONLY its replica (its
rungs fall back per call; the other replicas never see the error).

Telemetry: ``serve.replicas`` / ``serve.replica.<i>.outstanding``
gauges, ``serve.replica.<i>.rows`` + per-rung counters,
``serve.replica.<i>.latency`` histograms (percentiles feed the
`telemetry diff` sentinel), and the ``serving.sharded.stripe_
imbalance`` gauge (max/mean cumulative rows per replica; 1.0 =
perfectly balanced).

ref parity: the reference has no serving tier; this is the serving
analog of its data-parallel tree learner — rows partitioned over
workers, model replicated (data_parallel_tree_learner.cpp), inverted
for inference.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import numpy as np

import jax

from .. import telemetry
from ..analysis import make_lock
from ..utils.log import LightGBMError
from .runtime import DEFAULT_MAX_BATCH_ROWS, ServingRuntime

#: fallback-ladder rungs from best to most degraded — a striped call
#: reports the WORST rung any of its chunks used, so a single wedged
#: replica is visible on the merged trace (bounded sits above the exact
#: ladder: it is the fastest rung, and a stripe that fell off it must
#: win the "most degraded" fold over one that kept it)
_RUNG_ORDER = ("bounded", "compiled", "device_sum", "slot_path",
               "host_walk")


def resolve_shard_devices(n: int) -> List:
    """The device list for `serve_shard_devices=n` (0 = all visible).

    Fails loudly when the request overflows the machine — a silently
    smaller replica set would invalidate capacity planning."""
    devs = jax.devices()
    if n <= 0:
        return list(devs)
    if n > len(devs):
        raise LightGBMError(
            f"serve_shard_devices={n} exceeds visible devices "
            f"({len(devs)})")
    return list(devs[:n])


class ShardedServingRuntime:
    """One model served by per-device `ServingRuntime` replicas.

    Drop-in for `ServingRuntime` where the registry/batcher are
    concerned: same `predict/refresh/demote/warmup/device_bytes/stale`
    surface.  `device_bytes` is the TOTAL across replicas and
    `num_replicas` lets the registry scale its per-device
    `serve_vram_budget_mb` accordingly.
    """

    def __init__(self, booster, *,
                 devices: Optional[List] = None,
                 shard_devices: int = 0,
                 max_batch_rows: int = DEFAULT_MAX_BATCH_ROWS,
                 start_iteration: int = 0,
                 num_iteration: Optional[int] = None,
                 name: str = "default",
                 device_sum: str = "auto",
                 compiled: str = "auto",
                 precision: str = "exact",
                 quant_bits: int = 8,
                 tile_vmem_kb: float = 512.0,
                 dispatch_timeout_ms: float = 0.0,
                 breaker_backoff_s: float = 30.0,
                 breaker_backoff_max_s: float = 600.0):
        if devices is None:
            devices = resolve_shard_devices(shard_devices)
        if not devices:
            raise LightGBMError("sharded serving needs >= 1 device")
        self._booster = booster
        self.name = name
        self.max_batch_rows = max(int(max_batch_rows), 1)
        self.devices = list(devices)
        # replica 0 exports (and caches) the arrays; the rest replicate
        # that cached export onto their own device — each replica builds
        # and pins its OWN compiled tile plan (the planes live on the
        # replica's device, so a shared plan would defeat the striping)
        self._replicas = [
            ServingRuntime(booster, max_batch_rows=self.max_batch_rows,
                           start_iteration=start_iteration,
                           num_iteration=num_iteration,
                           name=f"{name}.r{i}", device_sum=device_sum,
                           compiled=compiled, precision=precision,
                           quant_bits=quant_bits,
                           tile_vmem_kb=tile_vmem_kb,
                           device=dev,
                           dispatch_timeout_ms=dispatch_timeout_ms,
                           breaker_backoff_s=breaker_backoff_s,
                           breaker_backoff_max_s=breaker_backoff_max_s)
            for i, dev in enumerate(self.devices)]
        self._sched_lock = make_lock("serving.sharded._sched_lock")
        self._outstanding = [0] * len(self._replicas)  # rows in flight; guarded-by: _sched_lock
        self._routed = [0] * len(self._replicas)  # rows, cumulative; guarded-by: _sched_lock
        telemetry.REGISTRY.gauge("serve.replicas").set(
            len(self._replicas))
        self._set_balance_gauges()

    # --------------------------------------------------------- passthrough
    @property
    def num_replicas(self) -> int:
        return len(self._replicas)

    @property
    def replicas(self) -> List[ServingRuntime]:
        return list(self._replicas)

    @property
    def num_class(self) -> int:
        return self._replicas[0].num_class

    @property
    def demoted(self) -> bool:
        return all(r.demoted for r in self._replicas)

    @property
    def device_sum_active(self) -> bool:
        return self._replicas[0].device_sum_active

    @property
    def compiled_active(self) -> bool:
        return self._replicas[0].compiled_active

    def rung_status(self) -> Dict:
        """A rung is live only when EVERY replica serves it; `disabled`
        merges the replicas' causes (first replica to report a rung
        wins — the replicas share one export and one compiler)."""
        per = [r.rung_status() for r in self._replicas]
        out = {k: all(p[k] for p in per)
               for k in ("bounded", "compiled", "device_sum")}
        off: Dict = {}
        for p in per:
            for rung, why in p["disabled"].items():
                off.setdefault(rung, why)
        out["disabled"] = off
        return out

    @property
    def precision(self) -> str:
        return self._replicas[0].precision

    @property
    def bounded_active(self) -> bool:
        # the tier is "active" only when EVERY stripe can serve it — a
        # single degraded replica already breaks the latency story the
        # bounded tier exists for
        return all(r.bounded_active for r in self._replicas)

    @property
    def bounded_bound(self):
        return self._replicas[0].bounded_bound

    @property
    def bounded_measured_error(self):
        # the published contract covers every stripe: report the WORST
        # probe measurement across replicas
        vals = [r.bounded_measured_error for r in self._replicas]
        vals = [v for v in vals if v is not None]
        return max(vals) if vals else None

    @property
    def booster(self):
        """The served booster (same accessor as `ServingRuntime`)."""
        return self._booster

    def num_feature(self) -> int:
        return self._replicas[0].num_feature()

    def buckets(self) -> List[int]:
        return self._replicas[0].buckets()

    def stale(self) -> bool:
        return self._replicas[0].stale()

    def refresh(self) -> None:
        for r in self._replicas:
            r.refresh()
        telemetry.REGISTRY.gauge("serve.replicas").set(
            len(self._replicas))

    def demote(self) -> int:
        return sum(r.demote() for r in self._replicas)

    def device_bytes(self) -> int:
        """TOTAL export bytes across every replica (per-device usage is
        this / num_replicas — the copies are byte-identical)."""
        return sum(r.device_bytes() for r in self._replicas)

    def _ledger_release(self) -> None:
        """Drop every replica's memory-ledger handles (registry close
        path — replicas register under `serve.<name>.r<i>.*`)."""
        for r in self._replicas:
            r._ledger_release()

    def warmup(self) -> int:
        """Warm every replica's bucket ladder on its own device (the
        jit caches are keyed per device, so each replica pays its own
        compiles exactly once, at load)."""
        return sum(r.warmup() for r in self._replicas)

    # ------------------------------------------------------------ striping
    def _assign(self, chunks: List) -> List[int]:
        """Deterministic least-outstanding-work assignment, computed
        before any dispatch: greedy over a snapshot of the outstanding
        vector, ties to the lowest replica index."""
        with self._sched_lock:
            load = list(self._outstanding)
            assign = []
            for lo, hi in chunks:
                i = min(range(len(load)), key=lambda r: (load[r], r))
                assign.append(i)
                load[i] += hi - lo
                self._outstanding[i] += hi - lo
                self._routed[i] += hi - lo
            for i in range(len(self._replicas)):
                telemetry.REGISTRY.gauge(
                    f"serve.replica.{i}.outstanding").set(
                        self._outstanding[i])
        return assign

    def _set_balance_gauges(self) -> None:
        with self._sched_lock:
            routed = list(self._routed)
        total = sum(routed)
        mean = total / max(len(routed), 1)
        imb = (max(routed) / mean) if mean > 0 else 1.0
        telemetry.REGISTRY.gauge(
            "serving.sharded.stripe_imbalance").set(round(imb, 4))

    def _run_replica(self, i: int, X: np.ndarray, my_chunks: List,
                     want_raw: bool, out_parts: dict, errors: list,
                     rungs: list) -> None:
        rep = self._replicas[i]
        lat = telemetry.REGISTRY.histogram(f"serve.replica.{i}.latency")
        rows_c = telemetry.REGISTRY.counter(f"serve.replica.{i}.rows")
        for lo, hi in my_chunks:
            clock = telemetry.StageClock()
            t0 = time.perf_counter()
            try:
                out_parts[lo] = rep.predict(X[lo:hi], raw_score=want_raw,
                                            clock=clock)
            except Exception as e:   # replica-local: others keep serving
                errors.append(e)
            finally:
                lat.observe(time.perf_counter() - t0)
                rows_c.inc(hi - lo)
                rung = clock.rung or "host_walk"
                telemetry.REGISTRY.counter(
                    f"serve.replica.{i}.{rung}").inc()
                rungs.append((rung, clock))
                with self._sched_lock:
                    self._outstanding[i] -= hi - lo
                    telemetry.REGISTRY.gauge(
                        f"serve.replica.{i}.outstanding").set(
                            self._outstanding[i])

    def predict(self, X, raw_score: bool = False,
                clock: Optional[telemetry.StageClock] = None) -> np.ndarray:
        """Striped prediction, byte-identical to the single-device
        runtime: chunk boundaries fall at `max_batch_rows` exactly like
        `ServingRuntime`'s internal chunking, each chunk runs the full
        ladder on its replica, results reassemble in row order."""
        if not (isinstance(X, np.ndarray) and X.dtype == np.float64
                and X.flags["C_CONTIGUOUS"]):
            X = np.ascontiguousarray(np.asarray(X, dtype=np.float64))
        if X.ndim == 1:
            X = X.reshape(1, -1)
        n = X.shape[0]
        B = self.max_batch_rows
        chunks = [(lo, min(lo + B, n)) for lo in range(0, n, B)] \
            or [(0, 0)]
        assign = self._assign(chunks)
        by_rep: dict = {}
        for (lo, hi), i in zip(chunks, assign):
            by_rep.setdefault(i, []).append((lo, hi))
        out_parts: dict = {}
        errors: list = []
        rungs: list = []
        with telemetry.span("serve.sharded.predict", model=self.name,
                            rows=n, replicas=len(by_rep)):
            if len(by_rep) == 1:
                (i, my_chunks), = by_rep.items()
                self._run_replica(i, X, my_chunks, raw_score, out_parts,
                                  errors, rungs)
            else:
                threads = [
                    threading.Thread(
                        target=self._run_replica,
                        args=(i, X, my_chunks, raw_score, out_parts,
                              errors, rungs),
                        name=f"lgbm-tpu-serve-stripe-r{i}", daemon=True)
                    for i, my_chunks in sorted(by_rep.items())]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
        self._set_balance_gauges()
        if errors:
            raise errors[0]
        if clock is not None and rungs:
            # fold stripe stage deltas into the caller's clock (safe:
            # every stripe thread joined above) and surface the most
            # degraded rung any stripe used
            for _, cc in rungs:
                for stage, secs in cc.stages.items():
                    clock.add(stage, secs)
            clock.rung = max(
                (r for r, _ in rungs),
                key=lambda r: _RUNG_ORDER.index(r)
                if r in _RUNG_ORDER else len(_RUNG_ORDER))
        parts = [out_parts[lo] for lo, _ in chunks]
        return parts[0] if len(parts) == 1 \
            else np.concatenate(parts, axis=0)
