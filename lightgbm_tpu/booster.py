"""Booster — the user-facing training/prediction handle.

TPU-native re-design of the reference's GBDT core + Booster wrapper
(ref: src/boosting/gbdt.cpp `GBDT::{Init,TrainOneIter,UpdateScore}`;
src/boosting/gbdt_model_text.cpp `GBDT::SaveModelToString` /
`LoadModelFromString`; python-package/lightgbm/basic.py `Booster`;
src/c_api.cpp `Booster` wrapper).

Architecture: the host Python object owns (a) the device-resident training
state — feature-major bin matrix, scores, per-feature metadata — and (b) the
host-side model (list of `Tree`).  One boosting iteration is:
grad/hess (jit) → grow_tree (single jitted XLA program) → tiny device→host
sync of the flat tree → jitted score updates for train + valid sets.  This
mirrors the reference CUDA learner's design point: gradients, bins and
partitions never leave the device; only the finished tree structure does
(ref: cuda_single_gpu_tree_learner.cpp).
"""
from __future__ import annotations

import copy
import functools
import hashlib
import io
import json
import time
from collections import deque
from typing import (Any, Callable, Dict, List, NamedTuple, Optional, Tuple,
                    Union)

import jax
import jax.numpy as jnp
import numpy as np

from . import telemetry
from .basic import Dataset, _to_2d_float
from .metrics import Metric, create_metrics
from .objectives import ObjectiveFunction, create_objective
from .ops.grow import DeviceTree, GrowerSpec, make_grower
from .ops.leaf_rows import leaf_rows, pass_serves
from .ops.predict import traverse_bins
from .tree import K_CATEGORICAL_MASK, Tree
from .utils import log
from .utils.binning import BIN_TYPE_CATEGORICAL
from .utils.config import Config
from .utils.log import LightGBMError

__all__ = ["Booster"]


class _PendingChunk(NamedTuple):
    """A dispatched-but-not-harvested fused chunk (pipelined training).

    Holds the DEVICE-side futures JAX async dispatch returned: the
    stacked trees and the per-iteration score snapshots.  The score
    carries themselves are NOT here — `_dispatch_chunk` rebinds
    `_train_score`/`_valid_scores` to the chunk's outputs immediately, so
    the next chunk can be enqueued before this one is harvested."""
    spec: Any            # BulkSpec the chunk was dispatched with
    stacked: Any         # stacked DeviceTree pytree (device)
    t_iter: Any          # [C, ...] per-iter train scores (device; [C, 0] off)
    v_iter: Tuple        # per-valid [C, ...] per-iter scores (device)
    it0: int             # first iteration index of the chunk
    dispatch_t: float    # perf_counter right after dispatch returned


def _placed(what: str, make: Callable[[], Any]) -> Any:
    """`make()`, one upload of training state, under a `setup.place` span
    (attr `what`).  A recording span waits for the arrays, so it holds the
    transfer and not only its dispatch."""
    with telemetry.summed_span("setup.place", what=what) as span:
        out = make()
        if span is not telemetry.NOOP:
            jax.block_until_ready(out)
    return out


class _DeviceData:
    """Device-resident view of a constructed Dataset."""

    def __init__(self, ds: Dataset, for_train: bool = True):
        ds.construct()
        self._ds = ds
        self.num_data, self.num_feature = ds._num_data, ds._num_feature
        # EFB: the grower trains on the bundled [G, N] matrix; the original
        # [F, N] stays for tree traversal.  Valid sets are only traversed,
        # so their bundled matrix is neither built nor uploaded.  A
        # sparse-EFB training set has NO dense [N, F] matrix at all —
        # `bins_fm` materializes lazily if a traversal path (DART drop,
        # per-tree valid scoring on train bins) actually needs it.
        self.efb = getattr(ds, "efb", None)
        # external-memory: the spilled shard store replaces the in-host
        # matrices — bins_fm/bundle_fm assemble lazily by STREAMING shards
        # to the device (datastore/assemble.py), not via a full host copy
        self._store = getattr(ds, "datastore", None)
        # one accounting object for every prefetcher this dataset spawns
        # (bins + bundle assembly, sharded placement): hit/stall totals
        # and the residency watermark accumulate per RUN, not per pass
        self._pf_stats = None
        if self._store is not None:
            from .datastore.prefetch import PrefetchRunStats
            self._pf_stats = PrefetchRunStats()
        self._for_train = for_train
        # uploaded at first use (`bins_fm`): a sharded learner places the
        # host matrix on its mesh (`bins_host`), and the whole of it
        # never passes through one device
        self._bins_fm = None
        # where the per-row arrays go: the learner's row sharding, set by
        # the booster before it first reads them (None: the default
        # device)
        self.row_sharding = None
        # raw values retained for linear-tree leaf fits / scoring
        self.raw_ref = ds.data if ds.data is not None else None
        self._raw2d: Optional[np.ndarray] = None
        self._bundle_fm = None
        if self.efb is not None and for_train and self._store is None:
            bd = ds.bundle_data
            if bd is None:  # e.g. train continuation on a referenced Dataset
                if ds.bin_data is not None:
                    from .utils.efb import build_bundled
                    bd = ds.bundle_data = build_bundled(
                        np.asarray(ds.bin_data), self.efb)
                else:
                    from .utils.efb import build_bundled_sparse
                    bd = ds.bundle_data = build_bundled_sparse(
                        ds.sparse_binned, self.efb, ds.bin_mappers)
            self._bundle_fm = _placed("bundle", lambda: jnp.asarray(
                np.ascontiguousarray(np.asarray(bd).T)))
        mappers = ds.bin_mappers
        self.feat_nb = jnp.asarray(
            np.array([m.num_bin for m in mappers], dtype=np.int32))
        # host copy: static inputs (the histogram kernel's lane plan) are
        # derived from it without a device read
        self.num_bins = tuple(int(m.num_bin) for m in mappers)
        self.feat_missing = jnp.asarray(
            np.array([m.missing_type for m in mappers], dtype=np.int32))
        self.feat_default = jnp.asarray(
            np.array([m.default_bin for m in mappers], dtype=np.int32))
        self.base_allowed = np.array(
            [not m.is_trivial for m in mappers], dtype=bool)
        # one device copy up front: per-iteration/per-chunk consumers
        # (`_feature_mask`, `_run_chunk`) must not pay a fresh H2D
        # transfer each call (graft-lint R001 churn)
        self.base_allowed_dev = jnp.asarray(self.base_allowed)
        # host + device copies: host-side predicates (`has_cat`) read
        # the np copy instead of syncing the device array back
        # (graft-lint R001)
        self.is_cat_np = np.array(
            [m.bin_type == BIN_TYPE_CATEGORICAL for m in mappers],
            dtype=bool)
        self.is_cat = jnp.asarray(self.is_cat_np)
        self.max_bin = max(int(m.num_bin) for m in mappers)
        self._label = self._weight = None
        self.init_score = ds.get_init_score()
        self.query_boundaries = ds._query_boundaries

    @property
    def store(self):
        """The spilled shard store backing this dataset (None when
        in-memory) — the streamed mesh placement reads it directly."""
        return self._store

    @property
    def datastore_pending(self) -> bool:
        """True while a spilled dataset's training matrix has not been
        assembled on device yet — the booster defers that first assembly
        into the train.chunk span so the per-shard spans nest there."""
        needs_bundle = self.efb is not None and self._for_train
        pending = self._bundle_fm is None if needs_bundle \
            else self._bins_fm is None
        return self._store is not None and pending

    def _assemble_from_store(self, payload: str):
        from .datastore.assemble import assemble_feature_major
        depth = Config(self._ds.params or {}).datastore_prefetch
        return assemble_feature_major(self._store, payload=payload,
                                      prefetch_depth=depth,
                                      run_stats=self._pf_stats)

    def _put_rows(self, what: str,
                  rows: Optional[np.ndarray]) -> Optional[jax.Array]:
        """A per-row host array as f32 on the device(s) the training rows
        are on, a `setup.place` span of the training set; None stays
        None."""
        if rows is None:
            return None
        rows = np.asarray(rows, np.float32)
        if self.row_sharding is None:
            return self._placed(what, lambda: jnp.asarray(rows))
        return self._placed(
            what, lambda: jax.device_put(rows, self.row_sharding))

    def _placed(self, what: str, make: Callable[[], Any]) -> Any:
        """`make()`, an upload of this data set's rows: a `setup.place`
        span where it is the training set."""
        return _placed(what, make) if self._for_train else make()

    @property
    def label(self):
        """[N] f32 on the device, uploaded at first use; None without."""
        if self._label is None:
            self._label = self._put_rows("label", self._ds.get_label())
        return self._label

    @property
    def weight(self):
        if self._weight is None:
            self._weight = self._put_rows("weight", self._ds.get_weight())
        return self._weight

    def bins_host(self) -> Optional[np.ndarray]:
        """The dense bins feature-major [F, N] on the host; None where the
        data set keeps none (sparse EFB, spilled to a shard store)."""
        if self._ds.bin_data is None:
            return None
        return np.ascontiguousarray(np.asarray(self._ds.bin_data).T)

    @property
    def bins_fm(self):
        if self._bins_fm is None:
            host = self.bins_host()
            if host is not None:
                self._bins_fm = self._placed("bins",
                                             lambda: jnp.asarray(host))
            elif self._store is not None:
                self._bins_fm = self._assemble_from_store("bins")
            else:
                log.warning("materializing the dense [N, F] bin matrix "
                            "from a sparse dataset for tree traversal — "
                            "avoid DART / train-set traversal paths on "
                            "sparse-EFB data if memory-bound")
                dense = self._ds._dense_bin_matrix()
                self._bins_fm = jnp.asarray(np.ascontiguousarray(dense.T))
        return self._bins_fm

    @property
    def bundle_fm(self):
        if self._bundle_fm is None and self.efb is not None \
                and self._for_train and self._store is not None:
            self._bundle_fm = self._assemble_from_store("bundle")
        return self._bundle_fm

    def get_raw(self) -> np.ndarray:
        """Raw feature matrix (linear trees only; requires the Dataset to
        have kept raw data — basic.py construct retains it under
        linear_tree)."""
        if self._raw2d is None:
            if self.raw_ref is None:
                raise LightGBMError(
                    "linear_tree needs raw feature values; construct the "
                    "Dataset with linear_tree in params (or "
                    "free_raw_data=False)")
            self._raw2d = _to_2d_float(self.raw_ref)
        return self._raw2d


def _traverse_padded(tree: Tree, num_leaves_cap: int, dd: _DeviceData,
                     scale_values: np.ndarray) -> Tuple:
    """Pad host tree arrays to fixed [cap-1]/[cap] so the jitted traversal
    compiles once per shape."""
    ni_cap = max(num_leaves_cap - 1, 1)
    ni = tree.num_internal()

    def pad(a, size, dtype):
        out = np.zeros(size, dtype=dtype)
        out[:len(a)] = a
        return jnp.asarray(out)

    feat = pad(tree.split_feature[:ni], ni_cap, np.int32)
    is_cat_node = (tree.decision_type[:ni] & 1) != 0
    thr = pad(np.where(is_cat_node, 0, tree.threshold_bin[:ni]),
              ni_cap, np.int32)
    dl = pad((tree.decision_type[:ni] & 2) != 0, ni_cap, bool)
    left = pad(tree.left_child[:ni], ni_cap, np.int32)
    right = pad(tree.right_child[:ni], ni_cap, np.int32)
    vals = pad(scale_values, num_leaves_cap, np.float32)
    iscat = pad(is_cat_node, ni_cap, bool)
    catmask = np.zeros((ni_cap, dd.max_bin), dtype=bool)
    if tree.num_cat > 0 and tree.cat_bin_masks.size:
        for i in np.nonzero(is_cat_node)[0]:
            m = tree.cat_bin_masks[int(tree.threshold_bin[i])]
            catmask[i, :len(m)] = m[:dd.max_bin]
    return feat, thr, dl, left, right, iscat, jnp.asarray(catmask), vals


_jit_traverse = jax.jit(traverse_bins)


def _probe_why(res) -> str:
    """` (cause: detail)` suffix for a falsy kernel-probe result, so the
    fallback event and warning carry the compiler's message or the
    mismatch numbers (ops/pallas_hist.ProbeResult)."""
    detail = getattr(res, "detail", "")
    return f" ({res.cause}: {detail[:600]})" if detail else ""


def _count_growth(tree: Tree, reduce_bytes: int = 0) -> None:
    """What growing `tree` cost, onto the always-on counters: the rows its
    histograms needed, its leaves, and (wave grower) the waves' passes and
    its strict tail's passes, splits served from a speculated histogram,
    and speculated ones left unused: passes a tree = 1 + waves + tail;
    the routing passes over the rows (`grow.route_passes`: one a wave and
    a speculation where `ops/route.py` serves, else one a pick and a
    slot) and the picks and slots they routed (`grow.route_picks`; with
    categorical columns `grow.route_cat_picks` of them categorical);
    its categorical splits and the bins in their left sets
    (`grow.cat_splits`, `grow.cat_left_bins`);
    on the f32 Pallas kernel, its calls by the body that ran
    (`grow.hist_passes_full`, `grow.hist_passes_c<capacity>`, summed over
    the shards of a mesh) and the rows they contracted: needed /
    contracted is the useful share of what the MXU multiplies.  Over a
    mesh (`reduce_bytes` > 0: what one shard hands to the collectives of
    one reduction, `parallel/learner.hist_reduce_bytes`) every pass ends
    in one cross-shard histogram reduction: `grow.reduce_passes`,
    `grow.reduce_bytes` (one shard's)."""
    counter = telemetry.REGISTRY.counter
    counter("grow.hist_rows_needed").inc(tree.hist_rows_needed())
    counter("grow.leaves").inc(tree.num_leaves)
    counter("grow.cat_splits").inc(tree.num_cat)
    counter("grow.cat_left_bins").inc(int(tree.cat_bin_masks.sum()))
    if tree.tail_stats is not None:
        passes, hits, unused, _, waves, route_passes, route_picks, \
            *cat_picks = tree.tail_stats
        counter("grow.route_cat_picks").inc(sum(cat_picks))
        counter("grow.tail_passes").inc(passes)
        counter("grow.tail_spec_hits").inc(hits)
        counter("grow.tail_spec_unused").inc(unused)
        counter("grow.wave_passes").inc(waves)
        counter("grow.route_passes").inc(route_passes)
        counter("grow.route_picks").inc(route_picks)
        if reduce_bytes:
            counter("grow.reduce_passes").inc(1 + waves + passes)
            counter("grow.reduce_bytes").inc(
                (1 + waves + passes) * reduce_bytes)
    if tree.hist_calls is not None:
        from .ops.pallas_hist import LANE, hist_bodies
        for (body, _), calls in zip(hist_bodies(), tree.hist_calls):
            counter(f"grow.hist_passes_{body}").inc(calls)
        counter("grow.hist_rows_contracted").inc(tree.hist_calls[-1] * LANE)


def _with_rows(jitted, *rows):
    """`jitted(score, *rows, *more)` as a function of (score, *more):
    per-row arrays handed to a jitted function as arguments at every
    call, behind the signature its callers know."""
    def call(score, *more):
        return jitted(score, *rows, *more)
    # telemetry's cache poll; a Python function, not the bound method
    # itself: jaxlib's method objects are invisible to the cycle
    # collector, and booster -> call -> method -> jitted -> booster
    # would keep every booster's device arrays for good
    call._cache_size = lambda: jitted._cache_size()
    return call


class Booster:
    """Booster (API parity: python-package/lightgbm/basic.py `Booster`)."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None):
        self.params = copy.deepcopy(params) if params else {}
        self.best_iteration = -1
        self.best_score: Dict = {}
        self.trees: List[Tree] = []
        self.pandas_categorical = None
        self.train_set: Optional[Dataset] = None
        self.valid_sets: List[Dataset] = []
        self.name_valid_sets: List[str] = []
        self._network_initialized = False
        self.cur_iter = 0
        # training flight recorder (telemetry/recorder.py); stays None
        # unless flight_recorder=true — the hot paths carry one `is None`
        # check and model-loaded boosters never construct one
        self._flight = None

        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError(
                    f"Training data should be Dataset instance, met "
                    f"{type(train_set).__name__}")
            self._init_train(train_set)
        elif model_file is not None:
            with open(model_file, "r") as f:
                self.model_from_string(f.read())
        elif model_str is not None:
            self.model_from_string(model_str)
        else:
            raise TypeError("Need at least one training dataset or model "
                            "file or model string to create Booster instance")

    # params accepted by the config layer but not (yet) acted on by this
    # build — users must hear about it instead of silently losing the knob
    # (ref: config.cpp Config::CheckParamConflict warns-and-corrects; an
    # accepted-and-ignored param is a correctness trap).  Entries are
    # removed as the features land.
    _INERT_PARAMS = ()

    def _warn_inert_params(self) -> None:
        from .utils.config import _PARAMS, canonical_param_name
        seen = {canonical_param_name(k) for k in self.params}
        for name in self._INERT_PARAMS:
            if name not in seen:
                continue
            default = _PARAMS[name][0]
            if getattr(self.config, name) != default:
                log.warning(f"Parameter {name} is accepted but not yet "
                            "implemented in lightgbm_tpu — it has NO effect "
                            "on this run")
        # socket-era network params are superseded by the mesh runtime
        # (ref: Config machines/local_listen_port → SURVEY §2.7.5)
        for name in ("machines", "local_listen_port", "time_out"):
            if name in seen and \
                    getattr(self.config, name) != _PARAMS[name][0]:
                log.warning(
                    f"Parameter {name} configures the reference's TCP "
                    "transport and is ignored here — multi-host setup is "
                    "lightgbm_tpu.parallel.init(coordinator_address=...) "
                    "+ num_machines/tree_learner")

    # ------------------------------------------------------------- training
    def _init_train(self, train_set: Dataset) -> None:
        # objective may be passed as a callable in params (v4 custom-objective
        # path); normalize to "custom"
        self._fobj = None
        obj = self.params.get("objective")
        if callable(obj):
            self._fobj = obj
            self.params["objective"] = "none"
        self.config = Config(self.params)
        self._warn_inert_params()
        if self.config.telemetry_sink:
            # attach BEFORE _DeviceData so the dataset.bin span is captured;
            # idempotent per path, so re-init / multiple boosters share one
            # appender
            telemetry.TRACER.attach_jsonl(self.config.telemetry_sink)
        if self.config.telemetry_spool or self.config.telemetry_spool_dir:
            # cross-process spool (telemetry/spool.py): same
            # attach-before-_DeviceData ordering, idempotent per dir
            telemetry.attach_spool(self.config.telemetry_spool_dir,
                                   role="trainer")
        # arm the attributed device-memory ledger BEFORE _DeviceData so
        # the bin-matrix upload is attributed from the first byte;
        # ledger on/off never changes trained bytes (tests pin this)
        telemetry.MEMLEDGER.configure(
            enabled=bool(self.config.memory_ledger),
            reconcile_ms=float(self.config.memory_reconcile_ms))
        with telemetry.span("setup.booster") as span:
            if span is not telemetry.NOOP:
                for gauge in ("setup.probe_s", "setup.place_s"):
                    telemetry.REGISTRY.gauge(gauge)     # reads 0, not absent
            self._init_training_state(train_set)

    def _init_training_state(self, train_set: Dataset) -> None:
        """The rest of `_init_train`, under its `setup.booster` span: the
        data on the device, the objective, the grower (kernel probes
        included) and the per-row state."""
        self._debug_nans = bool(self.config.tpu_debug_nans)
        if self._debug_nans:
            # numeric-sanitizer mode (ref: cmake/Sanitizer.cmake posture):
            # any NaN produced inside this booster's jitted training steps
            # raises FloatingPointError at the producing op instead of
            # poisoning the whole model.  Applied as a context around THIS
            # booster's dispatches (jax.debug_nans), never as the global
            # flag — a leaked global would slow and abort unrelated
            # boosters in the same process.
            log.warning("tpu_debug_nans=true: NaN checks enabled — "
                        "training is slower; use for debugging only")
        if self.config.debug_contracts:
            # runtime half of graft-lint R004: validate the @contract
            # shape/dtype specs on the ops/ entry points.  Trace-time
            # cost only, but the switch is process-global (a sibling
            # booster created with debug_contracts=false does not turn
            # it back off — see analysis.enable_runtime_checks)
            from .analysis import enable_runtime_checks
            enable_runtime_checks(True)
            log.warning("debug_contracts=true: runtime shape/dtype "
                        "contract checks enabled for this process")
        if self.config.debug_locks:
            # runtime half of graft-race R006: every make_lock lock
            # feeds the process-global acquisition-order witness; an
            # inverted order raises LockOrderError with both stacks.
            # Sticky process-global switch, like debug_contracts
            from .analysis import enable_lock_witness
            enable_lock_witness(True)
            log.warning("debug_locks=true: lock-order witness armed "
                        "for this process")
        train_set.params = {**(train_set.params or {}), **{
            k: v for k, v in self.params.items()
            if k in ("max_bin", "min_data_in_bin", "bin_construct_sample_cnt",
                     "use_missing", "zero_as_missing", "data_random_seed",
                     "max_bin_by_feature", "feature_pre_filter",
                     "enable_bundle", "max_conflict_rate", "linear_tree",
                     "label_column", "header",
                     # file-ingest column roles + streaming mode must reach
                     # construct(), or train and predict would drop
                     # different columns from the same file
                     "weight_column", "group_column", "ignore_column",
                     "two_round",
                     # external-memory spill config must reach construct()
                     # — that is where the shard store is written
                     "external_memory", "datastore_dir",
                     "datastore_shard_rows", "datastore_budget_mb",
                     "datastore_prefetch")}}
        if str(self.config.streaming_train or "auto").lower() == "on":
            # streaming_train="on" implies the external-memory spill: the
            # shard store IS the stream source, so it must exist before
            # _DeviceData constructs the dataset
            train_set.params["external_memory"] = True
        self.train_set = train_set
        self._dd = _DeviceData(train_set)
        self.objective_: Optional[ObjectiveFunction] = \
            create_objective(self.config)
        self.num_tree_per_iteration = (
            self.objective_.num_tree_per_iteration
            if self.objective_ is not None else max(self.config.num_class, 1))
        if self.objective_ is not None:
            label = train_set.get_label()
            if label is None:
                raise LightGBMError("Label should not be None")
            self.objective_.init_meta(
                label.astype(np.float64), train_set.get_weight(),
                train_set._query_boundaries)
            if getattr(train_set, "position", None) is not None:
                pos = train_set.get_position()
                if hasattr(self.objective_, "set_positions"):
                    # unbiased lambdarank (ref: v4 rank_objective.hpp
                    # position handling): propensity state rides the
                    # per-iteration grad call — see _grad_fn setup below
                    self.objective_.set_positions(pos)
                else:
                    log.warning(
                        f"Dataset positions are only consumed by the "
                        f"lambdarank objective — positions have NO effect "
                        f"on objective="
                        f"{getattr(self.objective_, 'name', '?')}")

        metric_names = self.config.metric or self.config.default_metric()
        self.metrics_: List[Metric] = create_metrics(self.config, metric_names)

        # boosting mode / sample strategy (ref: Boosting::CreateBoosting and
        # v4 data_sample_strategy: "goss" as boosting type is the legacy
        # spelling of strategy=goss on gbdt)
        boosting = self.config.boosting
        if boosting not in ("gbdt", "dart", "goss", "rf"):
            raise LightGBMError(f"Unknown boosting type {boosting}")
        self._use_goss = (boosting == "goss" or
                          self.config.data_sample_strategy == "goss")
        self._boost_mode = "gbdt" if boosting == "goss" else boosting
        if self._boost_mode == "rf":
            if not (self.config.bagging_freq > 0 and
                    (self.config.bagging_fraction < 1.0 or
                     self.config.feature_fraction < 1.0)):
                raise LightGBMError(
                    "Random forest mode requires bagging "
                    "(bagging_freq > 0 and bagging_fraction < 1.0)")
            # RF trees are independent averages: no init score, no shrinkage
            self.config.boost_from_average = False
        if self._boost_mode == "dart":
            # keep DART trees bias-free so drop/rescale math stays exact
            # (deviation: reference folds boost_from_average into tree 0 and
            # scales it along; starting from 0 avoids that coupling)
            self.config.boost_from_average = False
        self._average_output = self._boost_mode == "rf"

        self._ic_groups = self._parse_ic_groups()
        interm = self._monotone_intermediate()
        pool_slots = self._hist_pool_slots()
        if interm and pool_slots:
            log.warning("monotone_constraints_method=intermediate needs "
                        "per-leaf histograms to re-search moved leaves — "
                        "ignoring histogram_pool_size")
            pool_slots = 0
        self._grower_spec = GrowerSpec(
            num_leaves=self.config.num_leaves,
            max_depth=self.config.max_depth,
            max_bin=self._dd.max_bin,
            lambda_l1=self.config.lambda_l1,
            lambda_l2=self.config.lambda_l2,
            min_data_in_leaf=float(self.config.min_data_in_leaf),
            min_sum_hessian_in_leaf=self.config.min_sum_hessian_in_leaf,
            min_gain_to_split=self.config.min_gain_to_split,
            max_delta_step=self.config.max_delta_step,
            cat_smooth=self.config.cat_smooth,
            cat_l2=self.config.cat_l2,
            max_cat_threshold=self.config.max_cat_threshold,
            max_cat_to_onehot=self.config.max_cat_to_onehot,
            min_data_per_group=float(self.config.min_data_per_group),
            hist_impl=self._resolve_hist_impl(),
            hist_interpret=bool(self.config.hist_interpret),
            bundled=self._dd.efb is not None,
            bundle_max_bin=self._dd.efb.max_bin
            if self._dd.efb is not None else 0,
            hist_pool_slots=pool_slots,
            path_smooth=self.config.path_smooth,
            feature_fraction_bynode=self.config.feature_fraction_bynode,
            n_ic_groups=0 if self._ic_groups is None
            else self._ic_groups.shape[0],
            forced_splits=self._parse_forced_splits(),
            num_features_hint=self._dd.num_feature,
            cegb_tradeoff=self.config.cegb_tradeoff
            if self._cegb_active() else 0.0,
            cegb_penalty_split=self.config.cegb_penalty_split,
            cegb_coupled=bool(list(
                self.config.cegb_penalty_feature_coupled or [])),
            cegb_lazy=bool(list(
                self.config.cegb_penalty_feature_lazy or [])),
            extra_trees=self.config.extra_trees,
            voting_top_k=self.config.top_k,
            packed_const_hess_level=self._packed_const_hess_level(),
            monotone_intermediate=interm,
            wave_width=self._wave_width(),
            wave_gain_ratio=self._wave_gain_ratio(),
            wave_overgrow=self._wave_overgrow(),
            wave_strict_tail=self._wave_strict_tail(),
            has_cat=bool(self._dd.is_cat_np.any()),
            debug_checks=bool(self.config.tpu_debug_nans),
            hist_lane_plan=self._hist_lane_plan(),
        )
        self._grow_policy = self._resolve_grow_policy()
        self._record_hist_lanes()
        self._rng_key0 = jax.random.PRNGKey(
            self.config.bagging_seed % (2 ** 31))
        self._ff_key0 = jax.random.PRNGKey(
            self.config.feature_fraction_seed % (2 ** 31))
        self._grower = self._make_serial_grower()
        self._build_feat()
        self._setup_tree_learner()
        self._flight = None
        if self.config.flight_recorder:
            # opt-in per-round diagnostics: stats come from the host tree
            # arrays both training paths already materialize, so recording
            # adds no device syncs (and the grown model bytes are
            # identical either way — tests/test_flight_recorder.py)
            from .telemetry.recorder import (FlightRecorder,
                                             install_compile_listener,
                                             sample_memory)
            wave = None
            if self._grow_policy == "wave":
                wave = {"policy": "wave",
                        "width": int(self._grower_spec.wave_width),
                        "num_leaves": int(self.config.num_leaves)}
            self._flight = FlightRecorder(
                depth=self.config.flight_recorder_depth, wave=wave)
            # phase wall-clock (train.grow / train.decode / eval ...) is
            # read back from span timings, which NOOP spans never record:
            # force span recording into the registry for this opted-in
            # process even when no event sink is attached
            telemetry.TRACER.enable(True)
            install_compile_listener()
            sample_memory("init")
        # per-row state lives where the training rows live: split over
        # the learner's mesh like the bin matrix, so that gradients, the
        # score update and the grower's inputs never gather on one chip
        self._dd.row_sharding = self._row_sharding()
        telemetry.REGISTRY.gauge("mesh.shards").set(
            1 if self._mesh is None else self._mesh.devices.size)
        self._ones = _placed("ones", lambda: self._rows_of(self._dd, 1.0))

        K = self.num_tree_per_iteration
        self._init_scores = [0.0] * K
        self._boost_from_average_done = False
        self._train_score = _placed("score",
                                    lambda: self._zero_score(self._dd))
        self._valid_dd: List[_DeviceData] = []
        self._valid_scores: List[jax.Array] = []
        # pipelined chunk training state: FIFO of dispatched-but-not-yet-
        # harvested chunks and the iteration count they will add once
        # decoded (cur_iter only advances at harvest, but the NEXT
        # dispatch must derive its RNG streams from the post-chunk
        # iteration index)
        self._inflight: "deque[_PendingChunk]" = deque()
        self._pending_iters = 0
        self._pipe_prev_ready_t: Optional[float] = None

        self._grad_key0 = jax.random.PRNGKey(
            self.config.objective_seed % (2 ** 31))
        if self.objective_ is not None:
            # labels and weights are ARGUMENTS of the jitted gradients: a
            # closed-over [N] array is baked into the executable as a
            # constant (4 B a row, once a device of a mesh), and keeps
            # whatever placement it had when it was traced
            lbl = self._dd.label
            wgt = self._dd.weight
            if getattr(self.objective_, "needs_rng", False):
                def _grad(score, lbl, wgt, key):
                    return self.objective_.grad_hess(score, lbl, wgt, key=key)
                # per-iteration key = fold_in(key0, it) — the SAME derivation
                # the fused chunk trainer uses, so both paths are identical
                self._grad_rng_fn = _with_rows(jax.jit(_grad), lbl, wgt)
                self._grad_fn = lambda s: self._grad_rng_fn(
                    s, jax.random.fold_in(self._grad_key0, self.cur_iter))
            elif hasattr(self.objective_, "device_maps"):
                # lambdarank: its query maps are arguments as the rows are,
                # and its program is `jit_lambdarank` in a trace
                self._grad_fn = _with_rows(
                    jax.jit(self.objective_.lambdarank), lbl, wgt,
                    self.objective_.device_maps())
                if self.objective_.has_state:
                    # unbiased lambdarank: the propensity state must be a
                    # runtime input too — closed over, it would be baked
                    # into the jit as a constant and never update
                    self._obj_state = self.objective_.init_state()
                    self._grad_state_fn = self._grad_fn

                    def _grad(s):
                        g, h, self._obj_state = self._grad_state_fn(
                            s, self._obj_state)
                        return g, h
                    self._grad_fn = _grad
            else:
                def _grad(score, lbl, wgt):
                    return self.objective_.grad_hess(score, lbl, wgt)
                self._grad_fn = _with_rows(jax.jit(_grad), lbl, wgt)

    def _packed_const_hess_level(self) -> int:
        """Nonzero when the packed quantized histogram may derive counts
        from the hess field (unit-hessian objective, no dataset weights,
        packed impl selected): every live row quantizes to exactly
        hq = num_grad_quant_bins, so counts = hess_field / level and the
        count scatter sweep disappears — ONE sweep per histogram."""
        from .objectives import UNIT_HESSIAN_OBJECTIVES
        if self._resolve_hist_impl() != "packed":
            return 0
        if getattr(self.objective_, "name", None) \
                not in UNIT_HESSIAN_OBJECTIVES:
            return 0
        if self.train_set.get_weight() is not None:
            return 0
        return int(self.config.num_grad_quant_bins)

    def _monotone_intermediate(self) -> bool:
        """Whether the grower runs the `intermediate` monotone method
        (ref: monotone_constraints.hpp `IntermediateLeafConstraints`;
        config.h monotone_constraints_method).  `advanced` downgrades to
        intermediate, distributed learners downgrade to basic — both with
        a warning."""
        cfg = self.config
        mono = list(cfg.monotone_constraints or [])
        if not mono or not any(mono):
            return False
        method = (cfg.monotone_constraints_method or "basic").lower()
        if method == "basic":
            return False
        if method == "advanced":
            log.warning(
                "monotone_constraints_method=advanced is not implemented "
                "— using intermediate (ref: monotone_constraints.hpp "
                "AdvancedLeafConstraints is out of scope)")
        elif method != "intermediate":
            raise LightGBMError(
                f"Unknown monotone_constraints_method {method}")
        from .parallel.learner import TREE_LEARNER_ALIASES
        kind = TREE_LEARNER_ALIASES.get(
            str(cfg.tree_learner or "serial").lower(), "serial")
        if kind != "serial":
            log.warning(
                "monotone_constraints_method=intermediate is only "
                "implemented for the serial tree learner — using the "
                "basic method")
            return False
        return True

    def _cegb_active(self) -> bool:
        """CEGB is on when any penalty is configured
        (ref: cost_effective_gradient_boosting.hpp `IsEnable`)."""
        cfg = self.config
        return (cfg.cegb_tradeoff > 0.0
                and (cfg.cegb_penalty_split > 0.0
                     or bool(list(cfg.cegb_penalty_feature_coupled or []))
                     or bool(list(cfg.cegb_penalty_feature_lazy or []))))

    def _parse_ic_groups(self) -> Optional[np.ndarray]:
        """Parse interaction_constraints into [K, F] group masks
        (ref: config.h interaction_constraints "[0,1,2],[2,3]";
        col_sampler.hpp filters per-branch)."""
        raw = self.config.interaction_constraints
        if raw is None or raw == "" or raw == []:
            return None
        if isinstance(raw, str):
            try:
                groups = json.loads(raw)
            except json.JSONDecodeError:
                groups = json.loads(f"[{raw}]")
        else:
            groups = [list(g) for g in raw]
        F = self._dd.num_feature
        mask = np.zeros((len(groups), F), dtype=bool)
        for k, g in enumerate(groups):
            for j in g:
                if not 0 <= int(j) < F:
                    raise LightGBMError(
                        f"interaction_constraints feature index {j} out of "
                        f"range [0, {F})")
                mask[k, int(j)] = True
        return mask

    def _parse_forced_splits(self) -> tuple:
        """Flatten the forced-splits JSON (ref: serial_tree_learner.cpp
        `ForceSplits`; forcedsplits_filename nested
        {feature, threshold, left, right}) into BFS-order
        (leaf_slot, feature, threshold_bin) tuples matching the grower's
        child encoding (right child of step s = leaf s+1)."""
        fn = self.config.forcedsplits_filename
        if not fn:
            return ()
        with open(fn) as f:
            root = json.load(f)
        if not root:
            return ()
        mappers = self.train_set.bin_mappers
        out = []
        queue = [(root, 0)]
        while queue and len(out) < self.config.num_leaves - 1:
            node, leaf = queue.pop(0)
            j = int(node["feature"])
            thr = float(node["threshold"])
            b = mappers[j].value_to_bin(thr)
            out.append((leaf, j, int(b)))
            step = len(out) - 1
            if node.get("left"):
                queue.append((node["left"], leaf))
            if node.get("right"):
                queue.append((node["right"], step + 1))
        return tuple(out)

    def _hist_pool_slots(self) -> int:
        """Size the per-leaf histogram cache from `histogram_pool_size` MB
        (ref: config.h histogram_pool_size → feature_histogram.hpp
        `HistogramPool`).  0 = unbounded (one slot per leaf)."""
        pool_mb = self.config.histogram_pool_size
        if pool_mb is None or pool_mb <= 0:
            return 0
        bins, cols = self._probe_shape()
        slot_bytes = max(cols * bins * 3 * 4, 1)
        slots = int(pool_mb * 2 ** 20 // slot_bytes)
        slots = max(2, slots)
        return slots if slots < self.config.num_leaves else 0

    # default wave knobs from the quality/perf sweeps (PROFILE.md round
    # 3c): moderate waves (W=6) keep accuracy (W=14 leaked ~0.016 AUC of
    # capacity into breadth).  Overgrow-prune defaults OFF: measured, it
    # does not beat the capacity-aware gain floor on depth-hungry data —
    # wave depth (~log2 of the grown size), not leaf capacity, is what
    # binds (PROFILE.md "grow-then-prune" note) — but it remains an
    # opt-in knob for breadth-friendly data.  The width default is
    # DEFINED in ops/grow_wave.py so a directly-built GrowerSpec falls
    # back to the same swept value.
    WAVE_GAIN_RATIO_DEFAULT = 0.0
    WAVE_OVERGROW_DEFAULT = 0.0

    def _wave_width(self) -> int:
        """Leaves per batched histogram pass for the wave policy.
        `tpu_wave_width=0` (auto) picks the sweep default, capped at the
        MXU LHS capacity for the payload family (14 f32 / 42 quantized
        rows-per-leaf chunks).  Deterministic across backends given the
        same params — the backend-parity contract (CPU packed ↔ TPU
        pallas_q resolve to the same family)."""
        from .ops.grow_wave import WAVE_WIDTH_DEFAULT
        from .ops.pallas_hist import MULTI_CHUNK, MULTI_CHUNK_Q
        cap = MULTI_CHUNK_Q \
            if self._resolve_hist_impl() in ("pallas_q", "packed") \
            else MULTI_CHUNK
        w = int(self.config.tpu_wave_width or 0)
        if w <= 0:
            # overgrow mode wants the widest batch the family's kernel
            # chunk supports; plain waves keep the accuracy-sweep width
            w = cap if self._wave_overgrow() > 1.0 \
                else WAVE_WIDTH_DEFAULT
        return min(w, cap)

    def _wave_gain_ratio(self) -> float:
        r = float(self.config.tpu_wave_gain_ratio)
        return self.WAVE_GAIN_RATIO_DEFAULT if r < 0.0 else min(r, 1.0)

    def _wave_strict_tail(self) -> int:
        """Hybrid wave/strict schedule knob: `tpu_wave_strict_tail=-1`
        (auto) resolves to ~num_leaves/2 — enough strict endgame to
        recover the strict policy's capacity allocation where it binds,
        small enough that the early wide waves stay wave-batched; 0
        disables.  (r4's auto was ~L/3; the r5 multi-seed data moved
        it: at num_leaves=31, ratio0+tail16 beat ratio0+tail-auto(11)
        on every 500k seed — a clean tail A/B — and beat the r4
        floor0.8+tail-auto bench config on every 2M seed; PROFILE.md
        r5.  16 ≈ L/2.)  The grower caps
        it at its grow budget (LB - 1, which exceeds num_leaves - 1
        under overgrow — the tail is the endgame of the grow phase).
        Auto resolves to 0 under overgrow: the prune already
        reallocates capacity by gain, and a strict tail on the
        pre-prune growth measurably hurts it (tests/test_wave.py
        overgrow-quality); an explicit value is honored either way."""
        t = int(self.config.tpu_wave_strict_tail)
        if t < 0:
            t = 0 if self._wave_overgrow() > 1.0 \
                else (self.config.num_leaves + 1) // 2
        return max(t, 0)

    def _wave_overgrow(self) -> float:
        """Grow-then-prune factor (0 = off).  Auto-resolves to the sweep
        default for the wave policy; gated off under monotone
        constraints / path smoothing, where a pruned parent's restored
        output would ignore the clamp/smoothing chain."""
        pol = str(self.config.tree_grow_policy or "leafwise").lower()
        if pol not in ("wave", "batched"):
            return 0.0
        r = float(self.config.tpu_wave_overgrow)
        val = self.WAVE_OVERGROW_DEFAULT if r < 0.0 else r
        if val <= 1.0:
            return 0.0
        mono = list(self.config.monotone_constraints or [])
        if (mono and any(mono)) or self.config.path_smooth > 0.0:
            if not getattr(self, "_warned_overgrow", False):
                self._warned_overgrow = True
                log.warning(
                    "tpu_wave_overgrow is not supported with monotone "
                    "constraints or path smoothing (pruned parents "
                    "restore un-clamped outputs) — growing without "
                    "overgrow")
            return 0.0
        return val

    def _learner_topology(self):
        """ONE resolver for the learner kind + mesh shape — consumed by
        both `_setup_tree_learner` (which builds it) and
        `_resolve_grow_policy` (which judges wave eligibility), so the
        two can never drift.  Quiet: emits no warnings.

        Returns (kind, shards, n_dev, dcn, use_2level, s_last); `s_last`
        is the LAST (ICI) mesh-axis size — the shard count feature
        blocks split over (must match `mesh.shape[axes[-1]]` of the mesh
        `_setup_tree_learner` builds).  `kind` includes alias +
        EFB/2-level downgrades but NOT the one-device serial fallback —
        callers apply `shards <= 1` themselves (the setup path wants to
        warn, the policy path just wants the answer)."""
        from .parallel.learner import resolve_tree_learner
        cfg = self.config
        bundled = self._dd.efb is not None
        name = cfg.tree_learner or "serial"
        kind = resolve_tree_learner(name, bundled=bundled, quiet=True)
        if kind == "serial":
            return "serial", 1, 1, 1, False, 1
        n_dev = len(jax.devices())
        dims = None
        if cfg.mesh_shape:
            from .mesh.topology import parse_mesh_shape
            dims = parse_mesh_shape(cfg.mesh_shape)
        if dims is not None:
            # explicit topology wins over num_machines/tpu_dcn_slices;
            # an over-subscription fails loudly in get_mesh* at build
            # time rather than being silently clamped here
            shards = 1
            for d in dims:
                shards *= d
            dcn = dims[0] if len(dims) == 2 else 1
            use_2level = len(dims) == 2
        else:
            shards = cfg.num_machines if (cfg.num_machines or 0) > 1 \
                else n_dev
            shards = min(shards, n_dev)
            dcn = max(int(cfg.tpu_dcn_slices or 1), 1)
            use_2level = dcn > 1 and shards % dcn == 0 and shards // dcn > 1
        kind = resolve_tree_learner(name, bundled=bundled,
                                    two_level=use_2level, quiet=True)
        s_last = shards // dcn if use_2level else shards
        return kind, shards, n_dev, dcn, use_2level, s_last

    def _resolve_grow_policy(self) -> str:
        """Resolve `tree_grow_policy` with eligibility downgrades (see
        ops/grow_wave.py module docstring for the supported scope)."""
        pol = str(self.config.tree_grow_policy or "leafwise").lower()
        if pol in ("leafwise", "leaf", "strict"):
            return "leafwise"
        if pol not in ("wave", "batched"):
            raise LightGBMError(
                f"Unknown tree_grow_policy {pol!r} "
                "(expected 'leafwise' or 'wave')")
        spec = self._grower_spec
        # r5: CEGB and interaction constraints are wave-eligible — both
        # are per-candidate masks/penalties already computed inside
        # find_best_split, shared via make_cegb_penalty /
        # ic_allowed_from_used, and CEGB's coupled state is frozen
        # within a tree so candidate pricing is order-independent
        # (width-1 waves stay byte-identical to strict; tests/test_wave)
        # r5 (later): forced splits are wave-eligible too — the BFS
        # prefix runs as width-1 waves (strict order by construction),
        # then free growth resumes at full width
        reasons = []
        if spec.monotone_intermediate:
            reasons.append("monotone_constraints_method=intermediate")
        if spec.hist_pool_slots:
            # decision note COVERAGE.md r6: the wave frontier needs every
            # parent histogram resident at once for sibling-by-subtraction,
            # so a bounded pool cannot be threaded through make_wave_grower
            reasons.append(
                "histogram_pool_size (the bounded pool caps resident "
                f"histograms at {spec.hist_pool_slots} of "
                f"{spec.num_leaves}; dropping the cap restores the wave "
                "policy at the cost of the pool's memory bound — "
                "COVERAGE.md r6 decision note)")
        kind, shards = self._learner_topology()[:2]
        if shards <= 1:
            kind = "serial"      # the one-device fallback (wave-eligible)
        if kind not in ("serial", "data"):
            reasons.append(f"tree_learner={kind} (wave supports serial "
                           "and data-parallel)")
        if spec.hist_impl in ("pallas", "pallas_q"):
            # the wave path runs exactly ONE multi-leaf kernel block
            # shape (root pass padded to the wave width) — gate on a
            # probe of THAT shape (the single-leaf probe gating
            # hist_impl says nothing about the multi blocks)
            from .ops.grow_wave import wave_sizes
            from .ops.pallas_hist import probe_cached
            _, w = wave_sizes(spec)
            # (`_probe_shape` holds the data-parallel learner's pad
            # columns: the probe certifies the shape a shard runs)
            pb, pc = self._probe_shape()
            res = probe_cached(pb, pc, multi=True, width=w,
                               quantized=spec.hist_impl == "pallas_q",
                               interpret=spec.hist_interpret,
                               plan=spec.hist_lane_plan)
            if not res:
                reasons.append("a failing multi-leaf Pallas kernel probe "
                               "on this backend"
                               + _probe_why(res))
        if reasons:
            # priced downgrade (VERDICT r4 #4): strict measured 2.1x
            # slower than the wave AUC-parity config on TPU at the 2M
            # bench shape (1.4 vs 2.96 rounds/s, PROFILE.md r3c); under
            # the default int-lattice histograms the wave gains the
            # ~1.8x kernel speedup while strict (gather-dominated)
            # barely moves, widening the ceiling toward ~4x — see the
            # COVERAGE.md r7 repricing note
            telemetry.REGISTRY.counter("fallback.events").inc()
            telemetry.event("fallback.wave_downgrade", reasons=reasons)
            log.warning("tree_grow_policy=wave is not supported with "
                        + "; ".join(reasons)
                        + " — using the strict leafwise policy (expect "
                        "roughly 2-4x lower training throughput on TPU "
                        "under the default quantized histograms; "
                        "PROFILE.md r3c, COVERAGE.md r7)")
            return "leafwise"
        return "wave"

    def _make_serial_grower(self):
        if getattr(self, "_grow_policy", "leafwise") == "wave":
            from .ops.grow_wave import make_wave_grower
            return make_wave_grower(self._grower_spec)
        return make_grower(self._grower_spec)

    def _pad_columns(self) -> int:
        """Empty columns the data-parallel learner appends so that the
        column count divides its shards (`place_training_data`'s
        `pad_features`, `parallel/learner.padded_feature_count`): every
        shard's kernel sees them, all rows in bin 0."""
        kind, shards, _, _, _, s_last = self._learner_topology()
        if self._dd.efb is not None or shards <= 1 or kind != "data":
            return 0
        from .parallel.learner import padded_feature_count
        return padded_feature_count(self._dd.num_feature, s_last) \
            - self._dd.num_feature

    def _probe_shape(self):
        """(bin count, column count) the histogram kernels will ACTUALLY
        run at: the BUNDLE matrix shape under EFB (bundle columns can be
        wider than any single feature's bin count — probing the
        per-feature shape would certify the wrong Mosaic block), the
        padded column count under the data-parallel learner (Mosaic
        regressions are shape-specific)."""
        efb = self._dd.efb
        if efb is not None:
            return efb.max_bin, efb.n_cols
        return self._dd.max_bin, self._dd.num_feature + self._pad_columns()

    def _hist_lane_plan(self):
        """The f32 histogram kernel's static lane plan
        (ops/pallas_hist.py `lane_plan`), from the bin counts of the
        columns the kernel will see — the columns of `_probe_shape`: the
        mappers' `num_bin`, under EFB the bundle columns' widths, under
        the data-parallel learner also its pad columns (one bin each:
        they join a group).  None (every column on its own lanes) for the
        feature-parallel learner, whose shards each histogram another
        slice of the columns."""
        from .ops.pallas_hist import lane_plan
        efb = self._dd.efb
        kind, shards = self._learner_topology()[:2]
        if efb is None and shards > 1 and kind == "feature":
            return None
        num_bins = self._dd.num_bins + (1,) * self._pad_columns() \
            if efb is None else tuple(int(b) for b in efb.col_num_bin)
        return lane_plan(num_bins, self._probe_shape()[0])

    def _record_hist_lanes(self) -> None:
        """Gauges `hist.lanes_per_row` (lanes one histogram pass contracts
        a row) and `hist.packed_columns` (columns that share a lane
        group) of the grower this booster runs; 0 where no Pallas
        histogram kernel runs."""
        from .ops.pallas_hist import LANE, plan_lanes
        fam = self._grower_spec.hist_impl
        plan = self._grower_spec.hist_lane_plan if fam == "pallas" else None
        bins, cols = self._probe_shape()
        lanes = 0
        if fam in ("pallas", "pallas_q"):
            lanes = plan_lanes(plan) if plan is not None \
                else cols * (-(-bins // LANE) * LANE)
        packed = sum(len(members) for _, members in plan or ()
                     if len(members) > 1)
        telemetry.REGISTRY.gauge("hist.lanes_per_row").set(lanes)
        telemetry.REGISTRY.gauge("hist.packed_columns").set(packed)

    _HIST_IMPLS = ("auto", "segment_sum", "packed", "pallas", "pallas_q")

    def _quant_hist_reasons(self) -> list:
        """Why the int-lattice histogram family cannot apply (empty =
        eligible): payload values must be exact integer lattice points
        with hq >= 0 (GOSS rescale weights break integrality; custom
        objectives may return negative hessians, whose hq < 0 borrows
        into the packed grad field; more quant bins than the tile bound
        would overflow the 16-bit field)."""
        cfg = self.config
        from .ops.histogram import PACKED_MAX_QUANT_BINS
        reasons = []
        if not 0 < cfg.num_grad_quant_bins <= PACKED_MAX_QUANT_BINS:
            reasons.append(
                f"num_grad_quant_bins={cfg.num_grad_quant_bins} outside "
                f"(0, {PACKED_MAX_QUANT_BINS}]")
        if self._use_goss:
            reasons.append("GOSS rescale weights break lattice "
                           "integrality")
        if self._fobj is not None or self.objective_ is None:
            reasons.append("custom objective (negative hessians would "
                           "borrow into the packed grad field)")
        return reasons

    def _hist_impl_fallback(self, requested: str, reasons: list) -> None:
        """Priced degradation of an explicit or implied hist_impl request
        (VERDICT r4 #4 discipline: tell users what the fallback costs,
        not just that it happened).  De-duplicated per booster and
        per (request, reasons) — `_resolve_hist_impl` is consulted by
        several sizing helpers, and one decision must price once."""
        seen = getattr(self, "_hist_fallback_seen", None)
        if seen is None:
            seen = self._hist_fallback_seen = set()
        key = (requested, tuple(reasons))
        if key in seen:
            return
        seen.add(key)
        telemetry.REGISTRY.counter("fallback.events").inc()
        telemetry.event("fallback.hist_impl", requested=requested,
                        reasons=reasons)
        log.warning(f"hist_impl={requested} is not available with "
                    + "; ".join(reasons)
                    + " — degrading to the auto-selected path (the "
                    "lattice/kernel family is the fast path: one packed "
                    "sweep per (g, h) pair on CPU, the Pallas kernel in "
                    "place of the XLA scatter on TPU)")

    def _resolve_hist_impl(self) -> str:
        """Pick the histogram implementation.  Default (`hist_impl=auto`)
        promotes the int-lattice family wherever the model qualifies:
        the Pallas kernel on real TPU backends (pallas_q when the
        lattice applies, gated on a tiny compile-and-compare probe so a
        Mosaic regression degrades to the XLA path instead of crashing
        training), the packed-int scatter on CPU, segment-sum last.  A
        quantized-training request the lattice cannot honor emits a
        PRICED fallback event instead of degrading silently.  An
        explicit `hist_impl` pins the path; an ineligible request
        degrades to the auto choice with a priced event
        (degrade-don't-error, like the serving ladder)."""
        cfg = self.config
        from .ops.pallas_hist import probe_cached
        req = str(cfg.hist_impl or "auto").lower()
        if req not in self._HIST_IMPLS:
            raise LightGBMError(
                f"Unknown hist_impl {cfg.hist_impl!r} (expected one of "
                f"{', '.join(self._HIST_IMPLS)})")
        quant_reasons = self._quant_hist_reasons()
        quant_ok = cfg.use_quantized_grad and not quant_reasons
        interpret = bool(cfg.hist_interpret)
        on_tpu = bool(cfg.tpu_use_pallas) \
            and jax.devices()[0].platform == "tpu"
        if req != "auto":
            reasons = []
            if req in ("packed", "pallas_q"):
                if not cfg.use_quantized_grad:
                    reasons.append("use_quantized_grad=False (the "
                                   "int-lattice needs quantized "
                                   "gradients)")
                reasons.extend(quant_reasons)
            if req in ("pallas", "pallas_q"):
                if not cfg.tpu_use_pallas:
                    reasons.append("tpu_use_pallas=False")
                elif not (on_tpu or interpret):
                    reasons.append("no Pallas backend (not a TPU, and "
                                   "hist_interpret is off)")
                else:
                    res = probe_cached(*self._probe_shape(),
                                       quantized=req == "pallas_q",
                                       interpret=not on_tpu,
                                       plan=self._hist_lane_plan())
                    if not res:
                        reasons.append("a failing Pallas histogram probe "
                                       "on this backend" + _probe_why(res))
            if not reasons:
                return req
            self._hist_impl_fallback(req, reasons)
        # ---- auto: the int-lattice family is the default wherever the
        # model qualifies ----
        if cfg.use_quantized_grad and quant_reasons:
            # quantized training was requested but the lattice cannot
            # apply — priced, not silent
            self._hist_impl_fallback("quantized", quant_reasons)
        if on_tpu:
            # XLA lowers the 256-segment scatter-add to a SERIAL update
            # loop on TPU, so the Pallas one-hot-matmul kernel is the
            # default there.  The probe RAISES when the kernel does not compile
            # or run on the TPU; only a numeric mismatch degrades, with
            # the numbers in the event
            res = probe_cached(*self._probe_shape(), quantized=quant_ok,
                               plan=self._hist_lane_plan())
            if res:
                return "pallas_q" if quant_ok else "pallas"
            telemetry.REGISTRY.counter("fallback.events").inc()
            telemetry.event("fallback.pallas_probe",
                            shape=list(self._probe_shape()),
                            cause=res.cause, detail=res.detail)
            log.error("Pallas histogram probe disagrees with segment-sum "
                      f"on this backend ({res.detail}); falling back to "
                      "segment-sum")
        if quant_ok:
            # packed-int scatter: one sweep covers (g, h) — the CPU
            # backend's quantized fast path
            return "packed"
        return "segment_sum"

    def _build_feat(self) -> None:
        """Per-feature metadata pytree for the grower, incl. monotone
        constraints (ref: monotone_constraints.hpp BasicLeafConstraints;
        config.h monotone_constraints is per-feature in {-1, 0, +1},
        shorter vectors are zero-extended like the reference's parser)."""
        mono_cfg = list(self.config.monotone_constraints or [])
        mono = np.zeros(self._dd.num_feature, dtype=np.int32)
        if mono_cfg:
            k = min(len(mono_cfg), self._dd.num_feature)
            mono[:k] = np.asarray(mono_cfg[:k], dtype=np.int32)
        self._feat = dict(nb=self._dd.feat_nb, missing=self._dd.feat_missing,
                          default=self._dd.feat_default,
                          is_cat=self._dd.is_cat, mono=jnp.asarray(mono))
        if self._dd.efb is not None:
            efb = self._dd.efb
            self._feat.update(
                bundle_col=jnp.asarray(efb.col_of_feature),
                bundle_off=jnp.asarray(efb.off_of_feature),
                bundle_identity=jnp.asarray(efb.identity))
        if self._ic_groups is not None:
            self._feat["ic_groups"] = jnp.asarray(self._ic_groups)
        if self.config.feature_fraction_bynode < 1.0 \
                or self.config.extra_trees:
            # per-tree key injected at grow time (__boost / chunk_step)
            self._feat["ff_key"] = self._ff_key0
        if self._cegb_active():
            F = self._dd.num_feature

            def vec(v):
                out = np.zeros(F, np.float32)
                vals = list(v or [])
                out[:min(len(vals), F)] = vals[:F]
                return jnp.asarray(out)

            self._feat["cegb_coupled"] = vec(
                self.config.cegb_penalty_feature_coupled)
            self._feat["cegb_lazy"] = vec(
                self.config.cegb_penalty_feature_lazy)
            # features used anywhere in the model so far (ref: CEGB
            # feature_used_ bitmap, updated as trees land)
            self._feat["cegb_used"] = jnp.zeros(F, bool)

    def _setup_tree_learner(self) -> None:
        """Resolve `tree_learner` (+ device count) into the grower used for
        training — the TPU analog of the reference's learner factory
        (ref: tree_learner.cpp `TreeLearner::CreateTreeLearner`; the
        reference dispatches {serial,feature,data,voting} x device; here
        serial = 1-device grower and the rest are shard_map'ped over a mesh,
        see parallel/learner.py)."""
        from .parallel.learner import resolve_tree_learner
        cfg = self.config
        bundled = self._dd.efb is not None
        # quiet resolution via the shared topology resolver — warnings
        # fire once, after the cache check
        kind, shards, n_dev, dcn, use_2level, _ = self._learner_topology()
        if kind == "serial":
            self._mesh = None
            self._learner_cache_key = None
            if self._setup_streaming():
                return
            # external-memory sets keep _train_bins unresolved here: the
            # first train.chunk span assembles it (_ensure_train_bins), so
            # the per-shard H2D spans land inside the pipeline window
            self._train_bins = None if self._dd.datastore_pending else (
                self._dd.bundle_fm if bundled else self._dd.bins_fm)
            return
        # reset_parameter (lr schedules) calls this every iteration — reuse
        # the compiled grower and placed bins when nothing changed
        wave = self._grow_policy == "wave"
        key = (self._grower_spec, kind, shards, dcn if use_2level else 1,
               wave)
        if getattr(self, "_learner_cache_key", None) == key:
            return
        # cache miss → emit the one-time configuration warnings
        resolve_tree_learner(cfg.tree_learner or "serial", bundled=bundled,
                             two_level=use_2level)
        if (cfg.num_machines or 0) > n_dev:
            log.warning(f"num_machines={cfg.num_machines} exceeds visible "
                        f"devices ({n_dev}); using {n_dev}")
        if dcn > 1 and not use_2level:
            log.warning(f"cannot build a 2-level mesh from {shards} "
                        f"device(s) with tpu_dcn_slices={dcn} (need an "
                        "even division with >= 2 devices per slice); "
                        "using a flat mesh")
        if shards <= 1:
            log.warning(f"tree_learner={kind} requested but only one device "
                        "is visible; using the serial learner")
            self._mesh = None
            self._learner_cache_key = key
            if self._setup_streaming():
                return
            # external-memory: defer the assembly into the first
            # train.chunk span, exactly like the serial early-return
            self._train_bins = None if self._dd.datastore_pending else (
                self._dd.bundle_fm if bundled else self._dd.bins_fm)
            return
        self._streaming = None
        if str(cfg.streaming_train or "auto").lower() == "on":
            telemetry.REGISTRY.counter("fallback.events").inc()
            telemetry.event("fallback.stream_downgrade",
                            reasons=[f"tree_learner={kind}"])
            log.warning("streaming_train=on is not supported with "
                        f"tree_learner={kind} (shard-streamed training is "
                        "serial-only; distributed learners stream shards "
                        "once at placement instead) — training on the "
                        "placed device matrix")
        from .mesh import get_mesh, get_mesh_2level
        from .parallel.learner import make_distributed_grower, \
            place_training_data
        if use_2level:
            # 2-level mesh: heavy histogram traffic rides the ICI axis,
            # slices exchange only reduced blocks over DCN (SURVEY §2.7.5)
            self._mesh = get_mesh_2level(dcn, shards // dcn)
        else:
            self._mesh = get_mesh(shards)
        # the wave policy now runs data_rs too, so its feature axis is
        # block-padded exactly like the strict data learner's
        pad_features = (kind in ("data", "feature")
                        and self._dd.efb is None)
        if self._dd.datastore_pending and kind != "feature":
            # external-memory: stream disk shards straight to the device
            # that owns their rows (mesh/placement.py) — the host never
            # assembles the full matrix, peak residency is one device
            # slice + the prefetch window
            from .mesh.placement import place_from_datastore
            self._train_bins = _placed("bins", lambda: place_from_datastore(
                self._dd.store, self._mesh, kind,
                payload="bundle" if bundled else "bins",
                pad_features=pad_features,
                prefetch_depth=cfg.datastore_prefetch,
                collective_timeout_ms=cfg.mesh_collective_timeout_ms,
                run_stats=self._dd._pf_stats))
            # placement registered the per-device buffers under
            # `datastore.place` — the round-boundary ledger sweep must
            # not attribute the same bytes again under `train.bins`
            self._train_bins_attributed = True
        else:
            if self._dd.datastore_pending:
                log.warning("tree_learner=feature with external_memory "
                            "assembles the full device matrix before "
                            "replicating it on the mesh (features are "
                            "copied to every shard)")
            # EFB: training reads the bundled matrix (see _DeviceData);
            # dense bins go from the host straight onto the mesh
            train_src = self._dd.bundle_fm if bundled \
                else self._dd.bins_host()
            if train_src is None:
                train_src = self._dd.bins_fm
            self._train_bins = _placed("bins", lambda: place_training_data(
                np.asarray(train_src), self._mesh, kind,
                pad_features=pad_features))
            self._train_bins_attributed = False
        self._grower = make_distributed_grower(
            self._grower_spec, self._mesh, kind,
            self._dd.num_feature, self._dd.num_data, wave=wave,
            det_reduce=bool(self.config.deterministic_reduce))
        self._learner_cache_key = key
        log.info(f"tree_learner={kind}: training sharded over "
                 f"{shards} device(s)")

    def _setup_streaming(self) -> bool:
        """Engage the shard-streamed grower (lightgbm_tpu/streaming) for
        serial training: `streaming_train="on"` always (downgrade warns),
        `"auto"` only when the assembled device matrix would exceed
        `datastore_budget_mb` — the point where the budget stops being
        the real memory ceiling.  Returns True when the streamed engine
        is installed as `self._grower` (train bins never assemble)."""
        cfg = self.config
        mode = str(cfg.streaming_train or "auto").lower()
        if mode not in ("auto", "on", "off"):
            raise LightGBMError(
                f"Unknown streaming_train {mode!r} "
                "(expected 'auto', 'on' or 'off')")
        self._streaming = None
        if mode == "off":
            return False
        from .streaming import (streaming_downgrade_reasons,
                                streaming_spec)
        store = self._dd.store if self._dd.datastore_pending else None
        spec = streaming_spec(self._grower_spec, self._grow_policy)
        reasons = streaming_downgrade_reasons(spec, store)
        if self._boost_mode == "dart":
            reasons.append("boosting=dart (drop replay traverses the "
                           "resident train bins)")
        if cfg.linear_tree:
            reasons.append("linear_tree (leaf fits read the raw matrix)")
        if mode == "auto":
            if store is None:
                return False
            budget = float(cfg.datastore_budget_mb) * 2 ** 20
            if store.total_bytes("bins") <= budget:
                return False      # the assembled matrix fits the budget
            if reasons:
                # the user's budget WILL be exceeded by assembly — say so
                telemetry.REGISTRY.counter("fallback.events").inc()
                telemetry.event("fallback.stream_downgrade",
                                reasons=reasons)
                log.warning(
                    "the assembled bin matrix exceeds datastore_budget_mb"
                    f"={cfg.datastore_budget_mb} but streamed training is "
                    "not supported with " + "; ".join(reasons)
                    + " — assembling anyway (device memory is the "
                    "ceiling)")
                return False
        elif reasons:
            telemetry.REGISTRY.counter("fallback.events").inc()
            telemetry.event("fallback.stream_downgrade", reasons=reasons)
            log.warning("streaming_train=on is not supported with "
                        + "; ".join(reasons)
                        + " — using in-memory training (device memory is "
                        "the ceiling, not datastore_budget_mb)")
            return False
        depth = int(cfg.streaming_prefetch_depth or cfg.datastore_prefetch)
        key = (spec, depth)
        if getattr(self, "_stream_cache_key", None) != key:
            from .streaming import StreamingWaveGrower
            # the dataset's run-wide accounting object: streamed waves
            # and any assembly/placement prefetchers publish ONE
            # hit/stall total and one residency watermark per run
            self._stream_engine = StreamingWaveGrower(
                spec, store, prefetch_depth=depth,
                run_stats=self._dd._pf_stats,
                budget_mb=float(cfg.datastore_budget_mb))
            self._stream_cache_key = key
            log.info(
                f"streaming_train: shard-streamed training engaged "
                f"({store.n_shards} shards x ~{store.shard_rows} rows; "
                f"bins never materialize on device)")
        self._streaming = self._stream_engine
        self._grower = self._stream_engine
        self._train_bins = None
        return True

    def _ensure_train_bins(self) -> None:
        """Resolve a lazily-deferred training matrix (external-memory
        serial path).  Called inside the surrounding train.chunk span so
        the one-time shard-streaming assembly shows up as nested
        train.shard spans; later calls are no-ops."""
        if getattr(self, "_streaming", None) is not None:
            return  # streamed training: bins never assemble
        if self._train_bins is not None or getattr(self, "_dd", None) is None:
            return
        self._train_bins = self._dd.bundle_fm \
            if self._dd.efb is not None else self._dd.bins_fm

    def _row_sharding(self):
        """How the learner's mesh splits a per-row [N] array: over all its
        axes, as `place_training_data` splits the bin matrix's rows.
        None — the default device — for the serial learner, for
        feature-parallel (rows replicated), and for a row count that does
        not divide the shards (the grower pads those inside its program,
        so its inputs cannot arrive split)."""
        mesh = getattr(self, "_mesh", None)
        if mesh is None or self._learner_topology()[0] == "feature" \
                or self._dd.num_data % mesh.devices.size:
            return None
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(mesh, PartitionSpec(tuple(mesh.axis_names)))

    @staticmethod
    def _rows_of(dd: _DeviceData, value: float, k: int = 1) -> jax.Array:
        """[N] (or [N, k]) f32 of `value`, made where `dd`'s rows are."""
        shape = (dd.num_data,) if k == 1 else (dd.num_data, k)
        return jnp.full(shape, value, dtype=jnp.float32,
                        device=dd.row_sharding)

    def _zero_score(self, dd: _DeviceData) -> jax.Array:
        score = self._rows_of(dd, 0.0, self.num_tree_per_iteration)
        shape = score.shape
        if dd.init_score is not None:
            s = np.asarray(dd.init_score, dtype=np.float32)
            score = score + jnp.asarray(s.reshape(shape, order="F"))
        return score

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        """ref: basic.py `Booster.add_valid` / LGBM_BoosterAddValidData."""
        if data.reference is not self.train_set and \
                data.reference is not None and \
                data.bin_mappers is not self.train_set.bin_mappers:
            pass  # constructed against the right reference below
        if data.reference is None:
            data.reference = self.train_set
        if self.config.linear_tree:
            # valid sets also need raw values for linear-leaf scoring
            data.params = {**(data.params or {}), "linear_tree": True}
        dd = _DeviceData(data, for_train=False)
        self.valid_sets.append(data)
        self.name_valid_sets.append(name)
        self._valid_dd.append(dd)
        score = self._zero_score(dd)
        # replay existing model onto the new valid set (continued training)
        for it in range(self.cur_iter):
            for k in range(self.num_tree_per_iteration):
                tree = self.trees[it * self.num_tree_per_iteration + k]
                score = self._apply_tree_to_score(
                    score, tree, dd, k, bias_included=True)
        self._valid_scores.append(score)
        return self

    def _boost_from_average(self) -> None:
        cfg = self.config
        if (self._boost_from_average_done or self.objective_ is None
                or self._dd.init_score is not None):
            return
        self._boost_from_average_done = True
        if not cfg.boost_from_average:
            return
        label = self.train_set.get_label().astype(np.float64)
        weight = self.train_set.get_weight()
        init = self.objective_.boost_from_score(label, weight)
        inits = init if isinstance(init, list) else [init]
        K = self.num_tree_per_iteration
        if len(inits) == 1 and K > 1:
            inits = inits * K
        self._init_scores = [float(v) for v in inits]
        if any(abs(v) > 1e-35 for v in self._init_scores):
            add = np.asarray(self._init_scores, dtype=np.float32)
            if K == 1:
                self._train_score = self._train_score + add[0]
                self._valid_scores = [s + add[0] for s in self._valid_scores]
            else:
                self._train_score = self._train_score + add[None, :]
                self._valid_scores = [s + add[None, :]
                                      for s in self._valid_scores]

    def _sample_weights(self, iteration: int) -> jax.Array:
        """Bagging mask (ref: GBDT::Bagging / bagging.hpp) — fixed-shape
        0/1 weights instead of index subsets; key derivation shared with the
        fused chunk trainer (ops/fused.py) so both paths grow identical trees."""
        cfg = self.config
        n = self._dd.num_data
        if (cfg.pos_bagging_fraction < 1.0 or cfg.neg_bagging_fraction < 1.0) \
                and cfg.bagging_freq > 0:
            # per-class bagging stays host-side (label-dependent, binary
            # only); the bag renews every bagging_freq iterations
            bag_it = iteration // cfg.bagging_freq
            rng = np.random.RandomState(
                (cfg.bagging_seed + bag_it) % (2 ** 31))
            label = self.train_set.get_label()
            mask = np.zeros(n, dtype=np.float32)
            pos = label > 0
            mask[pos] = (rng.rand(int(pos.sum())) < cfg.pos_bagging_fraction)
            mask[~pos] = (rng.rand(int((~pos).sum())) <
                          cfg.neg_bagging_fraction)
            return jnp.asarray(mask)
        if cfg.bagging_freq <= 0 or cfg.bagging_fraction >= 1.0:
            return self._ones
        from .ops.fused import bagging_weights
        return bagging_weights(iteration, self._rng_key0, n,
                               bagging_fraction=cfg.bagging_fraction,
                               bagging_freq=cfg.bagging_freq)

    def _feature_mask(self, iteration: int, k: int) -> jax.Array:
        from .ops.fused import feature_mask
        base = self._dd.base_allowed_dev
        return feature_mask(iteration, k, self._ff_key0, base,
                            feature_fraction=self.config.feature_fraction)

    def _nan_check_ctx(self):
        """Per-booster numeric-sanitizer scope (tpu_debug_nans) — a
        context, not the process-global jax flag, so other boosters in
        the process are unaffected."""
        import contextlib
        return jax.debug_nans(True) if self._debug_nans \
            else contextlib.nullcontext()

    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration (ref: basic.py Booster.update →
        LGBM_BoosterUpdateOneIter → GBDT::TrainOneIter)."""
        with telemetry.span("train.chunk", rounds=1, fused=False,
                            round=self.cur_iter):
            with self._nan_check_ctx():
                out = self._update_impl(train_set, fobj)
            telemetry.REGISTRY.counter("train.rounds").inc()
            with telemetry.span("train.bookkeeping"):
                self._ledger_round()
                if self._flight is not None:
                    from .telemetry.recorder import sample_memory
                    sample_memory("train")
        return out

    def _ledger_round(self) -> None:
        """Round-boundary memory-ledger sweep: re-attribute the rebound
        O(N) training state (`assign` replaces the previous round's
        handles for the same owner), feed the leak sentinel, and emit
        the per-owner gauges into the event stream.  Host-side nbytes
        arithmetic only — never a device sync — and a strict no-op with
        the ledger disabled."""
        led = telemetry.MEMLEDGER
        if not led.enabled:
            return
        # dataset-resident device arrays: the bin matrix plus the
        # per-feature metadata / label / weight copies _DeviceData
        # pinned at construction.  When the bins were streamed straight
        # from the datastore the per-device buffers are already under
        # `datastore.place` — only the sidecar arrays go here then.
        dd = getattr(self, "_dd", None)
        bins: List[Any] = []
        if not getattr(self, "_train_bins_attributed", False):
            bins.append(getattr(self, "_train_bins", None))
        if dd is not None:
            bins += [getattr(dd, a, None) for a in
                     ("_bins_fm", "_bundle_fm", "feat_nb", "feat_missing",
                      "feat_default", "base_allowed_dev", "is_cat",
                      "label", "weight")]
        for v in (getattr(self, "_feat", None) or {}).values():
            bins.append(v)
        scores = [getattr(self, "_train_score", None),
                  getattr(self, "_ones", None),
                  getattr(self, "_obj_state", None)] \
            + list(getattr(self, "_valid_scores", []) or []) \
            + [e[-1] for e in getattr(self, "_last_contribs", []) or []]
        # identity-dedupe (serial path: `_train_bins` IS `_dd._bins_fm`)
        # — the same buffer must not be attributed twice
        seen: set = set()

        def _uniq(arrs):
            out = []
            for a in arrs:
                if getattr(a, "nbytes", None) and id(a) not in seen:
                    seen.add(id(a))
                    out.append(a)
            return out

        led.assign("train.bins", _uniq(bins))
        led.assign("train.scores", _uniq(scores))
        led.on_round()

    def _update_impl(self, train_set: Optional[Dataset] = None,
                     fobj=None) -> bool:
        if train_set is not None and train_set is not self.train_set:
            self._init_train(train_set)
        if getattr(self, "_dd", None) is None:
            raise LightGBMError(
                "Cannot train without a train set (was it freed by "
                "free_dataset()?); prediction and model IO remain "
                "available")
        self._ensure_train_bins()
        if getattr(self, "_scores_stale", False):
            # set_leaf_output mutated the model — cached scores are wrong
            self._rebuild_train_scores()
        fobj = fobj or self._fobj
        if fobj is not None and self._grower_spec.hist_impl in (
                "packed", "pallas_q"):
            # ad-hoc update(fobj=...) on a booster whose grower was
            # specialized for packed quantized histograms: custom
            # hessians may be negative, which corrupts the packed field
            raise LightGBMError(
                "update(fobj=...) cannot be combined with the packed "
                "quantized histogram; construct the Booster with "
                "objective='none' for custom objectives")
        K = self.num_tree_per_iteration
        if self._boost_mode == "dart":
            return self._update_dart(fobj)
        if fobj is None and self.objective_ is None:
            raise LightGBMError(
                "Custom objective function (fobj) is required when "
                "objective is none/custom")
        with telemetry.span("train.gradients"):
            if fobj is None:
                self._boost_from_average()
                score = self._train_score
                if self._boost_mode == "rf":
                    # RF trees are independent: gradients always taken at
                    # the constant base score (ref: rf.hpp RF::Boosting)
                    score = jnp.zeros_like(self._train_score)
                grad, hess = self._grad_fn(score)
                self.objective_.count_calls()
            else:
                preds = np.asarray(self._train_score, dtype=np.float64)
                if K > 1:
                    preds = preds.reshape(-1, order="F")
                g, h = fobj(preds, self.train_set)
                grad = jnp.asarray(np.asarray(g, dtype=np.float32)
                                   .reshape((-1, K), order="F").squeeze())
                hess = jnp.asarray(np.asarray(h, dtype=np.float32)
                                   .reshape((-1, K), order="F").squeeze())
                if K > 1:
                    grad = grad.reshape((-1, K))
                    hess = hess.reshape((-1, K))
        return self.__boost(grad, hess)

    def _goss_weights(self, iteration: int, grad, hess) -> jax.Array:
        """GOSS sample weights (ref: src/boosting/goss.hpp `GOSS::Bagging`):
        keep top_rate by |g·h|, sample other_rate of the rest, amplify the
        sampled small-gradient rows by (1-a)/b so the distribution is
        unbiased.  Fixed-shape mask instead of index subsets."""
        cfg = self.config
        n = self._dd.num_data
        # ref: GOSS waits 1/learning_rate iterations before sampling
        if iteration < int(1.0 / cfg.learning_rate):
            return self._ones
        if cfg.top_rate + cfg.other_rate >= 1.0:
            return self._ones
        from .ops.fused import goss_weights
        return goss_weights(iteration, self._rng_key0, grad, hess, n,
                            top_rate=cfg.top_rate,
                            other_rate=cfg.other_rate,
                            goss_start_iter=int(1.0 / cfg.learning_rate))

    def __boost(self, grad, hess) -> bool:
        cfg = self.config
        K = self.num_tree_per_iteration
        it = self.cur_iter
        with telemetry.span("train.sample"):
            if self._use_goss:
                # GOSS ranks the EXACT gradients; discretization happens
                # after sampling, like the reference (sample_strategy
                # before the tree learner's gradient discretizer)
                sw = self._goss_weights(it, grad, hess)
            else:
                sw = self._sample_weights(it)
        qscales = None
        if cfg.use_quantized_grad and cfg.num_grad_quant_bins > 0:
            # ref: v4 quantized training (cuda_gradient_discretizer.cu);
            # same key derivation as the fused chunk so paths agree
            from .ops.fused import quantize_gradients
            qkey = jax.random.fold_in(self._rng_key0, it * 2 + 1) \
                if cfg.stochastic_rounding else None
            if self._grower_spec.hist_impl in ("packed", "pallas_q"):
                grad, hess, qs = quantize_gradients(
                    grad, hess, cfg.num_grad_quant_bins, qkey,
                    return_scales=True,
                    const_hess_level=self._grower_spec
                    .packed_const_hess_level)
                qscales = jnp.stack(qs)
            else:
                grad, hess = quantize_gradients(
                    grad, hess, cfg.num_grad_quant_bins, qkey)
        dd = self._dd
        lr = 1.0 if self._boost_mode == "rf" else cfg.learning_rate
        all_const = True
        self._last_contribs = []  # for rollback_one_iter
        round_trees = [] if self._flight is not None else None
        for k in range(K):
            gk = grad if K == 1 else grad[:, k]
            hk = hess if K == 1 else hess[:, k]
            with telemetry.span("train.sample", k=k):
                allowed = self._feature_mask(it, k)
                feat = self._feat
                if "ff_key" in feat:
                    # fresh per-node sampling stream for each tree
                    # (ref: ColSampler per-tree reseed); same derivation
                    # as ops/fused.py chunk_step
                    feat = {**feat, "ff_key": jax.random.fold_in(
                        jax.random.fold_in(self._ff_key0, 2 ** 20 + it),
                        k)}
            if qscales is not None:
                feat = {**feat, "qscales": qscales}
            # first dispatch of a (re)built grower traces + compiles
            # synchronously — the span wall time is the compile cost
            warm = getattr(self, "_grower_warmed", None) is self._grower
            with telemetry.span("compile_warmup", kind="grower") \
                    if not warm else telemetry.NOOP:
                with telemetry.span("train.grow", k=k):
                    dev = self._grower(self._train_bins,
                                       gk.astype(jnp.float32),
                                       hk.astype(jnp.float32), sw,
                                       feat, allowed)
            self._grower_warmed = self._grower
            # the grower was only dispatched: the host waits for it in the
            # first read of its outputs.  A recorded round takes that wait
            # into a span of its own (the outputs of one program are ready
            # together), so train.decode holds the host decode alone;
            # with no recording the device_get in from_device waits as ever
            with telemetry.span("train.wait") as wait:
                if wait is not telemetry.NOOP:
                    jax.block_until_ready(dev.n_splits)
            with telemetry.span("train.decode"):
                tree = Tree.from_device(dev, self.train_set.bin_mappers, lr)
            _count_growth(tree, getattr(self._grower, "reduce_bytes", 0))
            if "cegb_used" in self._feat and tree.num_leaves > 1:
                # coupled penalties charge a feature once per MODEL
                used = np.array(jax.device_get(self._feat["cegb_used"]))
                feats = np.unique(
                    tree.split_feature[:tree.num_internal()])
                if not used[feats].all():
                    used[feats] = True
                    self._feat["cegb_used"] = jnp.asarray(used)
            if tree.num_leaves > 1:
                all_const = False
            with telemetry.span("train.score", k=k):
                contrib = self._tree_contribution(tree, dev, gk, hk, sw, lr)
                if K == 1:
                    new_train = self._train_score + contrib
                else:
                    new_train = self._train_score.at[:, k].add(contrib)
                self._last_contribs.append(("train", k, contrib))
                self._train_score = new_train
                # valid scores: bin-level traversal
                # (ref: ScoreUpdater::AddScore)
                for vi, vdd in enumerate(self._valid_dd):
                    self._valid_scores[vi] = self._apply_tree_to_score(
                        self._valid_scores[vi], tree, vdd, k,
                        bias_included=False, record=vi)
            with telemetry.span("train.bookkeeping", k=k):
                # fold init score into the stored model's first tree (ref:
                # gbdt.cpp TrainOneIter → Tree::AddBias after UpdateScore)
                if it == 0 and abs(self._init_scores[k]) > 1e-35:
                    tree.add_bias(self._init_scores[k])
                self.trees.append(tree)
                self._bump_model_version()
                if round_trees is not None:
                    round_trees.append(telemetry.tree_stats(tree))
        if round_trees is not None:
            with telemetry.span("train.bookkeeping"):
                self._flight.record_round(it, round_trees)
        self.cur_iter += 1
        if all_const:
            log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
        return all_const

    def _tree_contribution(self, tree: Tree, dev: DeviceTree, gk, hk, sw,
                           lr: float) -> jax.Array:
        """The new tree's per-row addition to the training score."""
        cfg = self.config
        if cfg.linear_tree and tree.num_leaves > 1:
            # ridge-fit linear leaves on raw values (ref:
            # linear_tree_learner.cpp `LinearTreeLearner::Train`)
            return jnp.asarray(self._fit_linear_tree(
                tree, dev, gk, hk, sw, lr).astype(np.float32))
        # L1-family leaf refit (ref: ObjectiveFunction::RenewTreeOutput →
        # serial_tree_learner.cpp RenewTreeOutput; applied pre-shrinkage)
        renew_alpha = getattr(self.objective_, "renew_percentile", None) \
            if self.objective_ is not None else None
        if renew_alpha is not None and tree.num_leaves > 1:
            scaled = self._renew_tree_output(tree, dev, sw,
                                             float(renew_alpha), lr)
        else:
            scaled = dev.leaf_value * lr
        # train score: final leaf_id from growth → one look-up a row
        return self._leaf_values(scaled, dev.leaf_id, grown=True)

    def _leaf_values(self, table, leaf_id, grown: bool = False) -> jax.Array:
        """[N] f32 `table[leaf_id]`, a tree's value for every row
        (`ops/leaf_rows.py`), counted on `score.lookup_rows` (rows the
        Pallas pass looked up) or `score.gather_rows` (rows that took the
        gather).  `grown`: the ids are the grower's own `leaf_id`, which
        a mesh's grower looks up shard by shard; a mesh's other rows
        (valid sets, a replayed tree's) are not split like them and keep
        the gather."""
        spec = self._grower_spec
        lookup = getattr(self._grower, "leaf_rows", None) if grown else None
        if lookup is None and self._mesh is None:
            lookup = functools.partial(leaf_rows, hist_impl=spec.hist_impl,
                                       interpret=spec.hist_interpret)
        on_pass = lookup is not None and \
            pass_serves(table.shape[0], spec.hist_impl)
        telemetry.REGISTRY.counter(
            "score.lookup_rows" if on_pass else "score.gather_rows").inc(
                int(leaf_id.shape[0]))
        return table[leaf_id] if lookup is None else lookup(table, leaf_id)

    def _renew_tree_output(self, tree: Tree, dev: DeviceTree, sw,
                           alpha: float, lr: float) -> jax.Array:
        """Refit leaf values as the alpha-percentile of in-leaf residuals
        (ref: regression_objective.hpp `RenewTreeOutput` — exact leaf
        optimum for L1/quantile/MAPE which their grad/hess only approximate).
        Runs entirely on device via one global (leaf, residual) sort
        (ops/renew.py) — the reference's per-leaf host loop has no business
        on a remote accelerator.  Returns the shrunken per-slot leaf values
        and rewrites the host tree in place."""
        from .ops.renew import renew_leaf_values
        dd = self._dd
        weighted, base_w = self._renew_base()
        key = (self.config.num_leaves, float(alpha), weighted)
        if getattr(self, "_renew_key", None) != key:
            self._renew_jit = jax.jit(functools.partial(
                renew_leaf_values, num_leaves=key[0], alpha=key[1],
                weighted=weighted))
            self._renew_key = key
        new_vals = self._renew_jit(dev.leaf_value,
                                   dd.label - self._train_score,
                                   base_w, sw, dev.leaf_id)
        scaled = new_vals * lr
        tree.leaf_value = np.asarray(jax.device_get(scaled),
                                     dtype=np.float64)[:tree.num_leaves]
        return scaled

    def _fit_linear_tree(self, tree: Tree, dev: DeviceTree, gk, hk, sw,
                         lr: float) -> np.ndarray:
        """Ridge-fit each leaf's linear model on the raw values of its
        path features, hessian-weighted, and return the per-row training
        contribution (ref: linear_tree_learner.cpp
        `LinearTreeLearner::CalculateLinear` — per-leaf XtHX normal
        equations with `linear_lambda` on the coefficients; rows with NaN
        in path features keep the constant leaf output)."""
        X = self._dd.get_raw()
        leaf_id = np.asarray(jax.device_get(dev.leaf_id))
        g = np.asarray(jax.device_get(gk), np.float64)
        h = np.asarray(jax.device_get(hk), np.float64)
        w = np.asarray(jax.device_get(sw), np.float64)
        lam = self.config.linear_lambda
        paths = tree.leaf_path_features()
        tree.is_linear = True
        tree.leaf_const = np.array(tree.leaf_value, np.float64)
        for leaf in range(tree.num_leaves):
            feats = paths[leaf]
            tree.leaf_features[leaf] = []
            tree.leaf_coeff[leaf] = []
            if not feats:
                continue
            rows = np.nonzero(leaf_id == leaf)[0]
            if not len(rows):
                continue
            Xl = X[np.ix_(rows, feats)]
            ok = ~np.isnan(Xl).any(axis=1) & (w[rows] > 0)
            fit = rows[ok]
            if len(fit) <= len(feats) + 1:
                continue
            A = np.concatenate([np.ones((len(fit), 1)),
                                X[np.ix_(fit, feats)]], axis=1)
            hh = (h[fit] * w[fit])[:, None]
            rhs = -(A.T @ (g[fit] * w[fit]))
            M = A.T @ (A * hh)
            M[np.arange(1, len(feats) + 1),
              np.arange(1, len(feats) + 1)] += lam
            try:
                beta = np.linalg.solve(M, rhs)
            except np.linalg.LinAlgError:
                continue
            if not np.all(np.isfinite(beta)):
                continue
            tree.leaf_const[leaf] = beta[0] * lr
            tree.leaf_features[leaf] = list(feats)
            tree.leaf_coeff[leaf] = [float(b) for b in beta[1:] * lr]
        return tree.linear_predict(X, leaf_id)

    def _apply_tree_to_score(self, score, tree: Tree, dd: _DeviceData, k: int,
                             bias_included: bool, record=None):
        if tree.is_linear and tree.num_leaves > 1:
            X = dd.get_raw()
            c = tree.linear_predict(X, tree.predict_leaf_index(X))
            contrib = jnp.asarray(c.astype(np.float32))
            if record is not None:
                self._last_contribs.append(("valid", record, k, contrib))
            if score.ndim == 1:
                return score + contrib
            return score.at[:, k].add(contrib)
        if tree.num_leaves <= 1:
            contrib = jnp.full((dd.num_data,), float(tree.leaf_value[0])
                               if bias_included else 0.0, dtype=jnp.float32)
        else:
            feat, thr, dl, left, right, iscat, catmask, v = \
                _traverse_padded(
                    tree, self.config.num_leaves, dd,
                    np.asarray(tree.leaf_value, dtype=np.float32))
            leaf_idx = _jit_traverse(feat, thr, dl, left, right, iscat,
                                     catmask, dd.feat_nb, dd.feat_missing,
                                     dd.bins_fm)
            contrib = self._leaf_values(v, leaf_idx)
        if record is not None:
            self._last_contribs.append(("valid", record, k, contrib))
        if score.ndim == 1:
            return score + contrib
        return score.at[:, k].add(contrib)

    def rollback_one_iter(self) -> "Booster":
        """Undo the last iteration (ref: GBDT::RollbackOneIter).

        The most recent iteration's contributions are cached; deeper
        rollbacks recompute the tree's contribution by bin-level traversal
        (the reference recomputes scores the same way on `ResetTrainingData`).
        """
        if self.cur_iter <= 0:
            return self
        K = self.num_tree_per_iteration
        cached = getattr(self, "_last_contribs", [])
        if cached:
            for entry in cached:
                if entry[0] == "train":
                    _, k, contrib = entry
                    if self._train_score.ndim == 1:
                        self._train_score = self._train_score - contrib
                    else:
                        self._train_score = \
                            self._train_score.at[:, k].add(-contrib)
                else:
                    _, vi, k, contrib = entry
                    if self._valid_scores[vi].ndim == 1:
                        self._valid_scores[vi] = \
                            self._valid_scores[vi] - contrib
                    else:
                        self._valid_scores[vi] = \
                            self._valid_scores[vi].at[:, k].add(-contrib)
            self._last_contribs = []
        else:
            rolling_first = self.cur_iter == 1
            for k in range(K):
                tree = self.trees[-K + k]
                bias = self._init_scores[k] if rolling_first else 0.0
                self._train_score = self._subtract_tree(
                    self._train_score, tree, self._dd, k, bias)
                for vi, vdd in enumerate(self._valid_dd):
                    self._valid_scores[vi] = self._subtract_tree(
                        self._valid_scores[vi], tree, vdd, k, bias)
        del self.trees[-K:]
        self.cur_iter -= 1
        # the freed Tree objects' ids can be handed to the very next
        # grown tree, so identity-keyed prediction caches (native /
        # device / serving export) could alias a stale model — the
        # version bump makes their keys miss (tests/test_serving.py)
        self._bump_model_version()
        return self

    def refit(self, data, label, decay_rate: float = 0.9,
              **kwargs) -> "Booster":
        """Refit the existing model's leaf values on new data, keeping every
        tree's structure (ref: basic.py `Booster.refit` → LGBM_BoosterRefit
        → gbdt.cpp `GBDT::RefitTree` → serial_tree_learner.cpp
        `SerialTreeLearner::FitByExistingTree`): route the new rows through
        each tree, recompute leaf outputs from the new data's grad/hess via
        the closed form, and blend `decay_rate*old + (1-decay_rate)*new`.
        Trees are processed in boosting order with scores updated as it
        goes, so later trees see the refit of earlier ones — exactly the
        reference's loop.  Returns a NEW Booster."""
        if self.objective_ is None:
            raise LightGBMError("Cannot refit due to null objective function")
        new_bst = Booster(model_str=self.model_to_string(num_iteration=-1),
                          params={**{k: v for k, v in self.params.items()
                                     if not callable(v)}, "verbosity": -1})
        X = _to_2d_float(data)
        y = np.asarray(label, dtype=np.float64).reshape(-1)
        n = X.shape[0]
        if len(y) != n:
            raise LightGBMError("Length of label is not same with #data")
        weight = kwargs.get("weight")
        group = kwargs.get("group")
        qb = None
        if group is not None:
            qb = np.concatenate([[0], np.cumsum(np.asarray(group,
                                                           np.int64))])
        obj = new_bst.objective_
        obj.init_meta(y, np.asarray(weight, np.float64)
                      if weight is not None else None, qb)
        cfg = self.config
        K = self.num_tree_per_iteration
        lr = 1.0 if getattr(self, "_average_output", False) \
            else cfg.learning_rate

        def host_leaf_output(g, h):
            # mirror ops/split.py leaf_output in f64
            t = np.sign(g) * np.maximum(np.abs(g) - cfg.lambda_l1, 0.0)
            denom = h + cfg.lambda_l2
            out = np.where(denom > 0, -t / np.where(denom > 0, denom, 1.0),
                           0.0)
            if cfg.max_delta_step > 0:
                out = np.clip(out, -cfg.max_delta_step, cfg.max_delta_step)
            return out

        label_j = jnp.asarray(y.astype(np.float32))
        w_j = jnp.asarray(np.asarray(weight, np.float32)) \
            if weight is not None else None
        score = np.zeros(n if K == 1 else (n, K), np.float32)
        is_rf = bool(getattr(self, "_average_output", False))
        key0 = jax.random.PRNGKey(cfg.objective_seed % (2 ** 31))
        for it in range(len(new_bst.trees) // K):
            # RF gradients are taken at the constant base score, never the
            # accumulated tree sum (ref: rf.hpp RF::Boosting)
            grad_at = jnp.asarray(np.zeros_like(score) if is_rf else score)
            if getattr(obj, "needs_rng", False):
                g, h = obj.grad_hess(grad_at, label_j, w_j,
                                     key=jax.random.fold_in(key0, it))
            else:
                g, h = obj.grad_hess(grad_at, label_j, w_j)
            g = np.asarray(jax.device_get(g), np.float64)
            h = np.asarray(jax.device_get(h), np.float64)
            for k in range(K):
                t = new_bst.trees[it * K + k]
                gk = g if K == 1 else g[:, k]
                hk = h if K == 1 else h[:, k]
                li = t.predict_leaf_index(X)
                nl = t.num_leaves
                sg = np.bincount(li, weights=gk, minlength=nl)
                sh = np.bincount(li, weights=hk, minlength=nl)
                cnt = np.bincount(li, minlength=nl)
                new_out = host_leaf_output(sg, sh) * lr
                old = np.asarray(t.leaf_value, np.float64)
                # leaves no new row reaches keep their old output
                mixed = np.where(cnt > 0, decay_rate * old
                                 + (1.0 - decay_rate) * new_out, old)
                t.leaf_value = mixed
                contrib = mixed[li].astype(np.float32)
                if K == 1:
                    score = score + contrib
                else:
                    score[:, k] += contrib
        # the loop rewrote leaf_value IN PLACE on new_bst's trees —
        # their ids never changed, so any prediction/serving cache the
        # refit walk populated must be dropped (and the version bumped)
        new_bst._invalidate_pred_caches()
        return new_bst

    # ------------------------------------------------- fused bulk training
    _BULK_CHUNK = 16

    def _bulk_eligible(self, with_eval: bool = False) -> bool:
        """Can training run as compiled device-side chunks?

        DART is excluded by design: its per-iteration drop/renormalize
        rescales ALREADY-DECODED host trees, which is inherently a host
        round-trip (ref: dart.hpp `DART::Normalize`)."""
        cfg = self.config
        ok = (self._fobj is None and self.objective_ is not None
              and self._boost_mode in ("gbdt", "rf")
              # streamed training is host-driven per wave — it cannot run
              # inside a fused device-side chunk
              and getattr(self, "_streaming", None) is None
              # CEGB coupled penalties mutate per-model host state;
              # linear-leaf ridge fits run on the host raw matrix;
              # stateful objectives (position-debiased lambdarank) update
              # propensities per iteration on the host side
              and not self._cegb_active()
              and not getattr(self.objective_, "has_state", False)
              and not cfg.linear_tree
              and cfg.pos_bagging_fraction >= 1.0
              and cfg.neg_bagging_fraction >= 1.0)
        if not ok:
            return False
        if not with_eval and self._valid_dd:
            return False
        return True

    def _make_bulk_spec(self, n_valid: int = 0, emit_train: bool = False):
        from .ops.fused import BulkSpec
        cfg = self.config
        rp = getattr(self.objective_, "renew_percentile", None)
        return BulkSpec(
            grower=self._grower_spec, chunk=self._BULK_CHUNK,
            num_class=self.num_tree_per_iteration,
            learning_rate=cfg.learning_rate,
            bagging_fraction=cfg.bagging_fraction,
            bagging_freq=cfg.bagging_freq,
            use_goss=self._use_goss
            and cfg.top_rate + cfg.other_rate < 1.0,
            top_rate=cfg.top_rate,
            other_rate=cfg.other_rate,
            goss_start_iter=int(1.0 / cfg.learning_rate),
            feature_fraction=cfg.feature_fraction,
            rf=self._boost_mode == "rf",
            needs_rng=getattr(self.objective_, "needs_rng", False),
            n_valid=n_valid, emit_train_scores=emit_train,
            renew_alpha=float(rp) if rp is not None else -1.0,
            renew_weighted=self._renew_base()[0],
            quant_bins=cfg.num_grad_quant_bins
            if cfg.use_quantized_grad else 0,
            quant_stochastic=cfg.stochastic_rounding)

    def _renew_base(self):
        """(weighted, base row weight) for the L1-family percentile refit —
        the single source of truth shared by the per-iteration path
        (_renew_tree_output) and the fused chunk (_bulk_trainer)."""
        weighted = self._dd.weight is not None \
            or self.config.objective == "mape"
        base_w = self._dd.weight if self._dd.weight is not None \
            else self._ones
        if self.config.objective == "mape":
            # ref: MAPE label_weight_ = 1/max(1, |label|)
            base_w = base_w / jnp.maximum(1.0, jnp.abs(self._dd.label))
        return weighted, base_w

    def _bulk_trainer(self, spec):
        from .ops.fused import make_bulk_trainer
        # the cache key includes the learner AND grow policy so switching
        # tree_learner / mesh / tree_grow_policy via reset_parameter
        # rebuilds the trainer closure
        key = (spec, getattr(self, "_learner_cache_key", None),
               self._grow_policy)
        if getattr(self, "_bulk_key", None) != key:
            grad = self._grad_rng_fn if spec.needs_rng else self._grad_fn
            renew_args = None
            if spec.renew_alpha >= 0.0:
                renew_args = (self._dd.label, self._renew_base()[1])
            # distributed meshes plug the shard_map'ped grower into the
            # chunk trainer — multi-chip training also fuses; the wave
            # policy's grower likewise rides in explicitly (the trainer's
            # default is the strict serial grower)
            grow_fn = self._grower \
                if (self._mesh is not None
                    or self._grow_policy == "wave") else None
            self._bulk_trainer_cache = make_bulk_trainer(spec, grad,
                                                         renew_args,
                                                         grow_fn)
            self._bulk_key = key
        return self._bulk_trainer_cache

    def _pipeline_depth(self) -> int:
        """Max fused chunks in flight (`tpu_pipeline_chunks`, floor 1)."""
        return max(1, int(self.config.tpu_pipeline_chunks or 1))

    def _dispatch_chunk(self, spec) -> _PendingChunk:
        """Enqueue ONE compiled chunk and return without waiting for it.

        JAX async dispatch makes the jitted call return device-side
        futures; the score carries are rebound to those futures at once,
        so chunk k+1 can be dispatched (its inputs are chunk k's
        device-side outputs) while chunk k still runs — the host decode/
        eval of chunk k then overlaps chunk k+1's device compute."""
        trainer = self._bulk_trainer(spec)
        # first dispatch of a (re)built trainer traces + compiles the whole
        # chunk program synchronously — span it as compile_warmup
        warm = getattr(self, "_bulk_warm_key", None) == self._bulk_key
        dd = self._dd
        valid_bins = tuple(v.bins_fm for v in self._valid_dd[:spec.n_valid])
        # cur_iter only advances when a chunk is harvested (decoded), so
        # in-flight rounds must be added back for the RNG stream index
        it0 = self.cur_iter + self._pending_iters
        telemetry.REGISTRY.gauge("train.pipeline.depth").set(
            self._pipeline_depth())
        with telemetry.span("train.chunk", rounds=spec.chunk, fused=True,
                            round=it0):
            self._ensure_train_bins()
            with telemetry.span("compile_warmup", kind="bulk_trainer") \
                    if not warm else telemetry.NOOP, self._nan_check_ctx():
                score, vfinal, stacked, v_iter, t_iter = trainer(
                    self._train_score,
                    tuple(self._valid_scores[:spec.n_valid]),
                    jnp.int32(it0), self._rng_key0, self._ff_key0,
                    self._grad_key0, self._train_bins, self._feat,
                    dd.base_allowed_dev, valid_bins)
        self._bulk_warm_key = self._bulk_key
        # rebind the (donated) score carries to the chunk's outputs NOW:
        # the old buffers are dead the moment the trainer returns, and the
        # next dispatch reads these futures without any host sync
        self._train_score = score
        if spec.n_valid:
            self._valid_scores[:spec.n_valid] = list(vfinal)
        self._pending_iters += spec.chunk
        self.objective_.count_calls(spec.chunk)
        pend = _PendingChunk(spec, stacked, t_iter, v_iter, it0,
                             time.perf_counter())
        self._inflight.append(pend)
        return pend

    def _harvest_chunk(self, pending: _PendingChunk):
        """Block on a dispatched chunk's outputs and decode them.

        Returns (finished, per-iter train scores or None, per-valid list
        of per-iter scores) — `_run_chunk`'s contract.  Must be called in
        dispatch order: tree decode appends to `self.trees`
        sequentially."""
        if not self._inflight or self._inflight[0] is not pending:
            raise LightGBMError("pipeline harvest out of dispatch order")
        self._inflight.popleft()
        spec = pending.spec
        with telemetry.span("train.harvest", rounds=spec.chunk,
                            round=pending.it0):
            # ONE device→host transfer for the trees AND every score
            # snapshot — each separate device_get pays a full
            # host<->device round trip (same batching Tree.from_device
            # got in tree.py)
            host, t_host, v_host = jax.device_get(
                (pending.stacked, pending.t_iter, pending.v_iter))
            ready_t = time.perf_counter()
            self._note_pipeline_gap(pending.dispatch_t, ready_t)
            with telemetry.span("train.decode", rounds=spec.chunk):
                finished = self._decode_stacked(host)
            t_np = np.asarray(t_host) if spec.emit_train_scores else None
            v_np = [np.asarray(v) for v in v_host]
        self._pending_iters -= spec.chunk
        telemetry.REGISTRY.counter("train.rounds").inc(spec.chunk)
        telemetry.REGISTRY.counter("train.chunks").inc()
        if self._flight is not None:
            from .telemetry.recorder import sample_memory
            sample_memory("train")
        return finished, t_np, v_np

    def _note_pipeline_gap(self, dispatch_t: float, ready_t: float) -> None:
        """Record the device-idle-per-chunk ESTIMATE: the gap between the
        previous chunk's outputs being ready (its device_get returning)
        and this chunk's dispatch.  Serial schedules pay the whole host
        decode/eval there; a pipelined schedule dispatched this chunk
        before the previous harvest, so the gap clamps to ~0.  An
        estimate — host-side timestamps can't see inside the XLA queue —
        but its trend is the pipeline's win, and `telemetry diff`
        sentinels it as a timing-class metric."""
        prev_ready = self._pipe_prev_ready_t
        self._pipe_prev_ready_t = ready_t
        if prev_ready is None:
            return
        idle = max(0.0, dispatch_t - prev_ready)
        telemetry.REGISTRY.gauge(
            "train.pipeline.device_idle_s").set(round(idle, 6))
        telemetry.REGISTRY.timing("train.pipeline.idle").observe(idle)

    def _run_chunk(self, spec):
        """Run ONE compiled chunk synchronously (dispatch + harvest
        back-to-back); returns (finished, per-iter train scores or None,
        per-valid list of per-iter scores)."""
        return self._harvest_chunk(self._dispatch_chunk(spec))

    def update_many(self, n_rounds: int) -> bool:
        """Run `n_rounds` boosting iterations, fusing them into compiled
        device-side chunks when nothing needs the host in between.  Falls
        back to per-iteration updates otherwise.  Returns the final
        `update()`-style is_finished flag.

        Chunks are pipelined up to `tpu_pipeline_chunks` in flight: the
        device computes chunk k+1 while the host decodes chunk k's trees
        (byte-identical models at any depth — only the SCHEDULE moves)."""
        finished = False
        remaining = n_rounds
        if self._bulk_eligible() and remaining >= self._BULK_CHUNK:
            self._boost_from_average()
            spec = self._make_bulk_spec()
            depth = self._pipeline_depth()
            while remaining >= self._BULK_CHUNK:
                self._dispatch_chunk(spec)
                remaining -= self._BULK_CHUNK
                if len(self._inflight) >= depth:
                    finished, _, _ = self._harvest_chunk(self._inflight[0])
            while self._inflight:
                finished, _, _ = self._harvest_chunk(self._inflight[0])
        for _ in range(remaining):
            finished = self.update()
        return finished

    def dispatch_chunk_eval(self, want_train_scores: bool) -> _PendingChunk:
        """Dispatch one fused chunk WITH per-iteration train/valid score
        emission and return its pending handle without waiting — the
        engine's chunked-eval loop uses this to run chunk k+1 on the
        device speculatively while chunk k's metrics/callbacks run on the
        host (early stopping rolls the speculated trees back)."""
        self._boost_from_average()
        spec = self._make_bulk_spec(n_valid=len(self._valid_dd),
                                    emit_train=want_train_scores)
        return self._dispatch_chunk(spec)

    def harvest_chunk_eval(self, pending: _PendingChunk):
        """Harvest a `dispatch_chunk_eval` chunk.  Returns (finished,
        train_scores [C, ...] | None, [valid_scores [C, ...]])."""
        return self._harvest_chunk(pending)

    def update_chunk_eval(self, want_train_scores: bool):
        """One fused chunk WITH per-iteration train/valid score emission —
        the engine evaluates metrics/callbacks from the emitted scores, so
        eval-driven training (early stopping) syncs once per chunk.
        Returns (finished, train_scores [C, ...] | None,
        [valid_scores [C, ...]])."""
        return self.harvest_chunk_eval(
            self.dispatch_chunk_eval(want_train_scores))

    def eval_with_scores(self, score_np: np.ndarray, data, name: str,
                         feval, it_count: int):
        """Evaluate metrics on an emitted per-iteration score snapshot
        (chunked-eval path; mirrors `_eval_score` + `_eval_one`)."""
        s = np.asarray(score_np, dtype=np.float64)
        if self._average_output and it_count > 0:
            s = s / it_count
        return self._eval_one(s, data, name, feval)

    def _decode_stacked(self, host) -> bool:
        """Decode a chunk of stacked trees into host Tree objects.  `host`
        is the already-transferred pytree — `_harvest_chunk` batches the
        tree readback with the score snapshots into one device_get."""
        K = self.num_tree_per_iteration
        # RF trees carry no shrinkage (must match the in-chunk score math)
        lr = 1.0 if self._boost_mode == "rf" else self.config.learning_rate
        chunk = host.n_splits.shape[0]
        all_const = True
        for c in range(chunk):
            round_trees = [] if self._flight is not None else None
            for k in range(K):
                at = c if K == 1 else (c, k)
                dev = DeviceTree(*[None if f is None else np.asarray(f[at])
                                   for f in host])
                tree = Tree.from_device(dev, self.train_set.bin_mappers, lr)
                _count_growth(tree, getattr(self._grower, "reduce_bytes", 0))
                if tree.num_leaves > 1:
                    all_const = False
                if self.cur_iter == 0 and abs(self._init_scores[k]) > 1e-35:
                    tree.add_bias(self._init_scores[k])
                self.trees.append(tree)
                self._bump_model_version()
                if round_trees is not None:
                    round_trees.append(telemetry.tree_stats(tree))
            if round_trees is not None:
                self._flight.record_round(
                    self.cur_iter, round_trees,
                    pipeline_depth=self._pipeline_depth())
            self.cur_iter += 1
        self._last_contribs = []
        return all_const

    def _update_dart(self, fobj=None) -> bool:
        """DART iteration (ref: src/boosting/dart.hpp `DART::TrainOneIter`:
        `DroppingTrees` → re-score without dropped trees → train → `Normalize`)."""
        cfg = self.config
        K = self.num_tree_per_iteration
        it = self.cur_iter
        if fobj is None and self.objective_ is None:
            raise LightGBMError("Custom objective function (fobj) is "
                                "required when objective is none/custom")
        self._boost_from_average()
        rng = np.random.RandomState((cfg.drop_seed + it) % (2 ** 31))
        dropped: List[int] = []
        if it > 0 and rng.rand() >= cfg.skip_drop:
            sel = np.nonzero(rng.rand(it) < cfg.drop_rate)[0]
            if cfg.max_drop > 0 and len(sel) > cfg.max_drop:
                sel = rng.choice(sel, cfg.max_drop, replace=False)
            if len(sel) == 0:
                sel = np.array([rng.randint(it)])
            dropped = sorted(int(d) for d in sel)
        # drop: remove their contributions from all running scores
        for d in dropped:
            for k in range(K):
                tree = self.trees[d * K + k]
                self._train_score = self._subtract_tree(
                    self._train_score, tree, self._dd, k, 0.0)
                for vi, vdd in enumerate(self._valid_dd):
                    self._valid_scores[vi] = self._subtract_tree(
                        self._valid_scores[vi], tree, vdd, k, 0.0)
        if fobj is not None:
            preds = np.asarray(self._train_score, dtype=np.float64)
            if K > 1:
                preds = preds.reshape(-1, order="F")
            g, h = fobj(preds, self.train_set)
            grad = jnp.asarray(np.asarray(g, dtype=np.float32)
                               .reshape((-1, K), order="F").squeeze())
            hess = jnp.asarray(np.asarray(h, dtype=np.float32)
                               .reshape((-1, K), order="F").squeeze())
            if K > 1:
                grad = grad.reshape((-1, K))
                hess = hess.reshape((-1, K))
        else:
            grad, hess = self._grad_fn(self._train_score)
            self.objective_.count_calls()
        finished = self.__boost(grad, hess)
        kdrop = len(dropped)
        if kdrop > 0:
            # ref: DART::Normalize
            if cfg.xgboost_dart_mode:
                new_scale = cfg.learning_rate / (kdrop + cfg.learning_rate)
                old_scale = kdrop / (kdrop + cfg.learning_rate)
            else:
                new_scale = 1.0 / (kdrop + 1.0)
                old_scale = kdrop / (kdrop + 1.0)
            self._invalidate_pred_caches()  # in-place value rescaling
            for k in range(K):
                tree = self.trees[-K + k]
                tree.leaf_value = tree.leaf_value * new_scale
                tree.internal_value = tree.internal_value * new_scale
                tree.shrinkage *= new_scale
            # new trees entered the scores at full scale: shave the excess
            for entry in self._last_contribs:
                if entry[0] == "train":
                    _, k, contrib = entry
                    adj = contrib * (1.0 - new_scale)
                    if self._train_score.ndim == 1:
                        self._train_score = self._train_score - adj
                    else:
                        self._train_score = \
                            self._train_score.at[:, k].add(-adj)
                else:
                    _, vi, k, contrib = entry
                    adj = contrib * (1.0 - new_scale)
                    if self._valid_scores[vi].ndim == 1:
                        self._valid_scores[vi] = self._valid_scores[vi] - adj
                    else:
                        self._valid_scores[vi] = \
                            self._valid_scores[vi].at[:, k].add(-adj)
            self._last_contribs = []
            # dropped trees come back rescaled
            for d in dropped:
                for k in range(K):
                    tree = self.trees[d * K + k]
                    tree.leaf_value = tree.leaf_value * old_scale
                    tree.internal_value = tree.internal_value * old_scale
                    tree.shrinkage *= old_scale
                    self._train_score = self._apply_tree_to_score(
                        self._train_score, tree, self._dd, k,
                        bias_included=True)
                    for vi, vdd in enumerate(self._valid_dd):
                        self._valid_scores[vi] = self._apply_tree_to_score(
                            self._valid_scores[vi], tree, vdd, k,
                            bias_included=True)
        return finished

    def _subtract_tree(self, score, tree: Tree, dd: _DeviceData, k: int,
                       bias: float):
        """score -= tree(bins) where the stored tree may carry a folded-in
        bias that the running score tracks separately.  Mirrors
        `_apply_tree_to_score` exactly, including the constant-tree case."""
        if tree.is_linear and tree.num_leaves > 1:
            X = dd.get_raw()
            c = tree.linear_predict(X, tree.predict_leaf_index(X)) - bias
            contrib = jnp.asarray(c.astype(np.float32))
            if score.ndim == 1:
                return score - contrib
            return score.at[:, k].add(-contrib)
        if tree.num_leaves <= 1:
            const = float(tree.leaf_value[0]) - bias \
                if len(tree.leaf_value) else 0.0
            if const == 0.0:
                return score
            if score.ndim == 1:
                return score - const
            return score.at[:, k].add(-const)
        feat, thr, dl, left, right, iscat, catmask, v = _traverse_padded(
            tree, self.config.num_leaves, dd,
            np.asarray(tree.leaf_value - bias, dtype=np.float32))
        leaf_idx = _jit_traverse(feat, thr, dl, left, right, iscat, catmask,
                                 dd.feat_nb, dd.feat_missing, dd.bins_fm)
        contrib = self._leaf_values(v, leaf_idx)
        if score.ndim == 1:
            return score - contrib
        return score.at[:, k].add(-contrib)

    # ------------------------------------------------------------------ eval
    def _eval_one(self, score: np.ndarray, ds: Dataset, data_name: str,
                  feval) -> List[Tuple[str, str, float, bool]]:
        with telemetry.span("eval", dataset=data_name):
            res = self._eval_one_impl(score, ds, data_name, feval)
        if self._flight is not None:
            # eval runs AFTER its round on both training paths; the
            # recorder folds the values into its eval series and amends
            # the latest ring record in place
            self._flight.note_eval(data_name, res)
            from .telemetry.recorder import sample_memory
            sample_memory("eval")
        return res

    def _eval_one_impl(self, score: np.ndarray, ds: Dataset, data_name: str,
                       feval) -> List[Tuple[str, str, float, bool]]:
        label = ds.get_label()
        weight = ds.get_weight()
        qb = ds._query_boundaries
        label64 = label.astype(np.float64) if label is not None else None
        w64 = weight.astype(np.float64) if weight is not None else None
        out = []
        for m in self.metrics_:
            for name, val in m.eval(score, label64, w64, qb):
                out.append((data_name, name, val, m.higher_better))
        if feval is not None:
            preds = score
            if self.objective_ is not None and self._fobj is None and \
                    self.objective_.need_convert:
                preds = np.asarray(jax.device_get(
                    self.objective_.convert_output(jnp.asarray(score))))
            fevals = feval if isinstance(feval, (list, tuple)) else [feval]
            for fe in fevals:
                res = fe(preds.reshape(-1, order="F")
                         if preds.ndim > 1 else preds, ds)
                if isinstance(res, list):
                    for name, val, hib in res:
                        out.append((data_name, name, val, hib))
                elif res is not None:
                    name, val, hib = res
                    out.append((data_name, name, val, hib))
        return out

    def _eval_score(self, score) -> np.ndarray:
        s = np.asarray(score, dtype=np.float64)
        if self._average_output and self.cur_iter > 0:
            s = s / self.cur_iter
        return s

    def _require_train_data(self) -> None:
        if self.train_set is None or getattr(self, "_dd", None) is None:
            raise LightGBMError(
                "No training data attached (was it freed by "
                "free_dataset()?)")
        if getattr(self, "_scores_stale", False):
            # set_leaf_output mutated the model — eval must see it too
            self._rebuild_train_scores()

    def eval_train(self, feval=None) -> List[Tuple[str, str, float, bool]]:
        # ref: basic.py Booster.eval_train reports under _train_data_name
        self._require_train_data()
        return self._eval_one(self._eval_score(self._train_score),
                              self.train_set,
                              getattr(self, "_train_data_name", "training"),
                              feval)

    def eval_valid(self, feval=None) -> List[Tuple[str, str, float, bool]]:
        self._require_train_data()
        out = []
        for name, ds, score in zip(self.name_valid_sets, self.valid_sets,
                                   self._valid_scores):
            out.extend(self._eval_one(self._eval_score(score), ds, name,
                                      feval))
        return out

    def eval(self, data: Dataset, name: str, feval=None):
        if data is self.train_set:
            return self.eval_train(feval)
        self._require_train_data()
        for i, vs in enumerate(self.valid_sets):
            if data is vs:
                return self._eval_one(self._eval_score(self._valid_scores[i]),
                                      data, name, feval)
        raise LightGBMError("Data for eval must be training or validation "
                            "data (use add_valid first)")

    # --------------------------------------------------------------- predict
    def _slice_trees(self, start_iteration: int,
                     num_iteration: Optional[int]) -> List[Tree]:
        K = self.num_tree_per_iteration
        if num_iteration is None:
            num_iteration = self.best_iteration \
                if self.best_iteration > 0 else -1
        if num_iteration <= 0:
            end = len(self.trees)
        else:
            end = min((start_iteration + num_iteration) * K, len(self.trees))
        return self.trees[start_iteration * K: end]

    def predict(self, data, start_iteration: int = 0,
                num_iteration: Optional[int] = None, raw_score: bool = False,
                pred_leaf: bool = False, pred_contrib: bool = False,
                data_has_header: bool = False, validate_features: bool = False,
                **kwargs) -> np.ndarray:
        """ref: basic.py Booster.predict → gbdt_prediction.cpp."""
        if isinstance(data, str):
            # text-file prediction (ref: Application task=predict /
            # Predictor file path) — same format as training files, label
            # column present and ignored
            from .cli import load_data_file
            data, _ = load_data_file(
                data, Config({k: v for k, v in self.params.items()
                              if not callable(v)}))
        X = _to_2d_float(data)
        n = X.shape[0]
        K = self.num_tree_per_iteration
        trees = self._slice_trees(start_iteration, num_iteration)
        telemetry.REGISTRY.counter("predict.rows").inc(n)
        if pred_leaf:
            out = np.zeros((n, len(trees)), dtype=np.int32)
            for i, t in enumerate(trees):
                out[:, i] = t.predict_leaf_index(X)
            return out
        if pred_contrib:
            return self._predict_contrib(X, trees)
        # per-row prediction early stop (ref: prediction_early_stop.cpp —
        # binary: 2|score| >= margin; multiclass: top1-top2 >= margin,
        # checked every pred_early_stop_freq tree groups)
        def _b(v):  # params reloaded from model text are strings
            return str(v).lower() in ("true", "1") if isinstance(v, str) \
                else bool(v)

        es = _b(kwargs.get("pred_early_stop",
                           self.params.get("pred_early_stop", False)))
        obj_name = getattr(getattr(self, "config", None), "objective", "")
        es = es and (obj_name == "binary" or K > 1)
        # TPU batch path (opt-in `device_predict=True`): one jitted
        # scan-of-vmapped-traversals over stacked padded trees
        # (ops/predict.py predict_raw_ensemble) instead of the host
        # per-tree walk — the batched analog of predictor.hpp's OpenMP
        # row loop.  Covers categorical splits (r5: per-node bitset
        # planes) and multiclass (r6: per-tree class plane, [N, K]
        # carry); falls back silently to the host path for linear trees
        # and prediction early stop.
        if (_b(kwargs.get("device_predict",
                          self.params.get("device_predict", False)))
                and not es):
            # the stacked ensemble is model-constant: cache the padded
            # arrays (and their device copies) across calls, keyed by
            # the resolved slice's object identity (stale on any model
            # replacement; in-place mutations invalidate explicitly)
            ck = self._tree_slice_key(trees) if trees else None
            cached = getattr(self, "_pred_dev_cache", None)
            stacked = cached[1] if ck and cached and cached[0] == ck \
                else self._stack_for_device(trees)
            # cache as soon as stacking succeeds — BEFORE the X-width
            # gate, so repeated too-narrow predict calls don't re-stack
            # (and re-upload) the full model each time (ADVICE r4)
            if ck and stacked is not None:
                self._pred_dev_cache = (ck, stacked)
            if stacked is not None and X.shape[1] >= stacked["min_features"]:
                with telemetry.span("predict.device", rows=n,
                                    trees=len(trees)):
                    raw = self._predict_raw_device(stacked, X, K)
                if self._flight is not None:
                    from .telemetry.recorder import sample_memory
                    sample_memory("predict")
                # same RF divisor as the host path (rounds, not trees —
                # identical for K == 1)
                if getattr(self, "_average_output", False) \
                        and len(trees) >= K:
                    raw = raw / max(len(trees) // K, 1)
                if raw_score or self.objective_ is None:
                    return raw
                return np.asarray(jax.device_get(
                    self.objective_.convert_output(jnp.asarray(raw))))
        raw = None  # allocated by whichever path fills it
        with telemetry.span("predict.host", rows=n, trees=len(trees)):
            if es and len(trees):
                raw = np.zeros((n, K), dtype=np.float64)
                freq = int(kwargs.get(
                    "pred_early_stop_freq",
                    self.params.get("pred_early_stop_freq", 10)))
                margin = float(kwargs.get(
                    "pred_early_stop_margin",
                    self.params.get("pred_early_stop_margin", 10.0)))
                active = np.ones(n, dtype=bool)
                all_active = True  # avoid masked copies until row decided
                for i, t in enumerate(trees):
                    if all_active:
                        raw[:, i % K] += t.predict(X)
                    else:
                        if not active.any():
                            break
                        raw[active, i % K] += t.predict(X[active])
                    if (i + 1) % (max(freq, 1) * K) == 0:
                        if K == 1:
                            decided = 2.0 * np.abs(raw[:, 0]) >= margin
                        else:
                            part = np.partition(raw, K - 2, axis=1)
                            decided = (part[:, K - 1] - part[:, K - 2]) \
                                >= margin
                        active &= ~decided
                        all_active = bool(active.all())
            else:
                # native tight-loop ensemble walk (ref: predictor.hpp +
                # c_api.cpp PredictSingleRowFast: model arrays resolved
                # once, each call is pure traversal; tree i accumulates
                # into class i % K like the reference's interleaving).
                # Exact f64 drop-in for the numpy path — same decision
                # semantics, same tree-order summation — so no behavior
                # flag is needed.  The library check comes FIRST (no point
                # flattening a model copy on toolchain-less hosts), and a
                # too-narrow X skips to the numpy path so it raises the
                # same IndexError it always did.
                from . import native
                nr = None
                flat = self._flatten_for_native(trees) \
                    if native.get_lib() is not None else None
                if flat is not None and X.shape[1] >= flat["min_features"]:
                    # num_threads rides per call (works for loaded models
                    # too — model_from_string builds self.config; no global
                    # OpenMP state, so concurrent boosters can't clobber
                    # each other)
                    nthr = int(getattr(self.config, "num_threads", 0) or 0)
                    nr = native.predict_rows(flat, X, K, nthr)
                if nr is not None:
                    raw = nr            # the C walk zero-inits and fills
                else:
                    raw = np.zeros((n, K), dtype=np.float64)
                    for i, t in enumerate(trees):
                        raw[:, i % K] += t.predict(X)
        if getattr(self, "_average_output", False) and len(trees) >= K:
            raw /= max(len(trees) // K, 1)
        if K == 1:
            raw = raw[:, 0]
        if raw_score or self.objective_ is None:
            return raw
        return np.asarray(jax.device_get(
            self.objective_.convert_output(jnp.asarray(raw))))

    def _stack_for_device(self, trees: List[Tree]):
        """Pad host trees into the stacked [T, NI]/[T, NL] arrays that
        `ops.predict.predict_raw_ensemble` scans.  Categorical ensembles
        (r5) add per-node bitset planes `cat_words` [T, NI, MW] +
        `cat_nwords` [T, NI] (MW = widest bitset in the ensemble; the
        per-node word count drives the same double-space range guard as
        the host walks).  Returns None only for linear leaves — callers
        fall back to the host walk."""
        if not trees or any(t.is_linear for t in trees):
            return None
        ni = max(max(t.num_leaves - 1, 1) for t in trees)
        T = len(trees)
        feat = np.zeros((T, ni), np.int32)
        thr = np.zeros((T, ni), np.float32)
        dtype_ = np.zeros((T, ni), np.int32)
        # pad nodes route to leaf 0 (~0 = -1): a single-leaf tree's root
        # terminates immediately with its constant value
        left = np.full((T, ni), -1, np.int32)
        right = np.full((T, ni), -1, np.int32)
        value = np.zeros((T, ni + 1), np.float32)
        has_cat = any(t.num_cat > 0 for t in trees)
        if has_cat:
            mw = 1
            for t in trees:
                if t.num_cat > 0 and len(t.cat_boundaries) > 1:
                    mw = max(mw, int(np.max(np.diff(t.cat_boundaries))))
            cat_words = np.zeros((T, ni, mw), np.uint32)
            cat_nwords = np.zeros((T, ni), np.int32)
        for i, t in enumerate(trees):
            k = t.num_leaves - 1
            feat[i, :k] = t.split_feature[:k]
            thr[i, :k] = t.threshold[:k]
            dtype_[i, :k] = t.decision_type[:k]
            left[i, :k] = t.left_child[:k]
            right[i, :k] = t.right_child[:k]
            value[i, :t.num_leaves] = t.leaf_value[:t.num_leaves]
            if has_cat and t.num_cat > 0:
                for nd in range(k):
                    if t.decision_type[nd] & 1:
                        cb = int(t.threshold_bin[nd])
                        lo = int(t.cat_boundaries[cb])
                        hi = int(t.cat_boundaries[cb + 1])
                        cat_nwords[i, nd] = hi - lo
                        cat_words[i, nd, :hi - lo] = t.cat_threshold[lo:hi]
        out = dict(feat=jnp.asarray(feat), thr=jnp.asarray(thr),
                   dtype=jnp.asarray(dtype_), left=jnp.asarray(left),
                   right=jnp.asarray(right), value=jnp.asarray(value),
                   min_features=int(feat.max()) + 1 if feat.size else 0)
        if has_cat:
            out["cat_words"] = jnp.asarray(cat_words)
            out["cat_nwords"] = jnp.asarray(cat_nwords)
        # multiclass (r6): per-tree class plane — same shape trick as the
        # bitset planes; slices always start on an iteration boundary, so
        # position-in-slice mod K IS the class (the host walk's i % K).
        # Absent for K == 1 so the single-class program is unchanged.
        K = self.num_tree_per_iteration
        if K > 1:
            out["cls"] = jnp.asarray(np.arange(T, dtype=np.int32) % K)
        return out

    def _tree_slice_key(self, trees: List[Tree]):
        """Cache key pinning the RESOLVED tree slice by object identity
        (first id + length determines a contiguous slice; a replaced
        model — model_from_string, refit — allocates new Tree objects,
        so stale hits are impossible even when counts coincide) AND by
        the model-mutation version: `rollback_one_iter` frees Tree
        objects whose ids the allocator can hand to the very next grown
        tree, so identity alone could alias a stale cache after a
        rollback + regrow of equal length (tests/test_serving.py).
        In-place mutations that keep identities must still call
        `_invalidate_pred_caches` (which bumps the version)."""
        return (getattr(self, "_model_version", 0), len(trees),
                id(trees[0]), id(trees[-1]))

    def model_fingerprint(self) -> str:
        """Content-addressed model identity: a short sha256 of the
        serialized model with its `[param: value]` lines stripped, so
        the same trees hash the same regardless of how the booster was
        configured or loaded (train vs model_from_string round-trip).
        The lineage ledger (telemetry/ledger.py) keys every
        control-plane record on this.  Cached per resolved tree slice
        (`_tree_slice_key`), so repeated calls on an unchanged model
        cost a tuple compare, not a re-serialization."""
        trees = self.trees
        ck = self._tree_slice_key(trees) if trees else None
        cached = getattr(self, "_fingerprint_cache", None)
        if cached is not None and cached[0] == ck:
            return cached[1]
        text = self.model_to_string()
        body = "\n".join(l for l in text.splitlines()
                         if not l.startswith("["))
        fp = hashlib.sha256(body.encode()).hexdigest()[:16]
        self._fingerprint_cache = (ck, fp)
        return fp

    def _flatten_for_native(self, trees: List[Tree]):
        """Per-tree-concatenated contiguous model arrays for the native
        ensemble walk (`native.predict_rows`), cached across calls
        (single-row latency is dominated by setup otherwise).  None for
        shapes the walk does not cover (linear trees)."""
        if not trees or any(t.is_linear for t in trees):
            return None
        ck = self._tree_slice_key(trees)
        cached = getattr(self, "_pred_native_cache", None)
        if cached and cached[0] == ck:
            return cached[1]
        offs = {k: [0] for k in ("node", "leaf", "cb", "bits")}
        cols = {k: [] for k in ("feat", "thr", "dtype", "left", "right",
                                "thr_bin", "leaf_value", "cat_bounds",
                                "cat_bits")}
        for t in trees:
            ni = max(t.num_leaves - 1, 0)
            cols["feat"].append(t.split_feature[:ni])
            cols["thr"].append(t.threshold[:ni])
            cols["dtype"].append(t.decision_type[:ni])
            cols["left"].append(t.left_child[:ni])
            cols["right"].append(t.right_child[:ni])
            cols["thr_bin"].append(t.threshold_bin[:ni])
            cols["leaf_value"].append(t.leaf_value[:t.num_leaves])
            cols["cat_bounds"].append(t.cat_boundaries)
            cols["cat_bits"].append(t.cat_threshold)
            offs["node"].append(offs["node"][-1] + ni)
            offs["leaf"].append(offs["leaf"][-1] + t.num_leaves)
            offs["cb"].append(offs["cb"][-1] + len(t.cat_boundaries))
            offs["bits"].append(offs["bits"][-1] + len(t.cat_threshold))
        dt = dict(feat=np.int32, thr=np.float64, dtype=np.int32,
                  left=np.int32, right=np.int32, thr_bin=np.int32,
                  leaf_value=np.float64, cat_bounds=np.int64,
                  cat_bits=np.uint32)
        flat = {k: np.ascontiguousarray(np.concatenate(v), dt[k])
                for k, v in cols.items()}
        for k in offs:
            flat[f"{k}_off"] = np.asarray(offs[k], np.int64)
        flat["n_trees"] = len(trees)
        # narrower X must fall back to the numpy path's IndexError, not
        # read out of bounds in C
        flat["min_features"] = int(flat["feat"].max()) + 1 \
            if len(flat["feat"]) else 0
        self._pred_native_cache = (ck, flat)
        return flat

    def _predict_raw_device(self, stacked, X: np.ndarray,
                            n_class: int = 1) -> np.ndarray:
        """Jitted stacked-ensemble batch predict in f32 ([N] for one
        class, [N, K] multiclass — the per-tree `cls` plane routes each
        scan step's output into its class column).

        Parity caveat: features AND thresholds are cast to f32, so a
        feature value lying strictly between a threshold and its f32
        rounding can route to the other subtree — such rows' errors are
        leaf-value-sized, not rounding-sized.  This affects only rows
        within f32 epsilon of a split threshold (thresholds are bin-edge
        midpoints, so real data virtually never sits there); the host
        walk remains the exact-f64 reference path."""
        from .ops.predict import (predict_raw_ensemble,
                                  predict_raw_ensemble_multi)
        if getattr(self, "_pred_dev_jit", None) is None:
            self._pred_dev_jit = jax.jit(predict_raw_ensemble)
            self._pred_dev_jit_multi = jax.jit(
                predict_raw_ensemble_multi, static_argnames="n_class")
        arrays = {k: v for k, v in stacked.items() if k != "min_features"}
        # f64 values beyond f32 range overflow to ±inf in this cast — the
        # routing we WANT (inf exceeds every threshold/span, so such rows
        # take the same branch as any huge in-range value); cast under
        # errstate so the intended saturation doesn't warn
        with np.errstate(over="ignore"):
            X32 = np.asarray(X, dtype=np.float32)
        if n_class > 1:
            out = self._pred_dev_jit_multi(arrays, jnp.asarray(X32),
                                           n_class=n_class)
        else:
            out = self._pred_dev_jit(arrays, jnp.asarray(X32))
        return np.asarray(jax.device_get(out), dtype=np.float64)

    def export_predict_arrays(self, start_iteration: int = 0,
                              num_iteration: Optional[int] = None) -> Dict:
        """One-shot model export for the serving runtime
        (serving/runtime.py): the stacked device traversal arrays (leaf-
        index space, `ops.predict.predict_leaf_ensemble`) plus the exact
        f64 per-tree leaf-value table for the host-side gather/sum.
        Cached per resolved tree slice; the key folds in
        `_model_version`, so `rollback_one_iter` / `refit` / continued
        training / `set_leaf_output` all invalidate it
        (tests/test_serving.py pins this).

        Returns a dict:
          stacked        — device arrays for predict_leaf_ensemble, or
                           None (linear trees: host-walk only)
          leaf_values    — [T, NL] f64 leaf outputs, tree-padded
          value_hi/lo    — [T, NL] u32 device planes: the raw bit
                           halves of `leaf_values` (hi = sign/exponent/
                           top mantissa word, lo = low mantissa word),
                           consumed by the exact device-sum program
                           (`ops.predict.predict_raw_ensemble_exact`).
                           A f32/f32 VALUE split cannot stand in: a
                           53-bit leaf mantissa does not fit two f32
                           significands, so the device carries the f64
                           bit patterns themselves.  None when stacked
                           is None.
          trees          — the resolved host Tree slice (fallback walk)
          num_class      — trees per iteration (K)
          average_factor — RF averaging divisor (1 = plain sum)
          version        — `_model_version` at export time
        """
        trees = self._slice_trees(start_iteration, num_iteration)
        ck = self._tree_slice_key(trees) if trees else None
        cached = getattr(self, "_serving_export_cache", None)
        if ck and cached and cached[0] == ck:
            return cached[1]
        stacked = self._stack_for_device(trees)
        nl = max((t.num_leaves for t in trees), default=1)
        leaf_values = np.zeros((len(trees), nl), np.float64)
        for i, t in enumerate(trees):
            leaf_values[i, :t.num_leaves] = t.leaf_value[:t.num_leaves]
        value_hi = value_lo = None
        if stacked is not None:
            bits = leaf_values.view(np.uint64)
            value_hi = jnp.asarray((bits >> 32).astype(np.uint32))
            value_lo = jnp.asarray(bits.astype(np.uint32))
        K = self.num_tree_per_iteration
        avg = max(len(trees) // K, 1) \
            if getattr(self, "_average_output", False) \
            and len(trees) >= K else 1
        export = {"stacked": stacked, "leaf_values": leaf_values,
                  "value_hi": value_hi, "value_lo": value_lo,
                  "trees": trees, "num_class": K, "average_factor": avg,
                  "version": getattr(self, "_model_version", 0)}
        if ck:
            self._serving_export_cache = (ck, export)
        return export

    def _predict_contrib(self, X: np.ndarray, trees: List[Tree]) -> np.ndarray:
        """TreeSHAP feature contributions (ref: PredictContrib → tree.cpp
        TreeSHAP recursion). Host implementation."""
        from .contrib import predict_contrib
        return predict_contrib(X, trees, self.num_tree_per_iteration)

    # ----------------------------------------------------------- model text
    def _objective_to_string(self) -> str:
        cfg = self.config
        o = cfg.objective
        if self.objective_ is None:
            return "custom"
        if o == "binary":
            return f"binary sigmoid:{cfg.sigmoid:g}"
        if o == "multiclass":
            return f"multiclass num_class:{cfg.num_class}"
        if o == "multiclassova":
            return (f"multiclassova num_class:{cfg.num_class} "
                    f"sigmoid:{cfg.sigmoid:g}")
        if o == "quantile":
            return f"quantile alpha:{cfg.alpha:g}"
        if o == "huber":
            return f"huber alpha:{cfg.alpha:g}"
        if o == "fair":
            return f"fair fair_c:{cfg.fair_c:g}"
        if o == "tweedie":
            return (f"tweedie "
                    f"tweedie_variance_power:{cfg.tweedie_variance_power:g}")
        if o == "lambdarank":
            return "lambdarank"
        if o == "rank_xendcg":
            return "rank_xendcg"
        return o

    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0,
                        importance_type: str = "split") -> str:
        """ref: gbdt_model_text.cpp `GBDT::SaveModelToString`."""
        trees = self._slice_trees(start_iteration, num_iteration)
        fnames = self.train_set.get_feature_name() if self.train_set \
            else getattr(self, "_loaded_feature_names",
                         [f"Column_{i}" for i in range(self.num_feature())])
        buf = io.StringIO()
        buf.write("tree\n")
        buf.write("version=v4\n")
        buf.write(f"num_class={max(self.num_tree_per_iteration, 1)}\n")
        buf.write(f"num_tree_per_iteration={self.num_tree_per_iteration}\n")
        buf.write("label_index=0\n")
        buf.write(f"max_feature_idx={len(fnames) - 1}\n")
        buf.write(f"objective={self._objective_to_string()}\n")
        if getattr(self, "_average_output", False):
            buf.write("average_output\n")
        buf.write("feature_names=" + " ".join(fnames) + "\n")
        if self.train_set is not None and self.train_set.bin_mappers:
            infos = [m.feature_info_str() for m in self.train_set.bin_mappers]
        else:
            infos = getattr(self, "_loaded_feature_infos", ["none"] * len(fnames))
        buf.write("feature_infos=" + " ".join(infos) + "\n")
        tree_strs = [t.to_string(i) for i, t in enumerate(trees)]
        buf.write("tree_sizes=" + " ".join(str(len(s) + 1)
                                           for s in tree_strs) + "\n")
        buf.write("\n")
        for s in tree_strs:
            buf.write(s + "\n")
        buf.write("end of trees\n\n")
        imp = self.feature_importance(importance_type)
        pairs = sorted([(v, n) for n, v in zip(fnames, imp) if v > 0],
                       reverse=True)
        buf.write("feature_importances:\n")
        for v, n in pairs:
            buf.write(f"{n}={v:g}\n")
        buf.write("\nparameters:\n")
        for k, v in self.params.items():
            if callable(v):
                continue
            if isinstance(v, (list, tuple)):
                v = ",".join(str(x) for x in v)
            buf.write(f"[{k}: {v}]\n")
        buf.write("end of parameters\n")
        buf.write("\npandas_categorical:" +
                  json.dumps(self.pandas_categorical) + "\n")
        return buf.getvalue()

    def model_from_string(self, model_str: str) -> "Booster":
        """ref: gbdt_model_text.cpp `GBDT::LoadModelFromString`."""
        lines = model_str.split("\n")
        header: Dict[str, str] = {}
        i = 0
        while i < len(lines):
            ln = lines[i].strip()
            if ln.startswith("Tree="):
                break
            if "=" in ln:
                k, v = ln.split("=", 1)
                header[k] = v
            i += 1
        self.num_tree_per_iteration = int(
            header.get("num_tree_per_iteration", 1))
        self._average_output = "average_output" in lines[:i]
        self._loaded_feature_names = header.get("feature_names", "").split()
        self._loaded_feature_infos = header.get("feature_infos", "").split()
        obj_str = header.get("objective", "regression").split()
        obj_params = {}
        for tok in obj_str[1:]:
            if ":" in tok:
                k, v = tok.split(":")
                obj_params[k] = v
        # parameters section round-trips (ref: GBDT::SaveModelToString
        # writes the config block; LoadModelFromString restores it) — this
        # keeps save→load→save byte-stable
        in_params = False
        for ln in lines:
            ln = ln.strip()
            if ln == "parameters:":
                in_params = True
                continue
            if ln == "end of parameters":
                break
            if in_params and ln.startswith("[") and ":" in ln:
                k, v = ln[1:-1].split(":", 1)
                self.params.setdefault(k.strip(), v.strip())
        params = dict(self.params)
        params["objective"] = obj_str[0] if obj_str else "regression"
        params.update(obj_params)
        params.setdefault("verbosity", -1)
        self.config = Config(params)
        self.objective_ = create_objective(self.config) \
            if obj_str and obj_str[0] != "custom" else None
        self.metrics_ = create_metrics(
            self.config, self.config.metric or self.config.default_metric())
        self._fobj = None
        # parse trees; the identity-keyed prediction caches are invalid
        # the moment the model is replaced wholesale (belt-and-braces vs
        # id() reuse after GC)
        self._invalidate_pred_caches()
        text = "\n".join(lines[i:])
        self.trees = []
        for section in text.split("Tree=")[1:]:
            section = section.split("\nend of trees")[0]
            self.trees.append(Tree.from_string("Tree=" + section))
        self.cur_iter = len(self.trees) // max(self.num_tree_per_iteration, 1)
        # pandas_categorical footer
        for ln in reversed(lines):
            if ln.startswith("pandas_categorical:"):
                try:
                    self.pandas_categorical = json.loads(
                        ln[len("pandas_categorical:"):])
                except json.JSONDecodeError:
                    pass
                break
        return self

    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> "Booster":
        with open(filename, "w") as f:
            f.write(self.model_to_string(num_iteration, start_iteration,
                                         importance_type))
        return self

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> Dict:
        """JSON model dump (ref: GBDT::DumpModel)."""
        trees = self._slice_trees(start_iteration, num_iteration)
        fnames = (self.train_set.get_feature_name() if self.train_set
                  else getattr(self, "_loaded_feature_names", []))

        def node_to_dict(t: Tree, node: int) -> Dict:
            if node < 0:
                leaf = ~node
                return {"leaf_index": int(leaf),
                        "leaf_value": float(t.leaf_value[leaf]),
                        "leaf_weight": float(t.leaf_weight[leaf]),
                        "leaf_count": int(t.leaf_count[leaf])}
            if t.decision_type[node] & K_CATEGORICAL_MASK:
                # ref: Tree::NodeToJSON
                kind, threshold = "==", "||".join(
                    str(c) for c in t.left_categories(node))
            else:
                kind, threshold = "<=", float(t.threshold[node])
            return {
                "split_index": int(node),
                "split_feature": int(t.split_feature[node]),
                "split_gain": float(t.split_gain[node]),
                "threshold": threshold,
                "decision_type": kind,
                "default_left": bool(t.decision_type[node] & 2),
                "missing_type": ["None", "Zero", "NaN"][
                    (t.decision_type[node] >> 2) & 3],
                "internal_value": float(t.internal_value[node]),
                "internal_weight": float(t.internal_weight[node]),
                "internal_count": int(t.internal_count[node]),
                "left_child": node_to_dict(t, t.left_child[node]),
                "right_child": node_to_dict(t, t.right_child[node]),
            }

        return {
            "name": "tree",
            "version": "v4",
            "num_class": max(self.num_tree_per_iteration, 1),
            "num_tree_per_iteration": self.num_tree_per_iteration,
            "label_index": 0,
            "max_feature_idx": len(fnames) - 1,
            "objective": self._objective_to_string(),
            "feature_names": fnames,
            "tree_info": [{
                "tree_index": i,
                "num_leaves": t.num_leaves,
                "num_cat": t.num_cat,
                "shrinkage": t.shrinkage,
                "tree_structure": node_to_dict(
                    t, 0 if t.num_leaves > 1 else ~0),
            } for i, t in enumerate(trees)],
            "pandas_categorical": self.pandas_categorical,
        }

    # ------------------------------------------------------------- metadata
    def flight_summary(self) -> Dict[str, Any]:
        """Flight-recorder summary of this booster's training run:
        per-round tree-shape/gain quantiles, top split features, eval
        first→last deltas, per-phase wall-clock, compile accounting and
        device-memory watermarks (telemetry/recorder.py), plus the
        analytic throughput block that used to live in
        `utils.profile.training_report`.  `{"enabled": False}` when the
        booster was built without `flight_recorder=true`."""
        if self._flight is None:
            return {"enabled": False}
        from .telemetry.recorder import poll_jit_caches, sample_memory
        # final compile-cache poll (the cache-growth signal next to the
        # listener's compile count) + one last memory sample
        poll_jit_caches([getattr(self, a, None)
                         for a in ("_grower", "_bulk_trainer_cache",
                                   "_grad_fn", "_grad_rng_fn",
                                   "_grad_state_fn", "_renew_jit")])
        sample_memory("summary")
        out = self._flight.summary()
        dd = getattr(self, "_dd", None)
        if dd is not None:
            efb = dd.efb
            cols = efb.n_cols if efb is not None else dd.num_feature
            tp = self._flight.throughput(dd.num_data, cols,
                                         self.config.num_leaves,
                                         self._grower_spec.hist_impl,
                                         efb is not None)
            if tp is not None:
                out["throughput"] = tp
        return out

    def current_iteration(self) -> int:
        return self.cur_iter

    def num_trees(self) -> int:
        return len(self.trees)

    def num_model_per_iteration(self) -> int:
        return self.num_tree_per_iteration

    def num_feature(self) -> int:
        if self.train_set is not None:
            return self.train_set.num_feature()
        return len(getattr(self, "_loaded_feature_names", []))

    def feature_name(self) -> List[str]:
        if self.train_set is not None:
            return self.train_set.get_feature_name()
        return list(getattr(self, "_loaded_feature_names", []))

    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        """ref: gbdt.cpp `GBDT::FeatureImportance`."""
        trees = self._slice_trees(0, iteration)
        out = np.zeros(self.num_feature(), dtype=np.float64)
        for t in trees:
            if importance_type == "split":
                t.feature_importance_split(out)
            elif importance_type == "gain":
                t.feature_importance_gain(out)
            else:
                raise LightGBMError(
                    f"Unknown importance type: {importance_type}")
        if importance_type == "split":
            return out.astype(np.int32)
        return out

    # -------------------------------------------- remaining stock surface
    def set_train_data_name(self, name: str) -> "Booster":
        """ref: basic.py Booster.set_train_data_name."""
        self._train_data_name = str(name)
        return self

    def free_dataset(self) -> "Booster":
        """Release the training/validation data (ref: basic.py
        `Booster.free_dataset` / LGBM_BoosterFreeDataset): prediction and
        model IO keep working, further training raises."""
        if self.train_set is not None:
            # prediction/model-text need these after the data is gone
            self._loaded_feature_names = self.train_set.get_feature_name()
        self.train_set = None
        self._dd = None
        self._train_bins = None
        self._train_score = None   # num_data-sized device arrays
        self._ones = None
        self._valid_dd = []
        self._valid_scores = []
        self.valid_sets = []
        return self

    def free_network(self) -> "Booster":
        """No-op (ref: basic.py Booster.free_network — the socket mesh
        teardown; XLA collectives over ICI/DCN need none)."""
        return self

    def set_network(self, *args, **kwargs) -> "Booster":
        """Accepted for API parity, with a warning (ref: basic.py
        Booster.set_network/machines — the TPU backend replaces the
        socket mesh with jax.distributed + device meshes; see
        lightgbm_tpu.parallel.init)."""
        log.warning("set_network is inert on the TPU backend — use "
                    "lightgbm_tpu.parallel.init() + tree_learner=data "
                    "for distributed training")
        return self

    def set_attr(self, **kwargs) -> "Booster":
        """In-memory string attributes (ref: basic.py Booster.set_attr;
        value None deletes)."""
        attr = getattr(self, "_attr", {})
        for k, v in kwargs.items():
            if v is None:
                attr.pop(k, None)
            else:
                attr[k] = str(v)
        self._attr = attr
        return self

    def get_attr(self, name: str) -> Optional[str]:
        return getattr(self, "_attr", {}).get(name)

    def lower_bound(self) -> float:
        """Minimum possible raw score (ref: GBDT::GetLowerBoundValue —
        sum over trees of each tree's smallest leaf output)."""
        return float(sum(
            float(np.min(t.leaf_value[:t.num_leaves]))
            for t in self.trees)) if self.trees else 0.0

    def upper_bound(self) -> float:
        """ref: GBDT::GetUpperBoundValue."""
        return float(sum(
            float(np.max(t.leaf_value[:t.num_leaves]))
            for t in self.trees)) if self.trees else 0.0

    def get_leaf_output(self, tree_id: int, leaf_id: int) -> float:
        """ref: LGBM_BoosterGetLeafValue."""
        return float(self.trees[tree_id].leaf_value[leaf_id])

    def set_leaf_output(self, tree_id: int, leaf_id: int,
                        value: float) -> "Booster":
        """Overwrite one leaf's output (ref: basic.py
        Booster.set_leaf_output / Tree::SetLeafOutput).  Cached training
        scores are rebuilt lazily before the next update()/eval."""
        self.trees[tree_id].leaf_value[leaf_id] = float(value)
        self._scores_stale = True
        # the rollback cache holds the OLD leaf's contributions
        self._last_contribs = []
        self._invalidate_pred_caches()
        return self

    def _bump_model_version(self) -> None:
        """Advance the monotonic model-mutation counter (tree append /
        rollback / in-place value edits).  Prediction caches fold it
        into their keys, and serving exports pin it so a
        `ServingRuntime` can detect a stale export cheaply
        (`export_predict_arrays` / serving/runtime.py `refresh`)."""
        self._model_version = getattr(self, "_model_version", 0) + 1

    def _invalidate_pred_caches(self) -> None:
        """Drop the flattened/stacked prediction caches after any
        IN-PLACE model mutation that their keys (tree slice, tree count,
        cur_iter) cannot see — set_leaf_output, shuffle_models, DART
        value rescaling."""
        self._pred_native_cache = None
        self._pred_dev_cache = None
        self._serving_export_cache = None
        self._bump_model_version()

    def shuffle_models(self, start_iteration: int = 0,
                       end_iteration: int = -1) -> "Booster":
        """Randomly permute whole iterations of trees in
        [start_iteration, end_iteration) (ref: basic.py
        Booster.shuffle_models / GBDT::ShuffleModels).  The raw-score sum
        is order-independent, so predictions are unchanged."""
        K = self.num_tree_per_iteration
        n_iter = len(self.trees) // K
        end = n_iter if end_iteration < 0 else min(end_iteration, n_iter)
        start = max(0, start_iteration)
        if end - start > 1:
            idx = np.arange(start, end)
            np.random.shuffle(idx)
            blocks = [self.trees[i * K:(i + 1) * K] for i in range(n_iter)]
            reordered = blocks[:start] + [blocks[i] for i in idx] + \
                blocks[end:]
            self.trees = [t for b in reordered for t in b]
            # the rollback cache refers to the pre-shuffle last iteration
            self._last_contribs = []
            # slice-based predictions (start/num_iteration) DO change
            self._invalidate_pred_caches()
        return self

    def get_split_value_histogram(self, feature, bins=None,
                                  xgboost_style: bool = False):
        """Histogram of this model's split thresholds for one feature
        (ref: basic.py Booster.get_split_value_histogram).  Returns
        (counts, bin_edges) like np.histogram, or a pandas DataFrame /
        [SplitValue, Count] array when xgboost_style=True."""
        fnames = self.feature_name()
        fidx = fnames.index(feature) if isinstance(feature, str) \
            else int(feature)
        values = []
        for t in self.trees:
            ni = t.num_internal()
            for i in range(ni):
                if t.split_feature[i] == fidx and \
                        not (t.decision_type[i] & 1):
                    values.append(t.threshold[i])
        n_unique = len(np.unique(values)) if values else 0
        if bins is None or (not isinstance(bins, str)
                            and np.isscalar(bins) and bins > n_unique):
            # ref: basic.py — one bin per distinct split value by default
            bins = max(n_unique, 1)
        hist, edges = np.histogram(values, bins=bins)
        if not xgboost_style:
            return hist, edges
        rows = np.column_stack([edges[1:], hist]).astype(np.float64)
        rows = rows[rows[:, 1] > 0]
        try:
            import pandas as pd
            return pd.DataFrame(rows, columns=["SplitValue", "Count"])
        except ImportError:
            return rows

    def trees_to_dataframe(self):
        """Model structure as one pandas DataFrame (ref: basic.py
        Booster.trees_to_dataframe; same column set)."""
        import pandas as pd
        fnames = self.feature_name()
        rows = []
        for ti, t in enumerate(self.trees):
            ni = t.num_internal()
            parent = {}
            depth = {("S", 0): 1} if ni else {("L", 0): 1}
            for i in range(ni):
                for child, tag in ((t.left_child[i], None),
                                   (t.right_child[i], None)):
                    key = ("L", ~child) if child < 0 else ("S", child)
                    parent[key] = i
                    depth[key] = depth.get(("S", i), 1) + 1

            def node_index(key):
                kind, idx = key
                return f"{ti}-{'L' if kind == 'L' else 'S'}{idx}"

            for i in range(ni):
                dt = int(t.decision_type[i])
                lc, rc = int(t.left_child[i]), int(t.right_child[i])
                rows.append({
                    "tree_index": ti,
                    "node_depth": depth.get(("S", i), 1),
                    "node_index": node_index(("S", i)),
                    "left_child": node_index(
                        ("L", ~lc) if lc < 0 else ("S", lc)),
                    "right_child": node_index(
                        ("L", ~rc) if rc < 0 else ("S", rc)),
                    "parent_index": node_index(("S", parent[("S", i)]))
                    if ("S", i) in parent else None,
                    "split_feature": fnames[int(t.split_feature[i])]
                    if int(t.split_feature[i]) < len(fnames)
                    else str(int(t.split_feature[i])),
                    "split_gain": float(t.split_gain[i]),
                    "threshold": float(t.threshold[i]),
                    "decision_type": "==" if dt & 1 else "<=",
                    "missing_direction": "left" if dt & 2 else "right",
                    "missing_type": {0: "None", 1: "Zero", 2: "NaN"}[
                        (dt >> 2) & 3],
                    "value": float(t.internal_value[i]),
                    "weight": float(t.internal_weight[i]),
                    "count": int(t.internal_count[i]),
                })
            for li in range(t.num_leaves):
                key = ("L", li)
                rows.append({
                    "tree_index": ti,
                    "node_depth": depth.get(key, 1),
                    "node_index": node_index(key),
                    "left_child": None, "right_child": None,
                    "parent_index": node_index(("S", parent[key]))
                    if key in parent else None,
                    "split_feature": None, "split_gain": None,
                    "threshold": None, "decision_type": None,
                    "missing_direction": None, "missing_type": None,
                    "value": float(t.leaf_value[li]),
                    "weight": float(t.leaf_weight[li]),
                    "count": int(t.leaf_count[li]),
                })
        return pd.DataFrame(rows)

    def _rebuild_train_scores(self) -> None:
        """Recompute cached train/valid scores from the current trees
        (after set_leaf_output mutated the model)."""
        K = self.num_tree_per_iteration

        def replay(dd):
            # boost_from_average's bias is folded into iteration 0's trees
            # (add_bias above) — replay onto the bare init-score base, the
            # same recipe as add_valid's canonical replay
            score = self._zero_score(dd)
            for it in range(self.cur_iter):
                for k in range(K):
                    t = self.trees[it * K + k]
                    score = self._apply_tree_to_score(
                        score, t, dd, k, bias_included=True)
            return score

        self._train_score = replay(self._dd)
        for i, dd in enumerate(self._valid_dd):
            self._valid_scores[i] = replay(dd)
        self._scores_stale = False

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """ref: basic.py Booster.reset_parameter (learning-rate schedules)."""
        self.params.update(params)
        self.config.update(params)
        self._grower_spec = self._grower_spec._replace(
            num_leaves=self.config.num_leaves,
            max_depth=self.config.max_depth,
            lambda_l1=self.config.lambda_l1,
            lambda_l2=self.config.lambda_l2,
            min_data_in_leaf=float(self.config.min_data_in_leaf),
            min_sum_hessian_in_leaf=self.config.min_sum_hessian_in_leaf,
            min_gain_to_split=self.config.min_gain_to_split,
            max_delta_step=self.config.max_delta_step,
            # quantization params may have changed: a stale hist_impl /
            # const-hess level would silently mis-scale histogram sums
            hist_impl=self._resolve_hist_impl(),
            hist_interpret=bool(self.config.hist_interpret))
        self._grower_spec = self._grower_spec._replace(
            packed_const_hess_level=self._packed_const_hess_level(),
            wave_width=self._wave_width(),
            wave_gain_ratio=self._wave_gain_ratio(),
            wave_overgrow=self._wave_overgrow())
        self._grow_policy = self._resolve_grow_policy()
        self._grower = self._make_serial_grower()
        self._build_feat()
        self._setup_tree_learner()
        return self

    def __copy__(self):
        return self.__deepcopy__(None)

    def __deepcopy__(self, _):
        return Booster(model_str=self.model_to_string(num_iteration=-1))

    def __getstate__(self):
        state = {"model_str": self.model_to_string(num_iteration=-1),
                 "params": self.params,
                 "best_iteration": self.best_iteration}
        return state

    def __setstate__(self, state):
        self.__init__(params=state.get("params"),
                      model_str=state["model_str"])
        self.best_iteration = state.get("best_iteration", -1)
