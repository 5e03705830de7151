#!/usr/bin/env bash
# CI entry (ref: .ci/test.sh in the reference).  Also the local gate:
#   ./scripts/run_ci.sh quick    # pre-commit tier, ~5-7 min of test time
#   ./scripts/run_ci.sh full     # the whole suite (nightly; ~30 min on 1 core)
# tests/conftest.py forces the virtual 8-device CPU mesh either way.
set -euo pipefail
cd "$(dirname "$0")/.."

tier="${1:-quick}"

# graft-lint + graft-race gates first (seconds, no jax backend): new
# findings beyond lint_baseline.json / race_baseline.json fail CI
# before any test burns minutes
./scripts/lint.sh

case "$tier" in
  quick) python -m pytest tests/ -m quick -q ;;
  full)  python -m pytest tests/ -q ;;
  *) echo "usage: $0 [quick|full]" >&2; exit 2 ;;
esac

# pipelined-dispatch smoke: a deep pipeline must reproduce the serial
# schedule's model byte-for-byte (tree lines; the params dump records the
# knob itself).  Fast CPU check of the dispatch/harvest split + donated
# score carries — the full matrix lives in tests/test_pipeline.py
JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np
import lightgbm_tpu as lgb

rng = np.random.RandomState(0)
X = rng.randn(1500, 8)
y = (X[:, 0] - X[:, 1] + .3 * rng.randn(1500) > 0).astype(float)


def text(depth):
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1, "tpu_pipeline_chunks": depth},
                    lgb.Dataset(X, label=y), num_boost_round=32)
    return "\n".join(l for l in bst.model_to_string().splitlines()
                     if not l.startswith("[tpu_pipeline_chunks:"))


assert text(1) == text(4), "pipelined model differs from serial"
print("[run_ci] pipeline smoke: depth 4 == depth 1 (byte-identical)")
EOF

# serving smoke: a golden model behind the stdlib HTTP frontend on an
# ephemeral port — POST /predict must be byte-identical to
# booster.predict, /healthz and /metrics must answer, an X-Request-Id
# must round-trip to a /debug/requests trace whose stage deltas sum to
# its e2e within 5% (the ISSUE 8 acceptance bound), and the /metrics
# exposition must carry classic histogram _bucket series.  Warm-up is
# off: the smoke checks wiring, the bucket/compile matrix lives in
# tests/test_serving.py, the trace matrix in tests/test_serving_trace.py
JAX_PLATFORMS=cpu python - <<'EOF'
import json
import sys
import threading
import urllib.request

import numpy as np

sys.path.insert(0, "tests")
from golden_common import GOLDEN_CASES, make_case_data
from lightgbm_tpu.booster import Booster
from lightgbm_tpu.serving import ServingClient
from lightgbm_tpu.serving.http import make_server

bst = Booster(model_file="tests/data/golden_binary.model.txt")
X, _ = make_case_data(GOLDEN_CASES["binary"])
X = X[:64]
# serve_trace_slow_ms=0: every completed request is recorded, so the
# smoke's one request is guaranteed to be inspectable at /debug/requests
client = ServingClient(bst, params={"serve_warmup": False,
                                    "serve_trace_slow_ms": 0.0})
srv = make_server(client, "127.0.0.1", 0)
port = srv.server_address[1]
threading.Thread(target=srv.serve_forever, daemon=True).start()
base = f"http://127.0.0.1:{port}"
body = json.dumps({"rows": X.tolist()}).encode()
req = urllib.request.Request(f"{base}/predict", data=body,
                             headers={"Content-Type": "application/json",
                                      "X-Request-Id": "ci-smoke-1"})
raw = urllib.request.urlopen(req, timeout=60)
assert raw.headers["X-Request-Id"] == "ci-smoke-1", "id header not echoed"
resp = json.loads(raw.read())
assert resp["request_id"] == "ci-smoke-1", "id body field not echoed"
got = np.asarray(resp["predictions"], np.float64)
want = bst.predict(X)
assert got.shape == want.shape and np.array_equal(got, want), \
    "HTTP /predict != booster.predict"
hz = json.loads(urllib.request.urlopen(f"{base}/healthz",
                                       timeout=30).read())
assert hz["status"] == "ok" and hz["models"] == ["default"], hz
assert hz["latency_ms"]["count"] >= 1 and hz["latency_ms"]["p99_ms"] > 0
metrics = urllib.request.urlopen(f"{base}/metrics",
                                 timeout=30).read().decode()
assert "lgbm_tpu" in metrics and "serve" in metrics, "metrics exposition"
assert "lgbm_tpu_serve_stage_e2e_seconds_bucket{" in metrics and \
    'le="+Inf"' in metrics, "histogram _bucket series missing"
dbg = json.loads(urllib.request.urlopen(f"{base}/debug/requests",
                                        timeout=30).read())
tr = next(t for t in dbg["requests"] if t["id"] == "ci-smoke-1")
assert tr["status"] == "ok" and tr["rows"] == 64, tr
stage_sum = sum(tr["stages_ms"].values())
assert abs(stage_sum - tr["e2e_ms"]) <= 0.05 * tr["e2e_ms"], \
    f"stages sum {stage_sum}ms vs e2e {tr['e2e_ms']}ms (>5% apart)"
srv.shutdown()
srv.server_close()
client.close()
print("[run_ci] serving smoke: HTTP parity + trace round-trip "
      f"(stages {stage_sum:.1f}ms ~ e2e {tr['e2e_ms']:.1f}ms) + "
      "histogram buckets OK")
EOF

# device-sum parity smoke: the exact on-device accumulation rung must
# pass its probe on a golden model and serve bytes identical to
# booster.predict, raw and transformed, with the N*K-score D2H payload
# (not T*N slots).  The per-family matrix + probe-degradation cases
# live in tests/test_serving.py
JAX_PLATFORMS=cpu python - <<'EOF'
import sys

import numpy as np

sys.path.insert(0, "tests")
from golden_common import GOLDEN_CASES, make_case_data
from lightgbm_tpu import telemetry
from lightgbm_tpu.booster import Booster
from lightgbm_tpu.serving import ServingRuntime, bucket_rows

bst = Booster(model_file="tests/data/golden_multiclass.model.txt")
X, _ = make_case_data(GOLDEN_CASES["multiclass"])
rt = ServingRuntime(bst)
assert rt.device_sum_active, "device-sum parity probe failed"
d2h = telemetry.REGISTRY.counter("serve.d2h_bytes")
before = d2h.value
for raw in (True, False):
    got = rt.predict(X[:300], raw_score=raw)
    want = bst.predict(X[:300], raw_score=raw)
    assert got.dtype == want.dtype and np.array_equal(got, want), \
        f"device-sum != booster.predict (raw={raw})"
K = rt.num_class
moved = d2h.value - before
assert moved == bucket_rows(300) * K * (8 + 4), \
    f"D2H {moved} B is not N*K scores"
assert telemetry.REGISTRY.counter("serve.device_sum").value >= 2
print("[run_ci] device-sum smoke: exact parity, "
      f"{moved} B D2H for 2x300x{K} scores")
EOF

# compiled-rung smoke (ISSUE 13): a golden model behind the HTTP
# frontend with serve_compiled=on — the tile-plane parity probe must
# pass, /predict must come off the compiled rung byte-identical to
# booster.predict, and a doctored plan (one corrupted node word) must be
# probe-rejected at refresh time and degrade to the next rung with zero
# request errors and identical bytes.  The per-family / ragged / cause
# matrix lives in tests/test_serving_compiler.py
JAX_PLATFORMS=cpu python - <<'EOF'
import json
import sys
import threading
import urllib.request

import numpy as np

sys.path.insert(0, "tests")
from golden_common import GOLDEN_CASES, make_case_data
from lightgbm_tpu import telemetry
from lightgbm_tpu.booster import Booster
from lightgbm_tpu.serving import ServingClient
import lightgbm_tpu.serving.runtime as srt
from lightgbm_tpu.serving.http import make_server

bst = Booster(model_file="tests/data/golden_multiclass.model.txt")
X, _ = make_case_data(GOLDEN_CASES["multiclass"])
X = np.ascontiguousarray(X[:128])
client = ServingClient(bst, params={"serve_warmup": False,
                                    "serve_compiled": "on",
                                    "serve_max_wait_ms": 0.0})
rt = client.registry.get().runtime
assert rt.compiled_active, "compiled parity probe failed on CPU"
srv = make_server(client, "127.0.0.1", 0)
port = srv.server_address[1]
threading.Thread(target=srv.serve_forever, daemon=True).start()
cc = telemetry.REGISTRY.counter("serve.compiled")
before = cc.value
body = json.dumps({"rows": X.tolist()}).encode()
req = urllib.request.Request(f"http://127.0.0.1:{port}/predict",
                             data=body,
                             headers={"Content-Type": "application/json"})
resp = json.loads(urllib.request.urlopen(req, timeout=120).read())
got = np.asarray(resp["predictions"], np.float64)
want = bst.predict(X)
assert got.shape == want.shape and np.array_equal(got, want), \
    "compiled /predict != booster.predict"
assert cc.value > before, "response did not come off the compiled rung"
tiles = rt._plan.num_tiles()
srv.shutdown()
srv.server_close()
client.close()

# doctored plan: reroute one child word — the refresh-time probe must
# reject it (cause=probe) and serving must keep its exact bytes one
# rung down, with zero errors
orig_build = srt.build_plan


def doctored(ex, **kw):
    plan = orig_build(ex, **kw)
    plan.planes[0]["kids"][0, 0, 0] = (3 << 16) | 3
    return plan


srt.build_plan = doctored
try:
    dis = telemetry.REGISTRY.counter("serve.compiled_disabled",
                                     cause="probe")
    dis_before = dis.value
    client2 = ServingClient(bst, params={"serve_warmup": False,
                                         "serve_compiled": "on",
                                         "serve_max_wait_ms": 0.0})
    rt2 = client2.registry.get().runtime
    assert not rt2.compiled_active, "doctored plan passed the probe"
    assert dis.value == dis_before + 1, "cause=probe not recorded"
    got2 = client2.predict(X)
    assert np.array_equal(got2, want), "degraded rung changed bytes"
    client2.close()
finally:
    srt.build_plan = orig_build
print(f"[run_ci] compiled smoke: HTTP parity off the compiled rung "
      f"({tiles} tiles), doctored plan probe-rejected with exact "
      "degradation")
EOF

# bounded-tier smoke (serve_precision=bounded): a golden model behind
# the HTTP frontend on the quantized-leaf rung — /predict must come off
# the bounded rung with max-abs-error vs the f64 reference within the
# PUBLISHED bound, and /healthz must expose the contract (bound +
# measured probe error) for the model.  The per-family matrix, the
# doctored-scale probe gate, and the exact-ladder byte-identity
# assertions live in tests/test_bounded_serving.py
JAX_PLATFORMS=cpu python - <<'EOF'
import json
import sys
import threading
import urllib.request

import numpy as np

sys.path.insert(0, "tests")
from golden_common import GOLDEN_CASES, make_case_data
from lightgbm_tpu import telemetry
from lightgbm_tpu.booster import Booster
from lightgbm_tpu.serving import ServingClient
from lightgbm_tpu.serving.http import make_server

bst = Booster(model_file="tests/data/golden_binary.model.txt")
X, _ = make_case_data(GOLDEN_CASES["binary"])
X = np.ascontiguousarray(X[:128])
client = ServingClient(bst, params={"serve_warmup": False,
                                    "serve_precision": "bounded",
                                    "serve_max_wait_ms": 0.0})
rt = client.registry.get().runtime
assert rt.bounded_active, "bounded rung did not pass its probe"
bound = rt.bounded_bound
assert bound is not None and bound > 0.0, bound
srv = make_server(client, "127.0.0.1", 0)
port = srv.server_address[1]
threading.Thread(target=srv.serve_forever, daemon=True).start()
base = f"http://127.0.0.1:{port}"
bc = telemetry.REGISTRY.counter("serve.bounded")
before = bc.value
body = json.dumps({"rows": X.tolist(), "raw_score": True}).encode()
req = urllib.request.Request(f"{base}/predict", data=body,
                             headers={"Content-Type": "application/json"})
resp = json.loads(urllib.request.urlopen(req, timeout=120).read())
got = np.asarray(resp["predictions"], np.float64)
want = bst.predict(X, raw_score=True)
err = float(np.max(np.abs(got - want)))
assert err <= bound, f"HTTP bounded error {err} > published bound {bound}"
assert bc.value > before, "response did not come off the bounded rung"
hz = json.loads(urllib.request.urlopen(f"{base}/healthz",
                                       timeout=30).read())
hb = hz["bounded"]["default"]
assert hb["active"] is True, hb
assert hb["bound"] == bound, hb
assert 0.0 <= hb["measured_max_abs_error"] <= bound, hb
srv.shutdown()
srv.server_close()
client.close()
print(f"[run_ci] bounded smoke: HTTP error {err:.3e} <= published "
      f"bound {bound:.3e}, /healthz exposes the contract")
EOF

# quantized-default training smoke: under quantized gradients the auto
# hist_impl resolution now lands on the int-lattice path by DEFAULT,
# and must produce trees BYTE-IDENTICAL to an explicit
# hist_impl=pallas_q run (interpret-mode, wave policy) — the
# default is a routing decision, never a numerics change.  The full
# impl matrix + priced-fallback cases live in tests/test_bounded_serving.py
JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np
import lightgbm_tpu as lgb

rng = np.random.RandomState(5)
X = rng.randn(1500, 8)
y = (X[:, 0] - X[:, 1] + .3 * rng.randn(1500) > 0).astype(float)
base = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
        "use_quantized_grad": True, "num_grad_quant_bins": 8,
        "tree_grow_policy": "wave"}


def trees(extra):
    bst = lgb.train({**base, **extra}, lgb.Dataset(X, label=y),
                    num_boost_round=4)
    s = bst.model_to_string()
    return s[:s.index("\nparameters:")]       # params echo the knobs


auto = trees({})
pallas_q = trees({"hist_impl": "pallas_q", "hist_interpret": True})
assert auto == pallas_q, \
    "auto quantized-default trees != explicit pallas_q trees"
print("[run_ci] quantized-default smoke: auto == pallas_q "
      "(byte-identical trees)")
EOF

# external-memory smoke: a dataset ~4x the datastore budget trains via
# the spilled shard store and must be byte-identical to the in-memory
# model, with the prefetch pipeline's host residency inside the budget
# (streaming_train pinned off: this smoke covers the ASSEMBLE route;
# the streamed route has its own smoke right below)
JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.telemetry import REGISTRY

rng = np.random.default_rng(9)
n, f = 20000, 52                      # ~0.99 MB of uint8 bins
X = rng.standard_normal((n, f))
y = (X[:, 0] - X[:, 3] + 0.1 * rng.standard_normal(n) > 0).astype(float)
budget_mb = 0.25
params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "min_data_in_leaf": 20}
mem = lgb.train(dict(params), lgb.Dataset(X, label=y), num_boost_round=4)
ext = lgb.train({**params, "external_memory": True,
                 "datastore_budget_mb": budget_mb,
                 "streaming_train": "off"},
                lgb.Dataset(X, label=y), num_boost_round=4)
strip = lambda s: "\n".join(l for l in s.splitlines()
                            if not l.startswith("["))
assert strip(mem.model_to_string()) == strip(ext.model_to_string()), \
    "spilled model != in-memory model"
g = REGISTRY.snapshot()["gauges"]
assert g["datastore.spill_bytes"] >= 4 * budget_mb * (1 << 20), g
assert g["datastore.shards"] >= 4, g
assert g["datastore.peak_resident_mb"] <= budget_mb, \
    f"prefetch held {g['datastore.peak_resident_mb']} MB > {budget_mb} MB"
print(f"[run_ci] external-memory smoke: byte parity over "
      f"{int(g['datastore.shards'])} shards, peak resident "
      f"{g['datastore.peak_resident_mb']} MB <= {budget_mb} MB budget")
EOF

# streaming smoke (ISSUE 15): the same 4x-over-budget dataset with
# streaming_train at its "auto" default must ENGAGE the shard-streamed
# engine (the bin matrix never materializes on device), stay
# byte-identical to the in-memory model, and keep the budget-governed
# staging slice (stream.peak_staging_mb — the double-buffered shard
# staging) inside the budget the assembled matrix would blow through.
# stream.peak_device_mb is the FULL device watermark (staging plus
# resident score/histogram state) and so only bounds staging from above
JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.telemetry import REGISTRY

rng = np.random.default_rng(9)
n, f = 20000, 52                      # ~0.99 MB of uint8 bins
X = rng.standard_normal((n, f))
y = (X[:, 0] - X[:, 3] + 0.1 * rng.standard_normal(n) > 0).astype(float)
budget_mb = 0.25
params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "min_data_in_leaf": 20}
mem = lgb.train(dict(params), lgb.Dataset(X, label=y), num_boost_round=4)
st = lgb.train({**params, "external_memory": True,
                "datastore_budget_mb": budget_mb},
               lgb.Dataset(X, label=y), num_boost_round=4)
strip = lambda s: "\n".join(l for l in s.splitlines()
                            if not l.startswith("["))
assert strip(mem.model_to_string()) == strip(st.model_to_string()), \
    "streamed model != in-memory model"
snap = REGISTRY.snapshot()
passes = snap["counters"].get("stream.shard_passes", 0)
assert passes > 0, "streaming_train=auto did not engage on over-budget"
g = snap["gauges"]
assert 0 < g["stream.peak_staging_mb"] <= budget_mb, \
    f"device staging held {g['stream.peak_staging_mb']} MB > {budget_mb} MB"
assert g["stream.peak_device_mb"] >= g["stream.peak_staging_mb"], g
assert g["datastore.peak_resident_mb"] <= budget_mb, g
print(f"[run_ci] streaming smoke: byte parity over {int(passes)} shard "
      f"passes, peak staging {g['stream.peak_staging_mb']} MB <= "
      f"{budget_mb} MB budget (full device watermark "
      f"{g['stream.peak_device_mb']} MB)")
EOF

# spool smoke (ISSUE 16): streamed training plus one served predict with
# the cross-process telemetry spool attached, then the jax-free timeline
# CLI must aggregate the spool, export a loadable Chrome trace, and the
# streaming-pass stall attribution must respect its disjoint-subinterval
# contract (stage sum <= pass wall, 5% clock-sanity slack).  The full
# matrix (2-process gloo aggregation, byte identity, straggler naming)
# lives in tests/test_spool.py
JAX_PLATFORMS=cpu python - <<'EOF'
import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.serving import ServingClient

spool = tempfile.mkdtemp(prefix="ci_spool_")
rng = np.random.default_rng(11)
n, f = 20000, 52
X = rng.standard_normal((n, f))
y = (X[:, 0] - X[:, 3] + 0.1 * rng.standard_normal(n) > 0).astype(float)
st = lgb.train({"objective": "binary", "num_leaves": 15, "verbosity": -1,
                "min_data_in_leaf": 20, "external_memory": True,
                "datastore_budget_mb": 0.25, "streaming_train": "on",
                "telemetry_spool_dir": spool},
               lgb.Dataset(X, label=y), num_boost_round=4)
# one served predict: the spool attach is process-global, so the serve
# spans land in the same proc-*.jsonl as the training passes
client = ServingClient(st, params={"serve_warmup": False})
got = client.predict(X[:64])
client.close()
assert got.shape == (64,), got.shape
telemetry.TRACER.emit_metrics_snapshot()
telemetry.TRACER.flush()

trace_path = os.path.join(spool, "trace.json")
r = subprocess.run([sys.executable, "-m", "lightgbm_tpu", "timeline",
                    spool, "--trace", trace_path],
                   capture_output=True, text=True)
assert r.returncode == 0, r.stderr[-2000:]
with open(trace_path) as fh:
    trace = json.load(fh)
assert trace["traceEvents"], "empty chrome trace"

from lightgbm_tpu.telemetry.spool import aggregate
agg = aggregate(spool)
stream = agg["stream"]
assert stream["passes"] > 0, "no stream.pass spans spooled"
assert stream["attributed_s"] <= stream["wall_s"] * 1.05, \
    (f"stage attribution {stream['attributed_s']}s exceeds pass wall "
     f"{stream['wall_s']}s — sub-intervals are no longer disjoint")
serve_spans = [e for e in agg["events"] if e.get("ev") == "span"
               and str(e.get("name", "")).startswith("serve.")]
assert serve_spans, "served predict left no serve.* spans in the spool"
print(f"[run_ci] spool smoke: timeline over "
      f"{len(agg['processes'])} process(es), {stream['passes']} streamed "
      f"passes, attributed {stream['attributed_s']:.3f}s <= wall "
      f"{stream['wall_s']:.3f}s, chrome trace "
      f"{len(trace['traceEvents'])} events")
EOF

# memory smoke (ISSUE 18): train + serve with the device-memory ledger
# armed, then hold the attribution contract end to end — the per-owner
# bytes on /debug/memory must cover the allocator watermark to within
# the 5% acceptance bound, zero budget-contract violations on a clean
# run, and the jax-free `memory` CLI must render the same snapshot
# from the live URL with rc 0.  The register/release/reconcile matrix,
# leak-slope oracle, doctored violations and OOM forensics live in
# tests/test_memledger.py
JAX_PLATFORMS=cpu python - <<'EOF'
import json
import subprocess
import sys
import threading
import urllib.request

import numpy as np

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.serving import ServingClient
from lightgbm_tpu.serving.http import make_server

rng = np.random.default_rng(13)
X = rng.standard_normal((2000, 16))
y = (X[:, 0] - X[:, 2] + 0.1 * rng.standard_normal(2000) > 0).astype(float)
bst = lgb.train({"objective": "binary", "num_leaves": 15, "verbosity": -1,
                 "min_data_in_leaf": 20, "memory_ledger": True},
                lgb.Dataset(X, label=y), num_boost_round=4)
client = ServingClient(bst, params={"serve_warmup": False})
client.predict(X[:64])
srv = make_server(client, "127.0.0.1", 0)
port = srv.server_address[1]
threading.Thread(target=srv.serve_forever, daemon=True).start()

snap = json.loads(urllib.request.urlopen(
    f"http://127.0.0.1:{port}/debug/memory", timeout=60).read())
assert snap["enabled"], "ledger not armed"
dev = snap["devices"]["dev0"]
owners = dev["owners"]
assert any(k.startswith("train.bins") for k in owners), owners.keys()
assert any(k.startswith("serve.") for k in owners), owners.keys()
assert sum(o["bytes"] for o in owners.values()) == dev["attributed_bytes"]
rec = snap["reconcile"]
if rec.get("source") != "unavailable":
    alloc = rec["devices"]["dev0"]["allocator_bytes"]
    assert rec["unattributed_bytes"] <= max(0.05 * alloc, 64), \
        (f"{rec['unattributed_bytes']}B of {alloc}B unattributed "
         f"> 5% bound; unknowns: {rec['largest_unknown']}")
viol = snap.get("budget_violations") or {}
assert not any(viol.values()), f"clean run counted violations: {viol}"
assert snap.get("oom_dumps", 0) == 0, snap["oom_dumps"]

r = subprocess.run([sys.executable, "-m", "lightgbm_tpu", "memory",
                    f"http://127.0.0.1:{port}"],
                   capture_output=True, text=True)
assert r.returncode == 0, r.stderr[-2000:]
assert "train.bins" in r.stdout, r.stdout[-2000:]
srv.shutdown()
srv.server_close()
client.close()
unattr = rec.get("unattributed_bytes", 0)
print(f"[run_ci] memory smoke: {len(owners)} owners cover "
      f"{dev['attributed_bytes']}B attributed, {unattr}B unattributed "
      f"({rec.get('source')}), zero violations, memory CLI rc 0")
EOF

# mesh smoke (PR 10): distributed training + sharded serving on the
# virtual 8-device mesh.  One data-parallel training round must be
# byte-identical to the serial learner (one round pins the psum
# ordering; multi-round score accumulation is covered with tolerances
# in tests/test_distributed.py), and a sharded-serving /predict over
# all 8 replicas must return bytes identical to the single-device
# runtime and to booster.predict.  The per-family / wedge / budget
# matrix lives in tests/test_sharded_serving.py
XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
python - <<'EOF'
import json
import sys
import threading
import urllib.request

import numpy as np
import jax

import lightgbm_tpu as lgb

assert len(jax.devices()) == 8, jax.devices()

# --- data-parallel training round vs serial, byte-identical
rng = np.random.RandomState(7)
X = rng.randn(2048, 6)
y = (X[:, 0] - 0.5 * X[:, 1] + 0.3 * rng.randn(2048) > 0).astype(float)
params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
          "min_data_in_leaf": 20}
strip = lambda s: "\n".join(l for l in s.splitlines()
                            if not l.startswith("["))
ser = lgb.train(dict(params), lgb.Dataset(X, label=y), num_boost_round=1)
dp = lgb.train({**params, "tree_learner": "data", "num_machines": 8},
               lgb.Dataset(X, label=y), num_boost_round=1)
assert strip(ser.model_to_string()) == strip(dp.model_to_string()), \
    "data-parallel round != serial round"
print("[run_ci] mesh smoke: 8-shard data-parallel round == serial "
      "(byte-identical)")

# --- sharded serving /predict parity over all 8 replicas
sys.path.insert(0, "tests")
from golden_common import GOLDEN_CASES, make_case_data
from lightgbm_tpu.booster import Booster
from lightgbm_tpu.serving import ServingClient, ServingRuntime
from lightgbm_tpu.serving.http import make_server
from lightgbm_tpu import telemetry

bst = Booster(model_file="tests/data/golden_multiclass.model.txt")
Xg, _ = make_case_data(GOLDEN_CASES["multiclass"])
single = ServingRuntime(bst, max_batch_rows=64, name="ci.1dev")
client = ServingClient(bst, params={"serve_warmup": False,
                                    "serve_shard_devices": 0,
                                    "serve_max_batch_rows": 64})
rt = client.registry.get().runtime
assert rt.num_replicas == 8, rt.num_replicas
srv = make_server(client, "127.0.0.1", 0)
port = srv.server_address[1]
threading.Thread(target=srv.serve_forever, daemon=True).start()
body = json.dumps({"rows": Xg.tolist()}).encode()
req = urllib.request.Request(f"http://127.0.0.1:{port}/predict",
                             data=body,
                             headers={"Content-Type": "application/json"})
resp = json.loads(urllib.request.urlopen(req, timeout=120).read())
got = np.asarray(resp["predictions"], np.float64)
want = bst.predict(Xg)
assert got.shape == want.shape and np.array_equal(got, want), \
    "sharded /predict != booster.predict"
assert np.array_equal(got, single.predict(Xg)), \
    "sharded /predict != single-device runtime"
used = sum(1 for i in range(8)
           if telemetry.REGISTRY.counter(f"serve.replica.{i}.rows").value)
assert used >= 2, f"striping engaged only {used} replica(s)"
srv.shutdown()
srv.server_close()
client.close()
print(f"[run_ci] mesh smoke: sharded /predict byte-identical over "
      f"{used} striped replicas")
EOF

# fleet smoke (ISSUE 11 + 12): the continuous-training loop end to end
# on a golden model — trainer daemon tailing an append-only store behind
# the HTTP frontend, rows appended, a shadow-gated hot-swap under a
# concurrent /predict loop that must see zero errors with every response
# byte-identical to whichever model version was live at its dispatch —
# then the control plane: a forced rejection and a second accepted swap,
# /debug/fleet probed (incl. the 400 contract), and the lineage CLI
# asserted to reconstruct the full ancestry WITH per-check gate evidence
# offline from the smoke's own JSONL sink.  The full matrix (tenancy,
# autoscaling, burn rate, drift, the swap/demote hammer) lives in
# tests/test_fleet.py and tests/test_fleet_observability.py
JAX_PLATFORMS=cpu python - <<'EOF'
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np

sys.path.insert(0, "tests")
from golden_common import GOLDEN_CASES, make_case_data
from lightgbm_tpu.booster import Booster
from lightgbm_tpu.datastore.store import ShardStore
from lightgbm_tpu.fleet import TrainerDaemon, create_fleet_store
from lightgbm_tpu.serving import ServingClient
from lightgbm_tpu.serving.http import make_server
from lightgbm_tpu import telemetry

bst = Booster(model_file="tests/data/golden_binary.model.txt")
X, y = make_case_data(GOLDEN_CASES["binary"])
store_dir = "/tmp/ci_fleet_store"
events_path = "/tmp/ci_fleet_events.jsonl"
import shutil
shutil.rmtree(store_dir, ignore_errors=True)
if os.path.exists(events_path):
    os.unlink(events_path)
create_fleet_store(store_dir, X, y, shard_rows=256)

# the lineage ledger mirrors every control-plane record to attached
# sinks — the offline CLI reads this file after the daemon is gone
telemetry.LEDGER.reset()
telemetry.TRACER.attach_jsonl(events_path)
# debug_locks arms the lock-order witness (graft-race runtime half)
# for the whole smoke: daemon + registry + batcher run with every lock
# acquisition order-checked, and the byte-identity assertions below
# double as proof the witness never touches served bytes
client = ServingClient(bst, params={"serve_warmup": False,
                                    "serve_max_wait_ms": 0.0,
                                    "debug_locks": True})
daemon = TrainerDaemon(
    store_dir, client.registry, bst,
    train_params={"objective": "binary", "num_leaves": 15,
                  "verbosity": -1},
    params={"fleet_retrain_rows": 128, "fleet_rounds": 3,
            "fleet_shadow_rows": 256, "serve_drift": True,
            "serve_drift_min_rows": 32})
root_fp = bst.model_fingerprint()
srv = make_server(client, "127.0.0.1", 0)
port = srv.server_address[1]
threading.Thread(target=srv.serve_forever, daemon=True).start()
base = f"http://127.0.0.1:{port}"
Xq = np.ascontiguousarray(X[:32])
body = json.dumps({"rows": Xq.tolist()}).encode()

responses, errors, stop = [], [], threading.Event()


def hammer():
    while not stop.is_set():
        try:
            req = urllib.request.Request(
                f"{base}/predict", data=body,
                headers={"Content-Type": "application/json"})
            resp = json.loads(urllib.request.urlopen(req, timeout=60).read())
            responses.append(
                np.asarray(resp["predictions"], np.float64).tobytes())
        except Exception as e:  # noqa: BLE001 — asserted empty below
            errors.append(e)


t = threading.Thread(target=hammer, daemon=True)
t.start()
time.sleep(0.3)                                   # traffic pre-swap
half = len(X) // 2
ShardStore.open(store_dir).append_rows(
    X[:half], label=y[:half].astype(np.float32))  # new generation
assert daemon.step(), "daemon did not retrain on the appended rows"
time.sleep(0.3)                                   # traffic post-swap
stop.set()
t.join(timeout=60)

assert daemon.swaps == 1 and daemon.rejects == 0, \
    (daemon.swaps, daemon.rejects)
live = daemon.live_booster
assert live is not bst and len(live.trees) > len(bst.trees)
assert all(bst.trees[i].to_string(i) == live.trees[i].to_string(i)
           for i in range(len(bst.trees))), "frozen prefix diverged"
assert not errors, errors[:3]
# JSON carries float64; predict may emit float32 — widen (exact) to compare
allowed = {np.asarray(bst.predict(Xq), np.float64).tobytes(),
           np.asarray(live.predict(Xq), np.float64).tobytes()}
assert responses and set(responses) <= allowed, \
    "a /predict response matched NEITHER live model version"
assert telemetry.REGISTRY.counter("fleet.gate.pass").value >= 1
fp1 = live.model_fingerprint()

# ---- control plane (ISSUE 12): force a rejection (any positive
# holdout loss exceeds a negative tolerance), then a second accepted
# swap — the lineage must carry both, each with measured gate evidence
ShardStore.open(store_dir).append_rows(
    X[:160], label=y[:160].astype(np.float32))
daemon.gate.tolerance = -1.0
assert daemon.step() and daemon.rejects == 1, "forced reject missed"
assert daemon.live_booster.model_fingerprint() == fp1, \
    "a REJECTED candidate went live"
daemon.gate.tolerance = 10.0
ShardStore.open(store_dir).append_rows(
    X[:160], label=y[:160].astype(np.float32))
assert daemon.step() and daemon.swaps == 2, "second swap missed"
fp2 = daemon.live_booster.model_fingerprint()
assert telemetry.REGISTRY.counter("serve.drift.computes").value >= 1, \
    "drift monitor never scored the sampled traffic"

# the unified ops surface, served live
snap = json.loads(urllib.request.urlopen(
    f"{base}/debug/fleet", timeout=30).read())
for key in ("ledger", "lineage", "tenants", "drift", "mesh"):
    assert key in snap, f"/debug/fleet missing {key!r}"
chain = [h["fingerprint"]
         for h in snap["lineage"]["default"]["ancestry"]]
assert chain == [root_fp, fp1, fp2], chain
assert snap["lineage"]["default"]["rejections"], "rejection not shown"
assert snap["drift"]["top"], "drift block empty"
try:
    urllib.request.urlopen(f"{base}/debug/fleet?n=-1", timeout=30)
    raise SystemExit("negative n was not rejected")
except urllib.error.HTTPError as e:
    assert e.code == 400, e.code

srv.shutdown()
srv.server_close()
daemon.stop()
client.close()
telemetry.TRACER.clear_sinks()
shutil.rmtree(store_dir, ignore_errors=True)
with open("/tmp/ci_fleet_fps.json", "w") as f:
    json.dump({"root": root_fp, "fp1": fp1, "fp2": fp2}, f)
print(f"[run_ci] fleet smoke: 2 gated hot-swaps + 1 forced reject, "
      f"{len(responses)} concurrent /predict responses all "
      "byte-consistent, 0 errors, /debug/fleet consistent")
EOF

# the lineage CLI must reconstruct the same ancestry OFFLINE from the
# smoke's JSONL sink — two swaps, the rejected candidate, and the
# per-check gate evidence (holdout losses next to their tolerance)
JAX_PLATFORMS=cpu python - <<'EOF'
import json
import subprocess
import sys

fps = json.load(open("/tmp/ci_fleet_fps.json"))
out = subprocess.run(
    [sys.executable, "-m", "lightgbm_tpu", "lineage",
     "/tmp/ci_fleet_events.jsonl"],
    capture_output=True, text=True, timeout=120)
assert out.returncode == 0, out.stderr
text = out.stdout
for fp in (fps["root"], fps["fp1"], fps["fp2"]):
    assert fp in text, f"lineage lost fingerprint {fp}\n{text}"
assert text.index(fps["root"]) < text.index(fps["fp1"]) < \
    text.index(fps["fp2"]), f"ancestry out of order\n{text}"
assert "gate PASS" in text and "REJECT" in text, text
assert "holdout[" in text and "tol" in text, \
    f"gate evidence missing from lineage report\n{text}"
rep = json.loads(subprocess.run(
    [sys.executable, "-m", "lightgbm_tpu", "lineage",
     "/tmp/ci_fleet_events.jsonl", "--json"],
    capture_output=True, text=True, timeout=120).stdout)
chain = [h["fingerprint"] for h in rep["ancestry"]]
assert chain == [fps["root"], fps["fp1"], fps["fp2"]], chain
assert rep["rejections"][0]["gate"]["checks"]["candidate_loss"] > 0
print("[run_ci] lineage CLI: full ancestry (root -> 2 swaps) + "
      "rejection evidence reconstructed offline from JSONL")
EOF

# chaos smoke (ISSUE 14): serve the golden model over HTTP with a HANG
# armed on the device-sum dispatch.  The watchdog must bound the wedged
# request (serve.watchdog.fired == 1), the ladder must degrade exactly
# ONE rung (slot_path serves, host_walk untouched), every response must
# stay byte-identical to booster.predict, and after disarm the breaker's
# half-open re-probe must restore the rung without a refresh().
JAX_PLATFORMS=cpu python - <<'EOF'
import json
import sys
import threading
import time
import urllib.request

import numpy as np

sys.path.insert(0, "tests")
from golden_common import GOLDEN_CASES, make_case_data
from lightgbm_tpu import telemetry
from lightgbm_tpu.booster import Booster
from lightgbm_tpu.resilience import FAULTS
from lightgbm_tpu.serving import ServingClient
from lightgbm_tpu.serving.http import make_server

def cval(name, **labels):
    return telemetry.REGISTRY.counter(name, **labels).value

bst = Booster(model_file="tests/data/golden_binary.model.txt")
X, _ = make_case_data(GOLDEN_CASES["binary"])
X = X[:64]
want = bst.predict(X)
# warmup=True: compiles happen at load time, so the dispatch deadline
# below only ever has to cover real dispatch — a 5 s deadline vs the
# 1 h hang horizon is unambiguous.  compiled=off makes device_sum the
# top rung (the one the fault wedges).
# debug_locks: run the whole chaos scenario (watchdog, breaker,
# rung demotion/re-probe) under the lock-order witness
client = ServingClient(bst, params={
    "serve_warmup": True, "serve_compiled": "off",
    "serve_max_wait_ms": 0.0,
    "serve_dispatch_timeout_ms": 5000.0,
    "serve_breaker_backoff_s": 2.0,
    "debug_locks": True})
rt = client.registry.get("default").runtime
assert rt.device_sum_active, "device_sum rung must start live"
srv = make_server(client, "127.0.0.1", 0)
port = srv.server_address[1]
threading.Thread(target=srv.serve_forever, daemon=True).start()
base = f"http://127.0.0.1:{port}"

def http_predict():
    body = json.dumps({"rows": X.tolist()}).encode()
    req = urllib.request.Request(
        f"{base}/predict", data=body,
        headers={"Content-Type": "application/json"})
    resp = json.loads(urllib.request.urlopen(req, timeout=120).read())
    return np.asarray(resp["predictions"], np.float64)

wd0 = cval("serve.watchdog.fired", site="serve.dispatch.device_sum")
sp0 = cval("serve.slot_path")
hw0 = sum(cval("serve.host_walk", cause=c)
          for c in ("device_error", "breaker_open", "disabled"))
FAULTS.arm("serve.dispatch.device_sum:hang")
t0 = time.monotonic()
np.testing.assert_array_equal(http_predict(), want)   # watchdog bounds it
wedged_s = time.monotonic() - t0
assert wedged_s < 60.0, f"wedged request not bounded ({wedged_s:.0f}s)"
np.testing.assert_array_equal(http_predict(), want)   # breaker skips rung
wd = cval("serve.watchdog.fired", site="serve.dispatch.device_sum") - wd0
sp = cval("serve.slot_path") - sp0
hw = sum(cval("serve.host_walk", cause=c)
         for c in ("device_error", "breaker_open", "disabled")) - hw0
assert wd == 1, f"watchdog fired {wd}x (want exactly 1: open breaker " \
    "must SKIP the wedged rung, not re-pay its deadline)"
assert sp >= 2, f"slot_path served {sp}x (want both degraded requests)"
assert hw == 0, f"host_walk took {hw} requests — degraded TWO rungs"
assert rt._breakers["device_sum"].state == "open"

# disarm + elapse the backoff: predicts kick ONE background half-open
# re-probe which re-proves byte parity and re-closes the breaker
FAULTS.disarm()
time.sleep(2.1)
deadline = time.monotonic() + 60.0
while rt._breakers["device_sum"].state != "closed":
    np.testing.assert_array_equal(http_predict(), want)
    assert time.monotonic() < deadline, \
        f"breaker never re-closed: {rt._breakers['device_sum'].state}"
    time.sleep(0.05)
assert cval("serve.breaker.recovered", rung="device_sum") >= 1
ds0 = cval("serve.device_sum")
np.testing.assert_array_equal(http_predict(), want)
assert cval("serve.device_sum") > ds0, "restored rung not serving"

srv.shutdown()
srv.server_close()
client.close()
print(f"[run_ci] chaos smoke: hang bounded in {wedged_s:.1f}s "
      "(watchdog x1), degraded exactly one rung (slot_path), "
      "all responses byte-identical, breaker re-probe restored "
      "device_sum after disarm")
EOF

# mini-soak smoke (ISSUE 20): the composed production plane under
# closed-loop multi-tenant traffic for ~60 s.  The `smoke` scenario
# drives one append-triggered gated hot-swap, a drift injection and a
# rung kill with breaker recovery over live HTTP, then the capacity
# ladder fits the falsifiable queueing model.  ZERO byte-inconsistent
# responses, every online expectation met, every SLO class inside its
# budget, zero unattributed swap-window sheds — and the emitted BENCH
# `soak` block must be sentinel-grade: doctoring in a byte
# inconsistency or a capacity collapse makes telemetry diff exit 1.
JAX_PLATFORMS=cpu python - <<'EOF'
import copy
import json

from lightgbm_tpu.soak import run_mini_soak
from lightgbm_tpu.telemetry.diff import diff_snapshots

block = run_mini_soak(params={"soak_capacity_max_steps": 4})
assert block["byte_inconsistent"] == 0, block
assert block["oracle_checked"] > 100, block["oracle_checked"]
assert block["swaps"] >= 1 and block["gate_pass"] >= 1, block
assert block["breaker_recovered"] >= 1, block
assert block["expect_fail"] == 0, block["expect_detail"]
assert block["slo_breach"] == 0, block["slo"]
assert block["sheds"]["unattributed_swap"] == 0, block["sheds"]
cap = block["capacity"]
assert cap["rows_per_sec_peak"] > 0 and cap["devices"] >= 1, cap

flat = json.loads(json.dumps(block))
doctors = (lambda s: s.update(byte_inconsistent=1),
           lambda s: s["capacity"].update(
               rows_per_sec_per_device=cap["rows_per_sec_per_device"] / 4))
for doctor in doctors:
    bad = copy.deepcopy(flat)
    doctor(bad)
    v = diff_snapshots({"soak": flat}, {"soak": bad})
    assert v["verdict"] == "regression", v
print(f"[run_ci] soak smoke: {block['requests']} requests / "
      f"{block['oracle_checked']} oracle checks, 0 byte-inconsistent, "
      f"{block['swaps']} gated hot-swap(s), breaker recovered x"
      f"{block['breaker_recovered']}, all SLO classes within budget, "
      f"capacity {cap['rows_per_sec_per_device']:.0f} rows/s/device "
      "(doctored regressions trip the sentinel)")
EOF

# perf-regression sentinel: fresh deterministic snapshot diffed against
# the checked-in baseline.  Counter-class drift (tree shape, recompiles,
# fallback events, memory watermarks) FAILS; wall-clock drift only warns
# (--warn-timings: this gate runs on the shared-core CPU fallback where
# absolute timings are noise).  Regenerate the baseline with
# scripts/telemetry_baseline.sh when the mechanism change is intended.
baseline="scripts/telemetry_baseline.json"
if [[ -f "$baseline" ]]; then
  snap="$(mktemp /tmp/telemetry_snapshot.XXXXXX.json)"
  trap 'rm -f "$snap"' EXIT
  JAX_PLATFORMS=cpu python scripts/telemetry_snapshot.py --out "$snap"
  JAX_PLATFORMS=cpu python -m lightgbm_tpu telemetry diff \
    "$baseline" "$snap" --warn-timings
else
  echo "[run_ci] no $baseline — sentinel skipped" >&2
fi
