"""Micro-benchmark: histogram implementations at Higgs shape.

Usage (real TPU):  python benchmarks/bench_hist.py [N] [F] [MB]

TIMING METHODOLOGY (round 3b): on the remote backend those rounds ran
on, `block_until_ready` returned before the device had actually executed,
so naive rep-loop timing reported async-dispatch fantasy numbers (this is
how round 2 recorded a 0.21 ms scatter that actually takes ~750 ms;
`chip_smoke.py` re-checks the call on the current chip).  Every
measurement here forces a real dependency chain through `lax.fori_loop`
(iteration i+1 consumes a scalar from iteration i's result) and
materialises the final value with `np.asarray`; per-call time is the
slope between k=1 and k=K chains, which cancels dispatch + transfer
overhead.
"""
import os
import sys
import time

import numpy as np

# runnable as `python benchmarks/bench_hist.py` from anywhere: the repo
# root (one level up) carries the package
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    f = int(sys.argv[2]) if len(sys.argv) > 2 else 28
    mb = int(sys.argv[3]) if len(sys.argv) > 3 else 256

    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops.histogram import leaf_histogram
    from lightgbm_tpu.ops.pallas_hist import (pallas_histogram,
                                              pallas_histogram_quantized)

    print(f"backend={jax.devices()[0].platform} n={n} f={f} mb={mb}")
    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, mb, (f, n)).astype(
        np.uint8 if mb <= 256 else np.uint16))
    payload = jnp.asarray(rng.randn(n, 3).astype(np.float32))
    mask = jnp.asarray(rng.rand(n) < 0.5)

    from lightgbm_tpu.ops.fused import quantize_gradients
    gq, hq, (sg, sh) = quantize_gradients(
        payload[:, 0], jnp.abs(payload[:, 1]) + 0.1, 8, return_scales=True)
    payload_q = jnp.stack([gq, hq, jnp.ones_like(gq)], axis=1)

    from lightgbm_tpu.ops.pallas_hist import (MULTI_CHUNK, MULTI_CHUNK_Q,
                                              pallas_histogram_multi,
                                              pallas_histogram_multi_quantized)
    leaf_id = jnp.asarray(
        np.random.RandomState(1).randint(0, 16, n).astype(np.int32))
    slots = jnp.arange(MULTI_CHUNK, dtype=jnp.int32)
    slots_q = jnp.arange(MULTI_CHUNK_Q, dtype=jnp.int32)

    impls = {
        "segment_sum": lambda p: leaf_histogram(bins, p, mask, mb),
        "pallas": lambda p: pallas_histogram(bins, p, mask, mb),
        "pallas_q": lambda p: pallas_histogram_quantized(
            bins, payload_q + p[:, :1] * 0, mask, mb, sg, sh),
        # the wave grower's batched passes: one call = 14 / 42 histograms
        f"pallas_multi_x{MULTI_CHUNK}": lambda p: pallas_histogram_multi(
            bins, p, leaf_id, slots, mb)[0],
        f"pallas_q_multi_x{MULTI_CHUNK_Q}":
            lambda p: pallas_histogram_multi_quantized(
                bins, payload_q + p[:, :1] * 0, leaf_id, slots_q, mb,
                sg, sh)[0],
    }

    # bins + payload + mask read per call
    bytes_per_call = n * f * bins.dtype.itemsize + n * 3 * 4 + n

    results = {}
    for name, fn in impls.items():
        try:
            k = 8

            @jax.jit
            def chain(p, k_, fn=fn):
                def body(i, acc):
                    # consume a scalar of the previous result so calls
                    # cannot overlap or be elided
                    return fn(p + acc[0, 0, 0] * 1e-20)
                return jax.lax.fori_loop(0, k_, body,
                                         jnp.zeros((f, mb, 3)))

            np.asarray(chain(payload, 1))           # compile + warmup
            t0 = time.perf_counter()
            np.asarray(chain(payload, 1))
            t1 = time.perf_counter() - t0
            t0 = time.perf_counter()
            np.asarray(chain(payload, k))
            tk = time.perf_counter() - t0
            dt = (tk - t1) / (k - 1)
            results[name] = dt
            print(f"{name:<14} {dt * 1e3:8.2f} ms/call "
                  f"{bytes_per_call / dt / 1e9:8.1f} GB/s")
        except Exception as e:  # pragma: no cover
            print(f"{name:<14} FAILED: {type(e).__name__}: {e}")

    if "segment_sum" in results:
        base = results["segment_sum"]
        for name, dt in results.items():
            if name != "segment_sum":
                print(f"{name} speedup vs segment_sum: {base / dt:.1f}x")


if __name__ == "__main__":
    main()
