"""Pallas histogram kernel equality vs the segment-sum path (interpret mode
on CPU; the driver's TPU bench exercises the compiled kernel).

Analog of the reference's CPU-vs-GPU histogram consistency checks
(tests/python_package_test/test_dual.py)."""
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops.histogram import leaf_histogram
from lightgbm_tpu.ops.pallas_hist import (pallas_histogram,
                                          pallas_histogram_quantized, probe)


def _case(n, f, mb, seed, weights=True):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, mb, (f, n)).astype(np.uint8)
    payload = rng.randn(n, 3).astype(np.float32)
    if not weights:
        payload[:, 2] = 1.0
    mask = rng.rand(n) < 0.6
    return (jnp.asarray(bins), jnp.asarray(payload), jnp.asarray(mask))


class TestPallasHistogram:
    @pytest.mark.parametrize("impl", ["onehot", "hilo"])
    @pytest.mark.parametrize("n,f,mb", [
        (512, 4, 16), (1000, 7, 32), (2048, 3, 256), (700, 5, 64),
    ])
    def test_matches_segment_sum(self, impl, n, f, mb):
        bins, payload, mask = _case(n, f, mb, seed=n + mb)
        want = np.asarray(leaf_histogram(bins, payload, mask, mb))
        got = np.asarray(pallas_histogram(bins, payload, mask, mb,
                                          impl=impl, row_tile=256,
                                          interpret=True))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        # counts are exact sums of 0/1 within f32 range
        np.testing.assert_allclose(got[..., 2], want[..., 2], atol=1e-4)

    def test_empty_mask(self):
        bins, payload, _ = _case(256, 3, 16, seed=1)
        mask = jnp.zeros(256, dtype=bool)
        got = np.asarray(pallas_histogram(bins, payload, mask, 16,
                                          row_tile=128, interpret=True))
        assert np.all(got == 0.0)

    def test_row_padding(self):
        # n not a multiple of row_tile: padded rows must contribute nothing
        bins, payload, mask = _case(300, 4, 16, seed=2)
        want = np.asarray(leaf_histogram(bins, payload, mask, 16))
        got = np.asarray(pallas_histogram(bins, payload, mask, 16,
                                          row_tile=256, interpret=True))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_feature_tiling(self):
        bins, payload, mask = _case(512, 10, 32, seed=3)
        want = np.asarray(leaf_histogram(bins, payload, mask, 32))
        got = np.asarray(pallas_histogram(bins, payload, mask, 32,
                                          row_tile=256, feat_tile=4,
                                          interpret=True))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_probe(self):
        assert probe(interpret=True)

    def test_probe_multi(self):
        # the wave-policy gate: full-M multi-leaf block shapes
        assert probe(interpret=True, multi=True)

    def test_refused_kernel_raises_on_tpu_degrades_elsewhere(
            self, monkeypatch):
        # off-TPU a kernel the backend refuses is a falsy result that
        # carries the message (compiled Pallas on CPU is refused); on a
        # TPU the BASE probes raise instead of letting training fall to
        # segment-sum, while the fused probe — an upgrade over a working
        # base — still degrades and keeps the message
        from lightgbm_tpu.ops import pallas_hist as ph
        from lightgbm_tpu.utils.log import LightGBMError
        res = probe(interpret=False)
        assert not res and res.cause == "compile"
        assert "interpret mode" in res.detail

        class _Tpu:
            platform = "tpu"

        monkeypatch.setattr(ph.jax, "devices", lambda *a: [_Tpu()])
        for kw in ({}, {"multi": True, "width": 4, "quantized": False}):
            with pytest.raises(LightGBMError, match="interpret mode"):
                probe(interpret=False, **kw)
        res = probe(interpret=False, fused=True, width=4, quantized=False)
        assert not res and res.cause == "compile"
        assert "interpret mode" in res.detail

    def test_multi_matches_per_leaf_interpret(self):
        rng = np.random.RandomState(21)
        n, f, mb = 512, 4, 16
        bins = jnp.asarray(rng.randint(0, mb, (f, n)).astype(np.uint8))
        payload = jnp.asarray(rng.randn(n, 3).astype(np.float32))
        leaf_id = jnp.asarray(rng.randint(0, 6, n).astype(np.int32))
        slots = jnp.asarray(np.array([2, 0, 6, 4], np.int32))  # 6 = pad
        from lightgbm_tpu.ops.pallas_hist import pallas_histogram_multi
        got = np.asarray(pallas_histogram_multi(
            bins, payload, leaf_id, slots, mb, row_tile=256,
            interpret=True))
        for i, sl in enumerate([2, 0, None, 4]):
            if sl is None:
                assert np.all(got[i] == 0.0)
            else:
                want = np.asarray(leaf_histogram(bins, payload,
                                                 leaf_id == sl, mb))
                np.testing.assert_allclose(got[i], want, rtol=1e-5,
                                           atol=1e-5)


class TestPallasHistogramQuantized:
    def _quant_case(self, n, f, mb, bins_q, seed, all_ones_w=True):
        rng = np.random.RandomState(seed)
        bins = rng.randint(0, mb, (f, n)).astype(np.uint8)
        s_g = np.float32(0.37)
        s_h = np.float32(0.11)
        gq = rng.randint(-bins_q, bins_q + 1, n).astype(np.float32)
        hq = rng.randint(0, bins_q + 1, n).astype(np.float32)
        w = np.ones(n, np.float32) if all_ones_w else \
            (rng.rand(n) < 0.8).astype(np.float32)
        payload = np.stack([gq * s_g * w, hq * s_h * w, w], axis=1)
        mask = rng.rand(n) < 0.6
        return (jnp.asarray(bins), jnp.asarray(payload), jnp.asarray(mask),
                jnp.float32(s_g), jnp.float32(s_h))

    @pytest.mark.parametrize("n,f,mb,bins_q", [
        (512, 4, 16, 8), (1000, 7, 32, 15), (2048, 3, 256, 4),
    ])
    def test_matches_segment_sum(self, n, f, mb, bins_q):
        bins, payload, mask, s_g, s_h = self._quant_case(
            n, f, mb, bins_q, seed=n + mb)
        want = np.asarray(leaf_histogram(bins, payload, mask, mb))
        got = np.asarray(pallas_histogram_quantized(
            bins, payload, mask, mb, s_g, s_h, row_tile=256,
            interpret=True))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        # counts and the recovered integer sums are exact
        np.testing.assert_array_equal(got[..., 2], want[..., 2])

    def test_bagging_zero_weights(self):
        # w in {0, 1}: zero-weight rows must vanish from every channel
        bins, payload, mask, s_g, s_h = self._quant_case(
            700, 5, 64, 8, seed=9, all_ones_w=False)
        want = np.asarray(leaf_histogram(bins, payload, mask, 64))
        got = np.asarray(pallas_histogram_quantized(
            bins, payload, mask, 64, s_g, s_h, row_tile=256,
            interpret=True))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(got[..., 2], want[..., 2])
