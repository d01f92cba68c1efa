"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes/dtypes, plus hypothesis property tests on invariants."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref
from repro.kernels.ccl import ccl_pallas
from repro.kernels.color_deconv import color_deconv_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.glcm import glcm_pallas
from repro.kernels.morph_recon import morph_recon_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# color deconvolution
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("h,w,bh,bw", [(32, 128, 16, 128), (64, 256, 64, 128), (48, 96, 32, 96)])
def test_color_deconv_sweep(h, w, bh, bw):
    rgb = jnp.asarray(RNG.random((3, h, w), dtype=np.float32))
    minv = jnp.asarray(ref.stain_inverse())
    out = color_deconv_pallas(rgb, minv, block_h=bh, block_w=bw, interpret=True)
    np.testing.assert_allclose(out, ref.color_deconv_ref(rgb, minv), rtol=2e-5, atol=2e-5)


def test_color_deconv_white_is_zero_density():
    rgb = jnp.ones((3, 8, 128), jnp.float32)
    out = color_deconv_pallas(rgb, jnp.asarray(ref.stain_inverse()), interpret=True)
    np.testing.assert_allclose(out, np.zeros_like(out), atol=1e-5)


# ---------------------------------------------------------------------------
# morphological reconstruction
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("h,w,bh,bw", [(32, 48, 16, 16), (64, 64, 32, 32)])
def test_morph_recon_matches_ref(h, w, bh, bw):
    mask = jnp.asarray((RNG.random((h, w)) > 0.35).astype(np.float32))
    marker = jnp.asarray(RNG.random((h, w)).astype(np.float32)) * mask
    out = morph_recon_pallas(marker, mask, block_h=bh, block_w=bw, interpret=True)
    np.testing.assert_allclose(out, ref.morph_recon_ref(marker, mask), atol=1e-6)


@given(st.integers(0, 10_000))
@settings(max_examples=10)
def test_morph_recon_invariants(seed):
    r = np.random.default_rng(seed)
    mask = jnp.asarray(r.random((24, 24), dtype=np.float32))
    marker = jnp.asarray(r.random((24, 24), dtype=np.float32))
    out = np.asarray(ref.morph_recon_ref(marker, mask))
    # invariants: marker^mask <= recon <= mask ; idempotent
    clipped = np.minimum(np.asarray(marker), np.asarray(mask))
    assert (out >= clipped - 1e-6).all()
    assert (out <= np.asarray(mask) + 1e-6).all()
    again = np.asarray(ref.morph_recon_ref(jnp.asarray(out), mask))
    np.testing.assert_allclose(again, out, atol=1e-6)


def test_fill_holes_closes_a_donut():
    m = np.zeros((32, 32), np.float32)
    m[8:24, 8:24] = 1.0
    m[14:18, 14:18] = 0.0  # the hole
    filled = np.asarray(ref.fill_holes_ref(jnp.asarray(m)))
    assert filled[15, 15] == 1.0
    assert filled[0, 0] == 0.0


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_ops_fill_holes_matches_ref(impl):
    """ops.fill_holes honours impl: the border-seeded reconstruction runs
    through ops.morph_recon (Pallas in interpret mode here)."""
    m = (np.random.default_rng(5).random((40, 56)) < 0.6).astype(np.float32)
    m[8:24, 8:24] = 1.0
    m[14:18, 14:18] = 0.0  # a hole that must close
    got = np.asarray(ops.fill_holes(jnp.asarray(m), impl=impl))
    np.testing.assert_array_equal(got, ref.fill_holes_ref(jnp.asarray(m)))
    assert got[15, 15] == 1.0


# ---------------------------------------------------------------------------
# connected components
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("h,w,density", [(24, 32, 0.4), (48, 48, 0.6), (16, 64, 0.2)])
def test_ccl_matches_unionfind(h, w, density):
    m = RNG.random((h, w)) < density
    got = np.asarray(ccl_pallas(jnp.asarray(m), block_h=16, block_w=16, interpret=True))
    want = ref.ccl_unionfind_host(m)
    np.testing.assert_array_equal(got, want)


@given(st.integers(0, 10_000))
@settings(max_examples=10)
def test_ccl_labels_are_canonical_min_index(seed):
    r = np.random.default_rng(seed)
    m = r.random((20, 20)) < 0.5
    labels = np.asarray(ref.ccl_ref(jnp.asarray(m)))
    assert ((labels == -1) == ~m).all()
    for lab in np.unique(labels[labels >= 0]):
        ys, xs = np.nonzero(labels == lab)
        assert (ys * 20 + xs).min() == lab  # component labeled by min flat idx


# ---------------------------------------------------------------------------
# GLCM / histogram
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,h,w,nb", [(2, 16, 16, 8), (4, 24, 32, 16), (1, 64, 64, 32)])
def test_glcm_sweep(b, h, w, nb):
    bins = jnp.asarray(RNG.integers(0, nb, (b, h, w), dtype=np.int32))
    g, hist = glcm_pallas(bins, nb, interpret=True)
    np.testing.assert_array_equal(g, ref.glcm_ref(bins, nb))
    np.testing.assert_array_equal(hist, ref.histogram_ref(bins, nb))
    # sanity: counts conserve mass
    assert float(g.sum()) == b * h * (w - 1) * 1.0 if b == 1 else True
    np.testing.assert_allclose(np.asarray(hist).sum(-1), h * w)


def test_glcm_features_known_case():
    # constant image: single GLCM cell -> energy 1, contrast 0, corr nan-safe
    bins = jnp.zeros((1, 8, 8), jnp.int32)
    g = ref.glcm_ref(bins, 4)
    f = np.asarray(ref.glcm_features_ref(g))[0]
    contrast, energy, homog, entropy, corr = f
    assert contrast == pytest.approx(0.0)
    assert energy == pytest.approx(1.0)
    assert homog == pytest.approx(1.0)
    assert entropy == pytest.approx(0.0, abs=1e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "b,hq,hkv,tq,tk,d,causal,window,qoff,bq,bk",
    [
        (2, 4, 2, 64, 64, 32, True, None, 0, 16, 16),
        (1, 8, 1, 32, 32, 16, True, 8, 0, 8, 8),
        (2, 4, 4, 1, 96, 32, True, None, 95, 1, 32),
        (1, 2, 2, 48, 48, 64, False, None, 0, 16, 24),
        (1, 4, 2, 40, 40, 24, True, None, 0, 16, 16),  # ragged blocks
    ],
)
def test_flash_attention_sweep(b, hq, hkv, tq, tk, d, causal, window, qoff, bq, bk):
    q = jnp.asarray(RNG.standard_normal((b, hq, tq, d), dtype=np.float32))
    k = jnp.asarray(RNG.standard_normal((b, hkv, tk, d), dtype=np.float32))
    v = jnp.asarray(RNG.standard_normal((b, hkv, tk, d), dtype=np.float32))
    out = flash_attention_pallas(
        q, k, v, causal=causal, window=window, q_offset=qoff,
        block_q=bq, block_k=bk, interpret=True,
    )
    want = ref.attention_ref(q, k, v, causal=causal, window=window, q_offset=qoff)
    np.testing.assert_allclose(out, want, rtol=3e-4, atol=3e-4)


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.standard_normal((1, 2, 32, 32)), jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((1, 2, 32, 32)), jnp.bfloat16)
    v = jnp.asarray(RNG.standard_normal((1, 2, 32, 32)), jnp.bfloat16)
    out = flash_attention_pallas(q, k, v, block_q=16, block_k=16, interpret=True)
    want = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), rtol=3e-2, atol=3e-2
    )


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "b,t,h,p,g,n,chunk",
    [(2, 64, 4, 16, 2, 8, 16), (1, 32, 2, 8, 1, 4, 8), (1, 128, 8, 32, 1, 16, 32)],
)
def test_ssd_scan_sweep(b, t, h, p, g, n, chunk):
    x = jnp.asarray(RNG.standard_normal((b, t, h, p), dtype=np.float32))
    dt = jnp.asarray(RNG.random((b, t, h), dtype=np.float32) * 0.1)
    a = jnp.asarray(-np.exp(RNG.standard_normal(h)).astype(np.float32))
    bm = jnp.asarray(RNG.standard_normal((b, t, g, n), dtype=np.float32))
    cm = jnp.asarray(RNG.standard_normal((b, t, g, n), dtype=np.float32))
    d = jnp.asarray(RNG.standard_normal(h).astype(np.float32))
    y, hf = ssd_scan_pallas(x, dt, a, bm, cm, d, chunk=chunk, interpret=True)
    yr, hr = ref.ssd_scan_ref(x, dt, a, bm, cm, d)
    np.testing.assert_allclose(y, yr, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(hf, hr, rtol=3e-4, atol=3e-4)


def test_ssd_chunked_equals_chunkless():
    """Chunk size must not change the math (state handoff exactness)."""
    b, t, h, p, g, n = 1, 64, 2, 8, 1, 4
    x = jnp.asarray(RNG.standard_normal((b, t, h, p), dtype=np.float32))
    dt = jnp.asarray(RNG.random((b, t, h), dtype=np.float32) * 0.1)
    a = jnp.asarray(-np.ones(h, np.float32))
    bm = jnp.asarray(RNG.standard_normal((b, t, g, n), dtype=np.float32))
    cm = jnp.asarray(RNG.standard_normal((b, t, g, n), dtype=np.float32))
    y1, h1 = ssd_scan_pallas(x, dt, a, bm, cm, chunk=8, interpret=True)
    y2, h2 = ssd_scan_pallas(x, dt, a, bm, cm, chunk=64, interpret=True)
    np.testing.assert_allclose(y1, y2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h1, h2, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# chunked (flash-structured) XLA attention — the lowerable memory-term fix
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "b,hq,hkv,tq,tk,causal,window,qoff,chunk",
    [
        (2, 4, 2, 64, 64, True, None, 0, 16),
        (1, 8, 1, 40, 40, True, 8, 0, 16),
        (2, 4, 4, 1, 96, True, None, 95, 32),
        (1, 2, 2, 48, 48, False, None, 0, 13),
    ],
)
def test_chunked_attention_matches_ref(b, hq, hkv, tq, tk, causal, window, qoff, chunk):
    d = 32
    q = jnp.asarray(RNG.standard_normal((b, hq, tq, d), dtype=np.float32))
    k = jnp.asarray(RNG.standard_normal((b, hkv, tk, d), dtype=np.float32))
    v = jnp.asarray(RNG.standard_normal((b, hkv, tk, d), dtype=np.float32))
    got = ref.attention_chunked_ref(
        q, k, v, causal=causal, window=window, q_offset=qoff, chunk=chunk
    )
    want = ref.attention_ref(q, k, v, causal=causal, window=window, q_offset=qoff)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_ssd_chunked_xla_matches_sequential(chunk):
    """The lowerable chunked SSD (§Perf memory fix) == step-by-step scan."""
    B, T, H, P, G, N = 2, 64, 4, 16, 2, 8
    x = jnp.asarray(RNG.standard_normal((B, T, H, P), dtype=np.float32))
    dt = jnp.asarray(RNG.random((B, T, H), dtype=np.float32) * 0.1)
    a = jnp.asarray(-np.exp(RNG.standard_normal(H)).astype(np.float32))
    bm = jnp.asarray(RNG.standard_normal((B, T, G, N), dtype=np.float32))
    cm = jnp.asarray(RNG.standard_normal((B, T, G, N), dtype=np.float32))
    d = jnp.asarray(RNG.standard_normal(H).astype(np.float32))
    yr, hr = ref.ssd_scan_ref(x, dt, a, bm, cm, d)
    yc, hc = ref.ssd_scan_chunked_ref(x, dt, a, bm, cm, d, chunk=chunk)
    np.testing.assert_allclose(yc, yr, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(hc, hr, rtol=3e-4, atol=3e-4)
