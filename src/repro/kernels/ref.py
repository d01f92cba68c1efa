"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

These are the ground truth the kernels are validated against in
``tests/test_kernels.py`` (shape/dtype sweeps, ``assert_allclose``) and the
implementations used for CPU execution and for dry-run lowering
(``impl='xla'``).

Notes on fidelity to the paper's operators (S5.1):
  * color deconvolution follows Ruifrok-Johnston optical-density unmixing
    (the paper uses OpenCV/ITK equivalents);
  * morphological reconstruction uses 4-connectivity; the GPU IWPP
    wavefront of [65] is replaced by separable forward/backward scans
    (same fixed point — see DESIGN.md hardware-adaptation notes);
  * connected component labeling is the union-find BWLabel of [50] on the
    host; the device path converges to the identical canonical labeling
    (min flat-index per component);
  * GLCM texture features follow Haralick's definitions.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.scan import roll_scan

# --------------------------------------------------------------------------
# Color deconvolution (stain unmixing)
# --------------------------------------------------------------------------
# Ruifrok & Johnston H&E+DAB stain matrix (rows: stains, cols: RGB OD).
RUIFROK_HED = np.array(
    [
        [0.650, 0.704, 0.286],  # hematoxylin
        [0.072, 0.990, 0.105],  # eosin
        [0.268, 0.570, 0.776],  # DAB
    ],
    dtype=np.float32,
)


def stain_inverse(stain_matrix: np.ndarray = RUIFROK_HED) -> np.ndarray:
    m = np.asarray(stain_matrix, dtype=np.float64)
    m = m / np.linalg.norm(m, axis=1, keepdims=True)
    return np.linalg.inv(m).astype(np.float32)


def color_deconv_ref(rgb: jax.Array, minv: jax.Array, eps: float = 1e-6) -> jax.Array:
    """(..., 3, H, W) float in [0,1] -> (..., 3, H, W) stain densities."""
    od = -jnp.log10(jnp.clip(rgb, eps, 1.0))
    # channels-first planar: out[s] = sum_c minv[c, s] * od[c], as f32
    # multiply-adds in the kernel's order: an einsum becomes a dot, which a
    # TPU runs in one bf16 pass at default precision
    c0, c1, c2 = od[..., 0, :, :], od[..., 1, :, :], od[..., 2, :, :]
    return jnp.stack(
        [minv[0, s] * c0 + minv[1, s] * c1 + minv[2, s] * c2 for s in range(3)],
        axis=-3,
    )


# --------------------------------------------------------------------------
# Morphological reconstruction by dilation (ReconToNuclei / FillHoles core)
# --------------------------------------------------------------------------
def _recon_scan_1d(marker: jax.Array, mask: jax.Array, axis: int, reverse: bool) -> jax.Array:
    """1-D grayscale reconstruction along ``axis`` via associative scan.

    The sequential recurrence m_j = min(I_j, max(J_j, m_{j-1})) is the
    composition of clamp functions f_j(x) = min(c_j, max(d_j, x)) with
    c=I (mask), d=J (marker); such functions compose closed-form:
      f2.f1 = (c', d') with c' = min(c2, max(d2, c1)), d' = max(d1, d2)
    so the whole row is a log-depth associative scan — the TPU-idiomatic
    replacement for the paper's GPU wavefront queues.  The scan is the
    roll-based doubling form (``kernels/scan.py``): XLA's TPU compiler
    takes minutes over a 4096-wide sweep of ``lax.associative_scan``.
    """

    def combine(a, b):
        c1, d1 = a
        c2, d2 = b
        return jnp.minimum(c2, jnp.maximum(d2, c1)), jnp.maximum(d1, d2)

    c, d = roll_scan(combine, (mask, marker), axis, reverse)
    return jnp.minimum(c, d)


def morph_recon_sweep_ref(marker: jax.Array, mask: jax.Array) -> jax.Array:
    """One 4-direction sweep (down, up, right, left) of reconstruction."""
    j = jnp.minimum(marker, mask)
    j = _recon_scan_1d(j, mask, axis=-2, reverse=False)
    j = _recon_scan_1d(j, mask, axis=-2, reverse=True)
    j = _recon_scan_1d(j, mask, axis=-1, reverse=False)
    j = _recon_scan_1d(j, mask, axis=-1, reverse=True)
    return j


def morph_recon_ref(marker: jax.Array, mask: jax.Array, max_iters: int = 256) -> jax.Array:
    """Grayscale reconstruction by dilation to fixed point (4-connectivity)."""

    def cond(state):
        j, prev, it = state
        return jnp.logical_and(jnp.any(j != prev), it < max_iters)

    def body(state):
        j, _, it = state
        return morph_recon_sweep_ref(j, mask), j, it + 1

    j0 = jnp.minimum(marker, mask)
    j1 = morph_recon_sweep_ref(j0, mask)
    j, _, _ = jax.lax.while_loop(cond, body, (j1, j0, jnp.asarray(1)))
    return j


def fill_holes_seed(mask01: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(marker, mask) of the fill-holes reconstruction: the complement of
    ``mask01``, seeded from its pixels on the image border."""
    inv = 1.0 - mask01
    h, w = mask01.shape[-2], mask01.shape[-1]
    border = jnp.zeros_like(mask01)
    border = border.at[..., 0, :].set(1.0).at[..., h - 1, :].set(1.0)
    border = border.at[..., :, 0].set(1.0).at[..., :, w - 1].set(1.0)
    return jnp.minimum(border, inv), inv


def fill_holes_ref(mask01: jax.Array) -> jax.Array:
    """Binary fill-holes via border-seeded reconstruction of the complement."""
    marker, inv = fill_holes_seed(mask01)
    background = morph_recon_ref(marker, inv)
    return 1.0 - background


# --------------------------------------------------------------------------
# Connected component labeling
# --------------------------------------------------------------------------
def ccl_unionfind_host(mask: np.ndarray) -> np.ndarray:
    """The paper's BWLabel: union-find forest over 4-neighbors (host oracle).

    Returns int32 labels; background = -1; each component labeled by the
    minimum flat index it contains (canonical form).
    """
    mask = np.asarray(mask) != 0
    h, w = mask.shape
    parent = np.arange(h * w, dtype=np.int64)

    def find(x: int) -> int:
        root = x
        while parent[root] != root:
            root = parent[root]
        while parent[x] != root:  # path compression
            parent[x], x = root, parent[x]
        return root

    def union(a: int, b: int) -> None:
        ra, rb = find(a), find(b)
        if ra != rb:
            if ra < rb:
                parent[rb] = ra
            else:
                parent[ra] = rb

    for i in range(h):
        for j in range(w):
            if not mask[i, j]:
                continue
            idx = i * w + j
            if i > 0 and mask[i - 1, j]:
                union(idx, idx - w)
            if j > 0 and mask[i, j - 1]:
                union(idx, idx - 1)
    labels = np.full((h, w), -1, dtype=np.int32)
    for i in range(h):
        for j in range(w):
            if mask[i, j]:
                labels[i, j] = find(i * w + j)
    return labels


def _ccl_scan_1d(labels: jax.Array, mask: jax.Array, axis: int, reverse: bool) -> jax.Array:
    """Min-label propagation along one axis within mask runs.

    f_j(x) = min(v_j, x if p_j else +inf); composes closed-form:
      (v', p') = (min(v2, v1 if p2 else inf), p1 & p2)
    """
    big = jnp.asarray(jnp.iinfo(jnp.int32).max, labels.dtype)

    def combine(a, b):
        v1, p1 = a
        v2, p2 = b
        v = jnp.minimum(v2, jnp.where(p2, v1, big))
        return v, jnp.logical_and(p1, p2)

    v, _ = roll_scan(combine, (labels, mask), axis, reverse)
    return jnp.where(mask, jnp.minimum(labels, v), labels)


def ccl_sweep_ref(labels: jax.Array, mask: jax.Array) -> jax.Array:
    l = _ccl_scan_1d(labels, mask, axis=-2, reverse=False)
    l = _ccl_scan_1d(l, mask, axis=-2, reverse=True)
    l = _ccl_scan_1d(l, mask, axis=-1, reverse=False)
    l = _ccl_scan_1d(l, mask, axis=-1, reverse=True)
    return l


def ccl_ref(mask: jax.Array, max_iters: int = 256) -> jax.Array:
    """Min-label propagation to fixed point; canonical (min flat index)."""
    mask_b = mask != 0
    h, w = mask.shape[-2], mask.shape[-1]
    init = jnp.arange(h * w, dtype=jnp.int32).reshape(mask.shape[-2:])
    init = jnp.broadcast_to(init, mask.shape)
    big = jnp.asarray(jnp.iinfo(jnp.int32).max, jnp.int32)
    labels = jnp.where(mask_b, init, big)

    def cond(state):
        l, prev, it = state
        return jnp.logical_and(jnp.any(l != prev), it < max_iters)

    def body(state):
        l, _, it = state
        return ccl_sweep_ref(l, mask_b), l, it + 1

    l1 = ccl_sweep_ref(labels, mask_b)
    l, _, _ = jax.lax.while_loop(cond, body, (l1, labels, jnp.asarray(1)))
    return jnp.where(mask_b, l, -1)


# --------------------------------------------------------------------------
# GLCM + histogram texture features (feature computation stage)
# --------------------------------------------------------------------------
def quantize_ref(tile: jax.Array, num_bins: int) -> jax.Array:
    """float [0,1] -> int32 bins [0, num_bins)."""
    return jnp.clip((tile * num_bins).astype(jnp.int32), 0, num_bins - 1)


def glcm_ref(bins: jax.Array, num_bins: int) -> jax.Array:
    """Horizontal-neighbor co-occurrence counts: (..., NB, NB) float32.

    Computed as a one-hot matmul (the TPU adaptation: GLCM accumulation
    becomes an MXU contraction instead of scatter-adds).
    """
    left = bins[..., :, :-1]
    right = bins[..., :, 1:]
    lhot = jax.nn.one_hot(left.reshape(*bins.shape[:-2], -1), num_bins, dtype=jnp.float32)
    rhot = jax.nn.one_hot(right.reshape(*bins.shape[:-2], -1), num_bins, dtype=jnp.float32)
    return jnp.einsum("...pa,...pb->...ab", lhot, rhot)


def glcm_features_ref(glcm: jax.Array) -> jax.Array:
    """Haralick features from a GLCM: (contrast, energy, homogeneity,
    entropy, correlation) -> (..., 5)."""
    nb = glcm.shape[-1]
    p = glcm / jnp.clip(glcm.sum(axis=(-2, -1), keepdims=True), 1e-12)
    i = jnp.arange(nb, dtype=jnp.float32)[:, None]
    j = jnp.arange(nb, dtype=jnp.float32)[None, :]
    contrast = (p * (i - j) ** 2).sum(axis=(-2, -1))
    energy = (p**2).sum(axis=(-2, -1))
    homogeneity = (p / (1.0 + jnp.abs(i - j))).sum(axis=(-2, -1))
    entropy = -(p * jnp.log(jnp.clip(p, 1e-12, 1.0))).sum(axis=(-2, -1))
    mu_i = (p * i).sum(axis=(-2, -1))
    mu_j = (p * j).sum(axis=(-2, -1))
    var_i = (p * (i - mu_i[..., None, None]) ** 2).sum(axis=(-2, -1))
    var_j = (p * (j - mu_j[..., None, None]) ** 2).sum(axis=(-2, -1))
    cov = (p * (i - mu_i[..., None, None]) * (j - mu_j[..., None, None])).sum(axis=(-2, -1))
    corr = cov / jnp.clip(jnp.sqrt(var_i * var_j), 1e-12)
    return jnp.stack([contrast, energy, homogeneity, entropy, corr], axis=-1)


def histogram_ref(bins: jax.Array, num_bins: int) -> jax.Array:
    hot = jax.nn.one_hot(bins.reshape(*bins.shape[:-2], -1), num_bins, dtype=jnp.float32)
    return hot.sum(axis=-2)


def histogram_features_ref(hist: jax.Array) -> jax.Array:
    """(mean, std, skewness, kurtosis) of the quantized intensity dist."""
    nb = hist.shape[-1]
    n = jnp.clip(hist.sum(axis=-1, keepdims=True), 1e-12)
    p = hist / n
    x = jnp.arange(nb, dtype=jnp.float32)
    mean = (p * x).sum(axis=-1)
    var = (p * (x - mean[..., None]) ** 2).sum(axis=-1)
    std = jnp.sqrt(jnp.clip(var, 1e-12))
    skew = (p * ((x - mean[..., None]) / std[..., None]) ** 3).sum(axis=-1)
    kurt = (p * ((x - mean[..., None]) / std[..., None]) ** 4).sum(axis=-1)
    return jnp.stack([mean, std, skew, kurt], axis=-1)


# --------------------------------------------------------------------------
# Attention (LM workloads; beyond-paper hot spot)
# --------------------------------------------------------------------------
def attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    q_offset: int = 0,
) -> jax.Array:
    """Reference softmax attention with GQA + causal + sliding window.

    q: (B, Hq, Tq, D); k, v: (B, Hkv, Tk, D); returns (B, Hq, Tq, D).
    ``q_offset`` positions queries at absolute index q_offset + arange(Tq)
    (decode: Tq=1, q_offset=cache_len-1).
    """
    b, hq, tq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kr.astype(jnp.float32))
    logits *= scale
    qpos = q_offset + jnp.arange(tq)[:, None]
    kpos = jnp.arange(k.shape[2])[None, :]
    mask = jnp.ones((tq, k.shape[2]), dtype=bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= qpos - kpos < window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, vr.astype(jnp.float32)).astype(q.dtype)


def attention_chunked_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    q_offset: int = 0,
    chunk: int = 1024,
) -> jax.Array:
    """Online-softmax attention scanned over key chunks (flash structure,
    pure XLA).  Never materializes the (Tq, Tk) score matrix — the
    lowerable stand-in for the Pallas flash kernel, used to drive the
    memory roofline term down on train/prefill cells.

    GQA is handled by a grouped einsum (no repeated K/V in memory).
    """
    b, hq, tq, d = q.shape
    _, hkv, tk, _ = k.shape
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    chunk = min(chunk, tk)
    n_chunks = -(-tk // chunk)
    pad = n_chunks * chunk - tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qg = q.reshape(b, hkv, g, tq, d).astype(jnp.float32)
    kc = jnp.moveaxis(k.reshape(b, hkv, n_chunks, chunk, d), 2, 0).astype(jnp.float32)
    vc = jnp.moveaxis(v.reshape(b, hkv, n_chunks, chunk, d), 2, 0).astype(jnp.float32)
    qpos = q_offset + jnp.arange(tq)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, ci = xs
        s = jnp.einsum("bkgqd,bkcd->bkgqc", qg, kb) * scale
        kpos = ci * chunk + jnp.arange(chunk)
        mask = kpos[None, :] < tk
        if causal:
            mask = mask & (qpos[:, None] >= kpos[None, :])
        if window is not None:
            mask = mask & (qpos[:, None] - kpos[None, :] < window)
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bkgqc,bkcd->bkgqd", p, vb)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, tq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, tq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, tq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kc, vc, jnp.arange(n_chunks))
    )
    out = acc / jnp.where(l > 0, l, 1.0)[..., None]
    return out.reshape(b, hq, tq, d).astype(q.dtype)


# --------------------------------------------------------------------------
# Mamba2 SSD scan (beyond-paper hot spot for the SSM archs)
# --------------------------------------------------------------------------
def ssd_scan_ref(
    x: jax.Array,  # (B, T, H, P)
    dt: jax.Array,  # (B, T, H)        softplus-ed step sizes
    a: jax.Array,  # (H,)              negative decay rates (A = -exp(a_log))
    b_: jax.Array,  # (B, T, G, N)
    c_: jax.Array,  # (B, T, G, N)
    d_: jax.Array | None = None,  # (H,) skip
    h0: jax.Array | None = None,  # (B, H, N, P) initial state
) -> tuple[jax.Array, jax.Array]:
    """Sequential state-space-duality scan: the oracle for ssd_scan.

    h_t = exp(dt_t * a) * h_{t-1} + dt_t * B_t x_t^T ;  y_t = C_t^T h_t (+ D x).
    Returns (y: (B,T,H,P), h_final: (B,H,N,P)).
    """
    bsz, t, h, p = x.shape
    g, n = b_.shape[2], b_.shape[3]
    rep = h // g
    bh = jnp.repeat(b_, rep, axis=2)  # (B, T, H, N)
    ch = jnp.repeat(c_, rep, axis=2)
    decay = jnp.exp(dt * a[None, None, :])  # (B, T, H)
    if h0 is None:
        h0 = jnp.zeros((bsz, h, n, p), dtype=jnp.float32)

    def step(hprev, inputs):
        xt, dtt, dect, bt, ct = inputs  # (B,H,P) (B,H) (B,H) (B,H,N) (B,H,N)
        hnew = (
            dect[..., None, None] * hprev
            + (dtt[..., None] * bt)[..., :, None] * xt[..., None, :]
        )
        yt = jnp.einsum("bhn,bhnp->bhp", ct, hnew)
        return hnew, yt

    xs = (
        jnp.moveaxis(x.astype(jnp.float32), 1, 0),
        jnp.moveaxis(dt.astype(jnp.float32), 1, 0),
        jnp.moveaxis(decay.astype(jnp.float32), 1, 0),
        jnp.moveaxis(bh.astype(jnp.float32), 1, 0),
        jnp.moveaxis(ch.astype(jnp.float32), 1, 0),
    )
    hf, ys = jax.lax.scan(step, h0.astype(jnp.float32), xs)
    y = jnp.moveaxis(ys, 0, 1)
    if d_ is not None:
        y = y + d_[None, None, :, None] * x.astype(jnp.float32)
    return y.astype(x.dtype), hf


def ssd_scan_chunked_ref(
    x: jax.Array,  # (B, T, H, P)
    dt: jax.Array,  # (B, T, H)
    a: jax.Array,  # (H,)
    b_: jax.Array,  # (B, T, G, N)
    c_: jax.Array,  # (B, T, G, N)
    d_: jax.Array | None = None,
    *,
    chunk: int = 128,
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD in pure XLA (the Pallas kernel's algorithm, lowerable).

    Scans over T/chunk blocks instead of T steps: within a chunk the work
    is dense matmuls (segment-decay masked C B^T), and only the (N, P)
    state crosses chunk boundaries — the recurrent-state HBM traffic drops
    by ~chunk x versus the step-by-step scan.  Used for training/prefill
    lowering (the step scan remains the numerical oracle).
    """
    bsz, t, h, p = x.shape
    g, n = b_.shape[2], b_.shape[3]
    rep = h // g
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    nc = t // chunk
    f32 = jnp.float32
    xh = jnp.moveaxis(x.astype(f32), 1, 2).reshape(bsz, h, nc, chunk, p)
    dth = jnp.moveaxis(dt.astype(f32), 1, 2).reshape(bsz, h, nc, chunk)
    bh = jnp.repeat(b_.astype(f32), rep, axis=2)
    ch = jnp.repeat(c_.astype(f32), rep, axis=2)
    bh = jnp.moveaxis(bh, 1, 2).reshape(bsz, h, nc, chunk, n)
    ch = jnp.moveaxis(ch, 1, 2).reshape(bsz, h, nc, chunk, n)
    la = dth * a[None, :, None, None]  # (B, H, nc, L) log decay
    cum = jnp.cumsum(la, axis=-1)
    total = cum[..., -1]
    li = jnp.arange(chunk)
    seg = jnp.where(
        li[:, None] >= li[None, :],
        jnp.exp(cum[..., :, None] - cum[..., None, :]),
        0.0,
    )  # (B, H, nc, L, L)
    gmat = (
        jnp.einsum("bhcln,bhcmn->bhclm", ch, bh) * seg * dth[..., None, :]
    )
    y_intra = jnp.einsum("bhclm,bhcmp->bhclp", gmat, xh)
    # inter-chunk state recurrence (scan over nc chunks)
    w = jnp.exp(total[..., None] - cum) * dth  # (B,H,nc,L)
    state_in = jnp.einsum("bhcln,bhclp->bhcnp", bh * w[..., None], xh)

    def carry_fn(hprev, xs):
        tot, s_in = xs  # (B,H), (B,H,N,P)
        hnew = jnp.exp(tot)[..., None, None] * hprev + s_in
        return hnew, hprev

    h0 = jnp.zeros((bsz, h, n, p), f32)
    hf, hprevs = jax.lax.scan(
        carry_fn,
        h0,
        (jnp.moveaxis(total, 2, 0), jnp.moveaxis(state_in, 2, 0)),
    )
    hprevs = jnp.moveaxis(hprevs, 0, 2)  # (B,H,nc,N,P) state entering chunk
    y_inter = jnp.exp(cum)[..., None] * jnp.einsum("bhcln,bhcnp->bhclp", ch, hprevs)
    y = (y_intra + y_inter).reshape(bsz, h, t, p)
    y = jnp.moveaxis(y, 1, 2)
    if d_ is not None:
        y = y + d_[None, None, :, None] * x.astype(f32)
    return y.astype(x.dtype), hf
