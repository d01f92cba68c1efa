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

from repro.kernels.distance import FAR, no_background
from repro.kernels.fixed_point import fixed_point
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
    return morph_recon_ref_counted(marker, mask, max_iters)[0]


def morph_recon_ref_counted(
    marker: jax.Array, mask: jax.Array, max_iters: int = 256
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``morph_recon_ref``, with its sweeps and whether it converged."""
    j0 = jnp.minimum(marker, mask)
    step = lambda j: morph_recon_sweep_ref(j, mask)  # noqa: E731
    return fixed_point(step, j0, step(j0), max_iters)


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
    return ccl_ref_counted(mask, max_iters)[0]


def ccl_ref_counted(
    mask: jax.Array, max_iters: int = 256
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``ccl_ref``, with its sweeps and whether it converged."""
    mask_b = mask != 0
    h, w = mask.shape[-2], mask.shape[-1]
    init = jnp.arange(h * w, dtype=jnp.int32).reshape(mask.shape[-2:])
    init = jnp.broadcast_to(init, mask.shape)
    big = jnp.asarray(jnp.iinfo(jnp.int32).max, jnp.int32)
    labels = jnp.where(mask_b, init, big)
    step = lambda l: ccl_sweep_ref(l, mask_b)  # noqa: E731
    l, calls, converged = fixed_point(step, labels, step(labels), max_iters)
    return jnp.where(mask_b, l, -1), calls, converged


# --------------------------------------------------------------------------
# Nucleus split: distance transform, h-maxima markers, watershed
# --------------------------------------------------------------------------
# Every step is 4-connected and keeps the opened mask as its domain: the
# graph has the mask's pixels as nodes and an edge between 4-neighbours
# that are both in it, and R(marker, mask) is reconstruction by dilation on
# that graph (``morph_recon`` with background 0 below every value on the
# mask, so nothing crosses the background).
#
#   1. d2(p): squared Euclidean distance to the nearest background pixel of
#      the tile, int32, 0 on background; pixels outside the tile are not
#      background (scipy.ndimage.distance_transform_edt's convention).
#   2. Markers (Pre-Watershed): g = R(d2 - h, d2); marker pixels are where
#      g - R(g - 1, g) >= 1, the regional maxima of the h-maxima transform;
#      each marker is labelled by its smallest raster index (a CCL).  The
#      values are small integers, exact in float32 (d2 < 2**24).
#   3. Cost: c = R(m, d2), m = d2 on marker pixels and 0 elsewhere: the
#      maximin bottleneck of a path from any marker.
#   4. Watershed: an edge q -> p between 4-neighbours on the mask when
#      c(p) == min(c(q), d2(p)); a pixel's label is the smallest marker label
#      that reaches it along such edges.  This is the least fixed point of a
#      min over a fixed graph, so every algorithm gives the same labels.
#      Each basin is then relabelled by its smallest raster index.
#   5. AreaThreshold: objects of fewer than min_area pixels become
#      background (-1, and 0 in the mask).
#
# Every component of the mask holds a marker: g attains its maximum over the
# component on a plateau, which is a regional maximum.  A component where
# d2 <= h throughout has g constant, so it is one marker and stays one
# object, as without the split.
#
# Every pixel of a component is reached.  By the fixed point of step 3, a
# pixel p off the markers has c(p) = min(d2(p), max_q c(q)) over its
# neighbours q, and c(p) >= 1 on the mask.  Suppose some pixels were not
# reached, and let p be one with the largest c among them; take a path of
# bottleneck c(p) from a marker x0 to p, and its first unreached pixel x_i.
# Every pixel of the path has c and d2 >= c(p), and x_i has c(x_i) = c(p)
# (no unreached c is larger), so c(x_i) = min(c(x_{i-1}), d2(x_i)): the
# edge x_{i-1} -> x_i exists and x_{i-1} is reached, so x_i is too.
#
# Every basin is connected and holds a pixel of its marker: if p has label l
# along a path from marker l, every pixel on that path has label l, for a
# smaller label reaching one of them would reach p as well.
def distance_transform_ref(mask: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Step 1: (H, W) mask -> (int32 squared distances, window steps).

    Along rows, the distance to the nearest background column from running
    max/min of background indices; along columns, ``min_i' a(i', j)**2 +
    (i - i')**2`` over a window widened one offset a step until each pixel's
    minimum is at most (window + 1)**2, beyond which no candidate can win.
    """
    h, w = mask.shape
    fg = mask != 0
    col = jnp.broadcast_to(jnp.arange(w, dtype=jnp.int32), (h, w))
    last = jax.lax.cummax(jnp.where(fg, -FAR, col), axis=1)
    nxt = jax.lax.cummin(jnp.where(fg, w + FAR, col), axis=1, reverse=True)
    a = jnp.minimum(jnp.minimum(col - last, nxt - col), FAR)
    # rows moved by a dynamic offset t: slices of the column range padded once
    pad = jnp.pad(a, ((h, h), (0, 0)), constant_values=FAR)

    def cond(state):
        best, t = state
        return jnp.logical_and(t < h - 1, jnp.any(best > (t + 1) * (t + 1)))

    def body(state):
        best, t = state
        t = t + 1
        up = jax.lax.dynamic_slice_in_dim(pad, h - t, h, axis=0)
        down = jax.lax.dynamic_slice_in_dim(pad, h + t, h, axis=0)
        return jnp.minimum(best, jnp.minimum(up * up, down * down) + t * t), t

    has_bg = jnp.any(~fg)
    best, t = jax.lax.while_loop(cond, body, (a * a, jnp.where(has_bg, 0, h)))
    d2 = jnp.where(has_bg, best, no_background(h, w))
    return d2, jnp.where(has_bg, t, 0)


def _neighbour(x: jax.Array, k: int, fill) -> jax.Array:
    """Each pixel's neighbour in edge bit k's direction: above, below, left,
    right; ``fill`` off the image."""
    pads = (((1, 0), (0, 0)), ((0, 1), (0, 0)), ((0, 0), (1, 0)), ((0, 0), (0, 1)))
    cut = (x[:-1, :], x[1:, :], x[:, :-1], x[:, 1:])[k]
    return jnp.pad(cut, pads[k], constant_values=fill)


def _edge_bits(ok) -> jax.Array:
    """int32 edge bits from ``ok(k)``, the bool plane of bit k."""
    edges = jnp.zeros((), jnp.int32)
    for k in range(4):
        edges = edges | (ok(k).astype(jnp.int32) << k)
    return edges


def watershed_edges(d2: jax.Array, cost: jax.Array) -> jax.Array:
    """Step 4's edges as int32 bits, one per neighbour a label may come from:
    bit 0 from above, 1 from below, 2 from the left, 3 from the right.  An
    edge q -> p needs both on the mask (d2 > 0) and c(p) == min(c(q), d2(p))."""
    on = d2 > 0
    d2 = d2.astype(cost.dtype)
    return _edge_bits(lambda k: on & _neighbour(on, k, False)
                      & (cost == jnp.minimum(_neighbour(cost, k, 0), d2)))


def same_label_edges(labels: jax.Array, on: jax.Array) -> jax.Array:
    """Edge bits between 4-neighbours that are both ``on`` and carry the same
    label: propagating raster indices along them names each connected basin
    by its smallest raster index (step 4's relabelling)."""
    return _edge_bits(lambda k: on & _neighbour(on, k, False)
                      & (labels == _neighbour(labels, k, -1)))


def watershed_ref(
    labels: jax.Array, edges: jax.Array, max_iters: int = 256
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Step 4's propagation: ``labels`` on the markers (int32 max elsewhere)
    carried along ``edges`` by directional min scans, one 4-direction sweep a
    step, to the least fixed point.  -> (labels, sweeps, converged)."""
    flags = [((edges >> k) & 1) != 0 for k in range(4)]

    def step(l):
        for (axis, reverse), flag in zip(((-2, False), (-2, True), (-1, False), (-1, True)), flags):
            l = _ccl_scan_1d(l, flag, axis=axis, reverse=reverse)
        return l

    return fixed_point(step, labels, step(labels), max_iters)


def split_nuclei_host(mask: np.ndarray, h: int, min_area: int) -> np.ndarray:
    """Steps 1-5 in NumPy and SciPy (host oracle): opened mask -> int32
    labels, -1 off.  Reconstructions by geodesic dilation one pixel a step,
    the watershed by relaxation over its edges, both to the fixed point."""
    from scipy import ndimage

    on = np.asarray(mask) != 0
    d2 = np.rint(ndimage.distance_transform_edt(on) ** 2).astype(np.int64)
    low = np.iinfo(np.int64).min // 2  # off the mask: never passes a value

    def neighbours(x, fill):
        p = np.pad(np.where(on, x, fill), 1, constant_values=fill)
        return p[:-2, 1:-1], p[2:, 1:-1], p[1:-1, :-2], p[1:-1, 2:]

    def recon(marker, ceiling):
        x = np.where(on, np.minimum(marker, ceiling), low)
        while True:
            nxt = np.where(on, np.minimum(ceiling, np.maximum.reduce([x, *neighbours(x, low)])), low)
            if np.array_equal(nxt, x):
                return x
            x = nxt

    g = recon(d2 - h, d2)
    markers = on & (g - recon(g - 1, g) >= 1)
    big = np.iinfo(np.int64).max
    labels = np.where(markers, ccl_unionfind_host(markers).astype(np.int64), big)
    cost = recon(np.where(markers, d2, 0), d2)
    edge = [on & (cost == np.minimum(c, d2)) for c in neighbours(cost, low)]
    while True:
        came = [np.where(e, v, big) for e, v in zip(edge, neighbours(labels, big))]
        nxt = np.where(on, np.minimum.reduce([labels, *came]), big)
        if np.array_equal(nxt, labels):
            break
        labels = nxt
    out = np.full(on.shape, -1, np.int32)
    ids, inv, area = np.unique(labels[on], return_inverse=True, return_counts=True)
    first = np.full(len(ids), big)
    np.minimum.at(first, inv, np.flatnonzero(on))
    out[on] = np.where(area[inv] >= min_area, first[inv], -1)
    return out


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
