"""Plain reference of the nucleus split that ``wsi_4k_ws`` adds to the
segmentation (arXiv:1405.7958 section 5: Pre-Watershed, Watershed and
AreaThreshold after BWLabel's mask).

Written from the equations below, not from the program: NumPy and SciPy
only, nothing imported from the program and nothing it made.  The mask and
everything before it, and the ROIs and features after it, are
``bench/reference.py``'s.

Every step is 4-connected and keeps the opened mask as its domain: the
graph below has the mask's pixels as nodes and an edge between 4-neighbours
that are both in the mask.  ``R(marker, mask)`` is reconstruction by
dilation on that graph: the largest ``x <= mask`` with ``x >= marker``
whose every pixel above its marker has a neighbour at least as high.

1. ``d2``: the squared Euclidean distance from each mask pixel to the
   nearest background pixel of the tile, an integer (``scipy.ndimage.
   distance_transform_edt`` squared and rounded; pixels outside the tile
   are not background, and a tile with no background pixel takes SciPy's
   distance to the pixel (-1, 0)).
2. Markers: ``g = R(d2 - h, d2)``; marker pixels are where
   ``g - R(g - 1, g) >= 1``, the regional maxima of the h-maxima transform;
   each marker is labelled by the smallest raster index of its 4-connected
   component.
3. Cost: ``c = R(m, d2)`` with ``m = d2`` on marker pixels and 0 elsewhere.
4. Watershed: an edge ``q -> p`` between 4-neighbours when
   ``c(p) == min(c(q), d2(p))``; a pixel's label is the smallest marker
   label that reaches it along such edges.  Each basin is then relabelled
   by its smallest raster index.
5. AreaThreshold: objects of fewer than ``min_area`` pixels become
   background (label -1, mask 0).

Here the reconstructions are geodesic dilations iterated to their fixed
point and the watershed labels a relaxation over the edges to its fixed
point, both on the mask's pixels alone.
"""
from __future__ import annotations

import numpy as np
from scipy import ndimage

from bench import reference

def squared_distance(mask: np.ndarray) -> np.ndarray:
    """Step 1: (H, W) bool -> int64 squared distances, 0 off the mask."""
    return np.rint(ndimage.distance_transform_edt(mask) ** 2).astype(np.int64)


def neighbours(mask: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """The mask's pixels as raster indices, and (4, K) the position in that
    list of each one's neighbour above, below, left and right (K where the
    neighbour is off the mask or off the tile)."""
    h, w = mask.shape
    flat = np.flatnonzero(mask)
    where = np.full(h * w, len(flat), np.int64)
    where[flat] = np.arange(len(flat))
    y, x = np.divmod(flat, w)
    nbr = np.full((4, len(flat)), len(flat), np.int64)
    for k, (dy, dx) in enumerate(((-1, 0), (1, 0), (0, -1), (0, 1))):
        yy, xx = y + dy, x + dx
        inside = (yy >= 0) & (yy < h) & (xx >= 0) & (xx < w)
        nbr[k, inside] = where[yy[inside] * w + xx[inside]]
    return flat, nbr


def _gather(x: np.ndarray, nbr: np.ndarray, fill) -> np.ndarray:
    """(4, K) neighbour values, ``fill`` where there is none."""
    return np.append(x, fill)[nbr]


def reconstruct(marker: np.ndarray, mask: np.ndarray, nbr: np.ndarray) -> np.ndarray:
    """``R(marker, mask)`` on the mask's pixels: dilate by the 4-neighbours
    under ``mask`` until nothing changes."""
    low = min(int(marker.min(initial=0)), 0) - 1
    x = np.minimum(marker, mask)
    while True:
        y = np.minimum(mask, np.maximum(x, _gather(x, nbr, low).max(axis=0)))
        if np.array_equal(y, x):
            return x
        x = y


def split(mask: np.ndarray, h: int, min_area: int) -> np.ndarray:
    """Steps 1-5 on the opened mask: (H, W) bool -> int32 labels, -1 off."""
    d2_full = squared_distance(mask)
    flat, nbr = neighbours(mask)
    d2 = d2_full.ravel()[flat]
    g = reconstruct(d2 - h, d2, nbr)
    marker = g - reconstruct(g - 1, g, nbr) >= 1
    marker_2d = np.zeros(mask.size, bool)
    marker_2d[flat[marker]] = True
    marker_labels = reference.canonical_labels(marker_2d.reshape(mask.shape)).ravel()[flat]
    marker_labels = marker_labels.astype(np.int64)

    c = reconstruct(np.where(marker, d2, 0), d2, nbr)
    # in-edges: q -> p where c(p) == min(c(q), d2(p))
    into = (nbr < len(flat)) & (c == np.minimum(_gather(c, nbr, 0), d2))
    big = np.iinfo(np.int64).max
    label = np.where(marker, marker_labels, big)
    while True:
        came = np.where(into, _gather(label, nbr, big), big).min(axis=0)
        nxt = np.minimum(label, came)
        if np.array_equal(nxt, label):
            break
        label = nxt
    if (label == big).any():
        raise AssertionError("a mask pixel that no marker reaches")

    ids, inv = np.unique(label, return_inverse=True)
    first = np.full(len(ids), big)
    np.minimum.at(first, inv, flat)
    keep = np.bincount(inv, minlength=len(ids)) >= min_area
    out = np.full(mask.size, -1, np.int32)
    out[flat] = np.where(keep[inv], first[inv], -1)
    return out.reshape(mask.shape)


def segment_from_hema(hema_n: np.ndarray, threshold: float, h: int,
                      min_area: int) -> tuple[np.ndarray, np.ndarray]:
    """Normalised hematoxylin -> (mask, labels) with the split."""
    raw = hema_n > hema_n.dtype.type(threshold)
    labels = split(reference.open_by_reconstruction(reference.fill_holes(raw)), h, min_area)
    return labels >= 0, labels


def analyse_tile(rgb: np.ndarray, cfg: dict, dtype=np.float64) -> dict:
    """The whole tile analysis with the split, in ``dtype``."""
    hema_n = reference.normalise(reference.hematoxylin(rgb, dtype))
    mask, labels = segment_from_hema(hema_n, cfg["seg_threshold"], cfg["split_h"],
                                     cfg["split_min_area"])
    crops, boxes = reference.object_rois(labels, hema_n, cfg["nucleus_roi"])
    feats = reference.texture_features(crops, cfg["num_bins"], dtype)
    return {"hema": hema_n, "mask": mask, "labels": labels, "boxes": boxes, "features": feats}
