"""BoundingBox unit + property tests."""
import numpy as np
import pytest
from hypothesis import given
from hypothesis import strategies as st

from repro.core import BoundingBox, union_all


def boxes(rank=2, lo=-50, hi=50):
    def mk(draw):
        los = [draw(st.integers(lo, hi - 1)) for _ in range(rank)]
        his = [draw(st.integers(l, hi)) for l in los]
        return BoundingBox(tuple(los), tuple(his))

    return st.composite(lambda draw: mk(draw))()


@given(boxes(), boxes())
def test_intersect_symmetric_and_contained(a, b):
    i1, i2 = a.intersect(b), b.intersect(a)
    assert i1.shape == i2.shape
    if not i1.is_empty:
        assert a.contains(i1) and b.contains(i1)
        assert a.intersects(b) and b.intersects(a)


@given(boxes(), boxes())
def test_union_contains_both(a, b):
    u = a.union(b)
    assert u.contains(a) and u.contains(b)
    assert union_all([a, b]).shape == u.shape


@given(boxes())
def test_inflate_shrink_roundtrip(a):
    if a.is_empty:
        return
    assert a.inflate(3).shrink(3) == a


def test_tiles_partition_exactly():
    box = BoundingBox((0, 0), (100, 100))
    tiles = list(box.tiles((50, 50)))
    assert len(tiles) == 4
    assert sum(t.volume for t in tiles) == box.volume
    # pairwise disjoint
    for i, t1 in enumerate(tiles):
        for t2 in tiles[i + 1 :]:
            assert not t1.intersects(t2)
    # paper's example: partition 4 of a <0,0;99,99>-ish domain
    assert tiles[-1] == BoundingBox((50, 50), (100, 100))


@given(st.integers(1, 7), st.integers(1, 97))
def test_tiles_cover_irregular(nt, extent):
    box = BoundingBox((0,), (extent,))
    tiles = list(box.tiles((nt,)))
    assert sum(t.volume for t in tiles) == extent


def test_split_weighted_covers():
    box = BoundingBox((0, 0), (100, 20))
    parts = box.split_weighted([1, 2, 7], axis=0)
    assert sum(p.volume for p in parts) == box.volume
    assert parts[0].hi[0] == 10 and parts[1].hi[0] == 30


def test_local_slices_and_ghost_cells():
    outer = BoundingBox((0, 0), (100, 100))
    part = BoundingBox((50, 50), (100, 100))
    roi = part.inflate(2, within=outer)  # ghost cells clipped at the border
    assert roi == BoundingBox((48, 48), (100, 100))
    arr = np.zeros(outer.shape)
    arr[roi.slices()] = 1
    assert arr.sum() == roi.volume
    back = roi.shrink(0)
    assert back == roi


def test_invalid_boxes_raise():
    with pytest.raises(ValueError):
        BoundingBox((0, 0), (1,))
    with pytest.raises(ValueError):
        BoundingBox((5,), (2,))
