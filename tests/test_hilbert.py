"""Hilbert / Morton SFC property tests (DHT routing foundation)."""
from hypothesis import given
from hypothesis import strategies as st

from repro.core import (
    hilbert_d2xy,
    hilbert_xy2d,
    morton_decode,
    morton_encode,
    sfc_index,
    sfc_order_for,
)


@given(st.integers(1, 6), st.data())
def test_hilbert_bijective(order, data):
    n = 1 << order
    x = data.draw(st.integers(0, n - 1))
    y = data.draw(st.integers(0, n - 1))
    d = hilbert_xy2d(order, x, y)
    assert 0 <= d < n * n
    assert hilbert_d2xy(order, d) == (x, y)


def test_hilbert_full_coverage_order3():
    order, n = 3, 8
    seen = {hilbert_xy2d(order, x, y) for x in range(n) for y in range(n)}
    assert seen == set(range(n * n))


def test_hilbert_locality_adjacent_d():
    """Consecutive curve positions are 4-neighbors (the locality property
    the paper's DHT exploits for range queries)."""
    order, n = 4, 16
    for d in range(n * n - 1):
        x1, y1 = hilbert_d2xy(order, d)
        x2, y2 = hilbert_d2xy(order, d + 1)
        assert abs(x1 - x2) + abs(y1 - y2) == 1


@given(st.integers(1, 5), st.lists(st.integers(0, 31), min_size=3, max_size=3))
def test_morton_roundtrip(order, coords):
    coords = tuple(c % (1 << order) for c in coords)
    d = morton_encode(order, coords)
    assert morton_decode(order, len(coords), d) == coords


def test_sfc_order_for():
    assert sfc_order_for(1) == 1
    assert sfc_order_for(16) == 4
    assert sfc_order_for(17) == 5


def test_sfc_index_dispatch():
    assert sfc_index(3, (1, 2)) == hilbert_xy2d(3, 1, 2)
    assert sfc_index(3, (1, 2, 3)) == morton_encode(3, (1, 2, 3))
