"""Elastic fleet membership: RingView minimal-remap properties, exact
shares, wire roundtrip, adopt rule, and the TokenBucket pacer."""
from fractions import Fraction

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.storage import RingView, TokenBucket, adopt_newer

V = 64  # virtual-domain size used throughout (any value works)


# ---------------------------------------------------------------------------
# genesis: bit-identical to the legacy static partition
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [1, 2, 3, 4, 7, 16])
def test_genesis_matches_legacy_range_partition(n):
    ring = RingView.genesis(n)
    assert ring.epoch == 0
    assert ring.servers == tuple(range(n))
    for rank in range(V):
        assert ring.owner(rank, V) == (rank * n) // V
        walk = ring.walk(rank, V)
        home = (rank * n) // V
        assert walk == [(home + i) % n for i in range(n)]


def test_genesis_rejects_empty_fleet():
    with pytest.raises(ValueError):
        RingView.genesis(0)


# ---------------------------------------------------------------------------
# join/leave: minimal remap + exact equal shares
# ---------------------------------------------------------------------------
def test_join_moves_only_newcomer_blocks():
    ring = RingView.genesis(3)
    grown = ring.join(7)
    assert grown.epoch == 1
    assert grown.servers == (0, 1, 2, 7)
    moved = 0
    for rank in range(V):
        before, after = ring.owner(rank, V), grown.owner(rank, V)
        if after == 7:
            moved += 1
        else:
            # minimal remap: nothing shuffles between incumbents
            assert after == before
    # equal shares -> the newcomer takes ~1/(m+1) of the blocks
    assert moved == pytest.approx(V // 4, abs=2)


def test_leave_moves_only_departed_blocks():
    ring = RingView.genesis(4)
    shrunk = ring.leave(1)
    assert shrunk.epoch == 1
    assert shrunk.servers == (0, 2, 3)
    for rank in range(V):
        if ring.owner(rank, V) != 1:
            assert shrunk.owner(rank, V) == ring.owner(rank, V)
        else:
            assert shrunk.owner(rank, V) in (0, 2, 3)


def test_shares_stay_exactly_equal_through_churn():
    ring = RingView.genesis(2)
    for sid in (5, 9, 12):
        ring = ring.join(sid)
    ring = ring.leave(0)
    ring = ring.leave(9)
    m = len(ring.servers)
    for sid in ring.servers:
        assert ring.share(sid) == Fraction(1, m)  # exact, not approximate
    assert sum((ring.share(s) for s in ring.servers), Fraction(0)) == 1


def test_join_leave_reject_bad_members():
    ring = RingView.genesis(2)
    with pytest.raises(ValueError):
        ring.join(1)  # already a member
    with pytest.raises(ValueError):
        ring.leave(5)  # not a member
    with pytest.raises(ValueError):
        RingView.genesis(1).leave(0)  # cannot empty the fleet


def test_walk_covers_fleet_in_ring_order_after_churn():
    ring = RingView.genesis(3).join(8).leave(1)
    for rank in range(V):
        walk = ring.walk(rank, V)
        assert walk[0] == ring.owner(rank, V)
        assert sorted(walk) == sorted(ring.servers)


# ---------------------------------------------------------------------------
# wire form + adopt rule
# ---------------------------------------------------------------------------
def test_json_roundtrip_and_checksum_stability():
    ring = RingView.genesis(3).join(5).leave(0)
    clone = RingView.from_json(ring.to_json())
    assert clone == ring
    assert clone.checksum() == ring.checksum()
    assert RingView.genesis(3).checksum() != ring.checksum()


def test_adopt_newer_keeps_highest_epoch():
    old = RingView.genesis(2)
    new = old.join(2)
    assert adopt_newer(old, new) is new
    assert adopt_newer(new, old) is new
    assert adopt_newer(None, old) is old
    assert adopt_newer(old, None) is old
    assert adopt_newer(old, old) is old  # tie keeps the incumbent


# ---------------------------------------------------------------------------
# property tests
# ---------------------------------------------------------------------------
churn = st.lists(
    st.tuples(st.sampled_from(["join", "leave"]), st.integers(0, 30)),
    max_size=8,
)


@given(n=st.integers(1, 12), ops=churn, vbits=st.integers(4, 10))
@settings(max_examples=40, deadline=None)
def test_prop_minimal_remap_and_exact_shares(n, ops, vbits):
    vsize = 1 << vbits
    ring = RingView.genesis(n)
    for op, sid in ops:
        if op == "join" and sid not in ring.servers:
            new = ring.join(sid)
            for rank in range(vsize):
                if new.owner(rank, vsize) != sid:
                    assert new.owner(rank, vsize) == ring.owner(rank, vsize)
        elif op == "leave" and sid in ring.servers and len(ring.servers) > 1:
            new = ring.leave(sid)
            for rank in range(vsize):
                if ring.owner(rank, vsize) != sid:
                    assert new.owner(rank, vsize) == ring.owner(rank, vsize)
        else:
            continue
        ring = new
        m = len(ring.servers)
        assert all(ring.share(s) == Fraction(1, m) for s in ring.servers)
        assert RingView.from_json(ring.to_json()) == ring


# ---------------------------------------------------------------------------
# TokenBucket pacer (deterministic via injected clock/sleep)
# ---------------------------------------------------------------------------
class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


def test_token_bucket_paces_beyond_burst():
    clk = _FakeClock()
    tb = TokenBucket(rate=10.0, burst=5.0, clock=clk, sleep=clk.sleep)
    waited = sum(tb.take() for _ in range(5))
    assert waited == 0.0  # burst absorbs the first 5
    w = tb.take()
    assert w == pytest.approx(0.1)  # then 1 token per 1/rate seconds
    assert sum(tb.take() for _ in range(10)) == pytest.approx(1.0)


def test_token_bucket_refills_while_idle_up_to_burst():
    clk = _FakeClock()
    tb = TokenBucket(rate=100.0, burst=3.0, clock=clk, sleep=clk.sleep)
    for _ in range(3):
        tb.take()
    clk.t += 60.0  # refill far past burst -> clamps at burst
    assert [tb.take() for _ in range(3)] == [0.0, 0.0, 0.0]
    assert tb.take() > 0.0


def test_token_bucket_rejects_bad_rate():
    with pytest.raises(ValueError):
        TokenBucket(rate=0.0)
