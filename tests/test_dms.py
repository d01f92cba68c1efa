"""Distributed memory storage (DataSpaces analogue) tests."""
import numpy as np
import pytest
from hypothesis import given
from hypothesis import strategies as st

from repro.core import BoundingBox, ElementType, RegionKey
from repro.storage import (
    DistributedMemoryStorage,
    InProcTransport,
    TransportError,
    decode_homes,
)

DOM = BoundingBox((0, 0), (64, 64))


def _key(name="R", ts=0, v=0):
    return RegionKey("t", name, ElementType.FLOAT32, ts, v)


class FaultyTransport(InProcTransport):
    """In-proc transport with switchable dead servers + call counters —
    deterministic fault injection for the write-failover/rollback tests
    (the socket chaos suite covers the same paths on real processes)."""

    def __init__(self, num_servers: int):
        super().__init__(num_servers)
        self.down: set[int] = set()
        self.lookup_calls = 0

    def _check(self, server: int) -> None:
        if server in self.down:
            raise TransportError(f"server {server} is down (injected)")

    def store(self, server, *a):
        self._check(server)
        return super().store(server, *a)

    def fetch(self, server, *a):
        self._check(server)
        return super().fetch(server, *a)

    def fetch_many(self, server, *a):
        self._check(server)
        return super().fetch_many(server, *a)

    def put_meta(self, server, *a):
        self._check(server)
        return super().put_meta(server, *a)

    def put_meta_batch(self, server, *a):
        self._check(server)
        return super().put_meta_batch(server, *a)

    def lookup(self, server, *a):
        self.lookup_calls += 1
        self._check(server)
        return super().lookup(server, *a)

    def keys(self, server):
        self._check(server)
        return super().keys(server)

    def drop(self, server, *a):
        self._check(server)
        return super().drop(server, *a)

    def drop_block(self, server, *a):
        self._check(server)
        return super().drop_block(server, *a)


def test_put_get_identity():
    dms = DistributedMemoryStorage(DOM, (16, 16), 4)
    arr = np.random.default_rng(0).random((64, 64), dtype=np.float32)
    dms.put(_key(), DOM, arr)
    assert np.array_equal(dms.get(_key(), DOM), arr)


@given(
    st.integers(0, 63), st.integers(0, 63), st.data()
)
def test_roi_reads_match_numpy(y0, x0, data):
    y1 = data.draw(st.integers(y0 + 1, 64))
    x1 = data.draw(st.integers(x0 + 1, 64))
    dms = DistributedMemoryStorage(DOM, (16, 16), 3)
    arr = np.arange(64 * 64, dtype=np.float32).reshape(64, 64)
    dms.put(_key(), DOM, arr)
    roi = BoundingBox((y0, x0), (y1, x1))
    assert np.array_equal(dms.get(_key(), roi), arr[roi.slices()])


def test_partial_put_roi_get():
    dms = DistributedMemoryStorage(DOM, (16, 16), 4)
    arr = np.ones((32, 32), np.float32)
    part = BoundingBox((16, 16), (48, 48))
    dms.put(_key(), part, arr)
    got = dms.get(_key(), BoundingBox((20, 20), (40, 40)))
    assert got.shape == (20, 20) and (got == 1).all()


def test_uncovered_roi_raises():
    dms = DistributedMemoryStorage(DOM, (16, 16), 2)
    dms.put(_key(), BoundingBox((0, 0), (16, 16)), np.ones((16, 16), np.float32))
    import pytest

    with pytest.raises(KeyError):
        dms.get(_key(), DOM)


def test_overlapping_writes_last_staged_wins():
    """Paper S3.4: storage keeps the last staged version of overlaps."""
    dms = DistributedMemoryStorage(DOM, (16, 16), 4)
    a = np.zeros((64, 64), np.float32)
    b = np.ones((32, 64), np.float32)
    dms.put(_key(), DOM, a)
    dms.put(_key(), BoundingBox((16, 0), (48, 64)), b)
    got = dms.get(_key(), DOM)
    assert (got[16:48] == 1).all() and (got[:16] == 0).all() and (got[48:] == 0).all()


def test_sfc_balances_servers():
    dms = DistributedMemoryStorage(DOM, (8, 8), 4)
    arr = np.random.default_rng(1).random((64, 64), dtype=np.float32)
    dms.put(_key(), DOM, arr)
    load = dms.server_load()
    assert len(load) == 4
    assert max(load) <= 2 * min(load)  # SFC range partition is balanced
    # at R > 1 the PHYSICAL load includes replica copies, which are not
    # an SFC imbalance — the balance check must use the primary view
    dms2 = DistributedMemoryStorage(DOM, (8, 8), 4, replication=2)
    dms2.put(_key(), DOM, arr)
    by_role = dms2.server_load(by_role=True)
    assert sum(by_role["total"]) == 2 * arr.nbytes
    assert sum(by_role["primary"]) == arr.nbytes
    assert sum(by_role["replica"]) == arr.nbytes
    # the primary (SFC-partition) view matches the unreplicated balance
    assert by_role["primary"] == load
    assert max(by_role["primary"]) <= 2 * min(by_role["primary"])


def test_metadata_propagated_payload_single_home():
    dms = DistributedMemoryStorage(DOM, (32, 32), 4)
    arr = np.ones((32, 32), np.float32)
    dms.put(_key(), BoundingBox((0, 0), (32, 32)), arr)
    stats = dms.transport.stats
    assert stats.puts == 1  # one payload block, one home server
    assert stats.meta_msgs == 3  # metadata broadcast to the other servers
    # every server's directory can answer
    for srv in dms._servers:
        assert srv.lookup(_key())


def test_versioned_keys_coexist_and_query():
    dms = DistributedMemoryStorage(DOM, (16, 16), 2)
    dms.put(_key(ts=0), DOM, np.zeros((64, 64), np.float32))
    dms.put(_key(ts=1), DOM, np.ones((64, 64), np.float32))
    found = dms.query("t", "R")
    assert [k.timestamp for k, _ in found] == [0, 1]
    assert (dms.get(_key(ts=1), DOM) == 1).all()
    dms.delete(_key(ts=0))
    assert len(dms.query("t", "R")) == 1


def test_trailing_channel_dims():
    dms = DistributedMemoryStorage(DOM, (16, 16), 4)
    key = RegionKey("t", "RGB", ElementType.UINT8)
    arr = np.random.default_rng(2).integers(0, 255, (64, 64, 3), dtype=np.uint8)
    dms.put(key, DOM, arr)
    roi = BoundingBox((10, 20), (30, 60))
    assert np.array_equal(dms.get(key, roi), arr[10:30, 20:60])


def test_replication_places_blocks_on_ring_neighbors():
    """replication=2: every block lands on its home AND the next server
    along the SFC virtual-domain ring, doubling resident bytes but
    leaving reads bit-exact."""
    from repro.storage import decode_homes

    dms = DistributedMemoryStorage(DOM, (16, 16), 4, replication=2)
    arr = np.random.default_rng(3).random((64, 64), dtype=np.float32)
    dms.put(_key(), DOM, arr)
    assert np.array_equal(dms.get(_key(), DOM), arr)
    assert sum(dms.server_load()) == 2 * arr.nbytes  # write amplification = R
    directory = dms.transport.lookup(1, _key())
    assert len(directory) == 16
    for bc, (_, h) in directory.items():
        homes = decode_homes(h)
        assert homes == dms.replica_servers(bc)
        assert homes[0] == dms.home_server(bc)
        assert homes[1] == (homes[0] + 1) % 4
        # the payload really is resident on both replicas
        for sid in homes:
            assert dms._servers[sid].fetch(_key(), bc) is not None
    assert dms.stats.failover_fetches == 0  # healthy fleet: primaries serve


def test_replication_validation():
    import pytest

    with pytest.raises(ValueError, match="replication"):
        DistributedMemoryStorage(DOM, (16, 16), 4, replication=0)
    with pytest.raises(ValueError, match="replication"):
        DistributedMemoryStorage(DOM, (16, 16), 4, replication=5)
    # full replication (R == num_servers) is legal: every server holds all
    dms = DistributedMemoryStorage(DOM, (16, 16), 4, replication=4)
    arr = np.ones((64, 64), np.float32)
    dms.put(_key(), DOM, arr)
    assert all(load == arr.nbytes for load in dms.server_load())
    assert np.array_equal(dms.get(_key(), DOM), arr)


def test_put_failover_rehomes_blocks_onto_live_servers():
    """A dead replica must not fail a put at R=2: blocks whose replica
    set touches the dead server re-home onto the next live server along
    the ring, every block still lands on R distinct live servers, and
    reads stay bit-exact."""
    tr = FaultyTransport(4)
    dms = DistributedMemoryStorage(DOM, (16, 16), transport=tr, replication=2)
    arr = np.random.default_rng(20).random((64, 64)).astype(np.float32)
    tr.down.add(2)
    dms.put(_key(), DOM, arr)  # must not raise
    assert dms.stats.put_failovers > 0
    load = dms.server_load()
    assert load[2] == 0  # nothing landed on the dead server
    assert sum(load) == 2 * arr.nbytes  # still R copies of every block
    for bc, (_, h) in tr.lookup(0, _key()).items():
        homes = decode_homes(h)
        assert len(homes) == 2 and 2 not in homes  # actual placement recorded
    np.testing.assert_array_equal(dms.get(_key(), DOM), arr)
    # even with the other replica of the re-homed blocks gone, reads
    # fail over to the re-homed copies: the write failover preserved R
    tr.down.add(1)
    np.testing.assert_array_equal(dms.get(_key(), DOM), arr)


def test_put_degrades_below_r_but_raises_only_at_zero_live():
    """With fewer live servers than R the put degrades (fewer copies,
    recorded faithfully); only zero writable replicas raises."""
    tr = FaultyTransport(2)
    dms = DistributedMemoryStorage(DOM, (32, 32), transport=tr, replication=2)
    arr = np.ones((64, 64), np.float32)
    tr.down.add(1)
    dms.put(_key(), DOM, arr)  # degraded: single copy per block
    for _, (_, h) in tr.lookup(0, _key()).items():
        assert decode_homes(h) == (0,)
    tr.down.add(0)
    with pytest.raises(TransportError, match="ANY server"):
        dms.put(_key("gone"), DOM, arr)


def test_failed_put_rolls_back_partial_blocks():
    """Satellite regression: a put that fails mid-way must not leak the
    blocks it already stored — server_load() returns to pre-put bytes
    and no directory mentions the key."""
    tr = FaultyTransport(4)
    dms = DistributedMemoryStorage(DOM, (16, 16), transport=tr)  # R=1: strict
    arr = np.random.default_rng(21).random((64, 64)).astype(np.float32)
    dms.put(_key("keep"), DOM, arr)
    pre = dms.server_load()
    assert sum(pre) == arr.nbytes
    tr.down.add(3)
    # R=1 with a dead server: blocks re-home, but the strictly-consistent
    # metadata broadcast fails -> the whole put fails and rolls back
    with pytest.raises(TransportError):
        dms.put(_key("fail"), DOM, arr)
    assert dms.stats.put_rollbacks > 0
    assert dms.server_load() == pre  # no orphaned payload bytes
    tr.down.clear()
    for sid in range(4):
        assert _key("fail") not in tr.keys(sid)  # no phantom directory entries
    np.testing.assert_array_equal(dms.get(_key("keep"), DOM), arr)  # untouched


def test_failed_reput_never_destroys_previous_data():
    """Rolling back a failed RE-put must not drop the key's previous
    incarnation: whatever mix of old/new blocks the failure left, every
    block stays readable (torn beats destroyed)."""
    old = np.ones((64, 64), np.float32)
    new = np.full((64, 64), 2.0, np.float32)
    # broadcast fails AFTER some directories acked (dead server mid-list)
    # and BEFORE any ack (dead server first): both paths must preserve
    for dead_sid in (3, 0):
        tr = FaultyTransport(4)
        dms = DistributedMemoryStorage(DOM, (16, 16), transport=tr)  # R=1 strict
        dms.put(_key(), DOM, old)
        tr.down.add(dead_sid)
        with pytest.raises(TransportError):
            dms.put(_key(), DOM, new)
        tr.down.clear()
        got = dms.get(_key(), DOM)  # must not raise: no entry may dangle
        assert np.isin(got, (1.0, 2.0)).all()
    # a fresh key alongside it still rolls back fully
    tr = FaultyTransport(4)
    dms = DistributedMemoryStorage(DOM, (16, 16), transport=tr)
    dms.put(_key(), DOM, old)
    pre = dms.server_load()
    tr.down.add(3)
    with pytest.raises(TransportError):
        dms.put(_key("fresh"), DOM, new)
    assert dms.server_load() == pre


def test_put_survives_stale_all_dead_liveness_cache():
    """A liveness cache that (stale-)marks EVERY server dead must not
    fail the put without trying: the fallback stores for real, the
    mirror of the read path's cache-dead fallback."""

    class AllDeadCache(FaultyTransport):
        def alive(self, server):
            return False  # every endpoint inside its backoff window

    tr = AllDeadCache(4)
    dms = DistributedMemoryStorage(DOM, (16, 16), transport=tr, replication=2)
    arr = np.random.default_rng(25).random((64, 64)).astype(np.float32)
    dms.put(_key(), DOM, arr)  # servers are actually fine: must succeed
    assert sum(dms.server_load()) == 2 * arr.nbytes
    np.testing.assert_array_equal(dms.get(_key(), DOM), arr)


def test_lookup_cost_r1_single_miss_lookup():
    """Satellite regression: at replication=1 every directory is strictly
    consistent, so a miss must cost exactly ONE lookup (the PR-3 cost);
    at R>1 the empty answer needs a second directory to confirm."""
    tr = FaultyTransport(4)
    dms = DistributedMemoryStorage(DOM, (16, 16), transport=tr)
    with pytest.raises(KeyError):
        dms.get(_key("absent"), DOM)
    assert tr.lookup_calls == 1

    tr2 = FaultyTransport(4)
    dms2 = DistributedMemoryStorage(DOM, (16, 16), transport=tr2, replication=2)
    with pytest.raises(KeyError):
        dms2.get(_key("absent"), DOM)
    assert tr2.lookup_calls == 2
    # hits pay one lookup at either factor
    arr = np.ones((64, 64), np.float32)
    for d, t in ((dms, tr), (dms2, tr2)):
        d.put(_key(), DOM, arr)
        t.lookup_calls = 0
        d.get(_key(), DOM)
        assert t.lookup_calls == 1


def test_read_balance_spreads_hot_key_over_replicas():
    """Healthy-fleet reads rotate over live replicas (balanced_fetches),
    never counting as fault failover; read_balance=False restores strict
    primary preference."""
    dms = DistributedMemoryStorage(DOM, (16, 16), 4, replication=2)
    arr = np.random.default_rng(22).random((64, 64)).astype(np.float32)
    dms.put(_key(), DOM, arr)
    hot = BoundingBox((0, 0), (16, 16))  # single block: one replica pair
    for _ in range(20):
        np.testing.assert_array_equal(dms.get(_key(), hot), arr[:16, :16])
    assert dms.stats.failover_fetches == 0
    assert 6 <= dms.stats.balanced_fetches <= 14  # ~half served by the replica

    pinned = DistributedMemoryStorage(
        DOM, (16, 16), 4, replication=2, read_balance=False
    )
    pinned.put(_key(), DOM, arr)
    for _ in range(20):
        pinned.get(_key(), hot)
    assert pinned.stats.balanced_fetches == 0
    assert pinned.stats.failover_fetches == 0


def test_repair_refills_server_that_rejoined_empty():
    """Anti-entropy: wipe one server (crash + rejoin-empty analogue) and
    repair() restores every block to R confirmed copies and re-fills the
    wiped directory; a second sweep is a no-op."""
    tr = FaultyTransport(4)
    dms = DistributedMemoryStorage(DOM, (16, 16), transport=tr, replication=2)
    arr = np.random.default_rng(23).random((64, 64)).astype(np.float32)
    dms.put(_key(), DOM, arr)
    victim = tr.servers[2]
    was_on_2 = sum(
        1
        for _, (_, h) in tr.lookup(0, _key()).items()
        if 2 in decode_homes(h)
    )
    assert was_on_2 > 0
    victim._blocks.clear()
    victim._meta.clear()
    report = dms.repair()
    assert report["repaired"] == was_on_2
    assert report["lost"] == 0
    assert dms.stats.repaired_blocks == was_on_2
    assert len(tr.lookup(2, _key())) == 16  # directory re-filled too
    assert sum(dms.server_load()) == 2 * arr.nbytes
    np.testing.assert_array_equal(dms.get(_key(), DOM), arr)
    again = dms.repair()
    assert again["repaired"] == 0 and again["meta_fixes"] == 0  # converged
    # a holder that fed the repair can now die: the blocks it shared
    # with the wiped server serve from the re-stored copies — without
    # the sweep they would have had a single live replica left
    tr.down.add(1)
    np.testing.assert_array_equal(dms.get(_key(), DOM), arr)


def test_repair_rehomes_around_dead_servers_and_reports_lost():
    """repair() places new copies only on live servers; a block whose
    every holder is gone is counted lost, not silently dropped."""
    tr = FaultyTransport(4)
    dms = DistributedMemoryStorage(DOM, (16, 16), transport=tr, replication=2)
    arr = np.ones((64, 64), np.float32)
    dms.put(_key(), DOM, arr)
    # wipe server 1's payload+meta AND kill server 2: repair must re-home
    # server 1's blocks onto live servers other than 2
    tr.servers[1]._blocks.clear()
    tr.servers[1]._meta.clear()
    tr.down.add(2)
    report = dms.repair()
    assert report["unreachable"] == 1
    assert report["repaired"] > 0
    for _, (_, h) in tr.lookup(0, _key()).items():
        homes = decode_homes(h)
        live_copies = [s for s in homes if s not in tr.down]
        assert len(live_copies) >= 2 or 2 in homes
    # lost blocks: wipe both replicas of everything, repair reports them
    tr2 = FaultyTransport(4)
    dms2 = DistributedMemoryStorage(DOM, (64, 64), transport=tr2, replication=2)
    dms2.put(_key(), DOM, arr)  # single block on 2 servers
    for s in tr2.servers:
        s._blocks.clear()
    homes = decode_homes(next(iter(tr2.lookup(0, _key()).values()))[1])
    for sid in homes:
        tr2.servers[sid]._meta.clear()
    report = dms2.repair()
    assert report["lost"] == 1
    assert dms2.stats.lost_blocks == 1


def test_auto_repair_background_thread():
    """start_auto_repair heals a wiped server without an explicit call;
    close() stops the thread."""
    import time

    tr = FaultyTransport(4)
    dms = DistributedMemoryStorage(DOM, (16, 16), transport=tr, replication=2)
    dms.start_auto_repair(0.05)
    with pytest.raises(RuntimeError, match="already running"):
        dms.start_auto_repair(0.05)
    arr = np.random.default_rng(24).random((64, 64)).astype(np.float32)
    dms.put(_key(), DOM, arr)
    tr.servers[1]._blocks.clear()
    tr.servers[1]._meta.clear()
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if dms.stats.repaired_blocks > 0 and len(tr.lookup(1, _key())) == 16:
            break
        time.sleep(0.02)
    assert dms.stats.repaired_blocks > 0
    assert sum(dms.server_load()) == 2 * arr.nbytes
    dms.close()
    assert dms._repair_thread is None
    with pytest.raises(ValueError, match="interval"):
        dms.start_auto_repair(0.0)


def test_throughput_accounting():
    dms = DistributedMemoryStorage(DOM, (16, 16), 4)
    arr = np.ones((64, 64), np.float32)
    dms.put(_key(), DOM, arr)
    dms.get(_key(), DOM)
    assert dms.transport.stats.bytes_put == arr.nbytes
    assert dms.transport.stats.bytes_get == arr.nbytes
    assert dms.aggregate_throughput() > 0


def test_transport_stats_snapshot_is_atomic_under_hammer():
    """as_dict() must snapshot all counters under the stats lock: with
    writers always bumping (puts, bytes_put) together via add(), every
    snapshot a reader takes must show bytes_put == 64 * puts — skew
    means a torn cross-counter read (mirrors the GatewayStats hammer;
    TransportStats was the remaining PR-7 follow-up)."""
    import threading

    from repro.storage.dms import TransportStats

    stats = TransportStats()
    rounds, writers = 2000, 4
    stop = threading.Event()
    skews = []

    def writer():
        for _ in range(rounds):
            stats.add(puts=1, bytes_put=64, bytes_put_raw=64)

    def reader():
        while not stop.is_set():
            snap = stats.as_dict()
            if snap["bytes_put"] != 64 * snap["puts"]:
                skews.append(snap)

    readers = [threading.Thread(target=reader) for _ in range(2)]
    threads = [threading.Thread(target=writer) for _ in range(writers)]
    for t in readers + threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    stop.set()
    for t in readers:
        t.join(timeout=10)
    assert not skews, skews[:3]
    final = stats.as_dict()
    assert final["puts"] == rounds * writers
    assert final["bytes_put"] == final["bytes_put_raw"] == 64 * rounds * writers
    stats.reset()
    assert all(v == 0 for v in stats.as_dict().values())
    with pytest.raises(AttributeError):
        stats.add(not_a_counter=1)  # typo'd counter names must not pass silently
