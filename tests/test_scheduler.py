"""WRM scheduling: PATS/FCFS/DL policies + both execution engines."""
import threading

import pytest
from hypothesis import given
from hypothesis import strategies as st

from repro.runtime import (
    DeviceKind,
    ReadyQueue,
    SchedulerConfig,
    SimulatedWRM,
    Task,
    TaskCost,
    ThreadedWRM,
    make_devices,
)


def _tasks(speedups, cpu_s=1.0):
    return [Task(f"t{i}", cost=TaskCost(cpu_s=cpu_s, speedup=s)) for i, s in enumerate(speedups)]


@given(st.lists(st.floats(0.5, 50.0), min_size=2, max_size=20))
def test_pats_queue_ordering(speedups):
    """Accelerator always gets max speedup, CPU min (paper Fig. 5)."""
    q = ReadyQueue("PATS")
    ts = _tasks(speedups)
    for t in ts:
        q.push(t)
    gpu_pick = q.peek_for(DeviceKind.ACCEL)
    cpu_pick = q.peek_for(DeviceKind.CPU)
    assert gpu_pick.speedup == max(speedups)
    assert cpu_pick.speedup == min(speedups)


def test_fcfs_queue_ordering():
    q = ReadyQueue("FCFS")
    ts = _tasks([5.0, 1.0, 9.0])
    for t in ts:
        q.push(t)
    assert q.peek_for(DeviceKind.ACCEL) is ts[0]
    assert q.peek_for(DeviceKind.CPU) is ts[0]


def test_dl_rule_paper_inequality():
    """DL picks the reuse task iff S_d >= S_q * (1 - TransferImpact)."""
    cfg = SchedulerConfig(policy="PATS", data_locality=True, transfer_impact=0.3)
    parent = Task("parent", cost=TaskCost(speedup=10.0))
    reuse_ok = Task("reuse_ok", deps=[parent], cost=TaskCost(speedup=8.0))
    best = Task("best", cost=TaskCost(speedup=10.0))
    from repro.runtime.dag import TaskState

    parent.state = TaskState.DONE
    q = ReadyQueue("PATS")
    q.push(reuse_ok)
    q.push(best)
    # S_d=8 >= 10*(1-0.3)=7  -> reuse wins on the accelerator
    assert q.select(DeviceKind.ACCEL, cfg, parent) is reuse_ok

    q2 = ReadyQueue("PATS")
    reuse_bad = Task("reuse_bad", deps=[parent], cost=TaskCost(speedup=5.0))
    parent.children = [reuse_bad]
    best2 = Task("best2", cost=TaskCost(speedup=10.0))
    q2.push(reuse_bad)
    q2.push(best2)
    # S_d=5 < 7 -> the higher-speedup task wins despite no reuse
    assert q2.select(DeviceKind.ACCEL, cfg, parent) is best2


def test_simulated_pats_beats_fcfs_on_heterogeneous_mix():
    def mk():
        return _tasks([1.2, 20.0] * 20)

    devs = make_devices(4, 1)
    fc = SimulatedWRM(devs, SchedulerConfig(policy="FCFS")).run(mk())
    pa = SimulatedWRM(devs, SchedulerConfig(policy="PATS")).run(mk())
    assert pa.makespan < fc.makespan


def test_simulated_respects_dependencies():
    a = Task("a", cost=TaskCost(cpu_s=1.0))
    b = Task("b", deps=[a], cost=TaskCost(cpu_s=1.0))
    c = Task("c", deps=[b], cost=TaskCost(cpu_s=1.0))
    res = SimulatedWRM(make_devices(4, 0)).run([c, b, a])
    order = {name: (s, e) for s, e, name, _ in res.task_log}
    assert order["a"][1] <= order["b"][0] and order["b"][1] <= order["c"][0]
    assert res.makespan == pytest.approx(3.0)


def test_simulated_prefetch_hides_transfers():
    def mk():
        return [
            Task(f"t{i}", cost=TaskCost(cpu_s=1.0, speedup=10.0, input_bytes=8_000_000_00))
            for i in range(8)
        ]

    devs = make_devices(0, 1)
    base = SimulatedWRM(devs, SchedulerConfig(policy="FCFS", prefetch=False)).run(mk())
    pref = SimulatedWRM(devs, SchedulerConfig(policy="FCFS", prefetch=True)).run(mk())
    assert pref.makespan < base.makespan


def test_simulated_dl_avoids_transfers():
    def mk():
        parents = [Task(f"p{i}", cost=TaskCost(cpu_s=1.0, speedup=10.0,
                                               input_bytes=10**9, output_bytes=10**9))
                   for i in range(6)]
        children = [Task(f"c{i}", deps=[p], cost=TaskCost(cpu_s=1.0, speedup=9.0,
                                                          input_bytes=10**9))
                    for i, p in enumerate(parents)]
        return parents + children

    devs = make_devices(1, 1)
    off = SimulatedWRM(devs, SchedulerConfig(policy="PATS", data_locality=False)).run(mk())
    on = SimulatedWRM(devs, SchedulerConfig(policy="PATS", data_locality=True,
                                            transfer_impact=0.3)).run(mk())
    assert on.makespan <= off.makespan


def test_threaded_wrm_executes_with_deps_and_variants():
    devs = make_devices(2, 1)
    wrm = ThreadedWRM(devs, SchedulerConfig(policy="PATS"))
    log = []
    lock = threading.Lock()

    def work(name):
        with lock:
            log.append(name)

    a = Task("a", cpu_fn=lambda: work("a"), accel_fn=lambda: work("a"))
    b = Task("b", cpu_fn=lambda: work("b"), deps=[a])
    wrm.submit(a)
    wrm.submit(b)
    wrm.wait_all()
    wrm.shutdown()
    assert log.index("a") < log.index("b")
    assert a.ran_on is not None


def test_threaded_wrm_failure_surfaces():
    wrm = ThreadedWRM(make_devices(1, 0))

    def boom():
        raise RuntimeError("kaput")

    wrm.submit(Task("bad", cpu_fn=boom))
    with pytest.raises(RuntimeError):
        wrm.wait_all()
    wrm.shutdown()


def test_measured_speedup_profile():
    import time

    wrm = ThreadedWRM(make_devices(1, 1))
    wrm.submit(Task("op", cpu_fn=lambda: time.sleep(0.02), accel_fn=lambda: time.sleep(0.002)))
    wrm.submit(Task("op", cpu_fn=lambda: time.sleep(0.02), accel_fn=lambda: time.sleep(0.002)))
    wrm.wait_all()
    wrm.shutdown()
    # with one CPU and one ACCEL thread both variants usually run; if both
    # landed on the same device kind, the estimate is undefined -> skip
    s = wrm.measured_speedup("op")
    if s is not None:
        assert s > 1.0
