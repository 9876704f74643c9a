"""VirtualClock / Scheduler / work-system / metrics tests
(ref test models: src/util/test/TimerTests.cpp, SchedulerTests.cpp,
src/work/test/WorkTests.cpp)."""
import pytest

from stellar_core_tpu.utils import (
    ActionType, ClockMode, MetricsRegistry, Scheduler, VirtualClock,
    VirtualTimer,
)
from stellar_core_tpu.work import (
    BasicWork, BatchWork, State, Work, WorkScheduler, WorkSequence,
    WorkWithCallback,
)


# -- clock ------------------------------------------------------------------


def test_virtual_time_advances_to_deadline():
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    fired = []
    t = VirtualTimer(clock)
    t.expires_from_now(5.0)
    t.async_wait(lambda: fired.append(clock.now()))
    assert clock.now() == 0.0
    clock.crank(block=True)
    assert fired == [5.0]
    assert clock.now() == 5.0


def test_timer_ordering_and_cancel():
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    order = []
    t1, t2, t3 = (VirtualTimer(clock) for _ in range(3))
    t1.expires_from_now(3.0)
    t1.async_wait(lambda: order.append("t1"))
    t2.expires_from_now(1.0)
    t2.async_wait(lambda: order.append("t2"))
    t3.expires_from_now(2.0)
    t3.async_wait(lambda: order.append("t3"), lambda: order.append("c3"))
    t3.cancel()
    while clock.crank(block=True):
        pass
    assert order == ["c3", "t2", "t1"]


def test_crank_until_predicate():
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    state = []

    def arm(delay):
        t = VirtualTimer(clock)
        t.expires_from_now(delay)
        t.async_wait(lambda: state.append(delay))
        return t

    timers = [arm(d) for d in (1, 2, 30)]
    assert clock.crank_until(lambda: len(state) == 2, timeout=10)
    assert clock.now() < 30


def test_timer_callbacks_can_rearm():
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    count = []

    def tick():
        count.append(clock.now())
        if len(count) < 3:
            t = VirtualTimer(clock)
            t.expires_from_now(1.0)
            t.async_wait(tick)

    t = VirtualTimer(clock)
    t.expires_from_now(1.0)
    t.async_wait(tick)
    clock.crank_until(lambda: len(count) == 3, timeout=10)
    assert count == [1.0, 2.0, 3.0]


# -- scheduler --------------------------------------------------------------


def test_scheduler_fairness():
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    sched = Scheduler(clock)
    ran = []
    for i in range(3):
        sched.enqueue("a", lambda i=i: ran.append(("a", i)))
    sched.enqueue("b", lambda: ran.append(("b", 0)))
    while sched.run_one():
        pass
    # queue b (never served) must run before queue a drains fully
    assert ("b", 0) in ran[:2]
    assert len(ran) == 4


def test_scheduler_sheds_droppable_after_window():
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    sched = Scheduler(clock, latency_window=5.0)
    ran = []
    sched.enqueue("q", lambda: ran.append("d"), ActionType.DROPPABLE)
    sched.enqueue("q", lambda: ran.append("n"), ActionType.NORMAL)
    clock.set_current_virtual_time(10.0)
    while sched.run_one():
        pass
    assert ran == ["n"]
    assert sched.stats_dropped == 1


# -- metrics ----------------------------------------------------------------


def test_metrics_registry():
    reg = MetricsRegistry()
    reg.counter("ledger.ledger.count").inc(5)
    t = reg.timer("ledger.ledger.close")
    for v in (0.001, 0.002, 0.003):
        t.update(v)
    snap = reg.snapshot()
    assert snap["ledger.ledger.count"]["count"] == 5
    assert snap["ledger.ledger.close"]["count"] == 3
    assert 0.001 <= snap["ledger.ledger.close"]["p50"] <= 0.003
    with pytest.raises(AssertionError):
        reg.meter("ledger.ledger.count")  # type clash


def test_metrics_registry_reentrant_from_gc_callback():
    """A collection inside a registration runs the gc callbacks on the
    thread that holds the registry lock; one that registers a metric
    there (the vitals gc-pause histogram) must not deadlock."""
    import gc
    import threading

    from stellar_core_tpu.utils.metrics import Gauge

    reg = MetricsRegistry()

    class CollectingGauge(Gauge):
        def __init__(self):
            super().__init__()
            gc.collect()

    def on_gc(phase, info):
        if phase == "stop":
            reg.histogram("test.gc.pause")

    gc.callbacks.append(on_gc)
    try:
        t = threading.Thread(target=reg._get,
                             args=("test.gauge", CollectingGauge),
                             daemon=True)
        t.start()
        t.join(10)
    finally:
        gc.callbacks.remove(on_gc)
    assert not t.is_alive(), "metrics registry deadlocked in gc callback"
    assert "test.gc.pause" in reg._metrics


# -- work system ------------------------------------------------------------


class CountdownWork(BasicWork):
    def __init__(self, name, n, fail_at=None):
        super().__init__(name, max_retries=0)
        self.n = n
        self.fail_at = fail_at

    def on_run(self):
        self.n -= 1
        if self.fail_at is not None and self.n == self.fail_at:
            return State.FAILURE
        return State.SUCCESS if self.n <= 0 else State.RUNNING


def test_work_scheduler_runs_to_success():
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    ws = WorkScheduler(clock)
    w = ws.schedule(CountdownWork("w", 5))
    assert ws.crank_all()
    assert w.state == State.SUCCESS


def test_work_sequence_ordering_and_failure():
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    ws = WorkScheduler(clock)
    ran = []
    seq = WorkSequence("seq", [
        WorkWithCallback("a", lambda: (ran.append("a"), True)[1]),
        WorkWithCallback("b", lambda: (ran.append("b"), False)[1]),
        WorkWithCallback("c", lambda: (ran.append("c"), True)[1]),
    ])
    seq.start()
    while not seq.done:
        seq.crank()
    assert seq.state == State.FAILURE
    assert ran == ["a", "b"]  # c never runs after b fails


def test_retry_then_success():
    class FlakyWork(BasicWork):
        def __init__(self):
            super().__init__("flaky", max_retries=2)
            self.attempts = 0

        def on_run(self):
            self.attempts += 1
            return State.SUCCESS if self.attempts == 3 else State.FAILURE

    w = FlakyWork()
    w.start()
    while not w.done:
        w.crank()
    assert w.state == State.SUCCESS
    assert w.attempts == 3


def test_batch_work_bounded_parallelism():
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    peak = [0]

    works = [CountdownWork(f"w{i}", 3) for i in range(10)]

    class Tracking(BatchWork):
        def on_run(self):
            live = sum(1 for c in self.children if not c.done)
            peak[0] = max(peak[0], live)
            return super().on_run()

    b = Tracking("batch", iter(works), batch_size=3)
    b.start()
    for _ in range(200):
        if b.done:
            break
        b.crank()
    assert b.state == State.SUCCESS
    assert peak[0] <= 3
    assert all(w.state == State.SUCCESS for w in works)


def test_timer_cancel_and_rearm_uses_new_deadline():
    """Regression (review finding): cancel + re-arm must not fire at the
    stale (earlier) deadline."""
    clock = VirtualClock(ClockMode.VIRTUAL_TIME)
    fired = []
    t = VirtualTimer(clock)
    t.expires_from_now(5.0)
    t.async_wait(lambda: fired.append(("old", clock.now())))
    t.cancel()
    t.expires_from_now(100.0)
    t.async_wait(lambda: fired.append(("new", clock.now())))
    while clock.crank(block=True):
        pass
    assert fired == [("new", 100.0)]
