"""Fault injection & supervision: plan grammar, deterministic firing,
publish quarantine, seeded backoff, watchdog restarts with measured
restart provenance, request-deadline expiry without double-release, and
the graceful-degradation paths (admission fallback, spec auto-disable,
signal-flush handlers)."""
import signal
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs.registry import MetricsRegistry
from repro.resilience import (
    BackoffPolicy,
    FaultInjector,
    Heartbeat,
    InjectedFault,
    NULL_INJECTOR,
    RestartContext,
    SupervisionError,
    install_flush_handlers,
    restore_handlers,
    parse_fault_plan,
    supervise,
    tree_all_finite,
)
from repro.runtime import (
    PolicyStore,
    QuarantinedVersionError,
    TrajectoryQueue,
    make_regime,
)
from repro.runtime.admission import AdmissionPolicy
from repro.serve import (
    BlockAllocator,
    ContinuousBatchingScheduler,
    Request,
    RequestState,
    ServeEngine,
)


def _params(v: float):
    return {"w": jnp.full((2,), float(v))}


# --- fault plan grammar -----------------------------------------------------


def test_parse_fault_plan_grammar():
    events = parse_fault_plan(
        "producer_crash:at_step=2;stall:slot=0,ms=200,count=3;"
        "nan_publish:at_publish=3,p=0.5")
    assert [e.kind for e in events] == [
        "producer_crash", "stall", "nan_publish"]
    assert events[0].params == {"at_step": 2}
    assert events[1].count == 3 and events[1].params["ms"] == 200
    assert events[2].p == 0.5
    assert parse_fault_plan("") == [] and parse_fault_plan(None) == []
    # list-of-chunks form (launcher flags pass lists)
    assert len(parse_fault_plan(["stall:ms=1", "stall:ms=2;stall:ms=3"])) == 3


def test_parse_fault_plan_rejects_bad_specs():
    with pytest.raises(ValueError, match="unknown fault kind"):
        parse_fault_plan("meteor_strike:at_step=1")
    with pytest.raises(ValueError, match="unknown option"):
        parse_fault_plan("producer_crash:at_publish=1")
    with pytest.raises(ValueError, match="key=value"):
        parse_fault_plan("stall:ms")


def test_injector_matching_and_exhaustion():
    reg = MetricsRegistry()
    inj = FaultInjector("producer_crash:at_step=2", registry=reg)
    assert inj.active and not NULL_INJECTOR.active
    inj.crash_if("producer", at_step=0)        # no match
    inj.crash_if("publish", at_step=2)         # wrong site
    with pytest.raises(InjectedFault):
        inj.crash_if("producer", at_step=2)
    inj.crash_if("producer", at_step=2)        # count=1: exhausted
    assert inj.fired_counts() == {"producer_crash": 1}
    assert reg.counter_values("fault_injected_total") == {
        "fault_injected_total{kind=producer_crash,site=producer}": 1.0}


def test_injector_missing_context_key_never_wildcards():
    inj = FaultInjector("stall:slot=3,ms=50", sleep=lambda s: None)
    # engine reports at_step but not slot -> must not fire
    assert inj.stall("engine_step", at_step=3) == 0.0
    assert inj.stall("engine_step", slot=3) == 0.05


def test_injector_probabilistic_firing_is_seed_deterministic():
    plan = "queue_stall:ms=1,p=0.5,count=100"

    def fired(seed):
        inj = FaultInjector(plan, seed=seed, sleep=lambda s: None)
        for call in range(40):
            inj.stall("queue_get", at_call=call)
        return inj.fired_counts().get("queue_stall", 0)

    a, b, c = fired(0), fired(0), fired(1)
    assert a == b                      # same seed -> identical replay
    assert 0 < a < 40                  # actually probabilistic
    assert c != a                      # seed moves the draw


def test_injector_poison_nans_first_leaf_only():
    inj = FaultInjector("learner_nan:at_step=7")
    params = {"a": jnp.ones((2,)), "b": jnp.ones((3,))}
    out, poisoned = inj.poison("learner_step", params, at_step=1)
    assert not poisoned and out is params
    out, poisoned = inj.poison("learner_step", params, at_step=7)
    assert poisoned
    assert not tree_all_finite(out)
    assert bool(jnp.all(jnp.isfinite(out["b"])))


# --- publish quarantine -----------------------------------------------------


def test_nan_publish_quarantined_never_served():
    reg = MetricsRegistry()
    inj = FaultInjector("nan_publish:at_publish=2", registry=reg)
    store = PolicyStore(_params(0.0), capacity=4, injector=inj,
                        guard_finite=True, registry=reg)
    assert store.publish(_params(1.0)) == 1
    poisoned_v = store.publish(_params(2.0))   # injector NaNs this one
    assert poisoned_v == 2                      # version still consumed
    assert store.quarantined_versions() == [2]
    assert store.meta(2).meta["quarantined"] is True
    # latest()/resolve_lagged() skip it; get() refuses it
    params, v = store.latest()
    assert v == 1
    np.testing.assert_allclose(np.asarray(params["w"]), 1.0)
    assert store.resolve_lagged(0) == 1
    with pytest.raises(QuarantinedVersionError):
        store.get(2)
    assert 2 not in store.retained_versions()
    assert store.publish(_params(3.0)) == 3     # recovery: next one serves
    assert store.latest()[1] == 3
    assert reg.counter_values("publish_quarantined_total") == {
        "publish_quarantined_total": 1.0}


def test_posthoc_quarantine_guards_reads():
    store = PolicyStore(_params(0.0), capacity=4, guard_finite=True)
    store.publish(_params(1.0))
    store.publish(_params(2.0))
    store.quarantine(2)
    assert store.latest()[1] == 1
    with pytest.raises(QuarantinedVersionError):
        store.get(2)
    with pytest.raises(KeyError):
        store.quarantine(99)                    # never published


def test_guard_finite_catches_organic_nans():
    store = PolicyStore(_params(0.0), capacity=4, guard_finite=True)
    v = store.publish({"w": jnp.array([1.0, jnp.nan])})
    assert store.quarantined_versions() == [v]
    assert store.latest()[1] == 0


# --- backoff + supervision --------------------------------------------------


def test_backoff_schedule_is_seed_deterministic_and_bounded():
    p = BackoffPolicy(base_ms=50, factor=2.0, max_ms=130, jitter=0.25,
                      max_restarts=4, seed=7)
    s1, s2 = p.schedule(), p.schedule()
    assert s1 == s2 and len(s1) == 4
    same = BackoffPolicy(base_ms=50, factor=2.0, max_ms=130, jitter=0.25,
                         max_restarts=4, seed=7)
    assert same.schedule() == s1                # pure function of fields
    other = BackoffPolicy(base_ms=50, factor=2.0, max_ms=130, jitter=0.25,
                          max_restarts=4, seed=8)
    assert other.schedule() != s1
    for i, d in enumerate(s1):
        base = min(130.0, 50.0 * 2.0 ** i) / 1e3
        assert base <= d <= base * 1.25         # jitter only inflates
    assert isinstance(s1[0], float)


def test_supervise_restarts_then_succeeds():
    reg = MetricsRegistry()
    attempts = []

    def run(ctx: RestartContext):
        attempts.append(ctx.attempt)
        if ctx.attempt < 2:
            raise RuntimeError(f"boom {ctx.attempt}")

    policy = BackoffPolicy(base_ms=1, max_ms=2, max_restarts=3, seed=0)
    restarts = supervise(run, policy=policy, name="p0", registry=reg)
    assert restarts == 2 and attempts == [0, 1, 2]
    assert reg.counter_values("watchdog_restart_total") == {
        "watchdog_restart_total{producer=p0}": 2.0}


def test_supervise_budget_exhaustion_raises():
    def run(ctx):
        raise ValueError("always")

    policy = BackoffPolicy(base_ms=1, max_ms=1, max_restarts=2, seed=0)
    with pytest.raises(SupervisionError) as ei:
        supervise(run, policy=policy, name="p1")
    assert ei.value.restarts == 2
    assert isinstance(ei.value.last_error, ValueError)


def test_supervise_clean_exits_do_not_consume_restarts():
    class Done(Exception):
        pass

    def run(ctx):
        raise Done()

    restarts = supervise(
        run, policy=BackoffPolicy(base_ms=1, max_restarts=3),
        clean_exits=(Done,))
    assert restarts == 0


def test_heartbeat_staleness_with_fake_clock():
    now = [0.0]
    hb = Heartbeat(timeout_s=1.0, clock=lambda: now[0])
    assert not hb.stale()
    now[0] = 2.0
    assert hb.stale()
    hb.beat()
    assert not hb.stale() and hb.beats == 1


# --- restart provenance through the threaded regime -------------------------


def test_threaded_regime_restart_provenance_and_lag_spike():
    """A crashed-and-restarted producer's first admitted batch carries
    restart provenance and the outage's lag spike, measured at
    admission (restart_admitted_total) rather than bypassing it."""
    reg = MetricsRegistry()
    inj = FaultInjector("producer_crash:at_step=2", registry=reg)
    store = PolicyStore(_params(0.0), capacity=8)
    queue = TrajectoryQueue(maxsize=1, registry=reg, injector=inj)
    regime = make_regime(
        "threaded", store, queue,
        lambda params: float(params["w"][0]),
        max_items=4, injector=inj,
        supervisor=BackoffPolicy(base_ms=250, jitter=0.0, max_restarts=2,
                                 seed=0))
    regime.start()
    try:
        first = queue.get(learner_version=store.version, timeout=30.0)
        assert first is not None and "restart" not in first.meta
        # The crash fires entering iteration 3 (produced == 2).  Wait for
        # the watchdog to log it, then publish during the 250 ms backoff:
        # the restarted producer's first batch must span the outage.
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if reg.counter_values("watchdog_restart_total"):
                break
            time.sleep(0.005)
        assert reg.counter_values("watchdog_restart_total") == {
            "watchdog_restart_total{producer=threaded}": 1.0}
        second = queue.get(learner_version=store.version, timeout=30.0)
        assert second is not None and "restart" not in second.meta
        for v in (1.0, 2.0, 3.0):
            store.publish(_params(v))
        third = queue.get(learner_version=store.version, timeout=30.0)
        assert third is not None
        assert third.meta["restart"] is True
        assert third.meta["restart_attempt"] == 1
        # Oldest spans back to the pre-crash pin -> the full outage lag.
        assert third.lag_oldest >= 3
        assert third.lag_newest <= third.lag_oldest
        assert reg.counter_values("restart_admitted_total") == {
            "restart_admitted_total": 1.0}
        assert queue.get(learner_version=store.version, timeout=30.0) \
            is not None                       # 4th item: stream completes
    finally:
        regime.stop()
    assert regime.restarts == 1
    assert inj.fired_counts() == {"producer_crash": 1}


def test_threaded_regime_restart_budget_exhaustion_surfaces():
    inj = FaultInjector("producer_crash:at_step=0,count=10")
    store = PolicyStore(_params(0.0), capacity=2)
    queue = TrajectoryQueue()
    regime = make_regime(
        "threaded", store, queue, lambda p: 0.0, max_items=4,
        injector=inj,
        supervisor=BackoffPolicy(base_ms=1, max_ms=2, max_restarts=2,
                                 seed=0))
    regime.start()
    try:
        with pytest.raises(RuntimeError, match="producer crashed"):
            # Budget exhausted -> SupervisionError surfaces on the
            # consumer side instead of a silent hang.
            regime.next_item(store.version, timeout=30.0)
        assert isinstance(regime.error, SupervisionError)
    finally:
        regime.stop()


# --- request deadlines + double-release hardening ---------------------------


def _sched(num_blocks=8, block_size=4, max_batch=2, **kw):
    return ContinuousBatchingScheduler(
        BlockAllocator(num_blocks, block_size),
        max_batch=max_batch, max_blocks_per_request=8, **kw)


def test_scheduler_deadline_expiry_releases_pages():
    now = [0.0]
    reg = MetricsRegistry()
    s = _sched(request_deadline_s=2.0, clock=lambda: now[0], registry=reg)
    r_run = Request(prompt=np.zeros((6,), np.int32), max_new_tokens=4)
    r_wait = Request(prompt=np.zeros((6,), np.int32), max_new_tokens=4)
    r_slow = Request(prompt=np.zeros((6,), np.int32), max_new_tokens=4,
                     deadline_s=9.0)    # per-request override
    for r in (r_run, r_slow, r_wait):   # FIFO: r_run + r_slow get the
        s.submit(r)                     # 2 slots, r_wait stays queued
        r.submit_time = now[0]
    s.schedule()
    assert s.allocator.num_free < s.allocator.num_blocks
    assert s.expire() == []             # within budget
    now[0] = 3.0
    expired = s.expire()
    assert set(expired) == {r_run, r_wait}
    assert r_slow.state is not RequestState.FINISHED   # its budget is 9 s
    assert r_run.finish_reason == "timeout"
    assert s.timeouts == 2
    assert s.timeouts_by_state == {"running": 1, "waiting": 1}
    assert reg.counter_values("request_timeout_total") == {
        "request_timeout_total{state=running}": 1.0,
        "request_timeout_total{state=waiting}": 1.0,
    }
    s.retire(r_slow, "eos")
    assert s.allocator.num_free == s.allocator.num_blocks  # nothing leaked


def test_scheduler_timeout_preemption_race_releases_once():
    """A deadline retirement racing a preemption (or a second retire)
    must release pages exactly once — the regression the FINISHED
    guards exist for."""
    now = [0.0]
    s = _sched(request_deadline_s=1.0, clock=lambda: now[0])
    r = Request(prompt=np.zeros((6,), np.int32), max_new_tokens=4)
    s.submit(r)
    r.submit_time = 0.0
    s.schedule()
    held = s.allocator.num_blocks - s.allocator.num_free
    assert held > 0
    now[0] = 5.0
    assert s.expire() == [r]
    free_after = s.allocator.num_free
    assert free_after == s.allocator.num_blocks
    # the races: preempt-after-timeout and retire-after-retire
    s._preempt(r)
    s.retire(r, "eos")
    assert s.allocator.num_free == free_after      # no double release
    assert r.finish_reason == "timeout"            # first retirement wins
    assert r.state is RequestState.FINISHED
    assert s.expire() == []                        # FINISHED never re-expires


# --- graceful degradation ---------------------------------------------------


class _RaisingAdmission(AdmissionPolicy):
    name = "raising"

    def admit(self, item):
        raise RuntimeError("controller bug")


def test_queue_admission_fallback_on_raising_controller():
    reg = MetricsRegistry()
    q = TrajectoryQueue(admission=_RaisingAdmission(), registry=reg,
                        fallback_max_lag=2)
    for v in (0, 7):
        q.put(f"p{v}", behavior_version=v, learner_version=8)
    with pytest.warns(RuntimeWarning, match="falling back to max_lag:2"):
        item = q.get(learner_version=8, timeout=1.0)
    # fallback admission: lag-8 item dropped, lag-1 item admitted
    assert item is not None and item.behavior_version == 7
    counters = reg.counter_values("admission_fallback_total")
    assert counters == {
        "admission_fallback_total{controller=raising}": 2.0}
    assert q.stats().dropped == 1


def test_spec_autodisable_after_repeated_all_reject():
    eng = ServeEngine.__new__(ServeEngine)    # unit-test the policy alone
    eng.speculate_k = 4
    eng.spec_disable_after = 3
    eng.spec_disabled = False
    eng._all_reject_rounds = 0
    eng.stats = type("S", (), {"spec_autodisables": 0})()
    eng.metrics = MetricsRegistry()
    from repro.obs.tracer import NULL_TRACER
    eng.tracer = NULL_TRACER
    eng._note_spec_round(accepted=0, n_active=2)
    eng._note_spec_round(accepted=3, n_active=2)   # a hit resets the run
    for _ in range(3):
        eng._note_spec_round(accepted=0, n_active=2)
    assert eng.spec_disabled and eng._spec_k_active == 0
    assert eng.stats.spec_autodisables == 1
    eng._note_spec_round(accepted=0, n_active=2)   # latched: counted once
    assert eng.metrics.counter_values("spec_autodisable_total") == {
        "spec_autodisable_total": 1.0}
    eng._note_spec_round(accepted=0, n_active=0)   # idle rounds ignored


def test_install_flush_handlers_one_shot():
    flushed = []
    prev = install_flush_handlers(flushed.append, signals=(signal.SIGTERM,))
    try:
        with pytest.raises(SystemExit) as ei:
            signal.raise_signal(signal.SIGTERM)
        assert ei.value.code == 128 + signal.SIGTERM
        assert flushed == [signal.SIGTERM]
        # one-shot: the previous disposition is already back
        assert signal.getsignal(signal.SIGTERM) is prev[signal.SIGTERM]
    finally:
        restore_handlers(prev)


# --- resilience stats plumbing ----------------------------------------------


def test_collect_resilience_stats_rollup():
    from repro.metrics.runtime_metrics import collect_resilience_stats

    reg = MetricsRegistry()
    inj = FaultInjector("nan_publish:at_publish=1", registry=reg)
    store = PolicyStore(_params(0.0), capacity=2, injector=inj,
                        guard_finite=True, registry=reg)
    store.publish(_params(1.0))
    stats = collect_resilience_stats(reg, store=store, injector=inj)
    assert stats["quarantined_versions"] == [1]
    assert stats["faults_fired"] == {"nan_publish": 1}
    assert stats["counters"][
        "fault_injected_total{kind=nan_publish,site=publish}"] == 1.0
    assert stats["counters"]["publish_quarantined_total"] == 1.0


def test_counter_values_never_invokes_producers():
    reg = MetricsRegistry()
    reg.register_producer(
        "recursive", lambda: {"boom": reg.counter_values()})
    reg.counter("a_total").inc()
    assert reg.counter_values("a_total") == {"a_total": 1.0}
