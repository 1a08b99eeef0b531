"""Chaos suite: the fault-tolerant serving contract.

Under EVERY injected fault schedule (see
``ContinuousBatchingScheduler`` *Failure semantics*):

  * every submitted handle RESOLVES — a ``GenerationResult`` or a typed
    :class:`ServingError` — and nothing hangs (the per-test timeout cap
    turns a hung handle into a failure);
  * the session keeps serving requests the fault didn't touch, and their
    TOKENS stay bit-identical to the fault-free run;
  * benign schedules (a slow replay, a dispatch retry that succeeds on a
    shorter chunk) keep the MODELED numbers (TTFT/TPOT) bit-identical
    too — every recovery rung is a transformation the scheduler is
    invariant to;
  * a replay fault degrades the session (inline replay over a fresh
    orchestrator) but never kills it: ``health()`` says so and new
    requests still serve.
"""
import dataclasses
import time
import warnings

import jax
import numpy as np
import pytest

from repro.core.cache import MixedPrecisionLRUCache
from repro.models import init_params
from repro.models.config import DyMoEPolicy, ModelConfig
from repro.serving import DyMoEEngine, EDFPolicy, EngineConfig, Request
from repro.serving.cost_model import EdgeProfile
from repro.serving.faults import AdmissionError, DeadlineExceeded, \
    DispatchError, FaultInjector, FaultSpec, InjectedFault, NO_FAULTS, \
    QueueFull, ReplayError, ServingError, SessionClosed, \
    submit_with_retry

pytestmark = pytest.mark.timeout(300)


@pytest.fixture(scope="module")
def moe_setup():
    cfg = ModelConfig(
        name="t", arch_type="moe", num_layers=2, d_model=64, vocab_size=128,
        num_heads=2, num_kv_heads=1, head_dim=32, num_experts=4,
        num_experts_per_tok=2, moe_d_ff=64, capacity_factor=4.0,
        dtype="float32", remat="none",
        dymoe=DyMoEPolicy(low_bits=2, retention=0.75))
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params, faults=None, **kw):
    kw.setdefault("decode_chunk", 4)
    return DyMoEEngine(cfg, params, EngineConfig(
        profile=EdgeProfile().with_vram(16), **kw), faults=faults)


def _script():
    """The request script every schedule replays: deterministic ragged
    prompts, more requests than slots (so admission waves + mid-run
    admission both happen)."""
    rng = np.random.default_rng(3)
    return [Request(prompt_tokens=rng.integers(1, 128, n).tolist(),
                    max_new_tokens=m, request_id=f"req-{i}")
            for i, (n, m) in enumerate(
                [(8, 6), (5, 4), (9, 8), (6, 3), (7, 5), (4, 7)])]


def _serve_script(eng, num_slots=2):
    """Submit the script, drive to completion, close; return handles."""
    session = eng.serve(num_slots=num_slots, slots_len=64)
    handles = [session.submit(r) for r in _script()]
    session.drain(cancel_queued=False)
    session.close()
    assert all(h.done for h in handles)
    return session, handles


@pytest.fixture(scope="module")
def baseline(moe_setup):
    """Fault-free run of the script: per-request tokens + modeled numbers
    the chaos runs are compared against."""
    cfg, params = moe_setup
    _, handles = _serve_script(_engine(cfg, params))
    assert all(h.error is None for h in handles)
    return {h.request_id: h.result(drive=False) for h in handles}


# ------------------------------------------------------------- injector


def test_fault_injector_schedule_and_counters():
    fi = FaultInjector([FaultSpec(site="s", at=1, times=2, note="boom")])
    fi.fire("s")                     # visit 0: clean
    with pytest.raises(InjectedFault, match="boom"):
        fi.fire("s")                 # visit 1
    with pytest.raises(InjectedFault):
        fi.fire("s")                 # visit 2
    fi.fire("s")                     # visit 3: window passed
    assert fi.visits("s") == 4
    assert [v for (_, v, _) in fi.fired] == [1, 2]
    fi.fire("other")                 # per-site counters
    assert fi.visits("other") == 1


def test_fault_injector_delay_and_inflate():
    fi = FaultInjector([
        FaultSpec(site="d", kind="delay", delay_s=0.05, times=1),
        FaultSpec(site="i", kind="inflate", factor=3.0, at=1, times=1)])
    t0 = time.perf_counter()
    fi.fire("d")
    assert time.perf_counter() - t0 >= 0.04
    assert fi.inflate("i", 10) == 10       # visit 0: identity
    assert fi.inflate("i", 10) == 30       # visit 1: scaled
    assert fi.inflate("i", 10) == 10


def test_fault_injector_probability_is_seeded():
    def fired(seed):
        fi = FaultInjector([FaultSpec(site="p", times=50,
                                      probability=0.5)], seed=seed)
        out = []
        for v in range(50):
            try:
                fi.fire("p")
                out.append(False)
            except InjectedFault:
                out.append(True)
        return out

    a, b = fired(7), fired(7)
    assert a == b                    # reproducible schedule
    assert any(a) and not all(a)     # actually probabilistic


def test_fault_spec_validation():
    with pytest.raises(ValueError, match="kind"):
        FaultSpec(site="s", kind="explode")
    with pytest.raises(ValueError, match="window"):
        FaultSpec(site="s", times=0)
    with pytest.raises(ValueError, match="probability"):
        FaultSpec(site="s", probability=1.5)


def test_no_faults_is_noop():
    NO_FAULTS.fire("anything")
    assert NO_FAULTS.inflate("anything", 5) == 5
    assert NO_FAULTS.visits("anything") == 0  # no specs: no counting


# ----------------------------------------------------- fault-free parity


def test_empty_injector_keeps_run_bit_identical(moe_setup, baseline):
    """Threading an (empty) injector through the hot path must not change
    tokens OR modeled numbers — the no-op fast path really is a no-op."""
    cfg, params = moe_setup
    _, handles = _serve_script(_engine(cfg, params,
                                       faults=FaultInjector([])))
    for h in handles:
        assert h.error is None
        r, b = h.result(drive=False), baseline[h.request_id]
        assert r.tokens == b.tokens
        assert r.ttft_s == b.ttft_s
        assert r.tpot_s == b.tpot_s


# ------------------------------------------------------- replay faults


def test_replay_fault_degrades_but_keeps_serving(moe_setup, baseline):
    """A crashed replay job fails ONLY the in-flight requests (typed
    ReplayError), the session falls back to inline replay over a fresh
    orchestrator, keeps serving the queue, and says so in health()."""
    cfg, params = moe_setup
    eng = _engine(cfg, params, faults=FaultInjector(
        [FaultSpec(site="replay.chunk", at=1)]))
    session = eng.serve(num_slots=2, slots_len=64)
    handles = [session.submit(r) for r in _script()]
    session.drain(cancel_queued=False)
    health = session.health()

    assert all(h.done for h in handles)
    failed = [h for h in handles if h.error is not None]
    served = [h for h in handles if h.error is None]
    assert failed and served        # fault took some, not all
    for h in failed:
        assert isinstance(h.error, ReplayError)
        with pytest.raises(ReplayError):
            h.result(drive=False)
    for h in served:                # untouched requests: token parity
        assert h.result(drive=False).tokens == baseline[h.request_id].tokens
    assert health.status == "degraded"
    assert health.replay_faults >= 1
    assert health.last_fault is not None

    # the degraded session still serves NEW submissions end to end
    late = session.submit(Request(prompt_tokens=[5, 6, 7],
                                  max_new_tokens=4, request_id="late"))
    session.drain(cancel_queued=False)
    res = late.result(drive=False)
    assert len(res.tokens) == 4
    assert np.isfinite(res.ttft_s) and np.isfinite(res.tpot_s)
    session.close()
    assert session.health().status == "closed"


def test_replay_prefill_fault_fails_wave_only(moe_setup, baseline):
    """A prefill-replay crash resolves that wave's requests with
    ReplayError; everything admitted later serves fine (degraded)."""
    cfg, params = moe_setup
    eng = _engine(cfg, params, faults=FaultInjector(
        [FaultSpec(site="replay.prefill", at=0)]))
    _, handles = _serve_script(eng)
    failed = [h for h in handles if h.error is not None]
    served = [h for h in handles if h.error is None]
    assert failed and served
    assert all(isinstance(h.error, ReplayError) for h in failed)
    for h in served:
        assert h.result(drive=False).tokens == baseline[h.request_id].tokens


def test_slow_replay_keeps_everything_bit_identical(moe_setup, baseline):
    """kind="delay" (slow host replay) exercises the replay-queue
    backpressure without touching ANY number: full bit-parity."""
    cfg, params = moe_setup
    eng = _engine(cfg, params, faults=FaultInjector(
        [FaultSpec(site="replay.chunk", kind="delay", delay_s=0.05,
                   times=3)]))
    session, handles = _serve_script(eng)
    assert session.health().replay_faults == 0
    for h in handles:
        assert h.error is None
        r, b = h.result(drive=False), baseline[h.request_id]
        assert r.tokens == b.tokens
        assert r.ttft_s == b.ttft_s
        assert r.tpot_s == b.tpot_s


# ------------------------------------------------------ dispatch faults


def test_dispatch_retry_is_bit_identical(moe_setup, baseline):
    """One failed dispatch attempt -> retried at half the chunk length.
    Chunking invariance makes the WHOLE run bit-identical — tokens and
    modeled TTFT/TPOT — and nobody fails."""
    cfg, params = moe_setup
    eng = _engine(cfg, params, faults=FaultInjector(
        [FaultSpec(site="device.dispatch", at=1, times=1)]))
    session = eng.serve(num_slots=2, slots_len=64)
    handles = [session.submit(r) for r in _script()]
    session.drain(cancel_queued=False)
    health = session.health()           # BEFORE close: live status
    session.close()
    assert health.dispatch_retries >= 1
    assert health.dispatch_failures == 0
    assert health.status == "ok"        # dispatch retries don't degrade
    for h in handles:
        assert h.error is None
        r, b = h.result(drive=False), baseline[h.request_id]
        assert r.tokens == b.tokens
        assert r.ttft_s == b.ttft_s
        assert r.tpot_s == b.tpot_s


def test_dispatch_exhaustion_fails_only_affected_slots(moe_setup,
                                                       baseline):
    """A dispatch that keeps failing walks the whole ladder (halve chunk,
    defer rows) and finally fails SOME slot(s) with DispatchError; every
    other request still serves with bit-identical tokens."""
    cfg, params = moe_setup
    eng = _engine(cfg, params, faults=FaultInjector(
        [FaultSpec(site="device.dispatch", at=1, times=4)]))
    session, handles = _serve_script(eng)
    health = session.health()
    failed = [h for h in handles if h.error is not None]
    served = [h for h in handles if h.error is None]
    assert failed and served
    assert all(isinstance(h.error, DispatchError) for h in failed)
    assert health.dispatch_failures == len(failed)
    for h in served:
        assert h.result(drive=False).tokens == baseline[h.request_id].tokens


# ----------------------------------------------------- admission faults


def test_admission_ladder_splits_then_fails_typed(moe_setup, baseline):
    """A failing admission wave is requeued and halved; with the fault
    persisting long enough, single candidates fail with AdmissionError —
    and the queue behind them still gets served."""
    cfg, params = moe_setup
    eng = _engine(cfg, params, faults=FaultInjector(
        [FaultSpec(site="admit.alloc", at=0, times=2)]))
    session, handles = _serve_script(eng)
    health = session.health()
    assert health.admission_retries + health.admission_failures >= 1
    failed = [h for h in handles if h.error is not None]
    assert all(isinstance(h.error, AdmissionError) for h in failed)
    for h in handles:
        if h.error is None:
            assert (h.result(drive=False).tokens
                    == baseline[h.request_id].tokens)


@pytest.mark.parametrize("site,err_cls", [("_prefill", AdmissionError),
                                           ("_decode_batched", DispatchError)])
def test_real_device_error_fails_touched_requests_and_raises(
        moe_setup, site, err_cls):
    """A real (non-injected) compile or device error is not retried: the
    requests it touched resolve with the typed error caused by it, the
    session turns degraded, step() raises, and close() resolves the
    rest — nothing is left pending."""
    cfg, params = moe_setup
    eng = _engine(cfg, params)
    boom = RuntimeError("RESOURCE_EXHAUSTED: out of device memory")

    def fail(*args, **kwargs):
        raise boom

    setattr(eng, site, fail)
    session = eng.serve(num_slots=2, slots_len=64)
    handles = [session.submit(r) for r in _script()]
    with pytest.raises(RuntimeError) as e:
        while session.step():
            pass
    assert e.value is boom
    health = session.health()
    touched = [h for h in handles if h.done]
    assert len(touched) == 2                     # one wave / both slots
    for h in touched:
        assert isinstance(h.error, err_cls)
        assert h.error.__cause__ is boom
    assert health.status == "degraded"
    assert health.admission_retries == health.dispatch_retries == 0
    assert health.admission_failures + health.dispatch_failures == 2
    session.close()
    assert all(h.done for h in handles)
    for h in handles:
        if h not in touched:
            assert isinstance(h.error, SessionClosed)


# --------------------------------------------------------- cache faults


def test_cache_corrupt_blob_becomes_typed_replay_error(moe_setup,
                                                       baseline):
    """A corrupted expert-blob transfer raises inside the orchestrator
    replay -> typed ReplayError on affected handles, degraded session,
    everyone else token-identical."""
    cfg, params = moe_setup
    eng = _engine(cfg, params, faults=FaultInjector(
        [FaultSpec(site="cache.blob.corrupt", at=5)]))
    _, handles = _serve_script(eng)
    failed = [h for h in handles if h.error is not None]
    assert failed                         # the corrupt load fired mid-run
    assert all(isinstance(h.error, ReplayError) for h in failed)
    for h in handles:
        if h.error is None:
            assert (h.result(drive=False).tokens
                    == baseline[h.request_id].tokens)


def test_cache_oversize_blob_bypasses_gracefully(moe_setup, baseline):
    """An inflated (oversized) blob drives the cache's bypass ladder:
    NO request fails, tokens are untouched, modeled numbers stay finite,
    and the bypass shows up in stats — not as an outage."""
    cfg, params = moe_setup
    eng = _engine(cfg, params, faults=FaultInjector(
        [FaultSpec(site="cache.blob.oversize", kind="inflate",
                   factor=1e9, at=2, times=4)]))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # the rate-limited bypass warning
        _, handles = _serve_script(eng)
    for h in handles:
        assert h.error is None
        r = h.result(drive=False)
        assert r.tokens == baseline[h.request_id].tokens  # device math
        assert np.isfinite(r.ttft_s) and np.isfinite(r.tpot_s)
        assert r.cache_stats["bypass_loads"] >= 1


def test_oversize_bypass_warns_once_per_key():
    cache = MixedPrecisionLRUCache(100)
    with pytest.warns(UserWarning, match="bypass"):
        cache.get((0, 0), "high", nbytes=500)
    with warnings.catch_warnings():
        warnings.simplefilter("error")    # same key again: SILENT
        cache.get((0, 0), "high", nbytes=500)
    with pytest.warns(UserWarning, match="bypass"):
        cache.get((0, 1), "high", nbytes=500)   # new key: one warning
    assert cache.stats.bypass_loads == 3


# ------------------------------------------- backpressure and deadlines


def test_bounded_queue_rejects_with_queue_full(moe_setup):
    cfg, params = moe_setup
    eng = _engine(cfg, params)
    session = eng.serve(num_slots=1, slots_len=64, max_queue=2)
    reqs = _script()
    a = session.submit(reqs[0])
    b = session.submit(reqs[1])           # queue now at the bound of 2
    with pytest.raises(QueueFull, match="admission queue is full"):
        session.submit(reqs[2])           # bound hit: NO handle created
    assert session.health().queue_rejections == 1
    assert session.health().queue_depth == 2
    # submit_with_retry(drive=True) steps the session until room frees
    c = submit_with_retry(session, reqs[2], attempts=50, drive=True)
    session.drain(cancel_queued=False)
    session.close()
    for h in (a, b, c):
        assert h.done and h.error is None


def test_queue_full_without_retry_raises_through(moe_setup):
    cfg, params = moe_setup
    eng = _engine(cfg, params)
    session = eng.serve(num_slots=1, slots_len=64, max_queue=1)
    h = session.submit(_script()[0])
    with pytest.raises(QueueFull):
        submit_with_retry(session, _script()[1], attempts=2,
                          backoff_s=0.001)   # sleep-only: queue never moves
    session.drain(cancel_queued=False)
    session.close()
    assert h.error is None


def test_expired_queued_requests_are_shed(moe_setup):
    """deadline_s=0 (and ttft_deadline_s=0) queued requests resolve with
    DeadlineExceeded before ever being admitted; others are untouched."""
    cfg, params = moe_setup
    eng = _engine(cfg, params)
    session = eng.serve(num_slots=1, slots_len=64)
    ok = session.submit(Request(prompt_tokens=[1, 2, 3], max_new_tokens=3,
                                request_id="ok"))
    doomed = session.submit(Request(prompt_tokens=[4, 5], max_new_tokens=3,
                                    deadline_s=0.0, request_id="doomed"))
    doomed2 = session.submit(Request(prompt_tokens=[6], max_new_tokens=3,
                                     ttft_deadline_s=0.0,
                                     request_id="doomed2"))
    session.drain(cancel_queued=False)
    session.close()
    assert ok.error is None and len(ok.result(drive=False).tokens) == 3
    for h in (doomed, doomed2):
        assert isinstance(h.error, DeadlineExceeded)
        with pytest.raises(DeadlineExceeded, match="shed"):
            h.result(drive=False)
    assert session.health().deadline_shed == 2


def test_expired_in_flight_request_is_evicted_partial(moe_setup):
    """An in-flight request past deadline_s is evicted at the next chunk
    boundary like a cancel: PARTIAL result, deadline_expired=True."""
    cfg, params = moe_setup
    eng = _engine(cfg, params)
    session = eng.serve(num_slots=1, slots_len=200)
    h = session.submit(Request(prompt_tokens=[1, 2, 3, 4],
                               max_new_tokens=150, deadline_s=0.3))
    session.step()                        # admit + first chunk
    assert session.health().in_flight == 1
    time.sleep(0.35)                      # let the wall clock expire it
    while session.step():
        pass
    session.flush()
    session.close()
    res = h.result(drive=False)
    assert res.cancelled and res.deadline_expired
    assert 0 < len(res.tokens) < 150      # partial, not complete
    assert session.health().deadline_evictions == 1


# ----------------------------------------------------------------- close


def test_close_resolves_every_outstanding_handle(moe_setup):
    """close() with queued + in-flight requests: every handle resolves
    with SessionClosed (none blocks), completed ones keep their result,
    and submit afterwards raises SessionClosed."""
    cfg, params = moe_setup
    eng = _engine(cfg, params)
    session = eng.serve(num_slots=1, slots_len=64)
    reqs = _script()
    done = session.submit(dataclasses.replace(reqs[0], max_new_tokens=1))
    session.step()                        # finishes `done` at its prefill
    inflight = session.submit(            # too long to finish inside the
        dataclasses.replace(reqs[1], max_new_tokens=40))  # admission step
    session.step()                        # admits `inflight`
    queued = session.submit(reqs[2])      # never admitted
    session.close()
    for h in (done, inflight, queued):
        assert h.done
    assert done.error is None             # completed work is kept
    assert len(done.result(drive=False).tokens) == 1
    for h in (inflight, queued):
        assert isinstance(h.error, SessionClosed)
        with pytest.raises(SessionClosed):
            h.result(drive=False)
        list(h.stream(drive=False))       # ENDS (already-pushed events
        #                                   drain) instead of hanging
    assert list(queued.stream(drive=False)) == []  # nothing ever ran
    with pytest.raises(SessionClosed):
        session.submit(reqs[3])
    assert session.health().status == "closed"


# --------------------------------------------- SLO policy fault sites


def test_preempt_fault_aborts_that_preemption_only(moe_setup, baseline):
    """An InjectedFault at ``preempt.evict`` ABORTS the preemption — the
    victim keeps its slot, the urgent request waits its turn, nobody
    fails, and the fault is visible in health(). With the fault window
    covering every attempt, the run completes preemption-free."""
    cfg, params = moe_setup
    eng = _engine(cfg, params, faults=FaultInjector(
        [FaultSpec(site="preempt.evict", at=0, times=100)]))
    session = eng.serve(num_slots=2, slots_len=96,
                        policy=EDFPolicy(ladder=None))
    bulk_reqs = [Request(prompt_tokens=list(range(1 + i, 9 + i)),
                         max_new_tokens=16, request_id=f"bulk{i}")
                 for i in range(2)]
    bulk = [session.submit(r) for r in bulk_reqs]
    for _ in range(16):                       # long bulk: slots stay busy
        if session.health().in_flight == 2:
            break
        session.step()
    assert session.health().in_flight == 2
    urgent = session.submit(Request(prompt_tokens=[40, 41, 42],
                                    max_new_tokens=2, request_id="urgent",
                                    priority=5))
    session.drain(cancel_queued=False)
    health = session.health()
    session.close()
    assert health.preemptions == 0            # every attempt was aborted
    assert health.last_fault is not None
    for h in bulk + [urgent]:
        assert h.error is None
        assert h.result(drive=False).preempted == 0


def test_degrade_fault_skips_rung_transition(moe_setup, baseline):
    """An InjectedFault at ``degrade.shift`` SKIPS that rung transition —
    the session stays at its current rung, keeps serving, and tokens stay
    bit-identical (degradation never touches them anyway)."""
    cfg, params = moe_setup
    eng = _engine(cfg, params, faults=FaultInjector(
        [FaultSpec(site="degrade.shift", at=0, times=1000)]))
    session = eng.serve(num_slots=2, slots_len=64, policy="edf")
    handles = [session.submit(r) for r in _script()]   # depth engages...
    session.drain(cancel_queued=False)
    health = session.health()
    session.close()
    assert health.rung_transitions == 0       # ...but every shift faulted
    assert health.pressure_rung == 0
    assert health.last_fault is not None
    for h in handles:
        assert h.error is None
        assert h.result(drive=False).tokens == baseline[h.request_id].tokens


# ------------------------------------------------- chaos schedule sweep


SCHEDULES = {
    "replay-crash": [FaultSpec(site="replay.chunk", at=1)],
    "replay-slow": [FaultSpec(site="replay.chunk", kind="delay",
                              delay_s=0.02, times=4)],
    "dispatch-burst": [FaultSpec(site="device.dispatch", at=1, times=4)],
    "admit-crash": [FaultSpec(site="admit.alloc", at=0, times=3)],
    "cache-corrupt": [FaultSpec(site="cache.blob.corrupt", at=5,
                                times=2)],
    "combo": [FaultSpec(site="replay.chunk", at=2),
              FaultSpec(site="device.dispatch", at=1, times=2),
              FaultSpec(site="admit.alloc", at=1)],
    # SLO-policy sites: these schedules run under an EDF session with a
    # mid-run priority burst (see POLICY_SCHEDULES below) so the
    # preemption and ladder paths are actually visited
    "preempt-evict": [FaultSpec(site="preempt.evict", at=0, times=1)],
    "degrade-shift": [FaultSpec(site="degrade.shift", at=0, times=1)],
    "slo-combo": [FaultSpec(site="preempt.evict", at=1),
                  FaultSpec(site="degrade.shift", at=0, times=2),
                  FaultSpec(site="replay.chunk", at=3)],
}

# schedules whose fault sites only exist on the policy paths: served
# through EDF with a mid-run priority burst (tokens stay bit-identical
# to the FIFO baseline — policy, preemption and rungs never change them)
POLICY_SCHEDULES = {"preempt-evict", "degrade-shift", "slo-combo"}


@pytest.mark.parametrize("name", sorted(SCHEDULES))
def test_chaos_schedule_every_handle_resolves(moe_setup, baseline, name):
    """THE invariant, per schedule: every handle resolves (result or
    typed ServingError), the session survives to serve a late request,
    and every successful request's tokens are bit-identical to the
    fault-free run."""
    cfg, params = moe_setup
    eng = _engine(cfg, params, faults=FaultInjector(SCHEDULES[name],
                                                    seed=0))
    if name in POLICY_SCHEDULES:
        session = eng.serve(num_slots=2, slots_len=64, policy="edf")
        reqs = _script()
        handles = [session.submit(r) for r in reqs[:4]]
        for _ in range(2):                      # slots busy, queue deep
            session.step()
        handles += [session.submit(dataclasses.replace(r, priority=3))
                    for r in reqs[4:]]          # urgent burst: preempts
    else:
        session = eng.serve(num_slots=2, slots_len=64)
        handles = [session.submit(r) for r in _script()]
    session.drain(cancel_queued=False)

    # a late submission AFTER the faults: the session must still serve
    late = session.submit(Request(prompt_tokens=[9, 8, 7],
                                  max_new_tokens=3, request_id="late"))
    session.drain(cancel_queued=False)
    session.close()

    for h in handles + [late]:
        assert h.done, f"{name}: {h.request_id} never resolved"
        if h.error is not None:
            assert isinstance(h.error, ServingError), \
                f"{name}: {h.request_id} got untyped {h.error!r}"
        elif h is not late:
            assert (h.result(drive=False).tokens
                    == baseline[h.request_id].tokens), \
                f"{name}: {h.request_id} tokens diverged"
    assert late.error is None            # post-fault service really works
    assert len(late.result(drive=False).tokens) == 3
