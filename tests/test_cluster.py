"""Multi-replica serving tier: router parity gates, placement,
backpressure rerouting, replica fault drain + cold restart, health
counter aggregation, and the sim-mesh / expert-parallel sharded load.

Parity gates (the cluster contract — see ``repro.serving.cluster``):

  * tokens: bit-identical to solo ``engine.generate`` for EVERY request,
    any replica count, any placement, shuffled submission order, full
    DyMoE accounting.
  * modeled TTFT/TPOT: bit-identical to solo whenever the request is
    first on its replica (one-request-per-replica workloads — the
    router adds zero deviation); for arbitrary workloads, bit-identical
    to a STANDALONE session serving the same routed subsequence (the
    session-level co-residency accounting, inherited unchanged).
  * a 1-replica cluster is byte-for-byte a plain session.

Sharded tests need >=4 host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4`` — the CI
``cluster`` leg sets it) and skip elsewhere; everything else runs on any
backend.
"""
import random
import threading

import jax
import pytest
from jax.sharding import AxisType

from repro.configs import get_config
from repro.launch.mesh import make_chip_mesh, make_sim_mesh
from repro.models import init_params
from repro.serving import ClusterRouter, ContinuousBatchingScheduler, \
    DyMoEEngine, EngineConfig, FaultInjector, FaultSpec, QueueFull, \
    Request, SamplingParams, ServingError
from repro.serving.cost_model import EdgeProfile

N_DEVICES = len(jax.devices())


@pytest.fixture(scope="module")
def cfg():
    return get_config("qwen2-moe-a2.7b").reduced()


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def engine(cfg, params):
    return DyMoEEngine(cfg, params, EngineConfig(
        profile=EdgeProfile().with_vram(12), decode_chunk=4))


def req(i, n_prompt=20, max_new=6, **kw):
    kw.setdefault("request_id", f"req-{i}")
    return Request(prompt_tokens=list(range(1 + i, n_prompt + 1 + i)),
                   max_new_tokens=max_new, **kw)


def sampled_req(i, **kw):
    return req(i, sampling=SamplingParams(temperature=0.7, top_k=8,
                                          seed=100 + i), **kw)


# ------------------------------------------------------------ parity gates


@pytest.mark.parametrize("n_replicas", [1, 2, 4])
@pytest.mark.parametrize("shuffle_seed", [None, 7])
def test_token_parity_vs_solo_any_replica_count(engine, n_replicas,
                                                shuffle_seed):
    """Every request's tokens — greedy and sampled — are bit-identical
    to a solo run, for any replica count and shuffled submission order,
    under full DyMoE accounting and multi-slot co-residency."""
    reqs = {i: (sampled_req(i) if i % 3 == 2 else req(i))
            for i in range(8)}
    solo = {i: engine.generate(r).tokens for i, r in reqs.items()}
    order = list(reqs)
    if shuffle_seed is not None:
        random.Random(shuffle_seed).shuffle(order)
    with ClusterRouter.replicate(engine, n_replicas, num_slots=2,
                                 slots_len=64) as router:
        handles = {i: router.submit(reqs[i]) for i in order}
        results = {i: h.result() for i, h in handles.items()}
    assert {i: r.tokens for i, r in results.items()} == solo
    assert all(r.ttft_s > 0 and r.tpot_s > 0 for r in results.values())


@pytest.mark.parametrize("n_replicas", [1, 2, 4])
@pytest.mark.parametrize("shuffle_seed", [None, 3])
def test_modeled_parity_vs_solo_first_on_replica(engine, n_replicas,
                                                 shuffle_seed):
    """With one request per replica, modeled TTFT AND TPOT are
    bit-identical to the solo engine whatever the replica count or
    placement order: the router itself adds zero modeled deviation."""
    reqs = {i: req(i, max_new=5 + i) for i in range(n_replicas)}
    solo = {i: engine.generate(r) for i, r in reqs.items()}
    order = list(reqs)
    if shuffle_seed is not None:
        random.Random(shuffle_seed).shuffle(order)
    with ClusterRouter.replicate(engine, n_replicas,
                                 num_slots=1, slots_len=64) as router:
        handles = {i: router.submit(reqs[i]) for i in order}
        results = {i: h.result() for i, h in handles.items()}
    for i in reqs:
        assert results[i].tokens == solo[i].tokens
        assert results[i].ttft_s == solo[i].ttft_s, i
        assert results[i].tpot_s == solo[i].tpot_s, i


def test_single_replica_cluster_is_a_plain_session(engine):
    """N=1 routes everything to the one session in submission order —
    results (tokens AND modeled numbers) are byte-for-byte what the bare
    scheduler produces for the same sequence, co-residency included."""
    reqs = [req(i, max_new=4 + (i % 3)) for i in range(5)]

    base = ContinuousBatchingScheduler(engine, num_slots=2)
    base._ensure_started(slots_len=64)
    want = [h.result() for h in [base.submit(r) for r in reqs]]
    base.close()

    with ClusterRouter.replicate(engine, 1, num_slots=2,
                                 slots_len=64) as router:
        got = [h.result() for h in [router.submit(r) for r in reqs]]
    for g, w in zip(got, want):
        assert (g.tokens, g.ttft_s, g.tpot_s) == (w.tokens, w.ttft_s,
                                                  w.tpot_s)


def test_routed_subsequence_matches_standalone_session(engine):
    """Placement is deterministic, and each replica's routed subsequence
    reproduces a standalone session serving exactly those requests —
    modeled numbers included, full accounting. This is the cluster's
    strong modeled-parity gate: the router never perturbs any session's
    view of its own traffic."""
    reqs = [req(i, max_new=4 + (i % 4)) for i in range(8)]
    with ClusterRouter.replicate(engine, 2, num_slots=2,
                                 slots_len=64) as router:
        handles = [router.submit(r) for r in reqs]
        results = [h.result() for h in handles]
        placements = [h.replica for h in handles]
    assert set(placements) == {0, 1}    # both replicas took traffic
    for ridx in range(2):
        sub = [i for i, p in enumerate(placements) if p == ridx]
        ref = ContinuousBatchingScheduler(engine, num_slots=2)
        ref._ensure_started(slots_len=64)
        want = [h.result() for h in [ref.submit(reqs[i]) for i in sub]]
        ref.close()
        for i, w in zip(sub, want):
            got = results[i]
            assert (got.tokens, got.ttft_s, got.tpot_s) == \
                (w.tokens, w.ttft_s, w.tpot_s), (ridx, i)


def test_threaded_drivers_token_parity(engine):
    """One driver thread per replica (the throughput mode): same token
    parity, every handle resolves, health counters add up."""
    reqs = [req(i) for i in range(8)]
    solo = [engine.generate(r).tokens for r in reqs]
    router = ClusterRouter.replicate(engine, 2, num_slots=2,
                                     slots_len=64, threaded=True)
    try:
        handles = [router.submit(r) for r in reqs]
        results = [h.result() for h in handles]
        health = router.health()
    finally:
        router.close()
    assert [r.tokens for r in results] == solo
    assert health.submitted == 8 and health.completed == 8


def test_threaded_concurrent_submitters(engine):
    """Many submitter threads against the threaded router: every handle
    resolves with solo-identical tokens (the placement lock + session
    locks keep the whole path safe under contention)."""
    solo = {i: engine.generate(req(i)).tokens for i in range(12)}
    router = ClusterRouter.replicate(engine, 3, num_slots=2,
                                     slots_len=64, threaded=True)
    out, errs = {}, []

    def client(i):
        try:
            out[i] = router.submit(req(i)).result().tokens
        except Exception as e:  # noqa: BLE001 — surfaced in the assert
            errs.append((i, e))

    try:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        router.close()
    assert not errs
    assert out == solo


# ------------------------------------------------- placement + backpressure


def test_least_loaded_placement_round_robins_an_idle_pool(engine):
    with ClusterRouter.replicate(engine, 3, num_slots=1,
                                 slots_len=64) as router:
        handles = [router.submit(req(i)) for i in range(6)]
        assert [h.replica for h in handles] == [0, 1, 2, 0, 1, 2]
        for h in handles:
            h.result()


def test_queue_full_reroutes_before_surfacing(engine):
    """A replica at its queue bound is skipped (rerouted to the next
    candidate), and the typed QueueFull only surfaces when EVERY replica
    rejected — with no handle created, exactly the single-session
    backpressure contract."""
    with ClusterRouter.replicate(engine, 2, num_slots=1, slots_len=64,
                                 max_queue=1,
                                 placement="round_robin") as router:
        # fill replica 0's bounded queue out-of-band so the pool is
        # asymmetric: round-robin rotation still points the next submit
        # at replica 0
        direct = router.replicas[0].submit(req(0))
        rerouted = router.submit(req(1))
        assert rerouted.replica == 1            # skipped the full replica
        assert router.health().reroutes == 1
        # now both queues are full: the typed error surfaces, handle-free
        n_before = len(router._handles)
        with pytest.raises(QueueFull):
            router.submit(req(99))
        assert len(router._handles) == n_before
        health = router.health()
        got = rerouted.result()
    assert health.merged.queue_rejections >= 3  # 1 rerouted + 2 surfaced
    assert got.tokens == engine.generate(req(1)).tokens
    assert direct.result(drive=False).tokens == \
        engine.generate(req(0)).tokens


def test_stream_and_cancel_are_sticky(engine):
    """stream()/cancel() on a cluster handle reach the owning replica:
    streamed chunks concatenate to the final tokens; a cancelled request
    resolves partial on its own replica while others are untouched."""
    with ClusterRouter.replicate(engine, 2, num_slots=1,
                                 slots_len=64) as router:
        long = router.submit(req(0, max_new=24))
        short = router.submit(req(1, max_new=4))
        assert (long.replica, short.replica) == (0, 1)
        streamed = []
        for ev in short.stream():
            streamed.extend(ev.tokens)
        assert streamed == short.result().tokens
        for _ in range(2):
            router.step()
        long.cancel()
        r = long.result()
    assert r.cancelled and 0 < len(r.tokens) < 24
    assert short.result().tokens == engine.generate(req(1, max_new=4)).tokens


# ------------------------------------------------------- health aggregation


def test_session_health_counts_submitted_and_completed(engine):
    """The scheduler satellite: monotonic lifetime counters on a bare
    session, covering both resolution paths (result and typed error)."""
    s = ContinuousBatchingScheduler(engine, num_slots=2)
    s._ensure_started(slots_len=64)
    h0 = s.health()
    assert (h0.submitted, h0.completed) == (0, 0)
    handles = [s.submit(req(i)) for i in range(3)]
    assert s.health().submitted == 3
    assert s.health().completed == 0
    for h in handles:
        h.result()
    assert s.health().completed == 3
    extra = s.submit(req(9))
    s.close()                       # typed-error path counts too
    assert extra.error is not None
    h1 = s.health()
    assert (h1.submitted, h1.completed) == (4, 4)


def test_cluster_health_merges_counters(engine):
    with ClusterRouter.replicate(engine, 2, num_slots=1,
                                 slots_len=64) as router:
        handles = [router.submit(req(i)) for i in range(4)]
        for h in handles:
            h.result()
        health = router.health()
    assert health.status == "ok"
    assert len(health.replicas) == 2
    assert health.submitted == 4 and health.completed == 4
    assert [s.submitted for s in health.replicas] == [2, 2]
    assert health.merged.submitted == sum(
        s.submitted for s in health.replicas)
    closed = router.health()
    assert closed.status == "closed"


# ------------------------------------------------ replica fault + restart


def test_replica_fault_drains_and_cold_restarts(cfg, params):
    """One replica's replay stream faults mid-run: its session degrades,
    the router quarantines + drains it through the existing recovery
    path and cold-restarts a fresh session; traffic continues throughout
    and the replica rejoins the pool. Requests untouched by the fault
    keep solo-identical tokens."""
    faulty = FaultInjector([FaultSpec(site="replay.chunk", at=1)])
    engine = DyMoEEngine(cfg, params, EngineConfig(
        profile=EdgeProfile().with_vram(12), decode_chunk=4))
    solo = {i: engine.generate(req(i)).tokens for i in range(10)}
    router = ClusterRouter.replicate(
        engine, 2, num_slots=1, slots_len=64,
        faults=[None, faulty])
    try:
        first = [router.submit(req(i)) for i in range(6)]
        results1 = {}
        for h in first:
            try:
                results1[int(h.request_id[4:])] = h.result()
            except ServingError:
                pass
        assert all(h.done for h in first)          # every handle resolved
        assert router.health().restarts >= 1       # cold restart happened
        # the pool kept serving through the fault: every request that
        # resolved with a result kept solo-identical tokens (the inline
        # replay fallback and the restart never touch token streams)
        for i, r in results1.items():
            assert r.tokens == solo[i], i
        # ...and the restarted replica rejoins the pool for new traffic
        second = [router.submit(req(6 + i)) for i in range(4)]
        placements = {h.replica for h in second}
        results = [h.result() for h in second]
        health = router.health()
    finally:
        router.close()
    assert 1 in placements                         # rejoined the pool
    assert [r.tokens for r in results] == [solo[6 + i] for i in range(4)]
    assert health.status == "ok"                   # healthy after restart
    # replica health is lifetime-monotonic ACROSS the cold restart: the
    # retired session's counters (including the fault that killed it)
    # stay in the merged snapshot
    assert health.merged.replay_faults >= 1
    assert health.submitted == 10 and health.completed == 10


def test_threaded_replica_fault_recovers(cfg, params):
    """Same fault under driver threads: the owning driver performs the
    drain + restart; every handle still resolves."""
    faulty = FaultInjector([FaultSpec(site="replay.chunk", at=1)])
    engine = DyMoEEngine(cfg, params, EngineConfig(
        profile=EdgeProfile().with_vram(12), decode_chunk=4))
    router = ClusterRouter.replicate(
        engine, 2, num_slots=1, slots_len=64,
        faults=[None, faulty], threaded=True)
    try:
        handles = [router.submit(req(i)) for i in range(8)]
        done = []
        for h in handles:
            try:
                done.append(h.result())
            except ServingError:
                done.append(None)
        assert all(h.done for h in handles)
        assert any(r is not None for r in done)
    finally:
        router.close()


@pytest.mark.timeout(300)
def test_threaded_replica_device_error_takes_it_out_of_service(
        cfg, params, engine):
    """A real (non-injected) device error on one replica's decode
    dispatch: its driver takes the replica out of service instead of
    retrying the failing program forever. Every handle resolves, the
    ones on the failed replica with a typed error caused by the device
    error; the healthy replica keeps solo-identical tokens and takes the
    traffic submitted afterwards."""
    boom = RuntimeError("INTERNAL: device program failed")
    broken = DyMoEEngine(cfg, params, EngineConfig(
        profile=EdgeProfile().with_vram(12), decode_chunk=4),
        qparams=engine.qparams)

    def fail(*args, **kwargs):
        raise boom

    broken._decode_batched = fail
    solo = {i: engine.generate(req(i)).tokens for i in range(8)}
    router = ClusterRouter([engine, broken], num_slots=1, slots_len=64,
                           threaded=True)
    try:
        first = [router.submit(req(i)) for i in range(6)]
        for h in first:
            try:
                assert h.result().tokens == solo[int(h.request_id[4:])]
                assert h.replica == 0
            except ServingError as e:
                assert h.replica == 1
                assert e.__cause__ is boom
        assert all(h.done for h in first)
        assert any(h.replica == 1 for h in first)
        health = router.health()
        assert health.status == "degraded"
        assert health.quarantined == (1,)
        assert router.replicas[1].error is boom
        later = [router.submit(req(6 + i)) for i in range(2)]
        assert {h.replica for h in later} == {0}
        assert [h.result().tokens for h in later] == [solo[6], solo[7]]
    finally:
        router.close()


# ------------------------------------------------------- sim mesh + shard


def test_make_sim_mesh_errors_clearly_when_flag_missing():
    """Asking for more devices than visible must raise with the exact
    flag to set — not hand back a degenerate mesh that silently no-ops
    every sharding."""
    want = N_DEVICES + 4
    with pytest.raises(RuntimeError) as e:
        make_sim_mesh(want)
    msg = str(e.value)
    assert f"--xla_force_host_platform_device_count={want}" in msg
    assert "XLA_FLAGS" in msg


def test_make_sim_mesh_shape():
    mesh = make_sim_mesh(N_DEVICES)
    assert mesh.shape == {"data": 1, "model": N_DEVICES}


def test_make_chip_mesh_shape_and_refusal():
    """The real-device expert-parallel mesh: (1, n) over the first n
    devices with Auto axes, and a plain refusal when fewer are visible."""
    mesh = make_chip_mesh(N_DEVICES)
    assert mesh.shape == {"data": 1, "model": N_DEVICES}
    assert list(mesh.devices.flat) == jax.devices()[:N_DEVICES]
    assert set(mesh.axis_types) == {AxisType.Auto}
    with pytest.raises(RuntimeError, match="make_chip_mesh"):
        make_chip_mesh(N_DEVICES + 1)


needs_mesh = pytest.mark.skipif(
    N_DEVICES < 4, reason="needs XLA_FLAGS="
    "--xla_force_host_platform_device_count=4 (CI cluster leg)")


@needs_mesh
def test_expert_parallel_engine_matches_unsharded(cfg, params, engine):
    """The engine loads expert-parallel sharded (packed stores sharded
    over E, KV slots over "model") and generates bit-identical tokens to
    the unsharded engine — partitioning is an execution detail."""
    mesh = make_sim_mesh(4)
    sharded = DyMoEEngine(cfg, params, EngineConfig(
        profile=EdgeProfile().with_vram(12), decode_chunk=4),
        mesh=mesh, expert_parallel=True, qparams=engine.qparams)
    # the routed packed stores really live sharded over E
    leaves = jax.tree_util.tree_flatten_with_path(sharded.qparams)[0]
    specs = [(path, leaf.sharding.spec) for path, leaf in leaves
             if "w_gate" in str(path) and hasattr(leaf, "sharding")]
    assert any("model" in str(spec) for _, spec in specs), specs
    for i in range(3):
        assert sharded.generate(req(i)).tokens == \
            engine.generate(req(i)).tokens


@needs_mesh
def test_sharded_cluster_token_parity(cfg, params, engine):
    """Replicas over a sharded engine: solo-identical tokens through the
    router, and the session's KV slot state is laid out on the mesh
    (replicated on every device: expert parallelism runs attention whole
    on each)."""
    mesh = make_sim_mesh(4)
    sharded = DyMoEEngine(cfg, params, EngineConfig(
        profile=EdgeProfile().with_vram(12), decode_chunk=4),
        mesh=mesh, expert_parallel=True, qparams=engine.qparams)
    solo = [sharded.generate(req(i)).tokens for i in range(6)]
    with ClusterRouter.replicate(sharded, 2, num_slots=2,
                                 slots_len=64) as router:
        kv = jax.tree_util.tree_leaves(
            router.replicas[0].session._caches)
        assert all(x.sharding.device_set == set(mesh.devices.flat)
                   for x in kv if hasattr(x, "sharding"))
        results = [router.submit(req(i)).result() for i in range(6)]
    assert [r.tokens for r in results] == solo
    assert solo == [engine.generate(req(i)).tokens for i in range(6)]
