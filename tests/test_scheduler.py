"""Continuous-batching scheduler: admission/eviction at chunk boundaries,
per-row done-masks, ragged right-aligned prefill, and the acceptance
contract — every request served through the slot batch yields greedy
tokens bit-identical to a solo ``generate`` of that request, with finite
per-request modeled TTFT/TPOT. The pipelined loop (host telemetry replay
overlapped with device decode) must be bit-identical to the serial
``pipeline=False`` reference in tokens AND modeled numbers, and a batched
admission wave must be bitwise-equal to the same admissions run solo."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import decode_many, decode_many_batched, init_params, \
    prefill, quantize_model
from repro.models.config import DyMoEPolicy, ModelConfig
from repro.serving import ContinuousBatchingScheduler, DyMoEEngine, \
    EngineConfig, Request
from repro.serving.cost_model import EdgeProfile


@pytest.fixture(scope="module")
def moe_setup():
    cfg = ModelConfig(
        name="t", arch_type="moe", num_layers=3, d_model=64, vocab_size=512,
        num_heads=4, num_kv_heads=2, head_dim=16, num_experts=8,
        num_experts_per_tok=2, moe_d_ff=64, capacity_factor=4.0,
        dtype="float32", remat="none",
        dymoe=DyMoEPolicy(low_bits=2, retention=0.75))
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# f32 rows of a batched program against the same rows run solo. XLA's CPU
# backend picks the reduction order of an f32 dot per operand shape, so a
# row batched with others differs from its solo run in the last bits
# (observed: at most 1.3e-6 on logits of magnitude ~2, i.e. a few ulp
# carried through 3 layers). 1e-5 leaves an order of magnitude of room
# and still fails any real mixing of rows, which moves logits by O(0.1).
# Tokens, Critical sets and cache layouts are still compared exactly.
_ROW_TOL = dict(rtol=1e-5, atol=1e-5)


def _ragged_requests(rng, specs):
    return [Request(prompt_tokens=rng.integers(1, 512, n).tolist(),
                    max_new_tokens=m, eos_token=e)
            for n, m, e in specs]


# ------------------------------------------------------------ acceptance


def test_ragged_stream_matches_solo_generate_bitwise(moe_setup):
    """THE acceptance criterion: a ragged request stream (mixed prompt
    lengths, mixed max_new_tokens / eos_token) served through the slot
    batch produces, per request, exactly the tokens a solo generate()
    yields — and real finite modeled TTFT/TPOT instead of NaN."""
    cfg, params = moe_setup
    eng = DyMoEEngine(cfg, params, EngineConfig(
        profile=EdgeProfile().with_vram(16), decode_chunk=4))
    rng = np.random.default_rng(5)
    reqs = _ragged_requests(rng, [
        (12, 9, None), (7, 5, None), (9, 14, None),
        (12, 3, None), (7, 7, None), (9, 2, None), (5, 11, None)])
    # give one request a real mid-stream eos (taken from its solo run)
    solo2 = eng.generate(reqs[2])
    eos = solo2.tokens[4]
    if eos not in solo2.tokens[:4]:   # only if it truly stops mid-stream
        reqs[2] = dataclasses.replace(reqs[2], eos_token=eos)
    out = eng.generate_batch(reqs, num_slots=3)
    assert len(out) == len(reqs)
    for req, res in zip(reqs, out):
        solo = eng.generate(req)
        assert res.tokens == solo.tokens
        assert np.isfinite(res.ttft_s) and res.ttft_s > 0
        assert np.isfinite(res.tpot_s) and res.tpot_s > 0
        assert res.wall_s > 0


def test_scheduler_respects_slot_budget_and_order(moe_setup):
    """More requests than slots: everything is served, results come back
    in submission order, and shrinking the slot count never changes any
    request's tokens (slots are independent B=1 programs)."""
    cfg, params = moe_setup
    eng = DyMoEEngine(cfg, params, EngineConfig(decode_chunk=4))
    rng = np.random.default_rng(7)
    reqs = _ragged_requests(rng, [(8, 6, None), (11, 4, None), (6, 8, None),
                                  (9, 5, None), (8, 3, None)])
    by_slots = {k: eng.generate_batch(reqs, num_slots=k) for k in (1, 2, 5)}
    for k, out in by_slots.items():
        assert [r.tokens for r in out] == \
            [r.tokens for r in by_slots[1]], k


def test_scheduler_admits_into_freed_slots(moe_setup):
    """Eviction frees capacity mid-run: with 2 slots and a straggler, the
    short requests must rotate through the freed slot (the run finishes
    in far fewer chunks than serial execution would need)."""
    cfg, params = moe_setup
    eng = DyMoEEngine(cfg, params, EngineConfig(decode_chunk=2))
    rng = np.random.default_rng(9)
    reqs = _ragged_requests(rng, [(8, 16, None)] + [(6, 3, None)] * 4)
    sched = ContinuousBatchingScheduler(eng, num_slots=2)
    out = sched.run(reqs)
    assert [len(r.tokens) for r in out] == [16, 3, 3, 3, 3]
    for req, res in zip(reqs, out):
        assert res.tokens == eng.generate(req).tokens
    # per-request accounting came through the shared orchestrator
    assert all(len(r.decode_timings) == len(r.tokens) - 1 for r in out)


def test_one_token_and_empty_edge_cases(moe_setup):
    cfg, params = moe_setup
    eng = DyMoEEngine(cfg, params, EngineConfig())
    assert eng.generate_batch([]) == []
    reqs = [Request(prompt_tokens=list(range(1, 9)), max_new_tokens=1),
            Request(prompt_tokens=list(range(1, 7)), max_new_tokens=5)]
    out = eng.generate_batch(reqs, num_slots=1)
    assert len(out[0].tokens) == 1 and out[0].tpot_s == 0.0
    assert out[0].tokens == eng.generate(reqs[0]).tokens
    assert len(out[1].tokens) == 5


def test_request_validation():
    with pytest.raises(ValueError, match="non-empty"):
        Request(prompt_tokens=[])
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request(prompt_tokens=[1], max_new_tokens=0)


# ------------------------------------------------------ pipelined serving


def _modeled_fingerprint(res):
    return (res.tokens, res.ttft_s, res.tpot_s, res.cache_stats,
            None if res.decode_timings is None
            else [t.total_s for t in res.decode_timings])


def test_pipelined_matches_serial_bitwise(moe_setup):
    """The pipeline parity contract: overlapping the host telemetry replay
    with device decode changes NO observable number — tokens, modeled
    TTFT/TPOT, per-step timings and cache stats are bit-identical to the
    ``pipeline=False`` serial loop on a ragged workload with mixed
    lengths, limits, an eos stop and a one-token request. Run twice to
    catch thread-scheduling nondeterminism."""
    cfg, params = moe_setup
    eng = DyMoEEngine(cfg, params, EngineConfig(
        profile=EdgeProfile().with_vram(16), decode_chunk=4))
    rng = np.random.default_rng(21)
    reqs = _ragged_requests(rng, [
        (12, 9, None), (7, 1, None), (9, 14, None),
        (12, 3, None), (7, 7, None), (5, 11, None)])
    # give one request a real mid-stream eos (taken from its solo run)
    solo2 = eng.generate(reqs[2])
    eos = solo2.tokens[4]
    if eos not in solo2.tokens[:4]:
        reqs[2] = dataclasses.replace(reqs[2], eos_token=eos)
    serial = eng.generate_batch(reqs, num_slots=3, pipeline=False)
    for attempt in range(2):
        piped = eng.generate_batch(reqs, num_slots=3, pipeline=True)
        for i, (a, b) in enumerate(zip(piped, serial)):
            assert _modeled_fingerprint(a) == _modeled_fingerprint(b), \
                (attempt, i)


def test_pipeline_dispatches_next_chunk_before_replay(moe_setup):
    """The overlap property, tested STRUCTURALLY (no timing): while chunk
    N's replay job is deliberately held hostage on the worker, the main
    loop must still dispatch chunk N+1 — i.e. the next device chunk never
    waits for the previous chunk's telemetry fetch/replay. A serial loop
    would deadlock here (the replay runs inline before the next
    dispatch), so the 30s timeout failing the event is the regression
    signal."""
    import threading

    cfg, params = moe_setup
    eng = DyMoEEngine(cfg, params, EngineConfig(decode_chunk=2))
    req = Request(prompt_tokens=list(range(1, 9)), max_new_tokens=9)
    eng.generate_batch([req], num_slots=1)   # warm: no compiles below

    dispatched = threading.Event()
    n_decode = [0]
    real_decode = eng._decode_batched
    real_replay = eng._replay

    def counting_decode(*a, **k):
        n_decode[0] += 1
        if n_decode[0] >= 2:
            dispatched.set()     # chunk N+1 left the host while...
        return real_decode(*a, **k)

    def gated_replay(*a, **k):
        if k.get("phase") == "decode" and not dispatched.is_set():
            assert dispatched.wait(timeout=30.0), \
                "next chunk was not dispatched while replay was pending"
        return real_replay(*a, **k)

    eng._decode_batched = counting_decode
    eng._replay = gated_replay
    try:
        out = eng.generate_batch([req], num_slots=1, pipeline=True)
    finally:
        eng._decode_batched = real_decode
        eng._replay = real_replay
    assert out[0].tokens == eng.generate(req).tokens
    assert n_decode[0] >= 2


def test_orchestrator_rejects_concurrent_replay(moe_setup):
    """The replay-ordering contract fails loudly: entering a replay while
    one is in flight (two threads bypassing the FIFO stream) raises."""
    cfg, params = moe_setup
    eng = DyMoEEngine(cfg, params, EngineConfig())
    orch = eng._make_orchestrator()
    orch._enter_replay()
    with pytest.raises(RuntimeError, match="concurrent replay"):
        orch.step_batch(np.ones((1, cfg.num_layers, cfg.num_experts), bool),
                        np.ones((1, cfg.num_layers, cfg.num_experts), bool),
                        None, np.zeros((1, cfg.num_layers)))
    orch._exit_replay()


def test_wall_and_queue_wait_accounting(moe_setup):
    """The wall_s fix: requests report SERVICE wall (admission->result)
    plus a separate queue wait, instead of every request being charged
    from scheduler start. With one slot the queue waits must be strictly
    ordered FIFO and the total elapsed must upper-bound each request's
    queue_wait + wall."""
    cfg, params = moe_setup
    eng = DyMoEEngine(cfg, params, EngineConfig(decode_chunk=2))
    rng = np.random.default_rng(11)
    reqs = _ragged_requests(rng, [(8, 8, None), (6, 8, None), (7, 8, None)])
    import time
    t0 = time.perf_counter()
    out = eng.generate_batch(reqs, num_slots=1)
    elapsed = time.perf_counter() - t0
    waits = [r.queue_wait_s for r in out]
    assert waits[0] < waits[1] < waits[2]      # FIFO admission order
    assert all(r.wall_s > 0 for r in out)
    for r in out:
        assert r.queue_wait_s + r.wall_s <= elapsed + 1e-3
    # the late request's service wall is a fraction of the elapsed run,
    # not (the old bug) the whole run measured from t0
    assert out[2].wall_s < 0.9 * elapsed


# ---------------------------------------------------- batched admission


@pytest.mark.parametrize("low_bits", [2, 0])
def test_row_local_prefill_rows_match_solo(moe_setup, low_bits):
    """The batched-admission kernel contract: a ragged row-local QUANTIZED
    prefill reproduces, per row, the solo prefill bitwise — logits,
    Critical sets, active masks — and per-row decode continues from the
    ragged caches exactly as from solo caches. ``predicted_next`` is
    allowed last-ulp float noise (reduction order of its tie-break term),
    but its expert ORDER — all the replay consumes — must match."""
    cfg, params = moe_setup
    cfg = dataclasses.replace(
        cfg, dymoe=dataclasses.replace(cfg.dymoe, low_bits=low_bits))
    qp = quantize_model(params, cfg)
    rng = np.random.default_rng(3)
    lens = [12, 7, 9]
    s = max(lens)
    prompts = [rng.integers(1, 512, n).tolist() for n in lens]
    padded = np.zeros((3, s), np.int32)
    for i, p in enumerate(prompts):
        padded[i, s - len(p):] = p
    lg, caches, info = prefill(params, cfg, jnp.asarray(padded), qparams=qp,
                               cache_slots=s + 5,
                               lengths=jnp.asarray(lens, jnp.int32),
                               row_local=True)
    assert np.asarray(info.critical_masks).shape == (cfg.num_layers, 3,
                                                     cfg.num_experts)
    for i, p in enumerate(prompts):
        slg, _, sinfo = prefill(params, cfg, jnp.asarray([p]), qparams=qp,
                                cache_slots=len(p) + 5)
        np.testing.assert_allclose(np.asarray(lg)[i], np.asarray(slg)[0],
                                   err_msg=str(i), **_ROW_TOL)
        np.testing.assert_array_equal(
            np.asarray(info.critical_masks)[:, i],
            np.asarray(sinfo.critical_masks), err_msg=str(i))
        np.testing.assert_array_equal(
            np.asarray(info.active_masks)[:, i],
            np.asarray(sinfo.active_masks), err_msg=str(i))
        np.testing.assert_allclose(
            np.asarray(info.predicted_next)[:, i],
            np.asarray(sinfo.predicted_next), rtol=1e-6, atol=1e-8)
        np.testing.assert_array_equal(
            np.argsort(-np.asarray(info.predicted_next)[:, i], axis=-1),
            np.argsort(-np.asarray(sinfo.predicted_next), axis=-1))
    # per-row decode continuation (the scheduler's device half)
    tok0 = jnp.argmax(lg, -1).astype(jnp.int32)
    toks, _, _, _, _ = decode_many_batched(
        params, cfg, tok0, caches, num_steps=4,
        done=jnp.zeros((3,), bool), n_emitted=jnp.ones((3,), jnp.int32),
        limits=jnp.full((3,), 9, jnp.int32),
        eos_tokens=jnp.full((3,), -1, jnp.int32), qparams=qp)
    for i, p in enumerate(prompts):
        slg, sc, _ = prefill(params, cfg, jnp.asarray([p]), qparams=qp,
                             cache_slots=len(p) + 4)
        st, _, _ = decode_many(params, cfg,
                               jnp.argmax(slg, -1).astype(jnp.int32), sc,
                               num_steps=4, qparams=qp)
        np.testing.assert_array_equal(np.asarray(toks)[:, i],
                                      np.asarray(st)[:, 0], err_msg=str(i))


def test_row_local_capacity_binding_and_threading(moe_setup):
    """Regression for the per-row capacity contract: (a) under HEAVY
    capacity binding (skewed routing, ~40% of (token, k) pairs dropped)
    every row of ``moe_apply_prefill_rows`` drops exactly the pairs a
    solo ``moe_apply`` of that row drops — outputs bitwise equal; (b) the
    ``row_capacities`` override (the scheduler passes exact host-computed
    ``_capacity`` values, because the in-graph f32 formula can truncate
    one slot differently from the host's f64 — e.g. capacity_factor=1.3
    at length 360: 117 vs 116) is actually threaded through to the drop
    decision."""
    from repro.models.layers.moe import _capacity, moe_apply, \
        moe_apply_prefill_rows

    cfg, params = moe_setup
    cfg = dataclasses.replace(cfg, capacity_factor=1.0)
    qw = jax.tree.map(lambda x: x[0],
                      quantize_model(params, cfg)["layers"]["moe"])
    rng = np.random.default_rng(0)
    base = rng.standard_normal(64)
    rows = [jnp.asarray(base[None] + 0.3 * rng.standard_normal((24, 64)),
                        jnp.float32) for _ in range(2)]
    # overflow by construction: the router scores every token by its
    # projection on ``base`` (positive for all of them), so all 24 tokens
    # of a row pick experts 0 and 1, and each of those two experts keeps
    # only _capacity(cfg, 24) = 8 of its 24 pairs: 2/3 of pairs drop
    router = np.outer(base / np.linalg.norm(base),
                      [4.0, 3.0, 0, 0, 0, 0, 0, 0]).astype(np.float32)
    p = dict(jax.tree.map(lambda x: x[0], params["layers"])["moe"],
             wg_router=jnp.asarray(router))
    crit = jnp.asarray(rng.random((2, 8)) < 0.5)
    cap = _capacity(cfg, 24)
    y, stats = moe_apply_prefill_rows(
        p, cfg, jnp.concatenate(rows), crit, qw, rows=2,
        row_capacities=jnp.full((2,), cap, jnp.int32))
    assert float(stats["dropped_frac"]) > 0.3   # capacity truly binds
    for i in range(2):
        y_solo, st = moe_apply(p, cfg, rows[i], critical_mask=crit[i],
                               qweights=qw)
        assert float(st.dropped_frac) > 0.3
        np.testing.assert_array_equal(np.asarray(y)[24 * i:24 * (i + 1)],
                                      np.asarray(y_solo), err_msg=str(i))
    # (b) the override reaches the drop decision: a capacity-1 pin must
    # change the output of a binding dispatch
    y_tight, _ = moe_apply_prefill_rows(
        p, cfg, jnp.concatenate(rows), crit, qw, rows=2,
        row_capacities=jnp.ones((2,), jnp.int32))
    assert not np.array_equal(np.asarray(y), np.asarray(y_tight))


def test_batched_admission_matches_solo_admissions(moe_setup):
    """N same-boundary admissions through ONE ragged row-local prefill
    wave are bitwise-equal to N solo admissions: the injected cache rows
    (left-aligned at injection), the tokens, and the replayed prefill
    telemetry (modeled TTFT) all match a one-slot serving of each request
    alone."""
    cfg, params = moe_setup
    eng = DyMoEEngine(cfg, params, EngineConfig(
        profile=EdgeProfile().with_vram(16), decode_chunk=4))
    rng = np.random.default_rng(17)
    reqs = _ragged_requests(rng, [(12, 6, None), (7, 5, None), (9, 7, None)])
    # all three admitted at the same (first) boundary: one prefill wave
    out = eng.generate_batch(reqs, num_slots=3, pipeline=False)
    for req, res in zip(reqs, out):
        solo = eng.generate(req)
        assert res.tokens == solo.tokens
        # the replayed prefill telemetry: same orchestrator decisions at
        # the same clock for the first admission of a fresh engine
        assert res.prefill_timing is not None
    # first-admitted request saw a fresh orchestrator in both runs: its
    # modeled TTFT must equal the solo run's bit for bit
    assert out[0].ttft_s == eng.generate(reqs[0]).ttft_s
    # the injected cache rows equal solo-prefilled caches bitwise
    qp = eng.qparams
    slots_len = max(len(r.prompt_tokens) + r.max_new_tokens for r in reqs)
    lens = [len(r.prompt_tokens) for r in reqs]
    smax = max(lens)
    padded = np.zeros((3, smax), np.int32)
    for i, r in enumerate(reqs):
        padded[i, smax - lens[i]:] = r.prompt_tokens
    _, rcaches, _ = prefill(params, cfg, jnp.asarray(padded), qparams=qp,
                            cache_slots=slots_len,
                            lengths=jnp.asarray(lens, jnp.int32),
                            row_local=True)
    from repro.models.model import init_decode_state
    batch = ContinuousBatchingScheduler._inject_rows(
        init_decode_state(cfg, 3, slots_len), rcaches,
        jnp.arange(3), jnp.arange(3))
    for i, r in enumerate(reqs):
        _, solo_c, _ = prefill(params, cfg,
                               jnp.asarray([r.prompt_tokens], jnp.int32),
                               qparams=qp, cache_slots=slots_len)
        for leaf, sleaf in zip(jax.tree.leaves(batch["layers"]),
                               jax.tree.leaves(solo_c["layers"])):
            got, want = np.asarray(leaf)[:, i], np.asarray(sleaf)[:, 0]
            if np.issubdtype(got.dtype, np.floating):   # K/V values
                np.testing.assert_allclose(got, want, err_msg=str(i),
                                           **_ROW_TOL)
            else:               # positions, lengths, offsets: the layout
                np.testing.assert_array_equal(got, want, err_msg=str(i))


# ------------------------------------------------- device-side done mask


def test_decode_many_batched_freezes_finished_rows(moe_setup):
    """Rows past their limit/eos freeze ON DEVICE: token re-fed, cache
    length pinned, telemetry zeroed — the scheduler's eviction contract."""
    cfg, params = moe_setup
    qp = quantize_model(params, cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (3, 10), 1, 512)
    logits, caches, _ = prefill(params, cfg, prompt, qparams=qp,
                                cache_slots=30)
    tok0 = jnp.argmax(logits, -1).astype(jnp.int32)
    toks, caches2, infos, done, emitted = decode_many_batched(
        params, cfg, tok0, caches, num_steps=6,
        done=jnp.asarray([False, False, True]),
        n_emitted=jnp.asarray([1, 1, 0], jnp.int32),
        limits=jnp.asarray([7, 3, 0], jnp.int32),
        eos_tokens=jnp.full((3,), -1, jnp.int32), qparams=qp)
    toks = np.asarray(toks)
    done = np.asarray(done)
    emitted = np.asarray(emitted)
    lengths = np.asarray(caches2["layers"].length)
    # row 0: ran all 6 steps (7 total emitted), cache advanced by 6
    assert emitted[0] == 7 and done[0]
    assert (lengths[:, 0] == 16).all()
    # row 1: froze after 2 more tokens (limit 3), cache advanced by 2,
    # its token column repeats the frozen token afterwards
    assert emitted[1] == 3 and done[1]
    assert (lengths[:, 1] == 12).all()
    assert (toks[2:, 1] == toks[1, 1]).all()
    # row 2 was never live: untouched cache, zeroed telemetry
    assert (lengths[:, 2] == 10).all()
    act = np.asarray(infos.active_masks)           # (T, L, B, E)
    assert act[:, :, 2].sum() == 0
    assert act[2:, :, 1].sum() == 0 and act[:2, :, 1].sum() > 0
    assert act[:, :, 0].sum() > 0


def test_decode_many_batched_rows_match_decode_many(moe_setup):
    """A live row of the slot-batched decode is bit-identical to the solo
    fused decode loop `generate` uses. The rows are assembled the way the
    scheduler assembles them — each prefilled SOLO (per-request critical
    masks) and injected into the slot batch — because the batch-shared
    prefill couples rows through its aggregated Critical set."""
    cfg, params = moe_setup
    qp = quantize_model(params, cfg)
    prompts = [jax.random.randint(jax.random.PRNGKey(s), (1, 8), 1, 512)
               for s in (2, 3)]
    solos, row_caches, t0s = [], [], []
    for p in prompts:
        lg, c, _ = prefill(params, cfg, p, qparams=qp, cache_slots=20)
        t0 = jnp.argmax(lg, -1).astype(jnp.int32)
        t, _, _ = decode_many(params, cfg, t0, c, num_steps=5, qparams=qp)
        solos.append(np.asarray(t)[:, 0])
        row_caches.append(c)
        t0s.append(t0)
    c = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=1),
                     *row_caches)
    toks, _, _, _, _ = decode_many_batched(
        params, cfg, jnp.concatenate(t0s), c, num_steps=5,
        done=jnp.zeros((2,), bool), n_emitted=jnp.ones((2,), jnp.int32),
        limits=jnp.full((2,), 9, jnp.int32),
        eos_tokens=jnp.full((2,), -1, jnp.int32), qparams=qp)
    toks = np.asarray(toks)
    np.testing.assert_array_equal(toks[:, 0], solos[0])
    np.testing.assert_array_equal(toks[:, 1], solos[1])


# ------------------------------------------- ragged right-aligned prefill


def test_ragged_prefill_rows_match_solo_prefill(moe_setup):
    """Right-aligned padded batched prefill (positions/attention offsets,
    pad-excluded routing stats) reproduces each row's solo-prefill logits
    bit-for-bit in the full-precision path, and greedy decode continues
    per row from the ragged caches exactly as from solo caches."""
    cfg, params = moe_setup
    rng = np.random.default_rng(3)
    lens = [12, 7, 9]
    s = max(lens)
    prompts = [rng.integers(1, 512, n).tolist() for n in lens]
    padded = np.zeros((3, s), np.int32)
    for i, p in enumerate(prompts):
        padded[i, s - len(p):] = p
    lg, caches, _ = prefill(params, cfg, jnp.asarray(padded),
                            cache_slots=s + 5,
                            lengths=jnp.asarray(lens, jnp.int32))
    for i, p in enumerate(prompts):
        solo_lg, _, _ = prefill(params, cfg, jnp.asarray([p]),
                                cache_slots=len(p))
        np.testing.assert_allclose(np.asarray(lg)[i],
                                   np.asarray(solo_lg)[0], err_msg=str(i),
                                   **_ROW_TOL)
    # decode continuation: per-row offsets place new tokens at the uniform
    # slot frontier while logical positions stay per-row
    offsets = np.asarray(caches["layers"].offset)
    assert (offsets == np.asarray([s - n for n in lens])[None, :]).all()
    tok0 = jnp.argmax(lg, -1).astype(jnp.int32)
    toks, _, _ = decode_many(params, cfg, tok0, caches, num_steps=4)
    for i, p in enumerate(prompts):
        solo_lg, sc, _ = prefill(params, cfg, jnp.asarray([p]),
                                 cache_slots=len(p) + 4)
        st, _, _ = decode_many(params, cfg,
                               jnp.argmax(solo_lg, -1).astype(jnp.int32),
                               sc, num_steps=4)
        np.testing.assert_array_equal(np.asarray(toks)[:, i],
                                      np.asarray(st)[:, 0], err_msg=str(i))


def test_static_batch_handles_ragged_prompts(moe_setup):
    """The lockstep baseline no longer demands equal-length prompts."""
    cfg, params = moe_setup
    eng = DyMoEEngine(cfg, params, EngineConfig(decode_chunk=4))
    rng = np.random.default_rng(13)
    reqs = _ragged_requests(rng, [(10, 6, None), (6, 4, None), (8, 8, None)])
    out = eng.generate_batch(reqs, static=True)
    assert [len(r.tokens) for r in out] == [6, 4, 8]
    assert np.isnan(out[0].ttft_s)  # baseline: telemetry discarded


# ----------------------------------------------------- dense-arch slots


def test_scheduler_serves_dense_arch():
    cfg = ModelConfig(
        name="d", arch_type="dense", num_layers=2, d_model=64,
        vocab_size=256, num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
        dtype="float32", remat="none")
    params = init_params(cfg, jax.random.PRNGKey(1))
    eng = DyMoEEngine(cfg, params, EngineConfig(decode_chunk=2))
    reqs = [Request(prompt_tokens=[1, 2, 3, 4], max_new_tokens=4),
            Request(prompt_tokens=[5, 6, 7], max_new_tokens=6)]
    out = eng.generate_batch(reqs, num_slots=1)
    for req, res in zip(reqs, out):
        assert res.tokens == eng.generate(req).tokens
        assert np.isfinite(res.ttft_s) and np.isfinite(res.tpot_s)
        assert res.cache_stats is None  # no orchestrator on dense archs
