"""Serving engine integration: generation determinism, ablation ordering,
cache accounting, chunked-decode parity — the system half of the paper."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.models import init_params
from repro.models.config import DyMoEPolicy, ModelConfig
from repro.serving import DyMoEEngine, EngineConfig, Request
from repro.serving.cost_model import EdgeCostModel, EdgeProfile, expert_bytes


@pytest.fixture(scope="module")
def moe_setup():
    cfg = ModelConfig(
        name="t", arch_type="moe", num_layers=4, d_model=64, vocab_size=512,
        num_heads=4, num_kv_heads=2, head_dim=16, num_experts=8,
        num_experts_per_tok=2, moe_d_ff=64, capacity_factor=4.0,
        dtype="float32", remat="none",
        dymoe=DyMoEPolicy(low_bits=2, retention=0.75))
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_greedy_generation_deterministic(moe_setup):
    cfg, params = moe_setup
    eng = DyMoEEngine(cfg, params, EngineConfig())
    req = Request(prompt_tokens=list(range(1, 17)), max_new_tokens=8)
    r1 = eng.generate(req)
    r2 = eng.generate(req)
    assert r1.tokens == r2.tokens
    assert len(r1.tokens) == 8


def test_timing_accounting_present(moe_setup):
    cfg, params = moe_setup
    eng = DyMoEEngine(cfg, params,
                      EngineConfig(profile=EdgeProfile().with_vram(16)))
    res = eng.generate(Request(prompt_tokens=list(range(1, 17)),
                               max_new_tokens=4))
    assert res.ttft_s > 0 and res.tpot_s > 0
    assert res.prefill_timing is not None
    assert len(res.decode_timings) == 3
    assert res.cache_stats["misses"] > 0


def test_ablation_ordering(moe_setup):
    """Modeled latency must reproduce paper Table 3's ordering:
    load-on-demand >= cache >= cache+prefetch, and dyquant reduces I/O."""
    cfg, params = moe_setup
    req = Request(prompt_tokens=list(range(1, 17)), max_new_tokens=6)

    def run(**kw):
        eng = DyMoEEngine(cfg, params, EngineConfig(
            profile=EdgeProfile().with_vram(16), **kw))
        r = eng.generate(req)
        return r.ttft_s + r.tpot_s * 5

    lod = run(enable_cache=False, enable_prefetch=False)
    cache = run(enable_cache=True, enable_prefetch=False)
    full = run(enable_cache=True, enable_prefetch=True)
    assert lod >= cache * 0.999
    assert cache >= full * 0.999


def test_batched_path(moe_setup):
    cfg, params = moe_setup
    eng = DyMoEEngine(cfg, params, EngineConfig())
    reqs = [Request(prompt_tokens=list(range(1, 9)), max_new_tokens=4)
            for _ in range(3)]
    out = eng.generate_batch(reqs)
    assert len(out) == 3
    assert all(len(r.tokens) == 4 for r in out)


def test_expert_bytes_scaling(moe_setup):
    cfg, _ = moe_setup
    b4 = expert_bytes(cfg, 4)
    b2 = expert_bytes(cfg, 2)
    b16 = expert_bytes(cfg, 16)
    assert b16 > b4 * 3 and b4 > b2


def test_cost_model_prefill_scales_with_seq(moe_setup):
    cfg, _ = moe_setup
    cm = EdgeCostModel(cfg, EdgeProfile())
    t1 = cm.layer_compute_s(phase="prefill", s_ctx=128, s_q=128,
                            active_experts_hi=4, tokens_routed=128)
    t2 = cm.layer_compute_s(phase="prefill", s_ctx=1024, s_q=1024,
                            active_experts_hi=4, tokens_routed=1024)
    assert t2 > t1


def test_chunked_decode_matches_per_token(moe_setup):
    """The acceptance contract: decode_chunk=16 and decode_chunk=1 produce
    bitwise-identical greedy tokens and identical modeled TTFT / TPOT /
    cache stats / weight-byte accounting."""
    cfg, params = moe_setup
    req = Request(prompt_tokens=list(range(1, 17)), max_new_tokens=12)
    r1 = DyMoEEngine(cfg, params,
                     EngineConfig(decode_chunk=1)).generate(req)
    r16 = DyMoEEngine(cfg, params,
                      EngineConfig(decode_chunk=16)).generate(req)
    r5 = DyMoEEngine(cfg, params,
                     EngineConfig(decode_chunk=5)).generate(req)
    assert r16.tokens == r1.tokens == r5.tokens
    assert r16.ttft_s == r1.ttft_s == r5.ttft_s
    assert r16.tpot_s == r1.tpot_s == r5.tpot_s
    assert r16.cache_stats == r1.cache_stats == r5.cache_stats
    assert r16.prefill_weight_bytes == r1.prefill_weight_bytes
    assert r16.decode_weight_bytes_per_tok == r1.decode_weight_bytes_per_tok
    assert len(r16.decode_timings) == len(r1.decode_timings) == 11


def test_sampling_is_chunk_invariant(moe_setup):
    """fold_in(key, global token index) keys make sampled outputs
    independent of the decode chunking."""
    cfg, params = moe_setup
    req = Request(prompt_tokens=list(range(1, 17)), max_new_tokens=10,
                  temperature=0.8, top_k=4)
    key = jax.random.PRNGKey(42)
    outs = [DyMoEEngine(cfg, params,
                        EngineConfig(decode_chunk=c)).generate(
                            req, rng_key=key).tokens
            for c in (1, 3, 16)]
    assert outs[0] == outs[1] == outs[2]


def test_eos_early_exit(moe_setup):
    """Generation stops at eos_token (inclusive) with identical modeled
    accounting whether the eos lands mid-chunk or on a chunk boundary."""
    cfg, params = moe_setup
    base = DyMoEEngine(cfg, params, EngineConfig()).generate(
        Request(prompt_tokens=list(range(1, 17)), max_new_tokens=12))
    eos = base.tokens[4]
    cut = base.tokens.index(eos) + 1
    req = Request(prompt_tokens=list(range(1, 17)), max_new_tokens=12,
                  eos_token=eos)
    r16 = DyMoEEngine(cfg, params,
                      EngineConfig(decode_chunk=16)).generate(req)
    r1 = DyMoEEngine(cfg, params,
                     EngineConfig(decode_chunk=1)).generate(req)
    assert r16.tokens == base.tokens[:cut]
    assert r16.tokens[-1] == eos
    assert r16.tokens == r1.tokens
    assert r16.tpot_s == r1.tpot_s
    assert r16.cache_stats == r1.cache_stats
    assert len(r16.decode_timings) == len(r1.decode_timings) == cut - 1


def test_sampler_fallback_without_key(moe_setup):
    """temperature > 0 with rng_key=None must not crash: the engine warns
    and decodes greedily (documented sample_token contract)."""
    cfg, params = moe_setup
    eng = DyMoEEngine(cfg, params, EngineConfig())
    greedy = eng.generate(Request(prompt_tokens=list(range(1, 17)),
                                  max_new_tokens=6))
    with pytest.warns(UserWarning, match="greedy"):
        r = eng.generate(Request(prompt_tokens=list(range(1, 17)),
                                 max_new_tokens=6, temperature=1.0))
    assert r.tokens == greedy.tokens


def test_sample_token_none_key_fallback():
    from repro.serving import sample_token
    logits = jax.numpy.asarray(np.random.default_rng(0)
                               .standard_normal((2, 16)), jax.numpy.float32)
    with pytest.warns(UserWarning, match="greedy"):
        out = sample_token(logits, None, temperature=0.7)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(logits.argmax(-1)))


def test_batched_path_per_request_limits(moe_setup):
    """generate_batch honors per-request max_new_tokens and eos_token and
    trims each row independently."""
    cfg, params = moe_setup
    eng = DyMoEEngine(cfg, params, EngineConfig())
    prompt = list(range(1, 9))
    base = eng.generate_batch([Request(prompt_tokens=prompt,
                                       max_new_tokens=8)
                               for _ in range(2)])
    eos0 = base[0].tokens[2]
    cut0 = base[0].tokens.index(eos0) + 1
    out = eng.generate_batch([
        Request(prompt_tokens=prompt, max_new_tokens=8, eos_token=eos0),
        Request(prompt_tokens=prompt, max_new_tokens=3),
    ])
    assert out[0].tokens == base[0].tokens[:cut0]
    assert out[1].tokens == base[1].tokens[:3]


def test_batched_path_stops_when_all_rows_finished(moe_setup):
    """When every row hits its limit/eos early, decode stops between chunks
    instead of running to max_new_tokens."""
    cfg, params = moe_setup
    eng = DyMoEEngine(cfg, params, EngineConfig(decode_chunk=2))
    prompt = list(range(1, 9))
    out = eng.generate_batch([Request(prompt_tokens=prompt,
                                      max_new_tokens=3)
                              for _ in range(2)])
    assert all(len(r.tokens) == 3 for r in out)


def test_tiny_vram_budget_serves_without_crash(moe_setup):
    """Regression: a VRAM budget smaller than one expert blob used to
    raise ValueError from the cache mid-request. It must now serve the
    request end-to-end — every oversized load degrades to a bypass
    (charged as missed bytes, never resident) with a one-time warning."""
    cfg, params = moe_setup
    profile = dataclasses.replace(EdgeProfile(), vram_bytes=1)
    eng = DyMoEEngine(cfg, params, EngineConfig(profile=profile))
    req = Request(prompt_tokens=list(range(1, 17)), max_new_tokens=6)
    with pytest.warns(UserWarning, match="bypass"):
        res = eng.generate(req)
    ref = DyMoEEngine(cfg, params, EngineConfig()).generate(req)
    assert res.tokens == ref.tokens       # math path untouched by budget
    assert res.cache_stats["bypass_loads"] > 0
    assert res.cache_stats["hits"] == 0   # nothing can ever be resident
    assert np.isfinite(res.ttft_s) and np.isfinite(res.tpot_s)
    # every active expert's bytes sit on the critical path every step
    assert res.tpot_s > ref.tpot_s
    # the batched/scheduled path survives the same budget (fresh
    # orchestrator => its cache warns once more)
    with pytest.warns(UserWarning, match="bypass"):
        out = eng.generate_batch(
            [req, Request(prompt_tokens=list(range(1, 9)),
                          max_new_tokens=3)], num_slots=2)
    assert [np.isfinite(r.tpot_s) for r in out] == [True, True]


def test_dense_arch_engine_fallback():
    """Engine serves non-MoE archs too (no orchestrator, modeled compute)."""
    cfg = ModelConfig(
        name="d", arch_type="dense", num_layers=2, d_model=64,
        vocab_size=256, num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
        dtype="float32", remat="none")
    params = init_params(cfg, jax.random.PRNGKey(1))
    eng = DyMoEEngine(cfg, params, EngineConfig())
    res = eng.generate(Request(prompt_tokens=[1, 2, 3, 4],
                               max_new_tokens=4))
    assert len(res.tokens) == 4
    assert res.cache_stats is None


# -------------------------------------------- weights built layer by layer


def test_init_quantized_params_matches_two_step_route(moe_setup):
    """Building the packed store layer by layer from the seed gives the
    same weights as drawing the dense stack, quantizing it, and dropping
    the dense routed experts."""
    from repro.models import drop_dense_experts, init_quantized_params, \
        quantize_model

    cfg, params = moe_setup
    lean, qparams = init_quantized_params(cfg, jax.random.PRNGKey(0))
    assert not {"w_gate", "w_up", "w_down"} & set(lean["layers"]["moe"])
    want = (drop_dense_experts(params), quantize_model(params, cfg))
    got_leaves, got_def = jax.tree.flatten((lean, qparams))
    want_leaves, want_def = jax.tree.flatten(want)
    assert got_def == want_def
    for a, b in zip(got_leaves, want_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("use_dymoe", [True, False])
def test_engine_keeps_dense_experts_only_without_dymoe(moe_setup, use_dymoe):
    """With DyMoE on, the engine serves routed experts from the packed
    store alone: no dense expert leaf stays resident or reaches jit. The
    "off" mode keeps (and needs) them."""
    from repro.models import init_quantized_params

    cfg, params = moe_setup
    eng = DyMoEEngine(cfg, params, EngineConfig(use_dymoe=use_dymoe))
    assert ("w_gate" in eng.params["layers"]["moe"]) == (not use_dymoe)
    req = Request(prompt_tokens=list(range(1, 13)), max_new_tokens=5)
    tokens = eng.generate(req).tokens
    if use_dymoe:   # the lean seeded build serves the same tokens
        lean, q = init_quantized_params(cfg, jax.random.PRNGKey(0))
        assert DyMoEEngine(cfg, lean, EngineConfig(),
                           qparams=q).generate(req).tokens == tokens
