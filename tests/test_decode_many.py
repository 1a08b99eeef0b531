"""Chunked decode equivalence: the fused ``decode_many`` scan must produce
exactly the tokens and telemetry of the per-step ``decode_step`` loop it
replaces — across MoE, dense, and SSM architectures — and its counter-based
(fold_in) sampling must be invariant to how the steps are chunked."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import decode_many, decode_step, init_params, prefill, \
    quantize_model
from repro.models.config import DyMoEPolicy, ModelConfig
from repro.serving.sampler import sample_token

STEPS = 6


def _moe_cfg():
    return ModelConfig(
        name="t", arch_type="moe", num_layers=3, d_model=64, vocab_size=256,
        num_heads=4, num_kv_heads=2, head_dim=16, num_experts=8,
        num_experts_per_tok=2, moe_d_ff=64, capacity_factor=4.0,
        dtype="float32", remat="none",
        dymoe=DyMoEPolicy(low_bits=2, retention=0.75))


def _dense_cfg():
    return ModelConfig(
        name="d", arch_type="dense", num_layers=2, d_model=64,
        vocab_size=256, num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
        dtype="float32", remat="none",
        dymoe=DyMoEPolicy(low_bits=2, retention=0.6))


def _ssm_cfg():
    return get_config("falcon_mamba_7b").reduced()


def _setup(cfg, use_q=True):
    params = init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_model(params, cfg) if use_q else None
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 1,
                                cfg.vocab_size)
    logits, caches, _ = prefill(params, cfg, prompt, qparams=qp,
                                cache_slots=prompt.shape[1] + STEPS + 1)
    tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return params, qp, tok0, caches


def _loop_reference(params, cfg, tok0, caches, qp):
    """The per-step loop decode_many replaces."""
    toks, infos = [], []
    tok, c = tok0, caches
    for _ in range(STEPS):
        lg, c, info = decode_step(params, cfg, tok, c, qparams=qp)
        tok = sample_token(lg)
        toks.append(np.asarray(tok))
        infos.append(info)
    return np.stack(toks), infos


@pytest.mark.parametrize("cfg_fn", [_moe_cfg, _dense_cfg, _ssm_cfg],
                         ids=["moe", "dense", "ssm"])
def test_greedy_tokens_match_per_step_loop(cfg_fn):
    cfg = cfg_fn()
    params, qp, tok0, caches = _setup(cfg)
    ref_toks, _ = _loop_reference(params, cfg, tok0, caches, qp)
    toks, _, _ = decode_many(params, cfg, tok0, caches, num_steps=STEPS,
                             qparams=qp)
    np.testing.assert_array_equal(np.asarray(toks), ref_toks)


def test_moe_telemetry_matches_per_step_loop():
    cfg = _moe_cfg()
    params, qp, tok0, caches = _setup(cfg)
    _, ref_infos = _loop_reference(params, cfg, tok0, caches, qp)
    _, _, infos = decode_many(params, cfg, tok0, caches, num_steps=STEPS,
                              qparams=qp)
    for field in ("critical_masks", "active_masks"):
        got = np.asarray(getattr(infos, field))
        ref = np.stack([np.asarray(getattr(i, field)) for i in ref_infos])
        np.testing.assert_array_equal(got, ref, err_msg=field)
    for field in ("predicted_next",):
        got = np.asarray(getattr(infos, field))
        ref = np.stack([np.asarray(getattr(i, field)) for i in ref_infos])
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-7,
                                   err_msg=field)
    assert infos.critical_masks.shape == (STEPS, cfg.num_layers,
                                          cfg.num_experts)


def test_final_caches_match_per_step_loop():
    cfg = _moe_cfg()
    params, qp, tok0, caches = _setup(cfg)
    tok, c = tok0, caches
    for _ in range(STEPS):
        lg, c, _ = decode_step(params, cfg, tok, c, qparams=qp)
        tok = sample_token(lg)
    _, c2, _ = decode_many(params, cfg, tok0, caches, num_steps=STEPS,
                           qparams=qp)
    np.testing.assert_array_equal(np.asarray(c["layers"].length),
                                  np.asarray(c2["layers"].length))
    np.testing.assert_allclose(np.asarray(c["layers"].k),
                               np.asarray(c2["layers"].k),
                               rtol=1e-6, atol=1e-7)


def test_sampling_is_chunk_invariant():
    """fold_in(key, global_step) keys: decoding 6 steps in one scan equals
    decoding 2 + 4 with the same base key and running start_step."""
    cfg = _moe_cfg()
    params, qp, tok0, caches = _setup(cfg)
    key = jax.random.PRNGKey(7)
    kw = dict(qparams=qp, rng_key=key, temperature=0.9, top_k=4)
    toks_all, _, _ = decode_many(params, cfg, tok0, caches, num_steps=STEPS,
                                 start_step=0, **kw)
    t1, c1, _ = decode_many(params, cfg, tok0, caches, num_steps=2,
                            start_step=0, **kw)
    t2, _, _ = decode_many(params, cfg, t1[-1], c1, num_steps=STEPS - 2,
                           start_step=2, **kw)
    np.testing.assert_array_equal(
        np.asarray(toks_all), np.concatenate([np.asarray(t1),
                                              np.asarray(t2)]))


def test_greedy_ignores_rng_key():
    cfg = _dense_cfg()
    params, qp, tok0, caches = _setup(cfg, use_q=False)
    a, _, _ = decode_many(params, cfg, tok0, caches, num_steps=3)
    b, _, _ = decode_many(params, cfg, tok0, caches, num_steps=3,
                          rng_key=jax.random.PRNGKey(3), temperature=0.0)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sampling_without_key_warns_and_is_greedy():
    cfg = _dense_cfg()
    params, qp, tok0, caches = _setup(cfg, use_q=False)
    ref, _, _ = decode_many(params, cfg, tok0, caches, num_steps=3)
    with pytest.warns(UserWarning, match="greedy"):
        got, _, _ = decode_many(params, cfg, tok0, caches, num_steps=3,
                                temperature=0.9)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
