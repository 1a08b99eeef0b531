"""Per-architecture smoke tests (assignment requirement): a REDUCED variant
of each family (<=2 layers, d_model<=512, <=4 experts) runs one forward and
one train step on CPU with shape + finiteness checks. VLM/audio archs also
exercise the embeds (stubbed frontend) input path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import (
    decode_step,
    forward,
    init_decode_state,
    init_params,
    loss_fn,
    prefill,
    quantize_model,
)
from repro.training.optimizer import AdamW, constant_lr


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch, rng):
    cfg = get_config(arch).reduced()
    cfg.validate()
    assert cfg.num_layers <= 2 or cfg.arch_type == "hybrid"
    assert cfg.d_model <= 512
    if cfg.is_moe:
        assert cfg.num_experts <= 4
    params = init_params(cfg, rng)
    B, S = 2, 16
    tokens = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}

    logits, aux = forward(params, cfg, tokens)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()

    opt = AdamW(lr=constant_lr(1e-3))
    state = opt.init(params)
    (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, cfg, batch)
    assert np.isfinite(float(loss))
    new_params, _ = opt.update(params, grads, state)
    # training actually changed the weights
    changed = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(new_params)))
    assert changed


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_serve_path(arch, rng):
    cfg = get_config(arch).reduced()
    params = init_params(cfg, rng)
    qp = quantize_model(params, cfg)
    B, S = 2, 16
    tokens = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    logits, caches, info = prefill(params, cfg, tokens, qparams=qp,
                                   cache_slots=32)
    assert logits.shape == (B, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    lg, caches, info2 = decode_step(params, cfg, tokens[:, 0],
                                    init_decode_state(cfg, B, 32),
                                    qparams=qp)
    assert lg.shape == (B, cfg.vocab_size)
    assert np.isfinite(np.asarray(lg)).all()
    if cfg.is_moe:
        # telemetry covers the MoE layers (all of them but a leading
        # dense stack)
        assert info.critical_masks.shape == (cfg.num_moe_layers,
                                             cfg.num_experts)
        assert info2.predicted_next.shape == (cfg.num_moe_layers,
                                              cfg.num_experts)


@pytest.mark.parametrize("arch", ["internvl2_26b", "musicgen_medium"])
def test_smoke_stubbed_frontend_embeds(arch, rng):
    """VLM/audio: precomputed patch/frame embeddings replace tokens."""
    cfg = get_config(arch).reduced()
    params = init_params(cfg, rng)
    B, S = 2, 16
    embeds = jax.random.normal(rng, (B, S, cfg.d_model), jnp.float32)
    logits, aux = forward(params, cfg, embeds=embeds)
    assert logits.shape == (B, S, cfg.vocab_size)
    lg, caches, _ = prefill(params, cfg, embeds=embeds, cache_slots=32)
    assert lg.shape == (B, cfg.vocab_size)
    assert np.isfinite(np.asarray(lg)).all()
