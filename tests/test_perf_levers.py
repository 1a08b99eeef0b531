"""§Perf hillclimb levers must preserve semantics exactly (or within dtype
tolerance): causal chunk skipping, bf16 attention, scan vs unrolled layer
stacks."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.models import init_params, prefill, decode_step, loss_fn
from repro.models.config import ModelConfig


def _dense_cfg(**kw):
    base = dict(name="d", arch_type="dense", num_layers=2, d_model=64,
                vocab_size=128, num_heads=4, num_kv_heads=2, head_dim=16,
                d_ff=128, dtype="float32", remat="none")
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def dense_setup():
    cfg = _dense_cfg()
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    toks = jax.random.randint(key, (2, 32), 0, 128)
    ref, _, _ = prefill(params, cfg, toks, cache_slots=64)
    return cfg, params, toks, np.asarray(ref)


def test_causal_skip_bit_exact(dense_setup):
    cfg, params, toks, ref = dense_setup
    c = dataclasses.replace(cfg, attn_causal_skip=True)
    lg, _, _ = prefill(params, c, toks, cache_slots=64)
    np.testing.assert_array_equal(np.asarray(lg), ref)


def test_causal_skip_with_window(dense_setup):
    cfg, params, toks, _ = dense_setup
    cw = dataclasses.replace(cfg, sliding_window=8)
    ref, _, _ = prefill(params, cw, toks, cache_slots=64)
    cs = dataclasses.replace(cw, attn_causal_skip=True)
    lg, _, _ = prefill(params, cs, toks, cache_slots=64)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref), atol=1e-5)


def test_bf16_attention_close(dense_setup):
    cfg, params, toks, ref = dense_setup
    c = dataclasses.replace(cfg, attn_compute_dtype="bfloat16")
    lg, _, _ = prefill(params, c, toks, cache_slots=64)
    assert np.abs(np.asarray(lg) - ref).max() < 0.2


def test_scan_vs_unrolled_identical(dense_setup):
    cfg, params, toks, ref = dense_setup
    c = dataclasses.replace(cfg, scan_layers=False)
    lg, _, _ = prefill(params, c, toks, cache_slots=64)
    np.testing.assert_allclose(np.asarray(lg), ref, atol=1e-5)
    # train forward too
    batch = {"tokens": toks, "labels": toks}
    l1, _ = loss_fn(params, cfg, batch)
    l2, _ = loss_fn(params, c, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


def test_unrolled_decode_consistency(dense_setup):
    cfg, params, toks, _ = dense_setup
    c = dataclasses.replace(cfg, scan_layers=False)
    full, _, _ = prefill(params, c, toks, cache_slots=64)
    _, caches, _ = prefill(params, c, toks[:, :31], cache_slots=64)
    dec, _, _ = decode_step(params, c, toks[:, 31], caches)
    # compare decode-after-31 against full prefill of 32
    assert np.abs(np.asarray(full) - np.asarray(dec)).max() < 2e-3
