"""The DeepSeek-V3 block (Moonlight-16B-A3B's: latent attention, a dense
lead layer, shared experts, a sigmoid router with a selection bias) at a
reduced size on the CPU, against the plain reference
``bench/references/deepseek_v3.py`` on the same seeded weights.

The program runs its activations in float32 over the configuration's
bfloat16 weights (as ``bench/tests/test_reference.py`` does), so it and
the reference compute the same mathematics, DyMoE policy included, and
differ by summation order alone: about 3e-6 on logits of scale 4. The
tolerance ``TOL`` = 1e-4 leaves thirty times that; the latent-attention
scores computed from bfloat16 operands move the logits by 2e-2 to 3e-2,
two hundred times ``TOL`` (``test_bf16_mla_scores_fall_outside``)."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.families import deepseek_v3 as fam
from bench.references import deepseek_v3 as ref
from repro.models.kv_cache import init_latent_cache
from repro.models.layers.mla import init_mla, mla_decode, mla_train
from repro.models.layers.moe import route, selection_scores
from repro.models.model import _t_l_array, decode_many_batched, \
    decode_step, init_decode_state, prefill
from repro.serving.scheduler import _capacity

SPEC = json.loads((Path(__file__).resolve().parents[1] / "bench" / "tests"
                   / "data" / "configs" / "tiny-deepseek.json").read_text())
SEED = 2 ** 31 + 91
TOL = 1e-4
SLOTS = 32
STEPS = 6


def _program(spec, attn_dtype="float32"):
    """(cfg, params, qparams): the program's weights from the reference's
    seed key, float32 activations over bfloat16 weights."""
    cfg = fam.program_config(spec)
    params, qparams = fam.init_weights(cfg, ref.seed_key(SEED))
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    return (dataclasses.replace(cfg, dtype="float32",
                                attn_compute_dtype=attn_dtype),
            params, qparams)


def _serve(cfg, params, qparams, prompts):
    """A ragged admission wave (right-aligned prompts, row-local DyMoE
    policy), then ``STEPS`` greedy steps of ``decode_many_batched`` over
    the latent cache, then one more step's logits. Returns (wave logits
    (n, V), served tokens (n, STEPS + 1), next logits (n, V))."""
    n, lens = len(prompts), [len(p) for p in prompts]
    toks = np.zeros((n, max(lens)), np.int32)
    for i, p in enumerate(prompts):
        toks[i, max(lens) - len(p):] = p
    lg0, caches, _ = jax.jit(lambda p, q, t, l, c: prefill(
        p, cfg, t, qparams=q, cache_slots=SLOTS, lengths=l, row_local=True,
        row_capacities=c))(params, qparams, jnp.asarray(toks),
                           jnp.asarray(lens, jnp.int32),
                           jnp.asarray([_capacity(cfg, s) for s in lens],
                                       jnp.int32))
    first = jnp.argmax(lg0, -1).astype(jnp.int32)
    z = jnp.zeros(n, jnp.int32)
    out, caches, _, done, _ = jax.jit(lambda p, q, t, c: decode_many_batched(
        p, cfg, t, c, num_steps=STEPS, done=jnp.zeros(n, bool),
        n_emitted=z + 1, limits=z + STEPS + 2, eos_tokens=z - 1, qparams=q,
        live_cap=n))(params, qparams, first, caches)
    assert not np.asarray(done).any()
    lg1, _, _ = jax.jit(lambda p, q, t, c: decode_step(
        p, cfg, t, c, qparams=q, per_row_moe=True,
        live_rows=jnp.ones(n, bool), moe_capacity=n))(params, qparams,
                                                      out[-1], caches)
    served = np.concatenate([np.asarray(first)[:, None], np.asarray(out).T],
                            axis=1)
    return np.asarray(lg0), served, np.asarray(lg1)


def _reference(spec, prompts, served):
    """The reference's full forward over prompt + served tokens, logits at
    every position that predicted a served token and at the next one."""
    n, m = served.shape
    t = max(len(p) for p in prompts) + m + 1
    toks = np.zeros((n, t), np.int32)
    n_prompt, n_total = np.zeros(n, np.int32), np.zeros(n, np.int32)
    rows_pos = np.zeros((n, m + 1), np.int32)
    for i, p in enumerate(prompts):
        seq = list(p) + list(served[i])
        toks[i, :len(seq)] = seq
        n_prompt[i], n_total[i] = len(p), len(seq)
        rows_pos[i] = len(p) - 1 + np.arange(m + 1)
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward_rows(spec, SEED, toks, n_prompt,
                                           n_total, rows_pos))


def _prompts():
    rng = np.random.default_rng(3)
    return [rng.integers(1, SPEC["vocab_size"], n).tolist() for n in (13, 8)]


def _compare(spec, attn_dtype="float32"):
    """(widest logit difference at the wave's and the next step's
    positions, widest gap of a served token below the reference's best)."""
    prompts = _prompts()
    lg0, served, lg1 = _serve(*_program(spec, attn_dtype), prompts)
    r = _reference(spec, prompts, served)
    gap = r[:, :-1].max(-1) - np.take_along_axis(
        r[:, :-1], served[..., None], -1)[..., 0]
    return max(np.abs(lg0 - r[:, 0]).max(), np.abs(lg1 - r[:, -1]).max()), \
        gap.max()


@pytest.mark.parametrize("dense", [1, 2], ids=["dense_lead_1",
                                               "dense_lead_2"])
def test_prefill_then_latent_decode_agrees_with_reference(dense):
    """Prefill of a ragged wave, then decoding through the latent cache by
    ``decode_many_batched``, against the reference's full forward: logits
    within ``TOL`` where the program returns them, and every served token
    the reference's argmax within ``TOL``. One and two leading dense
    layers before the MoE stack."""
    diff, gap = _compare(dict(SPEC, first_k_dense_replace=dense))
    assert diff < TOL, diff
    assert gap < TOL, gap


def test_bf16_mla_scores_fall_outside_the_tolerance():
    """The comparison is tight enough to see the latent-attention scores
    computed from bfloat16 operands (prefill and decode)."""
    diff, gap = _compare(SPEC, attn_dtype="bfloat16")
    assert max(diff, gap) > 20 * TOL, (diff, gap)


def test_absorbed_and_decompressed_mla_agree():
    """Decoding one token at a time against the latent cache (the query
    absorbs the key up-projection, the output the value up-projection)
    gives what decompressed causal attention over the whole sequence gives
    at every position, and the cache holds the latents and rope keys that
    decompressed attention hands to prefill. f32: summation order (1e-5)."""
    cfg, _, _ = _program(SPEC)
    b, s = 2, 9
    p = init_mla(cfg, jax.random.PRNGKey(1), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (b, s, cfg.d_model))
    want, _, (c_kv, k_pe) = mla_train(p, cfg, x)
    cache = init_latent_cache(b, 16, cfg.kv_lora_rank, cfg.qk_rope_head_dim,
                              jnp.float32)
    step = jax.jit(lambda x, c: mla_decode(p, cfg, x, c))
    got = []
    for t in range(s):
        y, cache = step(x[:, t:t + 1], cache)
        got.append(y)
    np.testing.assert_allclose(np.concatenate(got, axis=1), want,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(cache.k[:, :, :s], c_kv, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(cache.v[:, :, :s], k_pe, rtol=1e-6, atol=1e-6)


def test_router_selection_and_weights_agree():
    """The sigmoid router with its selection bias: the program's layer
    weights are the reference's (the same recipe from the seed), and its
    experts, gates (renormalized, times 2.446) and Critical-set scores
    (affinity plus bias) are the reference's. The bias changes the
    selection on some tokens, so selecting by affinity alone would not
    agree."""
    cfg, params, _ = _program(SPEC)
    s = ref.sizes(SPEC)
    layer = 1                                   # the first MoE layer
    lkey = ref._outer_keys(ref.seed_key(SEED), s["L"])[2][layer]
    want_w = ref.moe_weights(s, jax.random.split(lkey)[1])
    p = jax.tree.map(lambda a: a[layer - cfg.first_k_dense],
                     params["layers"]["moe"])
    # equal to 1 ulp: the program scales its draws inside one jitted init
    np.testing.assert_allclose(p["wg_router"], want_w["router"], rtol=1e-6)
    np.testing.assert_allclose(p["e_score_bias"], want_w["bias"], rtol=1e-6)

    h = jax.random.normal(jax.random.PRNGKey(4), (256, cfg.d_model))
    logits = jnp.matmul(h, p["wg_router"], precision="highest")
    aff, gates, idx = route(p, cfg, logits)
    select, want_gates, want_idx = ref.route(s, jax.nn.sigmoid(logits),
                                             want_w["bias"])
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(gates, want_gates, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), 2.446, rtol=1e-6)
    np.testing.assert_allclose(selection_scores(p, cfg, logits), select,
                               rtol=1e-6, atol=1e-6)
    by_affinity = jax.lax.top_k(aff, cfg.num_experts_per_tok)[1]
    assert (jnp.sort(by_affinity, -1) != jnp.sort(idx, -1)).any(-1).sum() > 0


def test_dense_lead_stack_layout():
    """The leading dense layer is stacked apart and kept unquantized; its
    latent cache rides beside the MoE stack's; the DyMoE depth schedule
    (t_l) and the decode telemetry span the MoE layers only."""
    spec = dict(SPEC, first_k_dense_replace=2, num_hidden_layers=4)
    cfg, params, qparams = _program(spec)
    assert cfg.num_moe_layers == 2
    assert params["dense_layers"]["mlp"]["w_gate"].shape[0] == 2
    assert set(qparams) == {"layers"}
    assert qparams["layers"]["moe"]["w_gate"].high.packed.shape[0] == 2
    caches = init_decode_state(cfg, 3, SLOTS)
    assert caches["dense_layers"].k.shape == (2, 3, 1, SLOTS,
                                              cfg.kv_lora_rank)
    assert caches["layers"].v.shape == (2, 3, 1, SLOTS,
                                        cfg.qk_rope_head_dim)
    np.testing.assert_array_equal(_t_l_array(cfg),
                                  ref.critical_counts(ref.sizes(spec)))
    _, _, info = jax.jit(lambda p, q, c: decode_step(
        p, cfg, jnp.ones(3, jnp.int32), c, qparams=q, per_row_moe=True,
        live_rows=jnp.ones(3, bool), moe_capacity=3))(params, qparams,
                                                      caches)
    assert info.critical_masks.shape[0] == cfg.num_moe_layers
