"""Grouped expert quant-matmul: Pallas kernel vs jnp oracle vs the
materializing escape hatch, plus the structural guarantee the tentpole is
about — the quantized MoE forward never materializes a dense
(E, dm, dff) dequantized weight tensor."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import count_pallas_calls, intermediate_avals
from repro.analysis.rules import FLOAT_DTYPES
from repro.kernels.quant_matmul.expert_quant_matmul import \
    _scale_after_dot, expert_quant_matmul_grouped_pallas, \
    grouped_scales_after_dot
from repro.kernels.quant_matmul.ops import expert_quant_matmul, force_impl
from repro.kernels.quant_matmul.ref import expert_quant_matmul_grouped_ref
from repro.models.config import DyMoEPolicy, ModelConfig
from repro.models.layers.moe import init_moe, moe_apply, quantize_moe
from repro.quant import MixedPrecisionWeights, mixed_precision_matmul


def _build(e, m, k, n, hi, lo, group, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((e, m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((e, k, n)), jnp.float32)
    mp = MixedPrecisionWeights.build(w, hi, lo, group)
    return x, mp


def _match(x, mp, crit, bm=8, bn=16, bk=64):
    ref = expert_quant_matmul(x, mp, crit, impl="ref", out_dtype=jnp.float32)
    pal = expert_quant_matmul(x, mp, crit, impl="pallas", interpret=True,
                              block_m=bm, block_n=bn, block_k=bk,
                              out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                               atol=5e-4, rtol=1e-4)
    return ref


@pytest.mark.parametrize("hi,lo", [(8, 4), (8, 2), (4, 2), (2, 2)])
def test_bit_pairs_mixed_mask(hi, lo):
    x, mp = _build(4, 16, 128, 32, hi, lo, 32)
    crit = jnp.asarray([True, False, False, True])
    _match(x, mp, crit)


@pytest.mark.parametrize("mask", [[1, 1, 1, 1], [0, 0, 0, 0], [1, 0, 1, 0]])
def test_critical_mask_patterns(mask):
    x, mp = _build(4, 16, 128, 32, 4, 2, 32)
    _match(x, mp, jnp.asarray(mask, bool))


def test_low_none_skips_to_zero():
    """"4/0": sub-critical experts contribute exactly zero, in the kernel
    and in the oracle, without their codes ever being unpacked."""
    x, mp = _build(4, 16, 128, 32, 4, None, 32)
    crit = jnp.asarray([True, False, True, False])
    ref = _match(x, mp, crit)
    assert not np.any(np.asarray(ref)[1]) and not np.any(np.asarray(ref)[3])
    assert np.any(np.asarray(ref)[0])


def test_matches_materializing_escape_hatch():
    x, mp = _build(4, 16, 128, 32, 4, 2, 32)
    crit = jnp.asarray([True, False, True, True])
    ref = expert_quant_matmul(x, mp, crit, impl="ref", out_dtype=jnp.float32)
    mat = mixed_precision_matmul(x, mp, crit, materialize=True,
                                 out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(mat),
                               atol=5e-4, rtol=1e-4)


@pytest.mark.parametrize("e,m,k,n", [(3, 13, 192, 24), (2, 5, 64, 17),
                                     (5, 8, 320, 40)])
def test_non_divisible_edge_shapes(e, m, k, n):
    x, mp = _build(e, m, k, n, 4, 2, 32, seed=e)
    crit = jnp.asarray(np.arange(e) % 2 == 0)
    _match(x, mp, crit)


def test_dense_one_expert_path():
    """Scalar-critical dense weights run through the same grouped kernel as
    a 1-expert group (the MLP / SSM projection call sites)."""
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.standard_normal((64, 48)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((5, 7, 64)), jnp.float32)
    mp = MixedPrecisionWeights.build(w, 4, 2, 32)
    for crit in (True, False):
        y = mixed_precision_matmul(x, mp, crit, skip_to_zero=False,
                                   out_dtype=jnp.float32)
        ref = mixed_precision_matmul(x, mp, crit, skip_to_zero=False,
                                     materialize=True,
                                     out_dtype=jnp.float32)
        assert y.shape == (5, 7, 48)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   atol=5e-4, rtol=1e-4)


def test_vmaps_for_sharded_dispatch():
    """moe_apply_sharded vmaps the quantized expert FFN over data shards."""
    x, mp = _build(4, 8, 64, 16, 4, 2, 32)
    crit = jnp.asarray([True, False, True, False])
    xs = jnp.stack([x, x * 2])
    ys = jax.vmap(lambda xi: expert_quant_matmul(
        xi, mp, crit, impl="ref", out_dtype=jnp.float32))(xs)
    ref = expert_quant_matmul(x, mp, crit, impl="ref",
                              out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(ys[0]), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ys[1]), 2 * np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


# ------------------------------------- grouped kernel, scales after dot
# Decode shapes of OLMoE-1B-7B (w_gate/w_up K=2048 N=1024, w_down K=1024
# N=2048) and Qwen3-30B-A3B (N=768; w_down K=768, padded to two K tiles)
# at 16 rows a region, group 64, the serving tiles (bn 128, bk 512).


@pytest.mark.parametrize("lo", [2, None], ids=["4/2", "4/0"])
@pytest.mark.parametrize("k,n", [(2048, 1024), (1024, 2048), (2048, 768),
                                 (768, 2048)])
def test_grouped_scale_after_dot_matches_oracle(k, n, lo):
    """bf16 codes on the MXU with the f32 group scales applied to the
    per-group partial sums match the dequantize-first f32 oracle up to f32
    summation order. Three experts hold watermarks 0, partial and full in
    each region; the 2-bit region is live, as at admission prefill."""
    cap, e, gs = 16, 3, 64
    rng = np.random.default_rng(k + n)
    w = jnp.asarray(rng.standard_normal((e, k, n)) / np.sqrt(k),
                    jnp.float32)
    mp = MixedPrecisionWeights.build(w, 4, lo, gs)
    m = cap if lo is None else 2 * cap
    counts = np.array([[0, cap], [5, 0], [cap, 9]], np.int32)
    if lo is None:
        counts[:, 1] = 0
    x = rng.standard_normal((e, m, k)).astype(np.float32)
    for ei, (c_hi, c_lo) in enumerate(counts):   # zero beyond watermarks
        x[ei, c_hi:cap] = 0.0
        x[ei, cap + c_lo:] = 0.0
    x = jnp.asarray(x, jnp.bfloat16)
    lp, ls = (None, None) if lo is None else (mp.low.packed, mp.low.scales)
    assert _scale_after_dot(16, 512, gs, x.dtype)
    ref = np.asarray(expert_quant_matmul_grouped_ref(
        x, mp.high.packed, mp.high.scales, lp, ls, cap_hi=cap, hi_bits=4,
        lo_bits=lo or 0, group_size=gs, out_dtype=jnp.float32))
    pal = np.asarray(expert_quant_matmul_grouped_pallas(
        x, mp.high.packed, mp.high.scales, lp, ls, jnp.asarray(counts),
        cap_hi=cap, hi_bits=4, lo_bits=lo or 0, group_size=gs, block_m=32,
        block_n=128, block_k=512, interpret=True, out_dtype=jnp.float32))
    scale = np.abs(ref).max()
    np.testing.assert_allclose(pal, ref, rtol=1e-5, atol=1e-5 * scale)
    assert not pal[0, :cap].any() and not pal[1, cap:].any()


def test_scale_after_dot_is_chosen_by_shape():
    """Decode blocks of both served configurations take the new body at
    every live_cap of the ladder, and so do admission waves of a few rows;
    128-row blocks (an 8-row Qwen3 wave) and f32 activations keep the
    dequantize-first body. The choice reads shapes and dtype alone."""
    from repro.configs import get_config

    for name in ("olmoe_1b_7b", "qwen3_30b_a3b"):
        pol = get_config(name).dymoe
        kw = dict(group_size=64, block_m=pol.block_m, block_k=pol.block_k)
        for cap in (1, 2, 4, 8, 16):
            for k in (2048, 1024, 768):
                assert grouped_scales_after_dot(cap, k, dtype=jnp.bfloat16,
                                                **kw)
                assert not grouped_scales_after_dot(cap, k,
                                                    dtype=jnp.float32, **kw)
    assert grouped_scales_after_dot(80, 2048, group_size=64, block_m=32,
                                    block_k=512, dtype=jnp.bfloat16)
    assert not grouped_scales_after_dot(160, 2048, group_size=64,
                                        block_m=128, block_k=512,
                                        dtype=jnp.bfloat16)
    # a function of (bm, bk, group_size, dtype): K beyond one tile and the
    # rows beyond one block do not enter
    assert _scale_after_dot(16, 512, 64, jnp.bfloat16) == \
        grouped_scales_after_dot(3, 4096, group_size=64, block_m=16,
                                 block_k=512, dtype=jnp.bfloat16)
    assert not _scale_after_dot(128, 512, 64, jnp.bfloat16)


# ------------------------------------------------ structural guarantee
# The jaxpr traversal lives in repro.analysis (walker.py) — these tests
# and the invariant linter share it, so the gates can never drift apart.


@pytest.mark.parametrize("low_bits", [2, 0])
def test_no_dense_expert_weight_intermediate(low_bits):
    """The quantized MoE forward must carry the packed representation into
    the GEMM: no float (E, dm, dff)/(E, dff, dm) dequantized weight may
    appear anywhere in the jaxpr (the old path materialized BOTH precision
    variants dense — ~2x the bytes of an unquantized baseline)."""
    cfg = ModelConfig(
        name="s", arch_type="moe", num_layers=1, d_model=32, vocab_size=64,
        num_heads=2, num_kv_heads=2, head_dim=16, num_experts=4,
        num_experts_per_tok=2, moe_d_ff=48, capacity_factor=2.0,
        dtype="float32", remat="none",
        dymoe=DyMoEPolicy(low_bits=low_bits, group_size=16))
    p = init_moe(cfg, jax.random.PRNGKey(0), jnp.float32)
    qw = quantize_moe(p, cfg)
    crit = jnp.asarray([True, False, True, False])
    x = jax.random.normal(jax.random.PRNGKey(1), (16, cfg.d_model),
                          jnp.float32)

    jaxpr = jax.make_jaxpr(
        lambda xi: moe_apply(p, cfg, xi, critical_mask=crit,
                             qweights=qw)[0])(x)
    e, dm, dff = cfg.num_experts, cfg.d_model, cfg.expert_d_ff
    forbidden = {(e, dm, dff), (e, dff, dm)}
    bad = [a for a in intermediate_avals(jaxpr)
           if getattr(a, "shape", None) in forbidden
           and getattr(a, "dtype", None) in FLOAT_DTYPES]
    assert not bad, f"dense dequantized expert weights materialized: {bad}"


def _rows_cfg(low_bits=2):
    return ModelConfig(
        name="s", arch_type="moe", num_layers=1, d_model=32, vocab_size=64,
        num_heads=2, num_kv_heads=2, head_dim=16, num_experts=4,
        num_experts_per_tok=2, moe_d_ff=48, capacity_factor=2.0,
        dtype="float32", remat="none",
        dymoe=DyMoEPolicy(low_bits=low_bits, group_size=16))


@pytest.mark.parametrize("low_bits", [2, 0])
def test_fused_rows_single_dispatch_per_matmul(low_bits):
    """The tentpole's structural contract: the fused row-local MoE forward
    launches ONE grouped expert kernel per expert matmul (gate/up/down =
    3 per layer) — the dual-dispatch path launched 6 (2 precision buffers
    x 3 matmuls). "4/0" runs the same 3 single-region launches."""
    from repro.models.layers.moe import moe_apply_rows

    cfg = _rows_cfg(low_bits)
    p = init_moe(cfg, jax.random.PRNGKey(0), jnp.float32)
    qw = quantize_moe(p, cfg)
    b = 8
    x = jax.random.normal(jax.random.PRNGKey(1), (b, cfg.d_model),
                          jnp.float32)
    crit = jax.random.bernoulli(jax.random.PRNGKey(2),
                                0.5, (b, cfg.num_experts))

    def run(fused):
        with force_impl("pallas"):
            return jax.make_jaxpr(
                lambda xi: moe_apply_rows(p, cfg, xi, crit, qweights=qw,
                                          fused=fused)[0])(x)

    assert count_pallas_calls(run(True)) == 3
    dual = 3 if low_bits == 0 else 6
    assert count_pallas_calls(run(False)) == dual


def test_decode_step_fused_dispatch_and_no_dense_weight():
    """Decode-path extension of the structural gate: one fused grouped
    kernel call per expert matmul in the traced per-row decode step (the
    layer scan body traces once), and no dense dequantized (E, dm, dff)
    weight anywhere in the jaxpr."""
    from repro.models import (decode_step, init_params, prefill,
                              quantize_model)

    cfg = ModelConfig(
        name="t", arch_type="moe", num_layers=2, d_model=32, vocab_size=64,
        num_heads=2, num_kv_heads=2, head_dim=16, num_experts=4,
        num_experts_per_tok=2, moe_d_ff=48, capacity_factor=2.0,
        dtype="float32", remat="none",
        dymoe=DyMoEPolicy(low_bits=2, group_size=16))
    params = init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_model(params, cfg)
    prompt = jnp.ones((2, 4), jnp.int32)
    logits, caches, _ = prefill(params, cfg, prompt, qparams=qp,
                                cache_slots=8)
    tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # force Pallas AFTER prefill ran (tracing never lowers, so the pallas
    # path is safe to trace on CPU; running it is not)
    with force_impl("pallas"):
        jaxpr = jax.make_jaxpr(
            lambda t, c: decode_step(params, cfg, t, c, qparams=qp,
                                     per_row_moe=True)[0])(tok0, caches)
    assert count_pallas_calls(jaxpr) == 3

    e, dm, dff = cfg.num_experts, cfg.d_model, cfg.expert_d_ff
    forbidden = {(e, dm, dff), (e, dff, dm)}
    bad = [a for a in intermediate_avals(jaxpr)
           if getattr(a, "shape", None) in forbidden
           and getattr(a, "dtype", None) in FLOAT_DTYPES]
    assert not bad, f"dense dequantized expert weights materialized: {bad}"


def test_unquantized_path_unchanged():
    """Without a critical mask the full-precision einsum path still runs
    (training) — sanity that the rewire didn't touch it."""
    cfg = ModelConfig(
        name="s", arch_type="moe", num_layers=1, d_model=32, vocab_size=64,
        num_heads=2, num_kv_heads=2, head_dim=16, num_experts=4,
        num_experts_per_tok=2, moe_d_ff=48, capacity_factor=2.0,
        dtype="float32", remat="none")
    p = init_moe(cfg, jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, cfg.d_model),
                          jnp.float32)
    y, stats = moe_apply(p, cfg, x)
    assert y.shape == x.shape
    assert bool(jnp.isfinite(y).all())
