"""Fused dual-buffer expert kernel: bit-parity sweep.

The tentpole's contract: ONE grouped dispatch walking both packed
precision regions of a combined capacity buffer — with per-(expert,
precision) live-slot watermarks making the grid ragged over LIVE rows —
is BIT-IDENTICAL to the dual-dispatch pair it replaced on every
(bit-mix, mask, raggedness) combination, and dead rows cost no slots
and come back exact zero. Sweeps: kernel-level (grouped oracle vs dual
composition, interpret-mode Pallas leg, vmap over slots), layer-level
(moe_apply_rows / moe_apply_prefill_rows fused vs dual, live raggedness
0/50/100%, capacity shrink), and end-to-end (decode_many_batched with a
live_cap on a half-drained batch)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.rules import LintTarget, run_rules
from repro.kernels.quant_matmul.ops import (expert_quant_matmul_fixed,
                                            expert_quant_matmul_grouped)
from repro.models.config import DyMoEPolicy, ModelConfig
from repro.models.layers.moe import (init_moe, moe_apply_prefill_rows,
                                     moe_apply_rows, quantize_moe)
from repro.quant import MixedPrecisionWeights
from repro.serving.scheduler import live_cap_for

E, K, N = 4, 64, 32
GROUP = 32


def _weights(hi, lo, seed=0):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((E, K, N)), jnp.float32)
    return MixedPrecisionWeights.build(w, hi, lo, GROUP)


def _combined_x(cap_hi, cap_lo, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((E, cap_hi + cap_lo, K)),
                       jnp.float32)


# ---------------------------------------------------------- kernel level


@pytest.mark.parametrize("hi,lo", [(8, 4), (4, 2), (2, 2)])
def test_grouped_oracle_bitwise_equals_dual_composition(hi, lo):
    """The fused op's jnp oracle must be BITWISE the two fixed-precision
    dispatches it fuses, run on the region slices."""
    mp = _weights(hi, lo)
    cap = 6
    x = _combined_x(cap, cap)
    fused = expert_quant_matmul_grouped(x, mp, cap_hi=cap, impl="ref",
                                        out_dtype=jnp.float32)
    y_hi = expert_quant_matmul_fixed(x[:, :cap], mp.high, impl="ref",
                                     out_dtype=jnp.float32)
    y_lo = expert_quant_matmul_fixed(x[:, cap:], mp.low, impl="ref",
                                     out_dtype=jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(fused), np.asarray(jnp.concatenate([y_hi, y_lo], axis=1)))


@pytest.mark.parametrize("hi,lo", [(8, 4), (4, 2), (4, None)])
def test_grouped_pallas_interpret_matches_oracle_ragged(hi, lo):
    """Interpret-mode Pallas leg with random per-(expert, precision)
    watermarks: skipped blocks must reproduce the oracle, which requires
    slots at/beyond the watermark to be zero (the dispatch invariant)."""
    mp = _weights(hi, lo, seed=2)
    cap = 8
    m = cap if lo is None else 2 * cap
    x = np.array(_combined_x(cap, m - cap, seed=3))
    rng = np.random.default_rng(4)
    counts = rng.integers(0, cap + 1, size=(E, 2)).astype(np.int32)
    if lo is None:
        counts[:, 1] = 0
    for e in range(E):                   # zero-fill beyond the watermarks
        x[e, counts[e, 0]:cap] = 0.0
        if lo is not None:
            x[e, cap + counts[e, 1]:] = 0.0
    x = jnp.asarray(x)
    ref = expert_quant_matmul_grouped(x, mp, cap_hi=cap, impl="ref",
                                      out_dtype=jnp.float32)
    pal = expert_quant_matmul_grouped(
        x, mp, jnp.asarray(counts), cap_hi=cap, impl="pallas",
        interpret=True, block_m=4, block_k=32,
        out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                               atol=5e-4, rtol=1e-4)
    # dead slots: exact zero out of both legs
    for e in range(E):
        assert not np.any(np.asarray(pal)[e, counts[e, 0]:cap])
        if lo is not None:
            assert not np.any(np.asarray(pal)[e, cap + counts[e, 1]:])


def test_grouped_vmap_over_slots():
    """The continuous-batching decode vmaps the per-row program over
    slots; the fused op's batch rule must keep one unpack per expert and
    stay value-correct."""
    mp = _weights(4, 2, seed=5)
    cap = 4
    xs = jnp.stack([_combined_x(cap, cap, seed=6),
                    2 * _combined_x(cap, cap, seed=6)])
    ys = jax.vmap(lambda xi: expert_quant_matmul_grouped(
        xi, mp, cap_hi=cap, impl="ref", out_dtype=jnp.float32))(xs)
    ref = expert_quant_matmul_grouped(xs[0], mp, cap_hi=cap, impl="ref",
                                      out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(ys[0]), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ys[1]), 2 * np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


# ----------------------------------------------------------- layer level


def _cfg(low_bits=2):
    return ModelConfig(
        name="s", arch_type="moe", num_layers=1, d_model=32, vocab_size=64,
        num_heads=2, num_kv_heads=2, head_dim=16, num_experts=4,
        num_experts_per_tok=2, moe_d_ff=48, capacity_factor=2.0,
        dtype="float32", remat="none",
        dymoe=DyMoEPolicy(low_bits=low_bits, group_size=16))


def _layer(low_bits=2, b=8, seed=0):
    cfg = _cfg(low_bits)
    p = init_moe(cfg, jax.random.PRNGKey(seed), jnp.float32)
    qw = quantize_moe(p, cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (b, cfg.d_model),
                          jnp.float32)
    crit = jax.random.bernoulli(jax.random.PRNGKey(seed + 2), 0.5,
                                (b, cfg.num_experts))
    return cfg, p, qw, x, crit


@pytest.mark.parametrize("low_bits", [2, 0])
def test_rows_fused_bitwise_equals_dual(low_bits):
    cfg, p, qw, x, crit = _layer(low_bits)
    yf, sf = moe_apply_rows(p, cfg, x, crit, qweights=qw, fused=True)
    yd, sd = moe_apply_rows(p, cfg, x, crit, qweights=qw, fused=False)
    np.testing.assert_array_equal(np.asarray(yf), np.asarray(yd))
    for k in sf:
        np.testing.assert_array_equal(np.asarray(sf[k]), np.asarray(sd[k]))


@pytest.mark.parametrize("dead_frac", [0.0, 0.5, 1.0])
def test_rows_live_raggedness(dead_frac):
    """Live-masked fused run vs (a) the dual path under the same mask —
    bitwise — and (b) the all-live full-capacity fused run on the live
    rows — bitwise: a row's output never depends on its dead neighbours,
    the shrunken capacity, or its slot index. Dead rows: exact zero."""
    b = 8
    cfg, p, qw, x, crit = _layer(2, b=b, seed=7)
    n_dead = int(b * dead_frac)
    live = np.ones(b, bool)
    if n_dead:
        live[np.random.default_rng(8).choice(b, n_dead, replace=False)] = 0
    live_j = jnp.asarray(live)
    n_live = max(1, int(live.sum()))
    cap = live_cap_for(n_live, b)     # the scheduler's actual ladder

    yf, _ = moe_apply_rows(p, cfg, x, crit, qweights=qw, live=live_j,
                           capacity=cap, fused=True)
    yd, _ = moe_apply_rows(p, cfg, x, crit, qweights=qw, live=live_j,
                           capacity=cap, fused=False)
    np.testing.assert_array_equal(np.asarray(yf), np.asarray(yd))

    yfull, _ = moe_apply_rows(p, cfg, x, crit, qweights=qw, fused=True)
    np.testing.assert_array_equal(np.asarray(yf)[live],
                                  np.asarray(yfull)[live])
    assert not np.any(np.asarray(yf)[~live])


def test_rows_capacity_values_bounded_retrace_grid():
    """Every power-of-two capacity the scheduler can pick yields the same
    live-row values — the shrink is invisible to tokens. The ladder
    itself is the shared ``live_cap_for`` and must satisfy the linter's
    retrace-budget rule (pow2 caps, ≤ log2(B)+1 distinct)."""
    b = 8
    cfg, p, qw, x, crit = _layer(2, b=b, seed=9)
    live = jnp.asarray([True, True, True, False, False, False, False, False])
    caps = sorted({live_cap_for(n, b) for n in range(3, b + 1)})
    assert caps == [4, 8]               # pow2 ladder >= live count (3)
    outs = []
    for cap in caps:
        y, _ = moe_apply_rows(p, cfg, x, crit, qweights=qw, live=live,
                              capacity=cap, fused=True)
        outs.append(np.asarray(y))
    np.testing.assert_array_equal(outs[0], outs[1])

    findings = run_rules(
        LintTarget(name="test/scheduler/retrace", cfg=cfg, phase="retrace",
                   slots=b, ladder=live_cap_for),
        only=["retrace-budget"])
    assert not findings, findings


@pytest.mark.parametrize("low_bits", [2, 0])
def test_prefill_rows_fused_bitwise_equals_dual(low_bits):
    """Prefill shapes: row-local regions, scatter-max watermarks, ragged
    ``token_valid`` — fused single dispatch stays bitwise the dual pair."""
    cfg = _cfg(low_bits)
    p = init_moe(cfg, jax.random.PRNGKey(10), jnp.float32)
    qw = quantize_moe(p, cfg)
    rows, s = 3, 6
    t = rows * s
    x = jax.random.normal(jax.random.PRNGKey(11), (t, cfg.d_model),
                          jnp.float32)
    crit = jax.random.bernoulli(jax.random.PRNGKey(12), 0.5,
                                (rows, cfg.num_experts))
    valid = np.ones((rows, s), bool)
    valid[1, :3] = False                 # ragged: row 1 left-padded
    valid[2, :5] = False                 # row 2 nearly empty
    valid = jnp.asarray(valid.reshape(-1))
    kw = dict(rows=rows, token_valid=valid)
    yf, sf = moe_apply_prefill_rows(p, cfg, x, crit, qw, fused=True, **kw)
    yd, sd = moe_apply_prefill_rows(p, cfg, x, crit, qw, fused=False, **kw)
    np.testing.assert_array_equal(np.asarray(yf), np.asarray(yd))
    for k in ("active", "load", "gate_mean"):
        np.testing.assert_array_equal(np.asarray(sf[k]), np.asarray(sd[k]))
    # padded positions produce exact zeros
    assert not np.any(np.asarray(yf)[~np.asarray(valid)])


# ----------------------------------------------------------- end to end


def test_decode_batched_live_cap_tokens_bitwise():
    """A half-drained batch decoded with the scheduler's shrunken
    ``live_cap`` emits BITWISE the tokens of the uncapped trace — the
    ragged fused grid and the capacity shrink are invisible to outputs."""
    from repro.models import (decode_many_batched, init_params, prefill,
                              quantize_model)

    cfg = ModelConfig(
        name="t", arch_type="moe", num_layers=2, d_model=32, vocab_size=64,
        num_heads=2, num_kv_heads=2, head_dim=16, num_experts=4,
        num_experts_per_tok=2, moe_d_ff=48, capacity_factor=4.0,
        dtype="float32", remat="none",
        dymoe=DyMoEPolicy(low_bits=2, group_size=16))
    params = init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_model(params, cfg)
    b, steps = 4, 4
    prompt = jnp.asarray(
        np.random.default_rng(13).integers(0, cfg.vocab_size, (b, 6)),
        jnp.int32)
    logits, caches, _ = prefill(params, cfg, prompt, qparams=qp,
                                cache_slots=6 + steps + 1)
    tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    done = jnp.asarray([False, False, True, True])
    kw = dict(num_steps=steps, done=done,
              n_emitted=jnp.ones((b,), jnp.int32),
              limits=jnp.full((b,), 10, jnp.int32),
              eos_tokens=jnp.full((b,), -1, jnp.int32), qparams=qp)
    t_cap, _, _, d_cap, e_cap = decode_many_batched(
        params, cfg, tok0, caches, live_cap=2, **kw)
    t_ref, _, _, d_ref, e_ref = decode_many_batched(
        params, cfg, tok0, caches, **kw)
    np.testing.assert_array_equal(np.asarray(t_cap), np.asarray(t_ref))
    np.testing.assert_array_equal(np.asarray(d_cap), np.asarray(d_ref))
    np.testing.assert_array_equal(np.asarray(e_cap), np.asarray(e_ref))
