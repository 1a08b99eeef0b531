"""Fused dual-buffer expert kernel: parity sweep.

ONE grouped dispatch walks both packed precision regions of a combined
capacity buffer, with per-(expert, precision) live-slot watermarks making
the grid ragged over LIVE rows. Each region must compute what a plain
dequantize-then-dot of its own store computes, every row must match a
plain per-token layer reference and its own solo call bit for bit, and
dead rows cost no slots and come back exact zero. Sweeps: kernel-level
(grouped oracle vs per-region plain reference, interpret-mode Pallas
leg), layer-level (moe_apply_rows / moe_apply_prefill_rows vs the plain
reference and vs single-row calls, live raggedness 0/50/100%, capacity
shrink), and end-to-end (decode_many_batched with a live_cap on a
half-drained batch)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.rules import LintTarget, run_rules
from repro.kernels.quant_matmul.ops import expert_quant_matmul_grouped
from repro.kernels.quant_matmul.ref import quant_matmul_ref
from repro.models.config import DyMoEPolicy, ModelConfig
from repro.models.layers.moe import (_capacity, init_moe,
                                     moe_apply_prefill_rows, moe_apply_rows,
                                     quantize_moe)
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
    """The fused op's jnp oracle must be BITWISE, region by region and
    expert by expert, the plain dequantize-then-dot of that region's own
    store on that expert's region slice."""
    mp = _weights(hi, lo)
    cap = 6
    x = _combined_x(cap, cap)
    fused = np.asarray(expert_quant_matmul_grouped(
        x, mp, cap_hi=cap, impl="ref", out_dtype=jnp.float32))
    for e in range(E):
        for qt, rows in ((mp.high, slice(0, cap)), (mp.low, slice(cap, None))):
            want = quant_matmul_ref(x[e, rows], qt.packed[e], qt.scales[e],
                                    bits=qt.bits, group_size=GROUP,
                                    out_dtype=jnp.float32)
            np.testing.assert_array_equal(fused[e, rows], np.asarray(want))


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


def _plain_layer(p, cfg, qw, x, crit, valid=None):
    """Plain per-token f32 reference of the quantized MoE layer, in numpy.

    x (T, dm); crit (T, E), the mask of each token's row; valid (T,). For
    each valid token and each of its routed experts: dequantize the store
    the mask picks (zero under "4/0"), apply SwiGLU, weight by the gate.
    Returns (y (T, dm), gates (T, k), experts (T, k))."""
    logits = np.asarray(x, np.float32) @ np.asarray(p["wg_router"])
    z = np.exp(logits - logits.max(-1, keepdims=True))
    probs = z / z.sum(-1, keepdims=True)
    idx = np.argsort(-probs, axis=-1, kind="stable")[:, :cfg.num_experts_per_tok]
    gates = np.take_along_axis(probs, idx, axis=-1)
    gates = gates / gates.sum(-1, keepdims=True)
    stores = {name: [None if q is None else np.asarray(q.dequantize(jnp.float32))
                     for q in (mp.high, mp.low)] for name, mp in qw.items()}
    x, crit = np.asarray(x, np.float32), np.asarray(crit)
    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        if valid is not None and not valid[t]:
            continue
        for g, e in zip(gates[t], idx[t]):
            prec = 0 if crit[t, e] else 1
            if stores["w_gate"][prec] is None:       # "4/0": skipped
                continue
            wg, wu, wd = (stores[n][prec][e] for n in ("w_gate", "w_up",
                                                       "w_down"))
            a = x[t] @ wg
            y[t] += g * (((a / (1 + np.exp(-a))) * (x[t] @ wu)) @ wd)
    return y, gates, idx


def _assert_row_stats(stats, gates, idx, e, valid=None):
    """Per-row load / active / gate_mean of a (rows, S) token block."""
    rows = stats["active"].shape[0]
    oh = np.eye(e)[idx]                                    # (T, k, E)
    if valid is not None:
        oh = oh * np.asarray(valid)[:, None, None]
    oh = oh.reshape(rows, -1, e)
    load = oh.sum(axis=1)
    gsum = np.einsum("rje,rj->re", oh, gates.reshape(rows, -1))
    np.testing.assert_array_equal(np.asarray(stats["active"]), load > 0)
    if "load" in stats:
        np.testing.assert_array_equal(np.asarray(stats["load"]), load)
    np.testing.assert_allclose(np.asarray(stats["gate_mean"]),
                               gsum / np.maximum(load, 1.0),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("low_bits", [2, 0])
def test_rows_fused_bitwise_equals_dual(low_bits):
    """Every row of the one-dispatch decode layer equals the plain
    per-token reference at the precision its own mask picks."""
    cfg, p, qw, x, crit = _layer(low_bits)
    y, stats = moe_apply_rows(p, cfg, x, crit, qweights=qw)
    want, gates, idx = _plain_layer(p, cfg, qw, x, crit)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-5)
    _assert_row_stats(stats, gates, idx, cfg.num_experts)


@pytest.mark.parametrize("low_bits", [2, 0])
def test_rows_independent_of_batch_neighbours(low_bits):
    """Row i of an 8-row call is bitwise row i of an 8-row call whose
    other rows (tokens and masks) are different, and matches the same
    call on row i alone. The CPU's f32 matmul sums a 1-row product in
    another order than an 8-row one, so that last match is to rounding."""
    cfg, p, qw, x, crit = _layer(low_bits, seed=3)
    _, _, _, x2, crit2 = _layer(low_bits, seed=5)
    layer = jax.jit(lambda x, c: moe_apply_rows(p, cfg, x, c, qweights=qw)[0])
    y = np.asarray(layer(x, crit))
    for i in range(x.shape[0]):
        yn = layer(x2.at[i].set(x[i]), crit2.at[i].set(crit[i]))
        np.testing.assert_array_equal(y[i], np.asarray(yn)[i])
        yi = layer(x[i:i + 1], crit[i:i + 1])
        np.testing.assert_allclose(y[i], np.asarray(yi)[0],
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dead_frac", [0.0, 0.5, 1.0])
def test_rows_live_raggedness(dead_frac):
    """Live-masked run vs the all-live full-capacity run on the live
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
                           capacity=cap)
    yfull, _ = moe_apply_rows(p, cfg, x, crit, qweights=qw)
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
                              capacity=cap)
        outs.append(np.asarray(y))
    np.testing.assert_array_equal(outs[0], outs[1])

    findings = run_rules(
        LintTarget(name="test/scheduler/retrace", cfg=cfg, phase="retrace",
                   slots=b, ladder=live_cap_for),
        only=["retrace-budget"])
    assert not findings, findings


def _prefill_wave(low_bits):
    """A ragged 3-row admission wave of 6 positions, left-padded."""
    cfg = _cfg(low_bits)
    p = init_moe(cfg, jax.random.PRNGKey(10), jnp.float32)
    qw = quantize_moe(p, cfg)
    rows, s = 3, 6
    x = jax.random.normal(jax.random.PRNGKey(11), (rows * s, cfg.d_model),
                          jnp.float32)
    crit = jax.random.bernoulli(jax.random.PRNGKey(12), 0.5,
                                (rows, cfg.num_experts))
    valid = np.ones((rows, s), bool)
    valid[1, :3] = False                 # ragged: row 1 left-padded
    valid[2, :5] = False                 # row 2 nearly empty
    return cfg, p, qw, x, crit, valid


@pytest.mark.parametrize("low_bits", [2, 0])
def test_prefill_rows_fused_bitwise_equals_dual(low_bits):
    """Prefill shapes: row-local regions, scatter-max watermarks, ragged
    ``token_valid`` — every valid token equals the plain per-token
    reference at its row's precision, with nothing dropped at this
    capacity."""
    cfg, p, qw, x, crit, valid = _prefill_wave(low_bits)
    rows, s = valid.shape
    valid = valid.reshape(-1)
    y, stats = moe_apply_prefill_rows(p, cfg, x, crit, qw, rows=rows,
                                      token_valid=jnp.asarray(valid))
    want, gates, idx = _plain_layer(p, cfg, qw, x,
                                    np.repeat(np.asarray(crit), s, axis=0),
                                    valid)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-5)
    assert float(stats["dropped_frac"]) == 0.0
    _assert_row_stats(stats, gates, idx, cfg.num_experts, valid)
    # padded positions produce exact zeros
    assert not np.any(np.asarray(y)[~valid])


@pytest.mark.parametrize("low_bits", [2, 0])
def test_prefill_rows_match_single_row(low_bits):
    """Each row of a ragged 3-row wave is bitwise that row of a wave whose
    other rows (tokens, masks, padding) are different, and matches the
    ``rows=1`` call on that row's unpadded tokens with the same row
    capacity. As for decode rows, the CPU's f32 matmul sums products of
    other row counts in another order, so that last match is to
    rounding."""
    cfg, p, qw, x, crit, valid = _prefill_wave(low_bits)
    rows, s = valid.shape
    dm = cfg.d_model
    layer = jax.jit(lambda x, c, rc, tv=None: moe_apply_prefill_rows(
        p, cfg, x, c, qw, rows=c.shape[0], token_valid=tv,
        row_capacities=rc)[0])

    def wave(x, crit, valid):
        caps = jnp.asarray([_capacity(cfg, int(n)) for n in valid.sum(1)],
                           jnp.int32)
        y = layer(x, crit, caps, jnp.asarray(valid.reshape(-1)))
        return np.asarray(y).reshape(rows, s, dm), caps

    y, caps = wave(x, crit, valid)
    xr = np.asarray(x).reshape(rows, s, dm)
    x_other = np.asarray(jax.random.normal(jax.random.PRNGKey(13),
                                           (rows, s, dm), jnp.float32))
    crit_other = jax.random.bernoulli(jax.random.PRNGKey(14), 0.5,
                                      crit.shape)
    valid_other = valid[::-1].copy()
    for r in range(rows):
        xn, vn = x_other.copy(), valid_other.copy()
        xn[r], vn[r] = xr[r], valid[r]
        yn, _ = wave(jnp.asarray(xn.reshape(-1, dm)),
                     crit_other.at[r].set(crit[r]), vn)
        np.testing.assert_array_equal(y[r], yn[r])
        yr = layer(jnp.asarray(xr[r][valid[r]]), crit[r:r + 1],
                   caps[r:r + 1])
        np.testing.assert_allclose(y[r][valid[r]], np.asarray(yr),
                                   rtol=1e-5, atol=1e-6)


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
