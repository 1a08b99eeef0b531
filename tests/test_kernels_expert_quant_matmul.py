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
    grouped_scales_after_dot, grouped_tiling
from repro.kernels.quant_matmul.ops import expert_quant_matmul, \
    expert_quant_matmul_grouped, force_impl
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


# ------------------------------------- grouped kernel, scales after dot
# Decode shapes of OLMoE-1B-7B (w_gate/w_up K=2048 N=1024, w_down K=1024
# N=2048), Qwen3-30B-A3B (N=768; w_down K=768, one K tile: 512 does not
# divide it) and Moonlight-16B-A3B (N=1408 = 11 x 128; w_down K=1408, one
# K tile) at 16 rows a region, group 64, bk 512; the MoE layer keeps those
# two stores padded to 1024 and 1536 (``expert_store_k``), but the kernel
# takes any K. The shape-chosen N tile is the whole width N there; the
# 128-column tile is the one it replaced.

_SERVED_KN = [(2048, 1024), (1024, 2048), (2048, 768), (768, 2048),
              (2048, 1408), (1408, 2048)]
# per-expert (hi, lo) watermarks at cap 16: "mixed" holds 0, partial and
# full in each region (the 2-bit region live, as at admission prefill);
# "alternate" has live and dead hi groups in turn, the lo region dead in
# most, as at decode
_WATERMARKS = {"mixed": [[0, 16], [5, 0], [16, 9]],
               "alternate": [[16, 0], [0, 0], [7, 0], [0, 3], [16, 0]]}


def _grouped_call(x, mp, counts, cap, lo, n_block=None):
    from repro.kernels.quant_matmul.expert_quant_matmul import \
        _grouped_pallas

    mp = jax.tree.map(lambda a: a[None], mp)      # a stack of one layer
    lp, ls = (None, None) if lo is None else (mp.low.packed, mp.low.scales)
    f = jax.jit(lambda *a: _grouped_pallas(
        *a, cap_hi=cap, hi_bits=4, lo_bits=lo or 0, group_size=64,
        block_m=32, block_k=512, interpret=True, out_dtype=jnp.float32,
        n_block=n_block))
    return np.asarray(f(x, mp.high.packed, mp.high.scales, lp, ls,
                        jnp.asarray(counts), jnp.int32(0)))


@pytest.mark.parametrize("pattern", sorted(_WATERMARKS))
@pytest.mark.parametrize("lo", [2, None], ids=["4/2", "4/0"])
@pytest.mark.parametrize("k,n", _SERVED_KN)
def test_grouped_scale_after_dot_matches_oracle(k, n, lo, pattern):
    """bf16 codes on the MXU with the f32 group scales applied to the
    per-group partial sums match the dequantize-first f32 oracle up to f32
    summation order. The whole-width N tile the wrapper chooses gives the
    128-column tile's output bit for bit: K is tiled alike, so every
    output sums the same partial products in the same order. Where 512
    does not divide K (``w_down`` of Qwen3 and Moonlight), K is one tile,
    read unpadded."""
    cap, gs = 16, 64
    counts = np.array(_WATERMARKS[pattern], np.int32)
    e = counts.shape[0]
    rng = np.random.default_rng(k + n)
    w = jnp.asarray(rng.standard_normal((e, k, n)) / np.sqrt(k),
                    jnp.float32)
    mp = MixedPrecisionWeights.build(w, 4, lo, gs)
    m = cap if lo is None else 2 * cap
    if lo is None:
        counts[:, 1] = 0
    x = rng.standard_normal((e, m, k)).astype(np.float32)
    for ei, (c_hi, c_lo) in enumerate(counts):   # zero beyond watermarks
        x[ei, c_hi:cap] = 0.0
        x[ei, cap + c_lo:] = 0.0
    x = jnp.asarray(x, jnp.bfloat16)
    lp, ls = (None, None) if lo is None else (mp.low.packed, mp.low.scales)
    assert _scale_after_dot(16, 512, gs, x.dtype)
    assert grouped_tiling(e, cap, 0 if lo is None else cap, n, k, hi_bits=4,
                          lo_bits=lo or 0, group_size=gs, block_m=32,
                          block_k=512, dtype=x.dtype).bn == n
    ref = np.asarray(expert_quant_matmul_grouped_ref(
        x, mp.high.packed, mp.high.scales, lp, ls, cap_hi=cap, hi_bits=4,
        lo_bits=lo or 0, group_size=gs, out_dtype=jnp.float32))
    pal = np.asarray(expert_quant_matmul_grouped(
        x, mp, jnp.asarray(counts), cap_hi=cap, impl="pallas",
        interpret=True, block_m=32, block_k=512, out_dtype=jnp.float32))
    np.testing.assert_array_equal(
        pal, _grouped_call(x, mp, counts, cap, lo, n_block=128))
    scale = np.abs(ref).max()
    np.testing.assert_allclose(pal, ref, rtol=1e-5, atol=1e-5 * scale)
    for ei, (c_hi, c_lo) in enumerate(counts):   # dead rows: exact zero
        assert not pal[ei, c_hi:cap].any() and not pal[ei, cap + c_lo:].any()


# w_down's K of each served configuration, scaled down by 4 with the K
# tile (512 -> 128): OLMoE's 1024 keeps two tiles; Qwen3's 768 and
# Moonlight's 1408 (192, 352) are Ks the tile does not divide: one tile
_LAYER_KS = {"olmoe": 256, "qwen3": 192, "moonlight": 352}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("lo", [2, None], ids=["4/2", "4/0"])
@pytest.mark.parametrize("shape", sorted(_LAYER_KS))
def test_grouped_reads_layer_of_stack_in_place(shape, lo, dtype):
    """Given the whole (L, E, ...) store and a layer index, the grouped
    kernel equals its call on that layer's slice bit for bit, at every
    layer of a 3-layer stack, with dead and partly live groups in both
    regions, in the body that scales after the dot (bf16) and the one
    that dequantizes first (f32); and it reads that layer's weights (the
    oracle on the slice agrees)."""
    k, n, e, cap, gs, layers = _LAYER_KS[shape], 64, 4, 8, 32, 3
    rng = np.random.default_rng(k)
    w = jnp.asarray(rng.standard_normal((layers, e, k, n)) / np.sqrt(k),
                    jnp.float32)
    mp = MixedPrecisionWeights.build(w, 4, lo, gs)
    counts = np.array([[cap, 0], [0, 5], [3, cap], [0, 0]], np.int32)
    m = cap if lo is None else 2 * cap
    if lo is None:
        counts[:, 1] = 0
    x = rng.standard_normal((e, m, k)).astype(np.float32)
    for ei, (c_hi, c_lo) in enumerate(counts):   # zero beyond watermarks
        x[ei, c_hi:cap] = 0.0
        x[ei, cap + c_lo:] = 0.0
    x, counts = jnp.asarray(x, dtype), jnp.asarray(counts)
    tiling = grouped_tiling(e, cap, 0 if lo is None else cap, n, k,
                            hi_bits=4, lo_bits=lo or 0, group_size=gs,
                            block_m=16, block_k=128, dtype=dtype)
    assert tiling.bk == (128 if shape == "olmoe" else k), tiling
    assert tiling.scale_after_dot == (dtype == jnp.bfloat16), tiling
    kw = dict(cap_hi=cap, impl="pallas", interpret=True, block_m=16,
              block_k=128, out_dtype=jnp.float32)
    stacked_call = jax.jit(lambda x, w, c, l: expert_quant_matmul_grouped(
        x, w, c, layer=l, **kw))
    for layer in range(layers):
        one = jax.tree.map(lambda a: a[layer], mp)
        got = np.asarray(stacked_call(x, mp, counts, jnp.int32(layer)))
        np.testing.assert_array_equal(
            got, np.asarray(expert_quant_matmul_grouped(x, one, counts,
                                                        **kw)))
        ref = np.asarray(expert_quant_matmul_grouped(
            x, one, cap_hi=cap, impl="ref", out_dtype=jnp.float32))
        np.testing.assert_allclose(got, ref, rtol=1e-5,
                                   atol=1e-5 * np.abs(ref).max())


# grid steps a call of w_gate, w_up and w_down at decode (16 rows a region,
# 8 for Moonlight), and the MoE layers of each cell's depth; w_down of
# Qwen3 (K 768) and Moonlight (K 1408) is stored at K 1024 and 1536
# (``expert_store_k``), two and three K tiles
_DECODE_STEPS = {"olmoe_1b_7b": ((512, 512, 256), 16, 16),
                 "qwen3_30b_a3b": ((1024, 1024, 512), 16, 16),
                 "moonlight_16b_a3b": ((512, 512, 384), 8, 15)}
# each cell's largest admission wave: rows, longest prompt
_LARGEST_WAVE = {"olmoe_1b_7b": (16, 256), "qwen3_30b_a3b": (16, 256),
                 "moonlight_16b_a3b": (8, 2048)}


@pytest.mark.parametrize("phase", ["decode", "wave"])
@pytest.mark.parametrize("name", sorted(_DECODE_STEPS))
def test_grouped_tile_and_grid_by_config(name, phase):
    """The N tile is chosen from the shapes: N itself or a multiple of 128
    that divides N; the K tile is ``block_k``, which divides the K every
    store is kept at. The grid the wrapper builds over a layer-stacked
    store is the one :func:`grouped_tiling` reports, at decode the table
    of steps a call (OLMoE 20,480 a decode step of 16 layers, from
    196,608 at 128 columns), and the call passes the vmem-footprint
    lint."""
    from repro.analysis import estimate_pallas_vmem, iter_eqns
    from repro.analysis.rules import LintTarget, run_rules
    from repro.configs import get_config
    from repro.models.layers.moe import _capacity, expert_store_k

    cfg = get_config(name)
    pol, e = cfg.dymoe, cfg.num_experts
    steps, rows, layers = _DECODE_STEPS[name]
    if phase == "wave":
        n_rows, prompt = _LARGEST_WAVE[name]
        rows = n_rows * _capacity(cfg, prompt)
    dm, dff = cfg.d_model, cfg.expert_d_ff
    got = []
    for k, n in ((dm, dff), (dm, dff), (dff, dm)):
        k = expert_store_k(k, pol.group_size)
        t = grouped_tiling(e, rows, rows, n, k, hi_bits=pol.high_bits,
                           lo_bits=pol.low_bits, group_size=pol.group_size,
                           block_m=pol.block_m, block_k=pol.block_k,
                           dtype=cfg.dtype)
        assert t.bn == n or (t.bn % 128 == 0 and n % t.bn == 0), t
        assert t.bn == n or not t.scale_after_dot, t
        assert t.bk == pol.block_k and k % t.bk == 0, t
        got.append(int(np.prod(t.grid)))

        sds = jax.ShapeDtypeStruct
        args = (sds((e, 2 * rows, k), jnp.bfloat16),
                sds((layers, e, n, k * pol.high_bits // 8), jnp.uint8),
                sds((layers, e, k // pol.group_size, n), jnp.float32),
                sds((layers, e, n, k * pol.low_bits // 8), jnp.uint8),
                sds((layers, e, k // pol.group_size, n), jnp.float32),
                sds((e, 2), jnp.int32), sds((), jnp.int32))
        jaxpr = jax.make_jaxpr(
            lambda *a: expert_quant_matmul_grouped_pallas(
                *a, cap_hi=rows, hi_bits=pol.high_bits,
                lo_bits=pol.low_bits, group_size=pol.group_size,
                block_m=pol.block_m, block_k=pol.block_k))(*args)
        (site,) = [s for s in iter_eqns(jaxpr, into_kernels=False)
                   if s.eqn.primitive.name == "pallas_call"]
        assert estimate_pallas_vmem(site.eqn).grid == t.grid
        assert not run_rules(LintTarget(name=f"{name}/{phase}", cfg=cfg,
                                        phase="decode_chunk", jaxpr=jaxpr),
                             only=["vmem-footprint"])
    if phase == "decode":
        assert tuple(got) == steps
        assert sum(got) * layers == {"olmoe_1b_7b": 20480,
                                     "qwen3_30b_a3b": 40960,
                                     "moonlight_16b_a3b": 21120}[name]


def _fetches(maps, grid, table):
    """Walk ``grid`` in grid order: per operand, the block indices the
    pipeline copies in (the first step's, then every change)."""
    out = [[] for _ in maps]
    for g, i, j, kk in np.ndindex(*grid):
        for f, seen in zip(maps, out):
            blk = tuple(int(v) for v in f(g, i, j, kk, table))
            if not seen or seen[-1] != blk:
                seen.append(blk)
    return out


@pytest.mark.parametrize("case", ["decode", "wave"])
def test_grouped_dead_steps_fetch_nothing(case):
    """Dead steps, and the precision a group does not read, repeat the
    block their buffer holds: the hi and lo weight buffers copy in only
    the blocks of live steps, each once, plus at most one first block.
    "decode": one row block, one N tile, four K tiles, no 2-bit group
    live, live and dead hi groups mixed; "wave": row blocks beyond a
    group's watermark and several N and K tiles. Every weight block is
    read at the table's layer, live or held."""
    from repro.kernels.quant_matmul.expert_quant_matmul import \
        grouped_index_maps, grouped_tables

    rng = np.random.default_rng(7)
    if case == "decode":
        e, cap, bm, nj, nk = 16, 16, 16, 1, 4
        counts = np.zeros((e, 2), np.int32)
        counts[rng.choice(e, 9, replace=False), 0] = cap
    else:
        e, cap, bm, nj, nk = 6, 48, 16, 2, 3
        counts = rng.integers(0, cap + 1, (e, 2)).astype(np.int32)
        counts[1] = 0
    nb_cap = -(-cap // bm)
    grid = (2 * e, nb_cap, nj, nk)
    layer = 2
    table = grouped_tables(jnp.asarray(counts), cap, cap, bm, layer)
    x_map, hc, hs, lc, ls, _ = grouped_index_maps(grid, 2)
    x_f, hc_f, hs_f, lc_f, ls_f = _fetches((x_map, hc, hs, lc, ls), grid,
                                           table)

    def live_blocks(region, order):
        out = []
        for g, i, j, kk in np.ndindex(*grid):
            nb = -(-int(counts[g // 2, g % 2]) // bm)
            if g % 2 == region and i < nb:
                blk = ((layer, g // 2, j, kk) if order == "jk"
                       else (layer, g // 2, kk, j))
                if not out or out[-1] != blk:
                    out.append(blk)
        return out

    for region, codes, scales in ((0, hc_f, hs_f), (1, lc_f, ls_f)):
        live = live_blocks(region, "jk")
        assert codes in (live, codes[:1] + live), (region, codes, live)
        assert scales in (live_blocks(region, "kj"),
                          scales[:1] + live_blocks(region, "kj"))
    live_x = sum(-(-int(c) // bm) for c in counts.reshape(-1)) * nj * nk
    assert len(x_f) <= live_x + 1
    if case == "decode":
        assert lc_f == [(layer, 0, 0, 0)] and ls_f == [(layer, 0, 0, 0)]
        assert len(hc_f) <= 9 * nk + 1


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
    """The structural contract: the row-local MoE forward launches ONE
    grouped expert kernel per expert matmul (gate/up/down = 3 per layer),
    both precision regions inside each. "4/0" runs the same 3
    single-region launches."""
    from repro.models.layers.moe import moe_apply_rows

    cfg = _rows_cfg(low_bits)
    p = init_moe(cfg, jax.random.PRNGKey(0), jnp.float32)
    qw = quantize_moe(p, cfg)
    b = 8
    x = jax.random.normal(jax.random.PRNGKey(1), (b, cfg.d_model),
                          jnp.float32)
    crit = jax.random.bernoulli(jax.random.PRNGKey(2),
                                0.5, (b, cfg.num_experts))

    with force_impl("pallas"):
        jaxpr = jax.make_jaxpr(
            lambda xi: moe_apply_rows(p, cfg, xi, crit, qweights=qw)[0])(x)
    assert count_pallas_calls(jaxpr) == 3


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
