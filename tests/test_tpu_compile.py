"""Ahead-of-time compiles for TPU v5e, without a chip.

The expert kernels at OLMoE-1B-7B's widths (E=64, K=2048, N=1024, group
64), and the grouped one at Qwen3-30B-A3B's (E=128, width 768), go through
the real v5e compiler here: interpret mode cannot see what
Mosaic refuses (a lane-splitting reshape, a row block that is not a whole
sublane tile), and such a refusal costs chip time to find. The compiler
also checks the expert-parallel MoE layer on a 2x2 topology: its kernels
must sit under ``shard_map`` (Mosaic kernels cannot be partitioned) and the
packed codes must never be all-gathered.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every xdist worker imports this
file. Every TPU compile test lives in this one file for the same reason.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, PartitionSpec as P, \
    SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.quant_matmul.expert_quant_matmul import \
    expert_quant_matmul_grouped_pallas, expert_quant_matmul_pallas, \
    grouped_scales_after_dot

E, K, N, GS = 64, 2048, 1024, 64      # OLMoE-1B-7B expert w_gate / w_up


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip cannot read back what the persistent cache holds
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _store(bits, sharding, e=E, n=N, k=K):
    return (_sds((e, n, k * bits // 8), jnp.uint8, sharding),
            _sds((e, k // GS, n), jnp.float32, sharding))


def _compiled_text(f, *args):
    return jax.jit(f).lower(*args).compile().as_text()


@pytest.mark.parametrize("lo_bits", [2, 0])
@pytest.mark.parametrize("cap", [1, 2, 8, 32])
def test_grouped_kernel_compiles_for_v5e(one_chip, cap, lo_bits):
    """The fused grouped kernel at every decode capacity the live_cap
    ladder produces (row blocks pad to a whole bf16 sublane tile) and at
    a prefill-sized one, with and without the low-bit region."""
    blocks = get_config("olmoe_1b_7b").dymoe
    kw = dict(cap_hi=cap, hi_bits=4, lo_bits=lo_bits, group_size=GS,
              block_m=blocks.block_m, block_n=blocks.block_n,
              block_k=blocks.block_k)
    hp, hs = _store(4, one_chip)
    x = _sds((E, cap * (2 if lo_bits else 1), K), jnp.bfloat16, one_chip)
    counts = _sds((E, 2), jnp.int32, one_chip)
    if lo_bits:
        lp, ls = _store(lo_bits, one_chip)
        text = _compiled_text(
            lambda x, hp, hs, lp, ls, c: expert_quant_matmul_grouped_pallas(
                x, hp, hs, lp, ls, c, **kw), x, hp, hs, lp, ls, counts)
    else:
        text = _compiled_text(
            lambda x, hp, hs, c: expert_quant_matmul_grouped_pallas(
                x, hp, hs, None, None, c, **kw), x, hp, hs, counts)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("cap", [1, 8, 16])
@pytest.mark.parametrize("k,n", [(2048, 768), (768, 2048)],
                         ids=["gate_up", "down"])
def test_grouped_kernel_compiles_for_v5e_at_qwen3_widths(one_chip, k, n,
                                                         cap):
    """Qwen3-30B-A3B's experts (E=128, width 768; w_down's K=768 pads to
    two K tiles) at the decode capacities: the body that applies the
    group scales after the dot is the one compiled, and the compiled
    program still names the kernel the benchmark's trace reader finds."""
    blocks = get_config("qwen3_30b_a3b").dymoe
    e = 128
    kw = dict(cap_hi=cap, hi_bits=4, lo_bits=2, group_size=GS,
              block_m=blocks.block_m, block_n=blocks.block_n,
              block_k=blocks.block_k)
    assert grouped_scales_after_dot(cap, k, group_size=GS,
                                    block_m=blocks.block_m,
                                    block_k=blocks.block_k,
                                    dtype=jnp.bfloat16)
    hp, hs = _store(4, one_chip, e=e, n=n, k=k)
    lp, ls = _store(2, one_chip, e=e, n=n, k=k)
    x = _sds((e, 2 * cap, k), jnp.bfloat16, one_chip)
    counts = _sds((e, 2), jnp.int32, one_chip)
    text = _compiled_text(
        lambda x, hp, hs, lp, ls, c: expert_quant_matmul_grouped_pallas(
            x, hp, hs, lp, ls, c, **kw), x, hp, hs, lp, ls, counts)
    assert "tpu_custom_call" in text
    assert "expert_quant_matmul_grouped_pallas" in text


@pytest.mark.parametrize("rows", [1, 80])
def test_mask_select_kernel_compiles_for_v5e(one_chip, rows):
    """The critical-mask kernel of the solo prefill and the reference
    decode: capacity 1 (decode) and 80 (a 512-token prompt)."""
    hp, hs = _store(4, one_chip)
    lp, ls = _store(2, one_chip)
    x = _sds((E, rows, K), jnp.bfloat16, one_chip)
    crit = _sds((E,), jnp.int32, one_chip)
    text = _compiled_text(
        lambda x, hp, hs, lp, ls, c: expert_quant_matmul_pallas(
            x, hp, hs, lp, ls, c, hi_bits=4, lo_bits=2, group_size=GS,
            block_m=32), x, hp, hs, lp, ls, crit)
    assert "tpu_custom_call" in text


def test_expert_parallel_moe_layer_compiles_for_v5e_2x2(topo):
    """One OLMoE MoE layer's decode dispatch over a (1, 4) mesh with the
    routed stores sharded over experts: it compiles (the kernels run
    under shard_map), and no all-gather moves packed (u8) codes."""
    from repro.kernels.quant_matmul.ops import force_impl
    from repro.models.layers.moe import moe_apply_rows, quantize_moe
    from repro.sharding.partition import param_shardings

    cfg = get_config("olmoe_1b_7b")
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    dm, dff = cfg.d_model, cfg.expert_d_ff
    p = {"wg_router": jax.ShapeDtypeStruct((dm, E), jnp.float32)}
    dense = {n: jax.ShapeDtypeStruct(s, jnp.bfloat16) for n, s in (
        ("w_gate", (E, dm, dff)), ("w_up", (E, dm, dff)),
        ("w_down", (E, dff, dm)))}
    qw = jax.eval_shape(lambda d: quantize_moe(d, cfg), dense)
    shardings = param_shardings({"moe": qw}, mesh,
                                expert_parallel=True)["moe"]
    assert all(s.spec[0] == "model" for s in jax.tree.leaves(shardings))
    qw = jax.tree.map(lambda a, s: _sds(a.shape, a.dtype, s), qw,
                      shardings)
    repl = jax.sharding.NamedSharding(mesh, P())
    p = jax.tree.map(lambda a: _sds(a.shape, a.dtype, repl), p)
    b = 4
    x = _sds((b, dm), jnp.bfloat16, repl)
    crit = _sds((b, E), jnp.bool_, repl)
    live = _sds((b,), jnp.bool_, repl)

    ep_cfg = dataclasses.replace(cfg, expert_mesh=mesh)

    def f(p, x, crit, qw, live):
        return moe_apply_rows(p, ep_cfg, x, crit, qw, live=live,
                              capacity=b)[0]

    with force_impl("pallas"):
        text = _compiled_text(f, p, x, crit, qw, live)
    assert "tpu_custom_call" in text
    gathers = [ln for ln in text.splitlines()
               if re.search(r"\ball-gather(-start)?\(", ln)]
    assert not [ln for ln in gathers if "u8[" in ln], gathers
