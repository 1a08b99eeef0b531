"""Partitioning rules over the QUANTIZED stores of every shipped config.

``test_sharding.py`` exercises the dense training path; the serving tier
loads ``quantize_model`` output — nested ``w_*/{high,low}/{packed,scales}``
leaves whose layouts differ per config (group counts, value-per-byte
packing, "4/0" configs with no low store). These tests pin the contract
the cluster's expert-parallel load relies on:

  * ``param_shardings(expert_parallel=True)`` puts "model" on the E dim
    (dim -3 of the trailing dims) of EVERY routed expert leaf — bf16,
    packed and scales, both precisions — whenever E divides the axis,
    and guards down to replication (never a crash, never a wrong dim)
    when it does not.
  * the baseline (TP) rules still shard packed/scales along N.
  * ``guard_spec`` drops exactly the indivisible entries.

Everything runs over ``jax.eval_shape`` abstract trees and an
``AbstractMesh`` — full-size configs (mixtral_8x7b, qwen3_30b_a3b)
included, zero devices and zero parameter bytes needed.
"""
import re

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.models import init_params
from repro.models.model import quantize_model
from repro.quant.qtensor import QuantizedTensor
from repro.sharding.partition import guard_spec, param_shardings

MESH_N = 4
MOE_CONFIGS = [n for n in ARCH_IDS if get_config(n).is_moe]

_ROUTED = re.compile(r"/moe/w_(gate|up|down)(/|$)")
_SHARED = re.compile(r"/moe/shared_w_")


def mesh4():
    return AbstractMesh((1, MESH_N), ("data", "model"))


def _path_str(path):
    out = []
    for p in path:
        out.append(str(getattr(p, "key", getattr(p, "name", p))))
    return "/" + "/".join(out)


def abstract_qparams(cfg):
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return params, jax.eval_shape(lambda p: quantize_model(p, cfg), params)


def quantized_leaves(tree):
    """(path, leaf) pairs in flatten order — no filtering, so zipping the
    qparams tree with its (structurally identical) shardings tree stays
    aligned leaf for leaf."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        yield _path_str(path), leaf


# ----------------------------------------------------- expert-parallel


@pytest.mark.parametrize("name", MOE_CONFIGS)
def test_expert_parallel_shards_every_routed_quantized_leaf(name):
    """Every routed-expert leaf of the quantized store — packed and
    scales, high and low precision — carries "model" on its E dim (and
    nowhere else) under ``expert_parallel=True``, for every MoE config
    whose expert count divides the axis."""
    cfg = get_config(name)
    mesh = mesh4()
    _, qparams = abstract_qparams(cfg)
    shardings = param_shardings(qparams, mesh, expert_parallel=True)
    routed = 0
    for (path, leaf), (_, sh) in zip(quantized_leaves(qparams),
                                     quantized_leaves(shardings)):
        spec = tuple(sh.spec) + (None,) * (len(leaf.shape) - len(sh.spec))
        if not _ROUTED.search(path) or _SHARED.search(path):
            assert "model" not in spec or not _SHARED.search(path), path
            continue
        routed += 1
        e_dim = len(leaf.shape) - 3      # trailing (E, *, *)
        assert leaf.shape[e_dim] == cfg.num_experts, (path, leaf.shape)
        if cfg.num_experts % MESH_N == 0:
            assert spec[e_dim] == "model", \
                f"{name}: {path} {leaf.shape} E dim not sharded: {spec}"
            assert all(s is None for i, s in enumerate(spec)
                       if i != e_dim), (path, spec)
        else:
            assert all(s is None for s in spec), \
                f"{name}: {path} indivisible E must replicate: {spec}"
    # the rule really fired: gate/up/down × (packed, scales) × precisions
    assert routed >= 6, f"{name}: only {routed} routed quantized leaves"


@pytest.mark.parametrize("name", MOE_CONFIGS)
def test_expert_parallel_bf16_routed_weights(name):
    """The bf16 routed expert weights shard over E too (mixed bf16 /
    quantized deployments must agree on the layout)."""
    cfg = get_config(name)
    if cfg.num_experts % MESH_N:
        pytest.skip("indivisible E covered by the quantized test")
    mesh = mesh4()
    params, _ = abstract_qparams(cfg)
    shardings = param_shardings(params, mesh, expert_parallel=True)
    hits = 0
    for (path, leaf), (_, sh) in zip(quantized_leaves(params),
                                     quantized_leaves(shardings)):
        if _ROUTED.search(path) and not _SHARED.search(path):
            e_dim = len(leaf.shape) - 3
            assert tuple(sh.spec)[e_dim] == "model", (path, sh.spec)
            hits += 1
    assert hits >= 3     # w_gate, w_up, w_down at least


@pytest.mark.parametrize("name", MOE_CONFIGS)
def test_baseline_tp_shards_quantized_n_dim(name):
    """Without ``expert_parallel``, packed shards its N dim (-2) and
    scales its N dim (-1) — mirroring the bf16 Megatron layout — for
    every quantized leaf whose N divides the axis."""
    cfg = get_config(name)
    mesh = mesh4()
    _, qparams = abstract_qparams(cfg)
    shardings = param_shardings(qparams, mesh)
    checked = 0
    for (path, leaf), (_, sh) in zip(quantized_leaves(qparams),
                                     quantized_leaves(shardings)):
        spec = tuple(sh.spec) + (None,) * (len(leaf.shape) - len(sh.spec))
        n_dim = (len(leaf.shape) - 2 if path.endswith("/packed")
                 else len(leaf.shape) - 1 if path.endswith("/scales")
                 else None)
        if n_dim is None:
            continue
        checked += 1
        if leaf.shape[n_dim] % MESH_N == 0:
            assert spec[n_dim] == "model", (name, path, leaf.shape, spec)
        else:
            assert spec[n_dim] is None, (name, path, leaf.shape, spec)
    assert checked >= 6


# ----------------------------------------------------------- guard_spec


@pytest.mark.parametrize("shape,spec,want", [
    # packed (E, N, K/vpb): divisible E stays sharded
    ((8, 1024, 512), P("model", None, None), P("model", None, None)),
    # indivisible E (mixtral-on-16 style) drops to replication
    ((6, 1024, 512), P("model", None, None), P(None, None, None)),
    # scales (E, G, N): guard is per-entry, not all-or-nothing
    ((6, 16, 1024), P("model", None, "model"), P(None, None, "model")),
    # short spec right-padded against a longer shape
    ((8, 64, 64, 64), P("model",), P("model", None, None, None)),
])
def test_guard_spec_on_quantized_shapes(shape, spec, want):
    assert guard_spec(spec, shape, mesh4()) == want


def test_guard_spec_every_config_lowers_without_crash():
    """The whole registry's quantized stores produce legal shardings on
    the 4-way mesh — no assertion, no crash, no sharded-but-indivisible
    spec (would fail device_put at load)."""
    mesh = mesh4()
    for name in ARCH_IDS:
        cfg = get_config(name)
        if not cfg.is_moe:
            continue
        _, qparams = abstract_qparams(cfg)
        for ep in (False, True):
            shardings = param_shardings(qparams, mesh, expert_parallel=ep)
            for (path, leaf), (_, sh) in zip(quantized_leaves(qparams),
                                             quantized_leaves(shardings)):
                spec = tuple(sh.spec)
                spec += (None,) * (len(leaf.shape) - len(spec))
                for dim, ax in zip(leaf.shape, spec):
                    if ax is not None:
                        n = mesh.shape[ax] if isinstance(ax, str) else 1
                        assert dim % n == 0, (name, ep, path, leaf.shape,
                                              spec)


def test_quantized_tensor_leaves_reached_through_fields():
    """The rules see ``.../high.packed`` etc. (dataclass-field paths) —
    a QuantizedTensor leaf is never treated as one opaque leaf."""
    cfg = get_config("qwen2_moe_a2p7b")
    _, qparams = abstract_qparams(cfg)
    leaves = jax.tree_util.tree_leaves(qparams)
    assert not any(isinstance(x, QuantizedTensor) for x in leaves)
    paths = [p for p, _ in quantized_leaves(qparams)]
    assert any(p.endswith("/packed") for p in paths)
    assert any(p.endswith("/scales") for p in paths)
