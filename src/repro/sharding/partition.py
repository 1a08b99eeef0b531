"""Partitioning rules: parameter-path → PartitionSpec, with divisibility
guards so one rule set covers all ten architectures.

Baseline layout (see DESIGN.md §6):
  * batch ("pod","data"); tensor/model parallel "model".
  * Attention projections column/row sharded over "model" (works for every
    arch because head_dim (64/128) keeps h·d divisible by 16 even when the
    head count is not, e.g. phi3's 40 heads).
  * Dense FFN Megatron column/row.
  * MoE experts: tensor-parallel *within* each expert (d_ff over "model") as
    the universal baseline — expert-parallel ("model" over E) is available
    via ``expert_parallel=True`` for archs whose expert count divides the
    axis (olmoe 64, qwen3-30b-a3b 128). Expert parallelism shards the
    routed experts only and replicates every other weight and the KV
    state: the expert kernels run under ``shard_map`` (see
    ``models.layers.moe._over_local_experts``), and the rest computes as
    on one device.
  * KV caches: batch over ("pod","data"), sequence slots over "model"
    (flash-decode style sharded-KV, avoids the kv_heads<16 GQA wall).
  * Quantized tensors: packed/scales sharded along their N dim, mirroring
    the bf16 layout.

Any rule whose dimension does not divide the mesh axis degrades to
replication on that dimension (guarded), so every (arch × mesh) lowers.
"""
from __future__ import annotations

import re
from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["param_shardings", "batch_spec", "cache_shardings", "shard_tree",
           "guard_spec"]

MODEL_AXIS = "model"


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def guard_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop spec entries whose dim is not divisible by the axis size."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, axes in zip(shape, entries):
        if axes is not None and dim % _axis_size(mesh, axes) != 0:
            axes = None
        out.append(axes)
    return P(*out)


def batch_spec(mesh: Mesh):
    """Composite batch axes present in the mesh ('pod' only in multi-pod)."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    return axes if len(axes) > 1 else (axes[0] if axes else None)


# --------------------------------------------------------------- param rules
#
# Rules are written on the TRAILING dims of each weight and right-aligned to
# the actual rank, so the same rule covers both per-layer and stacked
# (leading-L scan) layouts: e.g. wq rule (None, "model") applied to
# (L, dm, h·d) yields P(None, None, "model").

# (path regex, trailing-dim spec). Most-specific first.
_RULES = [
    # quantized stores: packed (.., N, K/vpb) / scales (.., G, N)
    (r"\.packed$", (MODEL_AXIS, None)),
    (r"\.scales$", (None, MODEL_AXIS)),
    # embeddings / unembedding
    (r"(^|/)embed$", (None, MODEL_AXIS)),
    (r"(^|/)lm_head$", (MODEL_AXIS, None)),
    # attention
    (r"/attn/w[qkv]$", (None, MODEL_AXIS)),
    (r"/attn/wo$", (MODEL_AXIS, None)),
    (r"/attn/b[qkv]$", (MODEL_AXIS,)),
    # dense mlp
    (r"/mlp/w_(gate|up)$", (None, MODEL_AXIS)),
    (r"/mlp/w_down$", (MODEL_AXIS, None)),
    # moe — router replicated; experts TP over d_ff (baseline)
    (r"/moe/wg_router$", (None, None)),
    (r"/moe/(shared_)?w_(gate|up)$", (None, None, MODEL_AXIS)),
    (r"/moe/(shared_)?w_down$", (None, MODEL_AXIS, None)),
    # mamba
    (r"/ssm/in_proj$", (None, MODEL_AXIS)),
    (r"/ssm/out_proj$", (MODEL_AXIS, None)),
    (r"/ssm/conv_w$", (MODEL_AXIS, None)),
    (r"/ssm/conv_b$", (MODEL_AXIS,)),
    (r"/ssm/x_proj$", (MODEL_AXIS, None)),
    (r"/ssm/dt_proj$", (None, MODEL_AXIS)),
    (r"/ssm/(dt_bias|d_skip)$", (MODEL_AXIS,)),
    (r"/ssm/a_log$", (MODEL_AXIS, None)),
    (r"/ssm/gate_norm/scale$", (MODEL_AXIS,)),
]

_EP_RULES = [
    # expert-parallel override: routed expert weights sharded over E.
    # Trailing-dims rules: bf16 (E, K, N); packed (E, N, K/vpb);
    # scales (E, G, N) — E is dim -3 in all three. The quantized store
    # nests a precision level under each weight
    # (``w_gate/{high,low}/{packed,scales}``), so the optional
    # ``/(high|low)`` component must be matched or every quantized leaf
    # silently falls through to the intra-expert TP rules below — caught
    # by test_sharding_quantized.py over every shipped config.
    (r"/moe/w_(gate|up|down)(/(high|low))?(\.(packed|scales))?$",
     (MODEL_AXIS, None, None)),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/" + "/".join(parts)


def _align(rule: Tuple, shape: Tuple[int, ...], lead_pad: int) -> P:
    """Right-align a trailing-dims rule to ``shape``, forcing the first
    ``lead_pad`` dims (the stacked-layer L dim) to None. Rules longer than
    the remaining rank keep their trailing entries."""
    nd = len(shape)
    body = nd - lead_pad
    rule = tuple(rule)[-body:] if body < len(rule) else tuple(rule)
    entries = [None] * (nd - len(rule)) + list(rule)
    return P(*entries)


def _spec_for(path_s: str, shape, mesh: Mesh, expert_parallel: bool) -> P:
    # "layers/" anywhere (params, or mu/nu inside optimizer state; the
    # leading dense stack's "dense_layers/" too) marks the stacked-layer
    # layout with a leading L dim
    lead_pad = 1 if "layers/" in path_s else 0
    if expert_parallel:
        # pure expert parallelism: only the routed experts are split; the
        # rest is replicated, so every device runs attention, router and
        # head on the whole batch and only expert outputs cross chips
        for pat, rule in _EP_RULES:
            if re.search(pat, path_s):
                return guard_spec(_align(rule, shape, lead_pad), shape, mesh)
        return P()
    for pat, rule in _RULES:
        if re.search(pat, path_s):
            return guard_spec(_align(rule, shape, lead_pad), shape, mesh)
    return P()


def param_shardings(tree: Any, mesh: Mesh, *, expert_parallel: bool = False):
    """NamedSharding tree for params / qparams / opt_state pytrees.

    QuantizedTensor leaves are reached through their dataclass fields; the
    field name (packed/scales) is appended to the path by tree_flatten, so
    the rules above match on ``...w_gate/packed`` — we normalise to
    ``w_gate.packed`` for rule syntax.
    """
    def leaf_spec(path, leaf):
        path_s = _path_str(path)
        # dataclass field access appears as /packed or /scales tail
        path_s = re.sub(r"/(packed|scales)$", r".\1", path_s)
        if not hasattr(leaf, "shape"):
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, _spec_for(path_s, leaf.shape, mesh,
                                             expert_parallel))

    return jax.tree_util.tree_map_with_path(leaf_spec, tree)


# --------------------------------------------------------------- activations


def cache_shardings(tree: Any, mesh: Mesh, *, expert_parallel: bool = False):
    """Decode-state shardings for the STACKED cache layout (leading L or
    n_sites dim): KV k/v (L, B, Hkv, slots, D) — batch over (pod, data),
    slots over model (flash-decode style); positions (L, B, slots); SSM
    conv/ssm state sharded over the channel/head dim. Replicated under
    ``expert_parallel``, whose attention runs whole on every device."""
    b_axes = batch_spec(mesh)

    def leaf_spec(path, leaf):
        if not hasattr(leaf, "shape") or expert_parallel:
            return NamedSharding(mesh, P())
        path_s = _path_str(path)
        nd = len(leaf.shape)
        if path_s.endswith("/k") or path_s.endswith("/v"):
            spec = P(None, b_axes, None, MODEL_AXIS, None)
        elif path_s.endswith("/positions"):
            spec = P(None, b_axes, MODEL_AXIS)
        elif path_s.endswith("/length"):
            spec = P(None, b_axes)
        elif path_s.endswith("/conv_state"):
            spec = P(None, b_axes, MODEL_AXIS, None)
        elif path_s.endswith("/ssm_state"):
            spec = P(None, b_axes, MODEL_AXIS, *([None] * (nd - 3)))
        else:
            spec = P(*([None] * nd))
        return NamedSharding(mesh, guard_spec(spec, leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(leaf_spec, tree)


def zero1_shardings(tree: Any, mesh: Mesh, *, expert_parallel: bool = False):
    """ZeRO-1: optimizer-state shardings = parameter shardings PLUS the
    "data" axis on the first still-replicated divisible dim, so Adam moments
    stop being replicated across data-parallel replicas (§Perf hillclimb B).
    """
    base = param_shardings(tree, mesh, expert_parallel=expert_parallel)
    dsize = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    d_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)

    def up(leaf, sh):
        if not hasattr(leaf, "shape") or not leaf.shape:
            return sh
        spec = list(sh.spec) + [None] * (len(leaf.shape) - len(sh.spec))
        for i, (dim, ax) in enumerate(zip(leaf.shape, spec)):
            if ax is None and dim % dsize == 0 and dim >= dsize:
                spec[i] = d_axes if len(d_axes) > 1 else d_axes[0]
                break
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(up, tree, base)


def shard_tree(tree: Any, shardings) -> Any:
    """device_put a concrete pytree according to a sharding tree."""
    return jax.tree.map(jax.device_put, tree, shardings)
