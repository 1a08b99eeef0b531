"""Top-level model: init / train forward / prefill / decode for every
assigned architecture family, with DyMoE integrated as a first-class feature.

**Scan-over-layers**: per-layer parameters are STACKED (leading dim L) and
the stack is driven by ``jax.lax.scan``, so the compiled HLO contains ONE
block body regardless of depth — this is what makes the 64-layer dry-runs
compile in seconds instead of hours (see EXPERIMENTS.md §Perf iteration 0).
Per-layer heterogeneity (DyMoE's depth schedule t_l, layer precision tiers,
the hybrid's shared-attention sites, look-ahead routers) rides along as
scanned inputs.

Layer pattern per family (pre-norm residual blocks):
  dense/vlm/audio:  x += Attn(n1(x));  x += MLP(n2(x))
  moe:              x += Attn(n1(x));  x += MoE(n2(x))      [+ shared experts]
  ssm:              x += Mamba(n(x))
  hybrid (zamba2):  Mamba backbone + a weight-SHARED attention block applied
                    every ``shared_attn_every`` layers (per-site KV caches).

DyMoE integration (inference paths):
  * prefill — per layer, attention also yields per-token received-attention
    mass (Eq. 1); heavy-hitter routing stats give expert importance (Eq. 2);
    the depth schedule's t_l picks the Critical set (Eq. 4–5); next-layer
    gate predictions (Eq. 6–7) are emitted for the prefetch engine.
  * decode — gate-guided importance (Eq. 3) + direct prefetch (Eq. 8).
  * dense/SSM archs — only the depth-aware layer tiering applies
    (DESIGN.md §Arch-applicability).
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.importance import heavy_hitter_mask, \
    prefill_expert_importance, prefill_expert_importance_rows, \
    select_critical, select_critical_rows
from repro.core.prefetch import predict_next_gates, prefetch_targets
from repro.core.schedule import critical_counts, retention_ratio
from repro.models.config import ModelConfig
from repro.models.kv_cache import KVCache, fill_kv_cache, init_kv_cache
from repro.models.layers.attention import attention_decode, attention_train, \
    init_attention
from repro.models.layers.mlp import init_mlp, mlp, mlp_quantized, quantize_mlp
from repro.models.layers.moe import init_moe, moe_apply_prefill_rows, \
    moe_apply_rows, moe_apply_sharded, quantize_moe
from repro.models.layers.norms import init_rmsnorm, rmsnorm
from repro.models.layers.rotary import sinusoidal_embedding
from repro.models.layers.ssm import init_mamba, init_ssm_cache, \
    mamba_decode, mamba_prefill
from repro.quant.qtensor import MixedPrecisionWeights

__all__ = [
    "init_params", "quantize_model", "init_quantized_params",
    "drop_dense_experts", "forward", "loss_fn", "train_step_fn",
    "prefill", "decode_step", "decode_many", "decode_many_batched",
    "init_decode_state", "DyMoEInfo",
]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _tmap(f, *trees):
    return jax.tree.map(f, *trees)


def _index_tree(tree, i):
    return _tmap(lambda x: x[i], tree)


def _scan_blocks(cfg: ModelConfig, body, carry0, xs):
    """lax.scan over the layer stack, or an unrolled Python loop when
    ``cfg.scan_layers`` is False (used by the dry-run to recover per-layer
    costs: XLA's cost_analysis counts a while-loop body once).

    Runs under the ``layers`` named scope: the per-layer sub-scopes of the
    body (``attention``, ``router``, ``moe_dispatch``, ``experts``,
    ``moe_combine``) nest inside it, and the scan's own bookkeeping (the
    per-layer slices of ``xs``, the stacking of ``ys``) reads as
    ``layers`` with no inner scope."""
    with jax.named_scope("layers"):
        if cfg.scan_layers:
            return jax.lax.scan(body, carry0, xs)
        carry = carry0
        ys = []
        for l in range(cfg.num_layers):
            carry, y = body(carry, _index_tree(xs, l))
            ys.append(y)
        if ys and ys[0] is not None:
            ys = _tmap(lambda *zs: jnp.stack(zs), *ys)
        else:
            ys = None
        return carry, ys


# --------------------------------------------------------------------- init


def _init_block(cfg: ModelConfig, key, kind: str, dtype) -> Dict[str, Any]:
    lp: Dict[str, Any] = {"norm1": init_rmsnorm(cfg.d_model, dtype)}
    k1, k2 = jax.random.split(key)
    if kind in ("attn_dense", "attn_moe"):
        lp["norm2"] = init_rmsnorm(cfg.d_model, dtype)
        lp["attn"] = init_attention(cfg, k1, dtype)
        if kind == "attn_moe":
            lp["moe"] = init_moe(cfg, k2, dtype)
        else:
            lp["mlp"] = init_mlp(cfg, k2, dtype)
    else:
        lp["ssm"] = init_mamba(cfg, k1, dtype)
    return lp


def _layer_keys(cfg: ModelConfig, key):
    """(embed, head, per-layer, shared) keys; the per-layer keys feed
    :func:`_init_block` one layer at a time."""
    k_embed, k_head, k_layers, k_shared = jax.random.split(key, 4)
    return k_embed, k_head, jax.random.split(k_layers, cfg.num_layers), \
        k_shared


def _init_outer(cfg: ModelConfig, k_embed, k_head, k_shared, dt) -> dict:
    """Everything but the layer stack: embedding, final norm, LM head and
    the hybrid's shared attention block."""
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, cfg.d_model))
                  * cfg.d_model ** -0.5).astype(dt),
        "final_norm": init_rmsnorm(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(
            k_head, (cfg.d_model, cfg.vocab_size)) * cfg.d_model ** -0.5
            ).astype(dt)
    if cfg.shared_attn_every:
        s1, s2 = jax.random.split(k_shared)
        params["shared_attn"] = {
            "norm1": init_rmsnorm(cfg.d_model, dt),
            "norm2": init_rmsnorm(cfg.d_model, dt),
            "attn": init_attention(cfg, s1, dt),
            "mlp": init_mlp(cfg, s2, dt),
        }
    return params


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    """Parameters with layer stack STACKED along a leading L dim. Layers
    are drawn one at a time (``lax.map``), so no intermediate spans the
    whole stack: at full width a vmapped draw would hold every layer's
    f32 expert weights at once."""
    cfg.validate()
    dt = _dtype(cfg)
    kinds = cfg.block_kinds()
    assert len(set(kinds)) == 1, "block kinds are uniform per arch"
    k_embed, k_head, layer_keys, k_shared = _layer_keys(cfg, key)
    params = _init_outer(cfg, k_embed, k_head, k_shared, dt)
    params["layers"] = jax.lax.map(
        lambda k: _init_block(cfg, k, kinds[0], dt), layer_keys)
    return params


def _quantize_block(cfg: ModelConfig, lp: dict) -> dict:
    """One layer's DyMoE mixed-precision store (see :func:`quantize_model`)."""
    pol = cfg.dymoe
    kind = cfg.block_kinds()[0]
    if kind == "attn_moe":
        return {"moe": quantize_moe(lp["moe"], cfg)}
    if kind == "attn_dense":
        return {"mlp": quantize_mlp(lp["mlp"], cfg)}
    return {"ssm": {
        name: MixedPrecisionWeights.build(
            lp["ssm"][name], pol.high_bits, pol.low_bits or None,
            pol.group_size)
        for name in ("in_proj", "out_proj")
    }}


def quantize_model(params, cfg: ModelConfig) -> Dict[str, Any]:
    """DyMoE mixed-precision store (paper §5: experts only — on non-MoE
    archs the FFN / SSM projections, the closest analogue). Quantized
    leaves keep the leading L dim and scan alongside the layer stack; each
    layer is quantized on its own (``lax.map``), so the f32 intermediates
    of the group-wise quantizer never span the whole stack."""
    return {"layers": jax.lax.map(partial(_quantize_block, cfg),
                                  params["layers"])}


_DENSE_EXPERTS = ("w_gate", "w_up", "w_down")


def drop_dense_experts(params) -> Dict[str, Any]:
    """``params`` without the dense routed-expert leaves. The quantized
    serving path reads routed experts only from the packed store, so
    keeping the bf16 copies beside it would only take device memory (at
    OLMoE-1B-7B's width, 13 GB of a 16 GB chip)."""
    moe = params["layers"].get("moe")
    if moe is None:
        return params
    moe = {k: v for k, v in moe.items() if k not in _DENSE_EXPERTS}
    return dict(params, layers=dict(params["layers"], moe=moe))


def init_quantized_params(cfg: ModelConfig, key
                          ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """``(drop_dense_experts(init_params(cfg, key)), quantize_model(...))``
    built one layer at a time: each layer is drawn, quantized and stripped
    of its dense routed experts before the next, so device memory holds
    the packed stores plus ONE layer's dense weights — never the dense
    stack. Same values as the two-step route."""
    cfg.validate()
    dt = _dtype(cfg)
    kind = cfg.block_kinds()[0]
    k_embed, k_head, layer_keys, k_shared = _layer_keys(cfg, key)
    params = _init_outer(cfg, k_embed, k_head, k_shared, dt)

    def one(k):
        lp = _init_block(cfg, k, kind, dt)
        q = _quantize_block(cfg, lp)
        return drop_dense_experts({"layers": lp})["layers"], q

    params["layers"], q = jax.jit(lambda ks: jax.lax.map(one, ks))(
        layer_keys)
    return params, {"layers": q}


# ------------------------------------------------------------------ helpers


def _embed(params, cfg: ModelConfig, tokens: Optional[jnp.ndarray],
           embeds: Optional[jnp.ndarray],
           positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    x = (embeds.astype(_dtype(cfg)) if embeds is not None
         else jnp.take(params["embed"], tokens, axis=0))
    if cfg.pos_emb == "sinusoidal":
        b, s, dm = x.shape
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        x = x + sinusoidal_embedding(positions, dm).astype(x.dtype)
    return x


def _lm_head(params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (x @ w).astype(jnp.float32)


def _layer_tier_flags(cfg: ModelConfig) -> jnp.ndarray:
    """Depth-aware layer criticality for non-MoE archs: a layer is Critical
    (high precision) when its retention ratio is >= the schedule mean."""
    lam = cfg.dymoe.lam
    mean_r = (1.0 + lam) / 2.0
    return jnp.asarray([
        retention_ratio(l, cfg.num_layers, lam, cfg.dymoe.depth_schedule)
        >= mean_r
        for l in range(cfg.num_layers)], bool)


def _t_l_array(cfg: ModelConfig) -> jnp.ndarray:
    return jnp.asarray(critical_counts(
        cfg.num_layers, max(cfg.num_experts, 1), cfg.dymoe.lam,
        cfg.dymoe.depth_schedule), jnp.int32)


def _shared_flags(cfg: ModelConfig) -> jnp.ndarray:
    return jnp.asarray([cfg.shared_attn_every and
                        l % cfg.shared_attn_every == 0
                        for l in range(cfg.num_layers)], bool)


def _site_index(cfg: ModelConfig) -> jnp.ndarray:
    """Per-layer index into the shared-site cache stack (valid where
    shared flag is set)."""
    idx, cur = [], 0
    for l in range(cfg.num_layers):
        idx.append(cur)
        if cfg.shared_attn_every and l % cfg.shared_attn_every == 0:
            cur += 1
    return jnp.asarray(idx, jnp.int32)


def _n_sites(cfg: ModelConfig) -> int:
    return len(range(0, cfg.num_layers, cfg.shared_attn_every)) \
        if cfg.shared_attn_every else 0


def _q_ssm(sp: dict, qs: dict, tier) -> dict:
    """Swap the SSM projections for ``(MixedPrecisionWeights, tier)`` pairs:
    ssm.py's ``_proj`` executes them straight from the packed codes of the
    tier-selected precision (no dense dequantized weight materialized)."""
    return dict(sp, in_proj=(qs["in_proj"], tier),
                out_proj=(qs["out_proj"], tier))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DyMoEInfo:
    """Per-step DyMoE telemetry for the orchestration engine / benchmarks."""

    critical_masks: Optional[jnp.ndarray] = None   # (L, E) bool
    active_masks: Optional[jnp.ndarray] = None     # (L, E) bool
    expert_load: Optional[jnp.ndarray] = None      # (L, E)
    expert_hh_load: Optional[jnp.ndarray] = None   # (L, E)
    gate_mean: Optional[jnp.ndarray] = None        # (L, E), prefill only
    predicted_next: Optional[jnp.ndarray] = None   # (L, E) Eq. 6–8 demand
    token_importance: Optional[jnp.ndarray] = None  # (B, S) Eq. 1, last layer
    aux_loss: Optional[jnp.ndarray] = None
    dropped_frac: Optional[jnp.ndarray] = None


def _shared_block_train(params, cfg: ModelConfig, x):
    sp = params["shared_attn"]
    a, _, kv = attention_train(sp["attn"], cfg,
                               rmsnorm(sp["norm1"], x, cfg.norm_eps))
    x = x + a
    x = x + mlp(sp["mlp"], cfg, rmsnorm(sp["norm2"], x, cfg.norm_eps))
    return x, kv


# ------------------------------------------------------- train forward


def forward(params, cfg: ModelConfig, tokens: Optional[jnp.ndarray] = None,
            *, embeds: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Training forward. Returns (logits (B,S,V) f32, aux_loss scalar)."""
    x = _embed(params, cfg, tokens, embeds)
    b, s, _ = x.shape
    kind = cfg.block_kinds()[0]
    hybrid = bool(cfg.shared_attn_every)

    def body(carry, xs):
        x, aux = carry
        lp = xs["block"]
        if cfg.act_seq_shard:
            # sequence-shard the residual stream so the remat-saved carry is
            # bounded to 1/model-shards per device (§Perf hillclimb B)
            from jax.sharding import PartitionSpec as _P
            x = jax.lax.with_sharding_constraint(
                x, _P(_P.UNCONSTRAINED, "model", _P.UNCONSTRAINED))
        if hybrid:
            def with_shared(x):
                return _shared_block_train(params, cfg, x)[0]
            x = jax.lax.cond(xs["shared"], with_shared, lambda x: x, x)
        if kind in ("attn_dense", "attn_moe"):
            a, _, _ = attention_train(lp["attn"], cfg,
                                      rmsnorm(lp["norm1"], x, cfg.norm_eps))
            x = x + a
            h = rmsnorm(lp["norm2"], x, cfg.norm_eps)
            if kind == "attn_dense":
                x = x + mlp(lp["mlp"], cfg, h)
            else:
                y, stats = moe_apply_sharded(lp["moe"], cfg, h.reshape(b * s, -1))
                x = x + y.reshape(b, s, -1)
                aux = aux + stats.aux_loss
        else:
            y, _ = mamba_prefill(lp["ssm"], cfg,
                                 rmsnorm(lp["norm1"], x, cfg.norm_eps),
                                 init_ssm_cache(cfg, b))
            x = x + y
        return (x, aux), None

    if cfg.remat == "block":
        body = jax.checkpoint(body)
    xs = {"block": params["layers"]}
    if hybrid:
        xs["shared"] = _shared_flags(cfg)
    (x, aux), _ = _scan_blocks(cfg, body, (x, jnp.zeros((), jnp.float32)), xs)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return _lm_head(params, cfg, x), aux


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray]
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    logits, aux = forward(params, cfg, batch.get("tokens"),
                          embeds=batch.get("embeds"))
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(nll)
    ce = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return ce + aux, {"ce": ce, "aux": aux}


def train_step_fn(cfg: ModelConfig, optimizer):
    """Returns a pure train_step(params, opt_state, batch)."""

    def step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, cfg, batch)
        params, opt_state = optimizer.update(params, grads, opt_state)
        return params, opt_state, dict(metrics, loss=loss)

    return step


# ------------------------------------------------------------------ prefill


def _ragged_hh_mask(tok_imp: jnp.ndarray, frac: float,
                    lengths: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Per-row heavy-hitter mask for a right-aligned ragged batch: the
    top-⌈frac·length_i⌉ threshold is taken over row i's REAL tokens only,
    mirroring :func:`heavy_hitter_mask` on the unpadded row."""
    ti = jnp.where(valid, tok_imp, -jnp.inf)
    k = jnp.maximum(1, jnp.round(frac * lengths).astype(jnp.int32))  # (B,)
    desc = -jnp.sort(-ti, axis=-1)
    thresh = jnp.take_along_axis(desc, (k - 1)[:, None], axis=-1)
    return ((ti >= thresh) & valid).astype(jnp.float32)


def prefill(params, cfg: ModelConfig, tokens: Optional[jnp.ndarray] = None,
            *, embeds: Optional[jnp.ndarray] = None,
            qparams: Optional[dict] = None,
            cache_slots: Optional[int] = None,
            full_logits: bool = False,
            lengths: Optional[jnp.ndarray] = None,
            row_local: bool = False,
            row_capacities: Optional[jnp.ndarray] = None,
            ) -> Tuple[jnp.ndarray, Any, DyMoEInfo]:
    """Prefill pass. DyMoE active when ``qparams`` is given and policy on.

    ``lengths`` (B,) enables RAGGED batches: ``tokens`` is right-aligned
    (row i left-padded with ``S - lengths[i]`` pads), per-row position
    offsets drive RoPE/sinusoidal embeddings, attention masks pad keys,
    routing statistics exclude pad tokens, and the KV cache records the
    per-row slot offset so decode continues at each row's own logical
    position while writing to the uniform slot frontier S. The last-token
    logits row ``x[:, -1]`` is every row's true last token — the point of
    right alignment. Attention-based archs only (an SSM scan would thread
    pads through its recurrent state).

    ``row_local`` (MoE archs; the batched-admission prefill mode): every
    row's Critical set is selected from ITS OWN per-row importance (Eq.
    1–2 restricted to the row's tokens) and experts execute through the
    dual-buffer :func:`moe_apply_prefill_rows`, so a row's precisions,
    logits and caches never depend on its batch neighbours — each row is
    bit-identical to its solo prefill. MoE telemetry leaves come back per
    row: ``(L, B, E)`` instead of ``(L, E)``, one block per request for
    the orchestrator replay. No-op for non-MoE archs. ``row_capacities``
    (B,) optionally pins each row's expert-capacity budget to the exact
    host-computed solo value (see :func:`moe_apply_prefill_rows`).

    Returns (last-token logits (B, V), caches, DyMoEInfo). Caches are a
    stacked pytree: {"layers": KVCache/SSMCache with leading L,
    "shared": KVCache with leading n_sites (hybrid only)}.
    """
    b_, s_ = (tokens.shape if tokens is not None else embeds.shape[:2])
    offsets = valid = positions = None
    if lengths is not None:
        assert cfg.block_kinds()[0] in ("attn_dense", "attn_moe"), \
            "ragged prefill requires attention archs"
        assert not cfg.shared_attn_every, \
            "ragged prefill unsupported for shared-attention hybrids"
        lengths = jnp.asarray(lengths, jnp.int32)
        offsets = jnp.full((b_,), s_, jnp.int32) - lengths       # (B,)
        idx = jnp.arange(s_, dtype=jnp.int32)[None, :]
        valid = idx >= offsets[:, None]                          # (B, S)
        positions = jnp.maximum(idx - offsets[:, None], 0)       # (B, S)
    x = _embed(params, cfg, tokens, embeds, positions=positions)
    b, s, _ = x.shape
    dt = _dtype(cfg)
    dymoe_on = qparams is not None and cfg.dymoe.enabled
    pol = cfg.dymoe
    kind = cfg.block_kinds()[0]
    hybrid = bool(cfg.shared_attn_every)
    slots = cache_slots or (cfg.sliding_window or max(s, cfg.max_seq_len))
    ring = cfg.sliding_window is not None and slots == cfg.sliding_window
    assert lengths is None or not ring, \
        "ragged prefill unsupported with sliding-window ring caches"

    xs: Dict[str, Any] = {"block": params["layers"]}
    if dymoe_on:
        xs["q"] = qparams["layers"]
        xs["tier"] = _layer_tier_flags(cfg)
        if kind == "attn_moe":
            xs["t_l"] = _t_l_array(cfg)
            xs["next_router"] = jnp.roll(
                params["layers"]["moe"]["wg_router"], -1, axis=0)
    elif kind == "attn_moe":
        xs["t_l"] = _t_l_array(cfg)
        xs["next_router"] = jnp.roll(
            params["layers"]["moe"]["wg_router"], -1, axis=0)
    if hybrid:
        xs["shared"] = _shared_flags(cfg)
        xs["site"] = _site_index(cfg)
        shared_caches0 = jax.vmap(
            lambda _: init_kv_cache(b, cfg.num_kv_heads, slots, cfg.head_dim,
                                    dt, ring)
        )(jnp.arange(_n_sites(cfg)))

    e = max(cfg.num_experts, 1)

    def body(carry, xs_l):
        if hybrid:
            x, shared_caches = carry
        else:
            (x,) = carry
        lp = xs_l["block"]

        if hybrid:
            def with_shared(operand):
                x, sc = operand
                x2, (k_s, v_s) = _shared_block_train(params, cfg, x)
                site = xs_l["site"]
                new = fill_kv_cache(_index_tree(sc, site), k_s, v_s)
                sc = _tmap(lambda full, n: full.at[site].set(n), sc, new)
                return x2, sc
            x, shared_caches = jax.lax.cond(
                xs_l["shared"], with_shared, lambda o: o, (x, shared_caches))

        telem: Dict[str, Any] = {}
        if kind in ("attn_dense", "attn_moe"):
            want_imp = kind == "attn_moe"
            with jax.named_scope("attention"):
                a, tok_imp, (k, v) = attention_train(
                    lp["attn"], cfg, rmsnorm(lp["norm1"], x, cfg.norm_eps),
                    positions=positions, kv_valid=valid,
                    want_token_importance=want_imp)
                cache = fill_kv_cache(
                    init_kv_cache(b, cfg.num_kv_heads, slots, cfg.head_dim,
                                  dt, ring), k, v, lengths=lengths,
                    offsets=offsets)
                x = x + a
            if kind == "attn_dense":
                h = rmsnorm(lp["norm2"], x, cfg.norm_eps)
                if dymoe_on:
                    y = mlp_quantized(xs_l["q"]["mlp"], cfg, h, xs_l["tier"])
                else:
                    y = mlp(lp["mlp"], cfg, h)
                x = x + y
            else:
                k_tok = cfg.num_experts_per_tok
                critical, hh = None, None
                with jax.named_scope("router"):
                    h = rmsnorm(lp["norm2"], x, cfg.norm_eps)
                    hflat = h.reshape(b * s, -1)
                    vflat = (valid.reshape(b * s) if valid is not None
                             else None)
                    if dymoe_on:
                        if valid is None:
                            hh = heavy_hitter_mask(
                                tok_imp, pol.heavy_hitter_frac
                            ).reshape(b * s)
                        else:
                            hh = _ragged_hh_mask(
                                tok_imp, pol.heavy_hitter_frac, lengths,
                                valid).reshape(b * s)
                    if dymoe_on or row_local:
                        # router pre-pass: pick the Critical set BEFORE
                        # expert compute (Eq. 1-2 -> Eq. 5)
                        logits_r = hflat.astype(jnp.float32) @ lp["moe"][
                            "wg_router"]
                        probs_r = jax.nn.softmax(logits_r, axis=-1)
                        gates_r, idx_r = jax.lax.top_k(probs_r, k_tok)
                        oh = jax.nn.one_hot(idx_r, e, dtype=jnp.float32)
                        if vflat is not None:  # pads route nowhere
                            oh = oh * vflat.astype(jnp.float32)[:, None,
                                                                None]
                    if dymoe_on and not row_local:
                        imp = prefill_expert_importance(
                            jnp.einsum("tke,t->e", oh, hh),
                            oh.sum(axis=(0, 1)))
                        critical = select_critical(imp, xs_l["t_l"])
                    if row_local:
                        # per-ROW Critical sets (batched-admission mode):
                        # each row's Eq. 1-2 importance over ITS OWN tokens
                        oh_r = oh.reshape(b, s, k_tok, e)
                        load_rows = oh_r.sum(axis=(1, 2))      # (B, E)
                        if dymoe_on:
                            imp_rows = prefill_expert_importance_rows(
                                jnp.einsum("bske,bs->be", oh_r,
                                           hh.reshape(b, s)), load_rows)
                            critical_rows = select_critical_rows(
                                imp_rows, xs_l["t_l"])
                if row_local:
                    if dymoe_on:
                        y, rstats = moe_apply_prefill_rows(
                            lp["moe"], cfg, hflat, critical_rows,
                            xs_l["q"]["moe"], rows=b, hh_mask=hh,
                            token_valid=vflat,
                            row_capacities=row_capacities)
                        active_rows = rstats["active"]
                        hh_load_rows = rstats["hh_load"]
                        gate_mean_rows = rstats["gate_mean"]
                        aux_t, dropped_t = (rstats["aux_loss"],
                                            rstats["dropped_frac"])
                    else:
                        y, stats = moe_apply_sharded(
                            lp["moe"], cfg, hflat, token_valid=vflat)
                        critical_rows = jnp.ones((b, e), bool)
                        active_rows = load_rows > 0
                        hh_load_rows = jnp.zeros_like(load_rows)
                        gn = gates_r / jnp.maximum(
                            gates_r.sum(-1, keepdims=True), 1e-9)
                        gate_mean_rows = jnp.einsum(
                            "bske,bsk->be", oh_r,
                            gn.reshape(b, s, k_tok)) / jnp.maximum(
                                load_rows, 1.0)
                        aux_t, dropped_t = stats.aux_loss, stats.dropped_frac
                else:
                    y, stats = moe_apply_sharded(
                        lp["moe"], cfg, hflat, hh_mask=hh,
                        critical_mask=critical,
                        qweights=xs_l["q"]["moe"] if dymoe_on else None,
                        token_valid=vflat)
                with jax.named_scope("moe_combine"):
                    x = x + y.reshape(b, s, -1)
                # look-ahead (Eq. 6-7) for the next layer's prefetcher
                with jax.named_scope("router"):
                    pg = predict_next_gates(hflat, xs_l["next_router"])
                    if row_local:
                        # per-row Eq. 7: each admission's own demand
                        pg_r = pg.reshape(b, s, e)
                        if valid is None:
                            freq = jax.vmap(lambda g: prefetch_targets(
                                g, k_tok, pol.prefetch_topk)[1])(pg_r)
                        else:
                            freq = jax.vmap(lambda g, v: prefetch_targets(
                                g, k_tok, pol.prefetch_topk,
                                token_valid=v)[1])(pg_r, valid)
                    else:
                        _, freq = prefetch_targets(pg, k_tok,
                                                   pol.prefetch_topk,
                                                   token_valid=vflat)
                if row_local:
                    telem = dict(
                        critical=critical_rows, active=active_rows,
                        load=load_rows, hh_load=hh_load_rows,
                        gate_mean=gate_mean_rows, pred=freq, aux=aux_t,
                        dropped=dropped_t,
                        tok_imp=(tok_imp if tok_imp is not None
                                 else jnp.zeros((b, s), jnp.float32)))
                else:
                    telem = dict(
                        critical=(critical if critical is not None
                                  else jnp.ones((e,), bool)),
                        active=stats.expert_load > 0,
                        load=stats.expert_load,
                        hh_load=stats.expert_hh_load,
                        gate_mean=stats.gate_mean,
                        pred=freq,
                        aux=stats.aux_loss,
                        dropped=stats.dropped_frac,
                        tok_imp=(tok_imp if tok_imp is not None
                                 else jnp.zeros((b, s), jnp.float32)),
                    )
        else:  # ssm
            h = rmsnorm(lp["norm1"], x, cfg.norm_eps)
            sp = lp["ssm"]
            if dymoe_on:
                sp = _q_ssm(sp, xs_l["q"]["ssm"], xs_l["tier"])
            y, cache = mamba_prefill(sp, cfg, h, init_ssm_cache(cfg, b, dt))
            x = x + y

        carry = (x, shared_caches) if hybrid else (x,)
        return carry, {"cache": cache, **telem}

    carry0 = (x, shared_caches0) if hybrid else (x,)
    carry, ys = _scan_blocks(cfg, body, carry0, xs)
    x = carry[0]
    with jax.named_scope("lm_head"):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = _lm_head(params, cfg, x if full_logits else x[:, -1])

    caches: Dict[str, Any] = {"layers": ys["cache"]}
    if hybrid:
        caches["shared"] = carry[1]
    info = DyMoEInfo()
    if kind == "attn_moe":
        info.critical_masks = ys["critical"]
        info.active_masks = ys["active"]
        info.expert_load = ys["load"]
        info.expert_hh_load = ys["hh_load"]
        info.gate_mean = ys["gate_mean"]
        # roll feeds layer 0's router to the last layer: mask it out
        pred = ys["pred"].at[-1].set(0.0)
        info.predicted_next = pred
        info.aux_loss = ys["aux"].sum()
        info.dropped_frac = ys["dropped"].mean()
        info.token_importance = ys["tok_imp"][-1]
    return logits, caches, info


# ------------------------------------------------------------------- decode


def init_decode_state(cfg: ModelConfig, batch: int, seq_len: int) -> Any:
    """Fresh stacked caches sized for ``seq_len`` context (ring-buffered to
    the sliding window when configured)."""
    dt = _dtype(cfg)
    slots = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
    ring = cfg.sliding_window is not None and slots == cfg.sliding_window
    kind = cfg.block_kinds()[0]

    def one(_):
        if kind in ("attn_dense", "attn_moe"):
            return init_kv_cache(batch, cfg.num_kv_heads, slots,
                                 cfg.head_dim, dt, ring)
        return init_ssm_cache(cfg, batch, dt)

    caches = {"layers": jax.vmap(one)(jnp.arange(cfg.num_layers))}
    if cfg.shared_attn_every:
        caches["shared"] = jax.vmap(
            lambda _: init_kv_cache(batch, cfg.num_kv_heads, slots,
                                    cfg.head_dim, dt, ring)
        )(jnp.arange(_n_sites(cfg)))
    return caches


def decode_step(params, cfg: ModelConfig, tokens: jnp.ndarray,
                caches: Any, *, qparams: Optional[dict] = None,
                per_row_moe: bool = False,
                live_rows: Optional[jnp.ndarray] = None,
                moe_capacity: Optional[int] = None,
                ) -> Tuple[jnp.ndarray, Any, DyMoEInfo]:
    """One decode step. tokens: (B,) int32. Returns (logits (B, V) f32,
    caches, DyMoEInfo with gate-guided importance + Eq. 8 predictions).

    ``per_row_moe`` (continuous-batching mode): the gate-guided Critical
    set (Eq. 3) is selected PER ROW instead of from the batch-mean gate,
    experts execute through the fused single-dispatch
    :func:`moe_apply_rows` (so a row's precision — and its tokens — never
    depend on batch neighbours, while weights still unpack once per
    precision stream, not per row), and the telemetry leaves come back
    per row: (B, L, E) instead of (L, E). Non-MoE archs are
    row-independent either way.

    ``live_rows`` (B,) bool marks rows that are really decoding: dead
    (finished/evicted/empty) rows take no MoE capacity slots — the fused
    kernel's ragged grid skips their FLOPs and weight I/O — and their KV
    writes freeze. Dead rows' logits are garbage by contract; the batched
    caller re-feeds their token unchanged and masks their telemetry.
    ``moe_capacity`` (static, requires ``live_rows``) bounds each MoE
    precision region to the chunk's live-row count instead of B."""
    dt = _dtype(cfg)
    kind = cfg.block_kinds()[0]
    hybrid = bool(cfg.shared_attn_every)
    dymoe_on = qparams is not None and cfg.dymoe.enabled
    pol = cfg.dymoe
    b = tokens.shape[0]
    e = max(cfg.num_experts, 1)

    positions = caches["layers"].length[0][:, None]  # (B,1) new-token pos
    x = _embed(params, cfg, tokens[:, None], None, positions=positions)

    xs: Dict[str, Any] = {"block": params["layers"],
                          "cache": caches["layers"]}
    if dymoe_on:
        xs["q"] = qparams["layers"]
        xs["tier"] = _layer_tier_flags(cfg)
    if kind == "attn_moe":
        xs["t_l"] = _t_l_array(cfg)
        xs["next_router"] = jnp.roll(
            params["layers"]["moe"]["wg_router"], -1, axis=0)
    if hybrid:
        xs["shared"] = _shared_flags(cfg)
        xs["site"] = _site_index(cfg)

    def body(carry, xs_l):
        if hybrid:
            x, shared_caches = carry
        else:
            (x,) = carry
        lp = xs_l["block"]
        cache = xs_l["cache"]

        if hybrid:
            def with_shared(operand):
                x, sc = operand
                sp = params["shared_attn"]
                site = xs_l["site"]
                a, new = attention_decode(
                    sp["attn"], cfg, rmsnorm(sp["norm1"], x, cfg.norm_eps),
                    _index_tree(sc, site), live=live_rows)
                sc = _tmap(lambda full, n: full.at[site].set(n), sc, new)
                x = x + a
                x = x + mlp(sp["mlp"], cfg,
                            rmsnorm(sp["norm2"], x, cfg.norm_eps))
                return x, sc
            x, shared_caches = jax.lax.cond(
                xs_l["shared"], with_shared, lambda o: o, (x, shared_caches))

        telem: Dict[str, Any] = {}
        if kind in ("attn_dense", "attn_moe"):
            with jax.named_scope("attention"):
                a, cache = attention_decode(
                    lp["attn"], cfg, rmsnorm(lp["norm1"], x, cfg.norm_eps),
                    cache, live=live_rows)
                x = x + a
            if kind == "attn_dense":
                h = rmsnorm(lp["norm2"], x, cfg.norm_eps)
                if dymoe_on:
                    y = mlp_quantized(xs_l["q"]["mlp"], cfg, h, xs_l["tier"])
                else:
                    y = mlp(lp["mlp"], cfg, h)
                x = x + y
            else:
                critical = None
                with jax.named_scope("router"):
                    h = rmsnorm(lp["norm2"], x, cfg.norm_eps)
                    hflat = h.reshape(b, -1)
                    if dymoe_on:
                        # Eq. (3): gate-guided importance — per row (each
                        # request's Critical set from ITS OWN gate scores,
                        # the solo-parity contract) or from the batch-mean
                        # gate
                        logits_r = hflat.astype(jnp.float32) @ lp["moe"][
                            "wg_router"]
                        imp = jax.nn.softmax(logits_r, axis=-1)  # (B, E)
                        critical = (
                            select_critical_rows(imp, xs_l["t_l"])
                            if per_row_moe
                            else select_critical(imp.mean(axis=0),
                                                 xs_l["t_l"]))
                if per_row_moe and dymoe_on:
                    y, rstats = moe_apply_rows(
                        lp["moe"], cfg, hflat, critical,
                        qweights=xs_l["q"]["moe"], live=live_rows,
                        capacity=moe_capacity)
                    active = rstats["active"]
                elif per_row_moe:
                    y, stats = moe_apply_sharded(lp["moe"], cfg, hflat)
                    # full precision: rows are independent already; only
                    # the telemetry needs the per-row shape
                    with jax.named_scope("router"):
                        oh = jax.nn.one_hot(
                            jax.lax.top_k(stats.router_logits,
                                          cfg.num_experts_per_tok)[1],
                            e, dtype=jnp.float32)                # (B, k, E)
                        active = oh.sum(axis=1) > 0
                        critical = jnp.ones(active.shape, bool)
                else:
                    y, stats = moe_apply_sharded(
                        lp["moe"], cfg, hflat, critical_mask=critical,
                        qweights=xs_l["q"]["moe"] if dymoe_on else None)
                    active = stats.expert_load > 0
                    if critical is None:
                        critical = jnp.ones((e,), bool)
                with jax.named_scope("moe_combine"):
                    x = x + y.reshape(b, 1, -1)
                with jax.named_scope("router"):
                    pg = predict_next_gates(hflat, xs_l["next_router"])
                    if per_row_moe:
                        # per-row Eq. (8): each row's own predicted demand
                        freq = jax.vmap(lambda g: prefetch_targets(
                            g[None], cfg.num_experts_per_tok,
                            pol.prefetch_topk)[1])(pg)           # (B, E)
                    else:
                        _, freq = prefetch_targets(
                            pg, cfg.num_experts_per_tok, pol.prefetch_topk)
                telem = dict(critical=critical, active=active, pred=freq)
        else:  # ssm
            h = rmsnorm(lp["norm1"], x, cfg.norm_eps)
            sp = lp["ssm"]
            if dymoe_on:
                sp = _q_ssm(sp, xs_l["q"]["ssm"], xs_l["tier"])
            y, cache = mamba_decode(sp, cfg, h, cache)
            x = x + y

        carry = (x, shared_caches) if hybrid else (x,)
        return carry, {"cache": cache, **telem}

    if hybrid:
        carry0 = (x, caches["shared"])
    else:
        carry0 = (x,)
    carry, ys = _scan_blocks(cfg, body, carry0, xs)
    x = carry[0]
    with jax.named_scope("lm_head"):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = _lm_head(params, cfg, x[:, 0])

    new_caches: Dict[str, Any] = {"layers": ys["cache"]}
    if hybrid:
        new_caches["shared"] = carry[1]
    info = DyMoEInfo()
    if kind == "attn_moe":
        info.critical_masks = ys["critical"]
        info.active_masks = ys["active"]
        info.predicted_next = ys["pred"].at[-1].set(0.0)
    return logits, new_caches, info


def decode_many(params, cfg: ModelConfig, tokens: jnp.ndarray, caches: Any,
                *, num_steps: int, start_step=0,
                qparams: Optional[dict] = None, rng_key=None,
                temperature=0.0, top_k: int = 0,
                row_keys=None, row_temperatures=None, row_top_ks=None,
                ) -> Tuple[jnp.ndarray, Any, DyMoEInfo]:
    """Fused multi-token decode: ``lax.scan`` over ``num_steps`` decode
    steps with on-device sampling, so a whole chunk costs ONE dispatch and
    ONE device→host transfer instead of ``num_steps`` of each.

    tokens: (B,) int32 — the last sampled token per sequence. The scan
    carries (tokens, caches, PRNG key); sampling happens inside the scan
    body via :func:`repro.serving.sampler.sample_token`. ``top_k`` is a
    trace-time static (it shapes ``lax.top_k``); ``temperature`` may be a
    traced scalar so a jitted wrapper does not recompile per requested
    temperature — when traced it must be > 0 and ``rng_key`` must be set
    (the greedy/sampling choice is structural: greedy iff ``rng_key is
    None`` or a *concrete* temperature is <= 0). Step ``i`` (global
    index ``start_step + i``; ``start_step`` may be a traced scalar so
    chunked callers don't retrace per chunk) draws its key as
    ``jax.random.fold_in(rng_key, start_step + i)`` — a counter-derived
    stream, so any chunking of the same request (chunk=1 vs chunk=16, or
    an early EOS exit) samples bit-identical tokens.

    Returns (sampled tokens (num_steps, B) int32, final caches, DyMoEInfo
    whose per-step telemetry leaves are stacked along a leading
    ``num_steps`` axis — e.g. critical_masks (num_steps, L, E)).

    ``temperature > 0`` without ``rng_key`` falls back to greedy with a
    warning (same contract as ``sample_token``).

    ``row_keys`` (B, 2) raw PRNG keys + ``row_temperatures`` (B,) +
    ``row_top_ks`` (B,) switch sampling to PER-ROW mode (the static batch
    path serving mixed per-request sampling): step ``i`` samples row r
    with ``fold_in(row_keys[r], start_step + i)`` through
    :func:`repro.serving.sampler.sample_token_rows`, so each row's tokens
    are bit-identical to a solo decode with that row's key — rows with
    ``temperature <= 0`` stay greedy. All three arrays are traced (mixed
    sampling never recompiles); ``rng_key``/``temperature``/``top_k`` are
    ignored in this mode.
    """
    # local import: serving depends on models, not the reverse
    from repro.serving.sampler import sample_token, sample_token_rows

    row_mode = row_keys is not None
    concrete_t = isinstance(temperature, (int, float))
    if not row_mode and concrete_t and temperature > 0.0 and rng_key is None:
        warnings.warn("decode_many: temperature > 0 but no PRNG key was "
                      "provided; falling back to greedy decoding")
    greedy = not row_mode and (
        rng_key is None or (concrete_t and temperature <= 0.0))
    key = rng_key if rng_key is not None else jax.random.PRNGKey(0)
    steps = jnp.arange(num_steps, dtype=jnp.int32) + start_step

    def body(carry, i):
        tok, caches, key = carry
        logits, caches, info = decode_step(params, cfg, tok, caches,
                                           qparams=qparams)
        with jax.named_scope("sample"):
            if row_mode:
                keys_i = jax.vmap(lambda k: jax.random.fold_in(k, i))(
                    row_keys)
                nxt = sample_token_rows(logits, keys_i, row_temperatures,
                                        row_top_ks)
            elif greedy:
                nxt = sample_token(logits)
            else:
                nxt = sample_token(logits, jax.random.fold_in(key, i),
                                   temperature=temperature, top_k=top_k)
        return (nxt, caches, key), (nxt, info)

    (_, caches, _), (toks, infos) = jax.lax.scan(
        body, (tokens, caches, key), steps)
    return toks, caches, infos


# ------------------------------------------- continuous-batching decode


def _mask_info_rows(info: DyMoEInfo, live: jnp.ndarray) -> DyMoEInfo:
    """Zero finished rows' telemetry: a frozen slot routes to no experts,
    so the orchestrator replay charges it neither I/O nor MoE compute.
    Leaves are the per-row decode layout (L, B, E); ``live`` is (B,)."""
    m = live[None, :, None]

    def mb(x):
        return None if x is None else x & m

    return DyMoEInfo(critical_masks=mb(info.critical_masks),
                     active_masks=mb(info.active_masks),
                     predicted_next=(None if info.predicted_next is None
                                     else info.predicted_next * m))


def decode_many_batched(params, cfg: ModelConfig, tokens: jnp.ndarray,
                        caches: Any, *, num_steps: int,
                        done: jnp.ndarray, n_emitted: jnp.ndarray,
                        limits: jnp.ndarray, eos_tokens: jnp.ndarray,
                        qparams: Optional[dict] = None,
                        rng_keys=None, temperatures=None, top_ks=None,
                        live_cap: Optional[int] = None,
                        ) -> Tuple[jnp.ndarray, Any, DyMoEInfo,
                                   jnp.ndarray, jnp.ndarray]:
    """Fused multi-step decode over a slot batch with a per-row
    done-mask — the device half of the continuous-batching scheduler.

    Rows decode independently (``decode_step`` with ``per_row_moe``: own
    Critical set and dual-buffer expert execution per row), so slot i's
    tokens are bit-identical to solo decoding of that request regardless
    of its neighbours. Per-row completion is enforced ON DEVICE inside the
    scan: once a row samples its ``eos_tokens`` entry (-1 = none) or its
    ``n_emitted`` count reaches ``limits``, the row freezes — its token
    re-feeds unchanged, its KV/SSM cache stops advancing, and its
    telemetry is zeroed so the modeled accounting charges finished (or
    empty) slots nothing. The scheduler can therefore always dispatch
    full ``num_steps`` chunks (one trace, no per-remainder recompiles)
    and evict/admit at chunk boundaries.

    Sampling is GREEDY unless ``rng_keys`` (B, 2) raw per-row PRNG keys +
    ``temperatures`` (B,) + ``top_ks`` (B,) are given (all traced — mixed
    per-request sampling never recompiles). Row r's step draws its key as
    ``fold_in(rng_keys[r], n_emitted[r])`` — the fold count is the ROW'S
    OWN emitted-token counter, not the scan index, so a request's PRNG
    stream is indexed by its global token position exactly like solo
    ``generate``'s ``fold_in(key, token_index)``: sampled tokens are
    bit-identical to the solo run and invariant to ``decode_chunk``, slot
    placement and admission order. Rows with ``temperature <= 0`` take
    the same greedy argmax as the no-sampling trace.

    The live-row mask (``~done``) is threaded INTO ``decode_step``: dead
    rows take no MoE capacity slots (the fused expert kernel's ragged
    grid skips their FLOPs and weight I/O entirely) and their KV writes
    freeze at the cache-write site, so the chunk-boundary freeze below
    is a no-op for KV caches and only still matters for SSM state.
    ``live_cap`` (STATIC, jit axis) optionally caps each MoE precision
    region at that many rows instead of B — the scheduler passes a
    power-of-two ≥ the chunk's live-slot count so mostly-drained batches
    shrink the expert buffers too (bounded retraces: log2(B) values).

    tokens/done/n_emitted/limits/eos_tokens: (B,). Returns (tokens
    (num_steps, B), caches, stacked DyMoEInfo with leaves (num_steps, L,
    B, E), done (B,), n_emitted (B,)).
    """
    # local import: serving depends on models, not the reverse
    from repro.serving.sampler import sample_token_rows

    done = done.astype(bool)

    def body(carry, _):
        tok, caches, dn, emitted = carry
        live = ~dn
        logits, new_caches, info = decode_step(
            params, cfg, tok, caches, qparams=qparams, per_row_moe=True,
            live_rows=live, moe_capacity=live_cap)
        with jax.named_scope("sample"):
            if rng_keys is None:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                keys = jax.vmap(jax.random.fold_in)(rng_keys, emitted)
                nxt = sample_token_rows(logits, keys, temperatures, top_ks)
            nxt = jnp.where(dn, tok, nxt)

        def freeze(new, old):  # finished rows' caches must not advance
            mask = live.reshape((1, -1) + (1,) * (new.ndim - 2))
            return jnp.where(mask, new, old)

        with jax.named_scope("kv_freeze"):
            caches = _tmap(freeze, new_caches, caches)
        emitted = emitted + live.astype(jnp.int32)
        dn = dn | ((eos_tokens >= 0) & (nxt == eos_tokens)) \
            | (emitted >= limits)
        info = _mask_info_rows(info, live)
        return (nxt, caches, dn, emitted), (nxt, info)

    (_, caches, done, n_emitted), (toks, infos) = jax.lax.scan(
        body, (tokens, caches, done, jnp.asarray(n_emitted, jnp.int32)),
        None, length=num_steps)
    return toks, caches, infos, done, n_emitted
