"""Mixture-of-Experts layer with sort-based capacity dispatch and DyMoE
mixed-precision expert execution.

Dispatch is scatter/gather based (no (T, E, C) one-hot einsum) so the HLO
FLOP count reflects *active* compute — essential for honest rooflines:
tokens are routed top-k, assigned a position inside their expert's capacity
buffer via a cumulative count, scattered to an (E, C, d) buffer, processed by
grouped expert FFNs, and gathered back weighted by their gates.

DyMoE integration (paper §4):
  * ``critical_mask`` (E,) selects per-expert precision at runtime —
    high-bit for Critical experts, low-bit or skip ("0-bit") for
    Sub-critical ones (paper §4.3/§5). The quantized expert FFN executes
    through the grouped ``expert_quant_matmul`` kernel straight from the
    packed codes of the selected precision — no dense (E, dm, dff)
    dequantized weight is ever materialized, so the bytes each layer moves
    scale with the selected bit width (the paper's I/O-volume argument).
  * The returned :class:`MoEStats` carries the per-expert token load,
    heavy-hitter token load (Eq. 2) and mean gate score (Eq. 3) consumed by
    the importance estimator, plus router logits for the look-ahead
    prefetcher (Eq. 6).
Shared experts (Qwen2-MoE) are always-active ⇒ always Critical (they are
selected by every token), so they run in high precision unconditionally.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.quant.mixed import mixed_precision_matmul
from repro.quant.qtensor import MixedPrecisionWeights
from repro.sharding.partition import MODEL_AXIS

__all__ = ["init_moe", "moe_apply", "moe_apply_rows",
           "moe_apply_prefill_rows", "quantize_moe", "scales_after_dot",
           "route", "selection_scores", "MoEStats"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MoEStats:
    """Per-layer routing statistics consumed by DyMoE core."""

    router_logits: jnp.ndarray      # (T, E)
    expert_load: jnp.ndarray        # (E,) token count routed to each expert
    expert_hh_load: jnp.ndarray     # (E,) heavy-hitter token count (Eq. 2)
    gate_mean: jnp.ndarray          # (E,) mean gate score over routed tokens
    aux_loss: jnp.ndarray           # scalar: load-balance + z-loss
    dropped_frac: jnp.ndarray       # scalar: fraction of (token, k) dropped


def init_moe(cfg: ModelConfig, key, dtype) -> dict:
    dm, dff, e = cfg.d_model, cfg.expert_d_ff, cfg.num_experts
    ks = jax.random.split(key, 7)
    p = {
        "wg_router": (jax.random.normal(ks[0], (dm, e)) * dm ** -0.5
                      ).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, dm, dff)) * dm ** -0.5
                   ).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, dm, dff)) * dm ** -0.5
                 ).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, dff, dm)) * dff ** -0.5
                   ).astype(dtype),
    }
    if cfg.num_shared_experts:
        se, sdff = cfg.num_shared_experts, cfg.expert_d_ff
        p["shared_w_gate"] = (jax.random.normal(ks[4], (se, dm, sdff))
                              * dm ** -0.5).astype(dtype)
        p["shared_w_up"] = (jax.random.normal(ks[5], (se, dm, sdff))
                            * dm ** -0.5).astype(dtype)
        p["shared_w_down"] = (jax.random.normal(ks[6], (se, sdff, dm))
                              * sdff ** -0.5).astype(dtype)
    if cfg.router_scoring == "sigmoid":
        # the selection bias (e_score_correction_bias) is a trained buffer
        # in a released model; here N(0, 0.1^2), so that ranking by
        # affinity plus bias and weighting by affinity part on some tokens
        p["e_score_bias"] = jax.random.normal(
            jax.random.fold_in(key, 7), (e,), jnp.float32) * 0.1
    return p


def selection_scores(p, cfg: ModelConfig, logits: jnp.ndarray
                     ) -> jnp.ndarray:
    """What the router ranks experts by, from its logits (..., E): the
    softmax probabilities, or (``router_scoring`` sigmoid) the sigmoid
    affinities plus the per-expert selection bias. Also DyMoE's decode
    importance (Eq. 3): a row's Critical experts are its ``t_l`` highest."""
    if cfg.router_scoring == "sigmoid":
        return jax.nn.sigmoid(logits) + p["e_score_bias"]
    return jax.nn.softmax(logits, axis=-1)


def route(p, cfg: ModelConfig, logits: jnp.ndarray
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k routing from router logits (..., E). Returns (affinities
    (..., E): softmax probabilities or sigmoid scores; gates (..., k);
    expert indices (..., k)). Softmax: the k most probable, renormalized.
    Sigmoid (DeepSeek-V3 ``noaux_tc``, one group): the k highest of
    affinity plus bias, weighted by their affinity alone. Either way the
    gates are renormalized to sum to one, then scaled by
    ``routed_scaling_factor``."""
    k = cfg.num_experts_per_tok
    if cfg.router_scoring == "sigmoid":
        aff = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(aff + p["e_score_bias"], k)
        gates = jnp.take_along_axis(aff, idx, axis=-1)
    else:
        aff = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(aff, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    if cfg.routed_scaling_factor != 1.0:
        gates = gates * cfg.routed_scaling_factor
    return aff, gates, idx


def quantize_moe(p, cfg: ModelConfig) -> dict:
    """Mixed-precision variants of the routed expert weights (paper §5:
    quantization focuses exclusively on expert layers). Router and shared
    experts stay in working precision."""
    pol = cfg.dymoe
    low = pol.low_bits or None
    return {
        name: MixedPrecisionWeights.build(p[name], pol.high_bits, low,
                                          pol.group_size)
        for name in ("w_gate", "w_up", "w_down")
    }


def _capacity(cfg: ModelConfig, t: int) -> int:
    # HOST-SIDE f64, deliberately: Python-float arithmetic so the
    # truncation is exact and identical wherever this is computed (the
    # scheduler's admission path depends on bit-matching it; an in-graph
    # f32 version can differ by one slot — see moe_apply_prefill_rows).
    # The dtype-discipline linter rule forbids f64 in TRACED serving code;
    # host-side capacity math like this is exactly the allowlisted form.
    c = int(cfg.capacity_factor * t * cfg.num_experts_per_tok
            / cfg.num_experts)
    # An expert can receive at most one capacity slot per token, so c > t
    # buys nothing: min(t, ·) OUTSIDE the floor keeps tiny dispatches tiny
    # (decode: t=1 -> capacity 1, not 8 — 8x less expert compute per row)
    # and can never introduce drops that the old max(8, min(t, c)) floor
    # would have avoided.
    return min(t, max(8, c))


def _expert_ffn(w_gate, w_up, w_down, xb: jnp.ndarray) -> jnp.ndarray:
    """xb: (E, C, dm) -> (E, C, dm) via per-expert SwiGLU."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xb, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", xb, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _moe_blocks(cfg: ModelConfig) -> dict:
    """Pallas tile sizes for the grouped expert matmuls, from the config
    (edge-sized d_model/d_ff configs override the 128/128/512 defaults so
    tiny capacity buffers don't pad to oversized tiles)."""
    pol = cfg.dymoe
    return dict(block_m=pol.block_m, block_n=pol.block_n,
                block_k=pol.block_k)


def scales_after_dot(cfg: ModelConfig, capacity: int) -> bool:
    """Whether the fused grouped dispatch's three expert matmuls, at
    ``capacity`` rows per precision region, apply their group scales after
    the dot: the static choice of
    :func:`repro.kernels.quant_matmul.expert_quant_matmul.grouped_scales_after_dot`
    for the activations' dtype and both contraction widths."""
    from repro.kernels.quant_matmul.expert_quant_matmul import \
        grouped_scales_after_dot

    pol = cfg.dymoe
    return all(grouped_scales_after_dot(
        capacity, k, group_size=pol.group_size, block_m=pol.block_m,
        block_k=pol.block_k, dtype=cfg.dtype)
        for k in (cfg.d_model, cfg.expert_d_ff))


def _over_local_experts(mesh, f, *args):
    """``f(*args)`` where every leaf of ``args`` has a leading expert dim E.

    With an expert-parallel ``mesh`` (``ModelConfig.expert_mesh``), ``f``
    runs under ``shard_map`` over its ``"model"`` axis: each device applies
    the expert kernels to its own experts' slice of the capacity buffer,
    next to its own shard of the packed stores. A Pallas kernel cannot be
    partitioned by the compiler, which would otherwise gather every
    expert's packed codes onto every device. Only the (E, M, dm)
    activations cross chips. With ``mesh=None`` ``f`` runs as is."""
    if mesh is None:
        return f(*args)
    spec = jax.sharding.PartitionSpec(MODEL_AXIS)
    return jax.shard_map(f, mesh=mesh, in_specs=(spec,) * len(args),
                         out_specs=spec, check_vma=False)(*args)


def _expert_ffn_grouped(qweights: dict, xb: jnp.ndarray,
                        counts: jnp.ndarray, *, cap_hi: int,
                        blocks: Optional[dict] = None,
                        mesh=None) -> jnp.ndarray:
    """SwiGLU over ONE combined dual-precision capacity buffer: each
    matmul is a single fused grouped dispatch walking the high region
    ``[0, cap_hi)`` and the low region ``[cap_hi, M)`` in one grid —
    instead of one dispatch per precision — and ``counts`` (E, 2)
    live-slot watermarks let the kernel skip dead row blocks outright
    (finished/evicted/padded slots cost no FLOPs and no weight I/O).
    The kernel takes its N tile from the shapes, so of ``blocks`` only
    ``block_m`` and ``block_k`` reach it. ``mesh``: see
    :func:`_over_local_experts`."""
    from repro.kernels.quant_matmul.ops import expert_quant_matmul_grouped

    tiles = {k: blocks[k] for k in ("block_m", "block_k")} if blocks else {}

    def ffn(qw, xb, counts):
        def mm(name, h):
            return expert_quant_matmul_grouped(h, qw[name], counts,
                                               cap_hi=cap_hi,
                                               out_dtype=xb.dtype, **tiles)

        h = jax.nn.silu(mm("w_gate", xb)) * mm("w_up", xb)
        return mm("w_down", h)

    return _over_local_experts(mesh, ffn, qweights, xb, counts)


def _shared_experts(p, x: jnp.ndarray) -> jnp.ndarray:
    """Always-active shared experts (Qwen2-MoE, DeepSeek-V3): (T, dm) ->
    (T, dm). The sum over the stacked experts is one SwiGLU of their
    summed width."""
    hs = jax.nn.silu(jnp.einsum("td,edf->etf", x, p["shared_w_gate"]))
    hs = hs * jnp.einsum("td,edf->etf", x, p["shared_w_up"])
    return jnp.einsum("etf,efd->td", hs, p["shared_w_down"])


def _expert_ffn_quantized(qw: dict, critical: jnp.ndarray, xb: jnp.ndarray,
                          blocks: Optional[dict] = None,
                          mesh=None) -> jnp.ndarray:
    """xb: (E, C, dm) -> (E, C, dm), every matmul executed straight from the
    packed buffer ``critical`` selects (grouped expert quant-matmul) — no
    dense (E, dm, dff) dequantized weight is ever materialized. In the
    "4/0" deployment sub-critical experts' outputs are zeroed inside the
    kernel, so a skipped expert contributes exactly nothing. ``mesh``: see
    :func:`_over_local_experts`."""
    def ffn(qw, critical, xb):
        def mm(name, h):
            return mixed_precision_matmul(h, qw[name], critical,
                                          skip_to_zero=True,
                                          out_dtype=xb.dtype,
                                          **(blocks or {}))
        h = jax.nn.silu(mm("w_gate", xb)) * mm("w_up", xb)
        return mm("w_down", h)

    return _over_local_experts(mesh, ffn, qw, critical, xb)


def moe_apply(p, cfg: ModelConfig, x: jnp.ndarray, *,
              hh_mask: Optional[jnp.ndarray] = None,
              critical_mask: Optional[jnp.ndarray] = None,
              qweights: Optional[dict] = None,
              token_valid: Optional[jnp.ndarray] = None,
              ) -> Tuple[jnp.ndarray, MoEStats]:
    """Apply the MoE layer to flattened tokens.

    Args:
      x: (T, dm) tokens.
      hh_mask: (T,) float/bool heavy-hitter indicator for Eq. (2) stats.
      critical_mask: (E,) bool — DyMoE precision selection; requires
        ``qweights``. None ⇒ full-precision (training) path.
      qweights: output of :func:`quantize_moe`.
      token_valid: (T,) bool — False marks padding tokens of a ragged
        batch: they take no capacity slot, produce zero output, and are
        excluded from every routing statistic, so a padded row's stats
        equal the unpadded row's.
    Returns:
      (y (T, dm), MoEStats)
    """
    t, dm = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    c = _capacity(cfg, t)

    with jax.named_scope("router"):
        logits = x.astype(jnp.float32) @ p["wg_router"]      # (T, E)
        probs, gates, idx = route(p, cfg, logits)            # (T, k)

    with jax.named_scope("moe_dispatch"):
        flat_e = idx.reshape(-1)                             # (T*k,)
        oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)      # (T*k, E)
        if token_valid is not None:
            valid_rep = jnp.repeat(token_valid.astype(bool), k)  # (T*k,)
            oh = oh * valid_rep[:, None].astype(oh.dtype)
        pos = jnp.cumsum(oh, axis=0) - 1                     # running count
        pos_in_e = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
        keep = pos_in_e < c
        if token_valid is not None:
            keep = keep & valid_rep   # pads: no slot, no gathered output
        slot = jnp.clip(pos_in_e, 0, c - 1)

        tok = jnp.repeat(jnp.arange(t), k)                   # (T*k,)
        xb = jnp.where(keep[:, None], x[tok], 0)
        buf = jnp.zeros((e, c, dm), x.dtype).at[flat_e, slot].add(
            xb.astype(x.dtype), mode="drop")

    with jax.named_scope("experts"):
        if critical_mask is not None:
            assert qweights is not None
            yb = _expert_ffn_quantized(qweights, critical_mask, buf,
                                       _moe_blocks(cfg),
                                       mesh=cfg.expert_mesh)  # (E, C, dm)
        else:
            yb = _expert_ffn(p["w_gate"], p["w_up"], p["w_down"], buf)

    with jax.named_scope("moe_combine"):
        ye = yb[flat_e, slot]                                # (T*k, dm)
        ye = jnp.where(keep[:, None], ye, 0) * gates.reshape(
            -1, 1).astype(x.dtype)
        y = ye.reshape(t, k, dm).sum(axis=1)

    if cfg.num_shared_experts:
        with jax.named_scope("experts"), jax.named_scope("shared_experts"):
            y = y + _shared_experts(p, x)

    # ----- statistics / losses (over valid tokens only) -----
    with jax.named_scope("router"):
        onehot_top = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # (T, k, E)
        if token_valid is not None:
            tv = token_valid.astype(jnp.float32)
            onehot_top = onehot_top * tv[:, None, None]
            n_valid = jnp.maximum(tv.sum(), 1.0)
            frac_probs = jnp.einsum("te,t->e", probs, tv) / n_valid
            z_loss = jnp.sum(jax.nn.logsumexp(logits, axis=-1) ** 2
                             * tv) / n_valid
            dropped = 1.0 - keep.sum() / jnp.maximum(valid_rep.sum(), 1.0)
        else:
            frac_probs = probs.mean(axis=0)
            z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
            dropped = 1.0 - keep.mean()
        load = onehot_top.sum(axis=(0, 1))                       # (E,)
        frac_tokens = load / jnp.maximum(load.sum(), 1.0)
        lb_loss = e * jnp.sum(frac_tokens * frac_probs)
        aux = cfg.router_aux_coef * lb_loss + cfg.router_z_coef * z_loss

        if hh_mask is None:
            hh_mask = jnp.zeros((t,), jnp.float32)
        hh_load = jnp.einsum("tke,t->e", onehot_top,
                             hh_mask.astype(jnp.float32))
        gate_sum = jnp.einsum("tke,tk->e", onehot_top,
                              gates.astype(jnp.float32))
        gate_mean = gate_sum / jnp.maximum(load, 1.0)

        stats = MoEStats(
            router_logits=logits,
            expert_load=load,
            expert_hh_load=hh_load,
            gate_mean=gate_mean,
            aux_loss=aux,
            dropped_frac=dropped,
        )
    return y, stats


def moe_apply_rows(p, cfg: ModelConfig, x: jnp.ndarray,
                   critical_rows: jnp.ndarray, qweights: dict, *,
                   live: Optional[jnp.ndarray] = None,
                   capacity: Optional[int] = None,
                   ) -> Tuple[jnp.ndarray, dict]:
    """Decode-time MoE where every row carries its OWN Critical mask.

    The continuous-batching decode needs per-request precision selection
    (a shared batch-mean mask would make a request's tokens depend on its
    batch neighbours). Naively that means one expert dispatch per row —
    B× the weight unpacking. Instead tokens are dispatched into TWO
    precision regions of ONE shared capacity buffer per expert — high
    slots then low slots — keyed by what the token's row selected for
    that expert, and the whole buffer runs a SINGLE fused grouped
    quant-matmul per expert matmul (:func:`_expert_ffn_grouped`): both
    precision streams execute in one kernel grid, each unpacked once
    regardless of B, and each token's math is bit-identical to the solo
    (B=1) path. Under "4/0" (``low is None``) the low region is never
    built and its precision group is elided from the grid — exact zeros,
    no I/O, matching the solo kernel's zeroing of sub-critical experts.

    ``live`` (B,) bool marks rows whose token is real: finished, evicted,
    or padded rows' tokens take NO capacity slot, and the per-expert
    occupancy watermarks handed to the kernel make their row blocks
    generate no grid steps — a done-mask translates into skipped FLOPs
    and skipped weight I/O, not just zeroed telemetry. Dead rows' y is
    exact zero (their logits/stats are garbage by contract — the batched
    decode freezes their token and masks their telemetry). ``capacity``
    (static, requires ``live``) shrinks each precision region from B to
    the chunk's live-row bound: an (expert, precision) pair can receive
    at most one slot per LIVE row, so ``capacity >= live_count`` can
    never drop a token — buffer memory and the dispatch scatter shrink
    with occupancy.

    x: (B, dm) one token per row; critical_rows: (B, E) bool.
    Returns (y (B, dm), per-row stats: {"active" (B, E) bool,
    "gate_mean" (B, E), "router_logits" (B, E)}).
    """
    b, dm = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    if capacity is None:
        c = b
    else:
        assert live is not None, \
            "capacity < B requires the live mask that bounds occupancy"
        c = max(1, min(int(capacity), b))

    with jax.named_scope("router"):
        logits = x.astype(jnp.float32) @ p["wg_router"]      # (B, E)
        _, gates, idx = route(p, cfg, logits)                # (B, k)

    def place(select):
        """Slot index inside the (expert, precision-stream) capacity
        region plus the per-expert occupancy count; selected tokens pack
        from slot 0, so the count IS the kernel's live-slot watermark."""
        ohs = oh * select[:, None].astype(oh.dtype)
        pos = jnp.cumsum(ohs, axis=0) - 1
        pos_in_e = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
        return jnp.clip(pos_in_e, 0, c - 1), jnp.minimum(ohs.sum(axis=0), c)

    skip_low = qweights["w_gate"].low is None            # "4/0"
    with jax.named_scope("moe_dispatch"):
        crit_tok = jnp.take_along_axis(critical_rows.astype(bool), idx,
                                       axis=1)
        flat_e = idx.reshape(-1)                             # (B*k,)
        flat_c = crit_tok.reshape(-1)
        oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)      # (B*k, E)
        if live is not None:
            live_rep = jnp.repeat(jnp.asarray(live).astype(bool), k)
            sel_hi = flat_c & live_rep
            sel_lo = ~flat_c & live_rep
        else:
            sel_hi, sel_lo = flat_c, ~flat_c
        tok = jnp.repeat(jnp.arange(b), k)
        slot_hi, n_hi = place(sel_hi)
        xb_hi = jnp.where(sel_hi[:, None], x[tok], 0)
        width = c if skip_low else 2 * c
        buf = jnp.zeros((e, width, dm), x.dtype).at[
            flat_e, slot_hi].add(xb_hi.astype(x.dtype), mode="drop")
        if skip_low:
            counts = jnp.stack([n_hi, jnp.zeros_like(n_hi)], axis=1)
        else:
            slot_lo, n_lo = place(sel_lo)
            xb_lo = jnp.where(sel_lo[:, None], x[tok], 0)
            buf = buf.at[flat_e, c + slot_lo].add(
                xb_lo.astype(x.dtype), mode="drop")
            counts = jnp.stack([n_hi, n_lo], axis=1)
    with jax.named_scope("experts"):
        yb = _expert_ffn_grouped(qweights, buf, counts, cap_hi=c,
                                 blocks=_moe_blocks(cfg),
                                 mesh=cfg.expert_mesh)
    with jax.named_scope("moe_combine"):
        if skip_low:
            ye = jnp.where(sel_hi[:, None], yb[flat_e, slot_hi], 0.0)
        else:
            ye = jnp.where(sel_hi[:, None], yb[flat_e, slot_hi],
                           jnp.where(sel_lo[:, None],
                                     yb[flat_e, c + slot_lo], 0.0))
        ye = ye * gates.reshape(-1, 1).astype(x.dtype)
        y = ye.reshape(b, k, dm).sum(axis=1)

    if cfg.num_shared_experts:
        with jax.named_scope("experts"), jax.named_scope("shared_experts"):
            y = y + _shared_experts(p, x)

    with jax.named_scope("router"):
        onehot_top = jax.nn.one_hot(idx, e, dtype=jnp.float32)  # (B, k, E)
        load = onehot_top.sum(axis=1)                           # (B, E)
        gate_sum = jnp.einsum("bke,bk->be", onehot_top,
                              gates.astype(jnp.float32))
        stats = dict(active=load > 0,
                     gate_mean=gate_sum / jnp.maximum(load, 1.0),
                     router_logits=logits)
    return y, stats


def moe_apply_prefill_rows(p, cfg: ModelConfig, x: jnp.ndarray,
                           critical_rows: jnp.ndarray, qweights: dict, *,
                           rows: int,
                           hh_mask: Optional[jnp.ndarray] = None,
                           token_valid: Optional[jnp.ndarray] = None,
                           row_capacities: Optional[jnp.ndarray] = None,
                           ) -> Tuple[jnp.ndarray, dict]:
    """Prefill-shaped MoE where every ROW carries its own Critical mask —
    :func:`moe_apply_rows`' dual-buffer trick at prefill shapes.

    A batched admission prefill must not couple its rows: with one shared
    Critical set, request A's importance profile would pick request B's
    expert precisions and B's tokens would stop matching its solo prefill.
    Instead each token inherits its ROW's (rows, E) mask and is dispatched
    into one of TWO precision regions — row-local high-precision slots and
    row-local low-precision slots — of ONE combined capacity buffer per
    expert, and every expert matmul is a SINGLE fused grouped dispatch
    (``expert_quant_matmul_grouped``) walking both regions in one kernel
    grid, so weights still unpack once per precision stream regardless of
    how many admissions share the batch. Per-(expert, region) occupancy
    watermarks let the kernel skip slot blocks beyond the highest
    occupied slot — padded tokens of a ragged admission wave cost no
    FLOPs and no weight I/O.

    Solo-parity details the scheduler's admission path relies on:
      * capacity is enforced PER ROW at the row's own solo budget
        ``_capacity(cfg, len_i)`` (``len_i`` = the row's valid-token
        count), with within-row slot order equal to the solo cumsum order,
        so a token is dropped here iff the solo prefill drops it;
      * tokens of a padded (``token_valid`` False) position take no slot
        and produce exact zeros;
      * under "4/0" (``low is None``) the low buffer is never built — no
        I/O, exact zeros — matching the solo kernel's in-kernel zeroing of
        sub-critical experts.

    x: (T, dm) tokens flattened from (rows, S) row-major; critical_rows:
    (rows, E) bool; hh_mask/token_valid: (T,). ``row_capacities`` (rows,)
    overrides the in-graph capacity computation with host-computed
    ``_capacity(cfg, len_i)`` values — the in-graph fallback runs the
    formula in f32, whose truncation can differ from the host's f64 by
    one slot for some (capacity_factor, length) pairs, so callers that
    know the row lengths (the scheduler's admission path) pass the exact
    values. Returns (y (T, dm), per-row stats:
    {"active"/"load"/"hh_load"/"gate_mean" (rows, E),
    "router_logits" (T, E), "aux_loss", "dropped_frac" scalars}).
    """
    t, dm = x.shape
    b = rows
    assert t % b == 0, (t, b)
    s = t // b
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    cmax = _capacity(cfg, s)      # static per-row buffer stride (>= c_row)

    with jax.named_scope("router"):
        logits = x.astype(jnp.float32) @ p["wg_router"]      # (T, E)
        probs, gates, idx = route(p, cfg, logits)            # (T, k)

    def stream_pos(select):
        """Within-ROW running slot index of each (token, k) pair inside the
        ``select``-ed stream (cumsum resets at row boundaries — the solo
        order), and the keep mask at the row's solo capacity."""
        ohs = oh * select[:, None].astype(oh.dtype)
        pos = jnp.cumsum(ohs.reshape(b, s * k, e), axis=1
                         ).reshape(t * k, e) - 1
        pos_in_e = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
        keep = select & (pos_in_e < c_row[row_rep])
        return pos_in_e, keep

    with jax.named_scope("moe_dispatch"):
        flat_e = idx.reshape(-1)                             # (T*k,)
        row_rep = jnp.repeat(jnp.arange(b), s * k)      # (T*k,) token's row
        crit_tok = jnp.take_along_axis(
            critical_rows.astype(bool)[jnp.repeat(jnp.arange(b), s)], idx,
            axis=1)                                          # (T, k)
        flat_c = crit_tok.reshape(-1)
        if token_valid is not None:
            valid_rep = jnp.repeat(token_valid.astype(bool), k)
            lens = token_valid.astype(jnp.int32).reshape(b, s).sum(axis=1)
        else:
            valid_rep = jnp.ones((t * k,), bool)
            lens = jnp.full((b,), s, jnp.int32)
        # per-row solo capacity: same formula as _capacity at the row's
        # own valid length, so batched drops reproduce the solo prefill's
        if row_capacities is not None:
            c_row = jnp.asarray(row_capacities, jnp.int32)   # (B,) exact
        else:
            c_row = jnp.minimum(lens, jnp.maximum(8, (
                jnp.float32(cfg.capacity_factor) * lens.astype(jnp.float32)
                * k / e).astype(jnp.int32)))                 # (B,)
        oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)      # (T*k, E)
        tok_of = jnp.repeat(jnp.arange(t), k)

        sel_hi = flat_c & valid_rep
        sel_lo = ~flat_c & valid_rep
    skip_low = qweights["w_gate"].low is None            # "4/0"
    cap = b * cmax

    def watermark(keep, slot):
        """Highest occupied slot + 1 per expert — regions are row-local
        here (not packed from 0), so the watermark, not the occupancy
        count, bounds the kernel's live blocks."""
        return jnp.zeros((e,), jnp.int32).at[flat_e].max(
            jnp.where(keep, slot + 1, 0).astype(jnp.int32), mode="drop")

    with jax.named_scope("moe_dispatch"):
        pos_hi, keep_hi = stream_pos(sel_hi)
        slot_hi = row_rep * cmax + jnp.clip(pos_hi, 0, cmax - 1)
        xbh = jnp.where(keep_hi[:, None], x[tok_of], 0)
        width = cap if skip_low else 2 * cap
        buf = jnp.zeros((e, width, dm), x.dtype).at[
            flat_e, slot_hi].add(xbh.astype(x.dtype), mode="drop")
        if skip_low:
            counts = jnp.stack([watermark(keep_hi, slot_hi),
                                jnp.zeros((e,), jnp.int32)], axis=1)
        else:
            pos_lo, keep_lo = stream_pos(sel_lo)
            slot_lo = row_rep * cmax + jnp.clip(pos_lo, 0, cmax - 1)
            xbl = jnp.where(keep_lo[:, None], x[tok_of], 0)
            buf = buf.at[flat_e, cap + slot_lo].add(
                xbl.astype(x.dtype), mode="drop")
            counts = jnp.stack([watermark(keep_hi, slot_hi),
                                watermark(keep_lo, slot_lo)], axis=1)
    with jax.named_scope("experts"):
        y_all = _expert_ffn_grouped(qweights, buf, counts, cap_hi=cap,
                                    blocks=_moe_blocks(cfg),
                                    mesh=cfg.expert_mesh)
    with jax.named_scope("moe_combine"):
        if skip_low:
            ye = jnp.where(keep_hi[:, None], y_all[flat_e, slot_hi], 0.0)
        else:
            ye = jnp.where(keep_hi[:, None], y_all[flat_e, slot_hi],
                           jnp.where(keep_lo[:, None],
                                     y_all[flat_e, cap + slot_lo], 0.0))
    if skip_low:
        with jax.named_scope("router"):
            # stats only: solo counts these
            _, keep_lo = stream_pos(sel_lo)
    with jax.named_scope("moe_combine"):
        ye = ye * gates.reshape(-1, 1).astype(x.dtype)
        y = ye.reshape(t, k, dm).sum(axis=1)

    if cfg.num_shared_experts:
        with jax.named_scope("experts"), jax.named_scope("shared_experts"):
            y = y + _shared_experts(p, x)

    # ----- per-row statistics (each row's block == its solo stats) -----
    with jax.named_scope("router"):
        onehot_top = jax.nn.one_hot(idx, e, dtype=jnp.float32)   # (T, k, E)
        if token_valid is not None:
            tv = token_valid.astype(jnp.float32)
            onehot_top = onehot_top * tv[:, None, None]
            n_valid = jnp.maximum(tv.sum(), 1.0)
            frac_probs = jnp.einsum("te,t->e", probs, tv) / n_valid
            z_loss = jnp.sum(jax.nn.logsumexp(logits, axis=-1) ** 2 * tv) \
                / n_valid
        else:
            frac_probs = probs.mean(axis=0)
            z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        kept = keep_hi | keep_lo
        dropped = 1.0 - kept.sum() / jnp.maximum(valid_rep.sum(), 1)
        oh_r = onehot_top.reshape(b, s, k, e)
        load = oh_r.sum(axis=(1, 2))                             # (B, E)
        if hh_mask is None:
            hh_mask = jnp.zeros((t,), jnp.float32)
        hh_load = jnp.einsum("bske,bs->be", oh_r,
                             hh_mask.astype(jnp.float32).reshape(b, s))
        gate_sum = jnp.einsum("bske,bsk->be", oh_r,
                              gates.astype(jnp.float32).reshape(b, s, k))
        gate_mean = gate_sum / jnp.maximum(load, 1.0)
        load_all = load.sum(axis=0)
        frac_tokens = load_all / jnp.maximum(load_all.sum(), 1.0)
        lb_loss = e * jnp.sum(frac_tokens * frac_probs)
        aux = cfg.router_aux_coef * lb_loss + cfg.router_z_coef * z_loss
        stats = dict(active=load > 0, load=load, hh_load=hh_load,
                     gate_mean=gate_mean, router_logits=logits,
                     aux_loss=aux, dropped_frac=dropped)
    return y, stats
