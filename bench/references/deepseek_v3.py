"""Plain reference of the DeepSeek-V3 block under the DyMoE precision
policy, in float32 ``jax.numpy``: decompressed latent attention, no cache,
no kernels.

It imports nothing of the program (helpers come from the other reference,
``moe_transformer.py``, which it does not change). It draws its weights
from the seed by the recipe the configuration file names (``weights``),
quantizes the routed experts itself, and follows the published
description (DeepSeek-V3, arXiv:2412.19437, and the released
``modeling_deepseek.py``):

* blocks: ``x += MLA(RMSNorm(x)); x += FFN(RMSNorm(x))``, RMSNorm scales of
  one; the first ``first_k_dense_replace`` layers take a dense SwiGLU of
  width ``intermediate_size``, the rest the MoE below; an untied LM head;
* latent attention, no q LoRA: ``q = x W_q`` split per head into
  ``qk_nope_head_dim`` and ``qk_rope_head_dim`` values; ``x W_kv_a`` gives
  the latent (``kv_lora_rank`` values, RMS-normed) and one rope key shared
  by the heads; ``latent W_kv_b`` gives each head's ``k_nope`` and ``v``.
  Rotary embedding pairs elements ``2i`` and ``2i + 1`` (DeepSeek's
  interleaved layout) at frequency ``theta^(-2i/d)``; scores over
  ``sqrt(qk_nope + qk_rope)``; causal softmax;
* router: ``s = sigmoid(h W_r)``; the ``num_experts_per_tok`` experts of
  largest ``s + b`` (``b`` the correction bias; ``noaux_tc`` with one
  group) are selected and weighted by their ``s``, renormalized to sum to
  one (``norm_topk_prob``) and times ``routed_scaling_factor``; each
  routed expert a SwiGLU of width ``moe_intermediate_size``; plus
  ``n_shared_experts`` shared SwiGLU experts of that width on every token,
  unweighted, in bfloat16 weights (never quantized);
* DyMoE (arXiv:2603.19172) over the MoE layers only, as in
  ``moe_transformer.py``: routed expert weights at two precisions by
  symmetric group-wise round-to-nearest; MoE layer ``m`` of ``M`` keeps
  ``t_m = ceil(r(m) * E)`` Critical experts. Prompt tokens (Eq. 1-2): a
  token's importance is the attention mass it receives, summed over heads
  and prompt queries and divided by the head count; heavy hitters, expert
  importance, the per-prompt Critical set and the per-expert prompt
  capacity as there. Generated tokens (Eq. 3): a token's Critical experts
  are the ``t_m`` largest of its selection scores ``s + b``.

Departures from the released model: the correction bias is drawn from
the seed (it is a trained buffer there); weights are random
(``normal_bf16``); prompt tokens beyond an expert's capacity get nothing
from it (the program's admission drops them; the released model is
dropless); the leading dense layer and the shared experts stay in
bfloat16 weights, as the program keeps them.

``forward_rows`` returns, for each sequence, the reference's logits at the
positions that predicted its served tokens. The caller compares.
"""
from __future__ import annotations

import math
from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from bench.references.moe_transformer import HIGHEST, _operands, \
    _rank_below, _rms, _rope, rtn, seed_key

__all__ = ["seed_key", "sizes", "critical_counts", "route", "forward_rows"]


# ------------------------------------------------------------------ sizes

def sizes(spec: dict) -> dict:
    pol = spec["dymoe"]
    return dict(
        dm=spec["hidden_size"], h=spec["num_attention_heads"],
        r=spec["kv_lora_rank"], dn=spec["qk_nope_head_dim"],
        dr=spec["qk_rope_head_dim"], dv=spec["v_head_dim"],
        L=spec["num_hidden_layers"], nd=spec["first_k_dense_replace"],
        V=spec["vocab_size"], E=spec["n_routed_experts"],
        k=spec["num_experts_per_tok"], ns=spec["n_shared_experts"],
        dff=spec["moe_intermediate_size"], dff_dense=spec[
            "intermediate_size"],
        eps=spec["rms_norm_eps"], theta=float(spec["rope_theta"]),
        rsf=float(spec["routed_scaling_factor"]),
        norm_topk=bool(spec["norm_topk_prob"]),
        hi=pol["high_bits"], lo=pol["low_bits"], g=pol["group_size"],
        retention=pol["retention"], hh_frac=pol["heavy_hitter_frac"],
        cf=spec["capacity_factor"],
    )


def critical_counts(s: dict) -> List[int]:
    """t_m over the MoE layers (every layer after the dense ones)."""
    lam = min(1.0, max(0.0, 2.0 * s["retention"] - 1.0))
    m = s["L"] - s["nd"]
    out = []
    for l in range(m):
        frac = l / (m - 1) if m > 1 else 0.0
        r = (1.0 - lam) * (math.cos(math.pi * frac) + 1.0) / 2.0 + lam \
            if m > 1 else 1.0
        out.append(max(1, min(s["E"], math.ceil(r * s["E"]))))
    return out


def prompt_capacity(s: dict, p: int) -> int:
    return min(p, max(8, int(s["cf"] * p * s["k"] / s["E"])))


# ---------------------------------------------------------------- weights
# The "normal_bf16" recipe: every matrix N(0, 1) times 1/sqrt(fan-in),
# stored in bfloat16 (the router in float32), norm scales one; the
# correction bias N(0, 0.1^2) in float32. Keys: the seed's key splits
# into (embedding, head, layers, unused); the layers' key into one key per
# layer; a layer's key into (attention, FFN); attention's into (W_q,
# W_kv_a, W_kv_b, W_o); a dense FFN's into (W_up, W_down, W_gate); an MoE
# FFN's into 7 (router, W_gate, W_up, W_down, shared W_gate, W_up, W_down)
# and the bias from the FFN key folded with 7.

def _outer_keys(key, n_layers):
    k_embed, k_head, k_layers, _ = jax.random.split(key, 4)
    return k_embed, k_head, jax.random.split(k_layers, n_layers)


def outer_weights(s: dict, key):
    k_embed, k_head, _ = _outer_keys(key, s["L"])
    dm, V = s["dm"], s["V"]
    embed = (jax.random.normal(k_embed, (V, dm)) * dm ** -0.5
             ).astype(jnp.bfloat16)
    head = (jax.random.normal(k_head, (dm, V)) * dm ** -0.5
            ).astype(jnp.bfloat16)
    return embed, head


def _normal(key, shape, fan_in, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape) * fan_in ** -0.5).astype(dtype)


def attention_weights(s: dict, key):
    dm, h, r = s["dm"], s["h"], s["r"]
    dn, dr, dv = s["dn"], s["dr"], s["dv"]
    ka = jax.random.split(key, 4)
    return dict(wq=_normal(ka[0], (dm, h * (dn + dr)), dm),
                wkv_a=_normal(ka[1], (dm, r + dr), dm),
                wkv_b=_normal(ka[2], (r, h * (dn + dv)), r),
                wo=_normal(ka[3], (h * dv, dm), h * dv))


def dense_weights(s: dict, key):
    dm, dff = s["dm"], s["dff_dense"]
    kf = jax.random.split(key, 3)
    return dict(w_up=_normal(kf[0], (dm, dff), dm),
                w_down=_normal(kf[1], (dff, dm), dff),
                w_gate=_normal(kf[2], (dm, dff), dm))


def moe_weights(s: dict, key):
    dm, e, dff, ns = s["dm"], s["E"], s["dff"], s["ns"]
    km = jax.random.split(key, 7)
    return dict(
        router=_normal(km[0], (dm, e), dm, jnp.float32),
        w_gate=_normal(km[1], (e, dm, dff), dm),
        w_up=_normal(km[2], (e, dm, dff), dm),
        w_down=_normal(km[3], (e, dff, dm), dff),
        shared_gate=_normal(km[4], (ns, dm, dff), dm),
        shared_up=_normal(km[5], (ns, dm, dff), dm),
        shared_down=_normal(km[6], (ns, dff, dm), dff),
        bias=jax.random.normal(jax.random.fold_in(key, 7), (e,),
                               jnp.float32) * 0.1)


# ---------------------------------------------------------------- forward

def _rope_pairs(x, pos, theta):
    """Rotary embedding pairing elements 2i and 2i + 1 (the result in
    half-split order, as the released code leaves it)."""
    return _rope(jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1), pos,
                 theta)


def _attention(s, w, x, n_prompt, control):
    """One sequence: x (T, dm) -> (out (T, dm), prompt token importance
    (T,))."""
    t = x.shape[0]
    h, r, dn, dv = s["h"], s["r"], s["dn"], s["dv"]
    qa, qw = _operands(control)
    mm = lambda a, b: jnp.matmul(qa(a), qw(b.astype(jnp.float32)),
                                 precision=HIGHEST)
    pos = jnp.arange(t)
    q = mm(x, w["wq"]).reshape(t, h, -1).transpose(1, 0, 2)   # (h, t, .)
    kv = mm(x, w["wkv_a"])
    lat = _rms(kv[:, :r], s["eps"])
    k_pe = _rope_pairs(kv[:, r:], pos, s["theta"])             # (t, dr)
    q_pe = _rope_pairs(q[..., dn:], pos, s["theta"])           # (h, t, dr)
    kvb = mm(lat, w["wkv_b"]).reshape(t, h, dn + dv).transpose(1, 0, 2)
    logits = (jnp.einsum("hqd,hpd->hqp", qa(q[..., :dn]), qa(kvb[..., :dn]),
                         precision=HIGHEST)
              + jnp.einsum("hqd,pd->hqp", qa(q_pe), qa(k_pe),
                           precision=HIGHEST)) * (dn + s["dr"]) ** -0.5
    causal = pos[:, None] >= pos[None, :]
    p = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    out = jnp.einsum("hqp,hpd->qhd", qa(p), qw(kvb[..., dn:]),
                     precision=HIGHEST).reshape(t, h * dv)
    in_prompt = pos < n_prompt
    mass = jnp.einsum("hqp,q->p", p, in_prompt.astype(jnp.float32),
                      precision=HIGHEST) / h
    return mm(out, w["wo"]), jnp.where(in_prompt, mass, -jnp.inf)


def _swiglu(qa, qw, x, w_gate, w_up, w_down):
    mm = lambda a, b: jnp.matmul(qa(a), qw(b.astype(jnp.float32)),
                                 precision=HIGHEST)
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def _dense_layer(s, x, n_prompt, lkey, control: str):
    """A leading dense layer for a batch of sequences, x (B, T, dm)."""
    k1, k2 = jax.random.split(lkey)
    attn, ffn = attention_weights(s, k1), dense_weights(s, k2)
    a, _ = jax.lax.map(lambda args: _attention(s, attn, *args, control),
                       (_rms(x, s["eps"]), n_prompt))
    x = x + a
    qa, qw = _operands(control)
    return x + _swiglu(qa, qw, _rms(x, s["eps"]), ffn["w_gate"],
                       ffn["w_up"], ffn["w_down"])


def route(s: dict, aff, bias):
    """The ``noaux_tc`` router with one group, from sigmoid affinities
    (..., E): (selection scores ``aff + bias``, gates (..., k), expert
    indices (..., k))."""
    select = aff + bias
    _, idx = jax.lax.top_k(select, s["k"])
    gates = jnp.take_along_axis(aff, idx, axis=-1)
    if s["norm_topk"]:
        gates = gates / gates.sum(-1, keepdims=True)
    return select, gates * s["rsf"], idx


def _moe_layer(s, x, n_prompt, n_total, cap, lkey, t_l, control: str):
    """An MoE layer for a batch of sequences: x (B, T, dm) float32.
    ``n_prompt``/``n_total``/``cap`` are (B,): prompt length, tokens in use
    (prompt plus served tokens fed back), prompt capacity per expert."""
    k1, k2 = jax.random.split(lkey)
    attn, moe = attention_weights(s, k1), moe_weights(s, k2)
    b, t, dm = x.shape
    e, k = s["E"], s["k"]
    a, mass = jax.lax.map(lambda args: _attention(s, attn, *args, control),
                          (_rms(x, s["eps"]), n_prompt))
    x = x + a
    hn = _rms(x, s["eps"])
    pos = jnp.arange(t)[None, :]
    in_prompt = pos < n_prompt[:, None]                      # (B, T)
    valid = pos < n_total[:, None]

    n_hh = jnp.maximum(1, jnp.round(s["hh_frac"] * n_prompt.astype(
        jnp.float32)).astype(jnp.int32))
    desc = -jnp.sort(-mass, axis=-1)
    thresh = jnp.take_along_axis(desc, (n_hh - 1)[:, None], axis=-1)
    hh = (mass >= thresh) & in_prompt

    qa, qw = _operands(control)
    aff = jax.nn.sigmoid(jnp.matmul(qa(hn), qw(moe["router"]),
                                    precision=HIGHEST))        # (B, T, E)
    select, gates, idx = route(s, aff, moe["bias"])            # (B, T, k)
    routed = jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(2)  # (B, T, E)

    rp = routed * in_prompt[..., None]
    load = rp.sum(1)                                           # (B, E)
    hh_load = (routed * hh[..., None]).sum(1)
    total = jnp.maximum(load.sum(-1, keepdims=True), 1.0)
    crit_prompt = _rank_below(hh_load + load / (total + 1.0), t_l)
    before = jnp.cumsum(rp, axis=1) - rp
    keep_prompt = before < cap[:, None, None]
    crit_decode = _rank_below(select, t_l)                     # (B, T, E)
    crit = jnp.where(in_prompt[..., None], crit_prompt[:, None, :],
                     crit_decode)
    keep = jnp.where(in_prompt[..., None], keep_prompt, True) \
        & valid[..., None]
    if control == "experts_low":
        crit = jnp.zeros_like(crit)

    crit_k = jnp.take_along_axis(crit, idx, axis=-1)
    keep_k = jnp.take_along_axis(keep, idx, axis=-1)
    weight = gates * keep_k                                    # (B, T, k)
    group = 2 * idx + jnp.where(crit_k, 0, 1)

    def stack(w):   # (E, in, out) -> (2E, in, out): high, low per expert
        return qw(jnp.stack([rtn(w, s["hi"], s["g"]),
                             rtn(w, s["lo"], s["g"])],
                            axis=1).reshape(2 * e, *w.shape[1:]))

    w_gate, w_up, w_down = (stack(moe[n]) for n in ("w_gate", "w_up",
                                                    "w_down"))

    def experts(args):
        """Routed experts of a few sequences, (pairs sorted by expert and
        precision) through one grouped matmul per weight, plus the shared
        experts."""
        hn_c, group_c, weight_c = args
        n = hn_c.shape[0] * hn_c.shape[1]
        g = group_c.reshape(-1)
        order = jnp.argsort(g, stable=True)
        sizes_ = jnp.bincount(g, length=2 * e).astype(jnp.int32)
        tok = jnp.repeat(jnp.arange(n), k)[order]
        flat = hn_c.reshape(n, dm)
        xs = qa(flat[tok])
        rd = partial(jax.lax.ragged_dot, group_sizes=sizes_,
                     precision=HIGHEST)
        hid = jax.nn.silu(rd(xs, w_gate)) * rd(xs, w_up)
        ys = rd(qa(hid), w_down) * weight_c.reshape(-1)[order][:, None]
        y = jnp.zeros((n, dm), jnp.float32).at[tok].add(ys)
        for i in range(s["ns"]):
            y = y + _swiglu(qa, qw, flat, moe["shared_gate"][i],
                            moe["shared_up"][i], moe["shared_down"][i])
        return y.reshape(hn_c.shape)

    rows = s["moe_rows"]
    split = lambda a: a.reshape(b // rows, rows, *a.shape[1:])
    y = jax.lax.map(experts, (split(hn), split(group), split(weight)))
    return x + y.reshape(b, t, dm)


@partial(jax.jit, static_argnames=("spec_items", "control"))
def _dense_jit(x, n_prompt, lkey, *, spec_items, control):
    return _dense_layer(dict(spec_items), x, n_prompt, lkey, control)


@partial(jax.jit, static_argnames=("spec_items", "control"))
def _moe_jit(x, n_prompt, n_total, cap, lkey, t_l, *, spec_items, control):
    return _moe_layer(dict(spec_items), x, n_prompt, n_total, cap, lkey,
                      t_l, control)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


@partial(jax.jit, static_argnames=("eps", "control"))
def _logits_at(x, head, rows_pos, *, eps, control):
    """Final norm and LM head at ``rows_pos`` (B, N) of x (B, T, dm)."""
    qa, qw = _operands(control)
    rows = jnp.take_along_axis(_rms(x, eps), rows_pos[..., None], axis=1)
    return jnp.matmul(qa(rows), qw(head.astype(jnp.float32)),
                      precision=HIGHEST)


def forward_rows(spec: dict, seed: int, tokens: np.ndarray,
                 n_prompt: np.ndarray, n_total: np.ndarray,
                 rows_pos: np.ndarray, *, control: str = "",
                 moe_rows: int = 4):
    """Reference logits (B, N, V), a device array, at positions
    ``rows_pos`` (B, N) of ``tokens`` (B, T). ``control`` computes a lower
    precision instead: ``experts_low`` runs every routed expert at the low
    bit width, ``fp8`` rounds the operands of every matmul to float8 e4m3,
    ``bf16`` to bfloat16. Runs layer by layer; each layer's program draws
    that layer's weights from the seed."""
    s = dict(sizes(spec), moe_rows=min(moe_rows, len(tokens)))
    if len(tokens) % s["moe_rows"]:
        raise ValueError(f"{len(tokens)} sequences, not a multiple of "
                         f"{s['moe_rows']}")
    key = seed_key(seed)
    items = tuple(sorted(s.items()))
    t_ls = critical_counts(s)
    cap = jnp.asarray([prompt_capacity(s, int(p)) for p in n_prompt],
                      jnp.int32)
    with jax.default_matmul_precision("highest"):
        embed, head = outer_weights(s, key)
        x = _embed(embed, jnp.asarray(tokens, jnp.int32))
        del embed
        np_ = jnp.asarray(n_prompt, jnp.int32)
        nt_ = jnp.asarray(n_total, jnp.int32)
        for l, lk in enumerate(_outer_keys(key, s["L"])[2]):
            if l < s["nd"]:
                x = _dense_jit(x, np_, lk, spec_items=items,
                               control=control)
            else:
                x = _moe_jit(x, np_, nt_, cap, lk,
                             jnp.int32(t_ls[l - s["nd"]]),
                             spec_items=items, control=control)
        return _logits_at(x, head, jnp.asarray(rows_pos, jnp.int32),
                          eps=s["eps"], control=control)
