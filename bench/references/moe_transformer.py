"""Plain reference of a pre-norm mixture-of-experts transformer under the
DyMoE precision policy, in float32 ``jax.numpy``.

It imports nothing of the program. It draws its weights from the seed by
the recipe the configuration file names (``weights``), quantizes the expert
weights itself, and follows the published description:

* blocks: ``x += Attn(RMSNorm(x)); x += MoE(RMSNorm(x))``, RMSNorm scales of
  one; attention with per-head RMSNorm on q and k, rotary position
  embedding (half-split), causal softmax, grouped KV heads; an untied LM
  head;
* router: ``softmax(h @ W_r)``, top-k experts, gates renormalized to sum to
  one; each expert a SwiGLU ``W_down(silu(h W_gate) * h W_up)``;
* DyMoE (arXiv:2603.19172): expert weights are stored at two precisions by
  symmetric group-wise round-to-nearest along the input dimension (``bits``
  high and low, ``group_size`` rows per scale). Layer ``l`` keeps
  ``t_l = ceil(r(l) * E)`` Critical experts, ``r(l) = (1 - lam)(cos(pi l /
  (L - 1)) + 1) / 2 + lam`` with ``lam = 2 * retention - 1``. Critical
  experts run at the high precision, the rest at the low one.
  - Prompt tokens (prefill, Eq. 1-2): a token's importance is the attention
    mass it receives, summed over heads and queries of its prompt and
    divided by the head count; the ``round(heavy_hitter_frac * P)`` most
    important are heavy hitters; an expert's importance is the number of
    heavy hitters routed to it, ties broken by its total load; the
    ``t_l`` most important (lower index first on ties) are Critical for the
    whole prompt. Each expert keeps the first ``min(P, max(8,
    int(capacity_factor * P * k / E)))`` prompt tokens routed to it, in
    position order; later ones get nothing from that expert.
  - Generated tokens (decode, Eq. 3): a token's Critical experts are the
    ``t_l`` largest of its own router probabilities. Nothing is dropped.

``logits_at`` returns, for each sequence, the reference's logits at the
positions that predicted its served tokens. The caller compares.
"""
from __future__ import annotations

import math
from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int):
    """The PRNG key of a seed of any size: ``PRNGKey`` keeps 32 bits, so
    the higher bits are folded in."""
    if seed < 0:
        raise ValueError(f"seed {seed} < 0")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


# ------------------------------------------------------------------ sizes

def sizes(spec: dict) -> dict:
    dm = spec["hidden_size"]
    h = spec["num_attention_heads"]
    pol = spec["dymoe"]
    return dict(
        dm=dm, h=h, hk=spec["num_key_value_heads"],
        d=spec.get("head_dim") or dm // h,
        L=spec["num_hidden_layers"], V=spec["vocab_size"],
        E=spec["num_experts"], k=spec["num_experts_per_tok"],
        dff=spec.get("moe_intermediate_size") or spec["intermediate_size"],
        eps=spec["rms_norm_eps"], theta=spec["rope_theta"],
        hi=pol["high_bits"], lo=pol["low_bits"], g=pol["group_size"],
        retention=pol["retention"], hh_frac=pol["heavy_hitter_frac"],
        cf=spec["capacity_factor"],
    )


def critical_counts(s: dict) -> List[int]:
    lam = min(1.0, max(0.0, 2.0 * s["retention"] - 1.0))
    out = []
    for l in range(s["L"]):
        frac = l / (s["L"] - 1) if s["L"] > 1 else 0.0
        r = (1.0 - lam) * (math.cos(math.pi * frac) + 1.0) / 2.0 + lam \
            if s["L"] > 1 else 1.0
        out.append(max(1, min(s["E"], math.ceil(r * s["E"]))))
    return out


def prompt_capacity(s: dict, p: int) -> int:
    c = int(s["cf"] * p * s["k"] / s["E"])
    return min(p, max(8, c))


# ---------------------------------------------------------------- weights
# The "normal_bf16" recipe: every matrix N(0, 1) times 1/sqrt(fan-in),
# stored in bfloat16 (the router in float32), keys split as below. Norm
# scales are one.

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


def layer_keys(s: dict, key):
    return _outer_keys(key, s["L"])[2]


def layer_weights(s: dict, lkey):
    dm, h, hk, d = s["dm"], s["h"], s["hk"], s["d"]
    e, dff = s["E"], s["dff"]
    k1, k2 = jax.random.split(lkey)
    ka = jax.random.split(k1, 4)
    bf = jnp.bfloat16
    attn = dict(
        wq=(jax.random.normal(ka[0], (dm, h * d)) * dm ** -0.5).astype(bf),
        wk=(jax.random.normal(ka[1], (dm, hk * d)) * dm ** -0.5).astype(bf),
        wv=(jax.random.normal(ka[2], (dm, hk * d)) * dm ** -0.5).astype(bf),
        wo=(jax.random.normal(ka[3], (h * d, dm)) * (h * d) ** -0.5
            ).astype(bf))
    km = jax.random.split(k2, 7)
    moe = dict(
        router=(jax.random.normal(km[0], (dm, e)) * dm ** -0.5
                ).astype(jnp.float32),
        w_gate=(jax.random.normal(km[1], (e, dm, dff)) * dm ** -0.5
                ).astype(bf),
        w_up=(jax.random.normal(km[2], (e, dm, dff)) * dm ** -0.5
              ).astype(bf),
        w_down=(jax.random.normal(km[3], (e, dff, dm)) * dff ** -0.5
                ).astype(bf))
    return attn, moe


def rtn(w, bits: int, group: int):
    """Symmetric group-wise round-to-nearest along axis -2, dequantized to
    float32: codes in [-2^(b-1), 2^(b-1) - 1], scale = absmax / (2^(b-1) -
    1) per group of ``group`` input rows and output column."""
    *lead, kin, n = w.shape
    qmax = (1 << (bits - 1)) - 1
    wg = w.astype(jnp.float32).reshape(*lead, kin // group, group, n)
    scale = jnp.max(jnp.abs(wg), axis=-2, keepdims=True) / qmax
    safe = jnp.where(scale == 0.0, 1.0, scale)
    q = jnp.clip(jnp.round(wg / safe), -qmax - 1, qmax)
    return (q * scale).reshape(*lead, kin, n)


# ---------------------------------------------------------------- forward

def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis`` (the
    largest magnitude maps to 448): the control's matmul operands."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0.0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _operands(control: str):
    """(activation, weight) rounding of the operands of every matmul
    (projections, attention scores and values, router, experts, head):
    identity; float8 along the contracted axis (per row of the left
    operand, per column of the right) under the ``fp8`` control; bfloat16
    under ``bf16``."""
    if control == "fp8":
        return partial(_fp8, axis=-1), partial(_fp8, axis=-2)
    if control == "bf16":
        bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
        return bf, bf
    ident = lambda a: a
    return ident, ident


def _attention(s, attn, x, n_prompt, n_total, control):
    """One sequence: x (T, dm) -> (out (T, dm), prompt token importance
    (T,)). Positions at or past ``n_total`` are padding."""
    t = x.shape[0]
    h, hk, d = s["h"], s["hk"], s["d"]
    qa, qw = _operands(control)
    mm = lambda a, w: jnp.matmul(qa(a), qw(w.astype(jnp.float32)),
                                 precision=HIGHEST)
    q = mm(x, attn["wq"]).reshape(t, h, d)
    k = mm(x, attn["wk"]).reshape(t, hk, d)
    v = mm(x, attn["wv"]).reshape(t, hk, d)
    pos = jnp.arange(t)
    q = _rope(_rms(q, s["eps"]).transpose(1, 0, 2), pos, s["theta"])
    k = _rope(_rms(k, s["eps"]).transpose(1, 0, 2), pos, s["theta"])
    v = v.transpose(1, 0, 2)
    g = h // hk
    qg = q.reshape(hk, g, t, d)
    logits = jnp.einsum("kgqd,kpd->kgqp", qa(qg), qa(k),
                        precision=HIGHEST) * d ** -0.5
    causal = pos[:, None] >= pos[None, :]
    logits = jnp.where(causal, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("kgqp,kpd->kgqd", qa(p), qw(v), precision=HIGHEST)
    out = out.reshape(h, t, d).transpose(1, 0, 2).reshape(t, h * d)
    in_prompt = pos < n_prompt
    mass = jnp.einsum("kgqp,q->p", p, in_prompt.astype(jnp.float32),
                      precision=HIGHEST) / h
    return mm(out, attn["wo"]), jnp.where(in_prompt, mass, -jnp.inf)


def _rank_below(values, t_l):
    """Per row: True where the entry ranks among the ``t_l`` largest, ties
    going to the lower index."""
    e = values.shape[-1]
    order = jnp.argsort(-values, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return rank < jnp.clip(t_l, 1, e)


def _layer(s, x, n_prompt, n_total, cap, lkey, t_l, control: str):
    """One block for a batch of sequences: x (B, T, dm) float32.
    ``n_prompt``/``n_total``/``cap`` are (B,): prompt length, tokens in
    use (prompt plus served tokens fed back), prompt capacity per expert."""
    attn, moe = layer_weights(s, lkey)
    b, t, dm = x.shape
    e, k = s["E"], s["k"]
    a, mass = jax.lax.map(
        lambda args: _attention(s, attn, *args, control),
        (_rms(x, s["eps"]), n_prompt, n_total))
    x = x + a
    hn = _rms(x, s["eps"])
    pos = jnp.arange(t)[None, :]
    in_prompt = pos < n_prompt[:, None]                      # (B, T)
    valid = pos < n_total[:, None]

    # heavy hitters: the round(frac * P) largest prompt masses (ties kept)
    n_hh = jnp.maximum(1, jnp.round(s["hh_frac"] * n_prompt.astype(
        jnp.float32)).astype(jnp.int32))
    desc = -jnp.sort(-mass, axis=-1)
    thresh = jnp.take_along_axis(desc, (n_hh - 1)[:, None], axis=-1)
    hh = (mass >= thresh) & in_prompt

    qa, qw = _operands(control)
    logits = jnp.matmul(qa(hn), qw(moe["router"].astype(jnp.float32)),
                        precision=HIGHEST)                      # (B, T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                       # (B, T, k)
    gates = gates / gates.sum(-1, keepdims=True)
    routed = jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(2)  # (B, T, E)

    # prompt: one Critical set per sequence, and per-expert capacity
    rp = routed * in_prompt[..., None]
    load = rp.sum(1)                                           # (B, E)
    hh_load = (routed * hh[..., None]).sum(1)
    total = jnp.maximum(load.sum(-1, keepdims=True), 1.0)
    imp = hh_load + load / (total + 1.0)
    crit_prompt = _rank_below(imp, t_l)                        # (B, E)
    before = jnp.cumsum(rp, axis=1) - rp                       # (B, T, E)
    keep_prompt = before < cap[:, None, None]
    # generated tokens: own Critical set, no capacity
    crit_decode = _rank_below(probs, t_l)                      # (B, T, E)
    crit = jnp.where(in_prompt[..., None], crit_prompt[:, None, :],
                     crit_decode)
    keep = jnp.where(in_prompt[..., None], keep_prompt, True) \
        & valid[..., None]
    if control == "experts_low":
        crit = jnp.zeros_like(crit)

    crit_k = jnp.take_along_axis(crit, idx, axis=-1)           # (B, T, k)
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
        """Routed experts of a few sequences: (pairs sorted by expert and
        precision) through one grouped matmul per weight."""
        hn_c, group_c, weight_c = args
        n = hn_c.shape[0] * hn_c.shape[1]
        g = group_c.reshape(-1)
        order = jnp.argsort(g, stable=True)
        sizes_ = jnp.bincount(g, length=2 * e).astype(jnp.int32)
        tok = jnp.repeat(jnp.arange(n), k)[order]
        xs = hn_c.reshape(n, dm)[tok]
        rd = partial(jax.lax.ragged_dot, group_sizes=sizes_,
                     precision=HIGHEST)
        xs = qa(xs)
        hid = jax.nn.silu(rd(xs, w_gate)) * rd(xs, w_up)
        ys = rd(qa(hid), w_down) * weight_c.reshape(-1)[order][:, None]
        return jnp.zeros((n, dm), jnp.float32).at[tok].add(
            ys).reshape(hn_c.shape)

    rows = s["moe_rows"]
    split = lambda a: a.reshape(b // rows, rows, *a.shape[1:])
    y = jax.lax.map(experts, (split(hn), split(group), split(weight)))
    return x + y.reshape(b, t, dm)


@partial(jax.jit, static_argnames=("spec_items", "control"))
def _layer_jit(x, n_prompt, n_total, cap, lkey, t_l, *, spec_items,
               control):
    return _layer(dict(spec_items), x, n_prompt, n_total, cap, lkey, t_l,
                  control)


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
    precision instead: ``experts_low`` runs every expert at the low bit
    width, ``fp8`` rounds the operands of every matmul to float8 e4m3,
    ``bf16`` to bfloat16. Runs layer by layer; each layer's
    program draws that layer's weights from the seed."""
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
        for l, lk in enumerate(layer_keys(s, key)):
            x = _layer_jit(x, np_, nt_, cap, lk, jnp.int32(t_ls[l]),
                           spec_items=items, control=control)
        return _logits_at(x, head, jnp.asarray(rows_pos, jnp.int32),
                          eps=s["eps"], control=control)
