"""Model FLOPs of a mixture-of-experts transformer, from its published
sizes.

Copied from the program's ``roofline.analysis.model_flops_estimate`` (the
MoE branch: attention projections, the routed experts a token selects, and
the unembedding, at 2 FLOPs per weight per token), with two terms added:

* attention over the context: a query at context ``c`` (itself and the
  ``c - 1`` keys before it) costs ``4 * heads * head_dim * c`` per layer,
  ``QK^T`` plus ``PV``;
* the router: ``2 * d_model * experts`` per layer.

These count the work the model needs, not what a program computes: padding,
capacity slack and recomputation are not model FLOPs. All sizes come from
the configuration file's keys (``bench/configs/<name>.json``).
"""
from __future__ import annotations


def _sizes(spec: dict) -> dict:
    dm = spec["hidden_size"]
    h = spec["num_attention_heads"]
    return dict(
        dm=dm, h=h, hk=spec["num_key_value_heads"],
        d=spec.get("head_dim") or dm // h,
        L=spec["num_hidden_layers"], V=spec["vocab_size"],
        E=spec["num_experts"], k=spec["num_experts_per_tok"],
        dff=spec.get("moe_intermediate_size") or spec["intermediate_size"],
    )


def weight_flops_per_token(spec: dict) -> float:
    """2 x the weights one token multiplies by: attention projections,
    router and selected experts in every layer, plus the unembedding."""
    s = _sizes(spec)
    attn = s["dm"] * (s["h"] + 2 * s["hk"]) * s["d"] + s["h"] * s["d"] * s["dm"]
    experts = s["k"] * 3 * s["dm"] * s["dff"]
    router = s["dm"] * s["E"]
    return 2.0 * ((attn + experts + router) * s["L"] + s["dm"] * s["V"])


def attention_flops(spec: dict, context: int) -> float:
    """QK^T and PV of one query over ``context`` keys, all layers."""
    s = _sizes(spec)
    return 4.0 * s["h"] * s["d"] * context * s["L"]


def decode_token_flops(spec: dict, context: int) -> float:
    """One decoded token whose query sees ``context`` keys (the prompt,
    the tokens decoded before it, and itself)."""
    return weight_flops_per_token(spec) + attention_flops(spec, context)

