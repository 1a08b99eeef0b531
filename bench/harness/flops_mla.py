"""Model FLOPs of one decoded token of a DeepSeek-V3-block model (latent
attention, a leading dense layer, shared and routed experts), from its
published sizes, at 2 FLOPs per multiply-add.

Per layer, latent attention in its absorbed decode form:

* projections: ``W_q`` (d_model -> heads x (qk_nope + qk_rope)), ``W_kv_a``
  (d_model -> kv_lora_rank + qk_rope), the query's key up-projection
  (heads x qk_nope -> heads x kv_lora_rank), the output's value
  up-projection (heads x kv_lora_rank -> heads x v_head) and ``W_o``
  (heads x v_head -> d_model);
* attention over the context: per head and context token, a score over
  kv_lora_rank + qk_rope values and a sum over kv_lora_rank values.

Then, per dense layer, a SwiGLU of width ``intermediate_size``; per MoE
layer, the router (d_model x routed experts), the ``num_experts_per_tok``
selected routed experts and the ``n_shared_experts`` shared ones, each a
SwiGLU of width ``moe_intermediate_size``; and the unembedding. These
count the work the model needs, not what the program computes: padding,
capacity slack, dead rows and recomputation are not model FLOPs. Sizes
come from the configuration file's keys.
"""
from __future__ import annotations


def _sizes(spec: dict) -> dict:
    return dict(
        dm=spec["hidden_size"], h=spec["num_attention_heads"],
        r=spec["kv_lora_rank"], dn=spec["qk_nope_head_dim"],
        dr=spec["qk_rope_head_dim"], dv=spec["v_head_dim"],
        L=spec["num_hidden_layers"], nd=spec["first_k_dense_replace"],
        V=spec["vocab_size"], E=spec["n_routed_experts"],
        k=spec["num_experts_per_tok"], ns=spec["n_shared_experts"],
        dff=spec["moe_intermediate_size"],
        dff_dense=spec["intermediate_size"])


def attention_weight_flops(spec: dict) -> float:
    """One token's latent-attention projections in one layer."""
    s = _sizes(spec)
    h = s["h"]
    return 2.0 * (s["dm"] * h * (s["dn"] + s["dr"])
                  + s["dm"] * (s["r"] + s["dr"])
                  + h * s["dn"] * s["r"] + h * s["r"] * s["dv"]
                  + h * s["dv"] * s["dm"])


def attention_flops(spec: dict, context: int) -> float:
    """One query's absorbed attention over ``context`` tokens, all
    layers: scores over latent plus rope key, sum over latents."""
    s = _sizes(spec)
    return 2.0 * s["h"] * context * (2 * s["r"] + s["dr"]) * s["L"]


def weight_flops_per_token(spec: dict) -> float:
    """2 x the weights one decoded token multiplies by, every layer, plus
    the unembedding."""
    s = _sizes(spec)
    swiglu = lambda width: 3 * s["dm"] * width
    dense = swiglu(s["dff_dense"])
    moe = s["dm"] * s["E"] + (s["k"] + s["ns"]) * swiglu(s["dff"])
    return (attention_weight_flops(spec) * s["L"]
            + 2.0 * (dense * s["nd"] + moe * (s["L"] - s["nd"])
                     + s["dm"] * s["V"]))


def decode_token_flops(spec: dict, context: int) -> float:
    """One decoded token whose query sees ``context`` tokens (the prompt,
    the tokens decoded before it, and itself)."""
    return weight_flops_per_token(spec) + attention_flops(spec, context)
