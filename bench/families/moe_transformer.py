"""How a ``moe_transformer`` configuration file becomes the program's
``ModelConfig``, and how the program's weights are made from the seed.

The model's shape, numerics and DyMoE policy come from the file; the
program's own entry for ``program_config`` supplies only what the file
does not state (kernel tile sizes and other tuning), so a later change to
the program's tuning reaches the benchmark and a change to a width does
not.
"""
from __future__ import annotations

import dataclasses

REFERENCE = "moe_transformer"

# keys whose value the program cannot change: the run would depart from
# the file, so a file stating another value is refused
_FIXED = {"hidden_act": "silu", "norm_topk_prob": True,
          "qk_norm": "per_head", "weights": "normal_bf16",
          "torch_dtype": "bfloat16"}


def program_config(spec: dict):
    from repro.configs import get_config

    for key, want in _FIXED.items():
        if spec.get(key, want) != want:
            raise ValueError(f"{spec['name']}: {key}={spec[key]!r}; the "
                             f"program runs only {want!r}")
    base = get_config(spec["program_config"])
    dff = spec.get("moe_intermediate_size") or spec["intermediate_size"]
    pol = spec["dymoe"]
    cfg = dataclasses.replace(
        base,
        name=spec["name"],
        arch_type="moe",
        num_layers=spec["num_hidden_layers"],
        d_model=spec["hidden_size"],
        num_heads=spec["num_attention_heads"],
        num_kv_heads=spec["num_key_value_heads"],
        head_dim=spec.get("head_dim") or
        spec["hidden_size"] // spec["num_attention_heads"],
        d_ff=dff, moe_d_ff=dff,
        num_experts=spec["num_experts"],
        num_experts_per_tok=spec["num_experts_per_tok"],
        num_shared_experts=0,
        vocab_size=spec["vocab_size"],
        qk_norm=True, qkv_bias=spec["attention_bias"],
        rope_theta=float(spec["rope_theta"]), pos_emb="rope",
        norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=spec["tie_word_embeddings"],
        sliding_window=None, dtype="bfloat16",
        capacity_factor=float(spec["capacity_factor"]),
        dymoe=dataclasses.replace(
            base.dymoe, enabled=True, high_bits=pol["high_bits"],
            low_bits=pol["low_bits"], group_size=pol["group_size"],
            retention=pol["retention"],
            heavy_hitter_frac=pol["heavy_hitter_frac"],
            depth_schedule=pol["depth_schedule"]),
    )
    cfg.validate()
    return cfg


def init_weights(cfg, key):
    """``(params, qparams)`` on the device, from the seed's key, through
    the program's own layer-by-layer construction of the packed stores."""
    from repro.models.model import init_quantized_params

    return init_quantized_params(cfg, key)
