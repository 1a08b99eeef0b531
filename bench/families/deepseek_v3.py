"""How a ``deepseek_v3`` configuration file becomes the program's
``ModelConfig``, and how the program's weights are made from the seed.

As for ``moe_transformer``: the model's shape, numerics and DyMoE policy
come from the file; the program's own entry (``program_config``) supplies
only what the file does not state (kernel tile sizes and other tuning).
"""
from __future__ import annotations

import dataclasses

REFERENCE = "deepseek_v3"

# keys whose value the program cannot change: the run would depart from
# the file, so a file stating another value is refused
_FIXED = {"hidden_act": "silu", "norm_topk_prob": True,
          "attention_bias": False, "q_lora_rank": None,
          "scoring_func": "sigmoid", "topk_method": "noaux_tc",
          "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
          "rope_scaling": None, "weights": "normal_bf16",
          "torch_dtype": "bfloat16"}


def program_config(spec: dict):
    from repro.configs import get_config

    for key, want in _FIXED.items():
        if spec.get(key, want) != want:
            raise ValueError(f"{spec['name']}: {key}={spec[key]!r}; the "
                             f"program runs only {want!r}")
    base = get_config(spec["program_config"])
    pol = spec["dymoe"]
    cfg = dataclasses.replace(
        base,
        name=spec["name"],
        arch_type="moe",
        num_layers=spec["num_hidden_layers"],
        first_k_dense=spec["first_k_dense_replace"],
        d_model=spec["hidden_size"],
        num_heads=spec["num_attention_heads"],
        num_kv_heads=1,
        head_dim=spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"],
        kv_lora_rank=spec["kv_lora_rank"],
        qk_nope_head_dim=spec["qk_nope_head_dim"],
        qk_rope_head_dim=spec["qk_rope_head_dim"],
        v_head_dim=spec["v_head_dim"],
        d_ff=spec["intermediate_size"],
        moe_d_ff=spec["moe_intermediate_size"],
        num_experts=spec["n_routed_experts"],
        num_experts_per_tok=spec["num_experts_per_tok"],
        num_shared_experts=spec["n_shared_experts"],
        router_scoring="sigmoid",
        routed_scaling_factor=float(spec["routed_scaling_factor"]),
        vocab_size=spec["vocab_size"],
        qk_norm=False, qkv_bias=False,
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
