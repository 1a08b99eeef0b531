"""Moonlight-16B-A3B: the DeepSeek-V3 block at 16B total / 3B active
[hf:moonshotai/Moonlight-16B-A3B, config.json, model_type deepseek_v3].

27 layers at d_model 2048: layer 0 a dense SwiGLU of width 11264
(``first_k_dense_replace`` 1), then 26 MoE layers of 64 routed experts of
width 1408 top-6 plus 2 shared experts. The router scores each expert by
a sigmoid, ranks by that score plus a per-expert correction bias
(``noaux_tc``, one group), renormalizes the selected scores and scales
them by 2.446. Attention is multi-head latent attention: 16 heads, no q
LoRA, a 512-wide latent KV plus a 64-wide shared rope key, q·k 128 + 64
and v 128 per head; rope theta 50000, no scaling.
"""
from repro.models.config import DyMoEPolicy, ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b",
        arch_type="moe",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=1,
        head_dim=192,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_theta=50000.0,
        d_ff=11264,
        moe_d_ff=1408,
        first_k_dense=1,
        num_experts=64,
        num_experts_per_tok=6,
        num_shared_experts=2,
        router_scoring="sigmoid",
        routed_scaling_factor=2.446,
        vocab_size=163840,
        pos_emb="rope",
        dtype="bfloat16",
        norm_eps=1e-5,
        max_seq_len=8192,
        # block_m=32 as OLMoE: 64 experts top-6 leave each expert's
        # capacity region a few rows deep at decode; block_n=128 tiles N
        # in the critical-mask kernel only: the grouped kernel takes its
        # N tile from the shapes (all of moe_d_ff=1408 at decode)
        dymoe=DyMoEPolicy(high_bits=4, low_bits=2, retention=0.75,
                          block_m=32, block_n=128, block_k=512),
        source="DeepSeek-V3 block, 64 routed top-6 + 2 shared, MLA "
               "[hf:moonshotai/Moonlight-16B-A3B]",
    )
