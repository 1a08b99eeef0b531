"""Pallas TPU kernels for DyMoE's compute hot-spots.

* ``expert_quant_matmul`` — fused unpack+dequant+matmul for int8/int4/int2
  packed weights, grouped per expert: one kernel runs every expert's
  matmul straight from the packed buffer its runtime critical mask selects
  (high-bit / low-bit / "0-bit" skip). Dense quantized layers run it as a
  1-expert group.
* ``expert_quant_matmul_grouped`` — both precision regions of one combined
  per-expert capacity buffer in one dispatch (the per-row MoE).
* ``attn_scores`` — flash attention that additionally accumulates per-key
  attention mass, the heavy-hitter token score of DyMoE Eq. (1), which a
  standard flash kernel never materializes.

Each kernel directory has ``<name>.py`` (pallas_call + BlockSpec),
``ops.py`` (jit'd public wrapper with jnp fallback) and ``ref.py``
(pure-jnp oracle used by tests).
"""
from repro.kernels.quant_matmul.ops import expert_quant_matmul, \
    expert_quant_matmul_grouped
from repro.kernels.attn_scores.ops import flash_attention_with_scores

__all__ = ["expert_quant_matmul", "expert_quant_matmul_grouped",
           "flash_attention_with_scores"]
