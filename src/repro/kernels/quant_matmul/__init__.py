from repro.kernels.quant_matmul.ops import expert_quant_matmul, \
    expert_quant_matmul_grouped

__all__ = ["expert_quant_matmul", "expert_quant_matmul_grouped"]
