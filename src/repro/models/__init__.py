"""Model zoo substrate: composable decoder-only transformer / SSM / hybrid
model definitions in functional JAX (pure pytrees, no framework deps).
"""
from repro.models.config import ModelConfig, DyMoEPolicy
from repro.models.model import (
    init_params,
    init_quantized_params,
    drop_dense_experts,
    quantize_model,
    forward,
    loss_fn,
    train_step_fn,
    prefill,
    decode_step,
    decode_many,
    decode_many_batched,
    init_decode_state,
    DyMoEInfo,
)

__all__ = [
    "ModelConfig",
    "DyMoEPolicy",
    "init_params",
    "init_quantized_params",
    "drop_dense_experts",
    "quantize_model",
    "forward",
    "loss_fn",
    "train_step_fn",
    "prefill",
    "decode_step",
    "decode_many",
    "decode_many_batched",
    "init_decode_state",
    "DyMoEInfo",
]
