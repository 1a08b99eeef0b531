from repro.kernels.mla_decode.ops import mla_decode_attention

__all__ = ["mla_decode_attention"]
