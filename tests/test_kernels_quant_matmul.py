"""Dense quantized matmul vs the pure-jnp dequantize-then-dot oracle,
swept over shapes / bit-widths / block shapes / dtypes.

Dense quantized layers (the dense MLP, a DeepSeek-V3 dense lead layer,
the SSM projections) run one packed matrix through
``mixed_precision_matmul``, which sends it to the mask-select expert
kernel as a 1-expert group; here that kernel runs in interpret mode."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.quant_matmul.ref import quant_matmul_ref
from repro.quant import MixedPrecisionWeights, mixed_precision_matmul


def _weights(k, n, bits, group, seed):
    w = np.random.default_rng(seed).standard_normal((k, n))
    return MixedPrecisionWeights.build(jnp.asarray(w, jnp.float32), bits,
                                       None, group)


def _pallas(x, mp, bm, bn, bk):
    return mixed_precision_matmul(x, mp, True, skip_to_zero=False,
                                  impl="pallas", interpret=True, block_m=bm,
                                  block_n=bn, block_k=bk,
                                  out_dtype=jnp.float32)


def _case(m, k, n, bits, group, bm, bn, bk, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((m, k)), dtype)
    mp = _weights(k, n, bits, group, seed + 1)
    qt = mp.high
    ref = quant_matmul_ref(x, qt.packed, qt.scales, bits=bits,
                           group_size=group, out_dtype=jnp.float32)
    pal = _pallas(x, mp, bm, bn, bk)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                               atol=5e-4, rtol=1e-4)


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_bits_sweep(bits):
    _case(16, 128, 32, bits, 32, 8, 16, 64)


@pytest.mark.parametrize("m,k,n", [(8, 64, 16), (32, 256, 64), (1, 128, 8)])
def test_shape_sweep(m, k, n):
    _case(m, k, n, 4, 32, min(8, m), 8, 64)


@pytest.mark.parametrize("bm,bn,bk", [(4, 8, 32), (16, 16, 128),
                                      (8, 32, 64)])
def test_block_sweep(bm, bn, bk):
    _case(16, 128, 32, 4, 32, bm, bn, bk)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dtype_sweep(dtype):
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((8, 64)), dtype)
    mp = _weights(64, 16, 4, 32, 5)
    qt = mp.high
    ref = quant_matmul_ref(x, qt.packed, qt.scales, bits=4, group_size=32,
                           out_dtype=jnp.float32)
    pal = _pallas(x, mp, 8, 16, 32)
    np.testing.assert_allclose(np.asarray(ref, np.float32),
                               np.asarray(pal, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_multi_k_blocks_accumulate():
    # K split across 4 grid steps exercises the scratch accumulator path
    _case(8, 512, 16, 4, 64, 8, 16, 128)


def test_leading_dims_reshape():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((2, 3, 64)), jnp.float32)
    mp = _weights(64, 16, 4, 32, 6)
    qt = mp.high
    y = _pallas(x, mp, 8, 16, 32)
    assert y.shape == (2, 3, 16)
    ref = quant_matmul_ref(x.reshape(6, 64), qt.packed, qt.scales, bits=4,
                           group_size=32, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y).reshape(6, 16),
                               np.asarray(ref), atol=5e-4, rtol=1e-4)
