"""The absorbed latent-attention decode kernel (Pallas, interpret mode on
the CPU) against its jnp path: rows of unequal length, a row of length 1,
an empty row, a row whose live slots start past slot 0, and a frozen row
of a slot batch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.mla_decode.mla_decode import mla_decode_attention, \
    pick_block
from repro.kernels.mla_decode.ref import mla_decode_attention_ref
from repro.models.kv_cache import init_latent_cache, update_kv_cache

H, R, DR = 4, 64, 16


def _inputs(rows, s, seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k[0], (rows, H, R + DR))
    ckv = jax.random.normal(k[1], (rows, s, R)).astype(dtype)
    kpe = jax.random.normal(k[2], (rows, s, DR)).astype(dtype)
    return q, ckv, kpe


def _both(q, ckv, kpe, starts, ends, block_s, **kw):
    starts = jnp.asarray(starts, jnp.int32)
    ends = jnp.asarray(ends, jnp.int32)
    got = mla_decode_attention(q, ckv, kpe, starts, ends, scale=0.125,
                               block_s=block_s, interpret=True, **kw)
    want = mla_decode_attention_ref(q, ckv, kpe, starts, ends, scale=0.125,
                                    **kw)
    return np.asarray(got), np.asarray(want)


@pytest.mark.parametrize("block_s", [32, 96])
def test_rows_of_unequal_length(block_s):
    """Lengths 96 (every block), 1, 0 (no live slot: zeros), 41 and a row
    whose slots start at 5 (a left-padded admission): online softmax over
    blocks and the per-row stop agree with one softmax over the row. f32
    throughout, so the two differ by summation order alone (1e-5)."""
    q, ckv, kpe = _inputs(5, 96)
    starts, ends = [0, 0, 0, 0, 5], [96, 1, 0, 41, 60]
    got, want = _both(q, ckv, kpe, starts, ends, block_s)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert not got[2].any()
    # one live slot: the output is that slot's latent, for every head
    np.testing.assert_allclose(got[1], np.broadcast_to(ckv[1, 0], (H, R)),
                               rtol=1e-6, atol=1e-6)


def test_bf16_cache_with_bf16_operands():
    """A bf16 cache with bf16 MXU operands, as the served model holds it:
    scores and sums stay f32 on both paths (bf16 rounding of the
    probabilities: 2e-2)."""
    q, ckv, kpe = _inputs(3, 64, seed=1, dtype=jnp.bfloat16)
    got, want = _both(q, ckv, kpe, [0, 0, 3], [64, 17, 40], 32,
                      compute_dtype=jnp.bfloat16)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_frozen_row_keeps_its_cache_and_its_output():
    """A slot batch of three rows decodes one step with row 1 frozen (a
    finished row): its cache length and slots stay as they were, the
    kernel stops at its old length, and its output repeats the previous
    step's; the live rows grow by one slot. Kernel and jnp path agree."""
    s, rows = 48, 3
    cache = init_latent_cache(rows, s, R, DR, jnp.float32)
    q, ckv, kpe = _inputs(rows, s + 1, seed=2)
    for t in range(5):
        cache = update_kv_cache(cache, ckv[:, None, t:t + 1],
                                kpe[:, None, t:t + 1])

    def attend(cache):
        ends = cache.offset + cache.length
        return _both(q, cache.k[:, 0], cache.v[:, 0], cache.offset, ends, 16)

    before, _ = attend(cache)
    live = jnp.asarray([True, False, True])
    stepped = update_kv_cache(cache, ckv[:, None, 5:6], kpe[:, None, 5:6],
                              live=live)
    np.testing.assert_array_equal(stepped.length, [6, 5, 6])
    np.testing.assert_array_equal(stepped.k[1], cache.k[1])
    got, want = attend(stepped)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[1], before[1], rtol=1e-6, atol=1e-6)
    assert not np.allclose(got[0], before[0])


@pytest.mark.parametrize("s,target,want", [
    (2816, 512, 256), (768, 512, 384), (4096, 512, 512), (96, 32, 32),
    (40, 512, 40), (48, 32, 16)])
def test_pick_block(s, target, want):
    """Whole lanes where a multiple of 128 divides the window, else whole
    bf16 sublane tiles, else the window."""
    assert pick_block(s, target) == want
