"""The latent-attention byte and FLOP counts on a hand-computed case, and
the three readers of the ``moonlight-16b-a3b-16l.doc_qa`` cell on a
synthetic trace."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.harness import cell, flops_mla, mla
from bench.harness import trace as tr

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


@pytest.fixture(scope="module")
def spec():
    return json.loads((CONFIGS / "moonlight-16b-a3b-16l.json").read_text())


def test_latent_bytes_by_hand(spec):
    # (512 latent + 64 rope key) x 2 B x 16 layers
    assert mla.latent_bytes_per_token(spec) == 576 * 2 * 16 == 18432
    assert mla.decode_read_bytes(spec, 1000) == 18432 * 1000


def test_decode_flops_by_hand(spec):
    dm, h, L, V = 2048, 16, 16, 163840
    attn = (dm * h * 192 + dm * 576 + h * 128 * 512 + h * 512 * 128
            + h * 128 * dm)
    dense = 3 * dm * 11264
    moe = dm * 64 + (6 + 2) * 3 * dm * 1408
    per_tok = 2 * (attn * L + dense + moe * 15 + dm * V)
    assert flops_mla.weight_flops_per_token(spec) == per_tok
    # per head and context token: scores over 512 + 64, sum over 512
    assert flops_mla.attention_flops(spec, 300) == \
        2 * h * 300 * (512 + 64 + 512) * L
    assert flops_mla.decode_token_flops(spec, 300) == \
        per_tok + flops_mla.attention_flops(spec, 300)


def _ctx(spec, chunk=4, layers=16, moe_layers=15, prompt=100):
    """One decode chunk of ``chunk`` steps (3 expert-kernel calls per MoE
    layer and step, one latent-attention call per layer and step) inside
    a window, and one client that got its first token from the prefill
    and ``chunk`` tokens at the chunk's end. Times in ns; the window opens
    at host time t0 = 1 s."""
    mods = [("jit_decode_many_batched(1)", 1_000, 101_000)]
    ops, t = [], 1_000
    for _ in range(chunk):
        for _ in range(moe_layers * 3):
            ops.append(("%expert_quant_matmul_grouped_pallas.1 = x", t,
                        t + 100))
            t += 100
        for _ in range(layers):
            ops.append(("%mla_decode_attention.3 = x", t, t + 250))
            t += 250
    trace = tr.Trace(device={"XLA Modules": mods, "XLA Ops": ops}, host=[],
                     window=(0, 200_000))
    rec = SimpleNamespace(prompt_len=prompt, submitted=0.0,
                          arrivals=[(1.0 + 500e-9, 1),
                                    (1.0 + 101_500e-9, chunk)])
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return SimpleNamespace(trace=trace, records=[rec], t0=1.0, t1=1.0002,
                           spec=spec, peaks=peaks, decode_chunk=chunk)


def test_mla_readers_on_a_synthetic_trace(spec):
    ctx = _ctx(spec)
    kernel_ns = 4 * 16 * 250
    assert cell.load_metric("mla_decode_ms_per_step").read(ctx) == \
        pytest.approx(kernel_ns / 4 / 1e6)
    # tokens 1..4 of a 100-token prompt see contexts 101..104
    nbytes = sum(mla.decode_read_bytes(spec, 100 + j) for j in range(1, 5))
    assert cell.load_metric("mla_decode_roofline").read(ctx) == \
        pytest.approx(100 * nbytes / (kernel_ns / 1e9 * 819e9))
    work = sum(flops_mla.decode_token_flops(spec, 100 + j)
               for j in range(1, 5))
    assert cell.load_metric("decode_mfu_pct.mla").read(ctx) == \
        pytest.approx(100 * work / (100_000 / 1e9 * 197e12))


def test_mla_readers_find_nothing_without_the_kernel(spec):
    """A program without the latent-attention kernel (another model, or a
    parent commit) reads no kernel time and no share: the readers return
    None and do not raise."""
    ctx = _ctx(spec)
    ctx.trace.device["XLA Ops"] = [
        ev for ev in ctx.trace.ops() if "mla_decode" not in ev[0]]
    assert cell.load_metric("mla_decode_ms_per_step").read(ctx) is None
    assert cell.load_metric("mla_decode_roofline").read(ctx) is None
    empty = SimpleNamespace(**{**vars(ctx), "records": []})
    assert cell.load_metric("decode_mfu_pct.mla").read(empty) is None
