"""Percentiles over every request, and the FLOP counts."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.harness import flops
from bench.harness.stats import percentile

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


@pytest.mark.parametrize("q", [0, 5, 50, 95, 100])
def test_percentile_matches_numpy_linear(q):
    xs = np.random.default_rng(0).lognormal(size=37).tolist()
    assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        percentile([], 95)


def test_flops_by_hand_for_olmoe():
    spec = json.loads((CONFIGS / "olmoe-1b-7b.json").read_text())
    dm, L, V = 2048, 16, 50304
    attn = dm * 16 * 128 * 3 + 16 * 128 * dm
    experts = 8 * 3 * dm * 1024
    per_tok = 2 * ((attn + experts + dm * 64) * L + dm * V)
    assert flops.weight_flops_per_token(spec) == per_tok
    assert flops.decode_token_flops(spec, 300) == \
        per_tok + 4 * 16 * 128 * 300 * L


def test_flops_agree_with_the_programs_estimate_plus_added_terms():
    """The copied count: the program's ``model_flops_estimate`` (decode,
    one token) plus the router and attention terms added here."""
    from bench.families import moe_transformer as fam
    from repro.roofline.analysis import model_flops_estimate

    spec = json.loads((CONFIGS / "qwen3-30b-a3b-16l.json").read_text())
    cfg = fam.program_config(spec)
    base = model_flops_estimate(cfg, tokens=1, phase="decode")
    router = 2 * 2048 * 128 * 16
    assert flops.decode_token_flops(spec, 10) == pytest.approx(
        base + router + flops.attention_flops(spec, 10))


def test_tokens_produced_spreads_each_delivery_over_its_interval():
    from types import SimpleNamespace as R

    from bench.harness.cell import tokens_produced

    # one client: 16 tokens every 2 s from t=1; the window [2, 7] holds
    # 2.5 intervals' worth whatever the phase of the deliveries
    rec = R(submitted=0.0, arrivals=[(1.0, 1)] + [(1.0 + 2 * i, 16)
                                                  for i in range(1, 6)])
    assert tokens_produced([rec], 2.0, 7.0) == pytest.approx(16 * 2.5)
    assert tokens_produced([rec], 2.5, 7.5) == pytest.approx(16 * 2.5)
    # a delivery at one instant inside the window counts whole
    burst = R(submitted=3.0, arrivals=[(3.0, 4)])
    assert tokens_produced([burst], 2.0, 7.0) == 4
