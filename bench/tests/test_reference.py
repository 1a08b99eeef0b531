"""The plain reference against the served path at a reduced size on the
CPU, the control, and faults planted in the timed path.

The program here runs its activations in float32 over the configuration's
bfloat16 weights, so the served path and the reference compute the same
mathematics and every served token must be the reference's argmax: the
DyMoE policy (row-local Critical sets of a ragged admission wave, prompt
capacity drops, per-token Critical sets at decode, both precisions)
agrees exactly. On the chip the program runs in bfloat16 and the limits in
``bench/limits`` absorb its rounding."""
import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.families import moe_transformer as fam
from bench.harness import cell, correct
from bench.references import moe_transformer as ref

DATA = Path(__file__).resolve().parent / "data"
SEED = 2 ** 31 + 77


@pytest.fixture(scope="module")
def spec():
    return cell.load_config("tiny-moe", DATA / "configs")


def _f32_program(monkeypatch):
    """Serve with float32 activations over the bfloat16 weights."""
    orig_cfg, orig_init = fam.program_config, fam.init_weights

    def program_config(spec):
        return dataclasses.replace(orig_cfg(spec), dtype="float32")

    def init_weights(cfg, key):
        params, qparams = orig_init(dataclasses.replace(
            cfg, dtype="bfloat16"), key)
        params = jax.tree.map(
            lambda a: a.astype(jnp.float32)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
        return params, qparams

    monkeypatch.setattr(fam, "program_config", program_config)
    monkeypatch.setattr(fam, "init_weights", init_weights)


BENCH = {"end_to_end": [{"name": "output_tok_s", "unit": "tokens/s"},
                        {"name": "setup_s", "unit": "s"}],
         "per_layer": []}
CELL = {"name": "tiny-moe.closed", "config": "tiny-moe",
        "traffic": "tiny_closed", "chips": 1}


def _run(fault=None, controls=()):
    with jax.default_matmul_precision("highest"):
        return cell.run(BENCH, CELL, seed=SEED, seconds=2.0, trace=False,
                        t_process=time.perf_counter(), require_tpu=False,
                        configs_dir=DATA / "configs",
                        traffic_dir=DATA / "traffic",
                        limits_dir=DATA / "limits", fault=fault,
                        controls=controls)


def test_served_path_agrees_with_reference(monkeypatch):
    _f32_program(monkeypatch)
    out = _run(controls=("experts_low", "fp8"))
    res = out["result"]
    assert res["correct"], res["checks"]
    got = out["readings"]["program"]
    assert got["gap"] == 0.0 and got["mean_gap_ratio"] == 0.0
    assert got["argmax_differs"] == 0
    assert got["served"] >= 20
    # the controls: every expert at the low precision, or float8 operands,
    # put other tokens first, far beyond the limit
    for name in ("experts_low", "fp8"):
        ctl = out["readings"][name]
        assert ctl["gap"] > 3 * res["checks"]["logit_gap"]["limit"], ctl
        assert ctl["mean_gap_ratio"] > res["checks"]["mean_gap_ratio"][
            "limit"], ctl
        # judged by the same limits, the control is not correct
        assert ctl["correct"] is False
    # the last key of the result holds every number compared
    assert list(res)[-1] == "checks"


def _wrap(engine, name, post):
    orig = getattr(engine, name)

    def wrapped(*a, **kw):
        return post(orig(*a, **kw), a, kw)

    setattr(engine, name, wrapped)


def _state_unchanged(engine):
    """The decode chunk returns the caches it was given."""
    _wrap(engine, "_decode_batched",
          lambda out, a, kw: (out[0], kw["caches"]) + tuple(out[2:]))


def _token_altered(engine):
    """One decoded token per chunk is changed where it is produced."""
    def post(out, a, kw):
        toks = out[0].at[1].set((out[0][1] + 1) % 512)
        return (toks,) + tuple(out[1:])
    _wrap(engine, "_decode_batched", post)


def _prefill_cache_lost(engine):
    """The admission prefill hands back an empty key/value cache."""
    def post(out, a, kw):
        logits, caches, info = out
        caches = jax.tree.map(jnp.zeros_like, caches)
        return logits, caches, info
    _wrap(engine, "_prefill", post)


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered,
                                   _prefill_cache_lost],
                         ids=["state_unchanged", "token_altered",
                              "prefill_cache_lost"])
def test_faults_make_correct_false(monkeypatch, fault):
    _f32_program(monkeypatch)
    res = _run(fault=fault)["result"]
    assert not res["correct"], res["checks"]


def test_control_reads_apart_from_reference(spec):
    """The control alone, on fixed tokens: all-low experts move the
    reference's top token at most positions."""
    rng = np.random.default_rng(0)
    pairs = [(rng.integers(1, 512, 20).tolist(),
              rng.integers(1, 512, 12).tolist()) for _ in range(3)]
    b = correct.build_batch(pairs, 4, 64, 16)
    with jax.default_matmul_precision("highest"):
        r = ref.forward_rows(spec, SEED, b.tokens, b.n_prompt, b.n_total,
                             b.rows_pos)
        c = ref.forward_rows(spec, SEED, b.tokens, b.n_prompt, b.n_total,
                             b.rows_pos, control="experts_low")
    assert correct.control_gap(r, r, b)["gap"] == 0.0
    assert correct.control_gap(r, c, b)["argmax_differs"] >= 10


def test_mean_gap_ratio_is_taken_against_the_yardstick():
    """Gaps of served tokens, and their mean over the bfloat16
    reference's: 0 where nothing strays, unread where only the program
    strays from a yardstick that reads 0."""
    pairs = [([1, 2], [3, 0])]
    b = correct.build_batch(pairs, 1, 4, 2)
    logits = np.zeros((1, 2, 4), np.float32)
    logits[0, 0, 3] = 1.0                  # the first served token is best
    logits[0, 1, 1] = 0.5                  # the second lies 0.5 below
    got = correct.served_gap(logits, b, yardstick=0.125)
    assert got["gap"] == 0.5 and got["mean_gap"] == 0.25
    assert got["mean_gap_ratio"] == 2.0 and got["argmax_differs"] == 1
    assert correct.served_gap(logits, b, yardstick=0.0)[
        "mean_gap_ratio"] is None
    same = correct.control_gap(logits, logits, b, yardstick=0.0)
    assert same["mean_gap_ratio"] == 0.0
