"""The ``deepseek_v3`` family and the ``doc_qa`` mix are found by name
like every other configuration and mix, and the served path of the
family agrees with its plain reference at a reduced size on the CPU."""
import dataclasses
import importlib
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench.families import deepseek_v3 as fam
from bench.harness import cell, traffic
from bench.run import load_benchmark

DATA = Path(__file__).resolve().parent / "data"
SEED = 2 ** 31 + 77


def test_every_configuration_has_its_family_and_reference():
    """A configuration names its family (``bench/families/<family>.py``),
    which names its plain reference (``bench/references/<name>.py``); the
    family turns the file into the program's configuration, whose widths
    and depth are the file's."""
    for c in load_benchmark()["configs"]:
        spec = cell.load_config(c["name"])
        family = importlib.import_module(f"bench.families.{spec['family']}")
        reference = importlib.import_module(
            f"bench.references.{family.REFERENCE}")
        assert callable(reference.seed_key)
        assert callable(reference.forward_rows)
        cfg = family.program_config(spec)
        assert cfg.num_layers == spec["num_hidden_layers"]
        assert cfg.d_model == spec["hidden_size"]
        assert cfg.vocab_size == spec["vocab_size"]


def test_family_keeps_the_published_block():
    """Moonlight's file becomes latent attention, a dense lead layer of
    its own width, two shared experts and the biased sigmoid router; a
    file stating what the program cannot run is refused."""
    spec = cell.load_config("moonlight-16b-a3b-16l")
    cfg = fam.program_config(spec)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert (cfg.first_k_dense, cfg.d_ff, cfg.moe_d_ff) == (1, 11264, 1408)
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.num_shared_experts) == (64, 6, 2)
    assert cfg.router_scoring == "sigmoid"
    assert cfg.routed_scaling_factor == 2.446
    assert cfg.num_moe_layers == 15
    with pytest.raises(ValueError):
        fam.program_config(dict(spec, scoring_func="softmax"))


def test_doc_qa_fits_its_slots():
    """``doc_qa``'s longest prompt and longest reply fit its window, and
    its warm-up covers both prompt lengths it draws."""
    mix = traffic.load("doc_qa")
    assert traffic.prompt_lengths(mix) == [1024, 2048]
    assert traffic.max_length(mix["output"]) == 768
    assert mix["slots_len"] == 2048 + 768


def _f32_program(monkeypatch):
    """Serve with float32 activations over the bfloat16 weights, so the
    program and the reference compute the same mathematics."""
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


def test_served_path_agrees_with_reference(monkeypatch):
    """Through ``serve()`` (ragged admission waves, decode chunks over the
    latent cache, a dense lead layer, shared experts, the biased sigmoid
    router): every served token is the reference's argmax, and the
    controls read far beyond the limits."""
    _f32_program(monkeypatch)
    bench = {"end_to_end": [{"name": "output_tok_s", "unit": "tokens/s"},
                            {"name": "setup_s", "unit": "s"}],
             "per_layer": []}
    one = {"name": "tiny-deepseek.closed", "config": "tiny-deepseek",
           "traffic": "tiny_closed", "chips": 1}
    with jax.default_matmul_precision("highest"):
        out = cell.run(bench, one, seed=SEED, seconds=2.0, trace=False,
                       t_process=time.perf_counter(), require_tpu=False,
                       configs_dir=DATA / "configs",
                       traffic_dir=DATA / "traffic",
                       limits_dir=DATA / "limits",
                       controls=("experts_low", "fp8"))
    res = out["result"]
    assert res["correct"], res["checks"]
    got = out["readings"]["program"]
    assert got["gap"] == 0.0 and got["argmax_differs"] == 0
    assert got["served"] >= 20
    for name in ("experts_low", "fp8"):
        assert out["readings"][name]["correct"] is False
