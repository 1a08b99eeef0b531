"""The serving session's own measurement: named device programs, named
scopes inside them, ``dymoe.*`` host spans in the profiler's trace, and the
live-group counter of the grouped expert kernel — computed only while the
profiler records."""
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import init_params
from repro.models.config import DyMoEPolicy, ModelConfig
from repro.models.layers import moe as moe_mod
from repro.models.layers.moe import init_moe, moe_apply_rows, quantize_moe
from repro.models.model import init_decode_state
from repro.serving import DyMoEEngine, EngineConfig, Request
from repro.serving import scheduler as sched_mod
from repro.serving.spans import group_bytes, latent_cache_bytes, \
    live_groups

DECODE_SCOPES = ("layers", "attention", "router", "moe_dispatch", "experts",
                 "moe_combine", "lm_head", "sample", "kv_freeze")
PREFILL_SCOPES = ("layers", "attention", "router", "moe_dispatch", "experts",
                  "moe_combine", "lm_head")


def _cfg(low_bits=2):
    return ModelConfig(
        name="t", arch_type="moe", num_layers=2, d_model=32, vocab_size=128,
        num_heads=2, num_kv_heads=2, head_dim=16, num_experts=4,
        num_experts_per_tok=2, moe_d_ff=48, capacity_factor=4.0,
        dtype="float32", remat="none",
        dymoe=DyMoEPolicy(low_bits=low_bits, group_size=16))


@pytest.fixture(scope="module")
def engine():
    cfg = _cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return DyMoEEngine(cfg, params, EngineConfig(decode_chunk=4))


def _lowered(eng, program):
    if program == "prefill":
        lens = [5, 8]
        return eng._prefill.lower(
            eng.params, tokens=jnp.ones((2, 8), jnp.int32),
            qparams=eng.qparams, cache_slots=32,
            lengths=jnp.asarray(lens, jnp.int32), row_local=True,
            row_capacities=jnp.asarray(lens, jnp.int32))
    b = 2
    return eng._decode_batched.lower(
        eng.params, tokens=jnp.ones(b, jnp.int32),
        caches=init_decode_state(eng.cfg, b, 32), num_steps=4,
        done=jnp.zeros(b, bool), n_emitted=jnp.ones(b, jnp.int32),
        limits=jnp.full(b, 8, jnp.int32), eos_tokens=jnp.full(b, -1,
                                                               jnp.int32),
        qparams=eng.qparams, live_cap=b)


@pytest.mark.parametrize("program,name,scopes", [
    ("prefill", "jit_prefill", PREFILL_SCOPES),
    ("decode", "jit_decode_many_batched", DECODE_SCOPES)])
def test_engine_programs_carry_names_and_scopes(engine, program, name,
                                                scopes):
    lowered = _lowered(engine, program)
    assert lowered.as_text().startswith(f"module @{name} ")
    # op locations name the scope path, ``.../<scope>/<op>``
    text = lowered.as_text(debug_info=True)
    for scope in scopes:
        assert re.search(rf'loc\("([^"]*/)?{scope}/', text), scope


def _kernel_counts(monkeypatch):
    """Record the ``counts`` each grouped expert-kernel call is handed."""
    seen = []
    orig = moe_mod._expert_ffn_grouped

    def spy(qweights, xb, counts, **kw):
        seen.append(np.asarray(counts))
        return orig(qweights, xb, counts, **kw)

    monkeypatch.setattr(moe_mod, "_expert_ffn_grouped", spy)
    return seen


@pytest.mark.parametrize("low_bits,seed", [(2, 0), (2, 3), (0, 5)])
def test_live_groups_equal_the_kernels_counts(monkeypatch, low_bits, seed):
    """The replay's counter, from the masks the decode program returns
    (dead rows zeroed), equals the live (expert, precision) groups of the
    watermarks ``moe_apply_rows`` hands the kernel."""
    cfg = _cfg(low_bits)
    b = 8
    p = init_moe(cfg, jax.random.PRNGKey(seed), jnp.float32)
    qw = quantize_moe(p, cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (b, cfg.d_model))
    crit = jax.random.bernoulli(jax.random.PRNGKey(seed + 2), 0.5,
                                (b, cfg.num_experts))
    live = np.random.default_rng(seed).random(b) < 0.6
    seen = _kernel_counts(monkeypatch)
    _, stats = moe_apply_rows(p, cfg, x, crit, qweights=qw,
                              live=jnp.asarray(live), capacity=b)
    (counts,) = seen
    m = live[:, None]
    active = np.asarray(stats["active"]) & m
    critical = np.asarray(crit) & m
    hi, lo = live_groups(critical[None, None], active[None, None],
                         skip_low=low_bits == 0)
    assert (hi, lo) == ((counts[:, 0] > 0).sum(), (counts[:, 1] > 0).sum())


@pytest.mark.parametrize("low_bits", [2, 0])
def test_group_bytes_per_layer_and_expert(low_bits):
    cfg = _cfg(low_bits)
    params = init_params(cfg, jax.random.PRNGKey(1))
    eng = DyMoEEngine(cfg, params, EngineConfig(decode_chunk=4))
    moe = eng.qparams["layers"]["moe"]
    groups = cfg.num_layers * cfg.num_experts
    hi, lo = group_bytes(eng.qparams)
    assert hi == sum(moe[n].nbytes("high") for n in moe) // groups > 0
    assert lo == (0 if low_bits == 0
                  else sum(moe[n].nbytes("low") for n in moe) // groups)


def _serve(eng):
    session = eng.serve(num_slots=2, slots_len=48)
    rng = np.random.default_rng(7)
    handles = [session.submit(Request(
        prompt_tokens=rng.integers(1, 128, n).tolist(), max_new_tokens=m))
        for n, m in ((6, 9), (9, 6), (7, 11))]
    while session.step():
        pass
    session.flush()
    for h in handles:
        assert len(h.result().tokens) == h.request.max_new_tokens
    session.close()


def _traced_spans(eng, tmp_path):
    """(thread, name, stats) of every ``dymoe.*`` span of one session
    served under the profiler; threads may share a name."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _serve(eng)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("dymoe."):
                    spans.append((f"{plane.name}#{i}", e.name,
                                  dict(e.stats)))
    return spans


def _of(spans, name, **match):
    return [st for _, nm, st in spans if nm == f"dymoe.{name}"
            and all(st.get(k) == v for k, v in match.items())]


def test_session_spans_under_the_profiler(engine, tmp_path):
    spans = _traced_spans(engine, tmp_path)

    def of(name, **match):
        return _of(spans, name, **match)

    assert {nm for _, nm, _ in spans} == {
        "dymoe.step", "dymoe.admit", "dymoe.dispatch", "dymoe.sync",
        "dymoe.replay_submit", "dymoe.replay"}
    steps = [st["boundary"] for st in of("step")]
    assert steps == sorted(steps) and len(set(steps)) == len(steps)
    for st in of("admit"):
        assert st["rows"] >= 1 and st["longest_prompt"] >= 6
        assert st["queue_wait_ms_max"] >= 0
        assert st["scaled_after_dot"] == 0    # f32 activations
    for st in of("dispatch"):
        assert st["steps"] == 4 and 1 <= st["rows"] <= st["live_cap"] <= 2
        assert st["scaled_after_dot"] == 0
    # one chunk index pairs a chunk's dispatch, boundary sync and replay
    chunks = {st["chunk"] for st in of("dispatch")}
    assert chunks and chunks == {st["chunk"] for st in of("sync")
                                 if "chunk" in st}
    replays = of("replay", kind="chunk")
    assert chunks == {st["chunk"] for st in replays}
    waves = {st["wave"] for st in of("admit")}
    assert waves == {st["wave"] for st in of("sync") if "wave" in st}
    assert waves == {st["wave"] for st in of("replay", kind="prefill")}
    hi_bytes, lo_bytes = group_bytes(engine.qparams)
    for st in replays:
        assert st["live_hi_groups"] + st["live_lo_groups"] > 0
        assert st["kernel_weight_bytes"] == (st["live_hi_groups"] * hi_bytes
                                             + st["live_lo_groups"]
                                             * lo_bytes)
    assert all(st["depth"] >= 0 for st in of("replay_submit"))
    # the replay worker's jobs run on their own thread
    threads = {th for th, nm, _ in spans if nm == "dymoe.replay"}
    assert threads.isdisjoint(
        {th for th, nm, _ in spans if nm == "dymoe.dispatch"})


def test_scaled_after_dot_on_dispatch_and_admit(tmp_path):
    """bf16 activations in short row blocks: every decode chunk's grouped
    kernel applies its group scales after the dot, and so does every
    admission wave of several rows; a one-row wave runs the solo program,
    which has no grouped kernel."""
    cfg = dataclasses.replace(_cfg(), dtype="bfloat16")
    eng = DyMoEEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                      EngineConfig(decode_chunk=4))
    spans = _traced_spans(eng, tmp_path)
    dispatch, admit = _of(spans, "dispatch"), _of(spans, "admit")
    assert dispatch and admit
    assert all(st["scaled_after_dot"] == 1 for st in dispatch)
    assert all(st["scaled_after_dot"] == int(st["rows"] > 1)
               for st in admit)
    assert any(st["rows"] > 1 for st in admit)


def test_counter_is_not_computed_without_the_profiler(engine, monkeypatch):
    calls = []
    orig = sched_mod.live_groups

    def counting(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(sched_mod, "live_groups", counting)
    assert not jax.profiler.TraceAnnotation.is_enabled()
    _serve(engine)
    assert calls == []


def _mla_cfg():
    """A latent-attention model with a dense lead layer (the DeepSeek-V3
    block at toy widths)."""
    return dataclasses.replace(
        _cfg(), num_layers=3, first_k_dense=1, d_ff=64, num_kv_heads=1,
        head_dim=24, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, num_shared_experts=1,
        router_scoring="sigmoid",
        routed_scaling_factor=2.446)


def test_latent_cache_bytes_counts_every_layer():
    cfg = _mla_cfg()
    caches = init_decode_state(cfg, 2, 48)
    # 3 layers (the dense one too) x 2 slots x 48 positions x (32 + 8) f32
    assert latent_cache_bytes(caches) == 3 * 2 * 48 * (32 + 8) * 4


def test_latent_attention_counters_on_admit_and_dispatch(tmp_path):
    """The session's first admission wave carries the slot batch's latent
    cache bytes, and no later wave does; every decode chunk's dispatch
    carries its live rows' summed context, which grows by the chunk's
    steps for each row that stays."""
    cfg = _mla_cfg()
    eng = DyMoEEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                      EngineConfig(decode_chunk=4))
    spans = _traced_spans(eng, tmp_path)
    admit = sorted(_of(spans, "admit"), key=lambda st: st["wave"])
    assert admit[0]["wave"] == 0
    assert admit[0]["latent_cache_bytes"] == latent_cache_bytes(
        init_decode_state(cfg, 2, 48))
    assert all("latent_cache_bytes" not in st for st in admit[1:])
    dispatch = sorted(_of(spans, "dispatch"), key=lambda st: st["chunk"])
    # the first wave admits the 6- and 9-token prompts, each with its
    # first token from the prefill
    assert dispatch[0]["rows"] == 2
    assert dispatch[0]["ctx_tokens"] == (6 + 1) + (9 + 1)
    for a, b in zip(dispatch, dispatch[1:]):
        if a["rows"] == b["rows"] == 2 and b["chunk"] == a["chunk"] + 1:
            assert b["ctx_tokens"] <= a["ctx_tokens"] + 2 * 4
    assert all(st["ctx_tokens"] > 0 for st in dispatch)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_latent_attention_programs_carry_their_scopes(program):
    """The DeepSeek-V3 block's parts are named in both serving programs:
    latent attention, the shared experts and the dense lead layer's FFN."""
    cfg = _mla_cfg()
    eng = DyMoEEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                      EngineConfig(decode_chunk=4))
    text = _lowered(eng, program).as_text(debug_info=True)
    for scope in ("mla", "shared_experts", "dense_ffn"):
        assert re.search(rf'loc\("([^"]*/)?{scope}/', text), scope
