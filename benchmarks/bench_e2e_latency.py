"""Paper Fig. 10 analogue: end-to-end TTFT / TPOT of DyMoE vs offloading
baselines on the paper's two evaluation models across VRAM budgets.

Full-size byte/FLOP model of the REAL configs (Mixtral-8×7B,
Qwen3-30B-A3B) driven through the REAL orchestrator (mixed-precision LRU +
look-ahead prefetch + single DMA queue) with skewed synthetic routing.
Baseline systems are modeled by their defining mechanism:
  accelerate         — load-on-demand, uniform int4, no cache reuse
  mixtral-offloading — LRU expert cache, uniform int4, no prefetch
  moe-infinity       — cache + activation-aware prefetch, bf16 experts
  dymoe-4/2, dymoe-4/0 — the paper's systems (r = 0.75)

Alongside the modeled numbers, ``e2e_decode_walltime`` rows MEASURE the
wall-clock decode throughput of the real jitted model through the serving
engine — chunked (``decode_chunk=16``, one dispatch + one device→host
transfer per chunk) vs token-at-a-time (``decode_chunk=1``) — and verify
the two paths emit bitwise-identical greedy tokens and identical modeled
TPOT/cache stats. ``--smoke`` runs only this section with few tokens and
asserts the parity + a minimum speedup, as a loud CI regression guard.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import zipf_routing_trace
from repro.kernels.quant_matmul.ops import expert_quant_matmul
from repro.models import init_params
from repro.models.config import DyMoEPolicy, ModelConfig
from repro.quant import MixedPrecisionWeights
from repro.configs import get_config
from repro.core.orchestrator import DynamicExpertOrchestrator, \
    OrchestratorConfig
from repro.core.schedule import critical_counts
from repro.serving import DyMoEEngine, EngineConfig, Request
from repro.serving.cost_model import EdgeCostModel, EdgeProfile, expert_bytes

DECODE_STEPS = 32
PREFILL_LEN = 512

# tiny-but-real MoE for the measured (wall-clock) decode throughput rows
TINY_MOE = ModelConfig(
    name="tiny-moe", arch_type="moe", num_layers=4, d_model=64,
    vocab_size=256, num_heads=4, num_kv_heads=2, head_dim=16,
    num_experts=8, num_experts_per_tok=2, moe_d_ff=64,
    capacity_factor=4.0, dtype="float32", remat="none",
    dymoe=DyMoEPolicy(high_bits=4, low_bits=2, retention=0.75))


# single source of truth for each modeled system: the (hi, lo) bit widths
# its experts execute at + its defining orchestration mechanisms. Byte
# accounting, the oracle check, and the orchestrator config all derive
# from this table so they cannot drift apart.
_SYSTEMS = {
    "accelerate": dict(bits=(4, 4), enable_cache=False,
                       enable_prefetch=False, enable_dyquant=False),
    "mixtral-offloading": dict(bits=(4, 4), enable_cache=True,
                               enable_prefetch=False, enable_dyquant=False),
    "moe-infinity": dict(bits=(16, 16), enable_cache=True,
                         enable_prefetch=True, enable_dyquant=False),
    "dymoe-4/2": dict(bits=(4, 2), enable_cache=True, enable_prefetch=True,
                      enable_dyquant=True),
    "dymoe-4/0": dict(bits=(4, 0), low_is_skip=True, enable_cache=True,
                      enable_prefetch=True, enable_dyquant=True),
}


def _system(name: str, cfg, vram_gb: int) -> OrchestratorConfig:
    pol = cfg.dymoe
    spec = dict(_SYSTEMS[name])
    hi, lo = spec.pop("bits")
    return OrchestratorConfig(
        num_layers=cfg.num_layers, num_experts=cfg.num_experts,
        experts_per_token=cfg.num_experts_per_tok,
        vram_budget_bytes=int((vram_gb << 30) * 0.6),
        pcie_bw=16e9, prefetch_topk=pol.prefetch_topk,
        bytes_high=expert_bytes(cfg, hi),
        bytes_low=expert_bytes(cfg, lo) if lo else 0,
        **spec)


@functools.lru_cache(maxsize=None)
def _grouped_kernel_oracle_err(hi_bits: int, lo_bits: int) -> float:
    """Interpret-mode oracle check of the grouped kernel at the bit pair a
    system deploys — evidence the modeled bytes describe a correct kernel."""
    rng = np.random.default_rng(7)
    e, m, k, n = 4, 8, 128, 32
    x = jnp.asarray(rng.standard_normal((e, m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((e, k, n)), jnp.float32)
    mp = MixedPrecisionWeights.build(w, hi_bits, lo_bits or None, 64)
    mask = jnp.arange(e) % 2 == 0
    ref = expert_quant_matmul(x, mp, mask, impl="ref", out_dtype=jnp.float32)
    pal = expert_quant_matmul(x, mp, mask, impl="pallas", interpret=True,
                              block_m=8, block_n=16, block_k=64,
                              out_dtype=jnp.float32)
    return float(jnp.abs(ref - pal).max())


def _run_system(name: str, cfg, vram_gb: int, seed: int = 0):
    ocfg = _system(name, cfg, vram_gb)
    orch = DynamicExpertOrchestrator(ocfg)
    cost = EdgeCostModel(cfg, EdgeProfile().with_vram(vram_gb))
    t_l = critical_counts(cfg.num_layers, cfg.num_experts, cfg.dymoe.lam,
                          cfg.dymoe.depth_schedule)
    trace = zipf_routing_trace(cfg.num_layers, cfg.num_experts,
                               cfg.num_experts_per_tok, DECODE_STEPS + 1,
                               seed=seed)

    def crit_from(active):
        # critical = depth-budgeted subset of active (gate-guided proxy)
        masks = []
        for l in range(cfg.num_layers):
            ids = np.flatnonzero(active[l])[:max(1, min(
                t_l[l], int(active[l].sum())))]
            m = np.zeros(cfg.num_experts, bool)
            m[ids] = True
            masks.append(m)
        return masks

    # ---- prefill: all experts active (long input hits everyone)
    all_active = [np.ones(cfg.num_experts, bool)] * cfg.num_layers
    crit = [np.zeros(cfg.num_experts, bool) for _ in range(cfg.num_layers)]
    for l in range(cfg.num_layers):
        crit[l][:t_l[l]] = True
    compute = [cost.layer_compute_s(
        phase="prefill", s_ctx=PREFILL_LEN, s_q=PREFILL_LEN,
        active_experts_hi=int(c.sum()),
        active_experts_lo=cfg.num_experts - int(c.sum()),
        tokens_routed=PREFILL_LEN) for c in crit]
    pred = [a.astype(float) for a in all_active]
    ttft = orch.step(crit, all_active, pred, compute).total_s

    # ---- decode: skewed per-step routing, look-ahead = next step's truth
    # perturbed (the paper's predictor is accurate but not perfect)
    steps: List[float] = []
    masks = list(trace)
    rng = np.random.default_rng(seed + 1)
    wbytes = 0
    for t in range(DECODE_STEPS):
        active = list(masks[t])
        crit = crit_from(masks[t])
        nxt = masks[t + 1].astype(float)
        noise = rng.random(nxt.shape) * 0.3
        pred = list(np.clip(nxt + noise - 0.15, 0, None))
        compute = [cost.layer_compute_s(
            phase="decode", s_ctx=PREFILL_LEN + t, s_q=1,
            active_experts_hi=int(c.sum()),
            active_experts_lo=int(a.sum()) - int((c & a).sum()),
            tokens_routed=1) for c, a in zip(crit, active)]
        # packed bytes this step's grouped quant-matmuls read, at the
        # system's deployed bit widths (skip => sub-critical moves nothing)
        wbytes += sum(
            int((c & a).sum()) * ocfg.bytes_high
            + (int(a.sum()) - int((c & a).sum())) * ocfg.bytes_low
            for c, a in zip(crit, active))
        steps.append(orch.step(crit, active, pred, compute).total_s)
    tpot = float(np.mean(steps))
    return ttft, tpot, orch.cache.stats, wbytes / DECODE_STEPS


def measured_decode_throughput(max_new: int = 65, smoke: bool = False
                               ) -> List[dict]:
    """Wall-clock decode tok/s of the REAL jitted model through the
    engine's fused reference path (``generate_reference`` — the pure
    B=1 loop, no scheduler): chunked decode vs the token-at-a-time loop,
    plus the parity checks (bitwise-identical greedy tokens, identical
    modeled numbers) that make the speedup a like-for-like comparison.
    This isolates the decode-FUSION win; the step-driven serving loop's
    own overhead (admission, boundary syncs, replay stream) is what the
    ``continuous_vs_static`` / ``sampled_continuous`` rows measure."""
    if smoke:
        max_new = 17
    params = init_params(TINY_MOE, jax.random.PRNGKey(0))
    req = Request(prompt_tokens=list(range(1, 17)), max_new_tokens=max_new)
    repeats = 3  # min-of-N: rides out scheduler noise (matters in CI)
    results, walls = {}, {}
    for chunk in (1, 16):
        eng = DyMoEEngine(TINY_MOE, params, EngineConfig(decode_chunk=chunk))
        eng.generate_reference(req)  # warm-up: compile both chunk sizes
        best = float("inf")
        for _ in range(repeats):
            results[chunk] = eng.generate_reference(req)
            # decode loop only — excludes prefill and its replay, which
            # are identical in both paths and would dilute the ratio
            best = min(best, results[chunk].decode_wall_s)
        walls[chunk] = best
    r1, r16 = results[1], results[16]
    tokens_match = bool(r16.tokens == r1.tokens)
    modeled_match = bool(r16.ttft_s == r1.ttft_s
                         and r16.tpot_s == r1.tpot_s
                         and r16.cache_stats == r1.cache_stats)
    speedup = walls[1] / walls[16]
    rows = []
    for chunk in (1, 16):
        n_dec = len(results[chunk].tokens) - 1
        rows.append(dict(
            bench="e2e_decode_walltime", arch=TINY_MOE.name,
            decode_chunk=chunk, new_tokens=len(results[chunk].tokens),
            decode_tok_s=round(n_dec / walls[chunk], 1),
            modeled_tpot_s=round(float(results[chunk].tpot_s), 7),
            speedup_vs_chunk1=round(speedup, 2) if chunk == 16 else 1.0,
            tokens_match=tokens_match, modeled_match=modeled_match))
    if smoke:
        assert tokens_match, "chunked decode changed greedy tokens"
        assert modeled_match, "chunked decode changed modeled TTFT/TPOT"
        assert speedup >= 1.5, \
            f"chunked decode speedup regressed: {speedup:.2f}x"
    return rows


# the continuous-vs-static serving comparison runs the paper's flagship
# "4/0" deployment (sub-critical experts skipped outright). 12 layers x
# 16 experts at a small width is deliberately the SCHEDULING regime: the
# per-chunk host work (telemetry fetch + per-row orchestrator replay +
# boundary bookkeeping, ~10-25% of the serial wall here) is large relative
# to the per-chunk device compute, so both effects under test are visible
# — lockstep batching burning device steps on drained rows, and the
# serial loop paying the whole host replay between dispatches. 4/2 would
# work too but doubles the dual-buffer path's dequant traffic, muddying
# the scheduling signal.
BENCH_MOE = dataclasses.replace(
    TINY_MOE, name="bench-moe", vocab_size=512, num_layers=12,
    num_experts=16, num_experts_per_tok=2, moe_d_ff=128,
    dymoe=dataclasses.replace(TINY_MOE.dymoe, low_bits=0))


def fused_vs_dual_decode(smoke: bool = False) -> List[dict]:
    """Straggler-workload decode through the fused dual-buffer expert
    kernel: a slot batch where half the rows have already drained (the
    regime every ragged serving trace ends in). Three dispatch variants
    of the SAME jitted ``decode_many_batched``:

      all_live          — every slot decoding (the cost ceiling),
      half_done         — half the rows done; the ragged live-row grid
                          skips their expert FLOPs/IO but buffers stay
                          at B (what a done-mask alone buys),
      half_done_livecap — same, plus the scheduler's power-of-two
                          ``live_cap`` shrinking the capacity buffers to
                          the live count (the full fused win).

    Parity is the headline: live rows' tokens must be BITWISE identical
    across all three (a row never feels its dead neighbours, the shrink,
    or its slot index) and dead rows' tokens stay frozen. ``--smoke``
    asserts parity always; the straggler speedup only on >2-core runners
    (tiny-model wall-clock is scheduler-noise-bound below that)."""
    import os
    from functools import partial

    from repro.models import (decode_many_batched, prefill, quantize_model)

    cfg = BENCH_MOE
    b = 8
    steps = 8 if smoke else 24
    params = init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_model(params, cfg)
    rng = np.random.default_rng(5)
    prompt = jnp.asarray(rng.integers(1, cfg.vocab_size, (b, 8)), jnp.int32)
    logits, caches, _ = prefill(params, cfg, prompt, qparams=qp,
                                cache_slots=8 + steps + 1)
    tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    half = np.zeros(b, bool)
    half[b // 2:] = True
    jfn = jax.jit(partial(decode_many_batched, cfg=cfg),
                  static_argnames=("num_steps", "live_cap"))

    def call(done, live_cap):
        return jfn(params, tokens=tok0, caches=caches, num_steps=steps,
                   done=jnp.asarray(done),
                   n_emitted=jnp.ones((b,), jnp.int32),
                   limits=jnp.full((b,), steps + 1, jnp.int32),
                   eos_tokens=jnp.full((b,), -1, jnp.int32),
                   qparams=qp, live_cap=live_cap)

    variants = {"all_live": (np.zeros(b, bool), None),
                "half_done": (half, None),
                "half_done_livecap": (half, b // 2)}
    toks, walls = {}, {}
    for name, (done, cap) in variants.items():
        out = call(done, cap)             # warm-up / compile
        toks[name] = np.asarray(out[0])
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            call(done, cap)[0].block_until_ready()
            best = min(best, time.perf_counter() - t0)
        walls[name] = best
    live = ~half
    parity = (np.array_equal(toks["half_done"][:, live],
                             toks["all_live"][:, live])
              and np.array_equal(toks["half_done_livecap"][:, live],
                                 toks["all_live"][:, live]))
    frozen = all(np.array_equal(toks[n][:, half],
                                np.broadcast_to(np.asarray(tok0)[half],
                                                (steps, b // 2)))
                 for n in ("half_done", "half_done_livecap"))
    speedup = walls["all_live"] / walls["half_done_livecap"]

    rows = []
    for name in variants:
        n_live = int((~variants[name][0]).sum())
        rows.append(dict(
            bench="fused_vs_dual", arch=cfg.name, variant=name,
            live_rows=n_live, num_slots=b, decode_steps=steps,
            live_cap=variants[name][1],
            decode_wall_s=round(walls[name], 4),
            decode_tok_s=round(steps * n_live / walls[name], 1)
            if n_live else 0.0,
            straggler_speedup=(round(speedup, 2)
                               if name == "half_done_livecap" else None),
            live_tokens_bitwise=parity, dead_tokens_frozen=frozen))
    if smoke:
        assert parity, ("live rows' tokens changed under the ragged "
                        "live-row grid / live_cap shrink")
        assert frozen, "a done row's token advanced"
        try:
            n_cores = len(os.sched_getaffinity(0))
        except AttributeError:
            n_cores = os.cpu_count() or 1
        if n_cores > 2:
            assert speedup >= 1.0, \
                f"straggler batch not cheaper than full batch: " \
                f"{speedup:.2f}x"
    return rows


def continuous_vs_static_batching(smoke: bool = False) -> List[dict]:
    """Ragged-workload serving throughput: the continuous-batching
    scheduler — PIPELINED (host telemetry replay overlapped with device
    decode, batched admission waves) and SERIAL (``pipeline=False``, host
    replay on the critical path) — against the static lockstep
    ``generate_batch`` baseline (whole batch locked until the last row
    drains, right-aligned padding, NaN telemetry).

    The workload is deliberately ragged — bucketed prompt lengths (so the
    admission waves compile a handful of shapes, as a real server would
    bucket) and heavily mixed ``max_new_tokens`` with two long stragglers
    over many short requests — the regime where lockstep batching burns
    device steps on drained rows while the scheduler keeps only
    ``num_slots`` rows hot, and where the serial loop pays the whole
    orchestrator replay between chunks. The ``pipelined_vs_serial``
    speedup is the ROADMAP "async host telemetry replay" win: chunk N+1
    is dispatched before chunk N's telemetry is even fetched.

    ``--smoke`` asserts the acceptance contract: per-request finite
    modeled latencies, per-row tokens bit-identical to solo `generate`,
    pipelined results bit-identical to serial (tokens AND modeled
    TTFT/TPOT — always), throughput at least the static baseline's, and a
    pipelined-over-serial speedup — the latter only on >2-core runners,
    where there is a core for the replay thread to overlap onto."""
    import os

    rng = np.random.default_rng(0)
    specs = [(16, 64), (24, 64)] + [
        (int(rng.choice([8, 16, 24])), int(rng.integers(3, 7)))
        for _ in range(22)]
    requests = [Request(prompt_tokens=rng.integers(
        1, BENCH_MOE.vocab_size, s).tolist(), max_new_tokens=m)
        for s, m in specs]
    params = init_params(BENCH_MOE, jax.random.PRNGKey(0))
    eng = DyMoEEngine(BENCH_MOE, params, EngineConfig(decode_chunk=8))
    num_slots = 4

    def serve(mode):
        if mode == "static":
            return eng.generate_batch(requests, static=True)
        return eng.generate_batch(requests, num_slots=num_slots,
                                  pipeline=(mode == "pipelined"))

    modes = ("pipelined", "serial", "static")
    for mode in modes:   # warm-up: compile every shape either path needs
        serve(mode)
    repeats = 3
    wall, outs = {}, {}
    for mode in modes:
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = serve(mode)
            best = min(best, time.perf_counter() - t0)
        wall[mode], outs[mode] = best, out
    new_tokens = {m: sum(len(r.tokens) for r in o) for m, o in outs.items()}
    tok_s = {m: new_tokens[m] / wall[m] for m in wall}
    speedup_static = tok_s["pipelined"] / tok_s["static"]
    speedup_serial = tok_s["pipelined"] / tok_s["serial"]
    cont = outs["pipelined"]
    finite_by_mode = {   # static is honestly False: NaN modeled by design
        m: all(np.isfinite(r.ttft_s) and np.isfinite(r.tpot_s) for r in o)
        for m, o in outs.items()}
    finite = finite_by_mode["pipelined"]
    # solo parity spot-check: a straggler + a short request
    parity = all(eng.generate(requests[i]).tokens == cont[i].tokens
                 for i in (0, 2))
    # pipeline parity: bit-identical tokens AND modeled numbers
    pipe_parity = all(
        a.tokens == b.tokens and a.ttft_s == b.ttft_s
        and a.tpot_s == b.tpot_s and a.cache_stats == b.cache_stats
        for a, b in zip(cont, outs["serial"]))
    rows = []
    for mode in modes:
        sched = mode != "static"
        res = outs[mode]
        rows.append(dict(
            bench="continuous_vs_static", arch=BENCH_MOE.name, mode=mode,
            num_requests=len(requests),
            num_slots=num_slots if sched else len(requests),
            new_tokens=new_tokens[mode],
            decode_tok_s=round(tok_s[mode], 1),
            speedup_vs_static=(round(tok_s[mode] / tok_s["static"], 2)
                               if sched else 1.0),
            pipelined_vs_serial=(round(speedup_serial, 2)
                                 if mode == "pipelined" else None),
            per_request_latency_finite=finite_by_mode[mode],
            mean_ttft_s=round(float(np.mean([r.ttft_s for r in res])), 6)
            if sched else None,
            mean_tpot_s=round(float(np.mean([r.tpot_s for r in res])), 7)
            if sched else None,
            mean_queue_wait_s=round(float(np.mean(
                [r.queue_wait_s for r in res])), 4) if sched else None,
            solo_parity=parity if mode == "pipelined" else None,
            pipelined_parity=pipe_parity if mode == "pipelined" else None))
    if smoke:
        assert finite_by_mode["pipelined"] and finite_by_mode["serial"], \
            "scheduler produced non-finite modeled TTFT/TPOT"
        assert parity, "continuous batching changed a request's tokens"
        assert pipe_parity, ("pipelined scheduler diverged from the serial "
                             "reference in tokens or modeled numbers")
        assert speedup_static >= 1.0, \
            f"continuous batching slower than static lockstep: " \
            f"{speedup_static:.2f}x"
        # the overlap win needs a spare core for the replay thread; on
        # <=2-core CI runners assert parity only. sched_getaffinity sees
        # cgroup/affinity limits that os.cpu_count() (host cores) misses.
        # threshold 1.0 (throughput parity), not the measured 1.05-1.15x:
        # the guard catches the pipeline REGRESSING below the serial loop
        # without flaking on a noisy-neighbor runner at the low end
        try:
            n_cores = len(os.sched_getaffinity(0))
        except AttributeError:  # non-Linux
            n_cores = os.cpu_count() or 1
        if n_cores > 2:
            assert speedup_serial >= 1.0, \
                f"pipelined replay overlap regressed: {speedup_serial:.2f}x"
    return rows


def sampled_continuous_serving(smoke: bool = False) -> List[dict]:
    """The step-driven serving loop under the paper's actual traffic
    shape: bursty MID-RUN arrivals (half the requests are submitted while
    ``step()`` is already being driven) with per-request SAMPLING
    (mixed temperature / top-k / seed plus interleaved greedy requests).

    Measures pipelined vs serial tok/s on that workload and — in
    ``--smoke`` — asserts the sampled pipeline parity contract exactly
    like the greedy guard: pipelined results bit-identical to the serial
    reference (tokens AND modeled TTFT/TPOT), and sampled tokens
    bit-identical to a solo ``generate`` of the same seed (the per-row
    counter-derived PRNG streams are invariant to admission order and
    slot placement)."""
    rng = np.random.default_rng(3)
    n = 8 if smoke else 16
    reqs = []
    for i in range(n):
        s = int(rng.choice([8, 16]))
        reqs.append(Request(
            prompt_tokens=rng.integers(1, BENCH_MOE.vocab_size, s).tolist(),
            max_new_tokens=int(rng.integers(3, 9)),
            temperature=(0.0 if i % 3 == 0
                         else float(rng.uniform(0.5, 1.2))),
            top_k=(0 if i % 3 == 0 else int(rng.choice([0, 4, 8]))),
            seed=(None if i % 3 == 0 else int(rng.integers(0, 1 << 16)))))
    params = init_params(BENCH_MOE, jax.random.PRNGKey(0))
    eng = DyMoEEngine(BENCH_MOE, params, EngineConfig(decode_chunk=8))
    slots_len = max(len(r.prompt_tokens) + r.max_new_tokens for r in reqs)

    def serve(pipeline: bool):
        sess = eng.serve(num_slots=4, pipeline=pipeline,
                         slots_len=slots_len)
        handles = [sess.submit(r) for r in reqs[:n // 2]]
        for _ in range(2):       # the engine is mid-decode...
            sess.step()
        # ...when the second burst arrives (mid-run admission)
        handles += [sess.submit(r) for r in reqs[n // 2:]]
        while sess.step():
            pass
        sess.flush()
        sess.close()
        return [h.result() for h in handles]

    for pipe in (True, False):   # warm-up: compile the sampling trace
        serve(pipe)
    wall, outs = {}, {}
    for pipe in (True, False):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            out = serve(pipe)
            best = min(best, time.perf_counter() - t0)
        wall[pipe], outs[pipe] = best, out
    pipe_parity = all(
        a.tokens == b.tokens and a.ttft_s == b.ttft_s
        and a.tpot_s == b.tpot_s and a.cache_stats == b.cache_stats
        for a, b in zip(outs[True], outs[False]))
    # solo spot-check: one sampled early arrival + one sampled mid-run one
    spots = [i for i in (1, n - 1) if reqs[i].temperature > 0]
    solo_parity = all(eng.generate(reqs[i]).tokens == outs[True][i].tokens
                      for i in spots)
    finite = all(np.isfinite(r.ttft_s) and np.isfinite(r.tpot_s)
                 for o in outs.values() for r in o)
    new_tokens = {p: sum(len(r.tokens) for r in o)
                  for p, o in outs.items()}
    rows = []
    for pipe in (True, False):
        rows.append(dict(
            bench="sampled_continuous", arch=BENCH_MOE.name,
            mode="pipelined" if pipe else "serial",
            num_requests=n, num_slots=4, midrun_arrivals=n - n // 2,
            sampled_requests=sum(r.temperature > 0 for r in reqs),
            new_tokens=new_tokens[pipe],
            decode_tok_s=round(new_tokens[pipe] / wall[pipe], 1),
            pipelined_vs_serial=(round(wall[False] / wall[True], 2)
                                 if pipe else None),
            mean_ttft_s=round(float(np.mean(
                [r.ttft_s for r in outs[pipe]])), 6),
            sampled_pipelined_parity=pipe_parity if pipe else None,
            sampled_solo_parity=solo_parity if pipe else None))
    if smoke:
        assert finite, "sampled serving produced non-finite modeled numbers"
        assert solo_parity, \
            "sampled continuous batching diverged from solo generate"
        assert pipe_parity, \
            "sampled pipelined serving diverged from the serial reference"
    return rows


def overload_burst_serving(smoke: bool = False) -> List[dict]:
    """SLO overload control at EQUAL offered load: the same bulk-plus-
    urgent-burst workload through ``policy="fifo"`` (blind arrival order)
    and ``policy="edf"`` (priority/EDF admission + chunk-boundary
    preemption + the pressure degradation ladder).

    Four bulk requests saturate two slots and back up the queue; an
    urgent burst (priority 1, wall-clock deadline calibrated from a
    measured warm-up run of the SAME workload, so the threshold tracks
    the runner's actual speed instead of a hard-coded wall) arrives
    mid-run. Under FIFO the urgent requests queue behind the whole bulk
    backlog and blow their deadlines (shed typed); under EDF they admit
    first and preempt the weakest bulk slot, which resumes and finishes
    bit-identically. Reported per mode: urgent deadline hit-rate (the
    headline — ``--smoke`` asserts EDF strictly beats FIFO), p99 urgent
    TTFT wall (queue wait; None when nothing completed), shed/preempt
    counts and ladder transitions, and the bulk token-parity bit.
    """
    params = init_params(BENCH_MOE, jax.random.PRNGKey(0))
    eng = DyMoEEngine(BENCH_MOE, params, EngineConfig(decode_chunk=8))
    n_bulk, n_urgent = 4, 4
    bulk_new = 24 if smoke else 48

    def requests(deadline_s):
        bulk = [Request(prompt_tokens=list(range(1 + i, 17 + i)),
                        max_new_tokens=bulk_new, request_id=f"bulk-{i}")
                for i in range(n_bulk)]
        urgent = [Request(prompt_tokens=list(range(40 + i, 48 + i)),
                          max_new_tokens=4, request_id=f"urgent-{i}",
                          priority=1, deadline_s=deadline_s)
                  for i in range(n_urgent)]
        return bulk, urgent

    def serve(policy, deadline_s):
        bulk_reqs, urgent_reqs = requests(deadline_s)
        sess = eng.serve(num_slots=2, slots_len=16 + bulk_new + 8,
                         policy=policy)
        t0 = time.perf_counter()
        bulk = [sess.submit(r) for r in bulk_reqs]
        for _ in range(2):            # slots busy, queue backed up...
            sess.step()
        urgent = [sess.submit(r) for r in urgent_reqs]  # ...the burst
        sess.drain(cancel_queued=False)
        wall = time.perf_counter() - t0
        health = sess.health()
        sess.close()
        assert all(h.done for h in bulk + urgent)
        return bulk, urgent, health, wall

    # warm-up both modes (compiles every admission/preemption shape),
    # then calibrate the urgent deadline from a measured FIFO run: half
    # the bulk-backlog drain time — comfortably missed by FIFO's blind
    # queueing, comfortably met by EDF's jump-the-queue admission
    for policy in ("fifo", "edf"):
        serve(policy, None)
    *_, t_cal = serve("fifo", None)
    deadline_s = 0.5 * t_cal

    rows = []
    outs = {}
    for policy in ("fifo", "edf"):
        bulk, urgent, health, wall = serve(policy, deadline_s)
        hits = [h for h in urgent
                if h.error is None
                and not h.result(drive=False).deadline_expired]
        waits = sorted(h.result(drive=False).queue_wait_s for h in hits)
        outs[policy] = dict(bulk=bulk, hit_rate=len(hits) / n_urgent)
        rows.append(dict(
            bench="overload_burst", arch=BENCH_MOE.name, mode=policy,
            num_slots=2, bulk_requests=n_bulk, urgent_requests=n_urgent,
            bulk_max_new=bulk_new, deadline_s=round(deadline_s, 4),
            deadline_hit_rate=len(hits) / n_urgent,
            p99_ttft_wall_s=(round(float(np.percentile(waits, 99)), 4)
                             if waits else None),
            shed=health.deadline_shed + health.infeasible_shed,
            infeasible_shed=health.infeasible_shed,
            preemptions=health.preemptions,
            rung_transitions=health.rung_transitions,
            wall_s=round(wall, 3)))
    # overload control never changes tokens: bulk rows that COMPLETED
    # (not shed — bulk carries no deadline, so all of them) must be
    # bit-identical across policies, preempted or not
    bulk_parity = all(
        a.result(drive=False).tokens == b.result(drive=False).tokens
        for a, b in zip(outs["fifo"]["bulk"], outs["edf"]["bulk"]))
    for r in rows:
        r["bulk_token_parity"] = bulk_parity
    if smoke:
        assert bulk_parity, "policy layer changed a bulk request's tokens"
        assert (outs["edf"]["hit_rate"] > outs["fifo"]["hit_rate"]), (
            f"EDF+preemption+degradation did not beat FIFO on deadline "
            f"hit-rate at equal load: edf={outs['edf']['hit_rate']:.2f} "
            f"vs fifo={outs['fifo']['hit_rate']:.2f}")
    return rows


def router_scaling(smoke: bool = False) -> List[dict]:
    """Aggregate decode throughput of the multi-replica serving tier:
    the same ragged workload pushed through ``ClusterRouter.replicate``
    at 1 / 2 / 4 replicas over ONE shared engine (weights + jit caches
    shared, per-replica sessions and driver threads), least-loaded
    placement, threaded drivers.

    Replicas decode CONCURRENTLY — each drives its own 2-slot session on
    its own thread against the shared jitted model — so aggregate tok/s
    should grow with the replica count up to the core budget. ``--smoke``
    asserts the cluster acceptance contract: per-request tokens
    bit-identical to solo ``generate`` at EVERY replica count (the router
    adds zero numeric deviation), every handle resolved, merged health
    counters consistent — and tok/s strictly increasing in replica count
    only on >2-core runners (a 1-2 core runner has nowhere to run the
    second replica's driver; parity is still asserted there)."""
    import os

    from repro.serving import ClusterRouter

    rng = np.random.default_rng(1)
    n_req = 16 if smoke else 48
    specs = [(int(rng.choice([8, 16, 24])), int(rng.integers(4, 9)))
             for _ in range(n_req)]
    requests = [Request(prompt_tokens=rng.integers(
        1, BENCH_MOE.vocab_size, s).tolist(), max_new_tokens=m,
        request_id=f"rs-{i}") for i, (s, m) in enumerate(specs)]
    params = init_params(BENCH_MOE, jax.random.PRNGKey(0))
    eng = DyMoEEngine(BENCH_MOE, params, EngineConfig(decode_chunk=8))
    solo = [eng.generate(r).tokens for r in requests]   # also warms jit

    def serve(n):
        router = ClusterRouter.replicate(eng, n, num_slots=2,
                                         slots_len=64, threaded=True)
        try:
            router.submit(dataclasses.replace(            # warm the pool
                requests[0], request_id="rs-warm")).result()
            t0 = time.perf_counter()
            handles = [router.submit(r) for r in requests]
            results = [h.result() for h in handles]
            wall = time.perf_counter() - t0
            health = router.health()
        finally:
            router.close()
        return results, wall, health

    counts = (1, 2, 4)
    serve(1)   # compile every admission/decode shape ONCE up front: the
    #            engine's jit cache is shared across pools, so without
    #            this the first-measured count eats all compiles and the
    #            later counts inherit a warm cache (phantom "scaling")
    try:
        n_cores = len(os.sched_getaffinity(0))
    except AttributeError:  # non-Linux
        n_cores = os.cpu_count() or 1
    rows, tok_s, parity = [], {}, {}
    for n in counts:
        results, wall, health = serve(n)
        new_tokens = sum(len(r.tokens) for r in results)
        tok_s[n] = new_tokens / wall
        parity[n] = all(r.tokens == s for r, s in zip(results, solo))
        rows.append(dict(
            bench="router_scaling", arch=BENCH_MOE.name, replicas=n,
            num_requests=n_req, num_slots=2, new_tokens=new_tokens,
            decode_tok_s=round(tok_s[n], 1),
            speedup_vs_1=round(tok_s[n] / tok_s[1], 2),
            solo_parity=parity[n], n_cores=n_cores,
            submitted=health.submitted, completed=health.completed,
            reroutes=health.reroutes, restarts=health.restarts,
            status=health.status))
    if smoke:
        assert all(parity.values()), (
            "routing changed a request's tokens: "
            f"{ {n: p for n, p in parity.items() if not p} }")
        for r in rows:
            # +1 for the per-pool warm-up request
            assert r["submitted"] == n_req + 1 and \
                r["completed"] == n_req + 1, r
            assert r["status"] == "ok" and r["restarts"] == 0, r
        # scaling is asserted pairwise, each pair gated on having the
        # cores to EXPRESS that concurrency (N driver threads + the
        # submitting thread): an oversubscribed pool measures context-
        # switch thrash, not the tier — e.g. 4 replicas on this repo's
        # single-core dev box clock in at 0.2x, all parity gates green
        if n_cores > 2:
            assert tok_s[1] < tok_s[2], (
                "2-replica aggregate decode throughput did not beat "
                f"solo: { {n: round(t, 1) for n, t in tok_s.items()} }")
        if n_cores > 4:
            assert tok_s[2] < tok_s[4], (
                "4-replica aggregate decode throughput did not beat "
                f"2-replica: { {n: round(t, 1) for n, t in tok_s.items()} }")
    return rows


def run(smoke: bool = False) -> List[dict]:
    rows = []
    if not smoke:
        for arch, budgets in (("mixtral_8x7b", (16, 24)),
                              ("qwen3_30b_a3b", (12, 16))):
            cfg = get_config(arch)
            for vram in budgets:
                for sysname in ("accelerate", "mixtral-offloading",
                                "moe-infinity", "dymoe-4/2", "dymoe-4/0"):
                    ttft, tpot, stats, wb_tok = _run_system(sysname, cfg,
                                                            vram)
                    hi_b, lo_b = _SYSTEMS[sysname]["bits"]
                    err = (_grouped_kernel_oracle_err(hi_b, lo_b)
                           if hi_b <= 8 else None)
                    rows.append(dict(
                        bench="e2e_latency", arch=cfg.name, vram_gb=vram,
                        system=sysname, ttft_s=round(float(ttft), 4),
                        tpot_s=round(float(tpot), 5),
                        hit_rate=round(stats.hit_rate, 3),
                        weight_mb_per_tok=round(wb_tok / 2**20, 2),
                        kernel_oracle_err=err))
    rows.extend(measured_decode_throughput(smoke=smoke))
    rows.extend(fused_vs_dual_decode(smoke=smoke))
    rows.extend(continuous_vs_static_batching(smoke=smoke))
    rows.extend(sampled_continuous_serving(smoke=smoke))
    rows.extend(overload_burst_serving(smoke=smoke))
    rows.extend(router_scaling(smoke=smoke))
    return rows


if __name__ == "__main__":
    import argparse
    import json
    import os
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config / few tokens; assert chunked-decode "
                         "parity and speedup (CI regression guard)")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_e2e.json (machine-readable per-row "
                         "tok/s, speedups, modeled TTFT/TPOT) so the perf "
                         "trajectory is tracked across PRs")
    args = ap.parse_args()
    rows = run(smoke=args.smoke)
    for r in rows:
        print(r)
    if args.json:
        payload = dict(
            bench="bench_e2e_latency", smoke=args.smoke,
            backend=jax.default_backend(), cpu_count=os.cpu_count(),
            rows=rows)
        with open("BENCH_e2e.json", "w") as f:
            json.dump(payload, f, indent=2, default=float)
        print(f"# wrote BENCH_e2e.json ({len(rows)} rows)")
