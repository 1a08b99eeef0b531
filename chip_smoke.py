#!/usr/bin/env python3
"""Bring-up smoke test: the DyMoE serving path on TPU at OLMoE-1B-7B's
published size (16 layers, d_model 2048, 64 experts of width 1024, top-8,
vocabulary 50304), mode "4/2", weights drawn from ``--seed``.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # expert parallelism on four chips

One chip: build the packed 4-bit/2-bit expert stores layer by layer, check
the fused grouped expert kernel and the mask-select kernel against their
jnp oracles on a small input, compile the admission-wave prefill and the
decode-chunk programs (both must hold the Pallas kernel,
``tpu_custom_call``), then serve through ``DyMoEEngine.serve()`` with 4
slots: submit 2 requests, step twice, submit 2 more, stream the last one,
drain. Fails on a request error, a non-ok ``health()``, or any dispatch or
admission retry.

Four chips (``--chips 4``, this phase only): the same requests through the
expert-parallel engine over a (1, 4) mesh of the chips, beside the
one-chip engine on device 0. Fails if a device holds more than a third of
the routed expert stores, or the compiled decode program all-gathers the
packed codes.

Only the last stdout line is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or outside a checkout of the repository, the script exits
non-zero and prints no result. Wall-clock numbers printed on the way are
from this one run; TTFT/TPOT labelled "modeled" come from the RTX 3090
cost model, not from the chip.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SLOTS = 4
PROMPT_LENS = (512, 256, 384, 512)   # two admission waves of shape (2, 512)
MAX_NEW = 64
SLOTS_LEN = max(PROMPT_LENS) + MAX_NEW
KERNEL_TOL = 2e-2   # max |pallas - oracle| / max |oracle|, f32 out


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileStats:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_requests = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1

    def line(self) -> str:
        return (f"compile: {self.seconds:.1f} s in {self.compiles} backend "
                f"compiles, persistent cache hits {self.cache_hits}/"
                f"{self.cache_requests}")


def requests(cfg, seed):
    import numpy as np
    from repro.serving import Request

    rng = np.random.default_rng(seed)
    return [Request(prompt_tokens=rng.integers(1, cfg.vocab_size, n).tolist(),
                    max_new_tokens=MAX_NEW, request_id=f"req-{i}")
            for i, n in enumerate(PROMPT_LENS)]


def serve(engine, reqs):
    """The served path: 2 submitted up front, 2 mid-run, the last one
    streamed, then drained. Returns (handles, health, streamed tokens)."""
    session = engine.serve(num_slots=SLOTS, slots_len=SLOTS_LEN)
    handles = [session.submit(r) for r in reqs[:2]]
    for _ in range(2):
        session.step()
    handles += [session.submit(r) for r in reqs[2:]]
    streamed = [t for ev in handles[-1].stream() for t in ev.tokens]
    session.drain(cancel_queued=False)
    health = session.health()
    session.close()
    for h in handles:
        if h.error is not None:
            fail(f"{h.request_id} resolved with {h.error!r}")
    if health.status != "ok":
        fail(f"health is {health.status!r}: {health}")
    for field in ("dispatch_retries", "admission_retries",
                  "admission_failures", "dispatch_failures"):
        if getattr(health, field):
            fail(f"health.{field} = {getattr(health, field)}")
    if streamed != handles[-1].result().tokens:
        fail("streamed tokens differ from the request's result")
    return handles, health


def agreement(a, b) -> str:
    n = min(len(a), len(b))
    same = sum(x == y for x, y in zip(a, b))
    prefix = next((i for i in range(n) if a[i] != b[i]), n)
    return f"{same}/{max(len(a), len(b))} equal, common prefix {prefix}"


def compile_programs(engine, cfg, jnp, init_decode_state):
    """AOT-compile the admission wave and both decode-chunk programs the
    session will run (the serving calls then reuse them); returns their
    HLO texts."""
    n, s = 2, max(PROMPT_LENS)
    wave = engine._prefill.lower(
        engine.params, tokens=jnp.zeros((n, s), jnp.int32),
        qparams=engine.qparams, cache_slots=SLOTS_LEN,
        lengths=jnp.full((n,), s, jnp.int32), row_local=True,
        row_capacities=jnp.ones((n,), jnp.int32)).compile()
    caches = engine.shard_decode_state(
        init_decode_state(cfg, SLOTS, SLOTS_LEN))
    texts = {"admission wave": wave.as_text()}
    for cap in (2, 4):
        dec = engine._decode_batched.lower(
            engine.params, tokens=jnp.zeros((SLOTS,), jnp.int32),
            caches=caches, num_steps=engine.ecfg.decode_chunk,
            done=jnp.zeros((SLOTS,), bool),
            n_emitted=jnp.zeros((SLOTS,), jnp.int32),
            limits=jnp.zeros((SLOTS,), jnp.int32),
            eos_tokens=jnp.zeros((SLOTS,), jnp.int32),
            qparams=engine.qparams, live_cap=cap).compile()
        texts[f"decode chunk live_cap={cap}"] = dec.as_text()
    return texts


def check_kernels(jax, jnp, qparams, seed):
    """Grouped and mask-select kernels against their jnp oracles, on
    layer 0's w_gate store and a small capacity buffer."""
    import numpy as np
    from repro.kernels.quant_matmul.ops import expert_quant_matmul, \
        expert_quant_matmul_grouped

    w = jax.tree.map(lambda a: a[0], qparams["layers"]["moe"]["w_gate"])
    e, k = w.high.packed.shape[0], w.high.k
    cap = 8
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, cap + 1, (e, 2)).astype(np.int32)
    x = rng.standard_normal((e, 2 * cap, k)).astype(np.float32)
    live = np.arange(cap)[None, :] < counts[:, :1]
    x[:, :cap] *= live[..., None]
    x[:, cap:] *= (np.arange(cap)[None, :] < counts[:, 1:])[..., None]
    x = jnp.asarray(x, jnp.bfloat16)
    crit = jnp.asarray(rng.random(e) < 0.5)
    cases = {
        "grouped": lambda impl: expert_quant_matmul_grouped(
            x, w, jnp.asarray(counts), cap_hi=cap, impl=impl,
            out_dtype=jnp.float32),
        "mask-select": lambda impl: expert_quant_matmul(
            x, w, crit, impl=impl, out_dtype=jnp.float32),
    }
    for name, f in cases.items():
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(f("ref"))
        got = np.asarray(f("pallas"))
        err = float(np.abs(got - ref).max() / np.abs(ref).max())
        log(f"kernel {name}: max|pallas - oracle| / max|oracle| = {err:.3e}"
            f" (limit {KERNEL_TOL})")
        if not err <= KERNEL_TOL:
            fail(f"{name} kernel disagrees with its oracle: {err:.3e}")


def expert_bytes_per_device(jax, qparams):
    """Bytes of the routed expert stores held by each device."""
    import collections
    out = collections.Counter()
    for leaf in jax.tree.leaves(qparams["layers"]["moe"]):
        for shard in leaf.addressable_shards:
            out[shard.device.id] += shard.data.nbytes
    return dict(sorted(out.items()))


def collectives(text: str) -> dict:
    ops = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
           "collective-permute")
    return {op: len(re.findall(rf"\b{op}(-start)?\(", text)) for op in ops}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no repository beside {Path(__file__).name}: run it from a "
             "checkout (src/repro is missing)")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} but JAX sees {len(devices)} device(s)")
    stats = CompileStats(jax)

    from repro.configs import get_config
    from repro.models.model import init_decode_state, init_quantized_params
    from repro.serving import DyMoEEngine, EngineConfig

    cfg = get_config("olmoe_1b_7b")
    pol = cfg.dymoe
    log(f"device: {dev.device_kind} x{len(devices)}; compile cache "
        f"{cache_dir}")
    log(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"experts={cfg.num_experts}x{cfg.expert_d_ff} "
        f"top-{cfg.num_experts_per_tok} vocab={cfg.vocab_size} mode "
        f"{pol.high_bits}/{pol.low_bits}")

    t = time.perf_counter()
    params, qparams = init_quantized_params(cfg, jax.random.PRNGKey(args.seed))
    jax.block_until_ready((params, qparams))
    log(f"weights from seed {args.seed}: {time.perf_counter() - t:.1f} s "
        "wall-clock (one run, compile included)")
    reqs = requests(cfg, args.seed)
    engine = DyMoEEngine(cfg, params, EngineConfig(), qparams=qparams)

    if args.chips == 1:
        check_kernels(jax, jnp, engine.qparams, args.seed)
        texts = compile_programs(engine, cfg, jnp, init_decode_state)
        for name, text in texts.items():
            n = text.count("tpu_custom_call")
            log(f"{name}: {n} tpu_custom_call")
            if not n:
                fail(f"the compiled {name} program holds no Pallas kernel")
        log(stats.line())
        t = time.perf_counter()
        handles, health = serve(engine, reqs)
        log(f"served {len(handles)} requests in "
            f"{time.perf_counter() - t:.1f} s wall-clock (one run); health "
            f"{health.status}, retries 0")
        for h in handles:
            r = h.result()
            log(f"  {h.request_id}: prompt {h.request.prompt_len}, "
                f"{len(r.tokens)} tokens; wall-clock {r.wall_s:.3f} s "
                f"service + {r.queue_wait_s:.3f} s queued (one run); "
                f"modeled TTFT {r.ttft_s * 1e3:.1f} ms, modeled TPOT "
                f"{r.tpot_s * 1e3:.2f} ms")
        ref = engine.generate_reference(reqs[-1])
        log(f"token agreement with generate_reference ({reqs[-1].request_id},"
            f" information only): "
            f"{agreement(handles[-1].result().tokens, ref.tokens)}")
    else:
        from repro.launch.mesh import make_chip_mesh

        mesh = make_chip_mesh(4)
        ep = DyMoEEngine(cfg, params, EngineConfig(), qparams=qparams,
                         mesh=mesh, expert_parallel=True)
        per_dev = expert_bytes_per_device(jax, ep.qparams)
        total = sum(expert_bytes_per_device(jax, engine.qparams).values())
        log(f"routed expert store bytes: one-chip engine {total}; EP engine "
            f"per device {per_dev}")
        if max(per_dev.values()) > total / 3:
            fail("a device holds more than a third of the expert stores")
        texts = compile_programs(ep, cfg, jnp, init_decode_state)
        for name, text in texts.items():
            log(f"EP {name}: collectives {collectives(text)}, "
                f"{text.count('tpu_custom_call')} tpu_custom_call")
            gathers = [ln for ln in text.splitlines()
                       if re.search(r"\ball-gather(-start)?\(", ln)
                       and "u8[" in ln]
            if gathers:
                fail(f"EP {name} all-gathers packed codes: {gathers[0]}")
        log(stats.line())
        one, _ = serve(engine, reqs)
        t = time.perf_counter()
        sharded, health = serve(ep, reqs)
        log(f"EP engine served {len(sharded)} requests in "
            f"{time.perf_counter() - t:.1f} s wall-clock (one run); health "
            f"{health.status}")
        for a, b in zip(one, sharded):
            log(f"  {a.request_id}: EP vs one-chip tokens "
                f"{agreement(b.result().tokens, a.result().tokens)}")

    log(stats.line())
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"peak_bytes_in_use (device 0): {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
