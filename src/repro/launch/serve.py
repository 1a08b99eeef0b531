"""Serving launcher: DyMoE-orchestrated generation with edge-latency
accounting, through the step-driven engine API.

One-shot (single request, greedy or sampled):

  PYTHONPATH=src python -m repro.launch.serve --arch olmoe-1b-7b \
      --vram-gb 16 --mode 4/2 --prompt-len 64 --max-new 32 \
      --temperature 0.8 --top-k 40 --seed 7

Open serving loop (``--requests N``): requests are SUBMITTED while the
engine is being stepped — half up front, the rest mid-run after a few
chunk boundaries (bursty-arrival shape) — and the last request's tokens
are streamed as TokenChunk events while its replay finalizes.

Robust serving knobs: ``--max-queue`` bounds the admission queue
(submits past it hit typed ``QueueFull`` backpressure and are retried
with backoff while the loop keeps stepping), ``--deadline-s`` gives every
request a wall-clock deadline (queued requests past it are shed with
``DeadlineExceeded``; in-flight ones are evicted with a partial result).
Ctrl-C drains gracefully: in-flight requests finish, queued ones are
cancelled, results collected — a second Ctrl-C aborts the drain.

SLO overload control (``--policy edf``): admission is ordered by
(priority desc, earliest deadline); ``--priority N`` marks the mid-run
burst as an urgent tier that admits first and PREEMPTS busy lower-tier
slots at a chunk boundary (preempted requests resume bit-identical);
queue pressure walks the precision degradation ladder (watch
``pressure_rung`` / ``rung_transitions`` / ``preemptions`` in the
reported health). ``--policy fifo`` (default) is the bit-exact
pre-policy path. Overload demo:

  PYTHONPATH=src python -m repro.launch.serve --requests 8 \
      --num-slots 2 --policy edf --priority 2 --deadline-s 30

Multi-replica tier (``--replicas N``): the same open loop routed through
a ``ClusterRouter`` — N sessions over ONE shared engine, least-loaded
placement, one driver thread per replica — reporting per-replica health
plus the merged cluster counters. ``--expert-parallel`` loads the model
sharded over a (1, n_devices) mesh (routed expert stores sharded over E,
KV slots over "model"); on CPU it best-effort requests 4 simulated host
devices before jax initializes (``xla_force_host_platform_device_count``).
Cluster demo:

  PYTHONPATH=src python -m repro.launch.serve --requests 8 \
      --replicas 2 --expert-parallel
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import ensure_sim_devices, make_chip_mesh, \
    make_sim_mesh
from repro.models import init_params, init_quantized_params
from repro.models.config import DyMoEPolicy
from repro.serving import ClusterRouter, DyMoEEngine, EngineConfig, \
    Request, SamplingParams, submit_with_retry
from repro.serving.cost_model import EdgeProfile


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmoe-1b-7b")
    ap.add_argument("--vram-gb", type=int, default=16)
    ap.add_argument("--mode", choices=["4/2", "4/0", "off"], default="4/2")
    ap.add_argument("--retention", type=float, default=0.75)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation for sampled decoding (0 = off)")
    ap.add_argument("--seed", type=int, default=None,
                    help="per-request PRNG seed; required for "
                         "temperature > 0 (else greedy fallback)")
    ap.add_argument("--requests", type=int, default=1,
                    help="> 1: open serving-loop demo with staggered "
                         "submissions and streamed tokens")
    ap.add_argument("--num-slots", type=int, default=2,
                    help="device slots for the open serving loop")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound the admission queue: submits past it get "
                         "typed QueueFull backpressure (retried here with "
                         "backoff while the loop keeps stepping)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock deadline: queued past it "
                         "-> shed (DeadlineExceeded); in flight past it "
                         "-> evicted with a partial result")
    ap.add_argument("--policy", choices=["fifo", "edf"], default="fifo",
                    help="scheduling policy: fifo (default, bit-exact "
                         "pre-policy path) or edf (priority + earliest-"
                         "deadline admission, infeasibility shedding, "
                         "chunk-boundary preemption, pressure-adaptive "
                         "precision degradation)")
    ap.add_argument("--priority", type=int, default=0,
                    help="priority tier for the MID-RUN burst half of the "
                         "open loop (higher admits first and may preempt "
                         "under --policy edf; ignored under fifo)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="> 1: route the open loop through a ClusterRouter "
                         "— N sessions over one shared engine, least-"
                         "loaded placement, one driver thread per replica "
                         "— and report per-replica health")
    ap.add_argument("--expert-parallel", action="store_true",
                    help="load the model sharded over a (1, n_devices) "
                         "mesh: routed expert stores sharded over E, KV "
                         "slots over the model axis (on CPU, best-effort "
                         "4 simulated host devices)")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--no-cache", action="store_true")
    ap.add_argument("--no-prefetch", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    mesh = None
    if args.expert_parallel:
        # CPU only: simulated host devices for the mesh. The flag must be
        # set before the first jax init, and a TPU backend ignores it (the
        # mesh is then made of the real chips)
        ensure_sim_devices(4)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    pol = DyMoEPolicy(
        enabled=args.mode != "off",
        low_bits=0 if args.mode == "4/0" else 2,
        retention=args.retention)
    cfg = dataclasses.replace(cfg, dymoe=pol)
    if args.expert_parallel:
        make = make_sim_mesh if jax.default_backend() == "cpu" \
            else make_chip_mesh
        mesh = make(len(jax.devices()))
    qparams = None
    if args.mode == "off":
        params = init_params(cfg, jax.random.PRNGKey(0))
    else:   # packed stores built layer by layer, no dense expert stack
        params, qparams = init_quantized_params(cfg, jax.random.PRNGKey(0))
    engine = DyMoEEngine(cfg, params, EngineConfig(
        profile=EdgeProfile().with_vram(args.vram_gb),
        use_dymoe=args.mode != "off",
        enable_cache=not args.no_cache,
        enable_prefetch=not args.no_prefetch,
        enable_dyquant=args.mode != "off"),
        mesh=mesh, expert_parallel=args.expert_parallel, qparams=qparams)
    sampling = SamplingParams(temperature=args.temperature,
                              top_k=args.top_k, seed=args.seed)

    def request(i: int, priority: int = 0) -> Request:
        # per-request sampling stream: seed offset keeps streams distinct
        sp = (sampling if sampling.seed is None else
              dataclasses.replace(sampling, seed=sampling.seed + i))
        return Request(prompt_tokens=list(range(1 + i, args.prompt_len
                                                + 1 + i)),
                       max_new_tokens=args.max_new, sampling=sp,
                       request_id=f"req-{i}", priority=priority,
                       deadline_s=args.deadline_s)

    if args.requests <= 1:
        res = engine.generate(request(0))
        print(json.dumps(dict(
            arch=cfg.name, mode=args.mode, vram_gb=args.vram_gb,
            temperature=args.temperature, top_k=args.top_k, seed=args.seed,
            ttft_ms=res.ttft_s * 1e3, tpot_ms=res.tpot_s * 1e3,
            wall_s=res.wall_s, tokens=res.tokens[:16],
            cache=res.cache_stats), indent=2))
        return

    # ---- open serving loop: staggered submissions + streamed tokens
    slots_len = args.prompt_len + args.max_new + args.requests
    if args.replicas > 1:
        session = ClusterRouter.replicate(
            engine, args.replicas, num_slots=args.num_slots,
            slots_len=slots_len, max_queue=args.max_queue,
            policy=args.policy, threaded=True)
    else:
        session = engine.serve(num_slots=args.num_slots,
                               slots_len=slots_len,
                               max_queue=args.max_queue,
                               policy=args.policy)
    handles = []
    try:
        n_first = max(1, args.requests // 2)
        for i in range(n_first):
            handles.append(submit_with_retry(session, request(i),
                                             drive=True))
        for _ in range(2):       # the engine is already decoding...
            if args.replicas > 1:
                time.sleep(0.02)   # ...on the per-replica driver threads
            else:
                engine.step()
        # ...the burst arrives — under --policy edf with --priority > 0
        # it admits first and may preempt the busy bulk slots
        for i in range(n_first, args.requests):
            handles.append(submit_with_retry(
                session, request(i, priority=args.priority), drive=True))
        print(f"# streaming {handles[-1].request_id} "
              f"(submitted mid-run, admitted into a freed slot):")
        for ev in handles[-1].stream():
            print(f"  {ev.phase:8s} +{len(ev.tokens):2d} tok "
                  f"modeled {ev.modeled_s * 1e3:8.3f} ms  {ev.tokens}")
        session.drain(cancel_queued=False)   # resolve every handle
    except KeyboardInterrupt:
        # graceful Ctrl-C: finish what's in flight, cancel what's still
        # queued, then report — a second Ctrl-C interrupts the drain too
        print("\n# Ctrl-C: draining in-flight requests "
              "(Ctrl-C again to abort the drain)...")
        session.drain()
    finally:
        health = session.health()
        session.close()   # any still-unresolved handle -> SessionClosed

    def row(h):
        placed = getattr(h, "replica", None)   # ClusterHandle only
        if h.error is not None:
            return dict(id=h.request_id, replica=placed,
                        error=type(h.error).__name__)
        r = h.result()   # already resolved by the drain above
        return dict(id=h.request_id, replica=placed,
                    priority=h.request.priority,
                    ttft_ms=r.ttft_s * 1e3,
                    tpot_ms=r.tpot_s * 1e3,
                    queue_wait_ms=(r.queue_wait_s or 0) * 1e3,
                    cancelled=r.cancelled,
                    deadline_expired=r.deadline_expired,
                    preempted=r.preempted,
                    tokens=r.tokens[:8])

    print(json.dumps(dict(
        arch=cfg.name, mode=args.mode, vram_gb=args.vram_gb,
        num_slots=args.num_slots, max_queue=args.max_queue,
        deadline_s=args.deadline_s, policy=args.policy,
        priority=args.priority, replicas=args.replicas,
        expert_parallel=args.expert_parallel,
        n_devices=len(jax.devices()),
        health=dataclasses.asdict(health),
        requests=[row(h) for h in handles]), indent=2))
    failed = [h.request_id for h in handles if h.error is not None]
    if failed:
        sys.exit(f"{len(failed)} request(s) resolved with an error: "
                 f"{', '.join(failed)}")


if __name__ == "__main__":
    main()
