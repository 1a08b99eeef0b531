"""One run of one cell: set-up, warm-up, the measured window, the
metrics, and the correctness check."""
from __future__ import annotations

import gc
import importlib
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

from bench.harness import correct as correct_mod
from bench.harness import traffic as traffic_mod
from bench.harness.stats import percentile

BENCH = Path(__file__).resolve().parents[1]
CONFIGS_DIR = BENCH / "configs"
METRICS_DIR = BENCH / "metrics"
# the trace starts this long before the window and stops this long after
# it, so every program that overlaps the window is recorded whole (a
# decode chunk takes a few seconds)
TRACE_MARGIN_S = 5.0
now = time.perf_counter


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_config(name: str, directory: Path = CONFIGS_DIR) -> dict:
    path = directory / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration {name!r} ({path})")
    return json.loads(path.read_text())


def load_metric(name: str, directory: Path = METRICS_DIR):
    """The reader module ``bench/metrics/<name>.py``."""
    path = directory / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CompileStats:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (as ``chip_smoke.py`` counts them). One per process: JAX keeps
    its listeners for the process's life."""

    _instance = None

    @classmethod
    def get(cls, jax) -> "CompileStats":
        if cls._instance is None:
            cls._instance = cls(jax)
        return cls._instance

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def metrics_for(bench: dict, workload: str, kind: str) -> list:
    """The cell's metrics of ``kind`` (``end_to_end`` / ``per_layer``)."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


# --------------------------------------------------------------- set-up

def _jax_setup(require_tpu: bool, chips: int):
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    # cache every program, not only those compiling for over a second
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise NoChip(f"JAX found no TPU (platform "
                         f"{devices[0].platform!r})")
        if len(devices) < chips:
            raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                         f"{len(devices)}")
    return jax, devices, cache_dir


def _wave(session, stepper, Request, n: int, s: int, max_new: int,
          vocab: int) -> None:
    """One admission wave of shape ``(n, s)``: one prompt of ``s`` tokens
    and ``n - 1`` one-token prompts, submitted together so the next step
    admits them as one wave (the program's shape is ``(n, longest)``
    whatever the other rows hold)."""
    reqs = [Request(prompt_tokens=[1 + (i % (vocab - 1))] * (s if i == 0
                                                              else 1),
                    max_new_tokens=max_new, request_id=f"warm-{n}-{s}-{i}")
            for i in range(n)]
    with stepper.lock:
        hs = [session.submit(r) for r in reqs]
    stepper.wake.set()
    for h in hs:
        h.result(drive=False)


def warm_shapes(session, stepper, mix: dict, Request, vocab: int) -> int:
    """Compile (or load) every program the mix's traffic can reach, and
    nothing else:

    * for each ``n`` from 1 (the solo program) to the slot count, a wave
      ``(n, shortest prompt)`` whose requests decode one token: the
      admission program, the injection of ``n`` rows into the slot batch
      and the decode chunk at ``live_cap_for(n, slots)``;
    * each other wave ``(n, s)``, ``s`` a longer prompt length the mix
      draws, with requests that end at their first token (no decode chunk
      follows)."""
    slots = mix["slots"]
    lengths = traffic_mod.prompt_lengths(mix)
    for n in range(1, slots + 1):
        _wave(session, stepper, Request, n, lengths[0], 2, vocab)
    for s in lengths[1:]:
        for n in range(1, slots + 1):
            _wave(session, stepper, Request, n, s, 1, vocab)
    return len(lengths) * slots


# --------------------------------------------------------------- metrics

def tokens_produced(records, t0: float, t1: float) -> float:
    """Output tokens the clients received, each delivery's tokens spread
    evenly over the time since that client's previous delivery (its submit,
    for the first token), counted by the share of that time inside
    [t0, t1]. The session delivers a decode chunk's tokens at once, every
    few seconds and for all rows together, so counting deliveries inside
    the window would move in steps of a whole chunk of the batch."""
    total = 0.0
    for r in records:
        prev = r.submitted
        for t, n in r.arrivals:
            if t > prev:
                total += n * max(0.0, min(t, t1) - max(prev, t0)) / (t - prev)
            elif t0 <= t <= t1:
                total += n
            prev = t
    return total


def end_to_end(records, t0: float, t1: float) -> dict:
    """Client-side numbers over the window [t0, t1]."""
    tokens = tokens_produced(records, t0, t1)
    tpots = [(r.arrivals[-1][0] - r.first) / (len(r.tokens) - 1) * 1e3
             for r in records
             if r.finished and t0 <= r.arrivals[-1][0] <= t1
             and len(r.tokens) > 1]
    ttfts = [(r.first - r.start) * 1e3 for r in records
             if r.first is not None and t0 <= r.first <= t1]
    out = {"output_tok_s": tokens / (t1 - t0)}
    if tpots:
        out["tpot_p95_ms"] = percentile(tpots, 95)
    if ttfts:
        out["ttft_p95_ms"] = percentile(ttfts, 95)
        out["ttft_p50_ms"] = percentile(ttfts, 50)
    out["_counts"] = {"tokens": round(tokens, 3),
                      "tpot_requests": len(tpots),
                      "ttft_requests": len(ttfts)}
    return out


# ------------------------------------------------------------------ run

def run(bench: dict, cell: dict, *, seed: int, seconds: float, trace: bool,
        t_process: float, require_tpu: bool = True,
        configs_dir: Path = CONFIGS_DIR,
        traffic_dir: Path = traffic_mod.TRAFFIC_DIR,
        limits_dir: Path = correct_mod.LIMITS_DIR,
        fault=None, controls=(), save_trace=None, warm: bool = True
        ) -> dict:
    """One run. ``fault``, for tests only, is called with the engine before
    serving and may break the timed path. ``controls`` (calibration only)
    names lower-precision variants of the reference to read at the same
    positions (see ``forward_rows``). ``save_trace`` (a path) keeps an
    excerpt of the reduced trace (two whole programs), gzipped JSON, for
    the reduction's tests. ``warm=False`` (calibration only, for a
    process that has run the cell before) skips the shape warm-up: its
    programs then load from the compilation cache as traffic reaches
    them."""
    spec = load_config(cell["config"], configs_dir)
    mix = traffic_mod.load(cell["traffic"], traffic_dir)
    limits = correct_mod.load_limits(cell["name"], limits_dir)
    jax, devices, cache_dir = _jax_setup(require_tpu, cell["chips"])
    dev = devices[0]
    stats = CompileStats.get(jax)
    family = importlib.import_module(f"bench.families.{spec['family']}")
    reference = importlib.import_module(
        f"bench.references.{family.REFERENCE}")
    from repro.serving import DyMoEEngine, EngineConfig, Request
    from bench.harness.serve import Stepper, Traffic

    cfg = family.program_config(spec)
    log(f"{cell['name']}: {dev.device_kind} x{len(devices)}, cache "
        f"{cache_dir}, seed {seed}")
    t = now()
    params, qparams = family.init_weights(cfg, reference.seed_key(seed))
    jax.block_until_ready((params, qparams))
    log(f"weights from the seed: {now() - t:.1f} s")
    engine = DyMoEEngine(cfg, params, EngineConfig(), qparams=qparams)
    del params, qparams
    if fault is not None:
        fault(engine)
    session = engine.serve(num_slots=mix["slots"], slots_len=mix["slots_len"])
    stepper = Stepper(session)
    stepper.start()
    t = now()
    waves = (warm_shapes(session, stepper, mix, Request, spec["vocab_size"])
             if warm else 0)
    log(f"warmed {waves} admission-wave shapes in {now() - t:.1f} s; "
        f"compiles so far {stats.compiles} ({stats.seconds:.1f} s), "
        f"persistent-cache hits {stats.cache_hits}")

    traffic = Traffic(session, stepper, mix, spec["vocab_size"], seed, Request)
    traffic.start()
    time.sleep(mix["warmup_s"])
    tdir = None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tdir, profiler_options=opts)
        time.sleep(TRACE_MARGIN_S)
    compiles0 = stats.compiles
    span = jax.profiler.TraceAnnotation("bench.window")
    t0 = now()
    span.__enter__()
    time.sleep(max(0.0, t0 + seconds - now()))
    t1 = now()
    span.__exit__(None, None, None)
    window_compiles = stats.compiles - compiles0
    if trace:
        time.sleep(TRACE_MARGIN_S)
        jax.profiler.stop_trace()
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    traffic.stop()
    stepper.stop_when_idle()
    health = session.health()
    session.close()
    records = list(traffic.records)
    late = [r.late_s for r in records if t0 <= r.start <= t1]
    log(f"compiles inside the window: {window_compiles}")
    if mix["loop"] == "open" and late:
        log(f"generator lateness over {len(late)} arrivals: max "
            f"{max(late) * 1e3:.3f} ms, p95 {percentile(late, 95) * 1e3:.3f}"
            " ms")

    e2e = end_to_end(records, t0, t1)
    failed = sum(1 for r in records if r.error is not None)
    failed = max(failed, health.admission_failures + health.dispatch_failures
                 + health.queue_rejections + health.deadline_shed
                 + health.infeasible_shed)
    log(f"window {t1 - t0:.3f} s: {e2e['_counts']}; requests "
        f"{len(records)}, failed {failed}, health {health.status}")

    # ---- the traced run's per-layer metrics
    per_layer, breakdown, busy = {}, None, None
    if trace:
        from bench.harness import trace as trace_mod
        from bench.harness.peaks import peaks_for

        tr = trace_mod.load_xspace(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        if save_trace is not None:
            trace_mod.save_excerpt(tr, save_trace)
        # what a per-layer reader gets: the reduced trace over the window,
        # the client records, the window on the host clock, the sizes, the
        # chip's peaks and the decode chunk length
        ctx = SimpleNamespace(trace=tr, records=records, t0=t0, t1=t1,
                              spec=spec, peaks=peaks_for(dev.device_kind),
                              decode_chunk=engine.ecfg.decode_chunk)
        for m in metrics_for(bench, cell["name"], "per_layer"):
            v = load_metric(m["name"]).read(ctx)
            if v is not None:
                per_layer[m["name"]] = {"value": v, "unit": m["unit"]}
        ops = trace_mod.clip(tr.ops(), tr.window)
        busy = trace_mod.busy_ns(ops, tr.window) / 1e9
        stepper_thread = next((th for th, nm, _, _ in tr.host
                              if nm == "bench.step"), "")
        breakdown = {
            "device_ops": trace_mod.top_by_name(trace_mod.leaves(ops), 10),
            "idle_gaps": trace_mod.name_gaps(
                trace_mod.gaps(ops, tr.window), tr.host, stepper_thread, 10)}
        window_s = tr.window_s

    # ---- free the program's state, then the reference
    slots_len = mix["slots_len"]
    del engine, session, stepper, traffic
    gc.collect()
    jax.clear_caches()
    log(f"device arrays still live before the reference: "
        f"{sum(a.nbytes for a in jax.live_arrays()) / 1e9:.3f} GB")
    t = now()
    sample = correct_mod.draw_sample(
        [r for r in records if r.end is not None and t0 <= r.end <= t1],
        mix["sample"], seed)
    readings = {}
    if sample:
        batch = correct_mod.build_batch(
            [(r.draw.prompt, r.result_tokens) for r in sample],
            mix["sample"], slots_len, traffic_mod.max_length(mix["output"]))
        rows = lambda control="": reference.forward_rows(
            spec, seed, batch.tokens, batch.n_prompt, batch.n_total,
            batch.rows_pos, control=control)
        ref = rows()
        # the reference in the configuration's bfloat16: the yardstick of
        # ``mean_gap_ratio``
        readings["bf16"] = correct_mod.control_gap(ref, rows("bf16"), batch)
        yard = readings["bf16"]["mean_gap"]
        readings["program"] = correct_mod.served_gap(ref, batch, yard)
        log(f"reference over {len(sample)} requests: {now() - t:.1f} s; " +
            str({k: v for k, v in readings["program"].items()
                 if k not in ("gaps", "margins")}))
        for name in controls:
            readings[name] = correct_mod.control_gap(ref, rows(name), batch,
                                                     yard)
        del ref
    done = [r for r in records if r.finished]
    exact = {
        "stream_mismatch": {"value": sum(
            r.tokens != r.result_tokens for r in done), "limit": 0},
        "wrong_length": {"value": sum(
            len(r.result_tokens) != r.draw.max_new for r in done),
            "limit": 0},
        # a program compiled inside the window would be timed with it
        "window_compiles": {"value": window_compiles, "limit": 0}}
    checks = {**correct_mod.gap_checks(readings.get("program"), limits),
              **exact}
    correct = correct_mod.judge(checks)
    # each control in the program's place, through the same limits
    for name in controls if sample else ():
        readings[name]["correct"] = correct_mod.judge(
            {**correct_mod.gap_checks(readings[name], limits), **exact})

    metrics = {}
    if trace:
        metrics = per_layer
    else:
        for m in metrics_for(bench, cell["name"], "end_to_end"):
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": t0 - t_process, "unit": "s"}
            elif m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        device["busy_s"] = busy
        device["window_s"] = window_s
    result = {"correct": bool(correct), "attempted": len(records),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    check_lines = [f"check {k} {v['value']} limit {v['limit']}"
                   for k, v in checks.items()]
    return {"result": result, "check_lines": check_lines,
            "records": records, "t0": t0, "t1": t1, "readings": readings,
            "reference_s": now() - t}

