"""The program's spans in a trace: the readers of the per-layer metrics
that come from them, on small synthetic traces, and the innermost-span
naming of idle gaps."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.harness import cell
from bench.harness import program_trace as pt
from bench.harness import trace as tr

DATA = Path(__file__).resolve().parent / "data"
NEW = ("expert_kernel_roofline", "boundary_host_ms", "replay_lag_ms")
# (op name, duration ns) of one decode program
PROGRAM = [("%fusion.1", 400), ("%expert_quant_matmul_grouped_pallas.4",
                                2000),
           ("%dynamic-slice_bitcast_fusion.6", 600), ("%fusion.7", 300)]
LENGTH = sum(d for _, d in PROGRAM)
STEPS = 4
WEIGHT_BYTES = 1_000_000


def _decode(t0, uid):
    """One decode program starting at ``t0``: its module and its ops
    (inside an enclosing loop event)."""
    ops = [("%while.0", t0, t0 + LENGTH)]
    t = t0
    for name, d in PROGRAM:
        ops.append((name, t, t + d))
        t += d
    return (f"jit_decode_many_batched({uid})", t0, t0 + LENGTH), ops


def _trace(window=(0, 12000)):
    """Two chunk boundaries: an admission wave's prefill, then the decode
    chunk it dispatched (chunks 0 and 1; the replay job of each runs on
    the worker after the chunk's boundary sync)."""
    mods, ops, program = [], [], []
    for chunk, b0 in enumerate((0, 6000)):
        mods.append((f"jit_prefill({chunk})", b0 + 100, b0 + 500))
        ops.append(("%fusion.0", b0 + 100, b0 + 500))
        m, o = _decode(b0 + 1000, 7)
        mods.append(m)
        ops += o
        program += [
            ("stepper", "dymoe.step", b0 + 50, m[2] + 60, {"boundary": chunk}),
            ("stepper", "dymoe.admit", b0 + 60, b0 + 600,
             {"wave": chunk, "rows": 2}),
            ("stepper", "dymoe.sync", b0 + 200, b0 + 520, {"wave": chunk}),
            ("stepper", "dymoe.dispatch", b0 + 900, b0 + 950,
             {"chunk": chunk, "rows": 2, "live_cap": 2, "steps": STEPS}),
            ("stepper", "dymoe.sync", b0 + 950, m[2] + 10, {"chunk": chunk}),
            ("worker", "dymoe.replay", m[2] + 40, m[2] + 540,
             {"kind": "chunk", "chunk": chunk, "rows": 2,
              "kernel_weight_bytes": WEIGHT_BYTES})]
    return pt.ProgramTrace(device={tr.MODULE_LINE: mods, tr.OPS_LINE: ops},
                           host=[("main", "bench.window", *window)],
                           window=window, program=program)


def _ctx(trace):
    return SimpleNamespace(trace=trace, peaks={"hbm_bytes_per_s": 1e12})


def _read(name, trace):
    return cell.load_metric(name).read(_ctx(trace))


def test_decode_programs_by_name_and_their_chunks():
    t = _trace()
    mods = pt.decode_programs(t)
    assert [m[0] for m in mods] == ["jit_decode_many_batched(7)"] * 2
    assert [d[4]["chunk"] for d in pt.chunk_of(mods, t)] == [0, 1]
    # a program whose enqueue the trace missed has no chunk
    t.program = [p for p in t.program
                 if not (p[1] == "dymoe.dispatch" and p[4]["chunk"] == 0)]
    assert pt.chunk_of(mods, t)[0] is None


def test_expert_kernel_roofline_from_the_replay_counter():
    # 2 chunks x 1 MB over 2 x 2000 ns of kernel at 1e12 B/s
    assert _read("expert_kernel_roofline", _trace()) == pytest.approx(
        100 * 2 * WEIGHT_BYTES / (2 * 2000e-9 * 1e12))
    # a window that ends halfway through the second chunk's program
    # counts half its bytes against the kernel time inside the window
    # (its kernel runs 400-2400 ns into the program: 1250 ns inside)
    half = _trace(window=(0, 7000 + LENGTH / 2))
    assert _read("expert_kernel_roofline", half) == pytest.approx(
        100 * 1.5 * WEIGHT_BYTES / ((2000 + 1250) * 1e-9 * 1e12))


def test_boundary_host_ms_leaves_out_the_syncs():
    t = _trace()
    # each step spans (b0+50, end+60); syncs (b0+200, b0+520) and
    # (b0+950, end+10)
    step = (1000 + LENGTH + 60) - 50
    host = step - 320 - (1000 + LENGTH + 10 - 950)
    assert _read("boundary_host_ms", t) == pytest.approx(host / 1e6)


def test_replay_lag_from_sync_end_to_replay_end():
    assert _read("replay_lag_ms", _trace()) == pytest.approx(530 / 1e6)


@pytest.mark.parametrize("name", NEW)
def test_readers_read_nothing_from_a_trace_without_program_spans(name):
    base = _trace()
    plain = tr.Trace(device=base.device, host=base.host, window=base.window)
    assert _read(name, plain) is None
    assert _read(name, pt.ProgramTrace(device=plain.device, host=plain.host,
                                       window=plain.window)) is None


def test_name_gaps_by_the_innermost_covering_span():
    host = [("stepper", "bench.step", 0, 100),
            ("client", "bench.stream_wait", 0, 100)]
    program = [("stepper", "dymoe.step", 10, 90, {}),
               ("stepper", "dymoe.sync", 20, 80, {}),
               ("worker", "dymoe.replay", 25, 75, {})]
    t = pt.ProgramTrace(device={}, host=host, window=(0, 100),
                        program=program)
    out = pt.name_gaps([(30, 70), (85, 99), (5, 12)], t, "stepper")
    assert out == [["dymoe.sync", 40e-9], ["bench.step", 14e-9],
                   ["bench.step", 7e-9]]
    # nothing of the stepper's covers it: another thread's innermost
    # span names it
    t.program = program[2:]
    t.host = host[1:]
    assert pt.name_gaps([(30, 70)], t, "stepper") == [
        ["dymoe.replay", 40e-9]]


def test_json_round_trip_and_old_excerpts_load():
    t = _trace()
    back = pt.ProgramTrace.from_json(t.to_json())
    assert back.program == [tuple(p) for p in t.program]
    assert back.ops() == t.ops()
    old = pt.ProgramTrace.from_json(
        tr.load_excerpt(DATA / "olmoe_decode_1s.trace.json.gz").to_json())
    assert old.program == [] and old.ops()


def test_save_excerpt_keeps_one_whole_boundary(tmp_path):
    path = tmp_path / "x.trace.json.gz"
    pt.save_excerpt(_trace(), str(path))
    ex = pt.load_excerpt(str(path))
    # from the first step's start to its chunk's replay end
    assert ex.window == (50, 1000 + LENGTH + 540)
    assert [m[0] for m in ex.modules()] == ["jit_prefill(0)",
                                            "jit_decode_many_batched(7)"]
    assert {p[1] for p in ex.program} == {
        "dymoe.step", "dymoe.admit", "dymoe.sync", "dymoe.dispatch",
        "dymoe.replay"}
    for name in NEW:
        assert _read(name, ex) is not None, name


def test_recorded_excerpt_read_end_to_end():
    """One chunk boundary recorded on a v5e chip (OLMoE-1B-7B,
    decode_heavy; ``program_trace.save_excerpt``): the decode program is
    found by name and matched to its enqueue, the span readers read it,
    and every idle gap is named by a span."""
    ex = pt.load_excerpt(DATA / "olmoe_decode_spans.trace.json.gz")
    (m,) = pt.decode_programs(ex)
    (d,) = pt.chunk_of([m], ex)
    assert d[4] == {"chunk": 22, "rows": 14, "live_cap": 16, "steps": 16}
    ctx = SimpleNamespace(trace=ex, peaks={"hbm_bytes_per_s": 819e9})
    roofline = cell.load_metric("expert_kernel_roofline").read(ctx)
    assert roofline == pytest.approx(3.5373, abs=1e-4)
    assert cell.load_metric("boundary_host_ms").read(ctx) == pytest.approx(
        9.521, abs=1e-3)
    assert cell.load_metric("replay_lag_ms").read(ctx) == pytest.approx(
        145.150, abs=1e-3)
    stepper = next(p[0] for p in ex.program if p[1] == "dymoe.step")
    names = pt.name_gaps(tr.gaps(ex.ops(), ex.window), ex, stepper, 6)
    assert [n for n, _ in names] == ["dymoe.sync", "dymoe.step",
                                     "dymoe.step", "bench.step",
                                     "dymoe.admit", "dymoe.step"]


def test_load_keeps_program_spans_with_their_stats(tmp_path):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("dymoe.dispatch", chunk=3,
                                              steps=16):
                jax.numpy.ones(4).block_until_ready()
            with jax.profiler.TraceAnnotation("other.span"):
                pass
    finally:
        jax.profiler.stop_trace()
    t = pt.load(str(tmp_path))
    assert [(p[1], p[4]) for p in t.program] == [
        ("dymoe.dispatch", {"chunk": 3, "steps": 16})]
    (th, _, s, e, _) = t.program[0]
    assert th == next(h[0] for h in t.host if h[1] == "bench.window")
    assert t.window[0] <= s < e <= t.window[1]
