"""Trace reduction: busy-interval union, idle share, gaps, sums and
sorting by name, the decode/prefill split of programs."""
import pytest

from bench.harness import programs
from bench.harness import trace as tr


def test_union_merges_overlaps_and_nesting():
    assert tr.union([(5, 7), (0, 2), (1, 3), (6, 6.5), (9, 9)]) == \
        [(0, 3), (5, 7)]


def test_busy_and_idle_share_inside_window():
    evs = [("a", 0, 10), ("b", 5, 15), ("c", 30, 40), ("d", 90, 120)]
    w = (0, 100)
    assert tr.busy_ns(evs, w) == 15 + 10 + 10
    assert tr.idle_share(evs, w) == pytest.approx(1 - 35 / 100)
    assert tr.gaps(evs, w) == [(15, 30), (40, 90)]


def test_idle_share_of_empty_window_is_an_error():
    with pytest.raises(ValueError):
        tr.idle_share([], (5, 5))


def test_short_name_and_top_by_name():
    evs = [("%fusion.316 = bf16[8] fusion(x)", 0, 4),
           ("%fusion.2 = f32[1] fusion(y)", 4, 5),
           ("%expert_quant_matmul_grouped_pallas.11 = bf16[64] custom-call",
            5, 15)]
    assert tr.short_name(evs[0][0]) == "fusion"
    assert tr.top_by_name(evs, 10) == [
        ["expert_quant_matmul_grouped_pallas", 10 / 1e9],
        ["fusion", 5 / 1e9]]


def test_leaves_drop_enclosing_loops():
    evs = [("%while.1", 0, 100), ("%a.1", 0, 10), ("%b.2", 20, 30),
           ("%while.2", 40, 90), ("%c.3", 50, 60)]
    assert [e[0] for e in tr.leaves(evs)] == ["%a.1", "%b.2", "%c.3"]


def test_inside_and_matching():
    mods = [("m", 0, 100), ("m", 200, 300)]
    ops = [("%k.1", 10, 20), ("%k.2", 150, 160), ("%x.1", 250, 260)]
    assert [e[0] for e in tr.inside(ops, mods)] == ["%k.1", "%x.1"]
    assert tr.matching(ops, ("k",)) == ops[:2]


def _two_programs(layers=2, chunk=4, calls=3):
    """A decode chunk (chunk passes) then an admission wave (one pass)."""
    mods = [("jit__unknown(1)", 0, 1000), ("jit__unknown(2)", 2000, 2500)]
    ops = []
    t = 1
    for _ in range(chunk * layers * calls):
        ops.append(("%expert_quant_matmul_grouped_pallas.1 = x", t, t + 5))
        t += 10
    t = 2001
    for _ in range(layers * calls):
        ops.append(("%expert_quant_matmul_grouped_pallas.2 = x", t, t + 5))
        t += 10
    return tr.Trace(device={"XLA Modules": mods, "XLA Ops": ops}, host=[],
                    window=(0, 3000))


def test_classify_decode_and_prefill_by_passes():
    trace = _two_programs()
    dec, pre = programs.classify(trace, layers=2, decode_chunk=4)
    assert [m[0] for m in dec] == ["jit__unknown(1)"]
    assert [m[0] for m in pre] == ["jit__unknown(2)"]
    assert programs.decode_steps(dec, trace.window, 4) == 4
    # a window that cuts the chunk in half counts half its steps
    assert programs.decode_steps(dec, (500, 3000), 4) == pytest.approx(2)


def test_name_gaps_prefer_the_stepper_thread():
    host = [("stepper", "bench.step", 0, 50), ("stepper", "bench.no_request",
                                              60, 100),
            ("client", "bench.stream_wait", 0, 100)]
    gaps = [(10, 40), (70, 95), (50, 60)]
    assert tr.name_gaps(gaps, host, "stepper", 3) == [
        ["bench.step", 30e-9], ["bench.no_request", 25e-9],
        ["bench.stream_wait", 10e-9]]


def test_excerpt_round_trip(tmp_path):
    trace = _two_programs()
    trace.device["XLA Ops"][0] = ("%fusion.3 = bf16[8] fusion(x)", 1, 6)
    trace.host.append(("main#0", "bench.step", 0, 2500))
    path = tmp_path / "t.json.gz"
    tr.save_excerpt(trace, str(path), programs=1)
    back = tr.load_excerpt(str(path))
    assert back.window == (0, 1000)
    assert back.ops()[0] == ("%fusion.3", 1, 6)
    assert back.host == [("main#0", "bench.step", 0, 1000)]
    assert back.modules() == [("jit__unknown(1)", 0, 1000)]



def test_recorded_chip_trace():
    """One second of a decode window of ``olmoe-1b-7b.decode_heavy``,
    recorded on a v5e (bench/tests/data; operation names cut to the HLO
    instruction): the reduction reads the numbers the run printed."""
    from pathlib import Path

    t = tr.load_excerpt(str(Path(__file__).resolve().parent / "data" /
                            "olmoe_decode_1s.trace.json.gz"))
    ops = t.ops()
    assert t.window_s == pytest.approx(1.0)
    assert tr.busy_ns(ops, t.window) / 1e9 == pytest.approx(0.991507878)
    assert 100 * tr.idle_share(ops, t.window) == pytest.approx(0.8492122)
    top = tr.top_by_name(tr.leaves(ops), 3)
    assert [k for k, _ in top] == ["expert_quant_matmul_grouped_pallas",
                                   "dynamic-slice_bitcast_fusion",
                                   "broadcast_select_fusion"]
    assert top[0][1] == pytest.approx(0.745148911)
    kernels = tr.matching(ops, ("expert_quant_matmul_grouped_pallas",))
    assert len(kernels) == 340
    # every gap lies inside the stepper's step span or before it
    stepper = next(th for th, n, _, _ in t.host if n == "bench.step")
    named = tr.name_gaps(tr.gaps(ops, t.window), t.host, stepper, 10)
    assert len(named) == 10
    assert {n for n, _ in named} <= {"bench.step", "bench.window",
                                     "bench.submit", "bench.stream_wait",
                                     "untraced"}


def test_decode_mfu_counts_work_by_its_chunks_share_of_the_window():
    """Each delivery counts by the share of its chunk's time inside the
    window, as that chunk's time does: a delivery after the window's end
    still counts for the part of its chunk the window holds."""
    import json
    from pathlib import Path
    from types import SimpleNamespace

    from bench.harness import cell, flops

    spec = json.loads((Path(__file__).resolve().parents[1] / "configs" /
                       "olmoe-1b-7b.json").read_text())
    layers, chunk = spec["num_hidden_layers"], 2
    mods, ops = [], []
    for i in range(3):                       # chunks [0, 1000) ... [2000, 3000)
        s = 1000 * i
        mods.append((f"jit__unknown({i})", s, s + 1000))
        ops += [("%expert_quant_matmul_grouped_pallas.1 = x", s + 10 * k,
                 s + 10 * k + 5) for k in range(layers * chunk)]
    trace = tr.Trace(device={"XLA Modules": mods, "XLA Ops": ops}, host=[],
                     window=(500, 2500))
    # host seconds = trace ns * 1e-9 (the window opened at t0 = 500 ns);
    # the first token comes from the prefill, then 2 tokens per chunk
    rec = SimpleNamespace(prompt_len=10, arrivals=[(0.0, 1)] + [
        ((1000 * i + 1000.5) * 1e-9, 2) for i in range(3)])
    peak = 1e15
    ctx = SimpleNamespace(trace=trace, records=[rec], t0=500e-9, t1=2500e-9,
                          spec=spec, peaks={"bf16_flops_per_s": peak},
                          decode_chunk=chunk)
    f = lambda c: flops.decode_token_flops(spec, c)
    work = (0.5 * (f(11) + f(12)) + (f(13) + f(14))
            + 0.5 * (f(15) + f(16)))
    got = cell.load_metric("decode_mfu_pct").read(ctx)
    assert got == pytest.approx(100 * work / (2000e-9 * peak))
