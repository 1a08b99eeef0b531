"""A configuration, a traffic mix, a metric or a limit is found by its
name in a directory of its own: adding one edits no existing file."""
import json
from types import SimpleNamespace

import pytest

from bench.harness import cell, correct, traffic


def test_new_files_are_found_by_name(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "limits").mkdir()
    (tmp_path / "configs" / "new-model.json").write_text(
        json.dumps({"name": "new-model", "hidden_size": 64}))
    (tmp_path / "traffic" / "new_mix.json").write_text(
        json.dumps({"loop": "open", "rate_per_s": 2.0}))
    (tmp_path / "metrics" / "new_metric.x.py").write_text(
        "LAYER = 'somewhere'\n\ndef read(ctx):\n    return ctx.value * 2\n")
    (tmp_path / "limits" / "new-model.new_mix.json").write_text(
        json.dumps({"logit_gap": {"limit": 0.5}}))

    assert cell.load_config("new-model", tmp_path / "configs")[
        "hidden_size"] == 64
    assert traffic.load("new_mix", tmp_path / "traffic")["rate_per_s"] == 2
    m = cell.load_metric("new_metric.x", tmp_path / "metrics")
    assert m.LAYER == "somewhere"
    assert m.read(SimpleNamespace(value=21)) == 42
    assert correct.load_limits("new-model.new_mix", tmp_path / "limits")[
        "logit_gap"]["limit"] == 0.5


def test_a_missing_name_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        cell.load_config("absent", tmp_path)
    with pytest.raises(FileNotFoundError):
        traffic.load("absent", tmp_path)
    with pytest.raises(FileNotFoundError):
        cell.load_metric("absent", tmp_path)


def test_every_benchmark_entry_has_its_files():
    from bench.run import load_benchmark
    bench = load_benchmark()
    for c in bench["configs"]:
        assert cell.load_config(c["name"])["name"] == c["name"]
    for w in bench["workloads"]:
        traffic.load(w["traffic"])
        correct.load_limits(w["name"])
    for m in bench["per_layer"]:
        mod = cell.load_metric(m["name"])
        assert mod.LAYER == m["layer"]
