"""The spec and the files it names."""
import json
import os
import shutil

import pytest

from harness import spec

BENCH = spec.BENCH


def test_every_cell_resolves_its_files():
    s = spec.load_spec()
    for w in s["workloads"]:
        cell = spec.resolve(s, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["kind"]
        assert cell.own["limits"]
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer


def test_names_and_units_use_allowed_characters():
    assert spec.check_names(spec.load_spec()) == []
    assert spec.NAME_RE.match("gpt2xl-1chip.aqsgd")
    assert not spec.NAME_RE.match("a b")
    assert not spec.UNIT_RE.match("tokens per second")


def test_contract_shape():
    s = spec.load_spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= s["run_seconds"] <= 51
    names = {m["name"] for m in s["end_to_end"]}
    for m in s["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in s["per_layer"]:
        assert m["moves"] in names
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for c in s["configs"]:
        assert c["file"].startswith("bench/")
        assert set(c["reduced"]) <= set(json.load(open(os.path.join(
            spec.ROOT, c["file"])))["published"])


def test_unknown_cell_and_missing_file_are_errors(tmp_path):
    s = spec.load_spec()
    with pytest.raises(spec.SpecError):
        spec.resolve(s, "no-such-cell")
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench)
    os.remove(bench / "traffic" / (s["workloads"][0]["traffic"] + ".json"))
    with pytest.raises(spec.SpecError):
        spec.resolve(s, s["workloads"][0]["name"], bench=str(bench))


def test_cell_added_as_new_files_is_found(tmp_path):
    """A new configuration, traffic mix, cell and per-layer metric are
    new files plus entries in BENCHMARK.json: nothing else changes."""
    s = spec.load_spec()
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__"))
    w = s["workloads"][0]
    old = spec.resolve(s, w["name"])
    cfg = dict(old.config, name="new-config")
    (bench / "configs" / "new-config.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "new-mix.json").write_text(
        json.dumps(dict(old.traffic, batch=2)))
    (bench / "cells" / "new-cell.json").write_text(json.dumps(old.own))
    (bench / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 1.5\n")
    s["configs"].append({"name": "new-config", "source": "x",
                         "file": "bench/configs/new-config.json",
                         "reduced": [], "why": "test"})
    s["workloads"].append({"name": "new-cell", "config": "new-config",
                           "traffic": "new-mix", "chips": 1, "why": "t"})
    s["per_layer"].append({"name": "new_metric", "unit": "ms",
                           "better": "lower", "source": "device_trace",
                           "layer": "launcher loop",
                           "moves": "tokens_per_s",
                           "workloads": ["new-cell"]})
    cell = spec.resolve(s, "new-cell", bench=str(bench))
    assert cell.traffic["batch"] == 2
    assert "new_metric" in [m["name"] for m in cell.per_layer]
    assert spec.metric_path("new_metric", str(bench)).endswith(
        "new_metric.py")
    assert "new_metric" not in [
        m["name"] for m in spec.resolve(s, w["name"],
                                        bench=str(bench)).per_layer]
