"""Discovery by name, and the shape BENCHMARK.json must keep: a new
configuration, traffic mix or per-layer metric is a new file plus an
entry, with no edit to the harness."""
import json
import re
from pathlib import Path

import pytest

from bench import harness, testing

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[g]]
    assert all(NAME.match(n) for n in names)
    for g in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = [x["name"] for x in BENCH[g]]
        assert len(ns) == len(set(ns)), g
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert (ROOT / "bench" / "tests").is_dir()


def test_every_cell_finds_its_files():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    configs = {c["name"] for c in BENCH["configs"]}
    for w in cells.values():
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        cfg = harness.load_config(ROOT, BENCH, w["config"])
        assert cfg["name"] == w["config"]
        for key in ("n", "d", "metric", "corpus_seed", "index", "search",
                    "correct"):
            assert key in cfg, (w["config"], key)
        harness.load_mix(ROOT, w["traffic"])
    for m in BENCH["per_layer"]:
        assert callable(harness.load_reader(ROOT, m["name"]))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_cell_reports_setup_another_metric_and_a_layer():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for w in BENCH["workloads"]:
        names = {m["name"] for m in harness.metrics_for(BENCH, w["name"],
                                                        False)}
        assert "setup_s" in names and len(names) >= 2
        assert harness.metrics_for(BENCH, w["name"], True)
    for m in BENCH["per_layer"]:
        for cell in m.get("workloads", [w["name"] for w in
                                        BENCH["workloads"]]):
            moved = {x["name"] for x in harness.metrics_for(BENCH, cell,
                                                            False)}
            assert m["moves"] in moved, (m["name"], cell)


def test_unknown_names_are_harness_errors(tmp_path):
    with pytest.raises(harness.HarnessError):
        harness.find_cell(BENCH, "nope.batch")
    with pytest.raises(harness.HarnessError):
        harness.load_config(ROOT, BENCH, "nope")
    with pytest.raises(harness.HarnessError):
        harness.load_mix(ROOT, "nope")
    with pytest.raises(harness.HarnessError):
        harness.load_reader(ROOT, "nope.metric")


NEW_READER = '''"""Queries answered in the traced window (a test metric)."""


def read(ctx):
    return float(len(ctx["window"].qidx))
'''


def test_new_files_are_picked_up_without_an_edit(tmp_path):
    root = testing.make_root(tmp_path, configs=("tiny_l2",))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = dict(testing.TINY_CONFIGS["tiny_l2"], name="tiny_new", n=2500)
    (root / "bench/configs/tiny_new.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/tiny_small.json").write_text(
        json.dumps({"kind": "batch", "batch": 16}))
    (root / "bench/metrics/test.answered.py").write_text(NEW_READER)
    bench["configs"].append({"name": "tiny_new", "source": "test",
                             "file": "bench/configs/tiny_new.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny_new.small", "config": "tiny_new",
                               "traffic": "tiny_small", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if "qps" == m["name"]:
            m["workloads"].append("tiny_new.small")
    bench["per_layer"].append({"name": "test.answered", "unit": "queries",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "qps",
                               "workloads": ["tiny_new.small"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    e2e = testing.run(root, "tiny_new.small", seconds=0.5)
    assert e2e["correct"], e2e["checks"]
    assert set(e2e["metrics"]) == {"setup_s", "qps", "recall_at_10"}
    layer = testing.run(root, "tiny_new.small", seconds=0.5, trace=True)
    assert layer["metrics"]["test.answered"]["value"] % 16 == 0
    assert layer["metrics"]["test.answered"]["value"] >= 16
