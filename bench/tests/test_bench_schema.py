"""The result line's schema, on tiny cells run end to end on the CPU."""
import json

import pytest

from bench import harness, testing


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return testing.make_root(tmp_path_factory.mktemp("bench_schema"))


def _check_common(r):
    assert isinstance(r["correct"], bool)
    assert isinstance(r["attempted"], int) and r["attempted"] > 0
    assert isinstance(r["failed"], int)
    dev = r["device"]
    assert dev["platform"] == "cpu" and dev["count"] >= 1
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    for m in r["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float)
    assert list(r)[-1] == "checks"
    assert list(r["checks"]) == ["recall_at_10", "dist_gap", "bad_ids",
                                 "unanswered"]
    for c in r["checks"].values():
        assert set(c) == {"value", "limit", "ok"}


def test_batch_line_has_the_end_to_end_metrics(root):
    r = testing.run(root, "tiny_l2.batch")
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert "breakdown" not in r
    _check_common(r)
    assert r["correct"] and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "qps", "recall_at_10"}
    assert r["attempted"] % 32 == 0


def test_traced_serve_line_has_layers_device_times_and_breakdown(root):
    r = testing.run(root, "tiny_ip.serve", trace=True)
    _check_common(r)
    assert r["correct"], r["checks"]
    assert {"gateway.queue_wait_p99_ms", "gateway.flush_p50_ms",
            "gateway.latency_p99_ms",
            "session.padded_rows_share"} <= set(r["metrics"])
    # no device plane on the CPU: the device readers find nothing
    assert "device.idle_share.serve" not in r["metrics"]
    assert r["device"]["window_s"] > 0 and "busy_s" in r["device"]
    bd = r["breakdown"]
    assert set(bd) == {"device_ops", "idle_gaps"}
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert list(r)[-2:] == ["breakdown", "checks"]


def test_print_result_ends_both_streams_with_the_check(capsys):
    result = {"correct": False, "attempted": 3, "failed": 1, "metrics": {},
              "device": {"platform": "tpu"},
              "checks": {"recall_at_10": {"value": 0.5, "limit": 0.8,
                                          "ok": False},
                         "dist_gap": {"value": 0.0, "limit": 1e-4,
                                      "ok": True}}}
    harness.print_result(result)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == result
    tail = err.strip().splitlines()[-2:]
    assert tail[0].startswith("bench: check recall_at_10 = 0.5 (limit >= 0.8)")
    assert tail[0].endswith("FAILED")
    assert tail[1].startswith("bench: check dist_gap = 0.0 (limit <= 0.0001)")


def test_serve_warm_up_leaves_nothing_to_build_in_the_window(root, capsys):
    r = testing.run(root, "tiny_l2.serve")
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"setup_s", "p50_ms", "recall_at_10"}
    err = capsys.readouterr().err
    assert "programs built in the window 0\n" in err
