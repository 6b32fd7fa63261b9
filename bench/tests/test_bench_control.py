"""The control, the reference at bfloat16 in the program's place, comes
out not correct: its distances miss the exact ones."""
import pytest

from bench import testing


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return testing.make_root(tmp_path_factory.mktemp("bench_control"))


@pytest.mark.parametrize("cell", ["tiny_l2.batch", "tiny_ip.serve"])
def test_control_is_not_correct(root, cell):
    r = testing.run(root, cell, system="control")
    assert r["correct"] is False
    assert not r["checks"]["dist_gap"]["ok"]
    assert r["checks"]["dist_gap"]["value"] > 10 * r["checks"]["dist_gap"]["limit"]
    assert r["checks"]["bad_ids"]["ok"] and r["checks"]["unanswered"]["ok"]
