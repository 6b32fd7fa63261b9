"""With the timed path broken underneath, a run that skips only the look
for a chip reads ``correct`` false, once for each fault a cell can have:
half of a batch answered with the other half's answers, an answer
altered where it is produced, answers handed to the wrong requests, and
a request never answered."""
import numpy as np
import pytest

from bench import testing, traffic


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return testing.make_root(tmp_path_factory.mktemp("bench_faults"),
                             configs=("tiny_l2",))


def _patch_session(monkeypatch, alter):
    from repro.core.searcher import Searcher
    orig = Searcher.__call__

    def broken(self, q):
        r = orig(self, q)
        ids, dists = alter(np.asarray(r.ids), np.asarray(r.dists))
        return r._replace(ids=ids, dists=dists)
    monkeypatch.setattr(Searcher, "__call__", broken)


def _half_left_out(ids, dists):
    half = ids.shape[0] // 2
    if half:
        ids, dists = ids.copy(), dists.copy()
        ids[half:2 * half] = ids[:half]
        dists[half:2 * half] = dists[:half]
    return ids, dists


def _one_answer_altered(ids, dists):
    ids = ids.copy()
    ids[0, 0] = (ids[0, 0] + 1) % 3000
    return ids, dists


def test_sound_run_is_correct(root):
    assert testing.run(root, "tiny_l2.batch")["correct"]


def test_half_batch_left_out(root, monkeypatch):
    _patch_session(monkeypatch, _half_left_out)
    r = testing.run(root, "tiny_l2.batch")
    assert r["correct"] is False
    assert not r["checks"]["dist_gap"]["ok"]


@pytest.mark.parametrize("cell", ["tiny_l2.batch", "tiny_l2.serve"])
def test_answer_altered_where_produced(root, monkeypatch, cell):
    _patch_session(monkeypatch, _one_answer_altered)
    r = testing.run(root, cell)
    assert r["correct"] is False
    assert not r["checks"]["dist_gap"]["ok"]


def test_answers_handed_to_the_wrong_requests(root, monkeypatch):
    from repro.gateway.queue import PendingRequest
    orig = PendingRequest._fulfill
    held = []

    def shifted(self, result):
        held.append(result)
        orig(self, held[-2] if len(held) > 1 else result)
    monkeypatch.setattr(PendingRequest, "_fulfill", shifted)
    r = testing.run(root, "tiny_l2.serve")
    assert r["correct"] is False
    assert not r["checks"]["dist_gap"]["ok"]


def test_request_never_answered(root, monkeypatch):
    from repro.gateway.queue import PendingRequest
    orig = PendingRequest._fulfill
    count = [0]

    def dropping(self, result):
        count[0] += 1
        if count[0] % 7:
            orig(self, result)
    monkeypatch.setattr(PendingRequest, "_fulfill", dropping)
    monkeypatch.setattr(traffic, "GRACE_S", 0.5)
    r = testing.run(root, "tiny_l2.serve")
    assert r["correct"] is False
    assert r["failed"] > 0 and not r["checks"]["unanswered"]["ok"]
