"""The scan's work count against a brute count on a tiny index."""
import jax
import numpy as np
import pytest

from bench import roofline
from bench.corpus import make_corpus
from bench.testing import TINY_CONFIGS


def _tables(cfg_name, seed=5):
    from repro.core import IndexConfig, build_index
    cfg = TINY_CONFIGS[cfg_name]
    x, q = make_corpus(cfg, seed)
    index = build_index(jax.random.PRNGKey(seed), x,
                        IndexConfig(**cfg["index"]))
    a = index.arrays
    t = {"centroids": np.asarray(index.centroids),
         "owned": np.asarray(a.owned), "refs": np.asarray(a.refs),
         "refs_other": np.asarray(a.refs_other), "misc": np.asarray(a.misc),
         "block_ids": np.asarray(a.block_ids),
         "block_other": np.asarray(a.block_other)}
    return cfg, t, np.asarray(q)


def brute_work(q, t, *, nprobe, max_scan, metric, m, nbits, bigk):
    """Query by query, list by list, in the search's scan order."""
    c = t["centroids"].astype(np.float64)
    ops = 0
    batch_blocks = set()
    for qv in q.astype(np.float64):
        d = (((c - qv) ** 2).sum(1) if metric == "l2" else -(c @ qv))
        sel = list(np.argsort(d, kind="stable")[:nprobe])
        rank = {int(lst): r for r, lst in enumerate(sel)}

        def earlier(other, r):
            return other >= 0 and rank.get(int(other), 1 << 30) < r
        order = []
        for r, lst in enumerate(sel):          # owned, by probe rank
            for blk in t["owned"][lst]:
                if blk >= 0 and not earlier(t["block_other"][blk, 0], r):
                    order.append((int(blk), r))
        for r, lst in enumerate(sel):          # referenced
            for blk, home in zip(t["refs"][lst], t["refs_other"][lst]):
                if blk >= 0 and not earlier(home, r):
                    order.append((int(blk), r))
        for r, lst in enumerate(sel):          # miscellaneous
            for blk in t["misc"][lst]:
                if blk >= 0:
                    order.append((int(blk), r))
        for blk, r in order[:max_scan]:
            batch_blocks.add(blk)
            for slot in range(t["block_ids"].shape[1]):
                if t["block_ids"][blk, slot] < 0:
                    continue
                if earlier(t["block_other"][blk, slot], r):
                    continue                   # twin scored at an earlier rank
                ops += m
    stored = sum(int((t["block_ids"][b] >= 0).sum()) for b in batch_blocks)
    nbytes = (stored * m * nbits / 8 + len(q) * m * 2 ** nbits * 4
              + len(q) * bigk * 8)
    return {"ops": float(ops), "bytes": float(nbytes)}


@pytest.mark.parametrize("cfg_name,max_scan", [
    ("tiny_l2", 24), ("tiny_l2", 5), ("tiny_ip", 24), ("tiny_ip", 3)])
def test_scan_work_matches_brute_count(cfg_name, max_scan):
    cfg, t, q = _tables(cfg_name)
    kw = dict(nprobe=cfg["search"]["nprobe"], max_scan=max_scan,
              metric=cfg["metric"], m=cfg["index"]["m_pq"],
              nbits=cfg["index"]["nbits"], bigk=40)
    got = roofline.scan_work(q[:24], t, **kw)
    want = brute_work(q[:24], t, **kw)
    assert got == want
    assert got["ops"] > 0


def test_work_ignores_padding_and_stored_width():
    cfg, t, q = _tables("tiny_l2")
    kw = dict(nprobe=4, max_scan=24, metric="l2", m=8, nbits=4, bigk=40)
    base = roofline.scan_work(q[:16], t, **kw)
    wider = dict(t, owned=np.pad(t["owned"], ((0, 0), (0, 9)),
                                 constant_values=-1),
                 block_ids=np.pad(t["block_ids"], ((0, 50), (0, 0)),
                                  constant_values=-1),
                 block_other=np.pad(t["block_other"], ((0, 50), (0, 0)),
                                    constant_values=-1))
    assert roofline.scan_work(q[:16], wider, **kw) == base


def test_min_time_names_its_bound():
    peak = {"ops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert roofline.min_time_s({"ops": 1e12, "bytes": 1e6}, peak) == (1.0, "ops")
    assert roofline.min_time_s({"ops": 1e3, "bytes": 2e9}, peak) == (2.0, "bytes")
