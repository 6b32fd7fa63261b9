"""``scan.kernel_us_per_step`` on a synthetic trace context: the kernel's
summed time over (queries answered x ``search.max_scan``), and nothing
where the configuration states no budget or the trace has no kernel."""
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]


def _ctx(max_scan=600, kernel_s=2.4, events=8, answered=1024):
    search = {"k": 10} if max_scan is None else {"max_scan": max_scan}
    return {"config": {"search": search},
            "trace": {"kernel_s": kernel_s, "kernel_events": events},
            "window": SimpleNamespace(qidx=np.arange(answered))}


@pytest.fixture(scope="module")
def read():
    return harness.load_reader(ROOT, "scan.kernel_us_per_step")


def test_kernel_time_per_grid_step(read):
    assert read(_ctx()) == pytest.approx(2.4 / (1024 * 600) * 1e6)
    # the per-query reading of the same trace is max_scan steps of this
    per_query = harness.load_reader(ROOT, "scan.kernel_us_per_query")
    assert per_query(_ctx()) == pytest.approx(600 * read(_ctx()))


@pytest.mark.parametrize("kw", [{"max_scan": None}, {"events": 0},
                                {"answered": 0}])
def test_nothing_to_read_is_none(read, kw):
    assert read(_ctx(**kw)) is None
