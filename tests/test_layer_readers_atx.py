"""The nine per-layer readers that read verifyd's span tree
(benchmark/layer_metrics/), each over the small hand-written span list
``benchmark/testdata/atx_spans.json``: the value worked out by hand, and
nothing to report on an empty list."""

import importlib
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"

# worked out by hand from the file (its "about" says what the spans are)
EXPECTED = {
    # generator median (104+126)/2 = 115, verifyd.http median (100+120)/2
    "outside_server_ms": 5.0,
    # 100-98 = 2 and 120-117 = 3; the http span cut by the window's end
    # is not wholly inside and does not count
    "http_self_ms": 2.5,
    # 0.2 and 0.6 of kind verifyd; the k2pow quantum's 50 is not the queue
    "sched_wait_ms": 0.4,
    # A: 86 - (70 + 3) = 13; B: 95 - union(80 and 10 overlapping by 5)
    # = 95 - 85 = 10
    "post_verify_host_ms": 11.5,
    "post_relayout_ms": 3.0,              # 2 and 4
    # A: sig ends 5 ms after post; B: membership and pow end before: 0
    "nonpost_tail_ms": 2.5,
    "lane_fill_counted": 100.0 * (37 + 20) / (64 + 32),
    # flights inside the window: 20 (cut by its start) + 73 + 85 = 178 ms
    # of 2,000 ms
    "vd_starved_share": 100.0 * (1 - 0.178 / 2.0),
    "post_merge_missed": 100.0 * 2 / 3,   # inflight 0, 1, 2
}


def _facts(doc):
    sys.path.insert(0, str(BENCH))
    try:
        from lib import layers
    finally:
        sys.path.remove(str(BENCH))
    return layers.Facts(run=None, reduction=None, spans=doc["spans"],
                        counters={}, generator=doc["generator"],
                        peaks=None, end_to_end={},
                        run_window_s=doc["run_window_s"])


def _reader(name):
    sys.path.insert(0, str(BENCH))
    try:
        return importlib.import_module(f"layer_metrics.{name}")
    finally:
        sys.path.remove(str(BENCH))


@pytest.fixture(scope="module")
def doc():
    with open(BENCH / "testdata" / "atx_spans.json") as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_the_value_worked_out_by_hand(doc, name):
    assert _reader(name).read(_facts(doc)) == pytest.approx(
        EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_has_nothing_to_report_without_its_spans(name):
    empty = {"spans": [], "generator": {}, "run_window_s": 2.0}
    assert _reader(name).read(_facts(empty)) is None
    # a parent commit's spans: the names exist, the new attributes do not
    old = {"spans": [
        {"name": n, "ts_us": 1000 + i, "dur_us": 10, "tid": 1,
         "inside": True, "clipped_us": 10, "args": {"id": i + 1,
                                                     "kind": "post"}}
        for i, n in enumerate(("verifyd.request", "runtime.quantum",
                               "farm.request", "farm.batch"))],
        "generator": {"latency_ms": [1.0]}, "run_window_s": 2.0}
    assert _reader(name).read(_facts(old)) is None
