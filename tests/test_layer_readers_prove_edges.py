"""The three readers of a proof's edges (ISSUE 36): ``prove_edge_ms``,
``prove_host_cpu_ms`` and ``prove_abandoned_share``
(benchmark/layer_metrics/), over spans written out by hand in the form
``lib/tracewin.TraceWindow.spans()`` gives them; each value worked out
by hand, and nothing to report on a parent commit's spans, which have
neither the new names nor the new attributes."""

import importlib
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
LO, HI = 1_000_000, 3_000_000       # the window, us


def _bench(modname):
    sys.path.insert(0, str(BENCH))
    try:
        return importlib.import_module(modname)
    finally:
        sys.path.remove(str(BENCH))


_ids = iter(range(1, 10_000))


def _span(name, ts, dur, tid=1, **args):
    end = ts + dur
    return {"name": name, "ts_us": ts, "dur_us": dur, "tid": tid,
            "inside": ts >= LO and end <= HI,
            "clipped_us": max(min(end, HI) - max(ts, LO), 0),
            "args": {"id": args.pop("id", next(_ids)), **args}}


SPANS = [
    # proof 1: opened at 1,000,100; the proof ends at 1,103,000, so
    # 102,900 us from open to end, of which k2pow 10,000 and two
    # overlapping flights 1,020,000-1,080,000 (60,000) are busy:
    # edge 32,900 us
    _span("prove.open", 1_000_100, 2_000),
    _span("prove.proof", 1_003_000, 100_000),
    _span("prove.k2pow", 1_004_000, 10_000),
    _span("device.flight", 1_020_000, 30_000),
    _span("device.flight", 1_040_000, 40_000),
    # proof 2: its open is the last one ON ITS THREAD that ended before
    # it (the one on thread 2 ended later and is not its own): 52,000 us
    # from 1,200,000 to 1,252,000, k2pow 5,000 and a flight 30,000 busy
    # (the flight of thread 2 is not its own): edge 17,000 us
    _span("prove.open", 1_200_000, 1_000),
    _span("prove.open", 1_201_500, 100, tid=2),
    _span("prove.proof", 1_202_000, 50_000),
    _span("prove.k2pow", 1_203_000, 5_000),
    _span("device.flight", 1_210_000, 30_000),
    _span("device.flight", 1_210_000, 40_000, tid=2),
    # proof 3 is cut by the window's end: not counted
    _span("prove.open", 2_990_000, 1_000),
    _span("prove.proof", 2_995_000, 10_000),
    # two flights' dispatches inside the window: 10 + 900 + 600 = 1,510
    # and 20 + 1,100 + 370 = 1,490 us of CPU (the read wait's is not
    # the dispatch's own); the one cut by the window's start is not read
    _span("prove.dispatch", 1_020_000, 2_500, id=101),
    _span("prove.read_wait", 1_020_000, 500, parent=101, cpu_us=400),
    _span("prove.convert", 1_020_500, 20, parent=101, cpu_us=10),
    _span("prove.upload", 1_020_520, 1_200, parent=101, cpu_us=900),
    _span("prove.enqueue", 1_021_720, 700, parent=101, cpu_us=600),
    _span("prove.dispatch", 1_040_000, 2_000, id=102),
    _span("prove.convert", 1_040_000, 30, parent=102, cpu_us=20),
    _span("prove.upload", 1_040_030, 1_400, parent=102, cpu_us=1_100),
    _span("prove.enqueue", 1_041_430, 500, parent=102, cpu_us=370),
    _span("prove.dispatch", 999_000, 3_000, id=103),
    _span("prove.upload", 1_000_100, 1_000, parent=103, cpu_us=99_999),
    # passes: 2 of 64 flights abandoned, then 0 of 64; the pass cut by
    # the window's end is not read: 2 of 128 = 1.5625%
    _span("prove.window", 1_015_000, 80_000, flights=64, abandoned=2),
    _span("prove.window", 1_209_000, 40_000, flights=64, abandoned=0),
    _span("prove.window", 2_996_000, 9_000, flights=64, abandoned=2),
]

WORKED_OUT = {
    "prove_edge_ms": (32.9 + 17.0) / 2,     # the median of two proofs
    "prove_host_cpu_ms": (1_510 + 1_490) / 2 / 1e3,
    "prove_abandoned_share": 100.0 * 2 / 128,
}

# what the parent commit's prover records: no prove.open, no cpu_us on
# any span, no flights or abandoned on a pass
PARENT = [
    _span("prove.proof", 1_003_000, 100_000),
    _span("prove.k2pow", 1_004_000, 10_000),
    _span("prove.window", 1_015_000, 80_000, window=0, groups=4),
    _span("prove.dispatch", 1_020_000, 2_500, id=201),
    _span("prove.convert", 1_020_500, 20, parent=201),
    _span("prove.upload", 1_020_520, 1_200, parent=201),
    _span("prove.enqueue", 1_021_720, 700, parent=201),
    _span("device.flight", 1_020_000, 30_000),
]


def _facts(spans):
    layers = _bench("lib.layers")
    return layers.Facts(run=None, reduction=None, spans=spans, counters={},
                        generator={}, peaks=None, end_to_end={},
                        run_window_s=(HI - LO) / 1e6)


@pytest.mark.parametrize("name", sorted(WORKED_OUT))
def test_reader_gives_the_value_worked_out_by_hand(name):
    reader = _bench(f"layer_metrics.{name}")
    assert reader.read(_facts(SPANS)) == pytest.approx(WORKED_OUT[name],
                                                       rel=1e-12)


@pytest.mark.parametrize("name", sorted(WORKED_OUT))
@pytest.mark.parametrize("spans", [[], PARENT], ids=["none", "parent"])
def test_reader_is_silent_without_the_new_names(name, spans):
    reader = _bench(f"layer_metrics.{name}")
    assert reader.read(_facts(spans)) is None


def test_readers_are_declared_for_the_prove_cell_alone():
    import json

    with open(BENCH.parent / "BENCHMARK.json") as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in WORKED_OUT:
        meta = _bench(f"layer_metrics.{name}").META
        entry = per_layer[name]
        assert entry["workloads"] == ["prove-mainnet.scan"]
        assert {k: entry[k] for k in meta} == meta
        assert meta["layer"] == "pipeline post/prover"
        assert meta["moves"] == "p50_ms" and meta["better"] == "lower"
