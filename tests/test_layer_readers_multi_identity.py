"""The per-layer readers of the multi-identity cell
(``benchmark/layer_metrics/mi_*.py``) over synthetic facts: packs of
the tenant scheduler as ``runtime.pack.dispatch`` / ``.retire`` spans,
with the segments and checkpoints a retire holds as its children."""

import importlib
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"


def _bench(modname):
    sys.path.insert(0, str(BENCH))
    try:
        return importlib.import_module(modname)
    finally:
        sys.path.remove(str(BENCH))


def _span(name, ts, dur, inside=True, **args):
    return {"name": name, "ts_us": ts, "dur_us": dur, "tid": 1,
            "inside": inside, "clipped_us": dur, "args": args}


def _facts(spans=()):
    layers = _bench("lib.layers")
    return layers.Facts(run=None, reduction=None, spans=list(spans),
                        counters={}, generator={}, peaks=None,
                        end_to_end={}, run_window_s=8.0)


def _pack(ts, lanes, tenants, bucket=None, inside=True):
    args = {"kind": "init_pack", "tenant": "*", "lanes": lanes,
            "tenants": tenants}
    if bucket is not None:
        args["bucket"] = bucket
    return _span("runtime.pack.dispatch", ts, 500, inside, **args)


def test_pack_fill_is_real_lanes_over_dispatched_width():
    fill = _bench("layer_metrics.mi_pack_fill")
    spans = [_pack(0, 8192, 1, 8192), _pack(3_000_000, 8192, 1, 8192),
             # a ragged pack: 5,000 real lanes at the 8,192 bucket
             _pack(6_000_000, 5000, 3, 8192),
             # cut by the window's edge: not counted
             _pack(9_000_000, 1, 1, 1, inside=False)]
    assert fill.read(_facts(spans)) == pytest.approx(
        100.0 * (8192 + 8192 + 5000) / (3 * 8192))
    assert fill.read(_facts(spans[:2])) == pytest.approx(100.0)


def test_pack_fill_has_nothing_to_read_without_a_bucket():
    fill = _bench("layer_metrics.mi_pack_fill")
    assert fill.read(_facts()) is None
    # a parent commit's pack spans: lanes and tenants, no bucket
    assert fill.read(_facts([_pack(0, 8192, 1)])) is None


def test_tenants_per_pack_is_the_median():
    per = _bench("layer_metrics.mi_tenants_per_pack")
    assert per.read(_facts()) is None
    spans = [_pack(0, 8192, 1, 8192), _pack(1, 8192, 1, 8192),
             _pack(2, 4096, 4, 4096), _pack(3, 10, 16, 16, inside=False)]
    assert per.read(_facts(spans)) == pytest.approx(1.0)
    spans.append(_pack(4, 4096, 6, 4096))
    assert per.read(_facts(spans)) == pytest.approx(2.5)


def test_retire_host_ms_sums_a_packs_segments_and_checkpoints():
    host = _bench("layer_metrics.mi_retire_host_ms")
    assert host.read(_facts()) is None
    spans = [
        # pack 1: one segment of 2 ms and a checkpoint of 6 ms = 8 ms
        _span("runtime.pack.retire", 0, 2_700_000, id=10),
        _span("runtime.segment", 2_690_000, 2_000, id=11, parent=10,
              tenant="id0"),
        _span("runtime.checkpoint", 2_692_000, 6_000, id=12, parent=10,
              tenant="id0", durable=8192),
        # pack 2: two tenants' segments, no checkpoint = 3 ms
        _span("runtime.pack.retire", 2_800_000, 2_700_000, id=20),
        _span("runtime.segment", 5_490_000, 1_000, id=21, parent=20),
        _span("runtime.segment", 5_491_000, 2_000, id=22, parent=20),
        # pack 3: a segment of 20 ms = 20 ms
        _span("runtime.pack.retire", 5_600_000, 2_700_000, id=30),
        _span("runtime.segment", 8_280_000, 20_000, id=31, parent=30),
        # a retire cut by the window's edge and its child: not counted
        _span("runtime.pack.retire", 8_400_000, 2_700_000, inside=False,
              id=40),
        _span("runtime.segment", 11_090_000, 500_000, id=41, parent=40),
        # a checkpoint under no pack (a finalize on another thread)
        _span("runtime.checkpoint", 0, 99_000, id=50),
    ]
    assert host.read(_facts(spans)) == pytest.approx(8.0)
    # a pack whose host work is nothing reads 0, not absent
    spans.append(_span("runtime.pack.retire", 9_000_000, 10, id=60))
    assert host.read(_facts(spans)) == pytest.approx(5.5)
