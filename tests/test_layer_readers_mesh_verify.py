"""The per-layer readers of the four-chip node-farm cell
(``benchmark/layer_metrics/vm_*.py``), and what the accepted ``vb_``
device readers would read there, over synthetic facts: a verify batch
that runs as one 16,384-lane label program sharded over four chips,
4,096 lanes a chip, the last chip all padding. And the mesh driver's
slices of a tile, which the check batch swaps indices at the ends of."""

import importlib
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
MIB2 = 2 * 128 * 8192          # bytes of V traffic a lane at N=8192


def _bench(modname):
    sys.path.insert(0, str(BENCH))
    try:
        return importlib.import_module(modname)
    finally:
        sys.path.remove(str(BENCH))


def _span(name, ts, dur, inside=True, **args):
    return {"name": name, "ts_us": ts, "dur_us": dur, "tid": 1,
            "inside": inside, "clipped_us": dur, "args": args}


def _facts(spans=(), chips=None, window_s=16.0):
    layers, xplane = _bench("lib.layers"), _bench("lib.xplane")
    red = None
    if chips is not None:
        red = xplane.Reduction(
            window_s=5.0,
            chips=[{"chip": c, "busy_s": sum(sum(d) for d in p.values()),
                    "programs": p, "ops": {}} for c, p in enumerate(chips)],
            host_spans=[], gaps_by_span={}, window_ns=(0.0, 5e9))
    return layers.Facts(run=None, reduction=red, spans=list(spans),
                        counters={}, generator={},
                        peaks={"hbm_bytes_per_s": 819e9}, end_to_end={},
                        run_window_s=window_s)


def _verify(ts, real, sent=(4096,) * 4, inside=True):
    return _span("post.verify", ts, 1600_000, inside,
                 lanes_valid=sum(real), lanes=sum(sent), tiles=1,
                 chips=len(sent), chip_lanes_valid=list(real),
                 chip_lanes=list(sent))


def test_chip_fill_min_is_the_median_of_each_calls_emptiest_chip():
    fill = _bench("layer_metrics.vm_chip_fill_min")
    spans = [
        _verify(0, [4096, 4096, 700, 0]),            # chip 3 all padding
        _verify(2_000_000, [4096, 4096, 1000, 0]),
        # all 256 proofs on the device: 9,472 lanes, the last chip empty
        _verify(4_000_000, [4096, 4096, 1280, 0]),
        # one call whose batch fills three chips and part of the fourth
        _verify(6_000_000, [4096, 4096, 4096, 2048]),
        # cut by the window's edge: not counted
        _verify(9_000_000, [4096, 4096, 4096, 4096], inside=False),
    ]
    assert fill.read(_facts(spans)) == pytest.approx(0.0)
    spans[0] = _verify(0, [4096, 4096, 4096, 1024])
    spans[1] = _verify(2_000_000, [4096, 4096, 4096, 3072])
    # medians of 0.25, 0.75, 0, 0.5 -> (0.25 + 0.5) / 2
    assert fill.read(_facts(spans)) == pytest.approx(37.5)
    # one chip: its fill is the call's
    one = [_verify(0, [8954], sent=[9216])]
    assert fill.read(_facts(one)) == pytest.approx(100.0 * 8954 / 9216)


def test_chip_fill_min_has_nothing_to_read_without_per_chip_lanes():
    fill = _bench("layer_metrics.vm_chip_fill_min")
    assert fill.read(_facts()) is None
    # a parent commit's spans: post.verify without the per-chip counts
    old = [_span("post.verify", 0, 10, lanes_valid=9000, lanes=16384)]
    assert fill.read(_facts(old)) is None
    # a call whose proofs were all rejected on the host sent no lane
    assert fill.read(_facts([_verify(0, [], sent=[])])) is None


def _chips(durs_by_chip):
    return [{"jit__labels_fused": list(d), "jit_proving_hash_jit": [1e-4]}
            for d in durs_by_chip]


def test_shard_busy_skew_is_the_spread_of_per_chip_busy():
    skew = _bench("layer_metrics.vm_shard_busy_skew")
    # busy is the union of every program on the chip, cut programs too
    chips = _chips([[1.50, 1.50], [1.50, 1.50], [1.48, 1.49],
                    [1.44, 1.46]])
    busy = [3.0001, 3.0001, 2.9701, 2.9001]
    assert skew.read(_facts(chips=chips)) == \
        pytest.approx(100.0 * (max(busy) - min(busy)) / max(busy))
    # a program placed on one chip alone makes that chip the busiest
    chips[3]["jit_verify_many_pow"] = [0.5]
    busy[3] += 0.5
    assert skew.read(_facts(chips=chips)) == \
        pytest.approx(100.0 * (max(busy) - min(busy)) / max(busy))
    assert _bench("layer_metrics.vm_shard_busy_skew").META["moves"] == \
        "proofs_per_s"


def test_shard_busy_skew_has_nothing_to_read_off_a_mesh():
    skew = _bench("layer_metrics.vm_shard_busy_skew")
    assert skew.read(_facts()) is None
    assert skew.read(_facts(chips=_chips([[1.5, 1.5]]))) is None


def test_accepted_batch_readers_read_the_sharded_program_per_chip():
    """One 16,384-lane program a batch on four chips: the trace holds one
    execution a chip for each, so ``vb_tile_prog_ms`` is a chip's time of
    the sharded program and ``vb_romix_roofline`` a chip's 4,096 lanes
    over it, as ``vb_romix_roofline`` divides by the chips."""
    spans = [_span("romix.dispatch", 10 + k * 1_600_000, 5, n=8192,
                   batch=16384, valid=16384) for k in range(3)]
    spans += [_span("device.flight", 5 + k * 1_600_000, 1_560_000,
                    program="labels_proving", lanes=16384, tiles=1,
                    chips=4) for k in range(3)]
    facts = _facts(spans, chips=_chips([[1.50, 1.52]] * 4))
    tiles = _bench("layer_metrics.vb_tile_prog_ms")
    assert tiles.by_width(facts) == {16384: [1.50, 1.52] * 4}
    assert tiles.read(facts) == pytest.approx(1510.0)
    roof = _bench("layer_metrics.vb_romix_roofline").read(facts)
    assert roof == pytest.approx(
        100.0 * (MIB2 * 4096 / 819e9) / ((1.50 + 1.52) / 2))


def test_each_chips_slice_of_each_tile_in_lane_order():
    mesh = _bench("drivers.node_farm_mesh")
    four = lambda width: 4 if width % 4 == 0 else 1     # noqa: E731
    assert mesh.chip_slices([(0, 16384)], four) == [
        (0, 4096), (4096, 4096), (8192, 4096), (12288, 4096)]
    assert mesh.chip_slices([(0, 32), (32, 32), (64, 1)], four) == [
        (0, 8), (8, 8), (16, 8), (24, 8), (32, 8), (40, 8), (48, 8),
        (56, 8), (64, 1)]


def test_check_batch_swaps_at_both_ends_of_each_chips_real_lanes():
    """K3 = K2 = 3, 16 proofs = 48 real lanes in one 64-lane tile on four
    chips: slices of 16 lanes, the last all padding. The generator swaps
    the two lowest and two highest swap lanes of each slice that holds
    real lanes, and none in the padding."""
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    try:
        from test_layer_readers_batch import _made_up_pool, _made_up_run
    finally:
        sys.path.pop(0)
    mesh = _bench("drivers.node_farm_mesh")
    slices = mesh.chip_slices([(0, 64)], lambda width: 4)
    gen = _bench("generators.atx_backlog").generate(
        _made_up_run(), _made_up_pool(64, 3),
        check=(16, [w for _at, w in slices]))
    by_slice = sorted(gen["check"]["tile"].values())
    assert by_slice == [0] * 4 + [1] * 4 + [2] * 4
