"""The per-layer readers of the node-farm batch cell: the six that are
its own (benchmark/layer_metrics/vb_*.py) and the three accepted ones
the cell joins (vd_device_idle_share, vd_starved_share,
post_merge_missed), over the small hand-written record
``benchmark/testdata/batch_spans.json`` (program spans, and a device
trace's reduction), each against the value worked out by hand; nothing
to report on an empty record or on the spans of a program that does not
tile. And the driver's own arithmetic (benchmark/drivers/node_farm.py):
the rate between steps of completions, the tile widths, and the
generator's requests and check batch."""

import importlib
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"

MIB2 = 2 * 128 * 8192          # bytes of V traffic a lane at N=8192
# worked out by hand from the file (its "about" says what the spans are)
EXPECTED = {
    # post farm.batch spans wholly inside: A and B, 256 proofs each (the
    # k2pow batch is another kind, batch C is cut by the window's end)
    "vb_batch_proofs": 256.0,
    # post.verify A and B: (8,954 + 9,028) valid of 2 x 9,216 lanes
    "vb_lane_fill": 100.0 * (8954 + 9028) / (2 * 9216),
    "vb_tiles_per_batch": 2.0,
    # A: 3,398 - 3,340 = 58 ms; B: 3,388 - 3,346 = 42 ms
    "vb_verify_host_ms": 50.0,
    # flights inside the 16 s: 600 (cut by the start) + 3,340 + 100 (the
    # k2pow batch, 30 ms clear of B's) + 3,346 + 60 (cut by the end) ms
    "vd_starved_share": 100.0 * (1 - 7.446 / 16.0),
    # the device trace holds 7.0 s, busy 6.86
    "vd_device_idle_share": 100.0 * (1 - 6.86 / 7.0),
    # both POST batches wholly inside were taken with nothing in flight
    "post_merge_missed": 0.0,
    # a flight is 3.34 s for 9,216 lanes = 0.36 ms a lane: 2.83 and 2.84 s
    # are 8,192-lane executions (2.97 s expected), 0.41 s a 1,024-lane one
    "vb_tile_prog_ms": 2835.0,
    "vb_romix_roofline": 100.0 * (MIB2 * (2 * 8192 + 1024) / 819e9)
    / (2.83 + 2.84 + 0.41),
}


def _bench(modname):
    sys.path.insert(0, str(BENCH))
    try:
        return importlib.import_module(modname)
    finally:
        sys.path.remove(str(BENCH))


def _facts(doc):
    layers, xplane = _bench("lib.layers"), _bench("lib.xplane")
    red = None
    if doc.get("reduction"):
        r = doc["reduction"]
        red = xplane.Reduction(
            window_s=r["window_s"],
            chips=[{"chip": 0, "busy_s": r["busy_s"],
                    "programs": r["programs"], "ops": {}}],
            host_spans=[], gaps_by_span={},
            window_ns=(0.0, r["window_s"] * 1e9))
    return layers.Facts(run=None, reduction=red, spans=doc["spans"],
                        counters=doc.get("counters", {}),
                        generator=doc["generator"],
                        peaks=doc.get("peaks"), end_to_end={},
                        run_window_s=doc["run_window_s"])


@pytest.fixture(scope="module")
def doc():
    with open(BENCH / "testdata" / "batch_spans.json") as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_the_value_worked_out_by_hand(doc, name):
    assert _bench(f"layer_metrics.{name}").read(_facts(doc)) == \
        pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_has_nothing_to_report_without_its_spans(doc, name):
    empty = {"spans": [], "generator": {}, "run_window_s": 16.0}
    assert _bench(f"layer_metrics.{name}").read(_facts(empty)) is None
    # a parent commit's spans: the names exist, the attributes of this
    # PR (tiles) and the counts do not; the trace is there
    old = {"spans": [
        {"name": n, "ts_us": 1000 + i, "dur_us": 10, "tid": 1,
         "inside": True, "clipped_us": 10, "args": {"id": i + 1}}
        for i, n in enumerate(("farm.batch", "post.verify",
                               "romix.dispatch"))],
        "generator": {}, "run_window_s": 16.0,
        "reduction": doc["reduction"], "peaks": doc["peaks"]}
    # (the others read spans and a trace the parent has too)
    if name == "vb_tiles_per_batch":
        assert _bench(f"layer_metrics.{name}").read(_facts(old)) is None


def test_label_programs_are_told_apart_by_width(doc):
    """An execution goes to the dispatched width nearest to its share of
    a flight's time; with no full tile among the executions the trace
    kept there is no tile time to report, and the roofline counts what
    is there at its own width."""
    tiles = _bench("layer_metrics.vb_tile_prog_ms")
    assert tiles.by_width(_facts(doc)) == {8192: [2.83, 2.84], 1024: [0.41]}
    short = json.loads(json.dumps(doc))
    short["reduction"]["programs"]["jit__labels_fused"] = [0.41]
    assert tiles.by_width(_facts(short)) == {1024: [0.41]}
    assert tiles.read(_facts(short)) is None
    roof = _bench("layer_metrics.vb_romix_roofline").read(_facts(short))
    assert roof == pytest.approx(100.0 * (MIB2 * 1024 / 819e9) / 0.41)


# --- the driver's arithmetic ------------------------------------------------

@pytest.fixture(scope="module")
def node_farm():
    return _bench("drivers.node_farm")


def test_rate_between_steps_of_completions(node_farm):
    # four requests of 64 ATXs return within milliseconds, a batch every
    # 3.4 s: 256 ATXs a step, whatever the first step's stragglers
    done = [(10.0 + 3.4 * s + 0.002 * r, 64) for s in range(5)
            for r in range(4)]
    rate, steps = node_farm.step_rate(done)
    assert steps == 5 and rate == pytest.approx(256 / 3.4)
    # first-to-last over single completions would read 7% more
    naive = sum(n for _t, n in sorted(done)[1:]) / (done[-1][0] - done[0][0])
    assert naive / rate > 1.05
    # evenly spaced completions: a step each, first to last
    rate, steps = node_farm.step_rate([(t, 8) for t in (1.0, 2.0, 3.0)])
    assert steps == 3 and rate == pytest.approx(8.0)
    assert node_farm.step_rate([(1.0, 64)]) == (None, 1)
    assert node_farm.step_rate([]) == (None, 0)


@pytest.mark.parametrize("lanes,ceiling,want", [
    (9472, 8192, [8192, 2048]), (8880, 8192, [8192, 1024]),
    (8192, 8192, [8192]), (8191, 8192, [8192]), (2368, 8192, [4096]),
    (16384 + 3, 8192, [8192, 8192, 4]), (148, 64, [64, 64, 32])])
def test_tile_widths_as_the_verifier_cuts_them(node_farm, lanes, ceiling,
                                               want, monkeypatch):
    assert node_farm.lane_tiles(lanes, ceiling) == want
    # ... and the verifier's own rule says the same
    from spacemesh_tpu.ops import scrypt
    from spacemesh_tpu.post import verifier

    monkeypatch.setattr(scrypt, "lane_ceiling",
                        lambda n, devices=None: ceiling)
    assert [w for _at, w in verifier._lane_tiles(lanes, 8192)] == want


def _made_up_pool(proofs: int, k2: int):
    """A pool the generator can build requests over (it never checks a
    proof)."""
    import hashlib

    from spacemesh_tpu.core.signing import EdSigner

    def h(tag):
        return hashlib.sha256(tag.encode()).digest()

    key = h("key")
    return {"total_labels": 256, "identities": [{
        "key": key.hex(), "node_id": EdSigner(seed=key).public_key.hex(),
        "commitment": h("commitment").hex()}],
        "poet": {"root": h("root").hex(), "leaf_count": 4},
        "proofs": [{"identity": 0, "challenge": h(f"ch{k}").hex(),
                    "nonce": k, "indices": list(range(k, k + k2)),
                    "pow_nonce": 0, "swap_pos": k % k2,
                    "swap_index": 200 + k, "swap_qualifies": k == 5,
                    "member": h(f"m{k}").hex(), "leaf_index": k % 4,
                    "leaf_nodes": [h("n").hex()] * 2}
                   for k in range(proofs)]}


def _made_up_run(**traffic):
    import types

    return types.SimpleNamespace(
        seed=7, window_s=2.0,
        config={"scrypt_n": 4, "k2": 3, "pow_difficulty": "ff" * 32},
        traffic={"loop": "closed", "workers": 2, "atx_per_request": 10,
                 "closed_requests_per_worker_per_s": 0.5, "lane": "sync",
                 "k3": 3, "invalid_share": 0.2, "warm_s": 0.0,
                 "invalid_modes": ["index_out_of_range", "forged_signature"],
                 **traffic})


def test_backlog_generator_cycles_the_traffics_modes_and_nothing_else():
    """Over a made-up pool: one ATX in five invalid, the traffic file's
    modes in turn and no other; four farm items an ATX; pool proofs
    taken in a cycle from a start the seed draws; no check batch unless
    asked."""
    from spacemesh_tpu.verify import farm

    pool = _made_up_pool(12, 3)
    gen = _bench("generators.atx_backlog").generate(_made_up_run(), pool)
    assert gen["workers"] == 2 and gen["k3"] == 3 and gen["lane"] == "sync"
    assert "check" not in gen
    reqs = gen["requests"]
    assert len(reqs) == 2 * (1 + 2)
    cycle = []
    for r in reqs:
        assert len(r["items"]) == len(r["want"]) == 4 * r["n_atx"] == 40
        kinds = [type(x) for x in r["items"][:4]]
        assert kinds == [farm.SigRequest, farm.MembershipRequest,
                         farm.PostRequest, farm.PowRequest]
        modes = [f["mode"] for f in r["atx"]]
        # ATX j is invalid when j mod 5 == 2: positions 2 and 7
        assert [k for k, m in enumerate(modes) if m] == [2, 7]
        cycle += [modes[2], modes[7]]
        assert r["want"][:4] == [True] * 4
    assert cycle == ["index_out_of_range", "forged_signature"] * 6
    order = [f["pool"] for r in reqs for f in r["atx"]]
    assert order == [(order[0] + j) % 12 for j in range(60)]


def test_check_batch_swaps_the_proofs_at_both_ends_of_each_tile():
    """K3 = K2 = 3, 16 proofs = 48 lanes cut as 32 + 16: proof q holds
    lanes 3q.., and its swapped index is lane 3q + swap_pos. The two
    lowest and two highest swap lanes of each tile are swapped, every
    item is a POST request that reaches the device, the cycle goes on
    where the requests ended, and a swapped index whose label happens to
    qualify leaves its proof valid."""
    from spacemesh_tpu.verify import farm

    pool = _made_up_pool(64, 3)
    gen = _bench("generators.atx_backlog").generate(
        _made_up_run(), pool, check=(16, [32, 16]))
    check = gen["check"]
    assert len(check["items"]) == len(check["want"]) == 16
    assert all(type(x) is farm.PostRequest for x in check["items"])
    last = gen["requests"][-1]["atx"][-1]["pool"]
    first = (last + 1) % 64
    lanes = {q: 3 * q + (first + q) % 64 % 3 for q in range(16)}
    want_tile = {}
    for t, (lo, hi) in enumerate([(0, 32), (32, 48)]):
        inside = sorted((x, q) for q, x in lanes.items() if lo <= x < hi)
        for _x, q in inside[:2] + inside[-2:]:
            want_tile[q] = t
    assert check["tile"] == want_tile
    assert sorted(want_tile.values()) == [0] * 4 + [1] * 4
    for q, item in enumerate(check["items"]):
        p = pool["proofs"][(first + q) % 64]
        idx = list(p["indices"])
        if q in want_tile:
            idx[p["swap_pos"]] = p["swap_index"]
        assert list(item.item.proof.indices) == idx
        assert check["want"][q] == (q not in want_tile
                                    or p["swap_qualifies"])
