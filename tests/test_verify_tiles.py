"""post/verifier.verify_many above the lane ceiling (ISSUE 31): a group
with more lanes than ops/scrypt.lane_ceiling runs as lane tiles, full
ones at the ceiling and the remainder in its shape bucket, in ONE
upload, ONE flight and ONE blocking fetch. Verdicts are held to the
plain reference the benchmark uses (``benchmark/lib/reference.py``:
hashlib.scrypt, a numpy Salsa20/8, the K3 rule restated) and to the
same call with the ceiling out of reach. The ceiling is forced low by
patching the one function that computes it; nothing else selects
tiling."""

import hashlib
import sys
from pathlib import Path

import pytest

from spacemesh_tpu.ops import scrypt
from spacemesh_tpu.post import verifier
from spacemesh_tpu.post.prover import Proof, ProofParams
from spacemesh_tpu.utils import metrics, tracing

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))
try:
    from lib import reference
finally:
    sys.path.remove(str(BENCH))

TOTAL = 64
DIFF = bytes([255] * 32)
# k1 / TOTAL = 1/2: half of all lanes qualify, so verdicts are mixed
PARAMS = ProofParams(k1=32, k2=1, k3=1, pow_difficulty=DIFF)
SEED = b"lane-tiles".ljust(32, b"\0")
CEILING = 8


def _item(i: int, n: int, index: int | None = None) -> verifier.VerifyItem:
    tag = b"%d/%d" % (n, i)
    return verifier.VerifyItem(
        Proof(nonce=i % 5, indices=[(7 * i) % TOTAL if index is None
                                    else index], pow_nonce=0, k2=1),
        hashlib.sha256(b"ch" + tag).digest(),
        hashlib.sha256(b"node" + tag).digest(),
        hashlib.sha256(b"commit" + tag).digest(), n, TOTAL)


def _want(it: verifier.VerifyItem, params=PARAMS) -> bool:
    return reference.verify_post(
        indices=list(it.proof.indices), nonce=it.proof.nonce,
        pow_nonce=it.proof.pow_nonce, challenge=it.challenge,
        node_id=it.node_id, commitment=it.commitment,
        scrypt_n=it.scrypt_n, total_labels=it.total_labels,
        k1=params.k1, k2=params.k2, k3=params.k3,
        pow_difficulty=params.pow_difficulty, seed=SEED)


def _qualifies(it: verifier.VerifyItem, index: int) -> bool:
    return reference.index_qualifies(
        commitment=it.commitment, challenge=it.challenge,
        nonce=it.proof.nonce, index=index, scrypt_n=it.scrypt_n,
        k1=PARAMS.k1, total_labels=TOTAL)


def _with_index(i: int, n: int, qualifying: bool) -> verifier.VerifyItem:
    """Item ``i`` with an index chosen so that it does / does not pass."""
    it = _item(i, n)
    j = next(j for j in range(TOTAL) if _qualifies(it, j) == qualifying)
    return _item(i, n, index=j)


@pytest.fixture(autouse=True)
def _tracer_off():
    tracing.stop()
    yield
    tracing.stop()


@pytest.fixture
def low_ceiling(monkeypatch):
    monkeypatch.setattr(scrypt, "lane_ceiling",
                        lambda n, devices=None: CEILING)


def _traced(items, params=PARAMS):
    tracing.start(capacity=1 << 12, jax_bridge=False)
    got = verifier.verify_many(items, params, SEED)
    tracing.stop()
    evs = [e for e in tracing.export()["traceEvents"] if e["ph"] == "X"]
    return got, evs


def _named(evs, name):
    return [e for e in evs if e["name"] == name]


def _programs() -> dict:
    return {dict(k)["lanes"]: v for k, v in
            metrics.post_verify_label_programs.sample().items()}


@pytest.mark.parametrize("b", [CEILING - 1, CEILING, CEILING + 1,
                               2 * CEILING, 2 * CEILING + 3])
def test_tiled_verdicts_equal_the_reference_and_the_untiled_call(
        b, monkeypatch):
    items = [_item(i, 4) for i in range(b)]
    want = [_want(it) for it in items]
    assert True in want and False in want
    monkeypatch.setattr(scrypt, "lane_ceiling",
                        lambda n, devices=None: 1 << 20)
    untiled, evs = _traced(items)
    assert len(_named(evs, "romix.dispatch")) == 1
    monkeypatch.setattr(scrypt, "lane_ceiling",
                        lambda n, devices=None: CEILING)
    before = _programs()
    tiled, evs = _traced(items)
    assert tiled == want == untiled
    # full tiles at the ceiling, the remainder in its bucket
    rest = b % CEILING
    widths = [CEILING] * (b // CEILING) \
        + ([scrypt.shape_bucket(rest)] if rest else [])
    progs = sorted(_named(evs, "romix.dispatch"), key=lambda e: e["ts"])
    assert [e["args"]["batch"] for e in progs] == widths
    assert all(e["args"]["batch"] <= CEILING for e in progs)
    (call,) = _named(evs, "post.verify")
    a = call["args"]
    assert a["tiles"] == len(widths) and a["lanes"] == sum(widths)
    assert a["lanes_valid"] == b and a["syncs"] == 1
    assert a["h2d_bytes"] == 19 * 4 * a["lanes"]
    assert a["d2h_bytes"] == 4 * a["lanes"]
    # above the ceiling too: one upload, one flight, one blocking fetch
    assert len(_named(evs, "romix.upload")) == 1
    (flight,) = _named(evs, "device.flight")
    assert flight["args"]["program"] == "labels_proving"
    assert flight["args"]["tiles"] == len(widths)
    assert flight["args"]["lanes"] == sum(widths)
    assert "romix.pad" not in {e["name"] for e in evs}
    after = _programs()
    for w in set(widths):
        assert after[w] - before.get(w, 0) == widths.count(w)


@pytest.mark.parametrize("lane", [CEILING - 1, CEILING],
                         ids=["last-of-first-tile", "first-of-second"])
def test_one_bad_index_at_a_tile_edge_flips_exactly_its_owner(
        lane, low_ceiling):
    items = [_with_index(i, 4, True) for i in range(2 * CEILING + 3)]
    assert verifier.verify_many(items, PARAMS, SEED) == [True] * len(items)
    items[lane] = _with_index(lane, 4, False)
    want = [i != lane for i in range(len(items))]
    assert [_want(it) for it in items] == want
    assert verifier.verify_many(items, PARAMS, SEED) == want


def test_a_proof_split_by_a_tile_edge_is_one_verdict(low_ceiling):
    """K3 = 3 indices a proof: proofs straddle the tile edges (8 is not
    a multiple of 3), and a failing index on either side of an edge
    fails the one proof that owns it."""
    params = ProofParams(k1=32, k2=3, k3=3, pow_difficulty=DIFF)
    items = []
    for i in range(7):                      # 21 lanes: 8 + 8 + 5 -> 8
        it = _item(i, 4)
        good = [j for j in range(TOTAL) if _qualifies(it, j)][:3]
        items.append(verifier.VerifyItem(
            Proof(it.proof.nonce, good, 0, 3), it.challenge, it.node_id,
            it.commitment, 4, TOTAL))
    assert verifier.verify_many(items, params, SEED) == [True] * 7
    # proof 2 owns lanes 6, 7 | 8: spoil the one behind the edge
    it = items[2]
    bad = next(j for j in range(TOTAL) if not _qualifies(it, j))
    items[2] = verifier.VerifyItem(
        Proof(it.proof.nonce, it.proof.indices[:2] + [bad], 0, 3),
        it.challenge, it.node_id, it.commitment, 4, TOTAL)
    want = [i != 2 for i in range(7)]
    assert [_want(it, params) for it in items] == want
    got, evs = _traced(items, params)
    assert got == want
    (call,) = _named(evs, "post.verify")
    assert call["args"]["tiles"] == 3 and call["args"]["lanes"] == 24
    assert call["args"]["lanes_valid"] == 21


def test_two_scrypt_n_groups_tile_each_on_their_own(low_ceiling):
    """2 x ceiling + 3 lanes at N=4 interleaved with ceiling + 1 at N=2:
    two flights, each cut at its own ceiling, verdicts scattered back to
    the callers' order."""
    items = [_item(i, 4) for i in range(2 * CEILING + 3)]
    for k in range(CEILING + 1):
        items.insert(2 * k, _item(k, 2))
    want = [_want(it) for it in items]
    got, evs = _traced(items)
    assert got == want
    by_n: dict = {}
    for e in sorted(_named(evs, "romix.dispatch"), key=lambda e: e["ts"]):
        by_n.setdefault(e["args"]["n"], []).append(e["args"]["batch"])
    assert by_n == {2: [8, 1], 4: [8, 8, 4]}
    flights = _named(evs, "device.flight")
    assert [f["args"]["tiles"] for f in flights] == [2, 3]
    assert len(_named(evs, "romix.upload")) == 2
    (call,) = _named(evs, "post.verify")
    a = call["args"]
    assert a["tiles"] == 5 and a["syncs"] == 2 and a["lanes"] == 29
    assert a["lanes_valid"] == len(items)


@pytest.mark.parametrize("b", [2 * 4 * CEILING + 3, 4 * CEILING + 1])
def test_on_the_virtual_mesh_the_ceiling_is_per_chip(b, low_ceiling,
                                                     monkeypatch):
    """Four virtual devices: a full tile is ceiling lanes on EACH chip,
    the remainder shards when its bucket divides by the mesh and stays
    on one device when it does not; verdicts do not change."""
    items = [_item(i, 4) for i in range(b)]
    want = [_want(it) for it in items]
    monkeypatch.setenv("SPACEMESH_MESH", "4")
    got, evs = _traced(items)
    assert got == want
    rest = b % (4 * CEILING)
    widths = [4 * CEILING] * (b // (4 * CEILING)) \
        + [scrypt.shape_bucket(rest)]
    progs = sorted(_named(evs, "romix.dispatch"), key=lambda e: e["ts"])
    assert [e["args"]["batch"] for e in progs] == widths
    assert all(e["args"]["batch"] <= 4 * CEILING for e in progs)
    (call,) = _named(evs, "post.verify")
    assert call["args"]["tiles"] == len(widths)
    assert call["args"]["lanes"] == sum(widths)
    assert call["args"]["lanes_valid"] == b
    assert len(_named(evs, "device.flight")) == 1


@pytest.mark.parametrize("ceiling, b, real, sent", [
    # one tile in its whole-batch bucket: 64 lanes, 16 a chip
    (1 << 20, 37, [16, 16, 5, 0], [16, 16, 16, 16]),
    # 32-lane mesh tiles, the rest (11 lanes) in a 16-lane one
    (CEILING, 2 * 4 * CEILING + 11, [20, 20, 19, 16], [20, 20, 20, 20]),
], ids=["untiled", "tiled"])
def test_on_the_virtual_mesh_each_chips_edges_fail_their_owner(
        ceiling, b, real, sent, monkeypatch):
    """Four virtual devices: a bad index at the first and the last real
    lane of each chip's slice of each tile fails exactly its proof,
    as the plain reference has it; the span counts each chip's real and
    dispatched lanes and the gauge says four chips."""
    monkeypatch.setattr(scrypt, "lane_ceiling",
                        lambda n, devices=None: ceiling)
    monkeypatch.setenv("SPACEMESH_MESH", "4")
    tiles = verifier._lane_tiles(b, 4)
    bad = set()
    for at, width in tiles:
        per = width // 4
        for c in range(4):
            lo, hi = at + c * per, min(at + (c + 1) * per, b)
            if lo < hi:
                bad |= {lo, hi - 1}
    items = [_with_index(i, 4, i not in bad) for i in range(b)]
    want = [i not in bad for i in range(b)]
    assert [_want(it) for it in items] == want
    got, evs = _traced(items)
    assert got == want
    (call,) = _named(evs, "post.verify")
    a = call["args"]
    assert a["chips"] == 4
    assert a["chip_lanes_valid"] == real and a["chip_lanes"] == sent
    assert sum(a["chip_lanes_valid"]) == a["lanes_valid"] == b
    assert sum(a["chip_lanes"]) == a["lanes"]
    (flight,) = _named(evs, "device.flight")
    assert flight["args"]["chips"] == 4
    assert metrics.post_verify_mesh_devices._values.get(()) == 4


def test_off_the_mesh_one_chip_holds_every_lane(low_ceiling, monkeypatch):
    monkeypatch.delenv("SPACEMESH_MESH", raising=False)
    items = [_item(i, 4) for i in range(2 * CEILING + 3)]
    metrics.post_verify_mesh_devices.set(4)
    got, evs = _traced(items)
    assert got == [_want(it) for it in items]
    (call,) = _named(evs, "post.verify")
    a = call["args"]
    assert a["chips"] == 1
    assert a["chip_lanes_valid"] == [2 * CEILING + 3]
    assert a["chip_lanes"] == [a["lanes"]] == [2 * CEILING + 4]
    assert _named(evs, "device.flight")[0]["args"]["chips"] == 1
    assert metrics.post_verify_mesh_devices._values.get(()) == 1


def test_the_ceiling_comes_from_the_devices_memory():
    """128 * N bytes of V a lane, in three quarters of what the device
    reports: the largest power of two of lanes. A 16 GB v5e chip
    (``bytes_limit`` 15.75 GiB) holds 8,192 lanes of N=8192: 8 GiB."""

    class Chip:
        def __init__(self, limit):
            self.limit = limit

        def memory_stats(self):
            return None if self.limit is None else \
                {"bytes_limit": self.limit}

    v5e = Chip(int(15.75 * 2**30))
    assert scrypt.lane_ceiling(8192, [v5e]) == 8192
    assert scrypt.lane_ceiling(8192, [Chip(16 * 10**9)]) == 8192
    assert scrypt.lane_ceiling(4096, [v5e]) == 16384
    assert scrypt.lane_ceiling(8192, [Chip(32 << 30)]) == 16384
    # a mesh: each chip holds that many, and the smallest chip decides
    assert scrypt.lane_ceiling(8192, [v5e, Chip(8 << 30)]) == 4096
    # a platform that reports nothing is taken for a v5e chip
    assert scrypt.lane_ceiling(8192, [Chip(None)]) == 8192
    assert scrypt.lane_ceiling(8192) == 8192        # the CPU, here
    with pytest.raises(ValueError, match="does not fit"):
        scrypt.lane_ceiling(8192, [Chip(1 << 20)])
