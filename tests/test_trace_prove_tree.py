"""One proof, one tree: ``Prover.prove`` with the span tracer on closes
into a single tree from ``prove.proof`` down to one ``device.flight``
per flight of batches, and the spans carry the counts the benchmark's
per-layer readers take (docs/OBSERVABILITY.md, docs/POST_PROVING.md)."""

import hashlib

import pytest

from spacemesh_tpu.post import initializer
from spacemesh_tpu.post.prover import ProofParams, Prover
from spacemesh_tpu.utils import metrics, tracing

K2 = 37
NG, GROUPS, BATCH = 16, 2, 512
# 10,000 labels are two full flights of eight batches and a ragged one
# of 1,808 labels: four of its eight scan steps, the fourth partly valid
TOTAL, FLIGHT = 10_000, 8 * BATCH
STAGES = ("prove.read_wait", "prove.convert", "prove.upload",
          "prove.enqueue")


@pytest.fixture(autouse=True)
def _tracer_off():
    tracing.stop()
    yield
    tracing.stop()


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    d = tmp_path_factory.mktemp("prove-tree")
    initializer.initialize(
        d, node_id=hashlib.sha256(b"tree-node").digest(),
        commitment=hashlib.sha256(b"tree-commitment").digest(),
        num_units=4, labels_per_unit=2500, scrypt_n=2, batch_size=4096)
    return d


def _prover(d, k1=26):
    return Prover(d, ProofParams(
        k1=k1, k2=K2, k3=K2,
        pow_difficulty=bytes.fromhex("0fffffffffffffff" + "00" * 24)),
        batch_labels=BATCH, nonce_group=NG, window_groups=GROUPS,
        use_pallas=False, mesh=None)


def _traced_proof(d, k1=26):
    """One ``Prover(...)`` and its proof under a capture, as a client
    makes them: the tracer is on from before the constructor."""
    h2d0 = sum(metrics.post_prove_h2d_bytes.sample().values())
    tracing.start(capacity=1 << 14, jax_bridge=False)
    prover = _prover(d, k1)
    proof = prover.prove(hashlib.sha256(b"tree-challenge").digest())
    tracing.stop()
    doc = tracing.export()
    tracing.validate(doc)
    assert doc["otherData"]["dropped_spans"] == 0
    h2d = sum(metrics.post_prove_h2d_bytes.sample().values()) - h2d0
    evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    return proof, prover.last_stats, evs, h2d


@pytest.fixture(scope="module")
def capture(store):
    return _traced_proof(store)


def _named(evs):
    out = {}
    for e in evs:
        out.setdefault(e["name"], []).append(e)
    return out


def _ancestors(e, by_id):
    while e["args"].get("parent") in by_id:
        e = by_id[e["args"]["parent"]]
        yield e


def test_one_proof_is_one_tree(capture):
    proof, stats, evs, _h2d = capture
    by_id = {e["args"]["id"]: e for e in evs}
    named = _named(evs)
    (root,) = named["prove.proof"]
    assert root["args"]["nonce"] == proof.nonce
    assert root["args"]["passes"] == stats.windows == len(
        named["prove.window"])
    assert root["args"]["labels_swept"] == stats.labels_swept
    assert root["args"]["early_exited"] == stats.early_exited
    # everything the proof did hangs under it
    for name in ("prove.k2pow", "prove.window", "prove.dispatch",
                 "prove.retire", "device.flight") + STAGES:
        assert named[name], name
        for e in named[name]:
            assert root in list(_ancestors(e, by_id)), name
    (k2pow,) = named["prove.k2pow"]
    assert not any(a["name"] == "prove.window"
                   for a in _ancestors(k2pow, by_id))
    windows = {w["args"]["id"] for w in named["prove.window"]}
    for name in ("prove.dispatch", "prove.retire"):
        assert {e["args"]["parent"] for e in named[name]} <= windows


def test_every_batch_has_its_stages_and_its_flight(capture):
    _proof, stats, evs, _h2d = capture
    named = _named(evs)
    dispatches = {e["args"]["id"]: e for e in named["prove.dispatch"]}
    assert len(dispatches) == stats.flights
    for name in STAGES:     # one of each, inside its batch's dispatch
        assert sorted(e["args"]["parent"] for e in named[name]) \
            == sorted(dispatches), name
        for e in named[name]:
            d = dispatches[e["args"]["parent"]]
            assert d["ts"] <= e["ts"] and \
                e["ts"] + e["dur"] <= d["ts"] + d["dur"] + 2
    for e in named["prove.enqueue"]:
        # ``batch`` is the lanes the program scans: its scan steps' width
        count = dispatches[e["args"]["parent"]]["args"]["count"]
        steps = -(-count // BATCH)
        assert (e["args"]["groups"], e["args"]["batches"],
                e["args"]["batch"], e["args"]["nonces"]) \
            == (GROUPS, steps, steps * BATCH, GROUPS * NG)
    assert sorted({e["args"]["batches"] for e in named["prove.enqueue"]}) \
        == [4, 8]
    assert sum(e["args"]["batches"] for e in named["prove.enqueue"]) \
        == stats.batches
    # a flight per retired batch: from its enqueue to its counts fetched
    retires = {e["args"]["id"]: e for e in named["prove.retire"]}
    flights = named["device.flight"]
    assert sorted(f["args"]["parent"] for f in flights) == sorted(retires)
    starts = sorted(e["ts"] for e in named["prove.enqueue"])
    for f in flights:
        r = retires[f["args"]["parent"]]
        assert f["args"]["program"] == "prove_scan"
        assert f["args"]["labels"] == r["args"]["count"] <= FLIGHT
        assert f["args"]["d2h_bytes"] == GROUPS * NG * 4
        assert f["ts"] + f["dur"] <= r["ts"] + r["dur"] + 2
        # it starts where the batch's enqueue does (the clock is read
        # just before the span opens)
        assert any(0 <= s - f["ts"] <= 500 for s in starts)
    # an early exit abandons the batches still in flight: those were
    # dispatched and never retired
    assert len(retires) <= len(dispatches)
    assert sum(r["args"]["count"] for r in retires.values()) \
        == stats.labels_swept


def test_upload_bytes_are_16_a_label_dispatched(capture):
    # label words only: the program makes its own lane indices; the
    # flight's count and start ride along as three u32 words. A ragged
    # last flight is padded to the flight's shape
    _proof, stats, evs, h2d = capture
    named = _named(evs)
    sent = sum(e["args"]["h2d_bytes"] for e in named["prove.upload"])
    assert sent == (16 * FLIGHT + 12) * stats.flights
    assert h2d == sent      # the counter counts what the spans say


def test_a_batch_crosses_the_boundary_once_each_way(capture):
    # the mechanism of ISSUE 28 at ISSUE 32's unit, pinned as PR 24's was
    # for post.verify: per FLIGHT of up to eight batches ONE upload call,
    # ONE program, ONE count vector back
    _proof, stats, evs, _h2d = capture
    named = _named(evs)
    by_parent = {}
    for name in ("prove.upload", "prove.enqueue"):
        for e in named[name]:
            by_parent.setdefault((e["args"]["parent"], name), []).append(e)
    for d in named["prove.dispatch"]:
        (up,) = by_parent[d["args"]["id"], "prove.upload"]
        (enq,) = by_parent[d["args"]["id"], "prove.enqueue"]
        assert up["args"]["arrays"] == 2    # both in one device_put
        assert up["args"]["h2d_bytes"] == 16 * FLIGHT + 12
        assert enq["args"]["programs"] == 1
        assert enq["args"]["batches"] == -(-d["args"]["count"] // BATCH)
        assert enq["args"]["groups"] == GROUPS
        assert enq["args"]["nonces"] == GROUPS * NG
        # ONE compaction epilogue a scan step over all the groups' rows
        # (ISSUE 35; it would read batches x groups at bdb9bbf)
        assert enq["args"]["epilogues"] == enq["args"]["batches"]
    flights = named["device.flight"]
    assert len(flights) == len(named["prove.retire"])
    # a pass of the store is three flights of 8 + 8 + 4 scan steps
    per_pass = -(-TOTAL // FLIGHT)
    assert stats.flights == len(named["prove.dispatch"]) \
        == stats.windows * per_pass
    assert stats.batches == stats.windows * -(-TOTAL // BATCH)
    assert stats.batches / stats.flights == 20 / 3      # the mean fill
    for f in flights:
        assert f["args"]["syncs"] == 1
        assert f["args"]["groups"] == GROUPS
        assert f["args"]["ready"] in (True, False)
    # the prefetch's hit count is in the stats and equals the trace's
    assert stats.retire_ready == sum(
        bool(f["args"]["ready"]) for f in flights)
    assert 0 <= stats.retire_ready <= stats.flights
    assert {"retire_ready", "flights"} <= set(stats.as_dict())


def test_spans_are_free_when_the_tracer_is_off():
    assert tracing.span("prove.proof", None) is tracing._NOP
    assert tracing.span("prove.enqueue", None) is tracing._NOP


# --- the edges of a proof (ISSUE 36) -----------------------------------

# the named stretches a proof's thread time lies in: leaves but for
# prove.k2pow (its engine's pow.* spans nest inside it), xla.compile
# under any of them, and the device.flight intervals, which start at an
# enqueue and end in a retire
LEAVES = ("prove.open", "prove.k2pow", "prove.prepare", "prove.read_wait",
          "prove.convert", "prove.upload", "prove.enqueue", "prove.retire",
          "prove.drain", "prove.decode", "prove.close")


def test_the_edges_of_a_proof_are_spans_where_they_belong(capture):
    _proof, stats, evs, _h2d = capture
    by_id = {e["args"]["id"]: e for e in evs}
    named = _named(evs)
    (root,) = named["prove.proof"]
    (run,) = named["prove.run"]
    windows = {w["args"]["id"]: w for w in named["prove.window"]}
    # one prove.open a Prover: the constructor, before the proof, on the
    # proof's thread, under nothing of the proof's
    (opened,) = named["prove.open"]
    assert opened["ts"] + opened["dur"] <= root["ts"]
    assert opened["tid"] == root["tid"]
    assert opened["args"].get("parent") is None
    # one prove.prepare a session (under prove.run) and one a pass
    # (under its prove.window)
    prep = {}
    for e in named["prove.prepare"]:
        prep.setdefault(e["args"]["what"], []).append(e)
    (session,) = prep["session"]
    assert session["args"]["parent"] == run["args"]["id"]
    assert sorted(e["args"]["parent"] for e in prep["pass"]) \
        == sorted(windows)
    # one prove.drain a pass, under it, after every flight it retired
    assert sorted(e["args"]["parent"] for e in named["prove.drain"]) \
        == sorted(windows)
    for e in prep["pass"] + named["prove.drain"]:
        w = windows[e["args"]["parent"]]
        assert w["ts"] <= e["ts"] and \
            e["ts"] + e["dur"] <= w["ts"] + w["dur"] + 2
        assert e["args"]["window"] == w["args"]["window"]
    for r in named["prove.retire"]:
        (drain,) = [d for d in named["prove.drain"]
                    if d["args"]["parent"] == r["args"]["parent"]]
        assert r["ts"] + r["dur"] <= drain["ts"] + 2
    # one prove.decode a returned proof: after the winning pass, under
    # the session, with the bytes its two fetches bring back
    (decode,) = named["prove.decode"]
    assert decode["args"]["parent"] == run["args"]["id"]
    last = max(windows.values(), key=lambda w: w["ts"])
    assert decode["ts"] >= last["ts"] + last["dur"] - 2
    assert decode["args"]["window"] == last["args"]["window"]
    assert decode["args"]["d2h_bytes"] == 2 * K2 * 4 * GROUPS * NG \
        + 4 * GROUPS * NG
    # one prove.close a proof, under it, after the session's span
    (closed,) = named["prove.close"]
    assert closed["args"]["parent"] == root["args"]["id"]
    assert run["ts"] + run["dur"] <= closed["ts"] + 2
    assert closed["ts"] + closed["dur"] <= root["ts"] + root["dur"] + 2
    for name in ("prove.prepare", "prove.drain", "prove.decode",
                 "prove.close"):
        for e in named[name]:
            assert root in list(_ancestors(e, by_id)), name
    assert stats.windows == len(windows)


def test_the_leaves_cover_a_proof_from_its_constructor(capture):
    # what the thread did from Prover(...) to the returned proof is in
    # named leaf spans, but for the engine's own glue
    _proof, _stats, evs, _h2d = capture
    named = _named(evs)
    (root,) = named["prove.proof"]
    (opened,) = named["prove.open"]
    lo, hi = opened["ts"], root["ts"] + root["dur"]
    spans = sorted((max(e["ts"], lo), min(e["ts"] + e["dur"], hi))
                   for n in LEAVES for e in named.get(n, ())
                   if e["tid"] == root["tid"])
    covered, end = 0, lo
    for a, b in spans:
        if b > end:
            covered += b - max(a, end)
            end = b
    assert covered >= 0.9 * (hi - lo), (covered, hi - lo)


def test_every_context_span_of_the_proof_carries_its_cpu_time(capture):
    _proof, _stats, evs, _h2d = capture
    for e in evs:
        if e["name"] in ("device.flight", "xla.compile"):
            # intervals: no thread CPU time
            assert "cpu_us" not in e["args"], e["name"]
            continue
        assert 0 <= e["args"]["cpu_us"] <= e["dur"], e
    named = _named(evs)
    for name in ("prove.convert", "prove.upload", "prove.enqueue"):
        assert all("cpu_us" in e["args"] for e in named[name]), name


def test_a_pass_says_how_many_flights_it_carried_and_dropped(capture):
    _proof, stats, evs, _h2d = capture
    named = _named(evs)
    windows = named["prove.window"]
    assert sum(w["args"]["flights"] for w in windows) == stats.flights \
        == len(named["prove.dispatch"])
    assert sum(w["args"]["abandoned"] for w in windows) \
        == stats.flights_abandoned \
        == len(named["prove.dispatch"]) - len(named["prove.retire"])


def test_a_forced_early_exit_abandons_what_the_engine_counts(store):
    # K1 at half the store: half the labels qualify for every nonce, so
    # nonce 0 holds K2 hits after the FIRST flight and nothing lower can
    # beat it: the pass exits at its first retire with the store's two
    # other flights dispatched (inflight 3) and never retired
    dropped0 = sum(metrics.post_prove_flights_abandoned.sample().values())
    proof, stats, evs, _h2d = _traced_proof(store, k1=TOTAL // 2)
    named = _named(evs)
    assert proof.nonce == 0 and stats.early_exited
    (window,) = named["prove.window"]
    assert len(named["prove.retire"]) == 1
    assert len(named["prove.dispatch"]) == 3 == window["args"]["flights"]
    assert window["args"]["abandoned"] == 2 == stats.flights_abandoned
    assert sum(metrics.post_prove_flights_abandoned.sample().values()) \
        - dropped0 == 2
    # the decode still runs after the drain, and waits for the dropped
    (decode,) = named["prove.decode"]
    (drain,) = named["prove.drain"]
    assert decode["ts"] >= drain["ts"] + drain["dur"] - 2


def test_the_new_call_sites_are_free_when_the_tracer_is_off(store,
                                                           monkeypatch):
    # off, every span() of a proof (prove.open to prove.close) returns
    # the no-op singleton: no live span is built and no clock is read
    def no_span(*a, **kw):
        raise AssertionError("a live span was built with the tracer off")

    monkeypatch.setattr(tracing, "_Span", no_span)
    for name in ("prove.open", "prove.prepare", "prove.drain",
                 "prove.decode", "prove.close"):
        assert tracing.span(name, None) is tracing._NOP
    prover = _prover(store)
    assert prover.prove(hashlib.sha256(b"untraced").digest()).k2 == K2
