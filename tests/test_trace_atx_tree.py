"""One ATX, one tree: a request through an in-process ``VerifydServer``
with the span tracer on closes into a single tree from ``verifyd.http``
down to the device flights, and the spans carry the counts the
benchmark's per-layer readers take (docs/OBSERVABILITY.md)."""

import asyncio
import time

import pytest

from spacemesh_tpu.ops import scrypt
from spacemesh_tpu.utils import tracing
from spacemesh_tpu.verify import workload
from spacemesh_tpu.verify.farm import PostRequest, PowRequest
from spacemesh_tpu.verifyd import VerifydClient, VerifydServer

REASONS = {"full", "idle", "deadline", "tuner", "block"}
SLACK_US = 2    # ts and dur are floored to whole microseconds


@pytest.fixture(scope="module")
def wl(tmp_path_factory):
    """Every kind in one request. 9 POST items: 6 distinct after the
    farm's dedup (4 challenges, two corrupted copies), one of which fails
    the host checks (too few indices), so the farm pads 6 to 8. 16 k2pow
    witnesses: 9 distinct, device width."""
    d = tmp_path_factory.mktemp("atx-tree-wl")
    return workload.build(str(d), sigs=8, vrfs=0, posts=9, memberships=4,
                          pows=16, post_challenges=4)


@pytest.fixture(autouse=True)
def _tracer_off():
    tracing.stop()
    yield
    tracing.stop()


def _capture(wl):
    async def go():
        server = VerifydServer(post_params=wl.post_params,
                               post_seed=wl.post_seed, workers=2)
        server.service.farm.ed_verifier = wl.ed
        server.service.farm.vrf_verifier = wl.vrf
        client = None
        try:
            port = await server.start()
            client = VerifydClient(f"http://127.0.0.1:{port}", "alice",
                                   retry=None)
            await client.register()
            tracing.start(capacity=1 << 14, jax_bridge=False)
            got = await client.verify(wl.requests)
            tracing.stop()
            return got
        finally:
            if client is not None:
                await client.aclose()
            await server.close()

    got = asyncio.run(go())
    doc = tracing.export()
    tracing.validate(doc)
    assert doc["otherData"]["dropped_spans"] == 0
    return got, [e for e in doc["traceEvents"] if e["ph"] == "X"]


def _inside(child, parent):
    return (child["ts"] >= parent["ts"] - SLACK_US
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + SLACK_US)


def test_one_request_is_one_tree(wl):
    got, evs = _capture(wl)
    assert got == wl.inline_all()
    named = {}
    for e in evs:
        named.setdefault(e["name"], []).append(e)

    # the root, and the request's identifier on every span of the tree
    (http,) = named["verifyd.http"]
    a = http["args"]
    req = a["id"]
    assert a["req"] == req and "parent" not in a
    assert a["status"] == "ok" and a["items"] == len(wl.requests)
    assert a["bytes_in"] > 0 and a["bytes_out"] > 0
    (request,) = named["verifyd.request"]
    assert request["args"]["parent"] == req and _inside(request, http)
    (drain,) = named["verifyd.drain"]
    quanta = [e for e in named["runtime.quantum"]
              if e["args"]["kind"] == "verifyd"]
    assert len(quanta) == 1 and quanta[0]["args"]["queue_wait_ms"] >= 0
    for e in [request, drain, quanta[0]] + named["farm.request"]:
        assert e["args"]["req"] == req, e["name"]
    assert len(named["farm.request"]) == len(wl.requests)

    # why each POST batch went, and what was in flight
    post_batches = [e for e in named["farm.batch"]
                    if e["args"]["kind"] == "post"]
    assert post_batches
    for b in post_batches:
        assert b["args"]["reason"] in REASONS
        for key in ("inflight", "target", "left"):
            assert isinstance(b["args"][key], int), key
    assert all(b["args"]["reason"] in REASONS for b in named["farm.batch"])

    # post.verify: stages in order, flights inside, lanes counted
    calls = named["post.verify"]
    batch_ids = {b["args"]["id"] for b in post_batches}
    order = ["post.verify.checks", "post.verify.pack", "romix.upload",
             "romix.dispatch", "post.verify.threshold"]
    assert "romix.pad" not in named          # the host pads, in numpy
    assert "post.verify.relayout" not in named   # the labels stay up
    for call in calls:
        assert call["args"]["parent"] in batch_ids
        kids = sorted((e for e in evs
                       if e["args"].get("parent") == call["args"]["id"]),
                      key=lambda e: (e["ts"], e["args"]["id"]))
        assert all(_inside(k, call) for k in kids)
        stages = [k["name"] for k in kids if k["name"] in order]
        assert stages == sorted(stages, key=order.index)
        assert set(stages) == set(order)
        # one flight: label program and proving hash back to back, only
        # the hash values (one u32 a lane) come back, in one sync
        (flight,) = [k for k in kids if k["name"] == "device.flight"]
        assert flight["args"]["program"] == "labels_proving"
        assert flight["args"]["lanes"] == call["args"]["lanes"]
        assert flight["args"]["d2h_bytes"] == 4 * call["args"]["lanes"]
        assert call["args"]["syncs"] == 1
        assert call["args"]["d2h_bytes"] == 4 * call["args"]["lanes"]
        (rd,) = [k for k in kids if k["name"] == "romix.dispatch"]
        assert rd["args"]["batch"] == call["args"]["lanes"]
        assert rd["args"]["valid"] <= rd["args"]["batch"]
        assert _inside(rd, flight)
    k3 = wl.post_params.k3
    distinct = {r.key(): r for r in wl.requests
                if isinstance(r, PostRequest)}
    host_ok = [r for r in distinct.values()
               if len(r.item.proof.indices) >= wl.post_params.k2
               and all(0 <= j < r.item.total_labels
                       for j in r.item.proof.indices)]
    assert len(distinct) == 6 and len(host_ok) == 5
    total = {k: sum(c["args"][k] for c in calls)
             for k in ("proofs", "host_rejected", "lanes_valid", "lanes",
                       "syncs", "h2d_bytes", "d2h_bytes")}
    assert total["proofs"] == len(distinct)
    assert total["host_rejected"] == len(distinct) - len(host_ok)
    assert total["lanes_valid"] == k3 * len(host_ok)
    assert total["syncs"] == len(calls)
    assert total["d2h_bytes"] == 4 * total["lanes"]
    assert total["h2d_bytes"] == 19 * 4 * total["lanes"]
    if len(calls) == 1:
        # 6 proofs padded to 8 by the farm; the pad repeats the batch's
        # first proof, so 7 or 8 of the 8 reach the device
        assert total["lanes"] in (scrypt.shape_bucket(7 * k3),
                                  scrypt.shape_bucket(8 * k3))

    # a k2pow batch at device width retires through the engine's span
    pows = {r.key() for r in wl.requests if isinstance(r, PowRequest)}
    assert len(pows) >= 8
    retire = named["pow_verify.retire"]
    assert retire and len(retire) == len(named["pow_verify.dispatch"])
    assert all(e["args"]["kind"] == "k2pow_verify" for e in retire)
    pow_flights = [e for e in named["device.flight"]
                   if e["args"]["program"] == "pow_verify"]
    assert len(pow_flights) == len(retire)
    for f in pow_flights:   # enqueued at dispatch, landed inside a retire
        end = f["ts"] + f["dur"]
        assert any(r["ts"] - SLACK_US <= end
                   <= r["ts"] + r["dur"] + SLACK_US for r in retire)


def test_a_merged_post_batch_still_closes_every_tree(wl):
    """Three clients' requests, the later two gathered behind the first
    one's flight and sent as ONE batch: each request is still one tree,
    every ``farm.request`` names its ``farm.batch`` and is listed among
    that batch's ``members``, and the verdicts are the inline ones."""
    distinct = list({r.key(): r for r in wl.requests
                     if isinstance(r, PostRequest)}.values())
    others = [r for r in wl.requests if not isinstance(r, PostRequest)]
    sent = [distinct[2 * i:2 * i + 2] + [others[i]] for i in range(3)]

    async def go():
        server = VerifydServer(post_params=wl.post_params,
                               post_seed=wl.post_seed, workers=4)
        farm = server.service.farm
        farm.ed_verifier, farm.vrf_verifier = wl.ed, wl.vrf
        real = farm._run_backend

        def slow_post(kind, reqs):  # a flight long enough to arrive in
            if kind == "post":
                time.sleep(0.3)
            return real(kind, reqs)

        farm._run_backend = slow_post
        clients = []
        try:
            port = await server.start()
            for name in ("alice", "bob", "carol"):
                clients.append(VerifydClient(f"http://127.0.0.1:{port}",
                                             name, retry=None))
                await clients[-1].register()
            tracing.start(capacity=1 << 14, jax_bridge=False)
            tasks = []
            for c, items in zip(clients, sent):
                tasks.append(asyncio.ensure_future(c.verify(items)))
                await asyncio.sleep(0.08)
            got = await asyncio.gather(*tasks)
            tracing.stop()
            return got
        finally:
            for c in clients:
                await c.aclose()
            await server.close()

    got = asyncio.run(go())
    assert got == [[wl.inline_verify(r) for r in items] for items in sent]
    doc = tracing.export()
    tracing.validate(doc)
    evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    by_id = {e["args"]["id"]: e for e in evs}
    roots = [e for e in evs if e["name"] == "verifyd.http"]
    assert len(roots) == 3

    def root_of(e):
        while "parent" in e["args"]:
            e = by_id[e["args"]["parent"]]
        return e

    batches = {e["args"]["id"]: e for e in evs if e["name"] == "farm.batch"}
    reqs = [e for e in evs if e["name"] == "farm.request"]
    assert len(reqs) == 9
    for r in reqs:
        root = root_of(r)
        assert root["name"] == "verifyd.http"
        assert r["args"]["req"] == root["args"]["req"]
        b = batches[r["args"]["batch"]]
        assert r["args"]["id"] in b["args"]["members"]
        assert b["args"]["kind"] == r["args"]["kind"]
    post = sorted((b for b in batches.values()
                   if b["args"]["kind"] == "post"), key=lambda e: e["ts"])
    assert [b["args"]["n"] for b in post] == [2, 4]
    first, merged = (b["args"] for b in post)
    assert first["held_ms"] == 0 and merged["held_ms"] > 50
    assert first["inflight"] == 0 and merged["inflight"] == 0
    assert len({by_id[m]["args"]["req"] for m in merged["members"]}) == 2
    # the one verifier call of the merged batch hangs under it, and so
    # under no single request: two trees share it by `members`
    calls = [e for e in evs if e["name"] == "post.verify"]
    assert sorted(c["args"]["parent"] for c in calls) == sorted(
        b["args"]["id"] for b in post)
