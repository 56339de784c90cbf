"""Multi-tenant scheduler coverage (runtime/scheduler.py, ISSUE 11).

Scheduler units — fair share under starvation pressure, deadline (EDF)
admission, quota enforcement, gang-scheduled prove windows, cancel and
close semantics — plus the packed-init bit-identity suite (scheduled
multi-tenant output == solo Initializer output, per tenant, at ragged
totals) and the multi-tenant e2e asserting per-tenant spans and
metrics.
"""

import hashlib
import threading
import time

import numpy as np
import pytest

from spacemesh_tpu.runtime import TenantScheduler
from spacemesh_tpu.runtime.scheduler import QuotaExceeded, SchedulerClosed
from spacemesh_tpu.utils import metrics, tracing

N = 2
PACK = 256


def _ids(count, salt=b""):
    return [(f"t{i}", hashlib.sha256(b"node-%d" % i + salt).digest(),
             hashlib.sha256(b"commit-%d" % i + salt).digest())
            for i in range(count)]


def _reference(commit, total):
    from spacemesh_tpu.ops import scrypt

    return scrypt.scrypt_labels(
        commit, np.arange(total, dtype=np.uint64), n=N).tobytes()


def _host_vrf_nonce(label_bytes):
    halves = np.frombuffer(label_bytes, dtype="<u8").reshape(-1, 2)
    return int(np.lexsort((np.arange(halves.shape[0]),
                           halves[:, 0], halves[:, 1]))[0])


# --- packed init bit-identity -----------------------------------------


def test_packed_init_matches_solo_initializer(tmp_path):
    """4 tenants at ragged totals (1, 7, 300, 1000): the scheduled
    packed path must write byte-identical labels and the identical VRF
    nonce the solo device-scan Initializer persists, and both must equal
    hashlib.scrypt under each tenant's own commitment."""
    from spacemesh_tpu.post import initializer
    from spacemesh_tpu.post.data import LabelStore

    totals = [1, 7, 300, 1000]
    ids = _ids(4)
    with TenantScheduler(workers=1, pack_lanes=PACK) as sched:
        handles = []
        for (tid, node, commit), total in zip(ids, totals):
            sched.register_tenant(tid)
            handles.append((tid, commit, total, sched.submit_init(
                tid, tmp_path / tid, node_id=node, commitment=commit,
                num_units=1, labels_per_unit=total, scrypt_n=N,
                max_file_size=1 << 20)))
        for tid, commit, total, h in handles:
            meta = h.result(timeout=300)
            store = LabelStore(tmp_path / tid, meta)
            got = store.read_labels(0, total)
            store.close()
            ref = _reference(commit, total)
            assert got == ref, f"{tid}: packed labels diverge"
            assert meta.vrf_nonce == _host_vrf_nonce(ref)
            assert meta.labels_written == total
            assert got == b"".join(
                hashlib.scrypt(commit, salt=i.to_bytes(8, "little"), n=N,
                               r=1, p=1, dklen=16) for i in range(total))
            node = next(n for t, n, _ in ids if t == tid)
            solo, _ = initializer.initialize(
                tmp_path / "solo" / tid, node_id=node, commitment=commit,
                num_units=1, labels_per_unit=total, scrypt_n=N,
                max_file_size=1 << 20, batch_size=PACK, mesh=None)
            store = LabelStore(tmp_path / "solo" / tid, solo)
            assert store.read_labels(0, total) == got
            store.close()
            assert (solo.vrf_nonce, solo.vrf_nonce_value) == \
                (meta.vrf_nonce, meta.vrf_nonce_value)


def test_packed_init_resume(tmp_path):
    """A partially-initialized directory resumes through the scheduler:
    only the remaining labels are computed and the final state matches
    a from-scratch run (labels deterministic, min-merge idempotent)."""
    from spacemesh_tpu.post import initializer
    from spacemesh_tpu.post.data import LabelStore

    tid, node, commit = _ids(1, salt=b"resume")[0]
    d = tmp_path / "resume"
    # first half via the solo path, stopped early
    init = initializer.Initializer(
        d, initializer.open_or_create_meta(
            d, node_id=node, commitment=commit, num_units=1,
            labels_per_unit=500, scrypt_n=N, max_file_size=1 << 20),
        batch_size=128, mesh=None)
    init.progress = lambda done, total: done >= 256 and init.stop()
    init.run()
    resumed_at = init.meta.labels_written
    assert 0 < resumed_at < 500
    with TenantScheduler(workers=1, pack_lanes=PACK) as sched:
        sched.register_tenant(tid)
        h = sched.submit_init(tid, d, node_id=node, commitment=commit,
                              num_units=1, labels_per_unit=500,
                              scrypt_n=N, max_file_size=1 << 20)
        meta = h.result(timeout=300)
    store = LabelStore(d, meta)
    got = store.read_labels(0, 500)
    store.close()
    ref = _reference(commit, 500)
    assert got == ref
    assert meta.vrf_nonce == _host_vrf_nonce(ref)


# --- scheduler units ---------------------------------------------------


def test_fair_share_under_starvation_pressure():
    """A tenant flooding 24 jobs cannot starve a 3-job tenant: with
    equal weights the light tenant's jobs complete interleaved near the
    front, not after the flood."""
    order = []
    with TenantScheduler(workers=1, autostart=False) as sched:
        sched.register_tenant("flood")
        sched.register_tenant("light")
        handles = []
        for i in range(24):
            handles.append(sched.submit_call(
                "flood", lambda i=i: (time.sleep(0.002),
                                      order.append(("flood", i)))[1]))
        for i in range(3):
            handles.append(sched.submit_call(
                "light", lambda i=i: (time.sleep(0.002),
                                      order.append(("light", i)))[1]))
        sched.start()
        for h in handles:
            h.result(timeout=60)
    light_done = [k for k, (t, _) in enumerate(order) if t == "light"]
    # stride scheduling alternates; all three light jobs land within
    # the first ~8 completions even against the 24-deep flood
    assert max(light_done) < 10, order


def test_deadline_job_jumps_fair_share_order():
    order = []
    gate = threading.Event()
    with TenantScheduler(workers=1, autostart=False) as sched:
        sched.register_tenant("a")
        sched.register_tenant("b")
        hs = [sched.submit_call("a", lambda: gate.wait(10))]
        hs += [sched.submit_call("a", lambda i=i: order.append(("a", i)))
               for i in range(3)]
        # b's job is already overdue: it must run BEFORE a's queued
        # backlog even though a is the only tenant the fair-share pick
        # has history for
        hs.append(sched.submit_call("b", lambda: order.append(("b", 0)),
                                    deadline_s=0.0))
        boosts = sum(metrics.runtime_deadline_boosts.sample().values())
        sched.start()
        gate.set()
        for h in hs:
            h.result(timeout=60)
    assert order[0] == ("b", 0), order
    assert sum(metrics.runtime_deadline_boosts.sample().values()) > boosts


def test_quota_max_queued_rejects():
    with TenantScheduler(workers=1, autostart=False) as sched:
        sched.register_tenant("q", max_queued=2)
        gate = threading.Event()
        h1 = sched.submit_call("q", lambda: gate.wait(10))
        h2 = sched.submit_call("q", lambda: None)
        with pytest.raises(QuotaExceeded):
            sched.submit_call("q", lambda: None)
        sched.start()
        gate.set()
        h1.result(timeout=30)
        h2.result(timeout=30)
        # slots freed: admission works again
        sched.submit_call("q", lambda: True).result(timeout=30)


def test_quota_max_inflight_caps_concurrency():
    peak = [0]
    live = [0]
    lock = threading.Lock()

    def job():
        with lock:
            live[0] += 1
            peak[0] = max(peak[0], live[0])
        time.sleep(0.01)
        with lock:
            live[0] -= 1

    with TenantScheduler(workers=4, autostart=False) as sched:
        sched.register_tenant("capped", max_inflight=1)
        hs = [sched.submit_call("capped", job) for _ in range(6)]
        sched.start()
        for h in hs:
            h.result(timeout=60)
    assert peak[0] == 1, f"max_inflight=1 tenant ran {peak[0]} quanta"


def test_gang_windows_serialize_prove_passes(tmp_path, monkeypatch):
    """gang_windows=1: two tenants' prove windows never overlap on the
    device even with two free workers (the window's donated carries own
    the device for the pass)."""
    from spacemesh_tpu.post import workload
    from spacemesh_tpu.post.prover import Prover

    dirs = []
    for i in range(2):
        d = str(tmp_path / f"store-{i}")
        workload.build(d, 512, 256)
        dirs.append(d)

    live = [0]
    peak = [0]
    lock = threading.Lock()
    orig = Prover._scan_window

    def traced(self, *a, **kw):
        with lock:
            live[0] += 1
            peak[0] = max(peak[0], live[0])
        try:
            return orig(self, *a, **kw)
        finally:
            with lock:
                live[0] -= 1

    monkeypatch.setattr(Prover, "_scan_window", traced)
    with TenantScheduler(workers=2, gang_windows=1) as sched:
        sched.register_tenant("p0")
        sched.register_tenant("p1")
        hs = [sched.submit_prove(f"p{i}", dirs[i], workload.CHALLENGE,
                                 workload.PARAMS, batch_labels=256)
              for i in range(2)]
        proofs = [h.result(timeout=600) for h in hs]
    assert peak[0] == 1, "two prove windows overlapped despite gang=1"
    for proof in proofs:
        assert workload.verify_proof(proof, 512)


def test_cancel_and_unregister_and_close(tmp_path):
    import concurrent.futures

    sched = TenantScheduler(workers=1, autostart=False)
    sched.register_tenant("x")
    gate = threading.Event()
    try:
        running = sched.submit_call("x", lambda: gate.wait(10))
        queued = sched.submit_call("x", lambda: None)
        assert queued.cancel()
        sched.start()
        with pytest.raises(concurrent.futures.CancelledError):
            queued.result(timeout=10)
        # unregister fails that tenant's still-queued jobs and drops
        # its per-tenant gauge series
        sched.register_tenant("gone")
        orphan = sched.submit_call("gone", lambda: None)
        sched.unregister_tenant("gone")
        with pytest.raises(SchedulerClosed):
            orphan.result(timeout=10)
        assert (("tenant", "gone"),) \
            not in metrics.runtime_tenant_queued.sample()
        gate.set()
        assert running.result(timeout=30) is True
        # close fails whatever is still queued; handles never strand
        stuck = sched.submit_call("x", lambda: gate.wait(10))
        blocked = sched.submit_call("x", lambda: None)
    finally:
        sched.close()
    with pytest.raises(SchedulerClosed):
        blocked.result(timeout=10)
    # the running-at-close job either finished or failed closed — but
    # its handle must be resolved either way
    assert stuck.done()


def test_unregister_with_lanes_in_flight_resolves_handle(tmp_path):
    """Unregistering a tenant whose init job still has packed lanes in
    flight (and more unpacked) must still resolve the handle — the
    in-flight segments finalize the errored job when they retire
    instead of stranding it in the jobs table forever."""
    from spacemesh_tpu.runtime import scheduler as sched_mod

    tid, node, commit = _ids(1, salt=b"strand")[0]
    sched = TenantScheduler(workers=1, pack_lanes=128, autostart=False)
    # slow the retire path down so lanes are reliably in flight when
    # the unregister lands
    orig = TenantScheduler._retire_pack

    def slow_retire(self, ticket):
        time.sleep(0.05)
        return orig(self, ticket)

    sched._retire_pack = slow_retire.__get__(sched)
    try:
        sched.register_tenant(tid)
        h = sched.submit_init(tid, tmp_path / "strand", node_id=node,
                              commitment=commit, num_units=1,
                              labels_per_unit=1000, scrypt_n=N,
                              max_file_size=1 << 20)
        sched.start()
        # wait until the packer actually has lanes outstanding
        job = sched._jobs[h.id]
        for _ in range(200):
            if job.outstanding > 0:
                break
            time.sleep(0.005)
        sched.unregister_tenant(tid)
        with pytest.raises((sched_mod.SchedulerClosed, Exception)):
            h.result(timeout=60)   # resolves (closed), never strands
        assert sched.drain(timeout=30)
    finally:
        sched.close()


def test_prove_session_parked_is_not_watched(tmp_path):
    """A session waiting between scheduler quanta (or in the pow gate)
    has no batch counter to advance: its liveness watchdog must be
    inactive while parked, active only inside a window scan — else
    every gang-queued tenant reads as a post.prove stall."""
    from spacemesh_tpu.post import workload

    prover = workload.build(str(tmp_path / "st"), 256, 256)
    session = prover.session(workload.CHALLENGE)
    try:
        assert not session._scanning       # parked: not watched
        assert session._wd.active() is False
        session.step()                     # pow gate quantum
        assert session._wd.active() is False  # still parked
        proof = None
        while proof is None:
            proof = session.step()
        assert session._wd.active() is False  # done: not watched
    finally:
        session.close()


# --- multi-tenant e2e: mixed load, per-tenant observability ------------


def test_multi_tenant_mixed_e2e(tmp_path):
    """4 tenants, mixed init+prove+verify+pow through one scheduler:
    every output bit-identical to its single-tenant twin, and the
    capture carries per-tenant spans + per-tenant metrics."""
    from spacemesh_tpu.post import workload
    from spacemesh_tpu.post.data import LabelStore
    from spacemesh_tpu.post.verifier import VerifyItem

    ids = _ids(4, salt=b"e2e")
    prove_dir = str(tmp_path / "prove-store")
    prover = workload.build(prove_dir, 512, 256)
    serial_proof = prover.prove_serial(workload.CHALLENGE)

    labels_before = {
        tid: metrics.runtime_tenant_labels.sample().get(
            (("tenant", tid),), 0) for tid, _, _ in ids}
    tracing.start(capacity=65536)
    try:
        with TenantScheduler(workers=2, pack_lanes=PACK) as sched:
            inits = []
            for tid, node, commit in ids:
                sched.register_tenant(tid)
                inits.append((tid, commit, sched.submit_init(
                    tid, tmp_path / tid, node_id=node, commitment=commit,
                    num_units=1, labels_per_unit=200, scrypt_n=N,
                    max_file_size=1 << 20)))
            sched.register_tenant("prover")
            hp = sched.submit_prove("prover", prove_dir,
                                    workload.CHALLENGE, workload.PARAMS,
                                    batch_labels=256)
            proof = hp.result(timeout=600)
            assert proof == serial_proof
            item = VerifyItem(proof=proof, challenge=workload.CHALLENGE,
                              node_id=workload.NODE,
                              commitment=workload.COMMITMENT,
                              scrypt_n=2, total_labels=512)
            hv = sched.submit_verify("prover", [item], workload.PARAMS,
                                     seed=b"e2e-seed".ljust(32, b"\0"))
            assert hv.result(timeout=300) == [True]
            for tid, commit, h in inits:
                meta = h.result(timeout=300)
                store = LabelStore(tmp_path / tid, meta)
                got = store.read_labels(0, 200)
                store.close()
                assert got == _reference(commit, 200)
    finally:
        tracing.stop()

    doc = tracing.export()
    tracing.validate(doc)
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    by_tenant = {}
    for e in spans:
        t = e.get("args", {}).get("tenant")
        if t:
            by_tenant.setdefault(t, set()).add(e["name"])
    # every init tenant appears in the capture (pack segments), and the
    # prover tenant's quanta do too
    for tid, _, _ in ids:
        assert "runtime.segment" in by_tenant.get(tid, set()), by_tenant
    assert "runtime.quantum" in by_tenant.get("prover", set())
    # per-tenant label accounting advanced for every tenant
    after = metrics.runtime_tenant_labels.sample()
    for tid, _, _ in ids:
        assert after.get((("tenant", tid),), 0) \
            >= labels_before[tid] + 200
