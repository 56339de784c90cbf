"""Span tracer (spacemesh_tpu/utils/tracing.py): no-op fast path, ring
bounds, contextvar causality, trace-event export validity, and the
end-to-end acceptance capture — one init + prove + verify-farm run whose
export links verify-farm requests to their batch and stamps one window
id across a prove pass's read/dispatch/retire spans."""

import asyncio
import hashlib
import json
import subprocess
import sys
import threading
import time

import pytest

from spacemesh_tpu.utils import tracing


@pytest.fixture(autouse=True)
def _tracer_off():
    """Every test starts and ends with the tracer disabled."""
    tracing.stop()
    yield
    tracing.stop()


# --- disabled fast path -----------------------------------------------


def test_disabled_span_is_the_noop_singleton():
    assert not tracing.is_enabled()
    assert tracing.span("anything") is tracing._NOP
    assert tracing.span("x", {"k": 1}, parent=7) is tracing._NOP
    # instant is a plain early return
    tracing.instant("x")
    # the singleton absorbs every protocol call
    with tracing.span("x") as sp:
        sp.set(a=1)
    assert sp is tracing._NOP and sp.id is None
    assert tracing.current_id() is None


def test_disabled_span_call_is_cheap():
    """The disabled path must stay an attribute check + singleton return
    (the acceptance criterion's '~dict-free work'): 200k calls in well
    under a second even on a loaded CI host."""
    span = tracing.span
    t0 = time.perf_counter()
    for _ in range(200_000):
        with span("hot"):
            pass
    dt = time.perf_counter() - t0
    assert dt < 1.0, f"disabled span path too slow: {dt:.3f}s / 200k"


# --- interval() and the compile listener --------------------------------


def _names(doc):
    return [e["name"] for e in doc["traceEvents"] if e["ph"] != "M"]


@pytest.mark.parametrize("on", [True, False], ids=["on", "off"])
def test_interval_records_parent_and_attrs(on):
    """A completed span from an earlier start: parent is the current
    span, the contextvar is untouched; off, nothing is recorded."""
    tracing.start(capacity=64)
    if not on:
        tracing.stop()
    t0 = time.perf_counter_ns()
    with tracing.span("outer") as outer:
        with tracing.span("between"):
            pass
        tracing.interval("device.flight", t0, {"program": "p", "lanes": 4})
        assert tracing.current_id() == outer.id
    doc = tracing.export()
    tracing.validate(doc)
    if not on:
        assert _names(doc) == []
        return
    evs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] != "M"}
    flight = evs["device.flight"]
    assert flight["ph"] == "X"
    assert flight["args"]["parent"] == outer.id
    assert flight["args"]["program"] == "p" and flight["args"]["lanes"] == 4
    # it began before the span that is its parent, and was not re-parented
    # under (nor did it re-parent) the span opened in between
    assert flight["ts"] <= evs["outer"]["ts"]
    assert evs["between"]["args"]["parent"] == outer.id
    assert flight["ts"] + flight["dur"] >= \
        evs["between"]["ts"] + evs["between"]["dur"]


@pytest.mark.parametrize("on", [True, False], ids=["on", "off"])
def test_fresh_jit_shape_leaves_an_xla_compile_child(on):
    """Which span recompiled: JAX's own duration events land as
    ``xla.compile`` spans under the span that caused them."""
    import jax
    import jax.numpy as jnp

    tracing.start(capacity=256)
    if not on:
        tracing.stop()
    fn = jax.jit(lambda x: x * 3 + 1)    # a new function: never cached
    with tracing.span("caller") as caller:
        fn(jnp.zeros((7, 3), jnp.uint32)).block_until_ready()
    doc = tracing.export()
    tracing.validate(doc)
    compiles = [e for e in doc["traceEvents"] if e["name"] == "xla.compile"]
    if not on:
        assert compiles == [] and _names(doc) == []
        return
    assert compiles, _names(doc)
    assert all(e["args"]["parent"] == caller.id for e in compiles)
    events = {e["args"]["event"] for e in compiles}
    assert events & {"backend_compile", "cache_retrieval"} and events <= {
        "trace", "lower", "backend_compile", "cache_retrieval"}
    # the thousands of sub-millisecond inner traces are not recorded
    assert all(e["dur"] >= 1000 for e in compiles)
    # the same shape again compiles nothing
    tracing.start(capacity=256)
    with tracing.span("caller"):
        fn(jnp.ones((7, 3), jnp.uint32)).block_until_ready()
    assert "xla.compile" not in _names(tracing.export())


def test_disabled_interval_is_cheap():
    """Off, ``interval`` is one branch: the bound the disabled span
    path is held to holds for it too."""
    interval = tracing.interval
    t0 = time.perf_counter()
    for _ in range(200_000):
        interval("hot", 0)
    dt = time.perf_counter() - t0
    assert dt < 1.0, f"disabled interval path too slow: {dt:.3f}s / 200k"


# --- recording + export -----------------------------------------------


def test_span_records_parenting_and_attrs():
    tracing.start(capacity=64)
    with tracing.span("outer", {"a": 1}) as outer:
        assert tracing.current_id() == outer.id
        with tracing.span("inner") as inner:
            inner.set(b=2)
        tracing.instant("mark", {"m": 3})
    assert tracing.current_id() is None
    doc = tracing.export()
    tracing.validate(doc)
    evs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] != "M"}
    assert evs["outer"]["args"]["a"] == 1
    assert evs["inner"]["args"]["parent"] == evs["outer"]["args"]["id"]
    assert evs["inner"]["args"]["b"] == 2
    assert evs["mark"]["ph"] == "i"
    assert evs["mark"]["args"]["parent"] == evs["outer"]["args"]["id"]
    assert evs["outer"]["dur"] >= evs["inner"]["dur"] >= 0


# --- thread CPU time on spans (ISSUE 36) --------------------------------


def _by_name(doc):
    return {e["name"]: e for e in doc["traceEvents"] if e["ph"] != "M"}


def test_sync_span_carries_its_threads_cpu_time():
    tracing.start(capacity=64)
    with tracing.span("compute"):
        t_end = time.thread_time() + 0.02
        while time.thread_time() < t_end:
            pass
    with tracing.span("wait"):
        time.sleep(0.05)
    evs = _by_name(tracing.export())
    compute, wait = evs["compute"], evs["wait"]
    # what the thread computed is CPU time, and never more than the wall
    assert 20_000 <= compute["args"]["cpu_us"] <= compute["dur"]
    # a span that waits is long on the wall clock and short on the CPU
    assert wait["dur"] >= 50_000
    assert 0 <= wait["args"]["cpu_us"] < wait["dur"] // 5


def test_span_entered_on_an_event_loop_thread_carries_no_cpu_time():
    # on a loop's thread the tasks interleaved across a span's awaits
    # would be counted as its own; a span in a worker thread the loop
    # hands work to runs on no loop and keeps it
    tracing.start(capacity=64)

    def in_worker():
        with tracing.span("worker"):
            pass

    async def main():
        with tracing.span("sync_on_loop"):
            await asyncio.sleep(0)
        async with tracing.span("async_on_loop"):
            await asyncio.to_thread(in_worker)

    asyncio.run(main())
    evs = _by_name(tracing.export())
    assert "cpu_us" not in evs["sync_on_loop"]["args"]
    assert "cpu_us" not in evs["async_on_loop"]["args"]
    assert 0 <= evs["worker"]["args"]["cpu_us"] <= evs["worker"]["dur"]


def test_span_exited_on_another_thread_carries_no_cpu_time():
    import contextvars

    tracing.start(capacity=64)
    ctx = contextvars.copy_context()    # one context, two threads
    sp = tracing.span("handed_over")
    ctx.run(sp.__enter__)
    t = threading.Thread(target=ctx.run,
                         args=(sp.__exit__, None, None, None))
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    ev = _by_name(tracing.export())["handed_over"]
    assert "cpu_us" not in ev["args"]


def test_interval_and_instant_carry_no_cpu_time():
    tracing.start(capacity=64)
    t0 = time.perf_counter_ns()
    with tracing.span("outer"):
        tracing.interval("device.flight", t0, {"program": "p"})
        tracing.instant("mark")
    evs = _by_name(tracing.export())
    assert "cpu_us" in evs["outer"]["args"]
    assert "cpu_us" not in evs["device.flight"]["args"]
    assert "cpu_us" not in evs["mark"]["args"]


def test_async_context_propagation():
    tracing.start(capacity=64)

    async def child():
        with tracing.span("child"):
            await asyncio.sleep(0)

    async def main():
        with tracing.span("root") as root:
            # both a created task and a plain await inherit the parent
            await asyncio.gather(child(), child())
            return root.id

    root_id = asyncio.run(main())
    doc = tracing.export()
    tracing.validate(doc)
    children = [e for e in doc["traceEvents"] if e["name"] == "child"]
    assert len(children) == 2
    assert all(e["args"]["parent"] == root_id for e in children)


def test_thread_parent_handoff():
    """Long-lived pool threads can't inherit contextvars — current_id()
    + the explicit parent argument is the documented handoff."""
    tracing.start(capacity=64)
    seen = {}

    def worker(parent):
        with tracing.span("pool.work", parent=parent) as sp:
            seen["id"] = sp.id

    with tracing.span("submitter") as sub:
        t = threading.Thread(target=worker, args=(tracing.current_id(),))
        t.start()
        t.join()
    doc = tracing.export()
    tracing.validate(doc)
    work = [e for e in doc["traceEvents"] if e["name"] == "pool.work"][0]
    assert work["args"]["parent"] == sub.id
    # the worker thread shows up as its own named track
    tids = {e["tid"] for e in doc["traceEvents"] if e["ph"] != "M"}
    assert len(tids) == 2


def test_ring_is_bounded_and_counts_drops():
    tracing.start(capacity=16)
    for i in range(50):
        with tracing.span(f"s{i}"):
            pass
    doc = tracing.export()
    tracing.validate(doc)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 16
    assert doc["otherData"]["captured_spans"] == 16
    assert doc["otherData"]["dropped_spans"] == 34
    # the ring keeps the NEWEST spans
    assert {e["name"] for e in xs} == {f"s{i}" for i in range(34, 50)}


def test_restart_resets_the_capture():
    tracing.start(capacity=16)
    with tracing.span("first"):
        pass
    tracing.start(capacity=16)  # new capture window
    with tracing.span("second"):
        pass
    names = {e["name"] for e in tracing.export()["traceEvents"]
             if e["ph"] == "X"}
    assert names == {"second"}


def test_export_json_roundtrip(tmp_path):
    tracing.start(capacity=16)
    with tracing.span("a"):
        pass
    path = tmp_path / "trace.json"
    tracing.export_json(str(path))
    doc = json.loads(path.read_text())
    tracing.validate(doc)
    assert any(e["name"] == "a" for e in doc["traceEvents"])


# --- validator --------------------------------------------------------


def test_validate_rejects_malformed_docs():
    with pytest.raises(ValueError):
        tracing.validate([])
    with pytest.raises(ValueError):
        tracing.validate({"traceEvents": [{"ph": "X"}]})  # missing keys
    base = {"name": "x", "pid": 1, "tid": 1}
    with pytest.raises(ValueError):  # unknown phase
        tracing.validate({"traceEvents": [{**base, "ph": "Z", "ts": 0}]})
    with pytest.raises(ValueError):  # X without dur
        tracing.validate({"traceEvents": [{**base, "ph": "X", "ts": 0}]})
    with pytest.raises(ValueError):  # ts going backwards
        tracing.validate({"traceEvents": [
            {**base, "ph": "X", "ts": 10, "dur": 1},
            {**base, "ph": "X", "ts": 5, "dur": 1}]})
    with pytest.raises(ValueError):  # E without B
        tracing.validate({"traceEvents": [{**base, "ph": "E", "ts": 0}]})
    with pytest.raises(ValueError):  # unclosed B
        tracing.validate({"traceEvents": [{**base, "ph": "B", "ts": 0}]})
    # matched B/E is fine
    tracing.validate({"traceEvents": [
        {**base, "ph": "B", "ts": 0},
        {**base, "ph": "E", "ts": 4}]})


# --- flame summary ----------------------------------------------------


def test_summarize_self_time_and_wait_split():
    tracing.start(capacity=64)
    with tracing.span("stage.work"):
        with tracing.span("stage.read_wait"):
            time.sleep(0.01)
    summary = tracing.summarize(tracing.export())
    by_name = {r["name"]: r for r in summary["top_self_time"]}
    # the child's time is subtracted from the parent's self time
    assert by_name["stage.work"]["self_us"] <= \
        by_name["stage.work"]["total_us"] - by_name["stage.read_wait"]["total_us"] \
        + 1000
    st = summary["stages"]["stage"]
    assert st["wait_us"] > 0
    assert 0.0 <= st["wait_frac"] <= 1.0
    text = tracing.render_summary(summary)
    assert "stage.read_wait" in text and "wait %" in text


# --- SPACEMESH_TRACE boot knob ----------------------------------------


def _boot_probe(trace_value: str) -> str:
    import os

    env = dict(os.environ)
    env["SPACEMESH_TRACE"] = trace_value
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c",
         "from spacemesh_tpu.utils import tracing; "
         "print(tracing.is_enabled(), tracing.TRACER.capacity)"],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip()


def test_boot_env_knob_starts_capture():
    assert _boot_probe("4096") == "True 4096"
    assert _boot_probe("off").startswith("False")


# --- the acceptance capture: init + prove + verify-farm ----------------


def _tiny_post_run(tmp_path):
    from spacemesh_tpu.post import initializer
    from spacemesh_tpu.post.prover import ProofParams, Prover

    node = hashlib.sha256(b"trace-node").digest()
    commit = hashlib.sha256(b"trace-commit").digest()
    ch = hashlib.sha256(b"trace-ch").digest()
    params = ProofParams(k1=64, k2=8, k3=4,
                         pow_difficulty=bytes([32]) + bytes([255]) * 31)
    initializer.initialize(
        str(tmp_path), node_id=node, commitment=commit, num_units=1,
        labels_per_unit=512, scrypt_n=2, max_file_size=4096,
        batch_size=128)
    return Prover(str(tmp_path), params, batch_labels=256).prove(ch)


async def _farm_leg():
    from spacemesh_tpu.core.signing import EdSigner
    from spacemesh_tpu.verify.farm import Lane, SigRequest, VerificationFarm

    signer = EdSigner()
    farm = VerificationFarm()
    reqs = [SigRequest(1, signer.public_key, b"msg-%d" % i,
                       signer.sign(1, b"msg-%d" % i)) for i in range(3)]
    try:
        verdicts = await asyncio.gather(
            *(farm.submit(r, lane=Lane.GOSSIP) for r in reqs))
    finally:
        await farm.aclose()
    return verdicts


def test_capture_init_prove_farm_end_to_end(tmp_path):
    """The PR's acceptance criterion: one capture over a small init +
    prove + verify-farm run exports valid trace-event JSON in which a
    verify-farm request span links to its batch's dispatch span and a
    prove window's read/dispatch/retire spans share one window id."""
    tracing.start(capacity=16384)
    proof = _tiny_post_run(tmp_path)
    assert proof.nonce >= 0
    verdicts = asyncio.run(_farm_leg())
    assert all(verdicts)
    tracing.stop()
    doc = tracing.export()
    tracing.validate(doc)
    evs = [e for e in doc["traceEvents"] if e["ph"] in ("X", "i")]
    names = {e["name"] for e in evs}

    # every layer of the node contributed spans
    assert {"init.run", "init.dispatch", "init.fetch", "init.write",
            "prove.run", "prove.window", "prove.read_io",
            "prove.dispatch", "prove.retire", "romix.dispatch",
            "farm.request", "farm.batch"} <= names

    # farm linkage: each non-dedup request span carries its batch's id,
    # and that batch's members list carries the request's id back
    batches = {e["args"]["id"]: e for e in evs
               if e["name"] == "farm.batch"}
    linked = 0
    for e in evs:
        if e["name"] == "farm.request" and "batch" in e["args"]:
            b = batches[e["args"]["batch"]]
            assert e["args"]["id"] in b["args"]["members"]
            linked += 1
    assert linked >= 1

    # prove window id: read/dispatch/retire of one pass share it, and
    # every batch-level prove span carries one
    windows = {}
    for e in evs:
        if e["name"] in ("prove.read_wait", "prove.dispatch",
                         "prove.retire"):
            windows.setdefault(e["args"]["window"], set()).add(e["name"])
    assert windows, "no windowed prove spans captured"
    first = min(windows)
    assert windows[first] == {"prove.read_wait", "prove.dispatch",
                              "prove.retire"}

    # the prove spans parent into their window span
    wspans = {e["args"]["id"] for e in evs if e["name"] == "prove.window"}
    for e in evs:
        if e["name"] == "prove.dispatch":
            assert e["args"]["parent"] in wspans

    # writer-pool spans crossed the thread boundary with their parent
    # (the submit-side stall span, itself nested under init.fetch)
    writes = [e for e in evs if e["name"] == "init.write"]
    submit_side = {e["args"]["id"] for e in evs
                   if e["name"] in ("init.fetch", "init.write_stall")}
    assert writes and all(e["args"].get("parent") in submit_side
                          for e in writes)
