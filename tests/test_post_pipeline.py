"""Streaming init pipeline: smoke, crash consistency, stop latency,
writer-pool durability ordering.

The crash-consistency tests are the contract behind interval metadata
saves (docs/POST_PIPELINE.md): kill the pipeline at various points, and a
resume from whatever metadata survived must converge to a byte-identical
label set and the same VRF nonce as an uninterrupted init — because the
persisted cursor never runs ahead of durably-written labels and the VRF
min-merge is idempotent over recomputed batches.
"""

import hashlib
import threading
import time

import numpy as np
import pytest

from spacemesh_tpu.ops import scrypt
from spacemesh_tpu.post import initializer
from spacemesh_tpu.post.data import LabelStore, PostMetadata
from spacemesh_tpu.utils import metrics

NODE = hashlib.sha256(b"pipe-node").digest()
COMMIT = hashlib.sha256(b"pipe-commitment").digest()

TOTAL = 1024
BATCH = 256
N = 2


def _init_kwargs(**over):
    kw = dict(node_id=NODE, commitment=COMMIT, num_units=1,
              labels_per_unit=TOTAL, scrypt_n=N, max_file_size=1 << 20,
              batch_size=BATCH)
    kw.update(over)
    return kw


def _disk_labels(d, count):
    meta = PostMetadata.load(d)
    store = LabelStore(d, meta)
    return store.read_labels(0, count)


@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    """Uninterrupted init: the ground truth for crash/resume equivalence."""
    d = tmp_path_factory.mktemp("pipe-ref")
    meta, res = initializer.initialize(d, **_init_kwargs())
    return d, meta, res


def test_pipeline_smoke(reference):
    d, meta, res = reference
    assert meta.labels_written == TOTAL
    assert res.labels_per_s > 0
    assert res.stats is not None and res.stats.batches == TOTAL // BATCH
    got = np.frombuffer(_disk_labels(d, TOTAL), dtype=np.uint8)
    want = scrypt.scrypt_labels(COMMIT, np.arange(TOTAL, dtype=np.uint64),
                                n=N)
    assert np.array_equal(got.reshape(-1, 16), want)
    # VRF nonce: first occurrence of the LE-u128 minimum, like np.lexsort
    lo = want[:, :8].copy().view("<u8").ravel()
    hi = want[:, 8:].copy().view("<u8").ravel()
    k = int(np.lexsort((lo, hi))[0])
    assert meta.vrf_nonce == k
    assert bytes.fromhex(meta.vrf_nonce_value) == bytes(want[k])


def test_pipeline_exports_metrics(reference):
    text = metrics.REGISTRY.expose()
    assert "post_pipeline_batches_dispatched_total" in text
    assert "post_pipeline_stage_seconds_total" in text
    assert "post_pipeline_meta_saves_total" in text


class _Crash(RuntimeError):
    pass


@pytest.mark.parametrize("crash_after", [0, 1, 2])
def test_crash_resume_bit_identical(tmp_path, reference, crash_after):
    """Kill the run after N flushed batches (no orderly shutdown, no final
    metadata save); the resume must produce bit-identical labels and the
    same VRF nonce as the uninterrupted reference."""
    _, ref_meta, _ = reference
    calls = []

    def die(done, total):
        calls.append(done)
        if len(calls) > crash_after:
            raise _Crash

    with pytest.raises(_Crash):
        initializer.initialize(
            tmp_path, **_init_kwargs(progress=die),
            meta_interval_s=0.0, meta_interval_labels=1)

    # durability ordering: whatever cursor survived must be backed by
    # readable bytes on disk
    try:
        meta = PostMetadata.load(tmp_path)
    except FileNotFoundError:
        meta = None
    if meta is not None and meta.labels_written > 0:
        assert meta.labels_written < TOTAL
        got = _disk_labels(tmp_path, meta.labels_written)
        want = scrypt.scrypt_labels(
            COMMIT, np.arange(meta.labels_written, dtype=np.uint64), n=N)
        assert got == want.tobytes()

    meta2, _ = initializer.initialize(tmp_path, **_init_kwargs())
    assert meta2.labels_written == TOTAL
    assert meta2.vrf_nonce == ref_meta.vrf_nonce
    assert meta2.vrf_nonce_value == ref_meta.vrf_nonce_value
    assert _disk_labels(tmp_path, TOTAL) == _disk_labels(
        reference[0], TOTAL)


def test_crash_in_writer_surfaces_and_resumes(tmp_path, reference):
    """A failing disk write must fail the run (not hang it), leave a
    conservative cursor, and still resume cleanly."""
    _, ref_meta, _ = reference
    real = LabelStore.write_labels
    hits = []

    def flaky(self, start, labels):
        hits.append(start)
        if len(hits) > 2:
            raise IOError("disk full (injected)")
        real(self, start, labels)

    from unittest import mock
    with mock.patch.object(LabelStore, "write_labels", flaky):
        with pytest.raises(RuntimeError, match="writer failed"):
            initializer.initialize(
                tmp_path, **_init_kwargs(),
                meta_interval_s=0.0, meta_interval_labels=1)

    meta2, _ = initializer.initialize(tmp_path, **_init_kwargs())
    assert meta2.labels_written == TOTAL
    assert meta2.vrf_nonce == ref_meta.vrf_nonce
    assert _disk_labels(tmp_path, TOTAL) == _disk_labels(
        reference[0], TOTAL)


def test_stop_before_dispatch_persists_cursor(tmp_path):
    """stop() must take effect before the next batch is dispatched, and
    the discarded-pending path must still persist the flushed cursor."""
    meta = PostMetadata(node_id=NODE.hex(), commitment=COMMIT.hex(),
                        scrypt_n=N, num_units=1, labels_per_unit=TOTAL,
                        max_file_size=1 << 20)
    dispatched = []
    init = initializer.Initializer(
        tmp_path, meta, batch_size=BATCH, inflight=3,  # pin: assertions
        # below assume the window fills before the run drains
        progress=lambda done, total: (dispatched.append(done),
                                      init.stop()))
    init.run()
    assert init.status == initializer.Status.STOPPED
    # stop fired on the first flushed batch: later batches may already be
    # in flight, but nothing further was dispatched after the stop check
    assert dispatched == [BATCH]
    on_disk = PostMetadata.load(tmp_path)
    assert on_disk.labels_written == BATCH
    got = _disk_labels(tmp_path, BATCH)
    want = scrypt.scrypt_labels(COMMIT, np.arange(BATCH, dtype=np.uint64),
                                n=N)
    assert got == want.tobytes()


def test_writer_durable_cursor_is_contiguous(tmp_path):
    """durable() only advances over contiguous completed writes, even when
    pool threads complete out of order."""
    meta = PostMetadata(node_id=NODE.hex(), commitment=COMMIT.hex(),
                        scrypt_n=N, num_units=1, labels_per_unit=TOTAL,
                        max_file_size=1 << 20)
    store = LabelStore(tmp_path, meta)
    gate = threading.Event()
    real = LabelStore.write_labels

    def gated(self, start, labels):
        if start == 0:
            assert gate.wait(10)
        real(self, start, labels)

    from unittest import mock
    with mock.patch.object(LabelStore, "write_labels", gated):
        w = store.start_writer(threads=2, queue_depth=4)
        try:
            w.submit(0, bytes(BATCH * 16))
            w.submit(BATCH, bytes(BATCH * 16))
            deadline = time.monotonic() + 10
            while w.bytes_written < BATCH * 16:
                assert time.monotonic() < deadline
                time.sleep(0.01)
            # second interval done, first still gated: cursor must hold
            assert w.durable() == 0
            gate.set()
            w.drain()
            assert w.durable() == 2 * BATCH
        finally:
            gate.set()
            w.close(drain=False)


def test_interval_metadata_saves_happen_midrun(tmp_path):
    """With a tiny interval, resume metadata is rewritten during the run,
    not only at the end — and any mid-run cursor respects the durability
    rule (it can trail the dispatch frontier, never lead the disk)."""
    seen = []

    def peek(done, total):
        if done == TOTAL:  # retiring the last batch: earlier interval
            # saves must already be on disk, final save has not happened
            m = PostMetadata.load(tmp_path)
            seen.append(m.labels_written)
            assert m.labels_written < TOTAL
            if m.labels_written:
                assert _disk_labels(tmp_path, m.labels_written)

    meta, res = initializer.initialize(
        tmp_path, **_init_kwargs(progress=peek),
        meta_interval_s=0.0, meta_interval_labels=1)
    assert seen, "progress callback never fired for the last batch"
    assert res.stats is not None and res.stats.meta_saves >= 2
    assert meta.labels_written == TOTAL


def test_profiler_pipeline_hook(capsys):
    """tools/profiler --pipeline: per-stage timings of a real streaming
    init, runnable without a full profile (tier-1 smoke for the hook;
    the CLI-level twin lives in test_tools_cli.py)."""
    import json

    from spacemesh_tpu.tools import profiler

    doc = profiler.pipeline_benchmark(2, 512, 256)
    json.dumps(doc)  # must be JSON-serializable
    assert doc["labels_per_sec"] > 0
    assert set(doc["stages"]) >= {"dispatch_s", "fetch_s",
                                  "write_stall_s", "write_s"}
    assert doc["stages"]["batches"] == 2
    assert doc["bottleneck"] in ("dispatch_s", "fetch_s", "write_stall_s")
