"""Streaming prove pipeline: reader pool, compacted hits, proof identity.

The prover-side mirror of test_post_pipeline.py: the pipelined scan must
produce bit-identical proofs to the legacy serial path over every backend
(XLA, Pallas-interpret, virtual mesh), read the store at most once per
nonce window, and keep the per-batch device->host traffic to compacted
hits instead of masks.
"""

import hashlib
import os
import threading
import time

import numpy as np
import pytest

from spacemesh_tpu.ops import proving, scrypt
from spacemesh_tpu.post import initializer
from spacemesh_tpu.post.data import LabelReader, LabelStore, PostMetadata
from spacemesh_tpu.post.prover import ProofParams, Prover
from spacemesh_tpu.utils import metrics

NODE = hashlib.sha256(b"pipe-node").digest()
COMMIT = hashlib.sha256(b"pipe-commit").digest()
CH = hashlib.sha256(b"pipe-challenge").digest()

PARAMS = ProofParams(k1=64, k2=16, k3=8,
                     pow_difficulty=bytes([255]) * 32)


@pytest.fixture(scope="module")
def unit(tmp_path_factory):
    d = tmp_path_factory.mktemp("prove-pipe")
    meta, _ = initializer.initialize(
        d, node_id=NODE, commitment=COMMIT, num_units=1,
        labels_per_unit=2048, scrypt_n=2, max_file_size=8192,
        batch_size=512)
    return d, meta


@pytest.fixture(scope="module")
def serial_proof(unit):
    d, _ = unit
    return Prover(d, PARAMS, batch_labels=512).prove_serial(CH)


# -- proof identity across backends -----------------------------------------


def test_pipelined_matches_serial(unit, serial_proof):
    d, _ = unit
    prover = Prover(d, PARAMS, batch_labels=512, pipelined=True)
    assert prover.prove(CH) == serial_proof
    assert prover.last_stats is not None
    assert prover.last_stats.batches > 0


def test_prover_traces_no_label_program(unit, serial_proof):
    # a prover never computes a label: building one, resolving where its
    # batches run and proving must not trace (let alone compile) either
    # label program — it asks the mesh rule, not the label kernel
    d, _ = unit
    before = scrypt.compiled_shape_count()
    prover = Prover(d, PARAMS, batch_labels=512)
    assert prover._resolve_mesh() is None  # the CPU rule: one device
    assert prover.prove(CH) == serial_proof
    assert scrypt.compiled_shape_count() == before


def test_wide_window_matches_serial(unit, serial_proof):
    # window spanning several nonce groups still picks the serial winner
    d, _ = unit
    prover = Prover(d, PARAMS, batch_labels=512, window_groups=4)
    assert prover.prove(CH) == serial_proof


def test_pallas_backend_matches_serial(unit, serial_proof):
    d, _ = unit
    prover = Prover(d, PARAMS, batch_labels=512, use_pallas=True)
    assert prover.prove(CH) == serial_proof


def test_sharded_backend_matches_serial(unit, serial_proof, monkeypatch):
    # conftest forces 8 virtual CPU devices; SPACEMESH_MESH=1 opts the
    # prover into lane sharding on them (as test_parallel does for init)
    d, _ = unit
    monkeypatch.setenv("SPACEMESH_MESH", "1")
    prover = Prover(d, PARAMS, batch_labels=512)
    assert prover._resolve_mesh() is not None
    assert prover.prove(CH) == serial_proof


@pytest.mark.parametrize("backend", ["pallas", "mesh"])
def test_wide_window_backends_match_serial(unit, serial_proof, monkeypatch,
                                           backend):
    # the window step over several nonce groups (one program a batch)
    # picks the serial winner on the other two backends too
    d, _ = unit
    kw = {"use_pallas": True, "mesh": None}
    if backend == "mesh":
        monkeypatch.setenv("SPACEMESH_MESH", "1")
        kw = {}
    prover = Prover(d, PARAMS, batch_labels=1024, window_groups=3, **kw)
    assert prover.scan_step()[2] == {"pallas": "pallas",
                                     "mesh": "xla-sharded"}[backend]
    assert prover.prove(CH) == serial_proof


def test_one_count_vector_a_batch_and_its_prefetch_is_counted(unit):
    # k1 < k2 here: no early exit, every dispatched batch retires
    d, meta = unit
    params = ProofParams(k1=8, k2=12, k3=8, pow_difficulty=bytes([255]) * 32)
    prover = Prover(d, params, batch_labels=512, window_groups=4)
    before = metrics.post_prove_d2h_bytes.sample().get((), 0.0)
    proof = prover.prove(CH)
    stats = prover.last_stats
    assert proof == Prover(d, params, batch_labels=512).prove_serial(CH)
    ng, groups = prover.nonce_group, prover.window_groups
    per_pass = meta.total_labels // prover.batch_labels
    assert stats.batches == stats.windows * per_pass
    # per retired batch ONE (groups * ng,) i32 vector; per deciding pass
    # the one state pair (counts + lo/hi carry of k2 slots a nonce)
    state = groups * ng * 4 + 2 * groups * ng * params.k2 * 4
    assert stats.d2h_bytes == stats.batches * groups * ng * 4 + state
    assert metrics.post_prove_d2h_bytes.sample().get((), 0.0) - before \
        == stats.d2h_bytes
    assert 0 <= stats.retire_ready <= stats.batches


def test_ragged_tail_single_shape(unit, serial_proof):
    # 2048 labels with batch 768: ragged 512-label tail is padded, not
    # recompiled or path-flipped; proof unchanged
    d, _ = unit
    prover = Prover(d, PARAMS, batch_labels=768)
    assert prover.batch_labels % proving.HIT_SEGMENT == 0
    assert prover.prove(CH) == serial_proof


# -- disk frugality + compacted D2H -----------------------------------------


def _read_bytes() -> float:
    return metrics.post_store_read_bytes._values.get((), 0.0)


def test_one_disk_pass_per_window(unit):
    d, meta = unit
    store_bytes = meta.total_labels * scrypt.LABEL_BYTES
    prover = Prover(d, PARAMS, batch_labels=512)
    before = _read_bytes()
    prover.prove(CH)
    stats = prover.last_stats
    read = _read_bytes() - before
    # at most one full store read per scanned nonce window (the reader may
    # have prefetched past an early exit by at most its queue depth)
    slack = prover.reader_queue * prover.batch_labels * scrypt.LABEL_BYTES
    assert read <= stats.windows * store_bytes + slack
    assert stats.windows >= 1


def test_early_exit_reads_less_than_store(unit):
    # k1=64 >> k2=16: nonce 0 qualifies after a fraction of the store, so
    # the sound early exit fires and the pass never reads the whole store
    d, meta = unit
    prover = Prover(d, PARAMS, batch_labels=256, inflight=1,
                    reader_queue=1)
    before = _read_bytes()
    proof = prover.prove(CH)
    read = _read_bytes() - before
    assert prover.last_stats.early_exited
    assert proof.nonce == 0
    assert read < meta.total_labels * scrypt.LABEL_BYTES


def test_d2h_is_compacted_hits_not_masks(unit):
    d, meta = unit
    prover = Prover(d, PARAMS, batch_labels=512)
    prover.prove(CH)
    stats = prover.last_stats
    # full masks would be nonce_group * batch bytes per batch; the
    # compacted path moves one count vector per batch plus one hit-pair
    # carry per pass
    mask_bytes = stats.batches * prover.nonce_group * prover.batch_labels
    assert stats.d2h_bytes < mask_bytes / 8
    assert stats.d2h_bytes > 0


# -- the compacted-scan step itself -----------------------------------------


def test_prove_step_accumulates_across_batches():
    import jax.numpy as jnp

    total, b, ng, cap = 1024, 512, 4, 8
    labels = scrypt.scrypt_labels(COMMIT, np.arange(total, dtype=np.uint64),
                                  n=2)
    t = proving.threshold_u32(24, total)
    cw = jnp.asarray(proving.challenge_words(CH))
    counts, carry = proving.init_hit_state(ng, cap)
    for start in range(0, total, b):
        idx = np.arange(start, start + b, dtype=np.uint64)
        lo, hi = scrypt.split_indices(idx)
        lw = scrypt.labels_to_words(labels[start:start + b])
        counts, _, carry = proving.prove_scan_step_jit(
            cw, jnp.uint32(0), jnp.asarray(lo), jnp.asarray(hi),
            jnp.asarray(lw), jnp.uint32(t), counts, carry,
            jnp.uint32(b), jnp.uint32(start), jnp.uint32(0),
            n_nonces=ng, max_hits=cap)
    counts_np = np.asarray(counts)
    for k in range(ng):
        vals = proving.proving_hashes(CH, k, np.arange(total, dtype=np.uint64),
                                      labels)
        want = np.nonzero(vals < t)[0]
        assert counts_np[k] == len(want)
        got = proving.decode_hits(counts, carry, k, cap)
        assert got == [int(i) for i in want[:cap]]


def test_prove_step_high_index_batches():
    # global label indices past 2^32: the u32 lo/hi split must carry
    import jax.numpy as jnp

    b, ng, cap = 256, 2, 8
    start = (1 << 32) - 128  # batch straddles the u32 boundary
    idx = np.arange(start, start + b, dtype=np.uint64)
    labels = scrypt.scrypt_labels(COMMIT, idx, n=2)
    t = proving.threshold_u32(32, b)
    cw = jnp.asarray(proving.challenge_words(CH))
    counts, carry = proving.init_hit_state(ng, cap)
    lo, hi = scrypt.split_indices(idx)
    lw = scrypt.labels_to_words(labels)
    counts, _, carry = proving.prove_scan_step_jit(
        cw, jnp.uint32(0), jnp.asarray(lo), jnp.asarray(hi),
        jnp.asarray(lw), jnp.uint32(t), counts, carry, jnp.uint32(b),
        jnp.uint32(start & 0xFFFFFFFF), jnp.uint32(start >> 32),
        n_nonces=ng, max_hits=cap)
    for k in range(ng):
        vals = proving.proving_hashes(CH, k, idx, labels)
        want = [int(start + i) for i in np.nonzero(vals < t)[0][:cap]]
        assert proving.decode_hits(counts, carry, k, cap) == want


# -- LabelReader pool --------------------------------------------------------


def _tiny_store(tmp_path, labels=512):
    meta = PostMetadata(node_id=NODE.hex(), commitment=COMMIT.hex(),
                        scrypt_n=2, num_units=1, labels_per_unit=labels,
                        max_file_size=1 << 20, labels_written=labels)
    store = LabelStore(tmp_path, meta)
    data = bytes(range(256)) * (labels * scrypt.LABEL_BYTES // 256)
    store.write_labels(0, data)
    return store, data


def test_reader_delivers_in_plan_order(tmp_path):
    store, data = _tiny_store(tmp_path)
    ranges = [(i * 64, 64) for i in range(8)]
    reader = store.start_reader(ranges, threads=3, depth=2)
    try:
        for start, count in ranges:
            lb = scrypt.LABEL_BYTES
            assert reader.get() == data[start * lb:(start + count) * lb]
    finally:
        reader.close()
    assert reader.bytes_read == len(data)


def test_reader_bounded_readahead(tmp_path):
    store, _ = _tiny_store(tmp_path)
    ranges = [(i * 32, 32) for i in range(16)]
    reader = store.start_reader(ranges, threads=2, depth=3)
    try:
        time.sleep(0.2)  # let the pool run ahead as far as it is allowed
        with reader._cond:
            buffered = len(reader._results)
        assert buffered <= 3
        for _ in ranges:
            reader.get()
    finally:
        reader.close()


def test_reader_error_propagates(tmp_path):
    store, data = _tiny_store(tmp_path)
    ranges = [(0, 32), (100000, 32)]  # second range is past EOF
    reader = store.start_reader(ranges, threads=1, depth=2)
    try:
        time.sleep(0.3)  # let the pool buffer slot 0 AND fail slot 1
        # an in-order result buffered before the failure still delivers;
        # the error surfaces on the range that is actually missing
        assert reader.get() == data[:32 * 16]
        with pytest.raises(RuntimeError, match="label reader failed"):
            reader.get()
    finally:
        reader.close()


def test_reader_close_mid_plan(tmp_path):
    store, _ = _tiny_store(tmp_path)
    ranges = [(i * 16, 16) for i in range(32)]
    reader = store.start_reader(ranges, threads=2, depth=2)
    reader.get()
    reader.close()  # early exit: pending reads dropped, no hang
    assert all(not t.is_alive() for t in reader._threads)


def test_read_fds_cached(tmp_path):
    store, data = _tiny_store(tmp_path)
    for _ in range(5):
        assert store.read_labels(10, 4) == data[10 * 16:14 * 16]
    assert len(store._read_fds) == 1
    store.close()
    assert not store._read_fds
    # reads reopen transparently after close
    assert store.read_labels(0, 2) == data[:32]
    store.close()


def test_read_fds_thread_safe(tmp_path):
    store, data = _tiny_store(tmp_path)
    errs = []

    def hammer():
        try:
            for i in range(50):
                assert store.read_labels(i % 32, 8) \
                    == data[(i % 32) * 16:((i % 32) + 8) * 16]
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    store.close()


# -- knob plumbing -----------------------------------------------------------


def test_env_knobs(unit, monkeypatch):
    d, _ = unit
    monkeypatch.setenv("SPACEMESH_PROVE_PIPELINE", "off")
    monkeypatch.setenv("SPACEMESH_PROVE_WINDOW_GROUPS", "3")
    monkeypatch.setenv("SPACEMESH_PROVE_INFLIGHT", "5")
    monkeypatch.setenv("SPACEMESH_PROVE_READERS", "4")
    monkeypatch.setenv("SPACEMESH_PROVE_QUEUE", "7")
    p = Prover(d, PARAMS)
    assert not p.pipelined
    assert (p.window_groups, p.inflight, p.readers, p.reader_queue) \
        == (3, 5, 4, 7)
    # explicit args beat the environment
    p = Prover(d, PARAMS, pipelined=True, window_groups=1, inflight=2,
               readers=1, reader_queue=2)
    assert p.pipelined
    assert (p.window_groups, p.inflight, p.readers, p.reader_queue) \
        == (1, 2, 1, 2)


def test_post_client_prove_opts(unit):
    from spacemesh_tpu.post.service import PostClient

    d, meta = unit
    client = PostClient(d, PARAMS, batch_labels=512, pipelined=False)
    proof, got_meta = client.proof(CH)
    assert got_meta.total_labels == meta.total_labels
    assert proof == Prover(d, PARAMS, batch_labels=512).prove(CH)
