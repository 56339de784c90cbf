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
from spacemesh_tpu.post import prover as prover_mod
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


def _little_store(tmp_path_factory, labels):
    d = tmp_path_factory.mktemp(f"prove-{labels}")
    meta, _ = initializer.initialize(
        d, node_id=NODE, commitment=COMMIT, num_units=1,
        labels_per_unit=labels, scrypt_n=2, max_file_size=8192,
        batch_size=256)
    return d, meta


@pytest.fixture(scope="module")
def long_unit(tmp_path_factory):
    # 128 batches of 64 labels = 16 flights of eight
    return _little_store(tmp_path_factory, 8192)


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


@pytest.mark.parametrize("batch, flight", [(512, 4), (128, 8)])
def test_one_count_vector_a_batch_and_its_prefetch_is_counted(unit, batch,
                                                              flight):
    # k1 < k2 here: no early exit, every dispatched flight retires. The
    # 2,048-label store is ONE flight of its four 512-label batches, or
    # two flights of eight 128-label ones
    d, meta = unit
    params = ProofParams(k1=8, k2=12, k3=8, pow_difficulty=bytes([255]) * 32)
    prover = Prover(d, params, batch_labels=batch, window_groups=4)
    assert prover.flight_batches(None) == flight
    before = metrics.post_prove_d2h_bytes.sample().get((), 0.0)
    flights0 = metrics.post_prove_flights.sample().get((), 0.0)
    batches0 = metrics.post_prove_batches.sample().get((), 0.0)
    proof = prover.prove(CH)
    stats = prover.last_stats
    assert proof == Prover(d, params, batch_labels=512).prove_serial(CH)
    ng, groups = prover.nonce_group, prover.window_groups
    per_pass = meta.total_labels // prover.batch_labels
    # batches keep counting batch_labels-wide scan steps (what the
    # liveness watchdog watches); flights count device calls
    assert stats.batches == stats.windows * per_pass
    assert stats.flights == stats.windows * per_pass // flight
    assert stats.batches / stats.flights == flight    # the mean fill
    assert metrics.post_prove_flights.sample()[()] - flights0 \
        == stats.flights
    assert metrics.post_prove_batches.sample()[()] - batches0 \
        == stats.batches
    # per retired FLIGHT one (groups * ng,) i32 vector; per deciding pass
    # the one state pair (counts + lo/hi carry of k2 slots a nonce)
    state = groups * ng * 4 + 2 * groups * ng * params.k2 * 4
    assert stats.d2h_bytes == stats.flights * groups * ng * 4 + state
    assert metrics.post_prove_d2h_bytes.sample().get((), 0.0) - before \
        == stats.d2h_bytes
    assert 0 <= stats.retire_ready <= stats.flights


@pytest.mark.parametrize("labels, batches, flight, flights", [
    (64, 1, 1, 1),          # a one-batch store runs the one-batch program
    (3 * 64 - 24, 3, 4, 1),  # ragged: three of the flight's four steps
    (8 * 64, 8, 8, 1),
    (11 * 64 - 24, 11, 8, 2),   # a full flight and a ragged one
])
def test_flights_of_every_fill_match_serial(tmp_path_factory, labels,
                                            batches, flight, flights):
    # k1 < k2: a nonce wins rarely, so passes run to the store's end
    d, _ = _little_store(tmp_path_factory, labels)
    params = ProofParams(k1=10, k2=12, k3=8,
                         pow_difficulty=bytes([255]) * 32)
    prover = Prover(d, params, batch_labels=64, nonce_group=8,
                    use_pallas=False, mesh=None)
    assert prover.flight_batches(None) == flight
    proof = prover.prove(CH)
    assert proof == Prover(d, params, batch_labels=64, nonce_group=8,
                           use_pallas=False).prove_serial(CH)
    stats = prover.last_stats
    if not stats.early_exited:
        assert stats.batches == stats.windows * batches
        assert stats.flights == stats.windows * flights
        assert stats.labels_swept == stats.windows * labels


def test_the_warmer_warms_the_flight_program_a_prover_runs(long_unit):
    # a warmer that warms another shape costs a compile inside somebody's
    # first proof: after runtime/workloads' prove_scan recipe (what
    # tools/warmcache runs) a default-shaped prover over a store of
    # eight batches or more compiles no window step of its own
    from spacemesh_tpu.runtime import workloads

    d, _ = long_unit
    doc = workloads.get("prove_scan").warm(0, 64)
    assert (doc["batch"], doc["flight_batches"], doc["pallas"]) \
        == (64, prover_mod.FLIGHT_BATCHES, False)
    warmed = proving.prove_scan_step_window._cache_size()
    params = ProofParams(k1=60, pow_difficulty=bytes([255]) * 32)
    prover = Prover(d, params, batch_labels=64, mesh=None)
    assert (prover.nonce_group, prover.window_groups, params.k2) \
        == (doc["nonce_group"], doc["groups"], 37)
    assert prover.flight_batches(None) == doc["flight_batches"]
    assert prover.prove(CH).k2 == 37
    assert proving.prove_scan_step_window._cache_size() == warmed


def test_a_mesh_flight_is_one_batch(unit, serial_proof, monkeypatch):
    # lane-sharding a flight would cut its sub-batches across devices:
    # on a mesh the unit that crosses stays one batch
    d, _ = unit
    monkeypatch.setenv("SPACEMESH_MESH", "1")
    prover = Prover(d, PARAMS, batch_labels=256)
    _step, mesh, impl = prover.scan_step()
    assert impl == "xla-sharded" and mesh is not None
    assert prover.flight_batches(mesh) == 1
    assert prover.flight_batches(None) == 8
    assert prover.prove(CH) == serial_proof
    stats = prover.last_stats
    assert stats.flights == stats.batches > 0


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


def test_early_exit_reads_less_than_store(long_unit):
    # k1=64 >> k2=16: nonce 0 qualifies after a fraction of the store, so
    # the sound early exit fires and the pass never reads the whole store
    # (16 flights of 8 x 64 labels)
    d, meta = long_unit
    prover = Prover(d, PARAMS, batch_labels=64, inflight=1,
                    reader_queue=1)
    before = _read_bytes()
    proof = prover.prove(CH)
    read = _read_bytes() - before
    assert prover.last_stats.early_exited
    assert proof.nonce == 0
    assert read < meta.total_labels * scrypt.LABEL_BYTES


def test_early_exit_decided_in_mid_flight(long_unit):
    # the winner's K2-th hit lies in a middle scan step of its flight:
    # the rule is asked once a flight, with scanned_end the flight's
    # end, and the proof is the serial scan's all the same (the winner's
    # indices are the first K2 slots of the carry wherever the pass
    # stopped)
    d, meta = long_unit
    b, f = 64, 8 * 64
    prover = Prover(d, PARAMS, batch_labels=b)
    proof = prover.prove(CH)
    assert proof == Prover(d, PARAMS, batch_labels=b).prove_serial(CH)
    stats = prover.last_stats
    assert stats.early_exited and stats.windows == 1
    decided = proof.indices[-1]
    assert (decided % f) // b < 7, "pick a challenge that decides mid-flight"
    flight_end = (decided // f + 1) * f
    assert flight_end <= stats.labels_swept < meta.total_labels
    assert stats.labels_swept % f == 0
    assert stats.batches == 8 * stats.flights


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


def _masks():
    """{case: (mask, max_hits)} for compact_hits against numpy: every
    place a segment search can land, and the cell's own shape."""
    rng = np.random.default_rng(37)
    seg = proving.HIT_SEGMENT
    some = rng.random((16, 1024)) < 0.02
    exactly = np.zeros((16, 1024), bool)
    for r in range(16):
        exactly[r, rng.choice(1024, 8 + r % 2, replace=False)] = True
    last = np.zeros((16, 1024), bool)
    last[::3, -1] = True
    straddle = np.zeros((16, 1024), bool)
    for r in range(16):     # runs across each segment boundary
        at = seg * (1 + r % 15)
        straddle[r, at - 1 - r % 3:at + 2 + r % 4] = True
    # one segment holding the whole batch
    one_seg = rng.random((16, seg)) < 0.12
    one_seg[0], one_seg[1] = False, True
    # the cell's scan step: 64 nonce rows x 16,384 lanes at 26 hits a
    # nonce over 2^23 labels, and at ~60 hits a row (past max_hits)
    p_cell = proving.threshold_u32(26, 1 << 23) / 2.0 ** 32
    cell = rng.random((64, 16384)) < p_cell
    busy = rng.random((64, 16384)) < 60 / 16384
    return {
        "rows_with_no_hits": (some * (np.arange(16) % 2)[:, None] > 0, 8),
        "no_hits_at_all": (np.zeros((16, 1024), bool), 8),
        "max_hits_and_one_more": (exactly, 8),
        "every_lane": (np.ones((16, 1024), bool), 8),
        "last_lane_of_last_segment": (last, 8),
        "straddling_segment_boundaries": (straddle, 4),
        "one_segment": (one_seg, 8),
        "cell_step_at_cell_rate": (cell, 37),
        "cell_step_past_max_hits": (busy, 37),
    }


@pytest.mark.parametrize("case", list(_masks()))
def test_compact_hits_matches_numpy(case):
    # compact_hits alone against np.nonzero, row by row: every program
    # equivalence test compares two programs that share it, so this is
    # the test a wrong segment search would fail
    import functools

    import jax

    mask, max_hits = _masks()[case]
    counts, pos, ok = (np.asarray(x) for x in jax.jit(functools.partial(
        proving.compact_hits, max_hits=max_hits))(mask))
    assert counts.dtype == np.int32 and pos.dtype == np.uint32
    assert np.array_equal(counts, mask.sum(axis=1))
    for r, row in enumerate(mask):
        want = np.nonzero(row)[0][:max_hits]
        assert np.array_equal(pos[r][ok[r]], want), r
        assert np.array_equal(ok[r], np.arange(max_hits) < len(want)), r
    if case == "max_hits_and_one_more":
        assert set(counts) == {max_hits, max_hits + 1}
    if case == "cell_step_at_cell_rate":
        assert 0 < counts.sum() and counts.max() < max_hits
    if case == "cell_step_past_max_hits":
        assert counts.max() > max_hits


# (the flight program against the per-group reference step, sub-batch by
# sub-batch, ragged, full and across 2^32, on both backends:
# tests/test_proving_pallas.py test_window_step_equals_per_group_steps)


def test_a_flight_is_a_whole_number_of_scan_steps():
    import jax.numpy as jnp

    with pytest.raises(ValueError, match="whole number"):
        proving.prove_scan_step_window(
            jnp.zeros(8, jnp.uint32), jnp.zeros(1, jnp.uint32),
            jnp.zeros((4, 192), jnp.uint32), jnp.zeros(3, jnp.uint32),
            jnp.uint32(1), *proving.init_hit_state(4, 8), n_nonces=4,
            max_hits=8, batch=128)


@pytest.mark.parametrize("total, batch, mesh, want", [
    (4096, 16384, None, 1), (2 * 16384, 16384, None, 2),
    (3 * 16384, 16384, None, 4), (5 * 16384 - 1, 16384, None, 8),
    (1 << 23, 16384, None, 8), (1 << 34, 16384, None, 8),
    (1 << 23, 16384, object(), 1)])
def test_flight_batches_adapts_to_the_store(total, batch, mesh, want):
    assert prover_mod.FLIGHT_BATCHES == 8
    assert prover_mod.flight_batches(total, batch, mesh) == want


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
