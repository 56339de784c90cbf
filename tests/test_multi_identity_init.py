"""Many identities through one tenant scheduler: the ``--identities``
form of ``python -m spacemesh_tpu.post init`` (``post/initializer.
initialize_many``), per-tenant durable checkpoints under the
single-identity rule (``post/initializer.save_checkpoint``), a crash
after a checkpoint, and the pack width the scheduler takes from the chip
(``runtime/scheduler.default_pack_lanes``).

The reference is independent of ``ops/scrypt``: ``hashlib.scrypt``
labels and a numpy argmin over the labels as little-endian u128 (the
earliest index on ties)."""

import hashlib
import json
import os
import threading

import numpy as np
import pytest

from spacemesh_tpu.post import faultfs, initializer
from spacemesh_tpu.post.data import LabelStore, PostMetadata

N = 16
FILE_BYTES = 2048          # 128 labels a file


def _ident(i, salt=b""):
    return (hashlib.sha256(b"mi-node-%d" % i + salt).digest(),
            hashlib.sha256(b"mi-commit-%d" % i + salt).digest())


def _labels(commitment, lo, hi, n=N):
    return b"".join(hashlib.scrypt(commitment, salt=i.to_bytes(8, "little"),
                                   n=n, r=1, p=1, dklen=16)
                    for i in range(lo, hi))


def _argmin(raw):
    halves = np.frombuffer(raw, dtype="<u8").reshape(-1, 2)
    return int(np.lexsort((np.arange(len(halves)),
                           halves[:, 0], halves[:, 1]))[0])


def _store(d):
    meta = PostMetadata.load(d)
    store = LabelStore(d, meta)
    try:
        return meta, store.read_labels(0, meta.labels_written)
    finally:
        store.close()


def _partial(d, node, commit, total, stop_at):
    """A solo Initializer session stopped once ``stop_at`` labels were
    fetched: the durable cursor it leaves lies at or below that."""
    meta = initializer.open_or_create_meta(
        d, node_id=node, commitment=commit, num_units=1,
        labels_per_unit=total, scrypt_n=N, max_file_size=FILE_BYTES)
    init = initializer.Initializer(d, meta, batch_size=64, mesh=None,
                                   save_barrier=True, meta_interval_s=1e9,
                                   meta_interval_labels=64)
    init.progress = lambda done, _total: done >= stop_at and init.stop()
    init.run()
    return meta.labels_written


def test_identities_entry_point_matches_reference_and_solo_init(
        tmp_path, capsys):
    """Four identities, three of them already part-initialized by the
    solo path and one complete, so the scheduler resumes each at a
    ragged cursor: every store equals hashlib.scrypt under ITS OWN
    commitment, its cursor is the total, its VRF nonce the numpy
    argmin, and each is byte-identical to ``init`` run alone."""
    from spacemesh_tpu.post.__main__ import main

    total = 300
    stops = [None, 64, 192, total]
    ids = []
    for i, stop_at in enumerate(stops):
        node, commit = _ident(i)
        d = tmp_path / "many" / f"id{i}"
        if stop_at is not None:
            got = _partial(d, node, commit, total, stop_at)
            assert 0 < got <= stop_at
        ids.append({"data_dir": str(d), "node_id": node.hex(),
                    "commitment": commit.hex()})
    f = tmp_path / "ids.json"
    f.write_text(json.dumps(ids))
    geometry = ["--num-units", "1", "--labels-per-unit", str(total),
                "--scrypt-n", str(N), "--max-file-size", str(FILE_BYTES)]
    assert main(["init", "--identities", str(f)] + geometry) == 0
    out = [json.loads(line) for line in
           capsys.readouterr().out.strip().splitlines()]
    assert [o["data_dir"] for o in out] == [e["data_dir"] for e in ids]
    for i, (entry, line) in enumerate(zip(ids, out)):
        commit = bytes.fromhex(entry["commitment"])
        meta, raw = _store(entry["data_dir"])
        want = _labels(commit, 0, total)
        assert raw == want, f"identity {i}: labels diverge"
        other = bytes.fromhex(ids[(i + 1) % len(ids)]["commitment"])
        assert raw[:16] != _labels(other, 0, 1)
        assert meta.labels_written == total == line["labels_written"]
        assert meta.vrf_nonce == _argmin(want) == line["vrf_nonce"]
        assert meta.intervals[-1][0] == total
        solo = tmp_path / "solo" / f"id{i}"
        assert main(["init", "--data-dir", str(solo),
                     "--node-id-hex", entry["node_id"],
                     "--commitment-hex", entry["commitment"]]
                    + geometry) == 0
        solo_meta, solo_raw = _store(solo)
        assert solo_raw == raw and solo_meta.vrf_nonce == meta.vrf_nonce
        assert solo_meta.vrf_nonce_value == meta.vrf_nonce_value


def test_identities_form_refuses_single_identity_flags(tmp_path):
    from spacemesh_tpu.post.__main__ import main

    f = tmp_path / "ids.json"
    f.write_text("[]")
    with pytest.raises(SystemExit):
        main(["init", "--identities", str(f), "--data-dir", "x",
              "--num-units", "1", "--labels-per-unit", "8"])
    with pytest.raises(SystemExit):
        main(["init", "--num-units", "1", "--labels-per-unit", "8"])


class _MetaWatch(faultfs.FaultFS):
    """Counts every metadata save as it is published and checks it
    against the bytes the shim holds as fsynced: ``cursors`` maps a data
    directory to the ``labels_written`` of each save, ``ahead`` lists
    saves whose cursor ran past the fsynced label prefix. With
    ``cut_after`` set, the first label write after that many saves
    fails as a power cut."""

    def __init__(self, cut_after=None):
        super().__init__()
        self.cursors = {}
        self.ahead = []
        self.cut_after = cut_after
        self.saves = 0

    def _fsynced_labels(self, d):
        n, i = 0, 0
        while True:
            image = self._durable.get(os.path.join(d, f"postdata_{i}.bin"))
            if not image:
                return n
            n += len(image) // 16
            if len(image) < FILE_BYTES:
                return n
            i += 1

    def replace(self, src, dst):
        if str(dst).endswith("postdata_metadata.json"):
            doc = json.loads(open(src).read())
            d = os.path.dirname(os.path.abspath(str(dst)))
            with self._lock:
                fsynced = self._fsynced_labels(d)
                self.saves += 1
            self.cursors.setdefault(d, []).append(doc["labels_written"])
            if doc["labels_written"] > fsynced:
                self.ahead.append((d, doc["labels_written"], fsynced))
        super().replace(src, dst)

    def pwrite(self, fd, data, offset):
        if self.cut_after is not None and self.saves >= self.cut_after:
            self.cut_after = None
            raise faultfs.PowerCut(f"power cut after {self.saves} saves")
        return super().pwrite(fd, data, offset)


@pytest.fixture
def every_pack(monkeypatch):
    """Packed jobs checkpoint at every retired pack of 128 lanes (the
    single-identity cadence the scheduler takes, cut to this size)."""
    monkeypatch.setattr(initializer, "DEFAULT_META_INTERVAL_S", 1e9)
    monkeypatch.setattr(initializer, "DEFAULT_META_INTERVAL_LABELS", 128)


def _many(tmp_path, count, total, **kw):
    ids = [(tmp_path / f"id{i}",) + _ident(i, b"ckpt") for i in range(count)]
    return ids, initializer.initialize_many(
        ids, num_units=1, labels_per_unit=total, scrypt_n=N,
        max_file_size=FILE_BYTES, pack_lanes=128, **kw)


def test_packed_jobs_checkpoint_during_the_run_never_ahead_of_fsync(
        tmp_path, every_pack):
    """Each tenant's metadata is saved while the run goes on (the
    cursor advances through 128, 256, ... before the final save), and
    no save names a label the shim does not hold as fsynced."""
    fs = _MetaWatch()
    total = 640
    ids, res = _many(tmp_path, 3, total, fs=fs)
    assert res.tenants == 3 and res.pack_lanes == 128
    assert res.widest_pack == 128
    assert not fs.ahead, fs.ahead
    for (d, node, commit), meta in zip(ids, res.metas):
        seen = fs.cursors[str(d)]
        assert [c for c in seen if 0 < c < total], \
            f"{d}: no checkpoint before the last ({seen})"
        assert seen == sorted(seen) and seen[-1] == total
        assert meta.labels_written == total
        assert [iv[0] for iv in meta.intervals] == sorted(set(seen) - {0})
        raw = _store(d)[1]
        assert raw == _labels(commit, 0, total)
        assert meta.vrf_nonce == _argmin(raw)


# the power cut ends the scheduler's packer thread, as a real one ends
# the process: the thread's exception is the point, not a leak
@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_crash_after_a_checkpoint_resumes_every_tenant_at_its_cursor(
        tmp_path, every_pack):
    """A power cut after some tenants have checkpointed: after the
    reboot every tenant reopens at the cursor its last durable save
    names (recovery rolls nothing back), and a second run ends with
    every store byte-identical to the reference."""
    fs = _MetaWatch(cut_after=3)
    total = 512
    with pytest.raises(faultfs.PowerCut):
        _many(tmp_path, 3, total, fs=fs)
    fs.reboot()
    ids = [(tmp_path / f"id{i}",) + _ident(i, b"ckpt") for i in range(3)]
    saved = []
    for d, node, commit in ids:
        path = d / "postdata_metadata.json"
        cursor = (json.loads(path.read_text())["labels_written"]
                  if path.exists() else 0)
        saved.append(cursor)
        meta = initializer.open_or_create_meta(
            d, node_id=node, commitment=commit, num_units=1,
            labels_per_unit=total, scrypt_n=N, max_file_size=FILE_BYTES)
        assert meta.labels_written == cursor, (d, cursor)
    assert any(0 < c < total for c in saved), saved
    first = {}
    res = initializer.initialize_many(
        ids, num_units=1, labels_per_unit=total, scrypt_n=N,
        max_file_size=FILE_BYTES, pack_lanes=128,
        progress=lambda i, done, _t: first.setdefault(i, done))
    for i, ((d, node, commit), meta) in enumerate(zip(ids, res.metas)):
        # the first segment retired after the reopen starts at the cursor
        assert first[i] - saved[i] in (128, total - saved[i]), \
            (i, first[i], saved[i])
        raw = _store(d)[1]
        assert raw == _labels(commit, 0, total)
        assert meta.labels_written == total
        assert meta.vrf_nonce == _argmin(raw)


def test_stop_cancels_every_job_and_keeps_what_is_durable(
        tmp_path, every_pack):
    """Setting ``stop`` cancels each tenant; each checkpoints its
    durable prefix (a stop, not a crash) and nothing past it is
    claimed."""
    stop = threading.Event()
    total = 4096

    def progress(i, done, _total):
        if done >= 256:
            stop.set()

    ids, res = _many(tmp_path, 2, total, progress=progress, stop=stop)
    for (d, node, commit), meta in zip(ids, res.metas):
        assert 0 <= meta.labels_written < total
        raw = _store(d)[1]
        assert raw == _labels(commit, 0, meta.labels_written)


def test_default_pack_width_is_the_lane_ceiling_times_devices(monkeypatch):
    """No ``pack_lanes``: a pack is ``ops/scrypt.lane_ceiling(n)`` wide
    on each device ``auto_mesh`` would shard it over (8,192 lanes at
    N=8192 on a device that reports no memory, as a v5e chip); an
    explicit width is kept (bucketed)."""
    import jax

    from spacemesh_tpu.ops import scrypt
    from spacemesh_tpu.runtime import TenantScheduler
    from spacemesh_tpu.runtime.scheduler import default_pack_lanes

    monkeypatch.delenv("SPACEMESH_MESH", raising=False)
    with TenantScheduler(workers=1, autostart=False) as sched:
        assert sched.pack_width(8192) == 8192 == default_pack_lanes(8192)
        assert sched.pack_width(N) == scrypt.lane_ceiling(N)
    with TenantScheduler(workers=1, pack_lanes=100,
                         autostart=False) as sched:
        assert sched.pack_width(8192) == 128
    monkeypatch.setenv("SPACEMESH_MESH", "on")
    assert default_pack_lanes(8192) == 8192 * jax.device_count()
