"""The mesh rule + shape bucketing + warmcache (ISSUE 6, ISSUE 29).

Four contracts, asserted rather than eyeballed:

* **the mesh rule** — parallel/mesh.py ``auto_mesh`` is the one place
  that says where a batch runs: a table over backend, visible devices,
  ``SPACEMESH_MESH`` and the batch;
* **sharded bit-identity** — an init session the rule shards writes
  byte-identical labels (and the same VRF nonce) as the single-device
  path, across ragged totals (1 / 7 / 1000) whose tail batches exercise
  the bucket-then-mesh pad in post/initializer.py ``_dispatch``;
* **bucketed executable reuse** — ragged batch sizes inside one
  power-of-two bucket share ONE compiled executable
  (ops/scrypt.py ``shape_bucket``), measured by the in-process compile
  counter, not by timing;
* **warmcache round-trip** — a cold ``tools/warmcache.py`` run populates
  the persistent XLA cache, so a second (warm) process finds every
  executable there and adds none.
"""

import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spacemesh_tpu.ops import scrypt
from spacemesh_tpu.parallel import mesh as pmesh
from spacemesh_tpu.parallel import topology
from spacemesh_tpu.post import initializer
from spacemesh_tpu.post.data import LabelStore, PostMetadata
from spacemesh_tpu.utils import metrics

NODE = hashlib.sha256(b"mesh-node").digest()
COMMIT = hashlib.sha256(b"mesh-commitment").digest()
N = 2
BATCH = 256


def _disk_labels(d, count):
    meta = PostMetadata.load(d)
    return LabelStore(d, meta).read_labels(0, count)


# --- sharded-vs-single bit-identity across ragged totals ------------------


@pytest.mark.parametrize("total", (1, 7, 1000))
def test_autotuned_mesh_init_bit_identical(total, monkeypatch, tmp_path):
    """End to end through the initializer: SPACEMESH_MESH=4 routes
    batches over the mesh (bucket pad + mesh pad + trim), and the bytes
    on disk — and the VRF nonce — match the single-device ground truth
    exactly. total=1 also proves the divisibility rule: four devices
    cannot shard one lane, so the session honestly runs single-device."""
    monkeypatch.setenv(pmesh.ENV_MESH, "4")

    d = tmp_path / f"mesh-{total}"
    meta, _res = initializer.initialize(
        d, node_id=NODE, commitment=COMMIT, num_units=1,
        labels_per_unit=total, scrypt_n=N, max_file_size=1 << 20,
        batch_size=BATCH, mesh="auto")

    assert meta.labels_written == total
    got = np.frombuffer(_disk_labels(d, total), dtype=np.uint8)
    want = scrypt.scrypt_labels(COMMIT, np.arange(total, dtype=np.uint64),
                                n=N)
    assert np.array_equal(got.reshape(-1, 16), want), \
        f"sharded labels diverged from single-device at total={total}"
    lo = want[:, :8].copy().view("<u8").ravel()
    hi = want[:, 8:].copy().view("<u8").ravel()
    assert meta.vrf_nonce == int(np.lexsort((lo, hi))[0])

    expected_devices = 4 if total >= 4 else 1
    assert metrics.post_mesh_devices._values.get(()) == expected_devices


def test_mesh_decision_consumed_and_reported(monkeypatch, tmp_path):
    """What the rule said is what the session runs with (the
    ``post_mesh_devices`` gauge, which the benchmark's check
    ``post_mesh_devices_equals_chips`` reads), and shard-imbalance
    telemetry appears for sharded runs."""
    monkeypatch.setenv(pmesh.ENV_MESH, "4")
    metrics.post_mesh_shard_imbalance.set(-1.0)
    d = tmp_path / "telemetry"
    initializer.initialize(
        d, node_id=NODE, commitment=COMMIT, num_units=1,
        labels_per_unit=512, scrypt_n=N, max_file_size=1 << 20,
        batch_size=BATCH, mesh="auto")
    assert metrics.post_mesh_devices._values.get(()) == 4
    imb = metrics.post_mesh_shard_imbalance._values.get(())
    assert imb is not None and 0.0 <= imb <= 1.0


@pytest.mark.parametrize("impl", ("xla",))
def test_sharded_impl_passthrough_bit_identity(impl):
    """The one kernel produces identical labels through the sharded
    entry point."""
    idx = np.arange(64, dtype=np.uint64)
    lo, hi = scrypt.split_indices(idx)
    want = scrypt.scrypt_labels(COMMIT, idx, n=4)
    mesh = pmesh.data_mesh(jax.devices()[:4])
    cw = scrypt.commitment_to_words(COMMIT)
    words = pmesh.scrypt_labels_sharded(mesh, cw, lo, hi, n=4)
    got = np.frombuffer(scrypt.labels_to_bytes(np.asarray(words)),
                        dtype=np.uint8).reshape(-1, 16)
    assert np.array_equal(got, want), "diverged under mesh"


# --- the mesh rule, one table ----------------------------------------------

# (backend, visible devices, SPACEMESH_MESH, batch) -> devices the batch
# shards over (None = one device) or the error
MESH_RULE = [
    ("cpu", 8, None, 256, None),
    ("cpu", 8, "on", 256, 8),
    ("cpu", 8, "4", 256, 4),
    ("cpu", 8, "off", 256, None),
    ("cpu", 8, "auto", 256, None),
    ("tpu", 1, None, 256, None),
    ("tpu", 4, None, 256, 4),
    ("tpu", 4, "off", 256, None),
    ("tpu", 4, "2", 256, 2),
    ("tpu", 4, "16", 256, 4),        # clipped to the visible devices
    ("tpu", 4, None, 6, None),       # the batch does not divide
    ("tpu", 4, "lots", 256, ValueError),
    ("tpu", 4, "-2", 256, ValueError),
]


@pytest.mark.parametrize(
    "backend,visible,env,batch,want", MESH_RULE,
    ids=[f"{b}-{v}-{e or 'unset'}-b{n}" for b, v, e, n, _ in MESH_RULE])
def test_mesh_rule(monkeypatch, backend, visible, env, batch, want):
    topology.get()  # built from the REAL devices before they are faked
    devs = jax.devices()[:visible]
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "devices", lambda: devs)
    if env is None:
        monkeypatch.delenv(pmesh.ENV_MESH, raising=False)
    else:
        monkeypatch.setenv(pmesh.ENV_MESH, env)
    if want is ValueError:
        with pytest.raises(ValueError, match="SPACEMESH_MESH"):
            pmesh.auto_mesh(batch)
        return
    mesh = pmesh.auto_mesh(batch)
    assert (mesh.size if mesh is not None else None) == want
    if mesh is not None:
        assert mesh is pmesh.data_mesh(devs[:want]), \
            "the rule must hand out the topology's one mesh per count"


# --- bucketed executable reuse (the compile counter, not a stopwatch) -----


def test_bucketed_shapes_share_one_executable():
    """Every ragged batch inside a power-of-two bucket reuses the
    bucket's executable; crossing the bucket boundary mints exactly one
    more. Asserted on the jit cache-entry counter."""
    n = 64  # a (n, shape) family no other test compiles
    cw = jnp.asarray(scrypt.commitment_to_words(COMMIT))

    def labels(b):
        lo, hi = scrypt.split_indices(np.arange(b, dtype=np.uint64))
        return scrypt.scrypt_labels_jit(cw, jnp.asarray(lo),
                                        jnp.asarray(hi), n=n)

    base = scrypt.compiled_shape_count()
    out5 = labels(5)
    assert out5.shape == (4, 5)  # trimmed back to the caller's batch
    assert scrypt.compiled_shape_count() == base + 1
    for b in (6, 7, 8):
        assert labels(b).shape == (4, b)
    assert scrypt.compiled_shape_count() == base + 1, \
        "ragged batches 5..8 must share the bucket-8 executable"
    labels(9)  # bucket 16
    assert scrypt.compiled_shape_count() == base + 2

    # bit-identity of the pad-and-trim against ground truth
    want = scrypt.scrypt_labels(COMMIT, np.arange(5, dtype=np.uint64), n=n)
    got = np.frombuffer(scrypt.labels_to_bytes(np.asarray(out5)),
                        dtype=np.uint8).reshape(-1, 16)
    assert np.array_equal(got, want)


def test_bucketed_min_scan_carry_is_exact():
    """Pad lanes repeat the last index: the VRF min-scan's carry must be
    identical to the unpadded result (first-occurrence wins)."""
    n = 64
    total = 11  # bucket 16: 5 pad lanes
    idx = np.arange(total, dtype=np.uint64)
    lo, hi = scrypt.split_indices(idx)
    cw = jnp.asarray(scrypt.commitment_to_words(COMMIT))
    base = scrypt.compiled_shape_count()
    words, _carry, snap = scrypt.scrypt_labels_with_min(
        cw, jnp.asarray(lo), jnp.asarray(hi),
        jnp.asarray(scrypt.vrf_carry_init()), n=n)
    assert words.shape == (4, total)
    assert scrypt.compiled_shape_count() == base + 1

    want = scrypt.scrypt_labels(COMMIT, idx, n=n)
    wlo = want[:, :8].copy().view("<u8").ravel()
    whi = want[:, 8:].copy().view("<u8").ravel()
    want_k = int(np.lexsort((wlo, whi))[0])
    decoded = scrypt.vrf_carry_decode(snap)
    assert decoded is not None and decoded[0] == want_k


def test_shape_bucket_contract(monkeypatch):
    assert scrypt.shape_bucket(1) == 1
    assert scrypt.shape_bucket(5) == 8
    assert scrypt.shape_bucket(8) == 8
    assert scrypt.shape_bucket(1000) == 1024
    monkeypatch.setenv(scrypt.ENV_BUCKETS, "off")
    assert scrypt.shape_bucket(1000) == 1000


# --- warmcache round-trip: cold compiles, warm adds nothing ----------------


def _run_warmcache(cache_dir):
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               # placed from outside: the code then sets nothing itself
               # (utils/accel.py), so the threshold comes from here too.
               # 0: EVERY compile is written, so a warm run that still
               # compiles anything shows as a new entry
               JAX_COMPILATION_CACHE_DIR=str(cache_dir),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    r = subprocess.run(
        [sys.executable, "-m", "spacemesh_tpu.tools.warmcache",
         "--n", "32", "--batches", "64", "--no-mesh"],
        env=env, capture_output=True, text=True, timeout=570)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout)


def test_warmcache_cold_then_warm(tmp_path):
    """The CLI's first (cold) run pays the XLA compiles into the
    persistent cache; a second process finds every executable there:
    it adds NO entry to the cache directory (a miss would compile and
    write one). Counted, not timed: a whole first call is import, trace
    and lower as well, on a host shared with five other test workers."""
    cache = tmp_path / "xla-cache"
    cold = _run_warmcache(cache)
    assert cold["cache_dir"] == str(cache) and cold["shapes"], cold
    cold_progs = cold["shapes"][0]["programs"]
    assert cold_progs, "cold run compiled nothing"
    entries = sorted(p.name for p in cache.iterdir())
    assert len(entries) >= len(cold_progs), \
        "the cold run must have written its executables"

    warm = _run_warmcache(cache)
    assert set(warm["shapes"][0]["programs"]) == set(cold_progs)
    new = sorted(set(p.name for p in cache.iterdir()) - set(entries))
    assert not new, f"the warm process compiled and wrote {new}"
