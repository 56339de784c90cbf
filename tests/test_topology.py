"""The GSPMD data plane (ISSUE 16): one process-wide topology, persistent
layout catalog, mesh-sharded pack/verify twins bit-identical to the
single-device paths."""

import hashlib

import jax
import numpy as np
import pytest

from spacemesh_tpu.ops import scrypt
from spacemesh_tpu.parallel import data_mesh, topology
from spacemesh_tpu.parallel import mesh as pmesh

N = 4


@pytest.fixture
def mesh_env(monkeypatch):
    """-> a setter for SPACEMESH_MESH, which starts unset: the way a
    test asks the mesh rule (parallel/mesh.py auto_mesh) for a sharded
    run on the virtual CPU devices."""
    monkeypatch.delenv(pmesh.ENV_MESH, raising=False)
    return lambda devices: monkeypatch.setenv(pmesh.ENV_MESH, str(devices))


# --- the topology singleton + persistent catalog --------------------------


def test_one_mesh_object_per_process():
    """Every entry point consumes the SAME Mesh/NamedSharding objects —
    the acceptance criterion that makes jit executable reuse structural
    rather than accidental."""
    t = topology.get()
    assert t is topology.get()
    lay = t.layouts()
    assert lay is t.layouts()
    assert lay.mesh is data_mesh()
    assert lay.mesh.shape == {"data": 8, "model": 1}
    # submesh catalogs are cached per count, prefix selections included
    sub = t.layouts(4)
    assert sub is t.layouts_for_devices(jax.devices()[:4])
    assert sub.mesh is data_mesh(jax.devices()[:4])
    # the sharding objects themselves are persistent (not per-call)
    assert lay.batch is t.layouts().batch
    assert lay.lane is t.layouts().lane
    assert pmesh.lane_sharding(lay.mesh) is lay.lane


def test_layouts_for_foreign_mesh_resolves_by_devices():
    lay = topology.get().layouts(2)
    resolved = topology.get().layouts_for(lay.mesh)
    assert resolved is lay


def test_replicate_is_noop_for_resident_carry():
    """The satellite fix: a carry already replicated on the mesh is
    returned as-is (same object), so donated carries stay resident
    across a pass instead of paying a device_put per batch."""
    lay = topology.get().layouts()
    carry = scrypt.vrf_carry_init()
    placed = lay.replicate(carry)
    assert lay.replicate(placed) is placed
    # and via the mesh.py entry point wrapper too
    assert pmesh.replicate(lay.mesh, placed) is placed


# --- sharded packed multi-tenant init: ragged totals ----------------------


@pytest.mark.parametrize("totals", [(1,), (7,), (7, 1039)],
                         ids=["1", "7", "7+1039"])
def test_packed_init_sharded_bit_identity(mesh_env, tmp_path, totals):
    """The TenantScheduler's pack dispatch routed over a 4-device mesh
    produces byte-identical label files and VRF nonces to the host
    reference at ragged totals (host pre-bucket pad + segment slicing)."""
    from spacemesh_tpu.post.data import LabelStore
    from spacemesh_tpu.runtime import TenantScheduler

    pack = 256
    mesh_env(4)
    ids = [(f"t{i}", hashlib.sha256(b"tnode%d" % i).digest(),
            hashlib.sha256(b"tcommit%d" % i).digest(), total)
           for i, total in enumerate(totals)]
    with TenantScheduler(workers=2, pack_lanes=pack) as sched:
        handles = []
        for tid, node, commit, total in ids:
            sched.register_tenant(tid)
            handles.append((tid, commit, total, sched.submit_init(
                tid, tmp_path / tid, node_id=node, commitment=commit,
                num_units=1, labels_per_unit=total, scrypt_n=N,
                max_file_size=1 << 20)))
        for tid, commit, total, h in handles:
            meta = h.result(timeout=600)
            store = LabelStore(tmp_path / tid, meta)
            got = np.frombuffer(store.read_labels(0, total),
                                dtype=np.uint8).reshape(-1, 16)
            store.close()
            want = scrypt.scrypt_labels(
                commit, np.arange(total, dtype=np.uint64), n=N)
            assert np.array_equal(got, want), f"{tid} labels diverged"
            lo = want[:, :8].copy().view("<u8").ravel()
            hi = want[:, 8:].copy().view("<u8").ravel()
            assert meta.vrf_nonce == int(np.lexsort((lo, hi))[0]), tid
    # the routing the packer consulted really was the sharded one
    mesh = pmesh.auto_mesh(scrypt.shape_bucket(pack))
    assert mesh is not None and mesh.size == 4


def test_packed_init_steady_state_zero_new_compiles(mesh_env, tmp_path):
    """A warm process dispatches sharded packs with ZERO new compiles:
    after the first pack at a bucket, compiled_shape_count() stays flat
    for every later pack at that bucket (acceptance criterion)."""
    from spacemesh_tpu.runtime import TenantScheduler

    pack = 128
    mesh_env(4)

    def run(tag, totals):
        with TenantScheduler(workers=2, pack_lanes=pack) as sched:
            hs = []
            for i, total in enumerate(totals):
                tid = f"{tag}{i}"
                sched.register_tenant(tid)
                hs.append(sched.submit_init(
                    tid, tmp_path / tid, node_id=hashlib.sha256(
                        b"zn%d" % i).digest(),
                    commitment=hashlib.sha256(b"zc%d" % i).digest(),
                    num_units=1, labels_per_unit=total, scrypt_n=N,
                    max_file_size=1 << 20))
            for h in hs:
                h.result(timeout=600)

    run("warm", (64, 64))           # compile the (n, bucket) executables
    warm = scrypt.compiled_shape_count()
    run("steady", (33, 95, 128))    # ragged lanes, same pack bucket
    assert scrypt.compiled_shape_count() == warm, \
        "steady-state sharded dispatch minted a new executable"


# --- sharded farm verify: ragged flat batches -----------------------------


@pytest.mark.parametrize("devices", [2, 4])
@pytest.mark.parametrize("count", [1, 7, 1039])
def test_farm_verify_sharded_matches_single_device(mesh_env, count, devices):
    """verify_many has ONE device path (ISSUE 24) and a mesh changes
    only where the arrays are placed: over a mesh-routed batch it
    returns the verdicts of the one-device pass, at ragged spot-check
    totals and with mixed verdicts."""
    from spacemesh_tpu.post import verifier
    from spacemesh_tpu.post.prover import Proof, ProofParams

    total_labels = 64
    p = ProofParams(k1=8, k2=1, k3=1, pow_difficulty=bytes([255] * 32))
    items = []
    for i in range(count):
        items.append(verifier.VerifyItem(
            Proof(nonce=0, indices=[i % total_labels], pow_nonce=0, k2=1),
            hashlib.sha256(b"vch%d" % i).digest(),
            hashlib.sha256(b"vnode%d" % i).digest(),
            hashlib.sha256(b"vcommit%d" % i).digest(),
            N, total_labels))
    seed = b"topology-seed".ljust(32, b"\0")
    bucket = scrypt.shape_bucket(count)

    assert pmesh.auto_mesh(bucket) is None
    single = verifier.verify_many(items, p, seed)
    mesh_env(devices)
    sharded = verifier.verify_many(items, p, seed)
    assert sharded == single
    if count > 1:
        assert True in single and False in single
    if bucket % devices == 0:
        mesh = pmesh.auto_mesh(bucket)
        assert mesh is not None and mesh.size == devices
