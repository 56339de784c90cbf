"""Registry of device workload kinds + their warm-shape recipes.

The runtime engine treats a workload as two callbacks; this module is
where the repo's actual device workloads are cataloged so tools can
enumerate them without importing every pipeline:

* ``init``        — fused single-identity label batches chained to the
                    on-device VRF min-scan (post/initializer.py).
* ``init_pack``   — the multi-tenant packed variant: ONE fused label
                    program over many identities' lanes (per-lane
                    commitment words), VRF minimum folded per tenant on
                    host (runtime/scheduler.py).
* ``prove_scan``  — the streaming prover's window step: the one program
                    a batch a default Prover binds here (post/prover.py).
* ``verify``      — the batched POST verifier's one flight: per-lane
                    label program, ``words_to_le``, per-lane proving
                    hash (post/verifier.py).
* ``k2pow``       — the SHA-256 nonce-search batch (ops/pow.py).
* ``k2pow_verify`` — the per-item-prefix k2pow witness verification
                    batch (ops/pow.py verify_many; the verifyd service
                    and the farm's "pow" kind dispatch it).

Each kind carries a ``warm(n, batch)`` recipe compiling exactly the
executables that kind runs at one (N, bucketed batch) shape —
tools/warmcache.py iterates :func:`registered` so a cold 16-tenant
start does not pay one serialized compile per workload kind
(docs/DEVICE_RUNTIME.md).

Also home to the host-side helpers the packed init path shares with its
tests: :func:`fold_min_host` (the per-tenant VRF running minimum over
fetched label bytes — bit-identical to the device scan's first-
occurrence LE-u128 argmin) and :class:`PackSegment`.
"""

from __future__ import annotations

import dataclasses
from typing import Callable


@dataclasses.dataclass(frozen=True)
class WorkloadKind:
    """One registered device workload kind."""

    name: str
    description: str
    # warm(n, batch) -> {program name: compile seconds}; compiles (or
    # cache-deserializes) every executable the kind runs at that shape
    warm: Callable[[int, int], dict]


_REGISTRY: dict[str, WorkloadKind] = {}


def register(kind: WorkloadKind) -> WorkloadKind:
    if kind.name in _REGISTRY:
        raise ValueError(f"workload kind {kind.name!r} already registered")
    _REGISTRY[kind.name] = kind
    return kind


def registered() -> list[WorkloadKind]:
    """All registered kinds, stable order (warmcache iterates this)."""
    return [_REGISTRY[k] for k in sorted(_REGISTRY)]


def get(name: str) -> WorkloadKind:
    return _REGISTRY[name]


# --- warm recipes -------------------------------------------------------
#
# Imports live inside the recipes: the registry must import without jax
# (spacecheck and CLI --list paths run before deps install).


def _timed(doc: dict, name: str, fn) -> None:
    import time

    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    doc[name] = round(time.perf_counter() - t0, 2)


def _warm_init(n: int, batch: int) -> dict:
    import hashlib

    import jax.numpy as jnp
    import numpy as np

    from ..ops import scrypt

    cw = scrypt.commitment_to_words(hashlib.sha256(b"warm-runtime").digest())
    idx = np.arange(batch, dtype=np.uint64)
    lo, hi = scrypt.split_indices(idx)
    jcw, jlo, jhi = jnp.asarray(cw), jnp.asarray(lo), jnp.asarray(hi)
    doc: dict = {}
    _timed(doc, "labels_fused",
           lambda: scrypt.scrypt_labels_jit(jcw, jlo, jhi, n=n))
    _timed(doc, "labels_min_fused",
           lambda: scrypt.scrypt_labels_with_min(
               jcw, jlo, jhi, jnp.asarray(scrypt.vrf_carry_init()), n=n)[0])
    return doc


def _warm_init_pack(n: int, batch: int) -> dict:
    import hashlib

    import jax.numpy as jnp
    import numpy as np

    from ..ops import scrypt

    # per-lane commitment words: the packed program's distinguishing
    # shape (a (8, B) cw is a different executable than a shared (8,))
    cw = np.stack([
        scrypt.commitment_to_words(hashlib.sha256(b"warm-%d" % i).digest())
        for i in range(2)], axis=1)
    cw = np.repeat(cw, (batch + 1) // 2, axis=1)[:, :batch]
    idx = np.arange(batch, dtype=np.uint64)
    lo, hi = scrypt.split_indices(idx)
    doc: dict = {}
    _timed(doc, "labels_fused_perlane",
           lambda: scrypt.scrypt_labels_jit(
               jnp.asarray(cw), jnp.asarray(lo), jnp.asarray(hi), n=n))
    # when packs at this bucket shard, the sharded twin is a DIFFERENT
    # executable (GSPMD-partitioned) — warm it too, or the first real
    # pack dispatch pays the compile
    from ..parallel import mesh as pmesh

    mesh = pmesh.auto_mesh(batch)
    if mesh is not None:
        _timed(doc, f"labels_fused_perlane_mesh{mesh.size}",
               lambda: pmesh.scrypt_labels_sharded(mesh, cw, lo, hi, n=n))
        doc["pack_devices"] = mesh.size
    return doc


def _warm_prove_scan(n: int, batch: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops import proving
    from ..post import prover
    from ..utils import accel

    # the window step a default Prover binds on this platform, at its
    # shapes (post/prover.py): the Pallas step wherever it runs
    # compiled, every nonce group of a pass over a whole flight of
    # FLIGHT_BATCHES batches in the one program (what any store of
    # eight batches or more runs; a smaller store's narrower flight
    # compiles in its own first proof)
    use_pallas = not accel.pallas_interpret()
    b = prover.bucket_batch(batch, use_pallas)
    f = prover.FLIGHT_BATCHES * b
    ng, cap = prover.DEFAULT_NONCE_GROUP, prover.ProofParams().k2
    groups = prover.default_window_groups(jax.devices()[0].platform)
    step = prover.window_step(ng, cap, b, use_pallas=use_pallas)
    cw = jnp.asarray(proving.challenge_words(bytes(32)))
    bases = jnp.asarray(ng * np.arange(groups), jnp.uint32)
    lw, words = jax.device_put([np.zeros((4, f), np.uint32),
                                np.array([f, 0, 0], np.uint32)])
    counts, carry = proving.init_hit_state(groups * ng, cap)
    doc: dict = {"batch": b, "flight_batches": prover.FLIGHT_BATCHES,
                 "nonce_group": ng, "groups": groups, "pallas": use_pallas}
    _timed(doc, "prove_scan_step_window",
           lambda: step(cw, bases, lw, words, jnp.uint32(1 << 30), counts,
                        carry))
    return doc


def _warm_verify(n: int, batch: int) -> dict:
    import jax
    import numpy as np

    from ..ops import proving, scrypt

    # post/verifier.verify_many's one flight at this bucket: the
    # per-lane label executable (init_pack's), then words_to_le and the
    # proving hash over per-lane challenges and nonces, fed from the
    # device-resident label words. Placement is all a mesh changes.
    doc = _warm_init_pack(n, batch)
    lo, hi = scrypt.split_indices(np.arange(batch, dtype=np.uint64))
    host = [np.broadcast_to(proving.challenge_words(bytes(32))[:, None],
                            (8, batch)),
            np.full(batch, 7, np.uint32), lo, hi,
            np.zeros((4, batch), np.uint32)]
    placements = [("", None)]
    from ..parallel import mesh as pmesh

    mesh = pmesh.auto_mesh(batch)
    if mesh is not None:
        from ..parallel import topology

        lay = topology.get().layouts_for(mesh)
        placements.append((f"_mesh{mesh.size}", [
            lay.lane, lay.batch, lay.batch, lay.batch, lay.lane]))
    for suffix, where in placements:
        chal, nonce, lo, hi, lw = jax.device_put(host, where)
        _timed(doc, "words_to_le" + suffix, lambda: scrypt.words_to_le(lw))
        _timed(doc, "proving_hash" + suffix,
               lambda: proving.proving_hash_jit(
                   chal, nonce, lo, hi, scrypt.words_to_le(lw)))
    return doc


def _warm_k2pow_verify(n: int, batch: int) -> dict:
    import hashlib

    import jax.numpy as jnp
    import numpy as np

    from ..ops import pow as k2pow
    from ..ops import scrypt

    # the verify path pads ragged chunks to their power-of-two bucket
    # (ops/pow.py verify_many), so warm exactly that shape
    b = max(scrypt.shape_bucket(batch), 1)
    block1 = np.stack([np.frombuffer(
        hashlib.sha256(b"warm-powv-%d" % i).digest() * 2,
        dtype=">u4").astype(np.uint32) for i in range(b)], axis=1)
    targets = np.broadcast_to(
        np.full((8, 1), 0xFFFFFFFF, dtype=np.uint32), (8, b)).copy()
    nonces = np.arange(b, dtype=np.uint64)
    lo = jnp.asarray((nonces & 0xFFFFFFFF).astype(np.uint32))
    hi = jnp.asarray((nonces >> 32).astype(np.uint32))
    doc: dict = {"batch": b}
    _timed(doc, "pow_verify_batch",
           lambda: k2pow.pow_verify_batch_jit(
               jnp.asarray(block1), lo, hi, jnp.asarray(targets)))
    return doc


def _warm_k2pow(n: int, batch: int) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from ..ops import pow as k2pow

    st = jnp.asarray(k2pow.prefix_state(bytes(32), bytes(32)))
    tgt = jnp.asarray(np.full(8, 0xFFFFFFFF, dtype=np.uint32))
    nonces = np.arange(batch, dtype=np.uint64)
    lo = jnp.asarray((nonces & 0xFFFFFFFF).astype(np.uint32))
    hi = jnp.asarray((nonces >> 32).astype(np.uint32))
    doc: dict = {}
    _timed(doc, "pow_batch",
           lambda: k2pow.below_target_jit(
               k2pow.pow_hash_batch_jit(st, lo, hi), tgt))
    return doc


INIT = register(WorkloadKind(
    "init", "fused label batch + on-device VRF min-scan", _warm_init))
INIT_PACK = register(WorkloadKind(
    "init_pack", "multi-tenant packed label batch (per-lane commitments)",
    _warm_init_pack))
PROVE_SCAN = register(WorkloadKind(
    "prove_scan", "streaming prove scan step (compact+merge on device)",
    _warm_prove_scan))
VERIFY = register(WorkloadKind(
    "verify", "batched POST verify flight (per-lane labels -> LE -> hash)",
    _warm_verify))
K2POW = register(WorkloadKind(
    "k2pow", "SHA-256 k2pow nonce-search batch", _warm_k2pow))
K2POW_VERIFY = register(WorkloadKind(
    "k2pow_verify",
    "per-item-prefix k2pow witness verification batch (verifyd)",
    _warm_k2pow_verify))


# --- packed-init host helpers ------------------------------------------


@dataclasses.dataclass
class PackSegment:
    """One tenant's contiguous lane range inside a packed dispatch."""

    job: object          # scheduler _InitJob
    start: int           # global label index of the segment's first lane
    count: int           # valid lanes (pre-bucket-pad)
    lane0: int           # first lane inside the packed batch


def fold_min_host(carry, label_bytes: bytes, start_index: int):
    """Fold one segment's labels into a per-tenant VRF running minimum.

    ``carry`` is ``None`` or ``(value_u128, index)``.  Bit-identical to
    the device scan (ops/scrypt.py _stage_minscan): the label's 16
    bytes read as a little-endian u128, ties keep the EARLIER index
    (np.lexsort first-occurrence semantics — the original host path the
    device carry replaced, reused here because a packed batch spans
    many tenants and the fused single-carry argmin cannot).
    """
    import numpy as np

    if not label_bytes:
        return carry
    halves = np.frombuffer(label_bytes, dtype="<u8").reshape(-1, 2)
    lo, hi = halves[:, 0], halves[:, 1]
    # primary key hi, then lo, then index: lexsort's first element is
    # the minimum with the smallest index
    best = int(np.lexsort((np.arange(lo.shape[0]), lo, hi))[0])
    value = (int(hi[best]) << 64) | int(lo[best])
    index = start_index + best
    if carry is None or value < carry[0] \
            or (value == carry[0] and index < carry[1]):
        return (value, index)
    return carry


def min_carry_to_meta(carry) -> tuple[int | None, str | None]:
    """(vrf_nonce, vrf_nonce_value hex) for PostMetadata — the exact
    byte layout post/initializer.py persists (lo u64 || hi u64, LE)."""
    if carry is None:
        return None, None
    value, index = carry
    lo = value & 0xFFFFFFFFFFFFFFFF
    hi = value >> 64
    return index, (lo.to_bytes(8, "little") + hi.to_bytes(8, "little")).hex()
