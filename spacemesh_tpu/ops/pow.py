"""k2pow — proof-gating proof-of-work as a batched TPU nonce search.

The reference gates NIPoST proof generation behind a RandomX PoW ("k2pow",
reference activation/post.go:71-81, difficulty config/mainnet.go:40-43).
RandomX is *deliberately* CPU-serial (random code execution over a 2 GiB
dataset) and has no sensible TPU mapping, so this framework replaces it —
behind the same validator seam (see post/verifier.py) — with a SHA-256
preimage search under a 256-bit big-endian target, which batches across
nonces on the VPU:

    pow_hash(challenge, node_id, nonce) = SHA256(challenge || node_id
                                                 || le64(nonce))
    valid <=> pow_hash < difficulty     (32-byte big-endian compare)

Difficulty is expressed exactly like the reference's (a 32-byte threshold;
lower = harder) so operator configs translate directly.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import tracing
from .sha256 import IV, sha256_compress

# Message layout: challenge(32) || node_id(32) || le64(nonce) = 72 bytes
# -> two 64-byte blocks with FIPS padding in the second.
_BIT_LEN = 72 * 8


def _words_be(data: bytes) -> np.ndarray:
    return np.frombuffer(data, dtype=">u4").astype(np.uint32)


@jax.jit
@jax.named_scope("pow_sha256")   # op metadata: the phase's name in a trace
def pow_hash_batch_jit(prefix_state, nonce_lo, nonce_hi):
    """SHA-256 over the second block for a (B,) batch of nonces.

    ``prefix_state``: (8,) u32 — midstate after the first 64-byte block
    (challenge || first half of node_id). ``nonce_lo/hi``: (B,) u32.
    Returns (8, B) u32 BE digest words.
    """
    from .sha256 import byteswap32

    b = nonce_lo.shape[0]
    # block 1 (in prefix_state): challenge(32) || node_id(32).
    # block 2: le64(nonce) || 0x80 || zeros || be64(bit length) —
    # words: [swap(lo), swap(hi), 0x80000000, 0*12, _BIT_LEN]
    tail = np.zeros((14, 1), dtype=np.uint32)
    tail[0, 0] = 0x80000000
    tail[13, 0] = _BIT_LEN
    block = jnp.concatenate([
        byteswap32(nonce_lo)[None],
        byteswap32(nonce_hi)[None],
        jnp.broadcast_to(jnp.asarray(tail), (14, b)),
    ])
    return sha256_compress(jnp.broadcast_to(prefix_state[:, None], (8, b)), block)


@jax.jit
def below_target_jit(digest_words, target_words):
    """Big-endian 256-bit compare: digest < target, per lane.

    digest_words: (8, B) u32; target_words: (8,) u32. Returns (B,) bool.
    """
    b = digest_words.shape[1]
    t = jnp.broadcast_to(target_words[:, None], (8, b))
    lt = digest_words < t
    eq = digest_words == t
    out = lt[7]
    for i in range(6, -1, -1):
        out = lt[i] | (eq[i] & out)
    return out


@jax.jit
def _first_block_jit(block):
    # jitted so that every search shares one compiled program: called
    # eagerly, the two fori_loops of sha256_compress close over fresh
    # functions, and JAX compiles both anew on every call (two backend
    # compiles a prove: tests/test_pow_proving.py)
    return sha256_compress(jnp.asarray(IV), block)


def prefix_state(challenge: bytes, node_id: bytes) -> np.ndarray:
    """Midstate after absorbing challenge||node_id (the first block)."""
    if len(challenge) != 32 or len(node_id) != 32:
        raise ValueError("challenge and node_id must be 32 bytes")
    return np.asarray(_first_block_jit(_words_be(challenge + node_id)))


def pow_hash(challenge: bytes, node_id: bytes, nonce: int) -> bytes:
    """Single hash on host (verification path: one 2-block SHA-256 is far
    cheaper than a device round-trip; the device path is for search)."""
    import hashlib

    if len(challenge) != 32 or len(node_id) != 32:
        raise ValueError("challenge and node_id must be 32 bytes")
    return hashlib.sha256(
        challenge + node_id + int(nonce).to_bytes(8, "little")).digest()


def search(challenge: bytes, node_id: bytes, difficulty: bytes,
           *, batch: int = 1 << 16, start: int = 0,
           max_batches: int = 1 << 16, inflight: int = 2,
           tenant: str = "-") -> int | None:
    """Find a nonce whose pow_hash is below ``difficulty`` (32B BE target).

    Scans ``batch`` nonces per device program through the shared runtime
    engine (runtime/engine.py): ``inflight`` batches stay enqueued so
    the host-side hit check of one batch overlaps the next batch's
    device compute.  Batches retire in nonce order, so the result — the
    smallest hit in the first batch containing one — is identical to
    the historical serial loop's.  A device failure raises — the search
    never re-runs a batch on the host unasked.  None when exhausted.
    """
    from ..runtime import engine

    if len(difficulty) != 32:
        raise ValueError("difficulty must be 32 bytes")
    st = jnp.asarray(prefix_state(challenge, node_id))
    tgt = jnp.asarray(_words_be(difficulty))

    def dispatch(base):
        nonces = np.arange(base, base + batch, dtype=np.uint64)
        lo = jnp.asarray((nonces & 0xFFFFFFFF).astype(np.uint32))
        hi = jnp.asarray((nonces >> 32).astype(np.uint32))
        # enqueue only: the (B,) hit mask crosses to host at retire
        return base, below_target_jit(pow_hash_batch_jit(st, lo, hi), tgt)

    def retire(ticket):
        # a 0 return is a valid winning nonce: the engine's early-exit
        # test is `is not None`, not truthiness
        base, ok = ticket
        hits = np.nonzero(np.asarray(ok))[0]
        return int(base + int(hits[0])) if hits.size else None

    pipe = engine.Pipeline(kind="k2pow", tenant=tenant,
                           inflight=inflight, span="pow")
    return pipe.run((start + i * batch for i in range(max_batches)),
                    dispatch, retire)


def verify(challenge: bytes, node_id: bytes, difficulty: bytes, nonce: int) -> bool:
    return pow_hash(challenge, node_id, nonce) < difficulty


# --- batched verification (verifyd / the verify farm's "pow" kind) ------
#
# Search batches many NONCES under one (challenge, node_id); verification
# at service scale batches many ITEMS, each with its own prefix and its
# own difficulty. Both 64-byte blocks run on device with per-lane state:
# block 1 is the item's challenge||node_id, block 2 its nonce + padding.


@jax.jit
def below_targets_jit(digest_words, target_words):
    """Per-lane big-endian 256-bit compare: digest < target.

    digest_words, target_words: (8, B) u32. Returns (B,) bool — the
    per-lane-target twin of :func:`below_target_jit`.
    """
    lt = digest_words < target_words
    eq = digest_words == target_words
    out = lt[7]
    for i in range(6, -1, -1):
        out = lt[i] | (eq[i] & out)
    return out


@jax.jit
@jax.named_scope("pow_sha256")
def pow_verify_batch_jit(block1, nonce_lo, nonce_hi, target_words):
    """Verify a (B,) batch of (challenge, node_id, nonce, difficulty)
    witnesses in one two-block SHA-256 pass.

    ``block1``: (16, B) u32 — each item's challenge||node_id words.
    ``nonce_lo/hi``: (B,) u32. ``target_words``: (8, B) u32 per-item
    difficulty. Returns (B,) bool.
    """
    from .sha256 import byteswap32

    b = nonce_lo.shape[0]
    st = sha256_compress(
        jnp.broadcast_to(jnp.asarray(IV)[:, None], (8, b)), block1)
    tail = np.zeros((14, 1), dtype=np.uint32)
    tail[0, 0] = 0x80000000
    tail[13, 0] = _BIT_LEN
    block2 = jnp.concatenate([
        byteswap32(nonce_lo)[None],
        byteswap32(nonce_hi)[None],
        jnp.broadcast_to(jnp.asarray(tail), (14, b)),
    ])
    return below_targets_jit(sha256_compress(st, block2), target_words)


def _verify_host(items: list) -> list[bool]:
    import hashlib

    out = []
    for challenge, node_id, difficulty, nonce in items:
        out.append(hashlib.sha256(
            challenge + node_id + int(nonce).to_bytes(8, "little")
        ).digest() < difficulty)
    return out


def verify_many(items: list, *, batch: int = 1 << 12,
                inflight: int = 2, min_device: int = 8,
                tenant: str = "-") -> list[bool]:
    """Batched k2pow verification: ``items`` are (challenge, node_id,
    difficulty, nonce) tuples; returns per-item validity, bit-identical
    to :func:`verify` on every item.

    Chunks of ``batch`` items run as one device program each through the
    shared runtime engine (``kind="k2pow_verify"``, ``inflight`` chunks
    enqueued so host packing of one chunk overlaps the previous chunk's
    device compute); ragged chunks pad to their power-of-two shape
    bucket by replicating lane 0, so occupancy changes reuse compiled
    executables. Batches below ``min_device`` items skip the device
    round-trip (two hashlib blocks are cheaper than a dispatch) — a
    choice made from the batch size, never from a failure: a device
    failure raises.
    """
    n = len(items)
    if n == 0:
        return []
    for challenge, node_id, difficulty, nonce in items:
        if len(challenge) != 32 or len(node_id) != 32:
            raise ValueError("challenge and node_id must be 32 bytes")
        if len(difficulty) != 32:
            raise ValueError("difficulty must be 32 bytes")
        if not 0 <= int(nonce) < 1 << 64:
            # fail fast and clearly: past this point an out-of-range
            # nonce would surface as an OverflowError mid-batch
            raise ValueError("nonce must be an unsigned 64-bit integer")
    if n < min_device:
        return _verify_host(items)
    from ..runtime import engine
    from . import scrypt

    results = np.zeros(n, dtype=bool)

    def dispatch(rng):
        lo_i, hi_i = rng
        chunk = items[lo_i:hi_i]
        count = len(chunk)
        pad = max(scrypt.shape_bucket(count), 1)
        rows = chunk + [chunk[0]] * (pad - count)
        block1 = np.stack([
            np.frombuffer(c + nid, dtype=">u4").astype(np.uint32)
            for c, nid, _d, _n in rows], axis=1)
        targets = np.stack([
            _words_be(d) for _c, _nid, d, _n in rows], axis=1)
        nonces = np.array([x[3] for x in rows], dtype=np.uint64)
        lo = jnp.asarray((nonces & 0xFFFFFFFF).astype(np.uint32))
        hi = jnp.asarray((nonces >> 32).astype(np.uint32))
        b1, tg = jnp.asarray(block1), jnp.asarray(targets)
        # the ticket carries the enqueue instant to retire, which closes
        # the device.flight span
        t0 = time.perf_counter_ns()
        return rng, pow_verify_batch_jit(b1, lo, hi, tg), t0

    def retire(ticket):
        (lo_i, hi_i), ok, t0 = ticket
        host = np.asarray(ok)
        tracing.interval("device.flight", t0,
                         {"program": "pow_verify", "lanes": host.shape[0],
                          "d2h_bytes": host.nbytes}
                         if tracing.is_enabled() else None)
        results[lo_i:hi_i] = host[:hi_i - lo_i]
        return None

    pipe = engine.Pipeline(kind="k2pow_verify", tenant=tenant,
                           inflight=inflight, span="pow_verify")
    pipe.run(((i, min(i + batch, n)) for i in range(0, n, batch)),
             dispatch, retire)
    return results.tolist()
