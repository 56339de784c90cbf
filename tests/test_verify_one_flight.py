"""post/verifier.verify_many on its one device path (ISSUE 24): the host
pads the flat batch to its bucket, label program -> words_to_le ->
proving hash run as one flight, only the hash values come back. Held to
an inline reference (``hashlib.scrypt`` + a numpy Salsa20/8), and to
"one executable per bucket, no eager op": every occupancy of a bucket
runs what the first call of that bucket compiled."""

import hashlib

import numpy as np
import pytest

from spacemesh_tpu.ops import scrypt
from spacemesh_tpu.post import verifier
from spacemesh_tpu.post.prover import Proof, ProofParams
from spacemesh_tpu.utils import tracing

TOTAL = 64
# k1 / TOTAL = 1/2: half of all lanes qualify, so verdicts are mixed
PARAMS = ProofParams(k1=32, k2=1, k3=1, pow_difficulty=bytes([255] * 32))
SEED = b"one-flight".ljust(32, b"\0")


def _rotl(x, k):
    return ((x << np.uint32(k)) | (x >> np.uint32(32 - k))).astype(np.uint32)


def _salsa20_8(block: np.ndarray) -> np.ndarray:
    """The Salsa20/8 core of RFC 7914 over 16 u32 words."""
    x = block.copy()
    with np.errstate(over="ignore"):
        for _ in range(4):
            for a, b, c, d in ((0, 4, 8, 12), (5, 9, 13, 1),
                               (10, 14, 2, 6), (15, 3, 7, 11),
                               (0, 1, 2, 3), (5, 6, 7, 4),
                               (10, 11, 8, 9), (15, 12, 13, 14)):
                x[b] ^= _rotl(x[a] + x[d], 7)
                x[c] ^= _rotl(x[b] + x[a], 9)
                x[d] ^= _rotl(x[c] + x[b], 13)
                x[a] ^= _rotl(x[d] + x[c], 18)
        return (x + block).astype(np.uint32)


def _want(it: verifier.VerifyItem) -> bool:
    """ops/proving.py's definition, from hashlib and numpy alone."""
    (j,) = it.proof.indices
    label = hashlib.scrypt(it.commitment, salt=j.to_bytes(8, "little"),
                           n=it.scrypt_n, r=1, p=1, dklen=16)
    state = np.zeros(16, dtype=np.uint32)
    state[0:8] = np.frombuffer(it.challenge, dtype="<u4")
    state[8] = it.proof.nonce
    state[9], state[10] = j & 0xFFFFFFFF, j >> 32
    state[12:16] = np.frombuffer(label, dtype="<u4")
    return int(_salsa20_8(state)[0]) < (PARAMS.k1 << 32) // TOTAL


def _item(i: int, n: int) -> verifier.VerifyItem:
    tag = b"%d/%d" % (n, i)
    return verifier.VerifyItem(
        Proof(nonce=i % 5, indices=[(7 * i) % TOTAL], pow_nonce=0, k2=1),
        hashlib.sha256(b"ch" + tag).digest(),
        hashlib.sha256(b"node" + tag).digest(),
        hashlib.sha256(b"commit" + tag).digest(), n, TOTAL)


@pytest.fixture(autouse=True)
def _tracer_off():
    tracing.stop()
    yield
    tracing.stop()


@pytest.mark.parametrize("lanes", [1, 7, 37, 64, 65])
def test_verdicts_equal_the_inline_reference(lanes):
    """``lanes`` spot-checks at N=4 interleaved with a third as many at
    N=2: two label programs, each padded to its own bucket, verdicts
    scattered back to the callers' order."""
    items = [_item(i, 4) for i in range(lanes)]
    for k in range((lanes + 2) // 3):
        items.insert(3 * k, _item(k, 2))
    want = [_want(it) for it in items]
    if lanes > 1:
        assert True in want and False in want
    tracing.start(capacity=1 << 12, jax_bridge=False)
    got = verifier.verify_many(items, PARAMS, SEED)
    tracing.stop()
    assert got == want
    evs = [e for e in tracing.export()["traceEvents"] if e["ph"] == "X"]
    (call,) = [e for e in evs if e["name"] == "post.verify"]
    a = call["args"]
    buckets = [scrypt.shape_bucket((lanes + 2) // 3),
               scrypt.shape_bucket(lanes)]
    assert a["syncs"] == 2 and a["lanes"] == sum(buckets)
    assert a["lanes_valid"] == len(items)
    assert a["d2h_bytes"] == 4 * a["lanes"]
    assert a["h2d_bytes"] == 19 * 4 * a["lanes"]    # 8 + 8 + 3 u32 a lane
    flights = [e for e in evs if e["name"] == "device.flight"]
    assert [f["args"]["program"] for f in flights] == ["labels_proving"] * 2
    assert [f["args"]["lanes"] for f in flights] == buckets
    names = {e["name"] for e in evs}
    assert "romix.pad" not in names and "post.verify.relayout" not in names


def _compiles():
    return [e["args"] for e in tracing.export()["traceEvents"]
            if e["name"] == "xla.compile"]


@pytest.mark.parametrize("bucket", [8, 64])
def test_one_call_per_bucket_compiles_for_every_occupancy(bucket):
    """After one call in a bucket nothing compiles at any other
    occupancy of it: neither a label program (the shape count is flat)
    nor anything else (no ``xla.compile`` event: no eager op is left on
    the path to compile per occupancy)."""
    items = [_item(i, 2) for i in range(bucket)]
    lo = bucket // 2 + 1
    want = [_want(it) for it in items]
    assert verifier.verify_many(items[:lo], PARAMS, SEED) == want[:lo]
    shapes = scrypt.compiled_shape_count()
    tracing.start(capacity=1 << 12, jax_bridge=False)
    for m in range(lo + 1, bucket + 1):
        assert verifier.verify_many(items[:m], PARAMS, SEED) == want[:m]
    tracing.stop()
    assert scrypt.compiled_shape_count() == shapes
    assert _compiles() == []
    # the instrument is live: a ragged DEVICE-resident batch still takes
    # ops/scrypt's eager pad, which compiles
    import jax.numpy as jnp

    odd = 2 * bucket + 3
    idx = jnp.arange(odd, dtype=jnp.uint32)
    tracing.start(capacity=1 << 12, jax_bridge=False)
    scrypt.scrypt_labels_jit(
        jnp.asarray(scrypt.commitment_to_words(bytes(32))), idx,
        jnp.zeros_like(idx), n=2).block_until_ready()
    tracing.stop()
    assert _compiles()
