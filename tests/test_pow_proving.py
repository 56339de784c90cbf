"""k2pow and proving-hash primitives: ground truth + statistics."""

import hashlib

import numpy as np
import pytest

from spacemesh_tpu.ops import pow as k2pow
from spacemesh_tpu.ops import proving, scrypt

CH = hashlib.sha256(b"challenge").digest()
NID = hashlib.sha256(b"node").digest()


def cpu_pow_hash(challenge, node_id, nonce):
    return hashlib.sha256(challenge + node_id + int(nonce).to_bytes(8, "little")).digest()


def test_pow_hash_device_path_matches_hashlib():
    # the DEVICE batch path (used by search) against the hashlib ground truth
    import jax.numpy as jnp

    nonces = np.array([0, 1, 12345, 2**32 + 7, 2**63 - 1], dtype=np.uint64)
    st = jnp.asarray(k2pow.prefix_state(CH, NID))
    lo = jnp.asarray((nonces & 0xFFFFFFFF).astype(np.uint32))
    hi = jnp.asarray((nonces >> 32).astype(np.uint32))
    d = np.asarray(k2pow.pow_hash_batch_jit(st, lo, hi))
    for k, nonce in enumerate(nonces):
        want = cpu_pow_hash(CH, NID, int(nonce))
        assert d[:, k].astype(">u4").tobytes() == want
        assert k2pow.pow_hash(CH, NID, int(nonce)) == want


def test_pow_search_and_verify():
    # easy difficulty: top byte 0x04 -> ~1/64 chance per nonce
    difficulty = bytes([0x04]) + bytes(31)
    nonce = k2pow.search(CH, NID, difficulty, batch=512, max_batches=8)
    assert nonce is not None
    assert k2pow.verify(CH, NID, difficulty, nonce)
    assert cpu_pow_hash(CH, NID, nonce) < difficulty
    # the found nonce is the first qualifying one in scan order
    for earlier in range(min(nonce, 200)):
        assert cpu_pow_hash(CH, NID, earlier) >= difficulty
    assert not k2pow.verify(CH, NID, bytes(32), nonce)  # impossible target


def test_pow_input_validation():
    with pytest.raises(ValueError):
        k2pow.search(CH, NID, b"short")
    with pytest.raises(ValueError):
        k2pow.prefix_state(b"x", NID)


def _mixed_pow_items(count, seed=9):
    """Deterministic mixed witnesses: per-item prefixes, difficulties
    spread around the acceptance boundary, 32/64-bit nonces."""
    rng = np.random.RandomState(seed)
    items = []
    for i in range(count):
        c = hashlib.sha256(b"powv-c%d" % i).digest()
        nid = hashlib.sha256(b"powv-n%d" % i).digest()
        diff = bytes(rng.randint(0, 256, size=32, dtype=np.int64)
                     .astype(np.uint8).tolist())
        nonce = int(rng.randint(0, 1 << 31))
        if i % 5 == 0:
            nonce |= (i + 1) << 33  # exercise the hi-u32 lanes
        items.append((c, nid, diff, nonce))
    return items


def test_pow_verify_many_device_matches_scalar():
    """The batched per-item-prefix device path (verifyd's farm kind) is
    bit-identical to scalar verify across chunking/padding seams."""
    items = _mixed_pow_items(37)
    expected = [k2pow.verify(*it) for it in items]
    assert any(expected) or True  # difficulties are random; just run
    # small chunks + ragged tail (pad to bucket) through the engine
    assert k2pow.verify_many(items, batch=16, min_device=1) == expected
    # one whole-batch chunk
    assert k2pow.verify_many(items, batch=4096, min_device=1) == expected
    # host path (below min_device) agrees
    assert k2pow.verify_many(items, min_device=1000) == expected
    assert k2pow.verify_many([]) == []


def test_pow_verify_many_device_failure_raises(monkeypatch):
    """A device dispatch failure raises — no chunk is re-verified on the
    host unasked, and nothing counts a fallback."""
    from spacemesh_tpu.utils import metrics

    items = _mixed_pow_items(24, seed=11)

    def boom(*a, **k):
        raise RuntimeError("device gone")

    monkeypatch.setattr(k2pow, "pow_verify_batch_jit", boom)
    before = sum(metrics.runtime_fallbacks.sample().values())
    with pytest.raises(RuntimeError, match="device gone"):
        k2pow.verify_many(items, batch=8, min_device=1)
    assert sum(metrics.runtime_fallbacks.sample().values()) == before


def test_pow_verify_many_validates_inputs():
    with pytest.raises(ValueError):
        k2pow.verify_many([(b"x", NID, bytes(32), 1)])
    with pytest.raises(ValueError):
        k2pow.verify_many([(CH, NID, b"short", 1)])
    # out-of-u64 nonces fail fast with a clear error, never a mid-batch
    # OverflowError from np.array/to_bytes
    with pytest.raises(ValueError, match="64-bit"):
        k2pow.verify_many([(CH, NID, bytes(32), 1 << 64)])
    with pytest.raises(ValueError, match="64-bit"):
        k2pow.verify_many([(CH, NID, bytes(32), -1)])


def test_pow_verify_runtime_kind_registered():
    """k2pow_verify is a registered workload kind with a warm recipe
    (tools/warmcache.py + the warm-cache CI job cover it)."""
    from spacemesh_tpu.runtime import workloads

    kind = workloads.get("k2pow_verify")
    assert any(k.name == "k2pow_verify" for k in workloads.registered())
    doc = kind.warm(8, 17)
    assert doc["batch"] == 32  # bucketed to the padded shape
    assert "pow_verify_batch" in doc


def test_proving_hash_deterministic_and_keyed():
    idx = np.arange(64, dtype=np.uint64)
    labels = scrypt.scrypt_labels(NID, idx, n=4)
    a = proving.proving_hashes(CH, 7, idx, labels)
    b = proving.proving_hashes(CH, 7, idx, labels)
    assert np.array_equal(a, b)
    # nonce, challenge, index, and label all key the hash
    assert not np.array_equal(a, proving.proving_hashes(CH, 8, idx, labels))
    other_ch = hashlib.sha256(b"other").digest()
    assert not np.array_equal(a, proving.proving_hashes(other_ch, 7, idx, labels))
    labels2 = np.array(labels)
    labels2[0] ^= 1
    assert a[0] != proving.proving_hashes(CH, 7, idx, labels2)[0]


def test_threshold_statistics():
    # E[qualifying] = k1: with 4096 labels and k1=256, expect ~256 +- 5 sigma
    total = 4096
    k1 = 256
    t = proving.threshold_u32(k1, total)
    idx = np.arange(total, dtype=np.uint64)
    labels = scrypt.scrypt_labels(NID, idx, n=2)
    vals = proving.proving_hashes(CH, 0, idx, labels)
    count = int((vals < t).sum())
    sigma = (k1 * (1 - k1 / total)) ** 0.5
    assert abs(count - k1) < 6 * sigma, (count, k1)


def test_proving_scan_matches_single_nonce():
    import jax.numpy as jnp

    idx = np.arange(128, dtype=np.uint64)
    labels = scrypt.scrypt_labels(NID, idx, n=2)
    t = proving.threshold_u32(16, 128)
    lo, hi = scrypt.split_indices(idx)
    lw = np.ascontiguousarray(labels).view("<u4").reshape(-1, 4).T.astype(np.uint32)
    cw = np.frombuffer(CH, dtype="<u4").astype(np.uint32)
    mask = np.asarray(proving.proving_scan_jit(
        jnp.asarray(cw), jnp.uint32(3), jnp.asarray(lo), jnp.asarray(hi),
        jnp.asarray(lw), jnp.uint32(t), n_nonces=4))
    assert mask.shape == (4, 128)
    for k in range(4):
        vals = proving.proving_hashes(CH, 3 + k, idx, labels)
        assert np.array_equal(mask[k], vals < t)


def test_second_search_compiles_nothing():
    """Every proof begins with a k2pow search over a new challenge: once
    one search has run, the next must find every program compiled (the
    first block's SHA-256 ran eagerly once and compiled its two loops
    anew on every call)."""
    import jax.monitoring

    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiled.append(kw.get("fun_name"))
        if event == "/jax/core/compile/backend_compile_duration" else None)
    diff = bytes([0x0f]) + bytes([0xff]) * 31
    first = k2pow.search(hashlib.sha256(b"a").digest(), NID, diff,
                         batch=1 << 10)
    n = len(compiled)
    second = k2pow.search(hashlib.sha256(b"b").digest(), NID, diff,
                          batch=1 << 10)
    assert first is not None and second is not None
    assert compiled[n:] == []
    # and the searches still find what hashlib confirms
    assert k2pow.verify(hashlib.sha256(b"a").digest(), NID, diff, first)
    assert k2pow.verify(hashlib.sha256(b"b").digest(), NID, diff, second)
