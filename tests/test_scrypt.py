"""Bit-exactness of the JAX scrypt labeler against hashlib.scrypt.

This is the TPU-build equivalent of the reference's e2e CGo tests
(reference activation/e2e) which validate byte-compatibility of proofs: the
CPU ground truth here is Python's OpenSSL-backed scrypt.
"""

import hashlib

import numpy as np
import pytest

from spacemesh_tpu.ops import scrypt


def cpu_label(commitment: bytes, index: int, n: int, dklen: int = 16) -> bytes:
    salt = int(index).to_bytes(8, "little")
    return hashlib.scrypt(commitment, salt=salt, n=n, r=1, p=1,
                          maxmem=256 * 1024 * 1024, dklen=dklen)


COMMIT = bytes(range(32))


@pytest.mark.parametrize("n", [2, 16, 8192])
def test_labels_match_hashlib(n):
    if n == 8192:  # mainnet N: keep the CPU-test cost bounded
        idx = np.array([0, 12345], dtype=np.uint64)
    else:
        idx = np.array([0, 1, 2, 7, 12345, 2**32 - 1, 2**32, 2**40 + 17],
                       dtype=np.uint64)
    got = scrypt.scrypt_labels(COMMIT, idx, n=n)
    for k, i in enumerate(idx):
        want = np.frombuffer(cpu_label(COMMIT, int(i), n), dtype=np.uint8)
        assert bytes(got[k]) == bytes(want), f"label mismatch at index {i}, n={n}"


def test_different_commitments_differ():
    idx = np.arange(4, dtype=np.uint64)
    a = scrypt.scrypt_labels(COMMIT, idx, n=16)
    b = scrypt.scrypt_labels(bytes(32), idx, n=16)
    assert not np.array_equal(a, b)


def test_input_validation():
    idx = np.array([1], dtype=np.uint64)
    for bad_n in (0, 1, 3, 6, 2**16, 2**20):
        with pytest.raises(ValueError):
            scrypt.scrypt_labels(COMMIT, idx, n=bad_n)
    with pytest.raises(ValueError):
        scrypt.scrypt_labels(b"short", idx, n=4)
    # scalar index is promoted to a 1-element batch
    got = scrypt.scrypt_labels(COMMIT, 5, n=4)
    assert bytes(got[0]) == cpu_label(COMMIT, 5, 4)


def test_multi_commitment_labels_match_hashlib():
    # per-lane keys: B=5 distinct commitments, non-contiguous indices
    commits = [hashlib.sha256(b"m%d" % i).digest() for i in range(5)]
    idx = np.array([0, 3, 9, 2**33, 77], dtype=np.uint64)
    got = scrypt.scrypt_labels_multi(
        np.stack([np.frombuffer(c, dtype=np.uint8) for c in commits]), idx, n=16)
    for k in range(5):
        want = hashlib.scrypt(commits[k], salt=int(idx[k]).to_bytes(8, "little"),
                              n=16, r=1, p=1, dklen=16)
        assert bytes(got[k]) == want, f"lane {k}"
    # B=1 and empty
    one = scrypt.scrypt_labels_multi(
        np.frombuffer(commits[0], dtype=np.uint8)[None], [7], n=16)
    assert bytes(one[0]) == cpu_label(commits[0], 7, 16)
    empty = scrypt.scrypt_labels_multi(
        np.zeros((0, 32), dtype=np.uint8), np.array([], dtype=np.uint64), n=16)
    assert empty.shape == (0, 16)
    with pytest.raises(ValueError):
        scrypt.scrypt_labels_multi(
            np.zeros((2, 32), dtype=np.uint8), [1, 2, 3], n=16)


@pytest.mark.parametrize("lanes", [37, 129, 148])
def test_multi_commitment_labels_match_hashlib_at_bucket_edges(lanes):
    """The verifier's widths: K2=37 indices of one proof (bucket 64), one
    lane past a bucket (129 -> 256) and four proofs (148 -> 256), every
    lane with its own commitment: each label equals hashlib's, so the
    host pad repeats the last LANE (index and commitment) and the trim
    drops exactly the pad."""
    commits = np.stack([np.frombuffer(hashlib.sha256(b"v%d" % i).digest(),
                                      dtype=np.uint8)
                        for i in range(lanes)])
    idx = (np.arange(lanes, dtype=np.uint64) * np.uint64(2654435761)
           ) % np.uint64(2**34)
    got = scrypt.scrypt_labels_multi(commits, idx, n=16)
    assert got.shape == (lanes, scrypt.LABEL_BYTES)
    for k in range(lanes):
        assert bytes(got[k]) == cpu_label(bytes(commits[k]), int(idx[k]),
                                          16), f"lane {k} of {lanes}"


def test_sha256_words_vs_hashlib():
    from spacemesh_tpu.ops import sha256 as s
    for msg in (b"", b"abc", b"x" * 55, b"y" * 56, b"z" * 200):
        got = np.asarray(s.sha256_words(np.asarray(s.pad_message_np(msg))))
        want = np.frombuffer(hashlib.sha256(msg).digest(), dtype=">u4")
        assert np.array_equal(got.astype(">u4"), want), f"sha256 mismatch len={len(msg)}"


def test_label_shape_and_determinism():
    idx = np.arange(33, dtype=np.uint64)
    a = scrypt.scrypt_labels(COMMIT, idx, n=8)
    b = scrypt.scrypt_labels(COMMIT, idx, n=8)
    assert a.shape == (33, scrypt.LABEL_BYTES)
    assert np.array_equal(a, b)
