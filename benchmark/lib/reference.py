"""Plain references, independent of the code under test.

* a label is ``hashlib.scrypt(commitment, salt=le64(index), n=N, r=1,
  p=1, dklen=16)`` (go-spacemesh activation/post.go:155 -> post-rs);
* the proving hash is one Salsa20/8 core application (written here in
  numpy from the Salsa20 specification, with the feed-forward add) over
  ``challenge(8 LE words) || nonce || idx_lo || idx_hi || 0 ||
  label(4 LE words)``, word 0 of the output; a label qualifies when that
  value is under ``floor(k1 * 2^32 / total_labels)``;
* a k2pow witness holds when ``sha256(challenge || node_id ||
  le64(nonce)) < difficulty`` as big-endian byte strings;
* a proof is valid when it carries at least K2 distinct in-range
  indices, its k2pow witness holds, and every index of the verifier's
  K3 subset qualifies. The subset rule is the deployment's
  (validation.go:206 PostSubset keyed by the verifier's seed); the
  program's concrete sampling is part of its wire-visible behaviour, so
  it is restated here, not imported.
"""

from __future__ import annotations

import hashlib

import numpy as np

LABEL_BYTES = 16


def label(commitment: bytes, index: int, n: int) -> bytes:
    return hashlib.scrypt(commitment, salt=int(index).to_bytes(8, "little"),
                          n=n, r=1, p=1, dklen=LABEL_BYTES,
                          maxmem=256 * 1024 * 1024)


def _rotl(x, n):
    return ((x << np.uint32(n)) | (x >> np.uint32(32 - n))).astype(np.uint32)


def salsa20_8(block: np.ndarray) -> np.ndarray:
    """Salsa20/8 core over a (16,) or (16, B) uint32 array (spec order:
    four double rounds of columnround + rowround, then add the input)."""
    x = [np.asarray(block[i], dtype=np.uint32) for i in range(16)]
    z = list(x)

    def qr(a, b, c, d):
        with np.errstate(over="ignore"):
            z[b] = z[b] ^ _rotl(z[a] + z[d], 7)
            z[c] = z[c] ^ _rotl(z[b] + z[a], 9)
            z[d] = z[d] ^ _rotl(z[c] + z[b], 13)
            z[a] = z[a] ^ _rotl(z[d] + z[c], 18)

    for _ in range(4):
        qr(0, 4, 8, 12); qr(5, 9, 13, 1)      # noqa: E702 columnround
        qr(10, 14, 2, 6); qr(15, 3, 7, 11)    # noqa: E702
        qr(0, 1, 2, 3); qr(5, 6, 7, 4)        # noqa: E702 rowround
        qr(10, 11, 8, 9); qr(15, 12, 13, 14)  # noqa: E702
    with np.errstate(over="ignore"):
        return np.stack([(z[i] + x[i]).astype(np.uint32)
                         for i in range(16)])


def proving_value(challenge: bytes, nonce: int, index: int,
                  label_bytes: bytes) -> int:
    state = np.zeros(16, dtype=np.uint32)
    state[0:8] = np.frombuffer(challenge, dtype="<u4")
    state[8] = nonce & 0xFFFFFFFF
    state[9] = index & 0xFFFFFFFF
    state[10] = (index >> 32) & 0xFFFFFFFF
    state[12:16] = np.frombuffer(label_bytes, dtype="<u4")
    return int(salsa20_8(state)[0])


def threshold(k1: int, total_labels: int) -> int:
    return min((k1 << 32) // total_labels, (1 << 32) - 1)


def k2pow_ok(challenge: bytes, node_id: bytes, difficulty: bytes,
             nonce: int) -> bool:
    return hashlib.sha256(challenge + node_id
                          + int(nonce).to_bytes(8, "little")).digest() \
        < difficulty


def k3_subset(indices: list, k3: int, seed: bytes, challenge: bytes,
              node_id: bytes) -> list:
    """The verifier's K3 sample of a proof's indices: all of them when
    ``k3 >= len``, else ``numpy.random.default_rng(le64(sha256(seed ||
    challenge || node_id)[:8])).choice(len, k3, replace=False)``,
    positions ascending."""
    if k3 >= len(indices):
        return list(indices)
    h = hashlib.sha256(seed + challenge + node_id).digest()
    rng = np.random.default_rng(int.from_bytes(h[:8], "little"))
    pick = rng.choice(len(indices), size=k3, replace=False)
    return [indices[i] for i in sorted(pick)]


def index_qualifies(*, commitment: bytes, challenge: bytes, nonce: int,
                    index: int, scrypt_n: int, k1: int,
                    total_labels: int) -> bool:
    lab = label(commitment, index, scrypt_n)
    return proving_value(challenge, nonce, index, lab) \
        < threshold(k1, total_labels)


def verify_post(*, indices: list, nonce: int, pow_nonce: int,
                challenge: bytes, node_id: bytes, commitment: bytes,
                scrypt_n: int, total_labels: int, k1: int, k2: int,
                k3: int, pow_difficulty: bytes, seed: bytes) -> bool:
    if (len(indices) < k2 or len(set(indices)) != len(indices)
            or any(not 0 <= j < total_labels for j in indices)
            or not k2pow_ok(challenge, node_id, pow_difficulty,
                            pow_nonce)):
        return False
    return all(index_qualifies(commitment=commitment, challenge=challenge,
                               nonce=nonce, index=j, scrypt_n=scrypt_n,
                               k1=k1, total_labels=total_labels)
               for j in k3_subset(indices, k3, seed, challenge, node_id))


def vrf_min_index(raw: bytes) -> int:
    """Index of the smallest little-endian u128 label in a store's bytes
    (first occurrence): the VRF nonce."""
    halves = np.frombuffer(raw, dtype="<u8").reshape(-1, 2)
    return int(np.lexsort((halves[:, 0], halves[:, 1]))[0])
