"""Pallas proving-scan kernel vs the XLA reference path (interpret mode)."""

import hashlib

import numpy as np

from spacemesh_tpu.ops import proving, proving_pallas, scrypt

CH = hashlib.sha256(b"pallas-ch").digest()
COMMIT = hashlib.sha256(b"pallas-commit").digest()


def test_pallas_scan_matches_reference():
    total = 1024
    idx = np.arange(total, dtype=np.uint64)
    labels = scrypt.scrypt_labels(COMMIT, idx, n=2)
    t = proving.threshold_u32(200, total)
    got = proving_pallas.proving_scan(CH, 5, idx, labels, t, n_nonces=4)
    assert got.shape == (4, total)
    assert got.any(), "expected some qualifying labels at this threshold"
    for k in range(4):
        vals = proving.proving_hashes(CH, 5 + k, idx, labels)
        assert np.array_equal(got[k], vals < t), f"nonce {k} mismatch"


def test_pallas_scan_padding():
    # batch not a multiple of the lane tile: wrapper pads + trims
    total = 700
    idx = np.arange(total, dtype=np.uint64)
    labels = scrypt.scrypt_labels(COMMIT, idx, n=2)
    t = proving.threshold_u32(100, total)
    got = proving_pallas.proving_scan(CH, 0, idx, labels, t, n_nonces=2)
    assert got.shape == (2, total)
    vals = proving.proving_hashes(CH, 0, idx, labels)
    assert np.array_equal(got[0], vals < t)


def _step_both(count, batch, nonce_base, n_nonces, start=0, max_hits=8):
    """Run the compacted prove step through Pallas (interpret) and XLA on
    the same padded batch; return both (counts, decoded hits) sets."""
    import jax.numpy as jnp

    idx = np.arange(start, start + batch, dtype=np.uint64)
    labels = scrypt.scrypt_labels(COMMIT, idx[:count], n=2)
    padded = np.concatenate(
        [labels, np.zeros((batch - count, labels.shape[1]), labels.dtype)])
    t = proving.threshold_u32(120, count)
    cw = jnp.asarray(proving.challenge_words(CH))
    lo, hi = scrypt.split_indices(idx)
    args = (cw, jnp.uint32(nonce_base), jnp.asarray(lo), jnp.asarray(hi),
            jnp.asarray(scrypt.labels_to_words(padded)), jnp.uint32(t))
    tail = (jnp.uint32(count), jnp.uint32(start & 0xFFFFFFFF),
            jnp.uint32(start >> 32))
    out = []
    for step in (proving.prove_scan_step_jit,
                 lambda *a, **kw: proving_pallas.prove_scan_step_pallas(
                     *a, interpret=True, **kw)):
        counts, carry = proving.init_hit_state(n_nonces, max_hits)
        counts, bc, carry = step(*args, counts, carry, *tail,
                                 n_nonces=n_nonces, max_hits=max_hits)
        out.append((np.asarray(counts),
                    [proving.decode_hits(counts, carry, k, max_hits)
                     for k in range(n_nonces)]))
    # ground truth from the scalar host path, restricted to valid lanes
    want_counts, want_hits = [], []
    for k in range(n_nonces):
        vals = proving.proving_hashes(CH, nonce_base + k, idx[:count], labels)
        hits = np.nonzero(vals < t)[0]
        want_counts.append(len(hits))
        want_hits.append([int(start + i) for i in hits[:max_hits]])
    return out, (np.asarray(want_counts), want_hits)


def test_step_equivalence_unaligned_tail():
    # a ragged tail batch (count % LANE_TILE != 0) is padded to the full
    # shape and masked on device; Pallas and XLA must agree bit-for-bit
    # with the host ground truth, with no pad-lane hits leaking in
    (xla, pallas), (want_counts, want_hits) = _step_both(
        count=700, batch=1024, nonce_base=0, n_nonces=4)
    for counts, hits in (xla, pallas):
        assert np.array_equal(counts, want_counts)
        assert hits == want_hits


def test_step_equivalence_window_crossing_group_boundary():
    # nonce window straddling a group boundary (base 24 with 16 nonces
    # covers groups 1 and 2): both kernels must key every nonce correctly
    (xla, pallas), (want_counts, want_hits) = _step_both(
        count=1024, batch=1024, nonce_base=24, n_nonces=16)
    for counts, hits in (xla, pallas):
        assert np.array_equal(counts, want_counts)
        assert hits == want_hits
