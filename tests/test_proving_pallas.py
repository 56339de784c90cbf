"""Pallas proving-scan kernel vs the XLA reference path (interpret mode)."""

import hashlib

import numpy as np
import pytest

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


# -- the window step: every nonce group of a pass in ONE program ------------


def _window_backends():
    import functools

    import jax

    from spacemesh_tpu.parallel import mesh as pmesh

    def sharded(*a, **kw):
        mesh = pmesh.data_mesh(jax.devices())
        a = list(a)
        a[5], a[6] = (pmesh.replicate(mesh, x) for x in a[5:7])
        return pmesh.prove_window_step_sharded(mesh, *a, **kw)

    return {"xla": proving.prove_scan_step_window,
            "pallas": functools.partial(
                proving_pallas.prove_scan_step_window_pallas,
                interpret=True),
            "mesh": sharded}


@pytest.mark.parametrize("backend", ["xla", "pallas", "mesh"])
def test_window_step_equals_per_group_steps(backend):
    # one window-step program a batch == ``groups`` per-group steps a
    # batch, bit for bit (counts, batch counts, carry), over two batches:
    # the second a ragged tail whose lanes cross 2^32, so the device-made
    # indices carry into the hi word where the host-made ones did
    import jax.numpy as jnp

    groups, ng, cap, b = 3, 4, 8, 1024
    first = (1 << 32) - b - 100
    batches = [(first, b), (first + b, 700)]
    bases = 5 + ng * np.arange(groups)
    cw = jnp.asarray(proving.challenge_words(CH))
    # ~4 hits a nonce a batch: some rows fill partly, some overflow ``cap``
    thr = jnp.uint32(proving.threshold_u32(4, b))
    step = _window_backends()[backend]
    state = proving.init_hit_state(groups * ng, cap)
    ref = [proving.init_hit_state(ng, cap) for _ in range(groups)]
    for start, count in batches:
        idx = np.arange(start, start + b, dtype=np.uint64)
        labels = np.zeros((b, scrypt.LABEL_BYTES), np.uint8)
        labels[:count] = scrypt.scrypt_labels(COMMIT, idx[:count], n=2)
        lw = scrypt.labels_to_words(labels)
        words = [count, start & 0xFFFFFFFF, start >> 32]
        counts, bc, carry = step(
            cw, jnp.asarray(bases, jnp.uint32), jnp.asarray(lw),
            jnp.asarray(words, jnp.uint32), thr, *state,
            n_nonces=ng, max_hits=cap)
        state = (counts, carry)
        lo, hi = scrypt.split_indices(idx)
        want_bc = []
        for g in range(groups):
            c, gbc, h = proving.prove_scan_step_jit(
                cw, jnp.uint32(bases[g]), jnp.asarray(lo), jnp.asarray(hi),
                jnp.asarray(lw), thr, *ref[g], *map(jnp.uint32, words),
                n_nonces=ng, max_hits=cap)
            ref[g] = (c, h)
            want_bc.append(np.asarray(gbc))
        assert np.array_equal(np.asarray(bc), np.concatenate(want_bc))
        assert np.array_equal(np.asarray(counts), np.concatenate(
            [np.asarray(c) for c, _ in ref]))
        assert np.array_equal(np.asarray(carry), np.concatenate(
            [np.asarray(h) for _, h in ref], axis=1))
    assert np.asarray(bc).shape == (groups * ng,)
    got = np.asarray(state[0])
    assert got.min() < cap < got.max(), "want rows under AND over cap"
    # the carried indices are global, and past 2^32 where they should be
    rows = [proving.decode_hits(*state, k, cap) for k in range(groups * ng)]
    assert all(r == sorted(r) and first <= r[0] and r[-1] < first + b + 700
               for r in rows)
    assert any(r[-1] >= 1 << 32 for r in rows)


def test_lane_indices_carry_into_the_hi_word():
    import jax.numpy as jnp

    start = (7 << 32) - 3
    lo, hi = proving.lane_indices(8, jnp.uint32(start & 0xFFFFFFFF),
                                  jnp.uint32(start >> 32))
    want_lo, want_hi = scrypt.split_indices(
        np.arange(start, start + 8, dtype=np.uint64))
    assert np.array_equal(np.asarray(lo), want_lo)
    assert np.array_equal(np.asarray(hi), want_hi)
    assert lo.dtype == hi.dtype == jnp.uint32


def test_flight_traces_the_kernel_once_and_outside_the_loop_body(monkeypatch):
    # the rolled loop's body calls the per-group step four times; the
    # step (and with it the Pallas kernel's ~10k jnp ops) is traced ONCE,
    # in the window program's own trace before the loop, and the body's
    # calls find it traced: first traced inside the body's nested trace
    # it cost 10-49 s of Python on a v5e's host against 2.9 s (PERF.md
    # section 6, PR 32). Fresh shapes, so nothing here is cached
    import traceback

    import jax.numpy as jnp

    seen = []
    kernel = proving_pallas._kernel

    def watched(*a, **kw):
        seen.append(any(f.name == "sub_batch"
                        for f in traceback.extract_stack()))
        return kernel(*a, **kw)

    monkeypatch.setattr(proving_pallas, "_kernel", watched)
    b, fb, ng, groups, cap = 2048, 4, 3, 4, 5
    lowered = proving_pallas.prove_scan_step_window_pallas.lower(
        jnp.zeros(8, jnp.uint32), jnp.zeros(groups, jnp.uint32),
        jnp.zeros((4, fb * b), jnp.uint32), jnp.zeros(3, jnp.uint32),
        jnp.uint32(1), *proving.init_hit_state(groups * ng, cap),
        n_nonces=ng, max_hits=cap, batch=b, interpret=True)
    assert seen == [False]
    assert "while" in lowered.as_text()
