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
    """Run the compacted prove step through XLA (the per-group reference
    step) and Pallas (interpret; the window step over ONE group: the
    same code as over four, with one mask) on the same padded batch;
    return both (counts, decoded hits) sets."""
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
    def window_of_one_group(cw, base, _lo, _hi, lw, thr, counts, carry,
                            *tail, **kw):
        return proving_pallas.prove_scan_step_window_pallas(
            cw, base[None], lw, jnp.stack(tail), thr, counts, carry,
            interpret=True, **kw)

    out = []
    for step in (proving.prove_scan_step_jit, window_of_one_group):
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


_WINDOW_CASES = [(backend, shape, groups)
                 for backend in ("xla", "pallas", "mesh")
                 # a mesh's flight is one batch (post/prover.flight_batches)
                 for shape in (("one",) if backend == "mesh"
                               else ("one", "flight_ragged", "flight_full"))
                 for groups in (1, 4)]


@pytest.mark.parametrize("backend, shape, groups", _WINDOW_CASES)
def test_window_step_equals_per_group_steps(backend, shape, groups):
    # ONE compaction epilogue a scan step over all the window's rows ==
    # the plain per-group step (its own epilogue over its own rows),
    # group by group and sub-batch by sub-batch, bit for bit: counts,
    # the flight's summed batch counts, the carry. "one" is the program
    # with no loop over four consecutive batches (the last ragged);
    # "flight_ragged" one rolled program of eight scan steps whose
    # seventh is ragged and whose eighth is empty; "flight_full" all
    # eight. The lanes cross 2^32 half way through the FIRST scan step,
    # while slots are free: the hits' lo words wrap inside the step, and
    # start_lo carries into start_hi from the flight's second step on
    import jax.numpy as jnp

    ng, cap, b, fb = 8, 4, 1024, 8
    rows = groups * ng
    first = (1 << 32) - b // 2 - 17
    if shape == "one":
        width, kw = b, {}
        calls = [(first + i * b, b) for i in range(3)] + [(first + 3 * b, 700)]
    else:
        width, kw = fb * b, {"batch": b}
        calls = [(first, width if shape == "flight_full" else 6 * b + 100)]
    step = _window_backends()[backend]
    rng = np.random.default_rng(35)
    cw = jnp.asarray(proving.challenge_words(CH))
    bases = 5 + ng * np.arange(groups)
    # ~2.5 hits a row a scan step against 4 slots: rows with none and
    # rows with more than max_hits in ONE step both occur
    thr = jnp.uint32(proving.threshold_u32(5, 2 * b))
    # rows that start at 0, at cap, past cap, and just under it; the
    # slots already filled hold marks the merge must leave alone
    counts0 = np.resize([0, cap, cap - 1, 0, cap + 5, 1, 0, cap - 2],
                        rows).astype(np.int32)
    carry0 = np.full((2, rows, cap), 0xFFFFFFFF, np.uint32)
    for r in range(rows):
        filled = min(counts0[r], cap)
        carry0[0, r, :filled] = 1000 * r + np.arange(filled)
        carry0[1, r, :filled] = 0
    state = (jnp.asarray(counts0), jnp.asarray(carry0))
    ref = [(jnp.asarray(counts0[g * ng:(g + 1) * ng]),
            jnp.asarray(carry0[:, g * ng:(g + 1) * ng]))
           for g in range(groups)]
    seen = []       # (counts before the scan step, its batch counts)
    for start, count in calls:
        lw = rng.integers(0, 1 << 32, size=(4, width), dtype=np.uint32)
        lw[:, count:] = 0
        counts, bc, carry = step(
            cw, jnp.asarray(bases, jnp.uint32), jnp.asarray(lw),
            jnp.asarray([count, start & 0xFFFFFFFF, start >> 32],
                        jnp.uint32),
            thr, *state, n_nonces=ng, max_hits=cap, **kw)
        state = (counts, carry)
        want_bc = np.zeros(rows, np.int64)
        for sub in range(-(-count // b)):
            at = start + sub * b
            lo, hi = scrypt.split_indices(
                np.arange(at, at + b, dtype=np.uint64))
            before = np.concatenate([np.asarray(c) for c, _ in ref])
            outs = [proving.prove_scan_step_jit(
                cw, jnp.uint32(bases[g]), jnp.asarray(lo), jnp.asarray(hi),
                jnp.asarray(lw[:, sub * b:(sub + 1) * b]), thr, *ref[g],
                jnp.uint32(min(b, count - sub * b)),
                jnp.uint32(at & 0xFFFFFFFF), jnp.uint32(at >> 32),
                n_nonces=ng, max_hits=cap) for g in range(groups)]
            ref = [(o[0], o[2]) for o in outs]
            sub_bc = np.concatenate([np.asarray(o[1]) for o in outs])
            seen.append((before, sub_bc))
            want_bc += sub_bc
        assert np.array_equal(np.asarray(bc), want_bc)
        assert np.array_equal(np.asarray(counts), np.concatenate(
            [np.asarray(c) for c, _ in ref]))
        assert np.array_equal(np.asarray(carry), np.concatenate(
            [np.asarray(h) for _, h in ref], axis=1))
    assert len(seen) == {"one": 4, "flight_ragged": 7, "flight_full": 8}[shape]
    # what the cases are here to meet, met in the reference's own figures
    before, sub_bc = (np.stack(x) for x in zip(*seen))
    assert (sub_bc > cap).any(), "no row with more than max_hits in a step"
    assert (sub_bc == 0).any(), "no row with no hit in a step"
    assert ((before == 0) & (sub_bc > 0)).any()
    assert ((before >= cap) & (sub_bc > 0)).any(), "hits past cap drop"
    assert ((before < cap) & (before + sub_bc > cap)).any(), \
        "no row crossed cap inside a step"
    got_counts, got_carry = (np.asarray(x) for x in state)
    # the marks stand, and what was merged is global and past 2^32
    # where it should be
    for r in range(rows):
        filled = min(counts0[r], cap)
        assert np.array_equal(got_carry[0, r, :filled],
                              1000 * r + np.arange(filled))
        new = proving.decode_hits(got_counts, got_carry, r, cap)[filled:]
        assert new == sorted(new)
        assert all(first <= i < calls[-1][0] + calls[-1][1] for i in new)
    his = got_carry[1][got_carry[0] != 0xFFFFFFFF]
    assert (his == 0).any() and (his == 1).any()


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
