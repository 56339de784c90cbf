"""POST proving & verification primitives — TPU-native nonce search.

Reference semantics (post-rs `post-service`, reached through the gRPC seam at
reference api/grpcserver/post_service.go; params reference
activation/post.go:27-61 and config/mainnet.go:187-189):

- A proof over a unit of ``total_labels`` labels is a nonce plus K2 label
  indices whose *proving hash* falls under a difficulty threshold; K1 sets
  the expected number of qualifying labels per nonce, so a nonce "wins"
  with tunable probability. Verification recomputes a K3-subset of the
  submitted indices' labels and re-checks the threshold.

TPU-first redesign (NOT a port): post-rs hashes the label stream with
AES128 keyed by the challenge — fast on CPU AES-NI, hostile on TPU (S-box
table lookups). Our proving hash is one Salsa20/8 application (pure ARX on
u32 lanes, the same core the labeler already uses):

    state = challenge(8 words LE) || nonce || idx_lo || idx_hi || 0
            || label(4 words LE)
    value = salsa20_8(state)[0]          # u32, uniform
    qualifies <=> value < threshold(k1, total_labels)

so proving streams labels through the VPU at full lane width. The
threshold is ``floor(k1 * 2^32 / total_labels)`` giving E[qualifying] = k1
per nonce over the unit.

All functions here are shape-static and jittable; the host-side scheduler
(post/prover.py) feeds label batches and collects qualifying indices.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .scrypt import salsa20_8


def threshold_u32(k1: int, total_labels: int) -> int:
    """Qualifying threshold: E[#qualifying labels per nonce] == k1."""
    if total_labels <= 0:
        raise ValueError("total_labels must be positive")
    t = (k1 << 32) // total_labels
    return min(t, (1 << 32) - 1)


def challenge_words(challenge: bytes) -> np.ndarray:
    if len(challenge) != 32:
        raise ValueError("challenge must be 32 bytes")
    return np.frombuffer(challenge, dtype="<u4").astype(np.uint32)


_challenge_words = challenge_words  # compat alias


@functools.partial(jax.jit, static_argnames=())
def proving_hash_jit(challenge_words, nonce, idx_lo, idx_hi, label_words):
    """Proving-hash values for a batch of labels.

    challenge_words: (8,) u32 LE shared, or (8, B) per-lane (batch verify);
    nonce: scalar u32 shared, or (B,) per-lane; idx_lo/idx_hi: (B,) u32;
    label_words: (4, B) u32 LE (batch minor, as produced by the labeler).
    Returns (B,) u32 hash values.
    """
    b = idx_lo.shape[0]
    with jax.named_scope("proving_hash"):   # op metadata: the phase's name
        ch = challenge_words.astype(jnp.uint32)
        if ch.ndim == 1:
            ch = ch[:, None]
        ch = jnp.broadcast_to(ch, (8, b))
        nv = jnp.broadcast_to(
            jnp.asarray(nonce, jnp.uint32).reshape(-1), (b,))
        state = jnp.concatenate([
            ch,
            nv[None],
            idx_lo[None],
            idx_hi[None],
            jnp.zeros((1, b), jnp.uint32),
            label_words,
        ])
        return salsa20_8(state)[0]


@functools.partial(jax.jit, static_argnames=("n_nonces",))
def proving_scan_jit(challenge_words, nonce_base, idx_lo, idx_hi, label_words,
                     threshold, valid=None, *, n_nonces: int):
    """Evaluate ``n_nonces`` consecutive nonces over one label batch: one
    nonce group's scan KERNEL in plain XLA, the twin of
    ops/proving_pallas.py ``_scan_pallas``.

    Returns (n_nonces, B) bool qualification mask; n_nonces is static so
    the whole sweep is one compiled program. Traced per nonce: the
    per-nonce stacking (rather than one fused (16, n*B) state) keeps each
    Salsa20/8 working set L2-resident — measured ~2x faster on CPU and
    neutral on TPU, where the Pallas kernel is the fast path anyway.
    ``valid`` cuts the pad lanes of a ragged tail batch (lane >= valid
    never qualifies), so every batch of a pass shares one compiled
    shape; per nonce and before the stack: cut after it, the masks of
    several groups concatenated compile 10x slower on the CPU.
    """
    alive = None if valid is None else (
        jnp.arange(idx_lo.shape[0], dtype=jnp.uint32) < valid)

    def one(k):
        vals = proving_hash_jit(challenge_words, nonce_base + jnp.uint32(k),
                                idx_lo, idx_hi, label_words)
        hit = vals < threshold.astype(jnp.uint32)
        return hit if alive is None else hit & alive
    return jnp.stack([one(k) for k in range(n_nonces)])


# --- on-device hit compaction ----------------------------------------------
#
# The streaming prover never copies a qualification mask to the host: each
# batch's hits are compacted on device into ascending (lane, rank) form and
# merged into a *donated* running hit state, so the per-batch D2H is one
# (n_nonces,) count vector (~100-1000x smaller than the mask) and the packed
# (nonce, index) hit pairs cross PCIe once per pass, not once per batch.

HIT_SEGMENT = 64  # lanes per compaction segment; batch must divide by this


def compact_hits(mask, *, max_hits: int):
    """Compact a (n_nonces, B) mask into per-nonce hit positions.

    Returns ``(batch_counts, local_pos, hit_valid)``: true per-nonce hit
    counts (i32), the ascending lane indices of each nonce's first
    ``max_hits`` hits (u32, garbage where invalid), and the validity mask.
    Two-level extraction — segment popcounts, then a gather of only the
    ``max_hits`` segments that actually contain the wanted hits — so the
    cost is one reduction pass over the mask, not a (n_nonces, B) sort.
    """
    n_nonces, b = mask.shape
    nseg = b // HIT_SEGMENT
    m3 = mask.reshape(n_nonces, nseg, HIT_SEGMENT)
    seg_sum = jnp.sum(m3, axis=-1, dtype=jnp.int32)
    seg_csum = jnp.cumsum(seg_sum, axis=1)
    batch_counts = seg_csum[:, -1]
    targets = jnp.arange(1, max_hits + 1, dtype=jnp.int32)
    # segment holding each nonce's j-th hit: the number of segment
    # cumsums below j (a row's cumsums never decrease, so this is
    # searchsorted(row, j, side="left")), by compare-and-count: no loop
    # of dependent steps, which a binary search lowers to; the hits
    # before that segment are the largest cumsum below j (0 if none)
    below = seg_csum[:, None, :] < targets[None, :, None]
    seg = jnp.sum(below, axis=-1, dtype=jnp.int32)
    prev = jnp.max(jnp.where(below, seg_csum[:, None, :], 0), axis=-1)
    segc = jnp.minimum(seg, nseg - 1)
    rank = targets[None, :] - prev             # 1-based rank within segment
    seg_lanes = jnp.take_along_axis(m3, segc[:, :, None], axis=1)
    within = jnp.cumsum(seg_lanes.astype(jnp.int32), axis=-1)
    lane = jnp.sum((within < rank[:, :, None]).astype(jnp.int32), axis=-1)
    local_pos = (segc * HIT_SEGMENT + lane).astype(jnp.uint32)
    hit_valid = targets[None, :] <= batch_counts[:, None]
    return batch_counts, local_pos, hit_valid


def merge_hits(hit_counts, hit_carry, batch_counts, local_pos, hit_valid,
               start_lo, start_hi):
    """Scatter one batch's compacted hits into the running device state.

    ``hit_carry`` is (2, n_nonces, cap) u32 — lo/hi halves of global label
    indices, slot-ordered (ascending) per nonce. Hits beyond ``cap`` drop:
    the prover sizes cap >= k2, and only the first k2 hits per nonce can
    ever appear in a proof. Returns (new_counts, batch_counts, hit_carry);
    callers donate hit_counts/hit_carry so the state rotates in place.
    """
    n_nonces, max_hits = local_pos.shape
    cap = hit_carry.shape[2]
    glo = (start_lo + local_pos).astype(jnp.uint32)
    ghi = (start_hi + (glo < local_pos).astype(jnp.uint32)).astype(jnp.uint32)
    targets = jnp.arange(max_hits, dtype=jnp.int32)
    rows = jnp.broadcast_to(jnp.arange(n_nonces)[:, None], local_pos.shape)
    slots = jnp.where(hit_valid, hit_counts[:, None] + targets[None, :], cap)
    hit_carry = hit_carry.at[0, rows, slots].set(glo, mode="drop")
    hit_carry = hit_carry.at[1, rows, slots].set(ghi, mode="drop")
    return hit_counts + batch_counts, batch_counts, hit_carry


def compact_and_merge(mask, hit_counts, hit_carry, start_lo, start_hi, *,
                      max_hits: int):
    """The epilogue both scan steps share, each phase under its named
    scope (op metadata only: ``scan_compact``, ``scan_merge``)."""
    with jax.named_scope("scan_compact"):
        counts, pos, ok = compact_hits(mask, max_hits=max_hits)
    with jax.named_scope("scan_merge"):
        return merge_hits(hit_counts, hit_carry, counts, pos, ok,
                          start_lo, start_hi)


@functools.partial(jax.jit, static_argnames=("n_nonces", "max_hits"),
                   donate_argnums=(6, 7))
def prove_scan_step_jit(challenge_words, nonce_base, idx_lo, idx_hi,
                        label_words, threshold, hit_counts, hit_carry,
                        valid, start_lo, start_hi, *, n_nonces: int,
                        max_hits: int):
    """One pipelined prove step: scan + compact + merge, all on device.

    ``valid`` masks pad lanes of a ragged tail batch (lane >= valid never
    qualifies), so every batch of a pass shares one compiled shape.
    Returns (hit_counts', batch_counts, hit_carry'); the carries are
    donated and cycle device-side across the pass — the only per-batch
    host fetch is ``batch_counts``.
    """
    with jax.named_scope("scan_kernel"):
        mask = proving_scan_jit(challenge_words, nonce_base, idx_lo, idx_hi,
                                label_words, threshold, valid,
                                n_nonces=n_nonces)
    return compact_and_merge(mask, hit_counts, hit_carry, start_lo,
                             start_hi, max_hits=max_hits)


def lane_indices(b: int, start_lo, start_hi, sharding=None):
    """Global label indices of a batch's ``b`` lanes, made on the device:
    ``start + lane`` as (lo, hi) u32 halves, the carry into the hi word
    taken the way ``merge_hits`` takes it for hit positions. ``sharding``
    pins the lane axis where the label words are lane-sharded."""
    lane = jnp.arange(b, dtype=jnp.uint32)
    if sharding is not None:
        lane = jax.lax.with_sharding_constraint(lane, sharding)
    lo = start_lo + lane
    return lo, start_hi + (lo < lane).astype(jnp.uint32)


def scan_window(group_mask, challenge_words, bases, label_words, meta,
                threshold, hit_counts, hit_carry, *, max_hits: int,
                batch: int | None = None, lane_sharding=None):
    """The window step both backends share: ONE program per FLIGHT of
    label batches. A scan step of it is ``group_mask`` (a nonce group's
    scan kernel, its ``n_nonces`` bound: :func:`proving_scan_jit` or the
    Pallas ``_scan_pallas``) once per base in ``bases`` over one batch,
    the groups' masks stacked group-major, and then ONE compaction
    epilogue (:func:`compact_and_merge`) over every nonce row of the
    window: what an epilogue costs is mostly fixed (its cumsum, gathers
    and two scatters, and the slices and concatenates four per-group
    epilogues needed), so one over 64 rows took a scan step from 0.80 to
    0.43 ms on a v5e; it holds no loop (:func:`compact_hits` finds each
    hit's segment by compare-and-count, where a binary search of 8-9
    dependent steps was half the step), which took the step to 0.20 ms
    in a bare loop (PERF.md section 6).

    ``meta`` is the flight's three u32 words ``[valid, start_lo,
    start_hi]``, uploaded beside ``label_words`` (4, lanes); the lane
    indices are made here (:func:`lane_indices`), not sent. ``batch`` is
    the static width of ONE scan step (kernels + compaction epilogue).
    Label words no wider than it (or ``batch`` None) are one step.
    Wider ones are a flight: the same step
    runs inside one ROLLED ``lax.fori_loop`` over sub-batches ``g = 0 ..
    ceil(valid / batch) - 1`` (a dynamic trip count: a ragged last flight
    runs only the sub-batches that hold labels), sub-batch ``g`` taking
    lanes ``[g * batch, (g + 1) * batch)`` by a dynamic slice, ``valid_g =
    clip(valid - g * batch, 0, batch)`` and ``start_g = start + g *
    batch`` with the carry into the hi word. Rolled, so ``group_mask``
    is lowered once per group whatever the flight holds; it is traced
    once too, before the loop, in the program's own trace.

    The hit state is one pair for the whole window, group-major:
    ``(groups * n_nonces,)`` counts and ``(2, groups * n_nonces, cap)``
    carry; row ``g * n_nonces + k`` is nonce ``bases[g] + k``, the order
    the stacked mask has. Rows are independent in every op of the
    epilogue, so the result is the per-group step's
    (:func:`prove_scan_step_jit`) group by group, bit for bit. Returns
    (hit_counts', batch_counts, hit_carry') like that step, each over
    all the window's nonces, ``batch_counts`` summed over the flight's
    sub-batches."""
    lanes = label_words.shape[1]
    valid, start_lo, start_hi = meta[0], meta[1], meta[2]

    def window_mask(words, valid, start_lo, start_hi):
        idx_lo, idx_hi = lane_indices(words.shape[1], start_lo, start_hi,
                                      lane_sharding)
        with jax.named_scope("scan_kernel"):
            return jnp.concatenate([
                group_mask(challenge_words, bases[g], idx_lo, idx_hi, words,
                           threshold, valid)
                for g in range(bases.shape[0])])

    def one_batch(words, valid, start_lo, start_hi, hit_counts, hit_carry):
        return compact_and_merge(
            window_mask(words, valid, start_lo, start_hi), hit_counts,
            hit_carry, start_lo, start_hi, max_hits=max_hits)

    if batch is None or lanes <= batch:
        return one_batch(label_words, valid, start_lo, start_hi, hit_counts,
                         hit_carry)
    if lanes % batch:
        raise ValueError(f"flight of {lanes} lanes is not a whole number "
                         f"of {batch}-lane scan steps")

    def sub_batch(g, state):
        hit_counts, flight_counts, hit_carry = state
        off = (g * batch).astype(jnp.uint32)
        lo = start_lo + off
        hit_counts, batch_counts, hit_carry = one_batch(
            jax.lax.dynamic_slice_in_dim(label_words, g * batch, batch, 1),
            jnp.minimum(valid - off, jnp.uint32(batch)),
            lo, start_hi + (lo < off).astype(jnp.uint32),
            hit_counts, hit_carry)
        return hit_counts, flight_counts + batch_counts, hit_carry

    # Trace the kernel HERE, in the program's own trace, and drop the
    # result (dead code: the compiler removes it): the loop body's calls
    # then find ``group_mask`` traced. Traced for the first time inside
    # the body's nested trace, the Pallas kernel's ~10k jnp ops cost
    # 10-49 s of Python on a v5e's host where they cost 2.9 s here
    # (PERF.md section 6, PR 32: measured, not explained).
    window_mask(label_words[:, :batch], jnp.minimum(valid, jnp.uint32(batch)),
                start_lo, start_hi)

    # g < steps implies g * batch < valid, so valid - off never wraps
    steps = (valid + jnp.uint32(batch - 1)) // jnp.uint32(batch)
    return jax.lax.fori_loop(
        jnp.int32(0), steps.astype(jnp.int32), sub_batch,
        (hit_counts, jnp.zeros_like(hit_counts), hit_carry))


@functools.partial(jax.jit,
                   static_argnames=("n_nonces", "max_hits", "batch",
                                    "lane_sharding"),
                   donate_argnums=(5, 6))
def prove_scan_step_window(challenge_words, bases, label_words, meta,
                           threshold, hit_counts, hit_carry, *,
                           n_nonces: int, max_hits: int,
                           batch: int | None = None, lane_sharding=None):
    """One pipelined prove step over a whole nonce window in ONE program
    (:func:`scan_window`): every group of ``bases`` through the XLA scan
    kernel, one compaction epilogue over all their rows, over a flight
    of ``batch``-lane scan steps (one step where ``label_words`` is no
    wider), so a flight is one upload, one program call and one
    ``(groups * n_nonces,)`` count vector back."""
    return scan_window(
        functools.partial(proving_scan_jit, n_nonces=n_nonces),
        challenge_words, bases, label_words, meta, threshold, hit_counts,
        hit_carry, max_hits=max_hits, batch=batch,
        lane_sharding=lane_sharding)


def init_hit_state(n_nonces: int, cap: int):
    """Fresh (hit_counts, hit_carry) device state for one prove pass."""
    return (jnp.zeros(n_nonces, jnp.int32),
            jnp.full((2, n_nonces, cap), 0xFFFFFFFF, jnp.uint32))


def decode_hits(hit_counts, hit_carry, nonce_row: int, limit: int
                ) -> list[int]:
    """Host-side: first ``limit`` global label indices of one nonce row."""
    counts = np.asarray(hit_counts)
    carry = np.asarray(hit_carry)
    n = min(int(counts[nonce_row]), carry.shape[2], limit)
    lo = carry[0, nonce_row, :n].astype(np.uint64)
    hi = carry[1, nonce_row, :n].astype(np.uint64)
    return [int(v) for v in (lo | (hi << np.uint64(32)))]


def proving_hashes(challenge: bytes, nonce: int, indices, labels: np.ndarray
                   ) -> np.ndarray:
    """Host entry: hash values for (nonce, labels[i]) pairs.

    ``labels``: (B, 16) uint8 as returned by the labeler. Returns (B,) u32.
    """
    from .scrypt import labels_to_words, split_indices

    cw = challenge_words(challenge)
    lo, hi = split_indices(np.atleast_1d(np.asarray(indices)).ravel())
    lw = labels_to_words(labels)
    out = proving_hash_jit(jnp.asarray(cw), jnp.uint32(nonce),
                           jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(lw))
    return np.asarray(out)
