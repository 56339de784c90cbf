"""Pallas TPU kernel for the proving scan — the label-stream hot loop.

Proving sweeps every stored label against a group of nonces
(ops/proving.py:proving_scan_jit). That op is pure streaming: for each
(label lane, nonce) pair one Salsa20/8 application and a threshold
compare — no cross-lane dataflow. This kernel keeps a label tile resident
in VMEM and unrolls the nonce group over it, so each label crosses
HBM->VMEM once per group instead of once per nonce (the XLA version
re-materializes the broadcast state per nonce).

Layout: the lane axis is viewed as (B // 128, 128) rows so every state
word of a tile is one dense (8, 128) u32 vreg — Mosaic has no 1-D vector
layout, and a (1, T) row would fill one sublane in eight. Inputs:
  scalars (11,) u32 in SMEM: challenge words 0..7, nonce_base, threshold,
                 valid
  idx_lo, idx_hi (B // 128, 128) u32
  lw    (4, B // 128, 128) u32 little-endian label words
Output:
  bits  (B // 128, 128) u32 — bit k set <=> the lane qualifies under
        nonce_base + k; pad lanes (``lane >= valid``) never qualify, so a
        ragged tail batch shares the full-batch compiled shape.

One u32 of hit bits per lane is the whole kernel output (the (n_nonces, B)
mask would be 4-16x the bytes); the surrounding jit unpacks it, and the
mask never crosses PCIe. A prove session runs
``prove_scan_step_window_pallas``: over each batch of one uploaded FLIGHT
(up to eight batches, post/prover.py FLIGHT_BATCHES) the kernel once per
nonce group of the pass, then ONE compaction epilogue over all the
groups' rows (ops/proving.py scan_window / compact_and_merge, the XLA
window step's), in one program whose loop over the flight's batches is
rolled, and the only D2H of a flight is its one count vector.

Grid: lane tiles of LANE_TILE. ``interpret=True`` runs the kernel on CPU
(the test path); on TPU the same call compiles via Mosaic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import proving

_LANES = 128
LANE_TILE = 8 * _LANES  # one (8, 128) u32 vreg per state word
MAX_NONCES = 32         # hit bits per lane in the kernel's u32 output


def _quarter(x, a, b, c, d):
    def rotl(v, n):
        return (v << jnp.uint32(n)) | (v >> jnp.uint32(32 - n))

    x[b] = x[b] ^ rotl(x[a] + x[d], 7)
    x[c] = x[c] ^ rotl(x[b] + x[a], 9)
    x[d] = x[d] ^ rotl(x[c] + x[b], 13)
    x[a] = x[a] ^ rotl(x[d] + x[c], 18)


def _kernel(sc_ref, lo_ref, hi_ref, lw_ref, out_ref, *, n_nonces: int):
    lo = lo_ref[...]              # (R, 128) u32
    hi = hi_ref[...]
    lw = [lw_ref[i] for i in range(4)]
    nonce0, thr, valid = sc_ref[8], sc_ref[9], sc_ref[10]
    r, c = lo.shape
    # global lane index of each tile lane (2-D iota: Mosaic has no 1-D)
    row = jax.lax.broadcasted_iota(jnp.int32, (r, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (r, c), 1)
    lane = (pl.program_id(0) * r + row) * c + col
    alive = lane.astype(jnp.uint32) < valid
    zeros = jnp.zeros((r, c), jnp.uint32)
    ch = [zeros + sc_ref[i] for i in range(8)]   # challenge rows
    bits = zeros
    for k in range(n_nonces):     # static unroll over the nonce group
        x = list(ch)
        x.append(zeros + (nonce0 + jnp.uint32(k)))
        x.append(lo)
        x.append(hi)
        x.append(zeros)
        x.extend(lw)
        in0 = x[0]
        for _ in range(4):        # Salsa20/8 = 4 double rounds
            _quarter(x, 0, 4, 8, 12)
            _quarter(x, 5, 9, 13, 1)
            _quarter(x, 10, 14, 2, 6)
            _quarter(x, 15, 3, 7, 11)
            _quarter(x, 0, 1, 2, 3)
            _quarter(x, 5, 6, 7, 4)
            _quarter(x, 10, 11, 8, 9)
            _quarter(x, 15, 12, 13, 14)
        hit = ((x[0] + in0) < thr) & alive
        bits = bits | jnp.where(hit, jnp.uint32(1 << k), jnp.uint32(0))
    out_ref[...] = bits


@functools.partial(jax.jit, static_argnames=("n_nonces", "interpret"))
def _scan_pallas(challenge_words, nonce_base, idx_lo, idx_hi, label_words,
                 threshold, valid, *, n_nonces: int, interpret: bool = False):
    """(n_nonces, B) bool mask; batch must divide by ``LANE_TILE``."""
    b = idx_lo.shape[0]
    if b % LANE_TILE:
        raise ValueError(f"batch {b} not a multiple of lane tile {LANE_TILE}")
    if not 1 <= n_nonces <= MAX_NONCES:
        raise ValueError(f"n_nonces {n_nonces} outside [1, {MAX_NONCES}]")
    rows, tile_rows = b // _LANES, LANE_TILE // _LANES
    scalars = jnp.concatenate([
        challenge_words.astype(jnp.uint32),
        jnp.stack([jnp.asarray(nonce_base, jnp.uint32),
                   jnp.asarray(threshold, jnp.uint32),
                   jnp.asarray(valid, jnp.uint32)])])
    lane_spec = pl.BlockSpec((tile_rows, _LANES), lambda i: (i, 0))
    bits = pl.pallas_call(
        functools.partial(_kernel, n_nonces=n_nonces),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.uint32),
        grid=(b // LANE_TILE,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            lane_spec,
            lane_spec,
            pl.BlockSpec((4, tile_rows, _LANES), lambda i: (0, i, 0)),
        ],
        out_specs=lane_spec,
        interpret=interpret,
    )(scalars,
      idx_lo.astype(jnp.uint32).reshape(rows, _LANES),
      idx_hi.astype(jnp.uint32).reshape(rows, _LANES),
      label_words.astype(jnp.uint32).reshape(4, rows, _LANES))
    shifts = jnp.arange(n_nonces, dtype=jnp.uint32)[:, None]
    return ((bits.reshape(1, b) >> shifts) & jnp.uint32(1)).astype(bool)


@functools.partial(jax.jit, static_argnames=("n_nonces", "interpret"))
def proving_scan_pallas(challenge_words, nonce_base, idx_lo, idx_hi,
                        label_words, threshold, *, n_nonces: int,
                        interpret: bool = False):
    """Drop-in for ops.proving.proving_scan_jit.

    Batch size must be a multiple of ``LANE_TILE``.
    """
    b = idx_lo.shape[0]
    return _scan_pallas(challenge_words, nonce_base, idx_lo, idx_hi,
                        label_words, threshold, jnp.uint32(b),
                        n_nonces=n_nonces, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("n_nonces", "max_hits", "batch",
                                    "interpret"),
                   donate_argnums=(5, 6))
def prove_scan_step_window_pallas(challenge_words, bases, label_words, meta,
                                  threshold, hit_counts, hit_carry, *,
                                  n_nonces: int, max_hits: int,
                                  batch: int | None = None,
                                  interpret: bool = False):
    """Pallas-backed twin of ops.proving.prove_scan_step_window: the
    kernel runs once per group of ``bases`` (``n_nonces`` each) over each
    ``batch``-lane scan step of the uploaded flight, then one compaction
    epilogue over all the groups' rows, all in one program (the steps of
    a flight in one rolled loop: four kernel custom-calls and one
    epilogue whatever it holds). Same contract: donated (hit_counts,
    hit_carry) device state, the one D2H a flight its count vector."""
    return proving.scan_window(
        functools.partial(_scan_pallas, n_nonces=n_nonces,
                          interpret=interpret),
        challenge_words, bases, label_words, meta, threshold, hit_counts,
        hit_carry, max_hits=max_hits, batch=batch)


def proving_scan(challenge: bytes, nonce_base: int, indices, labels: np.ndarray,
                 threshold: int, n_nonces: int) -> np.ndarray:
    """Host wrapper mirroring ops.proving host entries. Pads the batch to
    the lane tile. Returns (n_nonces, B) bool."""
    from ..utils import accel
    from .proving import challenge_words
    from .scrypt import labels_to_words, split_indices

    idx = np.atleast_1d(np.asarray(indices, dtype=np.uint64)).ravel()
    b = idx.shape[0]
    pad = (-b) % LANE_TILE
    if pad:
        idx = np.concatenate([idx, np.zeros(pad, np.uint64)])
        labels = np.concatenate(
            [labels, np.zeros((pad, labels.shape[1]), labels.dtype)])
    lo, hi = split_indices(idx)
    mask = proving_scan_pallas(
        jnp.asarray(challenge_words(challenge)), jnp.uint32(nonce_base),
        jnp.asarray(lo), jnp.asarray(hi),
        jnp.asarray(labels_to_words(labels)), jnp.uint32(threshold),
        n_nonces=n_nonces, interpret=accel.pallas_interpret())
    return np.asarray(mask)[:, :b]
