"""Pallas ROMix variant: contiguous-row (N, T, 32) V + async-copy gathers.

The XLA path (ops/scrypt.py romix_r1) stores V as (N, 32, B) and gathers a (32, B) slab per iteration with a per-lane random row —
one fused XLA gather.  This kernel flips the layout to (N, T, 32) so ONE
LANE'S ROW IS 128 CONTIGUOUS BYTES, then:

* phase 1 (fill): V rows stream VMEM->HBM with double-buffered async
  copies — the write of row i overlaps the BlockMix that produces row
  i+1;
* phase 2 (mix): per-lane gathers are explicit 128-byte DMAs, all T
  in flight together before the single wait-loop (the iteration's
  BlockMix depends on the gathered rows, so cross-iteration overlap is
  impossible — the overlap is across LANES within an iteration).

The Salsa20/8 core is kept fully in registers: the (T, 32) block is
split into 32 per-word (T,) columns once per phase and every quarter
round is elementwise column arithmetic — no per-round ``stack`` /
``concatenate`` relayouts for Mosaic to shuffle through VMEM.  The
block is only materialized as a (T, 32) tile at the DMA boundaries
(fill-buffer stores, Integerify staging, final output).

STATUS: Mosaic refuses this kernel on TPU — the 32-word minor dimension
is a quarter of a 128-lane vreg row, and the ``[:, 16:17]`` Integerify
staging slice is not aligned to that tiling (ROADMAP S4 records the
compiler's messages; a VMEM layout that compiles is that item's work).
So it is in NO default or raced set (ops/autotune.py): the explicit
``SPACEMESH_ROMIX=pallas`` is the only way here, and it raises on
failure.  Interpret mode verifies bit-exactness on CPU
(tests/test_romix_pallas.py — the autotune sweep in
tests/test_romix_autotune.py covers unaligned batches through the
lane-padding wrapper).

Reference workload: activation/post.go:27-61 (labels per unit),
config/mainnet.go:184-190 (N=8192, r=1, p=1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE_TILE = 128


def _rotl(x, n: int):
    return (x << jnp.uint32(n)) | (x >> jnp.uint32(32 - n))


def _quarter(x, a: int, b: int, c: int, d: int):
    x[b] = x[b] ^ _rotl(x[a] + x[d], 7)
    x[c] = x[c] ^ _rotl(x[b] + x[a], 9)
    x[d] = x[d] ^ _rotl(x[c] + x[b], 13)
    x[a] = x[a] ^ _rotl(x[d] + x[c], 18)


def _salsa20_8_cols(block):
    """Salsa20/8 over 16 per-word (T,) columns, fully unrolled in registers."""
    x = list(block)
    for _ in range(4):  # 4 double-rounds = 8 rounds
        _quarter(x, 0, 4, 8, 12)
        _quarter(x, 5, 9, 13, 1)
        _quarter(x, 10, 14, 2, 6)
        _quarter(x, 15, 3, 7, 11)
        _quarter(x, 0, 1, 2, 3)
        _quarter(x, 5, 6, 7, 4)
        _quarter(x, 10, 11, 8, 9)
        _quarter(x, 15, 12, 13, 14)
    return [x[i] + block[i] for i in range(16)]


def _blockmix_cols(cols):
    """scrypt BlockMix r=1 over 32 (T,) u32 columns, lanes major."""
    y0 = _salsa20_8_cols([cols[i] ^ cols[16 + i] for i in range(16)])
    y1 = _salsa20_8_cols([cols[16 + i] ^ y0[i] for i in range(16)])
    return tuple(y0 + y1)


def _to_cols(block):
    """(T, 32) tile -> tuple of 32 (T,) columns (the in-register layout)."""
    return tuple(block[:, i] for i in range(32))


def _to_block(cols):
    """32 (T,) columns -> (T, 32) tile, materialized for a DMA boundary."""
    return jnp.stack(cols, axis=1)


def _romix_kernel(x_ref, o_ref, v_ref, fill_buf, gather_buf, jsm,
                  fill_sem, jsem, gsem, *, n: int, tile: int,
                  mix_phase: bool):
    # ---- phase 1: fill V[i] = x_i, double-buffered writes ----
    def fill(i, cols):
        slot = i % 2

        @pl.when(i >= 2)
        def _():
            # retire the copy that used this slot two iterations ago
            # (same shape/size, so the reconstructed handle's wait
            # matches the outstanding transfer)
            pltpu.make_async_copy(fill_buf.at[slot], v_ref.at[0],
                                  fill_sem.at[slot]).wait()

        fill_buf[slot] = _to_block(cols)
        pltpu.make_async_copy(fill_buf.at[slot], v_ref.at[i],
                              fill_sem.at[slot]).start()
        return _blockmix_cols(cols)

    cols = lax.fori_loop(0, n, fill, _to_cols(x_ref[...]))
    # drain the last two in-flight writes
    for slot in (0, 1):
        pltpu.make_async_copy(fill_buf.at[slot], v_ref.at[0],
                              fill_sem.at[slot]).wait()

    if not mix_phase:  # profiler fill/mix split (tools/profiler.py --romix)
        o_ref[...] = _to_block(cols)
        return

    # ---- phase 2: x = BlockMix(x ^ V[Integerify(x)]), per-lane DMAs ----
    def mix(_, cols):
        # Integerify indices must become SMEM scalars: stage the word-16
        # column through a DMA (vector stores to SMEM don't lower)
        fill_buf[0, :, 16:17] = cols[16][:, None]
        stage = pltpu.make_async_copy(
            fill_buf.at[0, :, 16:17], jsm, jsem)
        stage.start()
        stage.wait()

        def start_lane(lane, _):
            row = (jsm[lane, 0] % jnp.uint32(n)).astype(jnp.int32)
            pltpu.make_async_copy(v_ref.at[row, lane],
                                  gather_buf.at[lane], gsem).start()
            return 0

        lax.fori_loop(0, tile, start_lane, 0)

        def wait_lane(lane, _):
            pltpu.make_async_copy(v_ref.at[0, 0], gather_buf.at[0],
                                  gsem).wait()
            return 0

        lax.fori_loop(0, tile, wait_lane, 0)
        g = gather_buf[...]
        return _blockmix_cols(tuple(cols[k] ^ g[:, k] for k in range(32)))

    o_ref[...] = _to_block(lax.fori_loop(0, n, mix, cols))


def romix_pallas(x, *, n: int, lane_tile: int = LANE_TILE,
                 interpret: bool = False, mix_phase: bool = True):
    """Drop-in for ops.scrypt.romix_r1: x is (32, B) u32; returns same.

    B must be a multiple of ``lane_tile`` (``romix_pallas_padded`` lifts
    that).  ``mix_phase=False`` stops after the fill phase — only the
    profiler's stage-split view uses it.
    """
    b = x.shape[1]
    if b % lane_tile:
        raise ValueError(f"batch {b} not a multiple of tile {lane_tile}")
    xt = x.T  # (B, 32) lanes major: one lane's row is contiguous

    scratch = [
        pltpu.HBM((n, lane_tile, 32), jnp.uint32),    # V
        pltpu.VMEM((2, lane_tile, 32), jnp.uint32),   # fill double-buffer
        pltpu.VMEM((lane_tile, 32), jnp.uint32),      # gathered rows
        pltpu.SMEM((lane_tile, 1), jnp.uint32),       # per-lane j
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA(()),
        pltpu.SemaphoreType.DMA(()),
    ]
    out = pl.pallas_call(
        functools.partial(_romix_kernel, n=n, tile=lane_tile,
                          mix_phase=mix_phase),
        grid=(b // lane_tile,),
        in_specs=[pl.BlockSpec((lane_tile, 32), lambda g: (g, 0))],
        out_specs=pl.BlockSpec((lane_tile, 32), lambda g: (g, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 32), jnp.uint32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(xt)
    return out.T


_romix_pallas_jit = jax.jit(
    romix_pallas, static_argnames=("n", "lane_tile", "interpret",
                                   "mix_phase"))


def romix_pallas_padded(x, *, n: int, lane_tile: int = LANE_TILE,
                        interpret: bool = False, mix_phase: bool = True):
    """``romix_pallas`` for ANY batch size: pads lanes up to the tile.

    The pad lanes run real (wasted) ROMix work — at most ``lane_tile-1``
    extra lanes per call, so callers with steady batch shapes should
    still size batches as tile multiples.  Traceable (jit-safe): the pad
    amount depends only on the static lane count.
    """
    b = x.shape[1]
    pad = -b % lane_tile
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((32, pad), dtype=jnp.uint32)], axis=1)
    out = romix_pallas(x, n=n, lane_tile=lane_tile, interpret=interpret,
                       mix_phase=mix_phase)
    return out[:, :b] if pad else out
