"""scrypt (N, r=1, p=1) as pure JAX — the POST labeling function.

The reference fills 64 GiB Space Units with 16-byte labels computed by the
post-rs native initializer (CGo/OpenCL; SURVEY.md §2.3, reference
activation/post.go:355). A label is scrypt of the smesher's commitment over
the label index. Here the whole pipeline — PBKDF2-HMAC-SHA256 envelope,
Salsa20/8 core, BlockMix, ROMix with its data-dependent gather — is
branch-free uint32 JAX, batched across labels (the embarrassingly parallel
axis: 2^32 labels per Space Unit).

Label definition (bit-exact against `hashlib.scrypt`, which is our CPU
ground truth in tests):

    label(commitment, i) = scrypt(password=commitment, salt=le64(i),
                                  N=n, r=1, p=1, dklen=16)

Kernel structure (docs/ROMIX_KERNEL.md):

* Salsa20/8 runs in the DIAGONAL-VECTOR formulation: the 4x4 word matrix
  is regrouped into four diagonal vectors of shape (4, B) so every
  quarter-round is ONE vector op over all four quarters at once — 4x
  fewer, 4x wider XLA ops than the scalar-word unrolling, which is what
  the op-dispatch-bound XLA:CPU backend needs (measured 6.4x on the
  ROMix stage; the rowround reuses the same dataflow after a lane roll).
* ROMix keeps V word-major, (N, 32, B): dense u32 tiles on the TPU, the
  data-dependent read one fused per-lane gather, the WHOLE batch in one
  pass (lane chunks of 256 and 1,024 ran 29% and 13% slower than the
  whole batch at 8,192 lanes on a v5e: ROADMAP S3).
* The whole label pipeline — PBKDF2 expand, ROMix, PBKDF2 finish, and
  optionally the VRF min-scan — compiles as ONE jitted program with a
  donated scan carry, so HMAC block state never round-trips through HBM
  between stages; it is verified against hashlib in tests/test_scrypt.py
  and tests/test_romix.py.

There is one kernel and nothing selects it: every entry point
(post/initializer.py, post/verifier.py, parallel/mesh.py) goes through
`scrypt_labels_jit` / `scrypt_labels_with_min`. Where a batch runs (one
device or a lane-sharded mesh) is parallel/mesh.py's `auto_mesh`.

TPU layout note: the batch is the MINOR dimension everywhere — block state
is (32, B) — so u32 tiles are fully dense ((8,128) tiling pads a trailing
dim of 32 by 4x; a trailing dim of B%128==0 pads nothing). Every op is
then a (B,)-wide VPU lane op and the data-dependent V[j] read is a
per-lane gather. V costs N*128 bytes per in-flight label (1 MiB at
mainnet N=8192), so batch size trades HBM for throughput; see
post/initializer.py (batch sizing) and bench.py.
"""

from __future__ import annotations

import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..utils import sanitize, tracing
from .sha256 import byteswap32, hmac_midstates, sha256_compress

LABEL_BYTES = 16  # reference: 16-byte labels, 2^32 per 64 GiB unit

ENV_BUCKETS = "SPACEMESH_SHAPE_BUCKETS"  # "0"/"off" disables bucketing


def shape_bucket(b: int) -> int:
    """The executable lane-count bucket for a batch of ``b`` labels: the
    next power of two (identity when ``b`` already is one).

    Every jitted program here compiles per (static args, input shape) —
    so without bucketing, an init session's ragged tail batch, the
    verifier's variable-count label recomputes, and every bench sweep
    size each mint a fresh executable (17-26s of XLA compile apiece on a
    cold host). Padding the lane axis up to a power-of-two bucket and
    trimming the output caps the executable population at log2(max
    batch) shapes per N; pad lanes repeat the last label index, which
    the VRF min-scan cannot distinguish from the real last lane (same
    value, first-occurrence lane wins — the identical argument the mesh
    pad in post/initializer.py relies on). ``SPACEMESH_SHAPE_BUCKETS=off``
    disables (tests that measure exact shapes)."""
    if b <= 1:
        return max(b, 1)
    if (os.environ.get(ENV_BUCKETS) or "").lower() in ("0", "off", "none"):
        return b
    return 1 << (b - 1).bit_length()


# ROMix's V may take this share of a device's memory; the rest is for a
# program's arguments, outputs and other temporaries (under 1% of V) and
# for whatever else the process keeps there (k2pow batches, the next
# tile's inputs)
V_MEMORY_SHARE = 0.75
# what a platform that reports no memory (the CPU) is taken to have: a
# v5e chip's 16 GiB
UNREPORTED_DEVICE_BYTES = 16 << 30


@functools.lru_cache(maxsize=None)
def _device_bytes(device) -> int:
    stats = device.memory_stats() or {}     # None on the CPU
    return int(stats.get("bytes_limit") or UNREPORTED_DEVICE_BYTES)


def lane_ceiling(n: int, devices=None) -> int:
    """The widest label program ONE device runs, in lanes: the largest
    power of two whose ROMix scratch (``128 * r * N`` bytes a lane, r=1)
    fits in :data:`V_MEMORY_SHARE` of the memory ``memory_stats()``
    reports (the smallest of ``devices``; default: the default device).
    At N=8192 on a 16 GB v5e chip: 8,192 lanes, 8 GiB of V. Nothing sets
    it: a wider batch runs as lane tiles (post/verifier.py). On a mesh
    the verifier's ceiling is chips x this one, and a group up to it is
    ONE tile in its whole-batch power-of-two bucket, in equal slices a
    chip: the padding lands on the last chips (four v5e chips run a 256-
    proof batch at K3 = 37 as one 16,384-lane program, the last chip all
    padding). What the rule does not see: it is static. It reads the
    device's LIMIT, not what is free (``bytes_in_use``), so a process
    holding gigabytes there (an init beside the farm) can still be
    refused a full tile; the share was checked on one kind of chip (any
    limit from 10.7 to 21.3 GiB gives 8,192 lanes at N=8192); and a
    platform reporting nothing counts as a v5e, whatever the host has."""
    devices = list(devices) if devices is not None else jax.devices()[:1]
    room = V_MEMORY_SHARE * min(_device_bytes(d) for d in devices)
    lanes = int(room // (128 * n))
    if lanes < 1:
        raise ValueError(f"scrypt n={n}: one lane's V ({128 * n} bytes) "
                         "does not fit the device")
    return 1 << (lanes.bit_length() - 1)


def _rotl(x, n: int):
    return (x << jnp.uint32(n)) | (x >> jnp.uint32(32 - n))


# Salsa20's 4x4 state regrouped into diagonal vectors: row q of _DIAG
# lists the state words whose quarter-round position is q. In this
# layout the columnround's four quarters are ONE quarter-round over
# (4, B) vectors, and the rowround is the same dataflow after rolling
# each vector q lanes (the standard SIMD salsa trick, cf. the reference
# implementation's core/salsa2012 SSE2 path).
_DIAG = np.array([[0, 5, 10, 15],
                  [4, 9, 14, 3],
                  [8, 13, 2, 7],
                  [12, 1, 6, 11]])
_UNDIAG = np.argsort(_DIAG.ravel())


def salsa20_8(block):
    """Salsa20/8 core. ``block``: (16, ...) u32 LE words (lanes trailing)."""
    a = block[_DIAG[0]]
    b = block[_DIAG[1]]
    c = block[_DIAG[2]]
    d = block[_DIAG[3]]
    for _ in range(4):  # 4 double-rounds = 8 rounds
        # columnround: all four column quarters, one vector quarter-round
        b = b ^ _rotl(a + d, 7)
        c = c ^ _rotl(b + a, 9)
        d = d ^ _rotl(c + b, 13)
        a = a ^ _rotl(d + c, 18)
        # realign diagonals, then the rowround is the same dataflow with
        # the b/d roles mirrored
        b = jnp.roll(b, 1, axis=0)
        c = jnp.roll(c, 2, axis=0)
        d = jnp.roll(d, 3, axis=0)
        d = d ^ _rotl(a + b, 7)
        c = c ^ _rotl(d + a, 9)
        b = b ^ _rotl(c + d, 13)
        a = a ^ _rotl(b + c, 18)
        b = jnp.roll(b, -1, axis=0)
        c = jnp.roll(c, -2, axis=0)
        d = jnp.roll(d, -3, axis=0)
    return jnp.concatenate([a, b, c, d])[_UNDIAG] + block


def blockmix_r1(x):
    """scrypt BlockMix for r=1: x is (32, ...) u32 LE, two 64-byte halves."""
    y0 = salsa20_8(x[0:16] ^ x[16:32])
    y1 = salsa20_8(x[16:32] ^ y0)
    return jnp.concatenate([y0, y1])


def romix_r1(x, n: int, *, mix_phase: bool = True):
    """scrypt ROMix for r=1 over a (32, B) u32 LE block batch. ``n`` static.

    Word-major V layout (n, 32, B): dense u32 tiles on TPU, and the
    data-dependent read is one fused per-lane gather. ``mix_phase=False``
    stops after the fill phase (profiler stage split only).
    """
    b = x.shape[1]
    v0 = jnp.zeros((n, 32, b), dtype=jnp.uint32)

    def fill(i, carry):
        v, xx = carry
        v = lax.dynamic_update_slice_in_dim(v, xx[None], i, axis=0)
        return v, blockmix_r1(xx)

    with jax.named_scope("romix_fill"):
        v, x = lax.fori_loop(0, n, fill, (v0, x))
    if not mix_phase:
        return x

    def mix(_, xx):
        j = xx[16] % jnp.uint32(n)  # Integerify: first word of B_{2r-1}, per lane
        vj = jnp.take_along_axis(
            v, j[None, None, :].astype(jnp.int32), axis=0
        )[0]
        return blockmix_r1(xx ^ vj)

    with jax.named_scope("romix_mix"):
        return lax.fori_loop(0, n, mix, x)


def _hmac_finish(outer_mid, inner_digest):
    """Outer HMAC compression over a 32-byte inner digest batch (8, B)."""
    b = inner_digest.shape[1]
    tail = np.zeros((8, 1), dtype=np.uint32)
    tail[0, 0] = 0x80000000
    tail[7, 0] = (64 + 32) * 8
    block = jnp.concatenate(
        [inner_digest, jnp.broadcast_to(jnp.asarray(tail), (8, b))])
    return sha256_compress(outer_mid, block)


def _pbkdf2_first(inner_mid, outer_mid, idx_lo, idx_hi):
    """PBKDF2(pw, salt=le64(index), c=1, dklen=128) -> (32, B) u32 LE words."""
    b = idx_lo.shape[0]
    out = []
    for i in (1, 2, 3, 4):
        # message = salt le64(index) || be32(i), then SHA padding to one block
        tail = np.zeros((14, 1), dtype=np.uint32)
        tail[0, 0] = i            # be32(block index)
        tail[1, 0] = 0x80000000   # padding start
        tail[13, 0] = (64 + 12) * 8
        block = jnp.concatenate([
            byteswap32(idx_lo)[None],
            byteswap32(idx_hi)[None],
            jnp.broadcast_to(jnp.asarray(tail), (14, b)),
        ])
        digest = _hmac_finish(outer_mid, sha256_compress(inner_mid, block))
        out.append(digest)
    return byteswap32(jnp.concatenate(out))  # repack BE digests as LE words


# The device phases carry names (jax.named_scope: op metadata only, the
# HLO and its fusion are the same) so a device trace can be read by
# phase: pbkdf2_expand, romix_fill, romix_mix, pbkdf2_finish, minscan.


@jax.named_scope("pbkdf2_finish")
def _pbkdf2_second(inner_mid, outer_mid, b_le):
    """PBKDF2(pw, salt=B'||be32(1), c=1) -> 32-byte digests, (8, B) u32 BE."""
    b = b_le.shape[1]
    st = sha256_compress(inner_mid, byteswap32(b_le[0:16]))
    st = sha256_compress(st, byteswap32(b_le[16:32]))
    tail = np.zeros((16, 1), dtype=np.uint32)
    tail[0, 0] = 1            # be32(block index 1)
    tail[1, 0] = 0x80000000   # padding start
    tail[15, 0] = (64 + 132) * 8
    st = sha256_compress(st, jnp.broadcast_to(jnp.asarray(tail), (16, b)))
    return _hmac_finish(outer_mid, st)


@jax.named_scope("pbkdf2_expand")
def _expand(commitment_words, idx_lo, idx_hi):
    # commitment_words: (8,) shared across the batch, or (8, B) per-lane
    # (the batched verifier recomputes labels of many smeshers at once)
    inner_mid, outer_mid = hmac_midstates(commitment_words)
    if inner_mid.ndim == 1:
        inner_mid = inner_mid[:, None]  # broadcast over lanes
        outer_mid = outer_mid[:, None]
    return inner_mid, outer_mid, _pbkdf2_first(inner_mid, outer_mid,
                                               idx_lo, idx_hi)


# standalone per-stage jits for the profiler's stage-timing view
# (tools/profiler.py --romix); labeling goes through the fused
# single-program pipelines below
_stage_expand = jax.jit(_expand)

_stage_romix_xla = jax.jit(romix_r1, static_argnames=("n", "mix_phase"))


@jax.jit
def _stage_finish(inner_mid, outer_mid, blk):
    return _pbkdf2_second(inner_mid, outer_mid, blk)[:4]


def _pads_eagerly(*arrays) -> bool:
    """Whether the eager bucket pad (:func:`_bucket_lanes`) applies: only
    to concrete, single-device inputs. Under a tracer (parallel/mesh.py
    jits around these wrappers) there is nothing to pad eagerly, and a
    multi-device batch was bucketed on the host by its caller."""
    for a in arrays:
        if isinstance(a, jax.core.Tracer):
            return False
        s = getattr(a, "sharding", None)
        if s is not None and len(s.device_set) > 1:
            return False
    return True


def pad_lanes(a: np.ndarray, pad: int) -> np.ndarray:
    """The bucket pad on the HOST: repeat the last lane ``pad`` times
    along the lane (last) axis. What every caller that still holds numpy
    arrays uses (:func:`_run`, post/verifier.py), so that the device sees
    one bucket-sized upload and no eager op."""
    if not pad:
        return a
    return np.concatenate([a, np.repeat(a[..., -1:], pad, axis=-1)],
                          axis=-1)


def _bucket_lanes(commitment_words, idx_lo, idx_hi):
    """Pad the lane axis up to its shape bucket (repeat the last index).
    Returns (cw, lo, hi, valid) with ``valid`` = the caller's lane count
    (trim the output to it), or the inputs unchanged when the batch is
    already bucket-sized. Only ragged DEVICE-resident inputs get here:
    host callers pad in numpy first (:func:`pad_lanes`)."""
    b = int(idx_lo.shape[0])
    bb = shape_bucket(b)
    if bb == b:
        return commitment_words, idx_lo, idx_hi, b
    pad = bb - b
    # eager device ops, each its own small program (compiled per distinct
    # occupancy): the span says what they cost the host while the device
    # runs something else
    with tracing.span("romix.pad", {"valid": b, "batch": bb}
                      if tracing.is_enabled() else None):
        idx_lo = jnp.concatenate(
            [jnp.asarray(idx_lo),
             jnp.broadcast_to(jnp.asarray(idx_lo)[-1:], (pad,))])
        idx_hi = jnp.concatenate(
            [jnp.asarray(idx_hi),
             jnp.broadcast_to(jnp.asarray(idx_hi)[-1:], (pad,))])
        cw = jnp.asarray(commitment_words)
        if cw.ndim == 2:  # per-lane commitments: repeat the last column too
            cw = jnp.concatenate(
                [cw, jnp.broadcast_to(cw[:, -1:], (cw.shape[0], pad))],
                axis=1)
    return cw, idx_lo, idx_hi, b


def compiled_shape_count() -> int:
    """Executables compiled for the fused label pipelines in this
    process — one per distinct (shape, static args). Tests assert shape
    bucketing keeps this flat across ragged batch sizes."""
    return _labels_fused._cache_size() + _labels_min_fused._cache_size()


# --- fused single-program pipelines -------------------------------------


@functools.partial(jax.jit, static_argnames=("n",))
def _labels_fused(commitment_words, idx_lo, idx_hi, *, n: int):
    """expand -> ROMix -> finish as ONE XLA program: PBKDF2/HMAC block
    state stays on device between stages instead of round-tripping
    through HBM as three executables' inputs/outputs."""
    inner_mid, outer_mid, blk = _expand(commitment_words, idx_lo, idx_hi)
    blk = romix_r1(blk, n)
    return _pbkdf2_second(inner_mid, outer_mid, blk)[:4]


def _labels_enqueue(commitment_words, idx_lo, idx_hi, *, n: int):
    """:func:`scrypt_labels_jit` that also says when the label program
    was enqueued and at what width: ``(words, t0_ns, batch)``. The
    caller that fetches ``words`` closes the ``device.flight`` span
    from ``t0_ns`` (:func:`_run`)."""
    valid = None
    if _pads_eagerly(commitment_words, idx_lo, idx_hi):
        commitment_words, idx_lo, idx_hi, valid = _bucket_lanes(
            commitment_words, idx_lo, idx_hi)
    batch = int(idx_lo.shape[0])
    sanitize.on_jit_shape("labels_fused", batch)
    t0 = time.perf_counter_ns()
    # the span covers the ENQUEUE (trace+compile on a cache miss, else
    # async dispatch) — device time shows up in the XLA trace, which the
    # SPACEMESH_TRACE_JAX bridge lines these spans up against
    with tracing.span("romix.dispatch",
                      {"n": n, "batch": batch,
                       "valid": batch if valid is None else valid}
                      if tracing.is_enabled() else None):
        words = _labels_fused(commitment_words, idx_lo, idx_hi, n=n)
    if valid is not None and valid != batch:
        words = words[:, :valid]
    return words, t0, batch


def scrypt_labels_jit(commitment_words, idx_lo, idx_hi, *, n: int):
    """Batch of labels. ``idx_lo/idx_hi``: (B,) u32 halves of label indices.

    Returns (4, B) u32 BE words = B 16-byte labels (batch minor), from
    one fused program. Ragged batches are padded to their power-of-two
    shape bucket and trimmed, so they reuse the bucket's executable
    instead of compiling their own (:func:`shape_bucket`; sharded/traced
    inputs skip the pad — mesh callers pre-bucket on host)."""
    return _labels_enqueue(commitment_words, idx_lo, idx_hi, n=n)[0]


# --- on-device VRF-nonce scan ----------------------------------------------
#
# The VRF nonce is the index of the numerically smallest LE-u128 label seen
# during init. Doing that scan on host (np.lexsort per batch) forces a full
# device->host round trip before every disk write; here it is a jitted
# argmin reduction that runs right after the label batch, device-side, and
# folds into a tiny running-minimum carry. The carry is donated, so across
# batches the scan is a single rolling (6,) u32 buffer:
#
#   carry = [k3, k2, k1, k0, idx_hi, idx_lo]
#
# where k3..k0 are the u32 limbs of the LE-u128 label key, MOST significant
# first (so lexicographic limb compare == u128 compare), and idx is the u64
# global label index of that minimum. Ties keep the earlier index — same
# first-occurrence semantics as np.lexsort.

VRF_CARRY_WORDS = 6
_U32_MAX = 0xFFFFFFFF


def vrf_carry_init(best: tuple[int, int] | None = None,
                   index: int = 0) -> np.ndarray:
    """Fresh (or resumed) host-side carry. ``best`` is the (hi, lo) u64
    halves of the current minimum label value, as stored in metadata."""
    c = np.full((VRF_CARRY_WORDS,), _U32_MAX, dtype=np.uint32)
    if best is not None:
        hi, lo = best
        c[0] = hi >> 32
        c[1] = hi & _U32_MAX
        c[2] = lo >> 32
        c[3] = lo & _U32_MAX
        c[4] = index >> 32
        c[5] = index & _U32_MAX
    return c


def vrf_carry_decode(carry) -> tuple[int, tuple[int, int]] | None:
    """Carry -> (index, (hi, lo)) or None when no label has been scanned."""
    c = np.asarray(carry)
    hi = int(c[0]) << 32 | int(c[1])
    lo = int(c[2]) << 32 | int(c[3])
    if hi == (_U32_MAX << 32 | _U32_MAX) and lo == hi:
        return None
    return int(c[4]) << 32 | int(c[5]), (hi, lo)


@jax.named_scope("minscan")
def _minscan(words, idx_lo, idx_hi, carry):
    # LE-u128 key limbs, most significant first (labels are LE bytes; the
    # (4, B) words are BE within each 4-byte group, so byteswap gives the
    # LE u32 limbs and word order gives significance).
    l3 = byteswap32(words[3])
    l2 = byteswap32(words[2])
    l1 = byteswap32(words[1])
    l0 = byteswap32(words[0])
    ff = jnp.uint32(_U32_MAX)
    m3 = jnp.min(l3)
    eq = l3 == m3
    m2 = jnp.min(jnp.where(eq, l2, ff))
    eq = eq & (l2 == m2)
    m1 = jnp.min(jnp.where(eq, l1, ff))
    eq = eq & (l1 == m1)
    m0 = jnp.min(jnp.where(eq, l0, ff))
    eq = eq & (l0 == m0)
    b = l3.shape[0]
    lane = jnp.min(jnp.where(eq, jnp.arange(b, dtype=jnp.int32),
                             jnp.int32(b)))
    batch = jnp.stack([m3, m2, m1, m0, idx_hi[lane], idx_lo[lane]])
    c3, c2, c1, c0 = carry[0], carry[1], carry[2], carry[3]
    lt = ((m3 < c3)
          | ((m3 == c3) & ((m2 < c2)
             | ((m2 == c2) & ((m1 < c1)
                | ((m1 == c1) & (m0 < c0)))))))
    new = jnp.where(lt, batch, carry)
    return new, new + jnp.uint32(0)


@functools.partial(jax.jit, donate_argnums=(3,))
def _stage_minscan(words, idx_lo, idx_hi, carry):
    """Fold one label batch into the running LE-u128 minimum.

    Returns ``(new_carry, snapshot)``: the donated rolling carry plus an
    independently-buffered copy of the same value, so callers can retain a
    per-batch snapshot while the carry buffer keeps rotating.
    """
    return _minscan(words, idx_lo, idx_hi, carry)


@functools.partial(jax.jit, static_argnames=("n",), donate_argnums=(3,))
def _labels_min_fused(commitment_words, idx_lo, idx_hi, carry, *, n: int):
    inner_mid, outer_mid, blk = _expand(commitment_words, idx_lo, idx_hi)
    blk = romix_r1(blk, n)
    words = _pbkdf2_second(inner_mid, outer_mid, blk)[:4]
    new_carry, snapshot = _minscan(words, idx_lo, idx_hi, carry)
    return words, new_carry, snapshot


def scrypt_labels_with_min(commitment_words, idx_lo, idx_hi, carry, *,
                           n: int):
    """Label batch + running VRF minimum, fully device-side.

    One host call enqueues ONE fused XLA program (PBKDF2 expand, ROMix,
    finish, min-scan); no data returns to host. Returns ``(words,
    new_carry, snapshot)``; ``carry`` is donated. Ragged batches pad to
    their shape bucket with the last index repeated — the min-scan
    cannot tell the pad lanes from the real last lane (same value,
    first-occurrence lane wins), so the carry is exact and only
    ``words`` is trimmed.
    """
    valid = None
    if _pads_eagerly(commitment_words, idx_lo, idx_hi, carry):
        commitment_words, idx_lo, idx_hi, valid = _bucket_lanes(
            commitment_words, idx_lo, idx_hi)
    batch = int(idx_lo.shape[0])
    sanitize.on_jit_shape("labels_min_fused", batch)
    with tracing.span("romix.dispatch",
                      {"n": n, "batch": batch, "minscan": True,
                       "valid": batch if valid is None else valid}
                      if tracing.is_enabled() else None):
        words, new_carry, snap = _labels_min_fused(
            commitment_words, idx_lo, idx_hi, carry, n=n)
    if valid is not None and valid != batch:
        words = words[:, :valid]
    return words, new_carry, snap


def commitment_to_words(commitment: bytes) -> np.ndarray:
    if len(commitment) != 32:
        raise ValueError("commitment must be 32 bytes")
    return np.frombuffer(commitment, dtype=">u4").astype(np.uint32)


def split_indices(indices: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    indices = np.asarray(indices, dtype=np.uint64)
    lo = (indices & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (indices >> np.uint64(32)).astype(np.uint32)
    return lo, hi


def labels_to_bytes(words) -> bytes:
    """(4, B) u32 BE word batch -> concatenated 16-byte labels."""
    return np.asarray(words, dtype=np.uint32).T.astype(">u4").tobytes()


def labels_to_words(labels: np.ndarray) -> np.ndarray:
    """(B, 16) uint8 labels -> (4, B) u32 LE words (proving-hash input)."""
    return np.ascontiguousarray(labels).view("<u4").reshape(-1, 4).T.astype(np.uint32)


@jax.jit
def words_to_le(words):
    """(4, B) BE label words -> LE proving-hash words, on device.

    The device-side twin of the host ``labels_to_bytes`` ->
    ``labels_to_words`` round trip: the verifier feeds the label
    program's words straight into the proving hash (post/verifier.py),
    so the endianness flip the host conversion performs for free happens
    here."""
    return byteswap32(words)


def _check_n(n: int) -> None:
    # RFC 7914: for r=1, N must be a power of two and < 2^(128*r/8) = 2^16
    if n < 2 or n >= 2**16 or (n & (n - 1)) != 0:
        raise ValueError(f"scrypt n must be a power of 2 in [2, 2^16), got {n}")


def _run(cw: np.ndarray, indices: np.ndarray, n: int) -> np.ndarray:
    """Shared tail: split indices, run the jit pipeline, pack (B,16) bytes."""
    if indices.size == 0:
        return np.zeros((0, LABEL_BYTES), dtype=np.uint8)
    lo, hi = split_indices(indices)
    # pad to the bucket here, in numpy: the device gets bucket-sized
    # arrays, so _bucket_lanes has nothing to do and nothing is trimmed
    # on the device
    b = lo.shape[0]
    pad = shape_bucket(b) - b
    lo, hi = pad_lanes(lo, pad), pad_lanes(hi, pad)
    if cw.ndim == 2:
        cw = pad_lanes(cw, pad)
    with tracing.span("romix.upload",
                      {"bytes": cw.nbytes + lo.nbytes + hi.nbytes}
                      if tracing.is_enabled() else None):
        jcw, jlo, jhi = jnp.asarray(cw), jnp.asarray(lo), jnp.asarray(hi)
    words, t0, batch = _labels_enqueue(jcw, jlo, jhi, n=n)
    host = np.asarray(words, dtype=np.uint32)   # blocks: the words land
    # the stretch in which the host had the label program outstanding
    tracing.interval("device.flight", t0,
                     {"program": "labels_fused", "lanes": batch,
                      "d2h_bytes": host.nbytes}
                     if tracing.is_enabled() else None)
    out = np.frombuffer(labels_to_bytes(host[:, :b]), dtype=np.uint8)
    return out.reshape(-1, LABEL_BYTES)


def scrypt_labels_multi(commitments: np.ndarray, indices, *, n: int = 8192
                        ) -> np.ndarray:
    """Labels for (commitment[i], index[i]) pairs — one program, many keys.

    ``commitments``: (B, 32) uint8. Used by the batched verifier to
    recompute labels for many smeshers in a single device pass.
    """
    _check_n(n)
    commitments = np.ascontiguousarray(np.asarray(commitments, dtype=np.uint8))
    if commitments.ndim != 2 or commitments.shape[1] != 32:
        raise ValueError("commitments must be (B, 32) bytes")
    indices = np.atleast_1d(np.asarray(indices)).ravel()
    if indices.shape[0] != commitments.shape[0]:
        raise ValueError("commitments and indices must have equal batch size")
    cw = commitments.view(">u4").astype(np.uint32).T  # (8, B)
    return _run(cw, indices, n)


def scrypt_labels(commitment: bytes, indices, *, n: int = 8192) -> np.ndarray:
    """Compute labels for ``indices`` (any u64 array). Returns (B, 16) uint8."""
    _check_n(n)
    indices = np.atleast_1d(np.asarray(indices)).ravel()
    return _run(commitment_to_words(commitment), indices, n)
