"""SPMD sharding of the POST pipeline over the process-wide topology.

One parallelism axis matters for this workload (SURVEY.md §2.4): the label
batch — spanning one identity's index range, or many identities' ranges
concatenated (multi-smesher DP; per-lane commitments). Everything is lane
arithmetic with no cross-lane dataflow except reductions (init stats, VRF
scan), so: shard the batch axis over the mesh, let XLA all-reduce the
scalar stats over ICI.

Mesh axis names: ``data`` (the lane/batch axis) and ``model`` (reserved
for V-sharded ROMix; size 1). The mesh and its ``NamedSharding`` layouts
are NOT built here — parallel/topology.py constructs them once per
process and this module's entry points consume the persistent catalog
(spacecheck SC010 keeps per-call construction from growing back).
Mainnet-scale example (BASELINE config 5): 16 smeshers x 4 SU on a
v5e-8 = batch lanes striped across 8 chips; each chip labels its stripe
and the host shards disk writes per smesher.

WHERE a batch runs is one rule, :func:`auto_mesh`: every caller that
routes a batch (post/initializer.py, post/prover.py, post/verifier.py,
runtime/scheduler.py, runtime/workloads.py, chip_smoke.py) asks it and
nothing else.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from ..ops import proving, scrypt
from ..ops.sha256 import byteswap32
from . import topology

DATA_AXIS = topology.DATA_AXIS

ENV_MESH = "SPACEMESH_MESH"


def data_mesh(devices=None) -> Mesh:
    """The process topology's mesh over all (or the given) devices.

    Same Mesh OBJECT on every call for a given device count — the
    topology builds each count once, so jit caches key on a stable mesh
    and sharded executables are reused across sessions and tenants."""
    if devices is None:
        return topology.get().layouts().mesh
    return topology.get().layouts_for_devices(list(devices)).mesh


def auto_mesh(batch: int) -> Mesh | None:
    """The mesh a batch of ``batch`` lanes runs on, or None for one device.

    On an accelerator: every visible device when there is more than
    one. On the CPU: a single device. ``SPACEMESH_MESH`` forces either
    way: ``0``/``off`` never shards, ``1``/``on`` takes every visible
    device, an integer >= 2 that many (clipped to the visible devices),
    unset/``auto`` leaves the rule above; anything else raises. None as
    well when the batch does not divide by the device count: the lane
    axis shards evenly or not at all."""
    raw = (os.environ.get(ENV_MESH) or "").strip().lower()
    devs = jax.devices()
    if raw in ("", "auto"):
        want = 1 if jax.default_backend() == "cpu" else len(devs)
    elif raw in ("0", "off", "none", "false"):
        want = 1
    elif raw in ("1", "on"):
        want = len(devs)
    else:
        try:
            want = int(raw)
        except ValueError:
            raise ValueError(f"{ENV_MESH}={raw!r}: expected off/on/auto "
                             "or a device count") from None
        if want < 1:
            raise ValueError(
                f"{ENV_MESH}={raw!r}: device count must be >= 1")
    want = min(want, len(devs))
    if want <= 1 or batch % want:
        return None
    return data_mesh(devs[:want])


def _layouts(mesh: Mesh) -> topology.MeshLayouts:
    return topology.get().layouts_for(mesh)


def _batch_sharding(mesh: Mesh) -> NamedSharding:
    return _layouts(mesh).batch


def lane_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for word-major arrays: (words, B) — shard the minor/lane
    axis, the placement the sharded label entry points use. Served from
    the topology catalog, never constructed per call."""
    return _layouts(mesh).lane


_lane_sharding = lane_sharding  # historical private alias


def replicate(mesh: Mesh, value) -> jax.Array:
    """Place ``value`` replicated across every device in the mesh (the
    VRF-scan carry lives like this between sharded batches). A no-op
    when ``value`` is already resident with this layout — donated
    carries stay on device across a whole pass instead of paying a
    fresh ``device_put`` per batch (topology.MeshLayouts.replicate)."""
    return _layouts(mesh).replicate(value)


def labels_with_min_sharded(mesh: Mesh, commitment_words, idx_lo, idx_hi,
                            carry, *, n: int):
    """Sharded label batch chained to the on-device VRF min-scan.

    Lane axis sharded over the mesh; the (6,) running-minimum carry is
    replicated and donated, and the argmin reduction lowers to ICI
    all-reduces under GSPMD. Returns ``(words, new_carry, snapshot)`` like
    scrypt.scrypt_labels_with_min, with ``words`` lane-sharded so the host
    can fetch and stripe each device's shard to disk independently.
    """
    lay = _layouts(mesh)
    idx_lo = lay.put_batch(idx_lo)
    idx_hi = lay.put_batch(idx_hi)
    cw = jnp.asarray(commitment_words)
    if cw.ndim == 2:
        cw = lay.put_lane(cw)
    return scrypt.scrypt_labels_with_min(cw, idx_lo, idx_hi,
                                         lay.replicate(carry), n=n)


def scrypt_labels_sharded(mesh: Mesh, commitment_words, idx_lo, idx_hi,
                          *, n: int):
    """Label batch sharded over the mesh. Batch size must divide evenly.

    ``commitment_words``: (8,) shared or (8, B) per-lane (multi-identity).
    Returns (4, B) u32 BE words with the lane axis sharded.
    """
    lay = _layouts(mesh)
    idx_lo = lay.put_batch(idx_lo)
    idx_hi = lay.put_batch(idx_hi)
    cw = jnp.asarray(commitment_words)
    if cw.ndim == 2:
        cw = lay.put_lane(cw)
    return scrypt.scrypt_labels_jit(cw, idx_lo, idx_hi, n=n)


def prove_batch_shardings(mesh: Mesh) -> list[NamedSharding]:
    """Where the two arrays of one prove batch's upload go: the (4, B)
    label words lane-sharded, the batch's start/count words replicated
    (``jax.device_put([label_words, meta], prove_batch_shardings(mesh))``
    is the prover's one transfer a batch)."""
    lay = _layouts(mesh)
    return [lay.lane, lay.replicated]


def prove_window_step_sharded(mesh: Mesh, challenge_words, bases,
                              label_words, meta, threshold, hit_counts,
                              hit_carry, *, n_nonces: int, max_hits: int):
    """One sharded streaming-prove window step (the multichip prove path):
    ``proving.prove_scan_step_window`` with the label lanes striped over
    the mesh exactly like ``labels_with_min_sharded`` stripes init
    batches. The program makes its lane indices under the same sharding;
    the Salsa20/8 sweep is embarrassingly parallel per lane, and GSPMD
    lowers the compaction epilogue's small reductions/gathers to ICI
    collectives (one epilogue a scan step over all the window's rows, so
    a quarter of the collectives four per-group epilogues made). The
    donated (hit_counts, hit_carry) state stays replicated (see
    ops/proving.py merge_hits); the prover replicates it via
    ``replicate()`` before the first batch of a pass. Batch size must
    divide by the mesh size — the prover's pad-and-trim already makes
    every batch the full ``batch_labels``.
    """
    lay = _layouts(mesh)
    return proving.prove_scan_step_window(
        jnp.asarray(challenge_words), bases, lay.put_lane(label_words), meta,
        threshold, hit_counts, hit_carry, n_nonces=n_nonces,
        max_hits=max_hits, lane_sharding=lay.batch)


@functools.partial(jax.jit, static_argnames=("n",))
def _init_step(commitment_words, idx_lo, idx_hi, threshold, *, n: int):
    words = scrypt.scrypt_labels_jit(commitment_words, idx_lo, idx_hi, n=n)
    # init statistics, all-reduced across the mesh by XLA:
    #  - how many labels fall under the proving threshold (K1 calibration)
    #  - running minimum of the labels' top-64-bit keys (coarse scan; the
    #    exact LE-u128 argmin is the device carry in ops/scrypt.py
    #    _stage_minscan, used by labels_with_min_sharded above)
    k_hi = byteswap32(words[3]).astype(jnp.uint32)
    k_lo = byteswap32(words[2]).astype(jnp.uint32)
    qualifying = jnp.sum((words[0] < threshold).astype(jnp.int32))
    min_hi = jnp.min(k_hi)
    is_min = k_hi == min_hi
    min_lo = jnp.min(jnp.where(is_min, k_lo, jnp.uint32(0xFFFFFFFF)))
    return words, qualifying, min_hi, min_lo


def init_step_sharded(mesh: Mesh, commitment_words, idx_lo, idx_hi,
                      threshold: int, *, n: int):
    """One sharded init step: labels + global stats (the multichip path).

    The label computation is embarrassingly parallel over lanes; the three
    scalar stats are cross-device reductions XLA lowers to ICI all-reduces.
    """
    lay = _layouts(mesh)
    idx_lo = lay.put_batch(idx_lo)
    idx_hi = lay.put_batch(idx_hi)
    cw = jnp.asarray(commitment_words)
    if cw.ndim == 2:
        cw = lay.put_lane(cw)
    return _init_step(cw, idx_lo, idx_hi, jnp.uint32(threshold), n=n)
