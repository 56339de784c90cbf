"""POST verification: recompute-and-check, batched across proofs.

The PostVerifier equivalent (reference activation/post_verifier.go:122-405
runs a CGo worker pool; validation semantics activation/validation.go:182).
TPU-first design: verification of MANY proofs is one batched label
recompute — all (proof, index) pairs are flattened into one lane batch
that goes to the device as ONE flight (one upload, one blocking fetch):
a label program and a proving-hash program, or, for a batch wider than
the device's lane ceiling (ops/scrypt.lane_ceiling: 8,192 lanes of
N=8192 on a 16 GB chip), one such pair per lane tile, enqueued back to
back — instead of a per-proof worker pool. The K3 spot-check subset (reference validation.go:206 PostSubset)
subsamples each proof's indices deterministically from a verifier seed.

Also verifies the k2pow witness (ops/pow.py replaces RandomX behind the
same seam).
"""

from __future__ import annotations

import dataclasses
import hashlib
import time

import jax
import numpy as np

from ..ops import pow as k2pow
from ..ops import proving, scrypt
from ..parallel import mesh as pmesh
from ..parallel import topology
from ..utils import metrics, tracing
from .prover import Proof, ProofParams


@dataclasses.dataclass
class VerifyItem:
    """One proof plus the identity/geometry it claims to cover."""

    proof: Proof
    challenge: bytes
    node_id: bytes
    commitment: bytes
    scrypt_n: int
    total_labels: int


def k3_subset(item: VerifyItem, k3: int, seed: bytes) -> list[int]:
    """K3-subsample of the proof's indices, keyed by the VERIFIER's seed.

    The seed must be unpredictable to the prover (reference
    validation.go:206 seeds PostSubset by the verifying node's id): a
    prover who can predict the sampled positions could stuff the k2-k3
    unsampled slots with garbage indices.
    """
    idx = item.proof.indices
    if k3 >= len(idx):
        return list(idx)
    h = hashlib.sha256(seed + item.challenge + item.node_id).digest()
    rng = np.random.default_rng(np.frombuffer(h[:8], dtype=np.uint64)[0])
    pick = rng.choice(len(idx), size=k3, replace=False)
    return [idx[i] for i in sorted(pick)]


def verify_many(items: list[VerifyItem], params: ProofParams | None = None,
                seed: bytes | None = None) -> list[bool]:
    """Verify a batch of proofs; returns per-proof validity.

    One device flight per ``scrypt_n`` group over the union of all
    spot-checked indices — the TPU replacement for the reference's
    worker-pool verify (proofs are lanes, not queue items). A flight is
    one label program and one proving-hash program; a group with more
    lanes than the device's ceiling (ops/scrypt.lane_ceiling) runs as
    several, one per lane tile, in the same flight: a batch of any size
    runs on the chip.

    ``seed`` keys the K3 spot-check subset; by default a fresh random seed
    is drawn per call so provers cannot predict which indices get checked.
    Pass an explicit seed only for reproducible verification (tests,
    deterministic replay).

    Traced as one ``post.verify`` span whose attributes count the call
    where the work happens (docs/OBSERVABILITY.md): distinct ``proofs``
    (a proof object repeated in ``items`` is the farm's power-of-two
    padding, not a proof), those ``host_rejected``, the ``lanes_valid``
    K3 indices they send to the device, the ``lanes`` dispatched after
    both paddings, the lane ``tiles`` (label programs) they went as,
    blocking device->host ``syncs``, ``h2d_bytes`` and ``d2h_bytes``;
    and where the lanes ran: ``chips``, the mesh size of the widest
    tile (1 off a mesh), and per chip of it ``chip_lanes_valid`` and
    ``chip_lanes``, the real and the dispatched lanes summed over the
    tiles (a tile on k chips holds width/k consecutive lanes on each,
    so a tile's padding, at its end, lands on its last chips).
    """
    import os

    p = params or ProofParams()
    if seed is None:
        seed = os.urandom(32)
    # the span holds this dict: filled in as the call goes
    tr = ({"proofs": 0, "host_rejected": 0, "lanes_valid": 0, "lanes": 0,
           "tiles": 0, "syncs": 0, "h2d_bytes": 0, "d2h_bytes": 0,
           "chips": 1, "chip_lanes_valid": [], "chip_lanes": []}
          if tracing.is_enabled() else None)
    with tracing.span("post.verify", tr):
        return _verify_many(items, p, seed, tr)


def _lane_tiles(b: int, n: int) -> list[tuple[int, int]]:
    """``(first lane, width)`` of the label programs a group of ``b``
    lanes at scrypt ``n`` runs as: full tiles at the lane ceiling of
    where such a batch runs (ops/scrypt.lane_ceiling lanes on each chip
    of its mesh), then what remains in its power-of-two shape bucket. So
    the executable population stays the buckets up to the ceiling, and
    at ``b`` up to the ceiling there is one tile: the group's bucket.
    On a mesh that ceiling is the mesh's (chips x the per-chip one), so
    a group up to it is ONE tile in its whole-batch bucket, sharded in
    equal slices: its padding, at the end, lands on the last chips (a
    node's 256-proof batch at K3 = 37 on four v5e chips: 8,880-9,472
    real lanes in 16,384, two chips full, one part full, one all
    padding)."""
    mesh = pmesh.auto_mesh(scrypt.shape_bucket(b))
    if mesh is None:
        ceiling = scrypt.lane_ceiling(n)
    else:
        ceiling = mesh.size * scrypt.lane_ceiling(n, mesh.devices.flat)
    tiles = [(at, ceiling) for at in range(0, b - ceiling + 1, ceiling)]
    rest = b % ceiling
    if rest:
        tiles.append((b - rest, scrypt.shape_bucket(rest)))
    return tiles


def _count_chip_lanes(tr: dict, tiles: list, chips: list, b: int) -> None:
    """Add one flight's lanes to the span's per-chip counts: a tile of
    ``width`` lanes on k chips puts lanes ``[at + c*width/k, at +
    (c+1)*width/k)`` on chip c (the lane axis shards in equal slices, in
    order), of which those below ``b`` are real; a tile off a mesh is
    chip 0's."""
    for key in ("chip_lanes_valid", "chip_lanes"):
        tr[key] += [0] * (max(chips) - len(tr[key]))
    for (at, width), k in zip(tiles, chips):
        per = width // k
        for c in range(k):
            tr["chip_lanes_valid"][c] += max(0, min(per, b - at - c * per))
            tr["chip_lanes"][c] += per


def _verify_many(items: list[VerifyItem], p: ProofParams, seed: bytes,
                 tr: dict | None) -> list[bool]:
    results = [True] * len(items)

    # 1) structural + pow checks (host, cheap)
    flat_idx: list[int] = []
    flat_owner: list[int] = []
    seen: set[int] = set()
    with tracing.span("post.verify.checks",
                      {"proofs": len(items)} if tr is not None else None):
        for i, it in enumerate(items):
            pr = it.proof
            first = tr is not None and id(it) not in seen
            if first:
                seen.add(id(it))
                tr["proofs"] += 1
            if (len(pr.indices) < p.k2
                    or len(set(pr.indices)) != len(pr.indices)
                    or any(not (0 <= j < it.total_labels)
                           for j in pr.indices)
                    or not k2pow.verify(it.challenge, it.node_id,
                                        p.pow_difficulty, pr.pow_nonce)):
                results[i] = False
                if first:
                    tr["host_rejected"] += 1
                continue
            subset = k3_subset(it, p.k3, seed)
            if first:
                tr["lanes_valid"] += len(subset)
            for j in subset:
                flat_idx.append(j)
                flat_owner.append(i)
    if not flat_idx:
        return results

    # 2) one flight of label recompute + proving hash over ALL proofs.
    # scrypt_n must be uniform per compiled program; group by n (usually 1).
    with tracing.span("post.verify.pack"):
        owners = np.array(flat_owner)
        idx = np.array(flat_idx, dtype=np.uint64)
        commits = np.stack([
            np.frombuffer(items[o].commitment, dtype=np.uint8)
            for o in flat_owner])
        chals = np.stack([
            np.frombuffer(items[o].challenge, dtype="<u4").astype(np.uint32)
            for o in flat_owner]).T  # (8, B)
        nonces = np.array([items[o].proof.nonce for o in flat_owner],
                          dtype=np.uint32)
        values = np.empty(len(idx), dtype=np.uint32)
    for n in sorted({items[o].scrypt_n for o in flat_owner}):
        with tracing.span("post.verify.pack"):
            sel = np.array([items[o].scrypt_n == n for o in flat_owner])
            b = int(sel.sum())
            lo, hi = scrypt.split_indices(idx[sel])
            cw8 = commits[sel].view(">u4").astype(np.uint32).T  # (8, b)
            group = (cw8, chals[:, sel], nonces[sel], lo, hi)
            # cut the flat batch into lane tiles and pad each to its
            # width HERE, in numpy (pad lanes repeat the last one,
            # trimmed after the fetch): the device gets bucket-sized
            # batches, so one executable of each program serves every
            # occupancy of a bucket and no eager device op pads or trims
            tiles = _lane_tiles(b, n)
            host, where, chips = [], [], []
            for at, width in tiles:
                take = min(width, b - at)
                host.append([scrypt.pad_lanes(a[..., at:at + take],
                                              width - take) for a in group])
                # a tile is a label batch like any other, so it shards
                # like one (parallel/mesh.py auto_mesh). Placement is
                # the only thing a mesh changes.
                mesh = pmesh.auto_mesh(width)
                chips.append(1 if mesh is None else mesh.size)
                if mesh is None:
                    where.append(None)
                else:
                    lay = topology.get().layouts_for(mesh)
                    where.append([lay.lane, lay.lane, lay.batch, lay.batch,
                                  lay.batch])
            h2d = sum(a.nbytes for tile in host for a in tile)
            bb = sum(width for _at, width in tiles)
        with tracing.span("romix.upload",
                          {"bytes": h2d} if tr is not None else None):
            dev = jax.device_put(host, where)
        # one flight: per tile the label program, the endianness flip
        # and the proving hash are enqueued back to back, tile after
        # tile, and only the hash values come back, in one fetch after
        # the last enqueue. The label pipeline emits BE word groups, the
        # proving hash eats LE; the words never leave the device in
        # between.
        t0 = time.perf_counter_ns()
        out = []
        for (cw8, chal_b, nonce_b, lo, hi), (_at, width) in zip(dev, tiles):
            out.append(proving.proving_hash_jit(
                chal_b, nonce_b, lo, hi, scrypt.words_to_le(
                    scrypt.scrypt_labels_jit(cw8, lo, hi, n=n))))
            metrics.post_verify_label_programs.inc(lanes=width)
        vals = jax.device_get(out)
        # tiles are powers of two: the widest one shards the widest
        metrics.post_verify_mesh_devices.set(max(chips))
        if tr is not None:
            tracing.interval("device.flight", t0,
                             {"program": "labels_proving", "lanes": bb,
                              "tiles": len(out), "d2h_bytes": 4 * bb,
                              "chips": max(chips)})
            tr["lanes"] += bb
            tr["tiles"] += len(out)
            tr["syncs"] += 1
            tr["h2d_bytes"] += h2d
            tr["d2h_bytes"] += 4 * bb
            tr["chips"] = max(tr["chips"], *chips)
            _count_chip_lanes(tr, tiles, chips, b)
        # each tile's own lanes, its padding dropped
        values[sel] = np.concatenate(
            [v[:b - at] for v, (at, _width) in zip(vals, tiles)])

    # 3) threshold check per item
    with tracing.span("post.verify.threshold"):
        thr = np.array([proving.threshold_u32(p.k1, items[o].total_labels)
                        for o in flat_owner], dtype=np.uint64)
        bad_owners = set(owners[values >= thr].tolist())
        for o in bad_owners:
            results[o] = False
    return results


def verify(item: VerifyItem, params: ProofParams | None = None,
           seed: bytes | None = None) -> bool:
    return verify_many([item], params, seed)[0]
