"""The pool of real POST proofs the verifyd cells verify.

Made once per checkout by the system's own init and prover at the
configuration's widths (``identities`` stores of ``store_units x
store_labels_per_unit`` labels, ``challenges_per_identity`` proofs
each), then kept as a JSON file under ``.cache/benchmark/fixtures/``
keyed by the configuration's sizes and ``fixture_seed``. The pool does
NOT depend on ``--seed``: making 256 real proofs takes a minute, every
run is a new process, and the verifier's cost does not depend on which
valid proof it checks. ``--seed`` draws everything else (arrivals,
order of clients, which ATXs are invalid and how, every message and
signature, the verifier's K3 seed).

Each proof's challenge is a member of one poet round of
``poet_members`` leaves (mainnet's order: 2^20, so a membership proof
carries 20 sibling hashes); the tree is built once with the pool
(about 5 s) and only each proof's member, leaf index and path are kept.

Beside each proof the pool keeps one *swap*: an in-range index that is
not in the proof, the position it replaces, and whether it qualifies,
by the plain reference (``lib/reference.py``), so the expected verdict
of a proof with one swapped index is exact and not "almost surely
False".
"""

from __future__ import annotations

import hashlib
import json
import shutil
import sys
import time
from pathlib import Path

from . import reference

POOL_SCHEMA = 3
_KEYS = ("scrypt_n", "k1", "k2", "pow_difficulty", "store_units",
         "store_labels_per_unit", "identities", "challenges_per_identity",
         "fixture_seed", "poet_members")


def _derive(seed, tag: str) -> bytes:
    return hashlib.sha256(f"benchmark/pool/{seed}/{tag}".encode()).digest()


def pool_key(cfg: dict) -> str:
    doc = json.dumps([POOL_SCHEMA] + [cfg[k] for k in _KEYS])
    return hashlib.sha256(doc.encode()).hexdigest()[:16]


def _merkle_levels(leaves: list) -> list:
    """Every level of the poet membership tree, leaves first, by the
    system's own node rule (consensus/poet.merkle_root)."""
    from spacemesh_tpu.core.hashing import sum256

    level = [sum256(m) for m in leaves]
    levels = [level]
    while len(level) > 1:
        nxt = [sum256(level[i], level[i + 1])
               for i in range(0, len(level) - 1, 2)]
        if len(level) % 2:
            nxt.append(level[-1])
        levels.append(nxt)
        level = nxt
    return levels


def _merkle_nodes(levels: list, index: int) -> list:
    nodes, i = [], index
    for level in levels[:-1]:
        sib = i ^ 1
        if sib < len(level):
            nodes.append(level[sib])
        i //= 2
    return nodes


def build(cfg: dict, work_dir: Path, log) -> dict:
    from spacemesh_tpu.core.signing import EdSigner
    from spacemesh_tpu.post import initializer
    from spacemesh_tpu.post.prover import ProofParams, Prover

    n = int(cfg["scrypt_n"])
    units, lpu = int(cfg["store_units"]), int(cfg["store_labels_per_unit"])
    total = units * lpu
    diff = bytes.fromhex(cfg["pow_difficulty"])
    params = ProofParams(k1=int(cfg["k1"]), k2=int(cfg["k2"]),
                         k3=int(cfg["k2"]), pow_difficulty=diff)
    fs = cfg["fixture_seed"]
    members = [_derive(fs, f"poet-member-{k}")
               for k in range(int(cfg["poet_members"]))]
    levels = _merkle_levels(members)
    root = levels[-1][0]
    identities, proofs = [], []
    t0 = time.perf_counter()
    for i in range(int(cfg["identities"])):
        key = _derive(fs, f"identity-{i}")
        node_id = EdSigner(seed=key).public_key
        commitment = _derive(fs, f"commitment-{i}")
        identities.append({"key": key.hex(), "node_id": node_id.hex(),
                           "commitment": commitment.hex()})
        d = work_dir / f"store-{i}"
        shutil.rmtree(d, ignore_errors=True)
        initializer.initialize(
            d, node_id=node_id, commitment=commitment, num_units=units,
            labels_per_unit=lpu, scrypt_n=n, batch_size=min(total, 8192))
        prover = Prover(d, params)
        for c in range(int(cfg["challenges_per_identity"])):
            challenge = _derive(fs, f"challenge-{i}-{c}")
            p = prover.prove(challenge)
            assert len(p.indices) == params.k2
            k = len(proofs)
            pos = k % params.k2
            swap = next(j for j in range((k * 7919) % total, 2 * total)
                        if j % total not in p.indices) % total
            leaf = (k * 2654435761) % len(members)   # spread over the tree
            proofs.append({
                "identity": i, "challenge": challenge.hex(),
                "nonce": p.nonce, "indices": list(p.indices),
                "pow_nonce": p.pow_nonce,
                "swap_pos": pos, "swap_index": swap,
                "swap_qualifies": reference.index_qualifies(
                    commitment=commitment, challenge=challenge,
                    nonce=p.nonce, index=swap, scrypt_n=n, k1=params.k1,
                    total_labels=total),
                "member": members[leaf].hex(), "leaf_index": leaf,
                "leaf_nodes": [x.hex() for x in _merkle_nodes(levels, leaf)],
            })
        shutil.rmtree(d, ignore_errors=True)
        log(f"pool: identity {i}: {len(proofs)} proofs "
            f"({time.perf_counter() - t0:.1f} s)")
    return {"schema": POOL_SCHEMA, "key": pool_key(cfg),
            "total_labels": total, "identities": identities,
            "proofs": proofs,
            "poet": {"root": root.hex(), "leaf_count": len(members)}}


def load_or_build(cfg: dict, cache: Path, log) -> tuple[dict, dict]:
    """-> (pool, {"built": bool, "seconds": float})"""
    t0 = time.perf_counter()
    path = cache / "fixtures" / f"atxpool-{pool_key(cfg)}.json"
    if path.exists():
        try:
            with open(path) as f:
                pool = json.load(f)
            if pool.get("key") == pool_key(cfg):
                return pool, {"built": False,
                              "seconds": time.perf_counter() - t0}
        except (OSError, ValueError) as e:
            print(f"benchmark: pool file unreadable ({e}); rebuilding",
                  file=sys.stderr)
    pool = build(cfg, cache / "fixtures" / "work", log)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp")
    with open(tmp, "w") as f:
        json.dump(pool, f)
    tmp.replace(path)
    shutil.rmtree(cache / "fixtures" / "work", ignore_errors=True)
    return pool, {"built": True, "seconds": time.perf_counter() - t0}
