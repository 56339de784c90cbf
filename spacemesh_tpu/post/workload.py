"""Shared reduced-parameter prove workload — ONE copy of the fixture that
bench.py's `post_prove_labels_per_sec` line and `tools/profiler.py --prove`
both measure (the prove-side analogue of verify/workload.py).

Reduced parameters (k1=64 > k2=16, the regime the repo's e2e tests use) and
a trivial k2pow, so the measured quantity is the label scan, not the pow
search. Node id, commitment, challenge and store geometry are fixed: the
winning nonce — and both provers' full proofs — are deterministic, and
``compare_serial_vs_pipelined`` refuses to report a number unless the two
paths produced bit-identical proofs and the verifier accepts them.

Not the yardstick for prove: that is the benchmark's cell
``prove-mainnet.scan`` (BENCHMARK.json, PERF.md), which runs whole proofs
at mainnet's K1=26 / K2=37 through the default Prover on the chip and
checks each against a plain reference prover. This fixture's k1 > k2
regime never needs a second pass and decides its winner early; it goes
with bench.py (ROADMAP D1).
"""

from __future__ import annotations

import hashlib
import time
from pathlib import Path

from . import initializer, verifier
from .prover import Proof, ProofParams, Prover

NODE = hashlib.sha256(b"bench-prove-node").digest()
COMMITMENT = hashlib.sha256(b"bench-prove-commit").digest()
CHALLENGE = hashlib.sha256(b"bench-prove-challenge").digest()
PARAMS = ProofParams(k1=64, k2=16, k3=8, pow_difficulty=bytes([255]) * 32)


def build(data_dir: str | Path, labels: int, batch: int,
          **prover_opts) -> Prover:
    """Init the fixed store under ``data_dir`` and return a Prover over it."""
    initializer.initialize(
        data_dir, node_id=NODE, commitment=COMMITMENT, num_units=1,
        labels_per_unit=labels, scrypt_n=2,
        max_file_size=64 * 1024 * 1024, batch_size=min(batch * 2, 8192))
    return Prover(data_dir, PARAMS, batch_labels=batch, **prover_opts)


def verify_proof(proof: Proof, total_labels: int) -> bool:
    return verifier.verify(verifier.VerifyItem(
        proof=proof, challenge=CHALLENGE, node_id=NODE,
        commitment=COMMITMENT, scrypt_n=2, total_labels=total_labels),
        PARAMS)


def compare_serial_vs_pipelined(prover: Prover, reps: int = 3) -> dict:
    """Best-of-``reps`` seconds for each path over the same store, with the
    proof-identity and verifier gates applied before any number escapes."""
    pow_nonce = prover._pow(CHALLENGE)

    def best_of(fn):
        fn()  # warm: compile + page cache
        t = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            proof = fn()
            t = min(t, time.perf_counter() - t0)
        return proof, t

    try:
        serial_proof, serial_s = best_of(
            lambda: prover._prove_serial(CHALLENGE, pow_nonce))
        pipe_proof, pipe_s = best_of(
            lambda: prover._prove_pipelined(CHALLENGE, pow_nonce))
    finally:
        # the internal entry points skip prove()'s per-session fd cleanup
        prover.store.close()
    if pipe_proof != serial_proof:
        raise RuntimeError(
            f"pipelined proof diverged from serial: "
            f"nonce {pipe_proof.nonce} vs {serial_proof.nonce}")
    if not verify_proof(pipe_proof, prover.meta.total_labels):
        raise RuntimeError("verifier rejected the pipelined proof")
    return {
        "proof": pipe_proof,
        "serial_s": serial_s,
        "pipelined_s": pipe_s,
        "speedup": serial_s / pipe_s if pipe_s > 0 else None,
        "stats": prover.last_stats.as_dict() if prover.last_stats else {},
    }
