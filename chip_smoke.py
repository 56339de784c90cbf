#!/usr/bin/env python3
"""chip_smoke.py — the POST plane end to end on one TPU host, in one process.

    python3 chip_smoke.py

Drives init -> prove -> verify -> verifyd once, through the functions the
CLIs call, at mainnet widths (go-spacemesh v1.7.6 config/mainnet.go as
carried by node/config.py PostConfig/SmeshingConfig: scrypt N=8192,
r=p=1, 16-byte labels, 4 space units, K1=26, K2=37, the mainnet k2pow
difficulty) with the CLIs' own default batch widths. Only SCALE is cut,
and only by time: LABELS_PER_UNIT labels per unit instead of 2^32.

Every phase checks what came out against a plain reference
(hashlib.scrypt, the serial prover, the XLA scan step, inline
verification) and the run fails if any check fails, if any phase raises,
or if a fallback counter moved. No phase is wrapped in a catch that lets
the run exit 0.

It refuses to start unless jax.devices()[0].platform == "tpu" (JAX itself
drops to the CPU with only a log line when it finds no chip). It is ONE
process from the first JAX import to exit and starts no child that
imports JAX: a chip belongs to one process. On success the last line of
stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

preceded by one ``{"report": ...}`` line with, per phase, the wall time,
compile seconds and the kernel decision that ran. Any failure exits
non-zero and prints no such line.
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import hashlib
import json
import random
import sys
import tempfile
import time
from pathlib import Path

SEED = 21                     # every identity/challenge byte derives from it
LABELS_PER_UNIT = 1 << 17     # x4 units = 2^19 labels = 64 full init batches
#                               (8 MiB store, ~3 min of init on one v5e chip)
SAMPLE_LABELS = 64            # stored labels re-derived with hashlib.scrypt
HI_WORD_INDICES = (2**32 - 1, 2**32, 2**33 + 17)
MAINNET_UNIT_LABELS = 1 << 32


class SmokeFailure(AssertionError):
    """A comparison against a reference did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(*a) -> None:
    print("chip_smoke:", *a, file=sys.stderr, flush=True)


def _derive(tag: str) -> bytes:
    return hashlib.sha256(f"chip-smoke/{SEED}/{tag}".encode()).digest()


@dataclasses.dataclass(frozen=True)
class Deployment:
    """What is initialized, proven and verified. The defaults are the
    repo's mainnet values; tests shrink scrypt_n and the scale."""

    scrypt_n: int
    num_units: int
    labels_per_unit: int
    k1: int
    k2: int
    k3_synced: int            # mainnet's synced-ATX spot-check regime
    pow_difficulty: bytes
    init_batch: int

    @classmethod
    def mainnet(cls, labels_per_unit: int = LABELS_PER_UNIT) -> "Deployment":
        from spacemesh_tpu.node.config import PostConfig, SmeshingConfig

        post, smeshing = PostConfig(), SmeshingConfig()
        return cls(scrypt_n=post.scrypt_n, num_units=smeshing.num_units,
                   labels_per_unit=labels_per_unit, k1=post.k1, k2=post.k2,
                   k3_synced=post.k3,
                   pow_difficulty=post.pow_difficulty_bytes,
                   init_batch=smeshing.init_batch)

    @property
    def total_labels(self) -> int:
        return self.num_units * self.labels_per_unit


class CompileClock:
    """Sums JAX's own compile-duration events so each phase can report
    how much of its wall time was compilation (and how much of that the
    persistent cache turned into a retrieval)."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    TRACE = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self) -> None:
        import jax.monitoring

        self.backend_s = 0.0
        self.trace_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_kw) -> None:
        if event == self.BACKEND:
            self.backend_s += secs
        elif event in self.TRACE:
            self.trace_s += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple[float, float, int, int]:
        return self.backend_s, self.trace_s, self.hits, self.misses


@contextlib.contextmanager
def phase(report: dict, clock: CompileClock, name: str):
    """Times one phase into ``report[name]``. Not a catch: an exception
    leaves the phase unrecorded and ends the run."""
    log(f"phase {name} ...")
    doc: dict = {}
    c0, t0 = clock.snapshot(), time.perf_counter()
    yield doc
    c1 = clock.snapshot()
    doc["wall_s"] = round(time.perf_counter() - t0, 3)
    doc["compile_s"] = round(c1[0] - c0[0], 3)
    doc["trace_lower_s"] = round(c1[1] - c0[1], 3)
    doc["cache_hits"] = c1[2] - c0[2]
    doc["cache_misses"] = c1[3] - c0[3]
    report[name] = doc
    log(f"phase {name} ok: {json.dumps(doc)}")


def _scrypt_ref(commitment: bytes, index: int, n: int) -> bytes:
    return hashlib.scrypt(commitment, salt=int(index).to_bytes(8, "little"),
                          n=n, r=1, p=1, dklen=16)


def _decision_doc(batch: int) -> dict:
    """Where a batch of this width runs: the rule initialize() and
    verify_many() asked (parallel/mesh.py auto_mesh)."""
    from spacemesh_tpu.parallel import mesh as pmesh

    mesh = pmesh.auto_mesh(batch)
    return {"devices": mesh.size if mesh else 1, "batch": batch}


# --- phases ------------------------------------------------------------


def run_init(dep: Deployment, data_dir: Path, node_id: bytes,
             commitment: bytes, doc: dict) -> None:
    import numpy as np

    from spacemesh_tpu.ops import scrypt
    from spacemesh_tpu.post import initializer
    from spacemesh_tpu.post.data import LabelStore
    from spacemesh_tpu.utils import metrics

    meta, res = initializer.initialize(
        data_dir, node_id=node_id, commitment=commitment,
        num_units=dep.num_units, labels_per_unit=dep.labels_per_unit,
        scrypt_n=dep.scrypt_n, batch_size=dep.init_batch)
    total = dep.total_labels
    check(res.labels_written == total == meta.labels_written,
          f"init wrote {res.labels_written} of {total} labels")
    devices_used = int(sum(metrics.post_mesh_devices.sample().values()))
    doc.update(
        labels=total, labels_per_s=round(res.labels_per_s, 1),
        fraction_of_4su=total / (dep.num_units * MAINNET_UNIT_LABELS),
        vrf_nonce=res.vrf_nonce, stages=res.stats.as_dict(),
        decision=_decision_doc(scrypt.shape_bucket(min(dep.init_batch,
                                                       total))),
        devices_used=devices_used)
    check(doc["decision"]["devices"] == devices_used,
          f"init ran on {devices_used} devices, routing says "
          f"{doc['decision']['devices']}")
    # every device contributed a non-empty shard to every batch
    check(res.stats.shards == res.stats.batches * devices_used,
          f"{res.stats.shards} shards fetched over {res.stats.batches} "
          f"batches on {devices_used} devices")

    # the store, as durable bytes on disk
    store = LabelStore(data_dir, meta)
    try:
        raw = store.read_labels(0, total)
    finally:
        store.close()
    check(len(raw) == total * scrypt.LABEL_BYTES, "store is short")
    doc["store_sha256"] = hashlib.sha256(raw).hexdigest()
    labels = np.frombuffer(raw, dtype=np.uint8).reshape(total,
                                                        scrypt.LABEL_BYTES)
    sample = random.Random(SEED).sample(range(total),
                                        min(SAMPLE_LABELS, total))
    for i in sample:
        check(bytes(labels[i]) == _scrypt_ref(commitment, i, dep.scrypt_n),
              f"stored label {i} != hashlib.scrypt")
    # VRF nonce: the index of the smallest LE-u128 label in the store
    # (first occurrence), recomputed on the host from the stored bytes,
    # and that label itself re-derived with hashlib
    halves = labels.view("<u8")
    order = np.lexsort((halves[:, 0], halves[:, 1]))
    check(int(order[0]) == res.vrf_nonce == meta.vrf_nonce,
          f"VRF nonce {res.vrf_nonce} != host minimum {int(order[0])}")
    check(bytes(labels[res.vrf_nonce])
          == _scrypt_ref(commitment, res.vrf_nonce, dep.scrypt_n),
          "VRF nonce label != hashlib.scrypt")
    # label indices past 2^32: the hi word of le64(index) is live
    got = scrypt.scrypt_labels(commitment,
                               np.array(HI_WORD_INDICES, dtype=np.uint64),
                               n=dep.scrypt_n)
    for k, i in enumerate(HI_WORD_INDICES):
        check(bytes(got[k]) == _scrypt_ref(commitment, i, dep.scrypt_n),
              f"label at index {i} != hashlib.scrypt")
    doc["checked"] = {"sampled_labels": len(sample), "vrf_nonce": True,
                      "hi_word_indices": list(HI_WORD_INDICES)}


def _check_scan_step(prover, step, mesh, challenge: bytes) -> None:
    """``step`` (the window step the Prover binds by default: one
    program over every nonce group of a pass and every batch of a
    flight, its lane indices made on the device) against
    ``proving.prove_scan_step_jit`` sub-batch by sub-batch and group by
    group on one real flight of the store: the last sub-batch empty and
    the one before it ragged where a flight has more than one, and the
    index carry past 2^32 falling inside the flight."""
    import jax.numpy as jnp
    import numpy as np

    from spacemesh_tpu.ops import proving, scrypt

    b, ng, cap = prover.batch_labels, prover.nonce_group, prover.params.k2
    groups = prover.window_groups
    fb = prover.flight_batches(mesh)
    f = fb * b
    count = min(f, prover.meta.total_labels) - 5 - (b if fb > 1 else 0)
    labels = np.zeros((f, scrypt.LABEL_BYTES), np.uint8)
    labels[:count] = np.frombuffer(
        prover.store.read_labels(0, count), np.uint8).reshape(count, -1)
    prover.store.close()
    start = 2**32 - f // 2 - 7
    cw = jnp.asarray(proving.challenge_words(challenge))
    lw = scrypt.labels_to_words(labels)
    # ~64x the proof threshold over a flight, so every nonce row carries
    # hits and the K2 slots fill over several sub-batches
    thr = jnp.uint32(proving.threshold_u32(prover.params.k1 * 64 // fb,
                                           prover.meta.total_labels))
    bases = 16 + ng * np.arange(groups)
    got = [np.asarray(x) for x in step(
        cw, jnp.asarray(bases, jnp.uint32), jnp.asarray(lw),
        jnp.asarray([count, start & 0xFFFFFFFF, start >> 32], jnp.uint32),
        thr, *proving.init_hit_state(groups * ng, cap))]
    state = [proving.init_hit_state(ng, cap) for _ in bases]
    flight_counts = np.zeros(groups * ng, np.int64)
    for g in range(-(-count // b)):
        at = start + g * b
        lo, hi = scrypt.split_indices(np.arange(at, at + b, dtype=np.uint64))
        outs = [proving.prove_scan_step_jit(
            cw, jnp.uint32(base), jnp.asarray(lo), jnp.asarray(hi),
            jnp.asarray(lw[:, g * b:(g + 1) * b]), thr, *st,
            jnp.uint32(min(b, count - g * b)), jnp.uint32(at & 0xFFFFFFFF),
            jnp.uint32(at >> 32), n_nonces=ng, max_hits=cap)
            for base, st in zip(bases, state)]
        state = [(o[0], o[2]) for o in outs]
        flight_counts += np.concatenate([np.asarray(o[1]) for o in outs])
    # counts stack by row; the carry is (2, rows, cap)
    want = [np.concatenate([np.asarray(c) for c, _ in state]), flight_counts,
            np.concatenate([np.asarray(c) for _, c in state], axis=1)]
    check(int(want[1].min()) > 0, "scan-step check saw an empty row")
    for g, w in zip(got, want):
        check(np.array_equal(g, w),
              "default window step != proving.prove_scan_step_jit "
              "sub-batch by sub-batch, group by group")


def run_prove(dep: Deployment, data_dir: Path, challenge: bytes, params,
              doc: dict):
    """One proof through the default path, checked against the serial
    prover and the XLA step: does it START and agree. What a proof costs
    is the benchmark's to say (cell ``prove-mainnet.scan``, PERF.md),
    not this phase's seconds."""
    from spacemesh_tpu.post.prover import Prover

    prover = Prover(data_dir, params)
    step, mesh, impl = prover.scan_step()    # what prove() binds by default
    proof = prover.prove(challenge)          # pipelined, k2pow included
    stats = prover.last_stats.as_dict()
    check(prover.pipelined, "the default prove path is not the pipeline")
    check(len(proof.indices) == dep.k2 == len(set(proof.indices)),
          f"proof carries {len(proof.indices)} indices, want {dep.k2}")
    serial = Prover(data_dir, params).prove_serial(challenge)
    check(proof == serial,
          f"pipelined proof {proof} != prove_serial's {serial}")
    checked = {"equals_prove_serial": True}
    if impl == "pallas":
        # on a mesh the default step IS the XLA one, sharded: there is
        # nothing to compare, and the report carries no such key
        _check_scan_step(prover, step, mesh, challenge)
        checked["scan_step_vs_xla"] = True
    doc.update(nonce=proof.nonce, pow_nonce=proof.pow_nonce,
               stats=stats,
               decision={"impl": impl,
                         "devices": mesh.size if mesh is not None else 1,
                         "batch": prover.batch_labels,
                         "flight_batches": prover.flight_batches(mesh),
                         "nonce_group": prover.nonce_group,
                         "window_groups": prover.window_groups,
                         "source": "platform"},
               checked=checked)
    return proof


def _verify_items(dep: Deployment, proof, challenge: bytes, node_id: bytes,
                  commitment: bytes):
    """[the proof, the proof with one index swapped, the proof under
    another challenge] and the index that was swapped in."""
    from spacemesh_tpu.post.verifier import VerifyItem

    def item(p, ch):
        return VerifyItem(proof=p, challenge=ch, node_id=node_id,
                          commitment=commitment, scrypt_n=dep.scrypt_n,
                          total_labels=dep.total_labels)

    swapped = next(i for i in range(dep.total_labels)
                   if i not in proof.indices)
    bad = dataclasses.replace(
        proof, indices=[swapped] + list(proof.indices[1:]))
    return [item(proof, challenge), item(bad, challenge),
            item(proof, _derive("another-challenge"))], swapped


def run_verify(dep: Deployment, items, swapped: int, params, doc: dict):
    from spacemesh_tpu.ops import scrypt
    from spacemesh_tpu.post import verifier

    seed = _derive("k3-seed")
    full = verifier.verify_many(items, params, seed=seed)
    check(full == [True, False, False],
          f"verify_many at K3={params.k3} -> {full}, "
          "want [True, False, False]")
    synced_params = dataclasses.replace(params, k3=dep.k3_synced)
    synced = verifier.verify_many(items, synced_params, seed=seed)
    # at K3=1 the swapped index is caught only when it is the one sampled
    sampled = verifier.k3_subset(items[1], dep.k3_synced, seed)
    want = [True, swapped not in sampled, False]
    check(synced == want,
          f"verify_many at K3={dep.k3_synced} -> {synced}, want {want}")
    lanes = 2 * params.k2  # the third item fails its pow witness on host
    doc.update(full=full, synced=synced,
               decision=_decision_doc(scrypt.shape_bucket(lanes)))
    return full


def run_verifyd(dep: Deployment, items, inline_post: list[bool], params,
                doc: dict) -> None:
    from spacemesh_tpu.core.signing import Domain, EdSigner, EdVerifier
    from spacemesh_tpu.ops import pow as k2pow
    from spacemesh_tpu.verify.farm import PostRequest, PowRequest, SigRequest
    from spacemesh_tpu.verifyd.client import VerifydClient
    from spacemesh_tpu.verifyd.server import VerifydServer

    seed = _derive("k3-seed")
    reqs: list = [PostRequest(it) for it in items]
    inline = list(inline_post)
    # the matching pow witnesses: each post item's (challenge, node_id,
    # nonce) under the mainnet difficulty
    for it in items:
        reqs.append(PowRequest(it.challenge, it.node_id,
                               params.pow_difficulty, it.proof.pow_nonce))
        inline.append(k2pow.verify(it.challenge, it.node_id,
                                   params.pow_difficulty,
                                   it.proof.pow_nonce))
    # a few signatures, one of them forged
    ed = EdVerifier()
    for k in range(4):
        signer = EdSigner(seed=_derive(f"signer-{k}"))
        msg = _derive(f"msg-{k}")
        sig = signer.sign(Domain.ATX, msg)
        if k == 3:
            msg = _derive("msg-forged")
        reqs.append(SigRequest(int(Domain.ATX), signer.public_key, msg, sig))
        inline.append(ed.verify(Domain.ATX, signer.public_key, msg, sig))
    check(inline[3:6] == [True, True, False] and inline[6:] == [
        True, True, True, False], f"inline reference verdicts {inline}")

    async def go() -> tuple[list[bool], dict]:
        server = VerifydServer(listen="127.0.0.1:0", post_params=params,
                               post_seed=seed)
        try:
            port = await server.start()
            client = VerifydClient(f"http://127.0.0.1:{port}", "chip-smoke")
            try:
                await client.register()
                verdicts = await client.verify(reqs)
                stats = await client.stats()
            finally:
                await client.aclose()
        finally:
            await server.close()      # drains, then closes the sockets
        return verdicts, stats

    verdicts, stats = asyncio.run(go())
    check(verdicts == inline,
          f"verifyd verdicts {verdicts} != inline verification {inline}")
    doc.update(items=len(reqs), verdicts=verdicts,
               kinds={"post": len(items), "pow": len(items), "sig": 4},
               farm=stats.get("farm"))


def run_phases(dep: Deployment, clock: CompileClock) -> dict:
    """init -> prove -> verify -> verifyd over one temporary store;
    returns the per-phase report. Raises on the first failed check."""
    from spacemesh_tpu.post.prover import ProofParams
    from spacemesh_tpu.utils import metrics

    node_id, commitment = _derive("node-id"), _derive("commitment")
    challenge = _derive("challenge")
    params = ProofParams(k1=dep.k1, k2=dep.k2, k3=dep.k2,
                         pow_difficulty=dep.pow_difficulty)
    report: dict = {}
    fallbacks0 = dict(metrics.runtime_fallbacks.sample())
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
        data_dir = Path(tmp) / "post"
        with phase(report, clock, "init") as doc:
            run_init(dep, data_dir, node_id, commitment, doc)
        with phase(report, clock, "prove") as doc:
            proof = run_prove(dep, data_dir, challenge, params, doc)
        items, swapped = _verify_items(dep, proof, challenge, node_id,
                                       commitment)
        with phase(report, clock, "verify") as doc:
            inline_post = run_verify(dep, items, swapped, params, doc)
        with phase(report, clock, "verifyd") as doc:
            run_verifyd(dep, items, inline_post, params, doc)
    moved = {k: v for k, v in metrics.runtime_fallbacks.sample().items()
             if v != fallbacks0.get(k, 0)}
    check(not moved, f"runtime_fallbacks_total moved: {moved}")
    report["fallbacks_moved"] = moved
    return report


def main() -> int:
    t0 = time.perf_counter()
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        log(f"refusing to start: JAX landed on {device} — this check is "
            "for the chip, and a CPU run proves nothing about it")
        return 2
    clock = CompileClock()
    from spacemesh_tpu import native
    from spacemesh_tpu.utils import accel

    dep = Deployment.mainnet()
    start = {"device": device, "devices": [str(d) for d in devs],
             "compile_cache": accel.enable_persistent_cache(),
             "native_loaded": native.status(),
             "deployment": {**dataclasses.asdict(dep),
                            "pow_difficulty": dep.pow_difficulty.hex(),
                            "total_labels": dep.total_labels}}
    log(json.dumps(start))
    report = run_phases(dep, clock)
    report = {"start": start, **report,
              "total_s": round(time.perf_counter() - t0, 3),
              "compile_s": round(clock.backend_s, 3)}
    print(json.dumps({"report": report}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
