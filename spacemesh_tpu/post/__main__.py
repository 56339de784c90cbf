"""tpu-post-worker CLI: init / prove / verify / benchmark.

The operator surface of the standalone POST worker (SURVEY.md §7 M0
deliverable), mirroring what post-rs ships as separate binaries (the
initializer, the post-service prover, and the profiler — reference
Makefile-libs.Inc fetches them prebuilt; activation/post_supervisor.go:105-127
exposes Providers()/Benchmark()).

Usage:
  python -m spacemesh_tpu.post init --data-dir D --node-id-hex .. \
      --commitment-hex .. --num-units 1 --labels-per-unit 1024 [--scrypt-n N]
  python -m spacemesh_tpu.post init --identities IDS.json --num-units 4 \
      --labels-per-unit 4294967296   # IDS.json: [{data_dir, node_id,
                                     # commitment}, ...], hex ids
  python -m spacemesh_tpu.post prove --data-dir D --challenge-hex ..
  python -m spacemesh_tpu.post verify --data-dir D --proof-file P.json \
      --challenge-hex ..
  python -m spacemesh_tpu.post benchmark [--batch B] [--scrypt-n N]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path


def _hex32(s: str) -> bytes:
    b = bytes.fromhex(s)
    if len(b) != 32:
        raise argparse.ArgumentTypeError("expected 32 bytes of hex")
    return b


def _identities(path: str) -> list:
    """``--identities``: a JSON list of ``{data_dir, node_id,
    commitment}`` (the two ids as 32 bytes of hex)."""
    out = []
    for entry in json.loads(Path(path).read_text()):
        try:
            out.append((entry["data_dir"], _hex32(entry["node_id"]),
                        _hex32(entry["commitment"])))
        except (KeyError, TypeError, ValueError,
                argparse.ArgumentTypeError) as e:
            raise SystemExit(f"{path}: bad identity {entry!r}: {e}")
    return out


def cmd_init_many(a) -> int:
    """Every identity of ``--identities`` through ONE tenant scheduler:
    a progress line per identity as its segments land, then one JSON
    line per identity."""
    from . import initializer

    ids = _identities(a.identities)

    def progress(i, done, total):
        print(f"{ids[i][0]}: {done}/{total} labels "
              f"({100 * done / total:.1f}%)", file=sys.stderr, flush=True)

    res = initializer.initialize_many(
        ids, num_units=a.num_units, labels_per_unit=a.labels_per_unit,
        scrypt_n=a.scrypt_n, max_file_size=a.max_file_size,
        progress=progress)
    for (d, _, _), meta in zip(ids, res.metas):
        print(json.dumps({
            "data_dir": str(d),
            "labels_written": meta.labels_written,
            "vrf_nonce": -1 if meta.vrf_nonce is None else meta.vrf_nonce,
            "elapsed_s": round(res.elapsed_s, 2)}))
    return 0


def cmd_init(a) -> int:
    from . import initializer

    if a.identities is not None:
        if a.data_dir or a.node_id_hex or a.commitment_hex:
            raise SystemExit("init: --identities replaces --data-dir, "
                             "--node-id-hex and --commitment-hex")
        return cmd_init_many(a)
    if not (a.data_dir and a.node_id_hex and a.commitment_hex):
        raise SystemExit("init: --data-dir, --node-id-hex and "
                         "--commitment-hex are required (or --identities)")

    def progress(done, total):
        print(f"\r{done}/{total} labels ({100 * done / total:.1f}%)",
              end="", file=sys.stderr, flush=True)

    meta, res = initializer.initialize(
        a.data_dir, node_id=a.node_id_hex, commitment=a.commitment_hex,
        num_units=a.num_units, labels_per_unit=a.labels_per_unit,
        scrypt_n=a.scrypt_n, max_file_size=a.max_file_size,
        batch_size=a.batch, progress=progress,
        inflight=a.inflight, writers=a.writers)
    print("", file=sys.stderr)
    out = {
        "labels_written": res.labels_written,
        "vrf_nonce": res.vrf_nonce,
        "labels_per_s": round(res.labels_per_s, 1),
        "elapsed_s": round(res.elapsed_s, 2),
    }
    if a.stage_timings and res.stats is not None:
        out["stages"] = {k: round(v, 3) if isinstance(v, float) else v
                         for k, v in res.stats.as_dict().items()}
    print(json.dumps(out))
    return 0


def cmd_prove(a) -> int:
    from .prover import ProofParams, Prover

    params = ProofParams(k1=a.k1, k2=a.k2, k3=a.k3)
    t0 = time.monotonic()
    prover = Prover(a.data_dir, params, batch_labels=a.batch,
                    pipelined=None if not a.serial else False,
                    window_groups=a.window_groups, inflight=a.inflight,
                    readers=a.readers)
    proof = prover.prove(a.challenge_hex)
    out = proof.to_dict() | {"elapsed_s": round(time.monotonic() - t0, 2)}
    if a.stage_timings and prover.last_stats is not None:
        out["stages"] = {k: round(v, 3) if isinstance(v, float) else v
                         for k, v in prover.last_stats.as_dict().items()}
    if a.out:
        Path(a.out).write_text(json.dumps(proof.to_dict()))
    print(json.dumps(out))
    return 0


def cmd_verify(a) -> int:
    from . import verifier
    from .data import PostMetadata
    from .prover import Proof, ProofParams

    meta = PostMetadata.load(a.data_dir)
    proof = Proof.from_dict(json.loads(Path(a.proof_file).read_text()))
    params = ProofParams(k1=a.k1, k2=a.k2, k3=a.k3)
    ok = verifier.verify(verifier.VerifyItem(
        proof=proof, challenge=a.challenge_hex,
        node_id=bytes.fromhex(meta.node_id),
        commitment=bytes.fromhex(meta.commitment),
        scrypt_n=meta.scrypt_n, total_labels=meta.total_labels), params)
    print(json.dumps({"valid": ok}))
    return 0 if ok else 1


def cmd_benchmark(a) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops import scrypt

    dev = jax.devices()[0]
    cw = jnp.asarray(scrypt.commitment_to_words(bytes(32)))
    idx = np.arange(a.batch, dtype=np.uint64)
    lo_, hi_ = scrypt.split_indices(idx)
    lo, hi = jnp.asarray(lo_), jnp.asarray(hi_)
    t0 = time.perf_counter()
    scrypt.scrypt_labels_jit(cw, lo, hi, n=a.scrypt_n).block_until_ready()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    scrypt.scrypt_labels_jit(cw, lo, hi, n=a.scrypt_n).block_until_ready()
    dt = time.perf_counter() - t0
    print(json.dumps({
        "device": str(dev), "batch": a.batch, "scrypt_n": a.scrypt_n,
        "labels_per_s": round(a.batch / dt, 1),
        "compile_s": round(compile_s, 2),
    }))
    return 0


def cmd_serve(a) -> int:
    """Serve every identity under --data-dir to the node.

    Two transports behind the same PostService registry:
    * default: listen on --listen, node dials us (JSON-RPC framing)
    * --node-address: DIAL the node's gRPC PostService and Register each
      identity over a bidirectional stream — the reference topology
      (reference api/grpcserver/post_service.go:91; the Rust post-service
      is spawned with the node's address the same way).
    """
    import asyncio

    from .prover import ProofParams
    from .remote import WorkerServer, discover_identities

    params = ProofParams(k1=a.k1, k2=a.k2, k3=a.k3,
                         pow_difficulty=bytes.fromhex(a.pow_difficulty))
    service = discover_identities(a.data_dir, params=params)

    async def go_grpc():
        from .grpc_worker import GrpcWorker

        worker = GrpcWorker(service, a.node_address)
        await worker.start()
        print(json.dumps({"event": "Registering",
                          "node_address": a.node_address,
                          "identities": [n.hex() for n in
                                         service.registered()]}),
              flush=True)
        try:
            await asyncio.Event().wait()  # until killed
        finally:
            await worker.stop()

    async def go_listen():
        server = WorkerServer(service, listen=a.listen)
        host, port = await server.start()
        print(json.dumps({"event": "Serving", "host": host, "port": port,
                          "identities": [n.hex() for n in
                                         service.registered()]}),
              flush=True)
        try:
            await asyncio.Event().wait()  # until killed
        finally:
            await server.stop()

    try:
        asyncio.run(go_grpc() if a.node_address else go_listen())
    except KeyboardInterrupt:
        pass
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="spacemesh_tpu.post")
    sub = p.add_subparsers(dest="cmd", required=True)

    pi = sub.add_parser("init", help="fill a POST data directory with labels")
    pi.add_argument("--data-dir")
    pi.add_argument("--node-id-hex", type=_hex32)
    pi.add_argument("--commitment-hex", type=_hex32)
    pi.add_argument("--identities", default=None, metavar="FILE",
                    help="initialize many identities through one tenant "
                    "scheduler: a JSON list of {data_dir, node_id, "
                    "commitment} (hex); the geometry and --scrypt-n are "
                    "shared")
    pi.add_argument("--num-units", type=int, required=True)
    pi.add_argument("--labels-per-unit", type=int, required=True)
    pi.add_argument("--scrypt-n", type=int, default=8192)
    pi.add_argument("--max-file-size", type=int, default=64 * 1024 * 1024)
    pi.add_argument("--batch", type=int, default=1 << 13)
    pi.add_argument("--inflight", type=int, default=None,
                    help="device batches in flight (default: "
                    "SPACEMESH_INFLIGHT or 3)")
    pi.add_argument("--writers", type=int, default=None,
                    help="background disk-writer threads (default: "
                    "SPACEMESH_WRITERS or 2)")
    pi.add_argument("--stage-timings", action="store_true",
                    help="include per-stage pipeline timings in the output")
    pi.set_defaults(fn=cmd_init)

    pp = sub.add_parser("prove", help="generate a proof over the challenge")
    pp.add_argument("--data-dir", required=True)
    pp.add_argument("--challenge-hex", type=_hex32, required=True)
    pp.add_argument("--k1", type=int, default=26)
    pp.add_argument("--k2", type=int, default=37)
    pp.add_argument("--k3", type=int, default=37)
    pp.add_argument("--batch", type=int, default=1 << 14)
    pp.add_argument("--serial", action="store_true",
                    help="use the legacy synchronous scan instead of the "
                    "streaming pipeline (docs/POST_PROVING.md)")
    pp.add_argument("--window-groups", type=int, default=None,
                    help="nonce groups scanned per disk pass (default: "
                    "SPACEMESH_PROVE_WINDOW_GROUPS, or 4 on TPU / 1 on CPU)")
    pp.add_argument("--inflight", type=int, default=None,
                    help="device batches in flight (default: "
                    "SPACEMESH_PROVE_INFLIGHT or 3)")
    pp.add_argument("--readers", type=int, default=None,
                    help="background label-reader threads (default: "
                    "SPACEMESH_PROVE_READERS or 2)")
    pp.add_argument("--stage-timings", action="store_true",
                    help="include per-stage prove pipeline timings")
    pp.add_argument("--out", help="write proof JSON here as well")
    pp.set_defaults(fn=cmd_prove)

    pv = sub.add_parser("verify", help="verify a proof file")
    pv.add_argument("--data-dir", required=True)
    pv.add_argument("--proof-file", required=True)
    pv.add_argument("--challenge-hex", type=_hex32, required=True)
    pv.add_argument("--k1", type=int, default=26)
    pv.add_argument("--k2", type=int, default=37)
    pv.add_argument("--k3", type=int, default=37)
    pv.set_defaults(fn=cmd_verify)

    pb = sub.add_parser("benchmark", help="time the labeler on this device")
    pb.add_argument("--batch", type=int, default=2048)
    pb.add_argument("--scrypt-n", type=int, default=8192)
    pb.set_defaults(fn=cmd_benchmark)

    ps = sub.add_parser("serve", help="serve identities to the node "
                        "(out-of-process worker)")
    ps.add_argument("--data-dir", required=True,
                    help="base dir holding per-identity POST data dirs")
    ps.add_argument("--listen", default="127.0.0.1:0")
    ps.add_argument("--node-address", default=None,
                    help="dial the node's gRPC PostService at host:port "
                    "instead of listening (reference topology)")
    ps.add_argument("--k1", type=int, default=26)
    ps.add_argument("--k2", type=int, default=37)
    ps.add_argument("--k3", type=int, default=37)
    ps.add_argument("--pow-difficulty", default="00ff" + "ff" * 30,
                    help="32-byte hex PoW difficulty")
    ps.set_defaults(fn=cmd_serve)

    a = p.parse_args(argv)
    from ..utils import accel

    # every subcommand runs on the platform JAX gives it and says which
    # (this also turns on the persistent compile cache)
    accel.announce_platform("spacemesh_tpu.post")
    return a.fn(a)


if __name__ == "__main__":
    sys.exit(main())
