"""Headline benchmark: POST init labels/sec on one chip (mainnet N=8192).

It runs on the platform JAX gives it — there is no probe and no CPU
fallback; ``JAX_PLATFORMS=cpu`` on the command line asks for the CPU.
EVERY JSON line carries ``platform`` and ``device_kind``: a line from a
``cpu`` run is a CPU-host number and never a device metric. A chip
belongs to one process, and this parent touches JAX first, so on a
non-CPU backend the phases that start child processes (verifyd fleet,
sim fabric, sim fabric mp) REFUSE with a ``"refused"`` line instead of
starting children against a held chip; the mesh and multi-tenant lines
run in-process there. (ROADMAP S1/D1 replace this file with a cell
runner whose parent never imports JAX.)

Prints THREE JSON lines for the init side. The headline first:
  {"metric": "post_init_labels_per_sec...", "value": N, "unit": "labels/s",
   "vs_baseline": N, "impl": "xla", "chunk": null, "fused": true}
("impl"/"chunk" are constants since the label kernel became one path —
docs/ROMIX_KERNEL.md — and "fused" records
that expand->romix->finish ran as one jitted program), then the
kernel-only rate, isolating the memory-hard ROMix core from the PBKDF2
envelope + pipeline overhead around it:
  {"metric": "post_init_kernel_labels_per_sec", ...}
then the compile cost, tracked separately from steady-state throughput:
  {"metric": "post_init_compile_s", "value": N, "unit": "s", ...}

Steady state is measured pipelined — all reps dispatched back-to-back and
synced once at the end, the way the streaming initializer drives the
device — so inter-rep host sync gaps don't pollute the number. Compiled
executables are reused across reps and across runs: the persistent
compilation cache (utils/accel.py) makes the 17-26s per-shape compile a
once-per-machine cost, so `post_init_compile_s` on a warm host drops to
the cache-deserialize time.

vs_baseline is the speedup over the reference CPU labeling path measured
in-process (hashlib.scrypt = OpenSSL scrypt, the same labeling function the
reference's CPU provider computes; the reference publishes no numbers of
its own — BASELINE.md). Progress goes to stderr; stdout carries only the
JSON lines.

After the POST metrics, a verification benchmark (ISSUE 2) runs a mixed
workload (ed25519 sigs + VRF proofs + POST proofs + poet memberships,
>=10% invalid) through the inline serial path and through the
verification farm (spacemesh_tpu/verify/), emitting:
  {"metric": "verify_serial_s", ...}
  {"metric": "verify_batched_s", ..., "speedup": serial/batched}
Both paths are warmed first so the numbers compare steady-state
throughput, not XLA compile time; decisions are asserted bit-identical.

Between the init and verify benchmarks, the PROVE side (ISSUE 3) measures
the streaming prover against the legacy serial scan over one shared
reduced-parameter store, emitting:
  {"metric": "post_prove_labels_per_sec", ..., "serial": N, "speedup": N}
Both provers must produce bit-identical proofs (asserted) and the
pipelined proof must verify; the rate is store labels covered per second
until the winning nonce is decided — the streaming pipeline's sound early
exit plus read/compute overlap is what the speedup measures
(docs/POST_PROVING.md).

After the kernel-only line, the MESH headline (ISSUE 6): the
multi-device path — label lanes sharded over virtual host devices on the
CPU (8 forced, the same count every test/driver entry point already
configures) where the mesh rule shards (parallel/mesh.py auto_mesh: on
the CPU only under SPACEMESH_MESH) — measured in a SUBPROCESS so the forced
host-device split cannot degrade the single-device lines above it. The
probe returns the sha256 digest of its sharded labels; the parent
recomputes the single-device digest (only when a mesh rate was actually
measured) and refuses to print the headline — exiting non-zero, so CI
goes red — on any mismatch:
  {"metric": "post_init_labels_per_sec_mesh", "value": N,
   "unit": "labels/s", "devices": D, "impl": ..., "vs_single": N,
   "vs_baseline": N, "bit_identical": true}
On a real multi-device accelerator the same measurement runs in-process
(the devices are physical; nothing to force). BENCH_MESH=0 disables.

After the prove bench, the MULTI-TENANT headline (ISSUE 11/16): 16
tenants' small init jobs through the runtime scheduler's packed
fair-share admission (spacemesh_tpu/runtime/) vs the same jobs run one
tenant at a time, per-tenant sha256 label digests + VRF nonces asserted
identical before any rate is reported (a mismatch exits non-zero). On
the CPU platform (unless BENCH_MESH=0) the measurement runs in a
SUBPROCESS with forced virtual host devices — the environment where the
scheduler's pack dispatch routes through the mesh-sharded program —
for the same single-device-honesty reason as the mesh probe;
"pack_devices" records how the tuned routing actually dispatched packs:
  {"metric": "post_multi_tenant_labels_per_sec", ..., "tenants": 16,
   "pack_devices": D, "sequential": N, "vs_sequential": N,
   "bit_identical": true}

After the farm verify bench, the VERIFYD headline (ISSUE 13): the same
mixed workload plus k2pow witnesses through the standalone verification
service (spacemesh_tpu/verifyd/) over real sockets — a multi-client
open-loop load vs a serial one-at-a-time client, every verdict asserted
identical to inline verification before any rate is reported:
  {"metric": "verifyd_proofs_per_sec", "value": N, "unit": "items/s",
   "p99_ms": N, "serial": N, "vs_serial": N, "bit_identical": true}

Last, the SIM FABRIC headline (ISSUE 18): the 514-node pure-fabric
``storm-512-bench`` scenario on the event-wheel hub (twice, replay
determinism) and on the legacy task-per-node hub, scenario digests
asserted identical across all three runs before any rate is reported:
  {"metric": "sim_fabric_events_per_sec", "value": N, "unit": "events/s",
   "legacy": N, "vs_legacy": N, "bit_identical": true}

And the MULTI-PROCESS fabric line (ISSUE 19): the same scenario with
the event wheel sharded over host cores (sim/shard.py) vs
single-process, all four digests asserted identical first; hosts
without >= 2 cores keep the fabric single-process and say so:
  {"metric": "sim_fabric_mp_events_per_sec", "value": N,
   "unit": "events/s", "single": N, "vs_single_process": N,
   "shards": W, "cores": C, "bit_identical": true}

Env knobs: BENCH_BATCH (label lanes per program), BENCH_N (scrypt N),
BENCH_REPS, BENCH_CPU_LABELS, BENCH_VERIFY_ITEMS (0 disables the verify
bench), BENCH_PROVE_LABELS (store size; 0 disables the prove bench),
BENCH_PROVE_BATCH, BENCH_TENANTS / BENCH_TENANT_LABELS / BENCH_TENANT_N
/ BENCH_TENANT_REPS / BENCH_PACK_LANES (the multi-tenant line; tenants=0
disables), BENCH_VERIFYD_ITEMS / BENCH_VERIFYD_CLIENTS /
BENCH_VERIFYD_PER_REQUEST / BENCH_VERIFYD_WORKERS (the verifyd line;
items=0 disables), BENCH_FLEET_ITEMS / BENCH_FLEET_REPLICAS /
BENCH_FLEET_CLIENTS / BENCH_FLEET_PER_REQUEST / BENCH_FLEET_WORKERS /
BENCH_FLEET_REPS / BENCH_FLEET_PIN / BENCH_FLEET_MIN_SPEEDUP (the
verifyd fleet line; items=0 disables; replicas pin to disjoint core
slices when the host has one per replica, and MIN_SPEEDUP enforces the
>= 1.5x fleet floor only on such hosts),
BENCH_MESH (0 disables the mesh line AND pins the
multi-tenant bench in-process single-device), BENCH_MESH_TIMEOUT /
BENCH_MT_TIMEOUT (probe subprocess seconds, default 1800),
BENCH_SIM_FABRIC (0/off disables the sim fabric line) /
BENCH_SIM_FABRIC_TIMEOUT (per-run subprocess seconds, default 600),
BENCH_SIM_FABRIC_MP (0/off disables the multi-process fabric line) /
BENCH_SIM_FABRIC_MP_SHARDS (worker count; default min(cores, light//64))
/ BENCH_SIM_FABRIC_MP_TIMEOUT (default 900) /
BENCH_SIM_FABRIC_MP_MIN_SPEEDUP (the >= 1.5x floor, enforced only
where the parent and every worker get their own core),
JAX_COMPILATION_CACHE_DIR (moves the compile cache out of the
checkout's .cache/ — utils/accel.py), plus SPACEMESH_MESH
(docs/ROMIX_KERNEL.md).
"""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import time


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def emit(doc: dict) -> None:
    """Print one result line, stamped with the device it was measured
    on (platform, device_kind, device count — as JAX reports them)."""
    from spacemesh_tpu.utils import accel

    print(json.dumps({**doc, **accel.device_fields()}), flush=True)


def children_refused(metric: str) -> bool:
    """True (after saying so on stderr AND stdout) when this process
    holds an accelerator: a child that needs the chip would fail or
    hang against it, so child-spawning phases do not start there."""
    import jax

    platform = jax.default_backend()
    if platform == "cpu":
        return False
    why = (f"this process holds the {platform} device; "
           "children that import JAX would contend for the same chip")
    log(f"{metric}: REFUSED — {why}")
    emit({"metric": metric, "refused": why})
    return True


def cpu_labels_per_sec(commitment: bytes, n: int, count: int) -> float:
    t0 = time.perf_counter()
    for i in range(count):
        hashlib.scrypt(commitment, salt=i.to_bytes(8, "little"), n=n, r=1,
                       p=1, maxmem=256 * 1024 * 1024, dklen=16)
    dt = time.perf_counter() - t0
    return count / dt


def measure_mesh(n: int, batch: int, reps: int) -> dict:
    """Measure the multi-device label path for one shape.

    Shards the same (commitment, indices) batch the single-device
    headline used over the mesh the rule gives (parallel/mesh.py
    auto_mesh), and returns a JSON-able doc carrying the sha256
    ``digest`` of the sharded labels — the caller compares it against
    the single-device digest before reporting any rate. ``devices`` is
    1 when the rule keeps the batch on one device."""
    import jax
    import numpy as np

    from spacemesh_tpu.ops import scrypt
    from spacemesh_tpu.parallel import mesh as pmesh

    mesh = pmesh.auto_mesh(batch)
    doc = {"devices": mesh.size if mesh else 1, "impl": "xla",
           "chunk": None, "tuned": "rule",
           "devices_visible": jax.device_count()}
    if mesh is None:
        return doc
    commitment = hashlib.sha256(b"bench-commitment").digest()
    cw = scrypt.commitment_to_words(commitment)
    idx = np.arange(batch, dtype=np.uint64)
    lo, hi = scrypt.split_indices(idx)
    t0 = time.perf_counter()
    words = pmesh.scrypt_labels_sharded(mesh, cw, lo, hi, n=n)
    words.block_until_ready()
    doc["compile_s"] = round(time.perf_counter() - t0, 2)
    doc["digest"] = hashlib.sha256(
        scrypt.labels_to_bytes(np.asarray(words))).hexdigest()
    t0 = time.perf_counter()
    outs = [pmesh.scrypt_labels_sharded(mesh, cw, lo, hi, n=n)
            for _ in range(reps)]
    jax.block_until_ready(outs)
    doc["labels_per_sec"] = round(reps * batch / (time.perf_counter() - t0),
                                  1)
    return doc


def mesh_probe_main() -> int:
    """Child-process entry (``bench.py --mesh-probe``; the parent starts
    it with JAX_PLATFORMS=cpu and only when it is on the CPU itself):
    force the virtual host devices (which would degrade the parent's
    single-device numbers — the reason this is a subprocess), and print
    the measure_mesh doc as the last stdout line."""
    n = int(os.environ["BENCH_MESH_N"])
    batch = int(os.environ["BENCH_MESH_BATCH"])
    reps = int(os.environ.get("BENCH_MESH_REPS", 3))

    from spacemesh_tpu.utils import accel

    accel.ensure_host_devices()
    accel.enable_persistent_cache()
    doc = measure_mesh(n, batch, reps)
    print(json.dumps(doc), flush=True)
    return 0


def mt_probe_main() -> int:
    """Child-process entry (``bench.py --mt-probe``): the multi-tenant
    packer bench on the CPU with forced virtual host devices —
    the environment where the scheduler's pack dispatch routes through
    the mesh-sharded program (runtime/scheduler.py _dispatch_pack). A
    subprocess for the same reason the mesh probe is one: the forced
    device split would degrade the parent's single-device lines. The
    bit-identity gate (per-tenant sha256 + VRF nonce vs the sequential
    Initializer) runs INSIDE this child and exits non-zero on any
    divergence; the parent propagates that as a red build."""
    from spacemesh_tpu.utils import accel

    accel.ensure_host_devices()
    accel.enable_persistent_cache()
    multi_tenant_bench()
    return 0


def run_mt_probe() -> None:
    """Run multi_tenant_bench in a subprocess with forced host devices,
    forwarding its JSON line; a failed child fails the bench."""
    timeout = int(os.environ.get("BENCH_MT_TIMEOUT", 1800))
    log(f"multi-tenant probe: packed admission over the mesh in a "
        f"subprocess (<= {timeout}s) ...")
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--mt-probe"],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=timeout,
            capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        log("multi-tenant probe: timed out; skipping the line")
        return
    sys.stderr.write(r.stderr)
    for line in r.stdout.strip().splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        print(line, flush=True)
    if r.returncode != 0:
        # the child's bit-identity gate (or an outright crash) — red
        log(f"multi-tenant probe: FAILED (rc={r.returncode})")
        sys.exit(1)


def run_mesh_probe(n: int, batch: int, reps: int) -> dict | None:
    """Run measure_mesh in a subprocess with forced host devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               BENCH_MESH_N=str(n), BENCH_MESH_BATCH=str(batch),
               BENCH_MESH_REPS=str(reps))
    timeout = int(os.environ.get("BENCH_MESH_TIMEOUT", 1800))
    log(f"mesh probe: racing + measuring the sharded path in a "
        f"subprocess (<= {timeout}s) ...")
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--mesh-probe"],
            env=env, timeout=timeout, capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        log("mesh probe: timed out; skipping the mesh headline")
        return None
    sys.stderr.write(r.stderr)
    if r.returncode != 0:
        log(f"mesh probe: failed (rc={r.returncode}); skipping")
        return None
    for line in reversed(r.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except ValueError:
            continue
    log("mesh probe: no JSON doc on stdout; skipping")
    return None


def prove_bench(labels: int, batch: int, reps: int = 3) -> None:
    """Streaming vs legacy-serial proving over one shared store.

    The deterministic reduced-parameter fixture lives in
    spacemesh_tpu/post/workload.py (ONE copy, shared with the profiler's
    --prove view); it asserts the two paths' proofs are bit-identical and
    verifiable before this reports a number.
    """
    import tempfile

    from spacemesh_tpu.post import workload

    with tempfile.TemporaryDirectory() as d:
        log(f"prove store: {labels} labels (scrypt N=2) ...")
        prover = workload.build(d, labels, batch)
        doc = workload.compare_serial_vs_pipelined(prover, reps=reps)

    serial_rate = labels / doc["serial_s"]
    pipe_rate = labels / doc["pipelined_s"]
    stats = doc["stats"]
    log(f"prove: serial {doc['serial_s'] * 1e3:.1f}ms, pipelined "
        f"{doc['pipelined_s'] * 1e3:.1f}ms ({doc['speedup']:.2f}x, "
        f"nonce {doc['proof'].nonce}, "
        f"early_exit={stats.get('early_exited')})")
    emit({
        "metric": "post_prove_labels_per_sec",
        "value": round(pipe_rate, 1),
        "unit": "labels/s",
        "serial": round(serial_rate, 1),
        "speedup": round(pipe_rate / serial_rate, 2),
        "labels": labels, "batch": batch,
        "proof_nonce": doc["proof"].nonce,
        "early_exited": bool(stats.get("early_exited")),
        "verified": True,
    })


def multi_tenant_bench() -> None:
    """16-tenant aggregate init throughput vs one-tenant-at-a-time.

    The workload is the multi-tenant service shape (ROADMAP #1): many
    smeshers each submitting a SMALL init job — per-job ownership pays
    one session (writer pool, watchdogs, metadata, drain) and one
    under-filled device program per tenant, while the runtime scheduler
    (spacemesh_tpu/runtime/) packs all tenants' lanes into full-bucket
    fused programs through one always-fed engine window.  Reduced N
    (like the prove bench's reduced-parameter store) keeps the measured
    quantity the orchestration gap, not the scrypt math — the same
    choice ROADMAP #5 motivates ("the gap is orchestration").

    Before ANY rate is reported, every tenant's label bytes (sha256)
    and VRF nonce from the scheduled path are asserted identical to the
    sequential Initializer's; a mismatch exits non-zero so CI goes red.
    Emits:
      {"metric": "post_multi_tenant_labels_per_sec", "value": N,
       "unit": "labels/s", "tenants": T, "sequential": N,
       "vs_sequential": N, "bit_identical": true}
    """
    import shutil
    import tempfile
    from pathlib import Path

    tenants = int(os.environ.get("BENCH_TENANTS", 16))
    labels = int(os.environ.get("BENCH_TENANT_LABELS", 128))
    n = int(os.environ.get("BENCH_TENANT_N", 8))
    reps = int(os.environ.get("BENCH_TENANT_REPS", 3))
    pack = int(os.environ.get("BENCH_PACK_LANES", 2048))

    from spacemesh_tpu.post import initializer
    from spacemesh_tpu.post.data import LabelStore
    from spacemesh_tpu.runtime import TenantScheduler

    ids = [(f"smesher-{i:02d}",
            hashlib.sha256(b"bench-mt-node-%d" % i).digest(),
            hashlib.sha256(b"bench-mt-commit-%d" % i).digest())
           for i in range(tenants)]
    total = tenants * labels

    def fingerprint(dir_, meta) -> tuple:
        store = LabelStore(dir_, meta)
        digest = hashlib.sha256(store.read_labels(0, labels)).hexdigest()
        store.close()
        return digest, meta.vrf_nonce, meta.vrf_nonce_value

    log(f"multi-tenant: {tenants} tenants x {labels} labels (N={n}, "
        f"pack={pack}) ...")
    with tempfile.TemporaryDirectory() as d:
        root = Path(d)

        def seq_round(tag: str) -> dict:
            prints = {}
            for tid, node, commit in ids:
                dir_ = root / f"{tag}-{tid}"
                meta, _res = initializer.initialize(
                    dir_, node_id=node, commitment=commit, num_units=1,
                    labels_per_unit=labels, scrypt_n=n,
                    max_file_size=1 << 24, batch_size=labels, mesh=None)
                prints[tid] = fingerprint(dir_, meta)
                shutil.rmtree(dir_)
            return prints

        sched = TenantScheduler(workers=2, pack_lanes=pack)
        for tid, _, _ in ids:
            sched.register_tenant(tid)

        def mt_round(tag: str) -> dict:
            handles = [
                (tid, sched.submit_init(
                    tid, root / f"{tag}-{tid}", node_id=node,
                    commitment=commit, num_units=1,
                    labels_per_unit=labels, scrypt_n=n,
                    max_file_size=1 << 24))
                for tid, node, commit in ids]
            prints = {}
            for tid, h in handles:
                meta = h.result(timeout=600)
                prints[tid] = fingerprint(root / f"{tag}-{tid}", meta)
                shutil.rmtree(root / f"{tag}-{tid}")
            return prints

        try:
            # warm both paths' executables (compile cost is its own
            # bench line; this line measures steady-state admission —
            # the scheduler's pack linger keeps measured packs full)
            seq_prints = seq_round("warm-seq")
            mt_prints = mt_round("warm-mt")
            best_seq = best_mt = float("inf")
            for r in range(reps):
                t0 = time.perf_counter()
                seq_round(f"s{r}")
                best_seq = min(best_seq, time.perf_counter() - t0)
                t0 = time.perf_counter()
                mt_round(f"m{r}")
                best_mt = min(best_mt, time.perf_counter() - t0)
        finally:
            sched.close()

    for tid, _, _ in ids:
        if seq_prints[tid] != mt_prints[tid]:
            # divergence must be a red build, not a quietly odd rate
            log(f"multi-tenant: FAILED — tenant {tid} diverged from the "
                f"sequential path: {seq_prints[tid]} != {mt_prints[tid]}")
            sys.exit(1)

    seq_rate = total / best_seq
    mt_rate = total / best_mt
    # how the packer's dispatch actually routed at the pack bucket: the
    # same tuned routing runtime/scheduler.py _dispatch_pack consults —
    # 1 means the tuner honestly kept single-device on this host
    from spacemesh_tpu.ops import scrypt
    from spacemesh_tpu.parallel import mesh as pmesh

    pack_mesh = pmesh.auto_mesh(scrypt.shape_bucket(pack))
    pack_devices = pack_mesh.size if pack_mesh is not None else 1
    log(f"multi-tenant: sequential {best_seq * 1e3:.0f}ms "
        f"({seq_rate:,.0f} labels/s), scheduled {best_mt * 1e3:.0f}ms "
        f"({mt_rate:,.0f} labels/s, {mt_rate / seq_rate:.2f}x, "
        f"pack_devices={pack_devices})")
    emit({
        "metric": "post_multi_tenant_labels_per_sec",
        "value": round(mt_rate, 1),
        "unit": "labels/s",
        "tenants": tenants,
        "labels_per_tenant": labels,
        "n": n,
        "pack_lanes": pack,
        "pack_devices": pack_devices,
        "sequential": round(seq_rate, 1),
        "vs_sequential": round(mt_rate / seq_rate, 2),
        "bit_identical": True,  # per-tenant sha256 + VRF nonce checked
        #                         above; a mismatch exits non-zero
    })


def verify_bench(total_items: int) -> None:
    """Serial vs farm-batched verification over one mixed workload."""
    import tempfile

    from spacemesh_tpu.verify import workload

    # composition: POST-heavy (the workload this repo accelerates) plus
    # the gossip sig/VRF/membership mix, ~12% invalid/malformed spread
    # across every kind. POST requests replicate 24 distinct proofs
    # (~8x, the gossip re-delivery fanout) — the farm's dedup is part of
    # what is being measured and is reported in the output.
    posts = max(total_items // 2, 8)
    vrfs = max(total_items // 8, 8)
    mems = max(total_items // 8, 8)
    sigs = max(total_items - posts - vrfs - mems, 16)
    with tempfile.TemporaryDirectory() as d:
        log(f"verify workload: {sigs} sigs + {vrfs} vrfs + {mems} "
            f"memberships + {posts} post proofs ...")
        w = workload.build(d, sigs=sigs, vrfs=vrfs, posts=posts,
                           memberships=mems, post_challenges=24)
        doc = workload.compare_serial_vs_farm(w)

    stats = doc["stats"]
    log(f"verify: serial {doc['serial_s']:.2f}s, "
        f"farm {doc['batched_s']:.2f}s "
        f"({doc['items']} items, {doc['rejected']} rejected, "
        f"occupancy<= {stats['max_occupancy']}, "
        f"dedup {stats['dedup_hits']})")
    emit({
        "metric": "verify_serial_s", "value": round(doc["serial_s"], 3),
        "unit": "s", "items": doc["items"], "rejected": doc["rejected"],
    })
    emit({
        "metric": "verify_batched_s", "value": round(doc["batched_s"], 3),
        "unit": "s", "items": doc["items"],
        "speedup": doc["speedup"],
        "batches": stats["batches"],
        "max_occupancy": stats["max_occupancy"],
        "dedup_hits": stats["dedup_hits"],
    })


def verifyd_bench(total_items: int) -> None:
    """verifyd headline: proofs verified/sec AT p99 latency under a
    heavy mixed open-loop load, through the network service over real
    sockets (spacemesh_tpu/verifyd/), vs a serial one-at-a-time client.

    The workload is the BASELINE.json second-metric shape scaled to the
    host (mixed NIPoST proofs + signatures + VRFs + memberships + k2pow
    witnesses; the 10k-NIPoST config is BENCH_VERIFYD_ITEMS=10000 on
    real hardware).  Before ANY rate is reported every verdict from the
    service — serial and open-loop — is asserted identical to inline
    verification; a mismatch exits non-zero so CI goes red.  Emits:
      {"metric": "verifyd_proofs_per_sec", "value": N, "unit":
       "items/s", "p99_ms": N, "serial": N, "vs_serial": N,
       "clients": C, "bit_identical": true, ...}
    """
    import asyncio
    import tempfile

    clients_n = int(os.environ.get("BENCH_VERIFYD_CLIENTS", 3))
    per_req = int(os.environ.get("BENCH_VERIFYD_PER_REQUEST", 32))
    posts = max(total_items // 4, 4)
    pows = max(total_items // 8, 8)
    vrfs = max(total_items // 16, 4)
    mems = max(total_items // 16, 4)
    sigs = max(total_items - posts - pows - vrfs - mems, 16)

    from spacemesh_tpu.core import signing
    from spacemesh_tpu.verify import workload
    from spacemesh_tpu.verifyd import VerifydClient, VerifydServer

    with tempfile.TemporaryDirectory() as d:
        log(f"verifyd workload: {sigs} sigs + {vrfs} vrfs + {mems} "
            f"memberships + {pows} k2pow + {posts} post proofs ...")
        w = workload.build(d, sigs=sigs, vrfs=vrfs, posts=posts,
                           memberships=mems, pows=pows,
                           post_challenges=min(24, posts))
        expected = w.inline_all()

        async def run() -> dict:
            server = VerifydServer(
                listen="127.0.0.1:0", post_params=w.post_params,
                post_seed=w.post_seed,
                workers=int(os.environ.get("BENCH_VERIFYD_WORKERS", 8)),
                default_rate=1e9, default_burst=1e9,
                max_pending_items=1 << 20)
            server.service.farm.ed_verifier = w.ed
            server.service.farm.vrf_verifier = w.vrf
            try:
                port = await server.start()
                base = f"http://127.0.0.1:{port}"
                reqs = w.requests

                cs = [VerifydClient(base, f"load-{i}")
                      for i in range(clients_n)]
                for c in cs:
                    await c.register(max_inflight=8)
                shards = [list(range(i, len(reqs), clients_n))
                          for i in range(clients_n)]
                lat: list = []
                got = [None] * len(reqs)

                async def one(c, idxs):
                    t1 = time.perf_counter()
                    vs = await c.verify([reqs[i] for i in idxs])
                    lat.append(time.perf_counter() - t1)
                    for i, v in zip(idxs, vs):
                        got[i] = v

                async def open_loop() -> None:
                    # open loop: every client's whole request schedule
                    # is issued up front; completions never gate
                    # arrivals
                    tasks = [one(c, shard[j:j + per_req])
                             for c, shard in zip(cs, shards)
                             for j in range(0, len(shard), per_req)]
                    await asyncio.gather(*tasks)

                # warm both paths' EXACT shapes untimed (per-bucket XLA
                # compiles are a once-per-machine cost the persistent
                # cache amortizes, not throughput): the POST verify
                # shape ladder first — farm batch composition varies
                # run to run, so every power-of-two bucket the farm can
                # produce is compiled up front — then the full
                # open-loop schedule once, plus a serial pass
                from spacemesh_tpu.post import verifier as post_verifier

                post_items = [r.item for r in reqs if r.kind == "post"]
                if post_items:
                    t0 = time.perf_counter()
                    k = 1
                    while k <= min(2 * len(post_items), 256):
                        await asyncio.to_thread(
                            post_verifier.verify_many,
                            (post_items * 3)[:k], w.post_params,
                            seed=w.post_seed)
                        k *= 2
                    log(f"verifyd: post shape-ladder warm "
                        f"{time.perf_counter() - t0:.1f}s")
                serial = VerifydClient(base, "serial")
                await serial.register()
                await open_loop()
                if got != expected:
                    return {"diverged": "warm"}
                # second warm pass: batch composition is timing-
                # dependent, so one pass can miss buckets the timed
                # phase would then compile
                got = [None] * len(reqs)
                await open_loop()
                if got != expected:
                    return {"diverged": "warm"}
                got = [None] * len(reqs)
                lat.clear()
                warm_serial = await serial.serial_verify(reqs)
                if warm_serial != expected:
                    return {"diverged": "warm-serial"}

                # best-of-N reps per phase (like every other bench
                # line): steady-state throughput, not scheduler noise
                reps = int(os.environ.get("BENCH_VERIFYD_REPS", 2))
                serial_s = float("inf")
                for _ in range(reps):
                    signing.clear_verify_cache()
                    t0 = time.perf_counter()
                    serial_got = await serial.serial_verify(reqs)
                    serial_s = min(serial_s, time.perf_counter() - t0)
                    if serial_got != expected:
                        return {"diverged": "serial"}
                await serial.aclose()

                # p99 is taken from the SAME rep whose wall time is
                # reported — "throughput at p99" must not pair one
                # rep's rate with another rep's tail
                open_s, best_lat = float("inf"), []
                for _ in range(reps):
                    signing.clear_verify_cache()
                    got = [None] * len(reqs)
                    lat.clear()
                    t0 = time.perf_counter()
                    await open_loop()
                    el = time.perf_counter() - t0
                    if got != expected:
                        return {"diverged": "open-loop"}
                    if el < open_s:
                        open_s, best_lat = el, list(lat)
                lat = best_lat
                for c in cs:
                    await c.aclose()
                if got != expected:
                    return {"diverged": "open-loop"}
                lat.sort()
                p99 = lat[min(int(len(lat) * 0.99), len(lat) - 1)]
                stats = server.service.stats_doc()
                return {"serial_s": serial_s, "open_s": open_s,
                        "p99_s": p99, "requests": len(lat),
                        "farm_batches": stats["farm"]["batches"],
                        "shed": stats["shed"],
                        "targets": stats["tuner"]["targets"]}
            finally:
                await server.close()

        doc = asyncio.run(run())

    if "diverged" in doc:
        # divergence must be a red build, not a quietly odd rate
        log(f"verifyd: FAILED — {doc['diverged']} verdicts diverged "
            f"from inline verification")
        sys.exit(1)
    n = len(expected)
    serial_rate = n / doc["serial_s"]
    open_rate = n / doc["open_s"]
    log(f"verifyd: serial {doc['serial_s']:.2f}s "
        f"({serial_rate:,.0f} items/s), open-loop {doc['open_s']:.2f}s "
        f"({open_rate:,.0f} items/s, {open_rate / serial_rate:.2f}x, "
        f"p99 {doc['p99_s'] * 1e3:.1f}ms, "
        f"{doc['farm_batches']} farm batches)")
    emit({
        "metric": "verifyd_proofs_per_sec",
        "value": round(open_rate, 1),
        "unit": "items/s",
        "p99_ms": round(doc["p99_s"] * 1e3, 2),
        "serial": round(serial_rate, 1),
        "vs_serial": round(open_rate / serial_rate, 2),
        "clients": clients_n,
        "items": n,
        "requests": doc["requests"],
        "shed": doc["shed"],
        "batch_targets": doc["targets"],
        "bit_identical": True,  # serial + open-loop verdicts checked
        #                         against inline above; a mismatch
        #                         exits non-zero before this line
    })


# child-process replica for the fleet bench: one real verifyd server
# per OS process (the fleet's whole point is capacity past one
# process), bound ports printed as the first stdout line, serving until
# stdin closes
_FLEET_REPLICA_SRC = r"""
import asyncio, json, sys

cfg = json.loads(sys.argv[1])


async def main():
    from spacemesh_tpu.post.prover import ProofParams
    from spacemesh_tpu.verifyd.server import VerifydServer

    params = ProofParams(
        k1=cfg["k1"], k2=cfg["k2"], k3=cfg["k3"],
        pow_difficulty=bytes.fromhex(cfg["pow_difficulty"]))
    server = VerifydServer(
        listen="127.0.0.1:0", post_params=params,
        post_seed=bytes.fromhex(cfg["post_seed"]),
        workers=cfg["workers"], default_rate=1e9, default_burst=1e9,
        max_pending_items=1 << 20)
    try:
        port = await server.start()
        print(json.dumps({"port": port}), flush=True)
        await asyncio.get_running_loop().run_in_executor(
            None, sys.stdin.read)
    finally:
        await server.close()


asyncio.run(main())
"""


class _SentinelFarm:
    """The fleet bench's local farm: reaching it means a replica
    failed mid-measurement — fail loudly, never quietly fold local
    verification into a 'fleet' rate."""

    async def submit(self, req, lane=None):
        raise RuntimeError("fleet bench fell back to the local farm")


def _spawn_fleet_replicas(count: int, cfg: dict,
                          pins: list | None = None) -> list:
    here = os.path.dirname(os.path.abspath(__file__))
    procs = []
    try:
        for i in range(count):
            argv = [sys.executable, "-c", _FLEET_REPLICA_SRC,
                    json.dumps(cfg)]
            if pins is not None:
                argv = ["taskset", "-c",
                        ",".join(str(c) for c in pins[i])] + argv
            p = subprocess.Popen(
                argv, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                text=True, cwd=here)
            procs.append(p)
        for p in procs:
            line = p.stdout.readline()
            p.port = json.loads(line)["port"]
        return procs
    except Exception:
        _stop_fleet_replicas(procs)
        raise


def _stop_fleet_replicas(procs: list) -> None:
    for p in procs:
        try:
            p.stdin.close()
        except Exception:  # noqa: BLE001 — already dead is fine
            pass
    for p in procs:
        try:
            p.wait(timeout=30)
        except Exception:  # noqa: BLE001 — drain hang: don't leak it
            p.kill()


def fleet_bench(total_items: int) -> None:
    """verifyd FLEET headline (ISSUE 17): proofs/sec through a
    3-replica fleet of real verifyd server PROCESSES behind the
    FleetRouter's consistent-hash placement, vs the same workload
    through a single verifyd process driven by the identical
    FleetVerifier plumbing (1-replica fleet — same client overhead, so
    the ratio isolates the fleet's capacity, not the driver).

    The mix is verification-heavy (k2pow + POST dominate) so the
    measured resource is server-side compute — the thing replicas
    multiply.  Every verdict from BOTH phases is asserted identical to
    inline verification before any rate is reported; a mismatch or any
    local-farm fallback exits non-zero.  Emits:
      {"metric": "verifyd_fleet_proofs_per_sec", "value": N,
       "unit": "items/s", "single": N, "vs_single": N, "replicas": 3,
       "clients": C, "cores": C, "pinned": bool,
       "bit_identical": true}

    Replica processes (and the baseline) pin to disjoint core slices
    when the host has one per replica — one replica per host is the
    fleet's deployment, and without pinning a lone XLA process eats
    every core and the ratio measures contention, not capacity.  The
    >= 1.5x acceptance floor (BENCH_FLEET_MIN_SPEEDUP=1.5) is enforced
    only on such hosts; elsewhere the benchtrend ``vs_single`` gate
    guards regressions.
    """
    import asyncio
    import tempfile

    replicas_n = int(os.environ.get("BENCH_FLEET_REPLICAS", 3))
    clients_n = int(os.environ.get("BENCH_FLEET_CLIENTS", 6))
    per_req = int(os.environ.get("BENCH_FLEET_PER_REQUEST", 8))
    workers = int(os.environ.get("BENCH_FLEET_WORKERS", 4))
    reps = int(os.environ.get("BENCH_FLEET_REPS", 2))
    min_speedup = float(os.environ.get("BENCH_FLEET_MIN_SPEEDUP", 0))

    # one replica per HOST is the fleet's deployment: model it by
    # pinning each replica process to its own disjoint core slice (the
    # baseline gets exactly one slice — a single host's capacity).
    # Unpinned, a lone XLA process already eats every core and N
    # co-scheduled replicas can only contend, so the ratio would
    # measure the host, not the fleet.
    try:
        cores = sorted(os.sched_getaffinity(0))
    except AttributeError:   # non-linux fallback
        cores = list(range(os.cpu_count() or 1))
    pin = (int(os.environ.get("BENCH_FLEET_PIN", 1)) != 0
           and shutil.which("taskset") is not None
           and len(cores) >= replicas_n > 1)
    slices = None
    if pin:
        per_slice = len(cores) // replicas_n
        slices = [cores[i * per_slice:(i + 1) * per_slice]
                  for i in range(replicas_n)]

    pows = max(total_items // 2, 8)
    posts = max(total_items // 8, 4)
    vrfs = max(total_items // 16, 4)
    mems = max(total_items // 16, 4)
    sigs = max(total_items - pows - posts - vrfs - mems, 16)

    from spacemesh_tpu.verify import workload
    from spacemesh_tpu.verifyd.fleet import fleet_from_urls

    with tempfile.TemporaryDirectory() as d:
        log(f"fleet workload: {sigs} sigs + {vrfs} vrfs + {mems} "
            f"memberships + {pows} k2pow + {posts} post proofs ...")
        w = workload.build(d, sigs=sigs, vrfs=vrfs, posts=posts,
                           memberships=mems, pows=pows,
                           post_challenges=min(8, posts))
        expected = w.inline_all()
        reqs = w.requests
        cfg = {"k1": w.post_params.k1, "k2": w.post_params.k2,
               "k3": w.post_params.k3,
               "pow_difficulty": w.post_params.pow_difficulty.hex(),
               "post_seed": w.post_seed.hex(), "workers": workers}

        cids = [f"load-{i}" for i in range(clients_n)]
        shards = [list(range(i, len(reqs), clients_n))
                  for i in range(clients_n)]

        async def drive(urls: list[str]) -> float:
            """Open-loop load through a FleetVerifier over ``urls``;
            returns best-of-reps wall seconds (inf on divergence)."""
            fv = fleet_from_urls(urls, farm=_SentinelFarm(),
                                 client_id="bench")
            try:
                fv.start()
                # pre-register every driver identity with open-loop
                # quotas (FleetVerifier's lazy register is a reconfig
                # no-op, so these knobs stick); a quota shed mid-run
                # would poison a breaker and fail the bench
                for rep in fv.router.replicas.values():
                    for cid in cids:
                        await rep.endpoint.register(
                            cid, max_queued=1 << 16, max_inflight=64)
                got = [None] * len(reqs)

                async def one(cid, idxs):
                    vs = await fv.verify_batch(
                        [reqs[i] for i in idxs], client_id=cid)
                    for i, v in zip(idxs, vs):
                        got[i] = v

                async def open_loop():
                    tasks = [one(cid, shard[j:j + per_req])
                             for cid, shard in zip(cids, shards)
                             for j in range(0, len(shard), per_req)]
                    await asyncio.gather(*tasks)

                # two untimed passes: per-shape farm compiles inside
                # each replica process are a once-per-host cost, and
                # batch composition varies pass to pass
                for _ in range(2):
                    got = [None] * len(reqs)
                    await open_loop()
                    if got != expected:
                        return float("inf")
                best = float("inf")
                for _ in range(reps):
                    got = [None] * len(reqs)
                    t0 = time.perf_counter()
                    await open_loop()
                    el = time.perf_counter() - t0
                    if got != expected:
                        return float("inf")
                    best = min(best, el)
                if fv.stats["local"] or fv.stats["local_fastfail"]:
                    return float("inf")   # a replica died mid-run
                return best
            finally:
                await fv.aclose()

        def phase(count: int) -> float:
            pins = slices[:count] if slices is not None else None
            procs = _spawn_fleet_replicas(count, cfg, pins)
            try:
                urls = [f"http://127.0.0.1:{p.port}" for p in procs]
                return asyncio.run(drive(urls))
            finally:
                _stop_fleet_replicas(procs)

        if pin:
            log(f"fleet: pinning each replica to "
                f"{len(slices[0])} core(s) of {len(cores)}")
        else:
            log(f"fleet: NOT pinning ({len(cores)} core(s) for "
                f"{replicas_n} replicas) — a single XLA process "
                f"already saturates this host, so vs_single measures "
                f"overhead, not fleet capacity")
        log(f"fleet: single-process baseline ({workers} workers) ...")
        single_s = phase(1)
        log(f"fleet: {replicas_n}-replica fleet ...")
        fleet_s = phase(replicas_n)

    if single_s == float("inf") or fleet_s == float("inf"):
        log("fleet: FAILED — verdicts diverged from inline "
            "verification or the fleet fell back to the local farm")
        sys.exit(1)
    n = len(expected)
    single_rate = n / single_s
    fleet_rate = n / fleet_s
    ratio = fleet_rate / single_rate
    log(f"fleet: single {single_s:.2f}s ({single_rate:,.0f} items/s), "
        f"{replicas_n} replicas {fleet_s:.2f}s ({fleet_rate:,.0f} "
        f"items/s, {ratio:.2f}x)")
    emit({
        "metric": "verifyd_fleet_proofs_per_sec",
        "value": round(fleet_rate, 1),
        "unit": "items/s",
        "single": round(single_rate, 1),
        "vs_single": round(ratio, 2),
        "replicas": replicas_n,
        "clients": clients_n,
        "items": n,
        "cores": len(cores),
        "pinned": bool(pin),
        "bit_identical": True,  # both phases' verdicts checked against
        #                         inline above; a mismatch exits
        #                         non-zero before this line
    })
    # the >= 1.5x acceptance floor needs one core slice per replica
    # (BENCH_FLEET_MIN_SPEEDUP=1.5 on such hosts); everywhere else the
    # benchtrend vs_single gate is the regression guard
    if min_speedup > 0 and pin and ratio < min_speedup:
        log(f"fleet: FAILED — {ratio:.2f}x < required "
            f"{min_speedup:.2f}x speedup over a single replica")
        sys.exit(1)


# Child body for one fabric measurement. A subprocess per run because
# the fabric is chosen at hub-construction time from the environment and
# because each run must start from a cold loop/registry — measuring both
# fabrics in one process would let the first run's compiled/warmed state
# (and its metric registry) bleed into the second.
_SIM_FABRIC_SRC = """\
import json, pathlib, sys, tempfile, time

from spacemesh_tpu.sim import builtin
from spacemesh_tpu.sim.scenario import run_scenario

with tempfile.TemporaryDirectory() as d:
    t0 = time.perf_counter()
    r = run_scenario(builtin("storm-512-bench"), tmp=pathlib.Path(d))
    wall = time.perf_counter() - t0
hub = r.stats["hub"]
print(json.dumps({
    "ok": r.ok, "digest": r.digest, "sim_wall": round(wall, 3),
    "delivered": hub["delivered"], "relayed": hub["relayed"]}))
"""


def sim_fabric_bench() -> None:
    """Event-wheel scenario fabric vs the legacy task-per-node hub.

    Runs the 514-node ``storm-512-bench`` scenario (sim/scenarios.py: a
    pure-fabric shape — smeshing and tracing off, sparse heartbeats, a
    long quiet tail — so the measurement is the hub's idle+relay cost,
    not the shared consensus/crypto floor) once per fabric in fresh
    subprocesses: the event fabric twice (replay determinism) and the
    legacy hub once.  The scenario digest — the full consensus/coverage
    event record — must be IDENTICAL across all three runs before any
    rate is reported; a divergent world means the fabrics delivered
    different messages and the ratio would be fiction:
      {"metric": "sim_fabric_events_per_sec", "value": N,
       "unit": "events/s", "vs_legacy": N, "bit_identical": true}
    The rate counts useful deliveries (frames handed to a subscriber)
    per wall second; both fabrics deliver the same world, so vs_legacy
    is a pure cost ratio, not a throughput-shape artifact.
    """
    timeout = int(os.environ.get("BENCH_SIM_FABRIC_TIMEOUT", 600))
    log(f"sim fabric: storm-512-bench on both fabrics "
        f"(subprocess runs, <= {timeout}s each) ...")

    def run_one(fabric: str, tag: str) -> dict | None:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   SPACEMESH_SIM_FABRIC=fabric)
        try:
            r = subprocess.run(
                [sys.executable, "-c", _SIM_FABRIC_SRC], env=env,
                timeout=timeout, capture_output=True, text=True)
        except subprocess.TimeoutExpired:
            log(f"sim fabric: {tag} timed out (> {timeout}s)")
            return None
        if r.returncode != 0:
            log(f"sim fabric: {tag} failed (rc={r.returncode})")
            sys.stderr.write(r.stderr)
            return None
        doc = None
        for line in reversed(r.stdout.strip().splitlines()):
            try:
                doc = json.loads(line)
                break
            except ValueError:
                continue
        if not isinstance(doc, dict) or not doc.get("ok"):
            log(f"sim fabric: {tag} scenario asserts failed")
            return None
        log(f"sim fabric: {tag}: {doc['sim_wall']:.2f}s, "
            f"{doc['delivered']} delivered, {doc['relayed']} relayed, "
            f"digest {doc['digest'][:16]}")
        return doc

    new1 = run_one("", "event #1")
    new2 = run_one("", "event #2")
    leg = run_one("legacy", "legacy")
    if new1 is None or new2 is None or leg is None:
        log("sim fabric: FAILED — a measurement run did not complete")
        sys.exit(1)
    if new1["digest"] != new2["digest"]:
        log(f"sim fabric: FAILED — event fabric replay diverged "
            f"({new1['digest'][:16]} vs {new2['digest'][:16]})")
        sys.exit(1)
    if new1["digest"] != leg["digest"]:
        log(f"sim fabric: FAILED — event vs legacy digests diverged "
            f"({new1['digest'][:16]} vs {leg['digest'][:16]})")
        sys.exit(1)

    wall_new = min(new1["sim_wall"], new2["sim_wall"])
    rate_new = new1["delivered"] / wall_new
    rate_leg = leg["delivered"] / leg["sim_wall"]
    ratio = rate_new / rate_leg
    log(f"sim fabric: event {wall_new:.2f}s ({rate_new:,.0f} events/s), "
        f"legacy {leg['sim_wall']:.2f}s ({rate_leg:,.0f} events/s, "
        f"{ratio:.2f}x)")
    emit({
        "metric": "sim_fabric_events_per_sec",
        "value": round(rate_new, 1),
        "unit": "events/s",
        "legacy": round(rate_leg, 1),
        "vs_legacy": round(ratio, 2),
        "delivered": new1["delivered"],
        "relayed": new1["relayed"],
        "event_wall_s": round(wall_new, 2),
        "legacy_wall_s": round(leg["sim_wall"], 2),
        "bit_identical": True,  # all three digests checked identical
        #                         above; a mismatch exits non-zero
        #                         before this line
    })


def sim_fabric_mp_bench() -> None:
    """Sharded (multi-process) scenario fabric vs single-process.

    Runs ``storm-512-bench`` with the event wheel sharded over host
    cores (sim/shard.py: conservative virtual-time windows over pipes)
    and single-process, twice each in fresh subprocesses.  The scenario
    is the CLEAN-LINK world — no RNG is ever drawn from the data-plane
    policies — so all four digests (two per shard count) must be
    IDENTICAL before any rate is reported; a divergence means the
    sharded fabric delivered a different world and the ratio would be
    fiction:
      {"metric": "sim_fabric_mp_events_per_sec", "value": N,
       "unit": "events/s", "single": N, "vs_single_process": N,
       "shards": W, "cores": C, "bit_identical": true}
    On hosts without at least two usable cores the fabric is kept
    single-process and the verdict says so honestly (shards=1,
    vs_single_process=1.0) rather than faking a speedup through
    oversubscription; the >= 1.5x acceptance floor
    (BENCH_SIM_FABRIC_MP_MIN_SPEEDUP) is enforced only where the
    parent and every worker get their own core — everywhere else the
    benchtrend vs_single_process gate is the regression guard.
    """
    timeout = int(os.environ.get("BENCH_SIM_FABRIC_MP_TIMEOUT", 900))
    cores = sorted(os.sched_getaffinity(0))
    want = int(os.environ.get("BENCH_SIM_FABRIC_MP_SHARDS", 0))
    shards = want or min(len(cores), 510 // 64)
    capable = len(cores) >= 2 and shards >= 2
    # fleet-bench pattern: the >= 1.5x floor is enforced only where the
    # parent and every worker get their own core (oversubscribed or
    # shared runners measure contention, not the fabric) — everywhere
    # else the benchtrend vs_single_process gate is the guard
    pinned = capable and len(cores) >= shards + 1
    min_speedup = float(os.environ.get(
        "BENCH_SIM_FABRIC_MP_MIN_SPEEDUP", 1.5 if pinned else 0))
    log(f"sim fabric mp: storm-512-bench single-process vs "
        f"{shards}-shard on {len(cores)} core(s) "
        f"(subprocess runs, <= {timeout}s each) ...")

    def run_one(w: int, tag: str) -> dict | None:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   SPACEMESH_SIM_FABRIC="",
                   SPACEMESH_SIM_SHARDS=str(w))
        try:
            r = subprocess.run(
                [sys.executable, "-c", _SIM_FABRIC_SRC], env=env,
                timeout=timeout, capture_output=True, text=True)
        except subprocess.TimeoutExpired:
            log(f"sim fabric mp: {tag} timed out (> {timeout}s)")
            return None
        if r.returncode != 0:
            log(f"sim fabric mp: {tag} failed (rc={r.returncode})")
            sys.stderr.write(r.stderr)
            return None
        doc = None
        for line in reversed(r.stdout.strip().splitlines()):
            try:
                doc = json.loads(line)
                break
            except ValueError:
                continue
        if not isinstance(doc, dict) or not doc.get("ok"):
            log(f"sim fabric mp: {tag} scenario asserts failed")
            return None
        log(f"sim fabric mp: {tag}: {doc['sim_wall']:.2f}s, "
            f"{doc['delivered']} delivered, digest {doc['digest'][:16]}")
        return doc

    if capable and not pinned:
        log(f"sim fabric mp: NOT enforcing the speedup floor "
            f"({len(cores)} core(s) for {shards} workers + parent) — "
            f"vs_single_process measures contention here, so the "
            f"benchtrend ratio gate is the regression guard")
    s1 = run_one(1, "single #1")
    s2 = run_one(1, "single #2")
    if s1 is None or s2 is None:
        log("sim fabric mp: FAILED — a single-process run did not "
            "complete")
        sys.exit(1)
    if s1["digest"] != s2["digest"]:
        log(f"sim fabric mp: FAILED — single-process replay diverged "
            f"({s1['digest'][:16]} vs {s2['digest'][:16]})")
        sys.exit(1)
    wall_single = min(s1["sim_wall"], s2["sim_wall"])
    rate_single = s1["delivered"] / wall_single

    if not capable:
        log(f"sim fabric mp: kept single-process — {len(cores)} "
            f"core(s) visible; sharding would oversubscribe, not "
            f"speed up")
        emit({
            "metric": "sim_fabric_mp_events_per_sec",
            "value": round(rate_single, 1),
            "unit": "events/s",
            "single": round(rate_single, 1),
            "vs_single_process": 1.0,
            "delivered": s1["delivered"],
            "shards": 1,
            "cores": len(cores),
            "pinned": False,
            "kept_single_process": True,
            "bit_identical": True,  # both single-process digests
            #                         checked identical above
        })
        return

    m1 = run_one(shards, f"{shards}-shard #1")
    m2 = run_one(shards, f"{shards}-shard #2")
    if m1 is None or m2 is None:
        log("sim fabric mp: FAILED — a sharded run did not complete")
        sys.exit(1)
    if m1["digest"] != m2["digest"]:
        log(f"sim fabric mp: FAILED — sharded replay diverged "
            f"({m1['digest'][:16]} vs {m2['digest'][:16]})")
        sys.exit(1)
    if m1["digest"] != s1["digest"]:
        # clean links draw nothing from the net RNG, so W=1 and W=k
        # must land the IDENTICAL digest (docs/SCENARIOS.md)
        log(f"sim fabric mp: FAILED — sharded vs single digests "
            f"diverged ({m1['digest'][:16]} vs {s1['digest'][:16]})")
        sys.exit(1)

    wall_mp = min(m1["sim_wall"], m2["sim_wall"])
    rate_mp = m1["delivered"] / wall_mp
    ratio = rate_mp / rate_single
    log(f"sim fabric mp: single {wall_single:.2f}s "
        f"({rate_single:,.0f} events/s), {shards} shards "
        f"{wall_mp:.2f}s ({rate_mp:,.0f} events/s, {ratio:.2f}x)")
    emit({
        "metric": "sim_fabric_mp_events_per_sec",
        "value": round(rate_mp, 1),
        "unit": "events/s",
        "single": round(rate_single, 1),
        "vs_single_process": round(ratio, 2),
        "delivered": m1["delivered"],
        "shards": shards,
        "cores": len(cores),
        "pinned": pinned,
        "single_wall_s": round(wall_single, 2),
        "mp_wall_s": round(wall_mp, 2),
        "bit_identical": True,  # all four digests checked identical
        #                         above; a mismatch exits non-zero
        #                         before this line
    })
    if min_speedup > 0 and ratio < min_speedup:
        log(f"sim fabric mp: FAILED — {ratio:.2f}x < required "
            f"{min_speedup:.2f}x speedup over single-process")
        sys.exit(1)


def main() -> None:
    n = int(os.environ.get("BENCH_N", 8192))
    reps = int(os.environ.get("BENCH_REPS", 3))
    cpu_count = int(os.environ.get("BENCH_CPU_LABELS", 24))
    batches = [int(b) for b in os.environ.get(
        "BENCH_BATCH", "8192,4096,2048,1024").split(",")]

    commitment = hashlib.sha256(b"bench-commitment").digest()

    from spacemesh_tpu.utils import accel

    cache_dir = accel.enable_persistent_cache()
    log(f"persistent compile cache: {cache_dir}")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from spacemesh_tpu.ops import scrypt

    dev = jax.devices()[0]
    log(f"device: {dev} platform={dev.platform} "
        f"device_kind={dev.device_kind} count={jax.device_count()}")

    cw = jnp.asarray(scrypt.commitment_to_words(commitment))
    compile_times: dict[int, float] = {}

    def measure(batch: int) -> float:
        idx = np.arange(batch, dtype=np.uint64)
        lo_, hi_ = scrypt.split_indices(idx)
        lo, hi = jnp.asarray(lo_), jnp.asarray(hi_)
        t0 = time.perf_counter()
        out = scrypt.scrypt_labels_jit(cw, lo, hi, n=n)
        out.block_until_ready()
        compile_s = time.perf_counter() - t0
        compile_times.setdefault(batch, compile_s)
        log(f"batch={batch}: compile+first run {compile_s:.1f}s")
        # steady state: the compiled executable is reused for every rep,
        # all reps enqueued back-to-back, one sync at the end (pipelined,
        # as post/initializer.py drives the device)
        t0 = time.perf_counter()
        outs = [scrypt.scrypt_labels_jit(cw, lo, hi, n=n)
                for _ in range(reps)]
        jax.block_until_ready(outs)
        return reps * batch / (time.perf_counter() - t0)

    best_rate, best_batch = 0.0, 0
    failed_batches: list[dict] = []  # reported in the headline line
    for batch in batches:
        try:
            rate = measure(batch)
            log(f"batch={batch}: {rate:,.0f} labels/s")
            if rate > best_rate:
                best_rate, best_batch = rate, batch
        except Exception as e:  # noqa: BLE001 — e.g. HBM OOM at big batches
            log(f"batch={batch}: failed ({type(e).__name__}: {e})")
            failed_batches.append(
                {"batch": batch,
                 "error": f"{type(e).__name__}: {e}"[:300]})
    if best_rate == 0.0:
        raise SystemExit(f"all batch sizes failed: {failed_batches}")

    # kernel-only throughput: the ROMix stage alone on a fixed random
    # block — isolates the memory-hard core from the PBKDF2 envelope +
    # host dispatch that the headline number includes
    x = jnp.asarray(np.random.RandomState(7).randint(
        0, 2**32, size=(32, best_batch), dtype=np.uint64).astype(np.uint32))

    def romix_only():
        return scrypt._stage_romix_xla(x, n=n)

    romix_only().block_until_ready()  # compile
    t0 = time.perf_counter()
    jax.block_until_ready([romix_only() for _ in range(reps)])
    kernel_rate = reps * best_batch / (time.perf_counter() - t0)
    log(f"kernel-only (romix): {kernel_rate:,.0f} labels/s")

    def single_device_digest() -> str:
        # single-device label digest for the mesh bit-identity check (one
        # more steady-state run of the compiled executable); only paid
        # when a mesh measurement actually produced a rate to vet
        idx = np.arange(best_batch, dtype=np.uint64)
        lo_, hi_ = scrypt.split_indices(idx)
        single_words = scrypt.scrypt_labels_jit(
            cw, jnp.asarray(lo_), jnp.asarray(hi_), n=n)
        return hashlib.sha256(
            scrypt.labels_to_bytes(np.asarray(single_words))).hexdigest()

    mesh_doc = None
    if os.environ.get("BENCH_MESH", "1") not in ("0", "off"):
        if jax.default_backend() == "cpu":
            # CPU platform: forced virtual host devices split the CPU
            # thread pool, so the mesh measurement lives in a subprocess
            # — the numbers above stay single-device-with-all-threads
            mesh_doc = run_mesh_probe(n, best_batch, reps)
        elif jax.device_count() > 1:
            mesh_doc = measure_mesh(n, best_batch, reps)
    if mesh_doc is not None and mesh_doc.get("labels_per_sec") \
            and mesh_doc.get("digest") != single_device_digest():
        # corrupted sharded labels must be a red build, not a quietly
        # missing headline (CI greps can't tell absent from broken)
        log(f"mesh: FAILED — sharded labels diverged from the "
            f"single-device digest at n={n} b={best_batch} "
            f"d={mesh_doc.get('devices')}")
        sys.exit(1)

    log(f"CPU baseline: {cpu_count} labels via hashlib.scrypt ...")
    cpu_rate = cpu_labels_per_sec(commitment, n, cpu_count)
    log(f"cpu: {cpu_rate:,.1f} labels/s (single core, OpenSSL)")

    emit({
        "metric": f"post_init_labels_per_sec_n{n}_b{best_batch}",
        "value": round(best_rate, 1),
        "unit": "labels/s",
        "vs_baseline": round(best_rate / cpu_rate, 2),
        "impl": "xla",
        "chunk": None,
        "fused": True,  # expand->romix->finish as one jitted program
        "failed_batches": failed_batches,
    })
    emit({
        "metric": "post_init_kernel_labels_per_sec",
        "value": round(kernel_rate, 1),
        "unit": "labels/s",
        "impl": "xla",
        "chunk": None,
        "batch": best_batch,
    })
    if mesh_doc is not None and mesh_doc.get("labels_per_sec"):
        mesh_rate = mesh_doc["labels_per_sec"]
        log(f"mesh: {mesh_rate:,.0f} labels/s over "
            f"{mesh_doc['devices']} devices ({mesh_rate / best_rate:.2f}x "
            f"single-device)")
        emit({
            "metric": f"post_init_labels_per_sec_mesh_n{n}"
                      f"_b{best_batch}",
            "value": mesh_rate,
            "unit": "labels/s",
            "devices": mesh_doc["devices"],
            "devices_visible": mesh_doc.get("devices_visible"),
            "impl": mesh_doc["impl"],
            "tuned": mesh_doc.get("tuned"),
            "vs_single": round(mesh_rate / best_rate, 2),
            "vs_baseline": round(mesh_rate / cpu_rate, 2),
            "compile_s": mesh_doc.get("compile_s"),
            "bit_identical": True,  # digest-checked above; a mismatch
            #                         exits non-zero before this line
        })
    elif mesh_doc is not None:
        log(f"mesh: the mesh rule kept single-device "
            f"(devices={mesh_doc.get('devices')}); no mesh headline")

    # compile cost of the winning shape, reported separately: near-zero on
    # a warm persistent cache, the full XLA compile on a cold one
    emit({
        "metric": "post_init_compile_s",
        "value": round(compile_times.get(best_batch, 0.0), 2),
        "unit": "s",
        "cache_dir": cache_dir,
    })

    prove_labels = int(os.environ.get("BENCH_PROVE_LABELS", 1 << 16))
    if prove_labels > 0:
        prove_bench(prove_labels,
                    int(os.environ.get("BENCH_PROVE_BATCH", 2048)))

    if int(os.environ.get("BENCH_TENANTS", 16)) > 0:
        if jax.default_backend() == "cpu" \
                and os.environ.get("BENCH_MESH", "1") not in ("0", "off"):
            # CPU platform: measure the packer over forced virtual host
            # devices in a subprocess (the mesh-sharded pack dispatch),
            # keeping this process honestly single-device
            run_mt_probe()
        else:
            multi_tenant_bench()

    verify_items = int(os.environ.get("BENCH_VERIFY_ITEMS", 512))
    if verify_items > 0:
        verify_bench(verify_items)

    verifyd_items = int(os.environ.get("BENCH_VERIFYD_ITEMS", 384))
    if verifyd_items > 0:
        verifyd_bench(verifyd_items)

    # the three phases below start child processes that import JAX
    fleet_items = int(os.environ.get("BENCH_FLEET_ITEMS", 384))
    if fleet_items > 0 \
            and not children_refused("verifyd_fleet_proofs_per_sec"):
        fleet_bench(fleet_items)

    if os.environ.get("BENCH_SIM_FABRIC", "1") not in ("0", "off") \
            and not children_refused("sim_fabric_events_per_sec"):
        sim_fabric_bench()

    if os.environ.get("BENCH_SIM_FABRIC_MP", "1") not in ("0", "off") \
            and not children_refused("sim_fabric_mp_events_per_sec"):
        sim_fabric_mp_bench()


if __name__ == "__main__":
    if "--mesh-probe" in sys.argv[1:]:
        raise SystemExit(mesh_probe_main())
    if "--mt-probe" in sys.argv[1:]:
        raise SystemExit(mt_probe_main())
    main()
