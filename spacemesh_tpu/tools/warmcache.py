"""Pre-warm the persistent XLA compile cache for the label shapes.

The label pipeline pays 17-26s of XLA compile per (N, batch) executable
on a cold host — a cost that dominates every short session (bench runs,
CI jobs, a node's first init batch after an upgrade). The persistent
compile cache (utils/accel.py) already makes that once-per-machine;
this tool makes it once-per-NOBODY by compiling the shapes ahead of
time, so tier-1/bench/operator sessions start warm (ISSUE 6; the CI
warm-cache job publishes the resulting cache directory and every other
job restores it).

What gets compiled per (N, bucketed batch):

* the fused single-device label programs (``_labels_fused`` and the
  min-scan variant) — the executables the verifier's recomputes and a
  one-device init hit;
* where the mesh rule shards a batch of that width (parallel/mesh.py
  ``auto_mesh``: more than one accelerator, or ``SPACEMESH_MESH``): the
  GSPMD-sharded twins — the executables the streaming initializer hits;
* with ``--prove``: the streaming prover's window step (the program a
  default Prover runs on this platform) at its default (bucketed) batch.

Shapes already in the cache deserialize in well under a second; the
per-program seconds in the output tell you which were actually cold.

Beyond the label shapes, every workload kind registered with the device
runtime (runtime/workloads.py: fused init, packed multi-tenant init,
prove scan step, verify batch, k2pow) warms its own executables at the
primary shape — so a cold 16-tenant start pays ZERO serialized compiles
across kinds (the runtime scheduler's first mixed admission hits a warm
cache for every kind it can dispatch).  ``--no-runtime`` skips that.

Usage:
  python -m spacemesh_tpu.tools.warmcache [--n 8192]
      [--batches 8192,4096,2048,1024,512] [--prove] [--no-mesh]
      [--no-runtime] [--pack-lanes 4096]
  python -m spacemesh_tpu.tools.profiler --warm      # same, via profiler
"""

from __future__ import annotations

import argparse
import hashlib
import json
import sys
import time


def _log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def _warm_shape(n: int, batch: int, mesh_ok: bool) -> dict:
    """Compile (or cache-deserialize) every executable one (n, batch)
    shape runs at; returns per-program seconds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops import scrypt

    commitment = hashlib.sha256(b"warmcache").digest()
    cw = scrypt.commitment_to_words(commitment)
    idx = np.arange(batch, dtype=np.uint64)
    lo, hi = scrypt.split_indices(idx)
    jcw, jlo, jhi = jnp.asarray(cw), jnp.asarray(lo), jnp.asarray(hi)

    doc: dict = {"n": n, "batch": batch, "programs": {}}

    def timed(name, fn):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        doc["programs"][name] = round(time.perf_counter() - t0, 2)
        _log(f"  {name}: {doc['programs'][name]}s")

    timed("labels_fused", lambda: scrypt.scrypt_labels_jit(
        jcw, jlo, jhi, n=n))
    timed("labels_min_fused", lambda: scrypt.scrypt_labels_with_min(
        jcw, jlo, jhi, jnp.asarray(scrypt.vrf_carry_init()), n=n)[0])

    if not mesh_ok:
        return doc
    from ..parallel import mesh as pmesh

    mesh = pmesh.auto_mesh(batch)
    doc["devices"] = mesh.size if mesh else 1
    if mesh is None:
        return doc
    timed(f"labels_sharded_d{mesh.size}",
          lambda: pmesh.scrypt_labels_sharded(mesh, cw, lo, hi, n=n))
    timed(f"labels_min_sharded_d{mesh.size}",
          lambda: pmesh.labels_with_min_sharded(
              mesh, cw, lo, hi, scrypt.vrf_carry_init(), n=n)[0])
    return doc


def _warm_prove(batch: int) -> dict:
    """Compile the window step a default streaming prover runs here, at
    its bucketed batch and a full flight of batches (the runtime's
    ``prove_scan`` recipe)."""
    from ..runtime import workloads

    doc = workloads.get("prove_scan").warm(0, batch)
    _log(f"  prove_scan_step_window b={doc['batch']} "
         f"x{doc['flight_batches']} groups={doc['groups']}: "
         f"{doc['prove_scan_step_window']}s")
    return doc


def _warm_runtime_kinds(n: int, batch: int, pack_lanes: int) -> dict:
    """Warm every registered runtime workload kind's executables.

    The packed init / verify kinds warm at the PACK bucket (the shape
    the multi-tenant scheduler composes), the rest at the session
    ``batch``; each kind's recipe lives beside the kind itself
    (runtime/workloads.py), so a new workload registered there is
    automatically covered here and by the CI warm-cache job.
    """
    from ..ops import scrypt
    from ..runtime import workloads

    pack = scrypt.shape_bucket(pack_lanes)
    out: dict = {}
    for kind in workloads.registered():
        b = pack if kind.name in ("init_pack", "verify") else batch
        _log(f"warming runtime kind {kind.name} (n={n} b={b}) ...")
        try:
            out[kind.name] = dict(kind.warm(n, b), batch=b)
        except Exception as e:  # noqa: BLE001 — e.g. OOM at big batches
            _log(f"  {kind.name} failed ({type(e).__name__}: {e})")
            out[kind.name] = {"failed": type(e).__name__}
    return out


def warm(n: int = 8192, batches: list[int] | None = None, *,
         mesh: bool = True, prove: bool = False,
         runtime_kinds: bool = True, pack_lanes: int = 4096) -> dict:
    """Warm the persistent caches; returns a JSON-able report."""
    import os

    from ..utils import accel

    if mesh and os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        # BEFORE any backend use (jax.default_backend below instantiates
        # it): expose the virtual host devices a forced SPACEMESH_MESH
        # shards over
        accel.ensure_host_devices()
    import jax

    platform = jax.default_backend()
    cache_dir = accel.enable_persistent_cache()
    _log(f"persistent compile cache: {cache_dir}")

    from ..ops import scrypt

    shapes = {(n, scrypt.shape_bucket(b))
              for b in (batches or [8192, 4096, 2048, 1024, 512])}
    t0 = time.perf_counter()
    done = []
    for sn, sb in sorted(shapes):
        _log(f"warming n={sn} b={sb} ...")
        try:
            done.append(_warm_shape(sn, sb, mesh))
        except Exception as e:  # noqa: BLE001 — e.g. OOM at big batches
            _log(f"  n={sn} b={sb} failed ({type(e).__name__}: {e})")
            done.append({"n": sn, "batch": sb,
                         "failed": type(e).__name__})
    doc = {
        "platform": platform,
        "devices_visible": jax.device_count(),
        "cache_dir": cache_dir,
        "shapes": done,
        "elapsed_s": round(time.perf_counter() - t0, 1),
    }
    if prove:
        doc["prove"] = _warm_prove(1 << 14)
    if runtime_kinds:
        primary = scrypt.shape_bucket(
            (batches or [8192])[0]) if batches else 8192
        doc["runtime_kinds"] = _warm_runtime_kinds(n, primary, pack_lanes)
        doc["elapsed_s"] = round(time.perf_counter() - t0, 1)
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="warmcache",
        description="pre-compile the label-program shapes into the "
                    "persistent XLA cache (docs/ROMIX_KERNEL.md)")
    ap.add_argument("--n", type=int, default=8192, help="scrypt N")
    ap.add_argument("--batches", default="8192,4096,2048,1024,512",
                    help="comma-separated label batch sizes (bucketed)")
    ap.add_argument("--no-mesh", action="store_true",
                    help="skip the sharded (multi-device) programs")
    ap.add_argument("--prove", action="store_true",
                    help="also warm the streaming prover's scan step")
    ap.add_argument("--no-runtime", action="store_true",
                    help="skip the registered runtime workload kinds "
                    "(fused/packed init, prove scan, verify, k2pow)")
    ap.add_argument("--pack-lanes", type=int, default=4096,
                    help="pack bucket for the multi-tenant init/verify "
                    "kind warms (runtime/scheduler.py pack_lanes)")
    a = ap.parse_args(argv)
    doc = warm(a.n, [int(b) for b in a.batches.split(",") if b],
               mesh=not a.no_mesh, prove=a.prove,
               runtime_kinds=not a.no_runtime, pack_lanes=a.pack_lanes)
    print(json.dumps(doc, indent=2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
