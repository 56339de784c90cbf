"""ROMix kernel autotuner: race the candidates once, persist the winner.

Which label-kernel variant is fastest is a per-host question (SURVEY.md
§7; the ASIC-crypto playbook of arxiv 2604.17808 / 2505.14657): the XLA
gather path with a VMEM/LLC-sized lane chunk wins where the working set
must be kept hot, the contiguous-row variant wins where the gather's
read amplification dominates.  Rather than hardcode that table, first
use races the candidates on a tiny calibration workload and persists the
winner per ``(platform, N, batch)`` under the checkout's cache root
(utils/accel.py CACHE_ROOT), so every entry point — post/initializer.py,
post/prover.py's scan, parallel/mesh.py, bench.py, tools/profiler.py —
picks up the tuned kernel with zero configuration, and a second process
on the same host skips the race entirely.

The Pallas kernel (ops/romix_pallas.py) is NOT a race candidate on any
platform: interpret mode is never a contender on CPU, and Mosaic refuses
the kernel's 32-word-minor VMEM layout on TPU (ROADMAP S4 has the
compiler's messages).  ``SPACEMESH_ROMIX=pallas`` stays the explicit
request — it raises on failure, it never degrades to another impl.  A
raced candidate that fails to compile or run raises too: the default set
holds only kernels that compiled and matched on the chip.

The grid has a MESH dimension (docs/ROMIX_KERNEL.md): on hosts exposing
more than one device — notably a CPU run's virtual host devices
(``--xla_force_host_platform_device_count``, which every test/driver
entry point already forces to 8) — the race also times the label kernel
lane-sharded over {2, 4, 8} devices via parallel/mesh.py. The
diagonal-vector Salsa program is op-dispatch-bound on XLA:CPU, so N
sequential per-device streams routinely beat one device's intra-op
parallelism (measured 3.2x at mainnet N on a 2-core host); whether and
at how many devices that trade wins is exactly what the race persists.
Mesh-aware callers (post/initializer.py, post/prover.py, bench.py) pass
``max_devices=None`` and route batches through the mesh when the winner
says so; shape-bound callers keep the default ``max_devices=1`` and are
served the best single-device row of the same measurements.

Decision precedence (highest first):

1. env overrides — ``SPACEMESH_ROMIX`` (``xla`` | ``xla-rows`` |
   ``pallas``) forces the implementation, ``SPACEMESH_ROMIX_CHUNK``
   (lanes per sequential V chunk; ``0``/``off`` = unchunked) forces the
   chunk, ``SPACEMESH_MESH`` forces the device count (``0``/``off`` = 1,
   ``1``/``on`` = every visible device, an integer >= 2 = exactly that
   many); any of them beats a cached winner;
2. the persisted winner for ``(platform, N, batch, device cap)``;
3. a race (disable with ``SPACEMESH_ROMIX_AUTOTUNE=off``, e.g. in
   latency-sensitive tests), whose result is persisted;
4. a static heuristic default (race disabled or impossible): the plain
   single-device XLA kernel.

Cache file: ``romix_autotune.json`` under utils/accel.py CACHE_ROOT (the
git-ignored directory inside the checkout; ``SPACEMESH_ROMIX_CACHE``
overrides the file path).  A corrupt or unreadable file is treated as
empty — the race re-runs and rewrites it.  See docs/ROMIX_KERNEL.md.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

import numpy as np

SCHEMA = 2  # v2: rows/winners carry a "devices" mesh dimension
IMPLS = ("xla", "xla-rows", "pallas")
MAX_MESH_DEVICES = 8  # the raced device-count grid is {1, 2, 4, 8}

# The two mesh SHAPES a sharded label batch can take over the topology's
# ``data`` axis (docs/ROMIX_KERNEL.md):
#   lane   — the word-major kernel: arrays stay (words, B), the lane axis
#            shards, V is gathered word-major per shard;
#   vshard — the contiguous-row kernel: V lives as per-lane (32,) rows,
#            so sharding the lanes shards each device's V scratch with
#            them (the row-sharded ROMix layout).
# Rows and winners are tagged with their shape, and race() additionally
# persists the best row PER shape — tools/warmcache.py warms both so a
# later SPACEMESH_ROMIX flip or a re-race that flips the winner still
# hits the persistent compile cache.
MESH_SHAPES = ("lane", "vshard")


def shape_of(impl: str) -> str:
    """The mesh shape an impl uses when its lanes shard over ``data``."""
    return "vshard" if impl == "xla-rows" else "lane"

ENV_IMPL = "SPACEMESH_ROMIX"
ENV_CHUNK = "SPACEMESH_ROMIX_CHUNK"
ENV_AUTOTUNE = "SPACEMESH_ROMIX_AUTOTUNE"
ENV_CACHE = "SPACEMESH_ROMIX_CACHE"
ENV_MESH = "SPACEMESH_MESH"  # shared with post/initializer.py + prover

# calibration workload: CAL_BATCH lanes bound the race cost independently
# of the production batch (chunk locality is a per-lane property, so the
# winner transfers to wider batches — docs/ROMIX_KERNEL.md discusses the
# one approximation this makes for the unchunked candidate)
CAL_BATCH = 512
CAL_REPS = 2

_OFF = ("0", "off", "none", "false")


def _log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


@dataclasses.dataclass(frozen=True)
class Decision:
    """A resolved kernel choice for one (platform, N, batch) shape."""

    impl: str                 # "xla" | "xla-rows" | "pallas"
    chunk: int | None         # lanes per sequential V chunk; None = whole batch
    source: str               # "env" | "cache" | "race" | "default" | "untuned"
    labels_per_sec: float | None = None  # calibration rate, when raced
    devices: int = 1          # lane-shard the batch over this many devices
    #                           (parallel/mesh.py; 1 = single-device dispatch)
    mesh_shape: str = "lane"  # which MESH_SHAPES layout the sharded
    #                           dispatch uses (meaningful when devices > 1)

    def as_json(self) -> dict:
        return {"impl": self.impl, "chunk": self.chunk,
                "source": self.source, "devices": self.devices,
                "shape": self.mesh_shape,
                "labels_per_sec": self.labels_per_sec}


def cache_path() -> str:
    """The autotune winners file, under the checkout's cache root."""
    explicit = os.environ.get(ENV_CACHE)
    if explicit:
        return os.path.expanduser(explicit)
    from ..utils import accel

    return str(accel.CACHE_ROOT / "romix_autotune.json")


def _key(platform: str, n: int, batch: int, dev_cap: int = 1) -> str:
    # dev_cap: the device budget the winner was selected under. A shape
    # has (at most) two persisted winners — the best single-device row
    # (d1, what ops/scrypt.py's per-call dispatch consumes) and the best
    # row under the host's mesh cap (what the mesh-aware init/prove/bench
    # callers consume) — so the two lookups never overwrite each other.
    return f"v{SCHEMA}:{platform}:n{n}:b{batch}:d{dev_cap}"


def _shape_key(platform: str, n: int, batch: int, dev_cap: int,
               shape: str) -> str:
    # the best row PER mesh shape under the same budget — what
    # shape_winner() serves warmcache and the sharded entry points so
    # both layouts' executables land in the persistent compile cache
    return _key(platform, n, batch, dev_cap) + f":s{shape}"


def _load_cache(path: str | None = None) -> dict:
    path = path or cache_path()
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        if not isinstance(doc, dict):
            raise ValueError("autotune cache root is not an object")
        return doc
    except FileNotFoundError:
        return {}
    except (OSError, ValueError) as e:
        # a corrupt winners file must never break labeling — re-race
        _log(f"romix autotune: ignoring unreadable cache {path} ({e})")
        return {}


def _store(key: str, entry: dict) -> None:
    _store_many({key: entry})


def _store_many(entries: dict) -> None:
    path = cache_path()
    doc = _load_cache(path)
    doc.update(entries)
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        # durable write (tmp + fsync + rename + dir-fsync): a power cut
        # mid-save must never leave a half-written winners file that the
        # corrupt-cache-ignored path above silently re-races away
        from ..utils import fsio

        fsio.atomic_write_text(
            path, json.dumps(doc, indent=1, sort_keys=True))
    except OSError as e:
        # persistence is an optimization (read-only HOME, sandboxed CI)
        _log(f"romix autotune: cannot persist winner ({e})")


def _entry_decision(entry: dict, batch: int, source: str) -> Decision | None:
    impl = entry.get("impl")
    chunk = entry.get("chunk")
    devices = entry.get("devices", 1)
    if impl not in IMPLS or impl == "pallas":
        return None  # pallas is never raced, so never a persisted winner
    if chunk is not None and (not isinstance(chunk, int) or chunk < 1):
        return None
    if not isinstance(devices, int) or isinstance(devices, bool) \
            or devices < 1:
        return None
    if chunk is not None and chunk >= batch:
        chunk = None
    shape = entry.get("shape") or shape_of(impl)
    if shape not in MESH_SHAPES:
        return None
    rate = entry.get("labels_per_sec")
    return Decision(impl, chunk, source,
                    rate if isinstance(rate, (int, float)) else None,
                    devices=devices, mesh_shape=shape)


def read_env() -> tuple[str | None, int | None, bool, bool]:
    """-> (impl override, chunk override, chunk was set, race disabled)."""
    impl = os.environ.get(ENV_IMPL) or None
    if impl is not None and impl not in IMPLS:
        raise ValueError(
            f"{ENV_IMPL}={impl!r}: expected one of {', '.join(IMPLS)}")
    chunk_raw = os.environ.get(ENV_CHUNK)
    chunk_set = chunk_raw is not None and chunk_raw != ""
    chunk: int | None = None
    if chunk_set and chunk_raw.lower() not in _OFF:
        chunk = int(chunk_raw)
        if chunk < 1:
            raise ValueError(f"{ENV_CHUNK}={chunk_raw!r}: must be >= 1")
    no_race = (os.environ.get(ENV_AUTOTUNE) or "").lower() in _OFF
    return impl, chunk, chunk_set, no_race


def read_mesh_env() -> int | None:
    """``SPACEMESH_MESH`` as a device count: None = auto (tuned),
    ``0``/``off`` = 1 (never shard), ``1``/``on`` = every visible device
    (the historical force-the-mesh switch), an integer >= 2 = exactly
    that many devices."""
    raw = os.environ.get(ENV_MESH)
    if raw is None:
        return None
    v = raw.strip().lower()
    if v in ("", "auto"):
        return None
    if v in _OFF:
        return 1
    if v in ("1", "on"):
        return _device_count()
    try:
        count = int(v)
    except ValueError:
        raise ValueError(
            f"{ENV_MESH}={raw!r}: expected off/on/auto or a device count")
    if count < 1:
        raise ValueError(f"{ENV_MESH}={raw!r}: device count must be >= 1")
    return count


def _device_count() -> int:
    import jax

    return jax.device_count()


def resolve_auto_mesh(n: int, batch: int):
    """-> (device list | None, Decision) for a mesh-aware caller in
    ``auto`` mode — ONE definition of the routing post/initializer.py
    and post/prover.py share (hand-rolled twins of this logic have
    already diverged once on knob parsing; see read_mesh_env).

    On the CPU the tuned mesh winner decides (devices > 1 only when
    the raced row says so and the host still exposes that many). On an
    accelerator the batch shards over every visible device.
    SPACEMESH_MESH forces either way (off -> always None; the CPU path
    honors it inside decide(), which collapses a forced count into the
    returned decision). Callers build the parallel/mesh.py
    Mesh from the returned device list; None means stay single-device.
    """
    import jax

    if jax.default_backend() != "cpu":
        forced = read_mesh_env()
        count = _device_count()
        d = decide(n, batch)
        if forced == 1 or count <= 1:
            return None, d
        return jax.devices()[:min(forced or count, count)], d
    d = decide(n, batch, max_devices=None)
    if d.devices > 1 and _device_count() >= d.devices:
        return jax.devices()[:d.devices], d
    return None, d


def _device_cap(max_devices: int | None) -> int:
    """The device budget for one decide() call: the caller's cap clipped
    to the host and the raced grid. ``max_devices=1`` short-circuits
    without touching the backend (the per-call dispatch path in
    ops/scrypt.py must not pay a device enumeration)."""
    if max_devices == 1:
        return 1
    cap = min(_device_count(), MAX_MESH_DEVICES)
    if max_devices is not None:
        cap = min(cap, max_devices)
    return max(cap, 1)


def chunk_candidates(n: int, batch: int,
                     targets: tuple[int, ...] = (256 << 20,)
                     ) -> list[int]:
    """Power-of-two lane chunks whose V working set (n * 128 bytes per
    lane) lands near each cache-capacity target, clipped to the batch."""
    row_bytes = 128  # one lane's (32,) u32 V row
    out = set()
    for t in targets:
        c = max(t // (n * row_bytes), 8)
        c = 1 << (int(c).bit_length() - 1)
        if c < batch:
            out.add(int(c))
    return sorted(out)


def default_decision(platform: str, n: int, batch: int) -> Decision:
    """Static heuristic when racing is disabled or impossible: the
    word-major XLA gather over the whole batch. Measured on CPU hosts the
    diagonal-vector Salsa is op-dispatch-bound, so sequential lane chunks
    only subtract lane width (docs/ROMIX_KERNEL.md) — chunking has to
    EARN its place through the race."""
    return Decision("xla", None, "default")


def mesh_candidates(device_count: int, cap: int = MAX_MESH_DEVICES
                    ) -> list[int]:
    """Power-of-two device counts to race the lane-sharded kernel over:
    {2, 4, 8} clipped to the visible devices and ``cap``."""
    out, d = [], 2
    while d <= min(device_count, cap):
        out.append(d)
        d *= 2
    return out


def candidates(platform: str, n: int, batch: int, mesh_cap: int = 1
                ) -> list[tuple[str, int | None, int]]:
    """The (impl, chunk, devices) grid raced for one shape."""
    chunks: list[int | None] = [None, *chunk_candidates(n, batch)]
    # no Pallas row on any platform (module docstring)
    impls = ("xla", "xla-rows") if platform == "cpu" else ("xla",)
    out = [(impl, c, 1) for impl in impls for c in chunks]
    if mesh_cap > 1:
        # mesh rows: both XLA layouts on CPU (the contiguous-row variant's
        # win condition — gather read amplification — is per-device, so it
        # can flip under sharding too), plain xla elsewhere. No chunk: a
        # sequential lane chunk inside a shard fights GSPMD partitioning
        # (ops/scrypt.py _tunable).
        for d in mesh_candidates(_device_count(), mesh_cap):
            out.extend((impl, None, d) for impl in impls)
    return out


def calibration_block(batch: int = CAL_BATCH, seed: int = 7) -> np.ndarray:
    """Deterministic (32, batch) u32 ROMix input, shared by the race and
    tools/profiler.py --romix so both measure the same workload."""
    rng = np.random.RandomState(seed)
    return rng.randint(0, 2**32, size=(32, batch),
                       dtype=np.uint64).astype(np.uint32)


# in-process memos. Race measurements are per (platform, n) — the
# calibration workload is FIXED at CAL_BATCH lanes, so one measurement
# serves every production batch size (bench sweeps, init tail batches,
# the verifier's variable-count label recomputes) — and are additionally
# persisted, so a new process deriving a winner for a new batch size
# never re-compiles. Resolved decisions are memoized per call signature
# (env included) so the steady dispatch path costs dict lookups, not a
# cache-file parse per batch.
_race_memo: dict[tuple, list[dict]] = {}
_decision_memo: dict[tuple, Decision] = {}


def reset_memo() -> None:
    """Drop in-process memos (tests simulating fresh processes)."""
    _race_memo.clear()
    _decision_memo.clear()


def _meas_key(platform: str, n: int) -> str:
    return f"v{SCHEMA}:meas:{platform}:n{n}:cal{CAL_BATCH}"


def _valid_rows(rows) -> list[dict]:
    out = []
    if not isinstance(rows, list):
        return out
    for r in rows:
        if (isinstance(r, dict) and r.get("impl") in IMPLS
                and (r.get("chunk") is None
                     or (isinstance(r.get("chunk"), int) and r["chunk"] >= 1))
                and isinstance(r.get("devices", 1), int)
                and not isinstance(r.get("devices", 1), bool)
                and r.get("devices", 1) >= 1
                and isinstance(r.get("labels_per_sec"), (int, float))):
            r.setdefault("devices", 1)
            # pre-shape rows (written by an older process) tag by impl
            if r.setdefault("shape", shape_of(r["impl"])) not in MESH_SHAPES:
                continue
            out.append(r)
    return out


def _race_measurements(platform: str, n: int, mesh_cap: int = 1
                       ) -> list[dict]:
    """All calibration measurements for (platform, n), raced lazily: the
    single-device grid on first use, mesh rows the first time a caller
    with a device budget > 1 asks. Rows persist incrementally, so a
    winners file written on a 1-device host grows mesh rows when it is
    first read on (or shipped to, via the CI cache) a multi-device one."""
    memo_key = (platform, n)
    rows = _race_memo.get(memo_key)
    if rows is None:
        rows = _valid_rows(
            _load_cache().get(_meas_key(platform, n), {}).get("raced"))
    missing = [c for c in candidates(platform, n, CAL_BATCH, mesh_cap)
               if (c[1] is None or c[1] < CAL_BATCH)
               and not any(r["impl"] == c[0] and r["chunk"] == c[1]
                           and r["devices"] == c[2] for r in rows)]
    if not missing:
        _race_memo[memo_key] = rows
        return rows
    from ..utils import metrics, tracing

    metrics.post_romix_autotune_races.inc()
    race_sp = tracing.span("romix.race",
                           {"platform": platform, "n": n,
                            "mesh_cap": mesh_cap}
                           if tracing.is_enabled() else None)
    race_sp.__enter__()
    try:
        rows = rows + _race_rows(platform, n, missing)
    finally:
        race_sp.__exit__(None, None, None)
    _race_memo[memo_key] = rows
    if rows:
        _store(_meas_key(platform, n),
               {"raced": rows, "cal_batch": CAL_BATCH,
                "tuned_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                          time.gmtime())})
    return rows


def _race_rows(platform: str, n: int,
               combos: list[tuple[str, int | None, int]]) -> list[dict]:
    import jax
    import jax.numpy as jnp

    from ..utils import tracing
    from . import scrypt

    x_host = jnp.asarray(calibration_block(CAL_BATCH))
    rows = []
    for impl, chunk, devices in combos:
        label = f"{impl}" + (f"/chunk={chunk}" if chunk else "") + (
            f"/devices={devices}" if devices > 1 else "")
        # a candidate that cannot compile or run RAISES out of the race:
        # the grid holds only kernels known to run on this platform
        with tracing.span("romix.race_candidate",
                          {"impl": impl, "chunk": chunk, "devices": devices}
                          if tracing.is_enabled() else None) as csp:
            if devices > 1:
                from ..parallel import mesh as pmesh

                mesh = pmesh.data_mesh(jax.devices()[:devices])
                x = jax.device_put(x_host, pmesh.lane_sharding(mesh))
            else:
                x = x_host

            def run():
                # interpret=False: the SAME static jit key production
                # uses, so the race's compile is reused, not repaid
                return scrypt.romix_tuned(x, n=n, impl=impl, chunk=chunk,
                                          interpret=False)

            t0 = time.perf_counter()
            run().block_until_ready()
            compile_s = time.perf_counter() - t0
            best = float("inf")
            for _ in range(CAL_REPS):
                t0 = time.perf_counter()
                run().block_until_ready()
                best = min(best, time.perf_counter() - t0)
            rate = CAL_BATCH / best
            _log(f"romix autotune: {label}: {rate:,.0f} labels/s "
                 f"(compile+first {compile_s:.1f}s)")
            csp.set(labels_per_sec=round(rate, 1),
                    compile_s=round(compile_s, 3))
            rows.append({"impl": impl, "chunk": chunk, "devices": devices,
                         "shape": shape_of(impl),
                         "labels_per_sec": round(rate, 1)})
    return rows


NOISE_BAND = 0.95  # rows within 5% of the best rate count as tied


def _select_winner(usable: list[dict]) -> dict:
    """The fastest row — except that among rows within the calibration
    noise band of the best rate, the one sharded over the FEWEST devices
    wins. Sharding overhead (SPMD rendezvous, per-shard D2H) grows with
    the production batch while the fixed 512-lane calibration slightly
    flatters wide meshes, so a near-tie at calibration is a real win for
    the narrower mesh at production shapes."""
    best = max(r["labels_per_sec"] for r in usable)
    near = [r for r in usable if r["labels_per_sec"] >= NOISE_BAND * best]
    return min(near, key=lambda r: (r["devices"], -r["labels_per_sec"]))


def race(platform: str, n: int, batch: int, dev_cap: int = 1,
         pin_devices: int | None = None) -> Decision | None:
    """Race (or reuse the measured race of) the candidate kernels on the
    fixed calibration workload, then persist and return the winner for
    ``(platform, n, batch)`` under a ``dev_cap`` device budget.

    ``pin_devices`` restricts selection to rows at exactly that device
    count (the SPACEMESH_MESH=<k> override); pinned selections are NOT
    persisted as winners — unsetting the override must fall back to the
    full-grid winner, not a pinned one — and return None when no row at
    that count survived."""
    rows = _race_measurements(platform, n, mesh_cap=dev_cap)
    usable = [r for r in rows
              if (r["chunk"] is None or r["chunk"] < batch)
              and r["devices"] <= dev_cap
              and r["devices"] <= batch
              and r["labels_per_sec"] > 0]
    if pin_devices is not None:
        usable = [r for r in usable if r["devices"] == pin_devices]
        if not usable:
            return None
    if not usable:
        return default_decision(platform, n, batch)
    win = _select_winner(usable)
    chunk = win["chunk"]
    d = Decision(win["impl"], chunk, "race", win["labels_per_sec"],
                 devices=win["devices"], mesh_shape=win["shape"])
    if pin_devices is not None:
        return dataclasses.replace(d, source="env")
    stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    entries = {_key(platform, n, batch, dev_cap): {
        "impl": win["impl"], "chunk": chunk,
        "devices": win["devices"], "shape": win["shape"],
        "labels_per_sec": win["labels_per_sec"],
        "cal_batch": CAL_BATCH, "raced": rows, "tuned_at": stamp}}
    # the best row per mesh SHAPE, under the same budget: warmcache
    # compiles both layouts into the persistent cache, so a later winner
    # flip (re-race, SPACEMESH_ROMIX override) never cold-compiles
    for shape in MESH_SHAPES:
        srows = [r for r in usable if r["shape"] == shape]
        if not srows:
            continue
        sw = _select_winner(srows)
        entries[_shape_key(platform, n, batch, dev_cap, shape)] = {
            "impl": sw["impl"], "chunk": sw["chunk"],
            "devices": sw["devices"], "shape": shape,
            "labels_per_sec": sw["labels_per_sec"],
            "cal_batch": CAL_BATCH, "tuned_at": stamp}
    _store_many(entries)
    _log(f"romix autotune: winner for {platform} n={n} b={batch} "
         f"(<= {dev_cap} devices): {win['impl']}"
         + (f"/chunk={chunk}" if chunk else "")
         + (f"/devices={win['devices']}" if win["devices"] > 1 else "")
         + f" ({win['labels_per_sec']:,.0f} labels/s, persisted)")
    return d


def shape_winner(n: int, batch: int, shape: str, *,
                 platform: str | None = None,
                 max_devices: int | None = None) -> Decision | None:
    """The persisted winner for one mesh *shape* under the caller's
    device budget, or None when no race has measured that shape yet (or
    every candidate of that shape failed on this host). A pure cache
    read — never races — so warmcache and tests can enumerate both
    layouts' winners without re-paying measurement."""
    if shape not in MESH_SHAPES:
        raise ValueError(
            f"mesh shape {shape!r}: expected one of {', '.join(MESH_SHAPES)}")
    if platform is None:
        import jax

        platform = jax.default_backend()
    dev_cap = _device_cap(max_devices)
    entry = _load_cache().get(
        _shape_key(platform, n, batch, dev_cap, shape), {})
    d = _entry_decision(entry, batch, "cache")
    if d is not None and d.mesh_shape != shape:
        return None  # entry corrupted by hand-editing: shape key disagrees
    return d


def decide(n: int, batch: int, *, platform: str | None = None,
           allow_race: bool = True, max_devices: int | None = 1
           ) -> Decision:
    """Resolve the kernel choice for one shape (precedence in the module
    docstring). The steady dispatch path — one call per label batch from
    post/initializer.py — is a memoized dict lookup; the env values are
    part of the memo key so override changes always take effect.

    ``max_devices``: the caller's device budget. The default (1) serves
    shape-bound callers — ops/scrypt.py's per-call dispatch, the
    profiler's stage views — the best single-device row. Mesh-aware
    callers (post/initializer.py, post/prover.py, bench.py) pass None
    (= up to min(visible devices, 8)) and route through parallel/mesh.py
    when the winning row says ``devices > 1``."""
    if platform is None:
        import jax

        platform = jax.default_backend()
    dev_cap = _device_cap(max_devices)
    memo_key = (platform, n, batch, allow_race, dev_cap,
                os.environ.get(ENV_IMPL), os.environ.get(ENV_CHUNK),
                os.environ.get(ENV_AUTOTUNE), os.environ.get(ENV_CACHE),
                os.environ.get(ENV_MESH))
    hit = _decision_memo.get(memo_key)
    if hit is not None:
        return hit
    d = _decide(n, batch, platform, allow_race, dev_cap)
    _decision_memo[memo_key] = d
    return d


def _decide(n: int, batch: int, platform: str, allow_race: bool,
            dev_cap: int) -> Decision:
    impl_env, chunk_env, chunk_set, no_race = read_env()
    mesh_env = read_mesh_env() if dev_cap > 1 else None
    if mesh_env is not None:
        mesh_env = max(1, min(mesh_env, dev_cap, batch))
    if mesh_env == 1:
        # SPACEMESH_MESH=off: the whole decision collapses to the
        # single-device budget — lookups, races, and persisted winners
        # all use the :d1 key, so the kill-switch also holds through the
        # race fall-through at the bottom
        dev_cap, mesh_env = 1, None
    cached = _entry_decision(
        _load_cache().get(_key(platform, n, batch, dev_cap), {}), batch,
        "cache")
    if cached is not None and cached.devices > min(dev_cap, batch):
        cached = None  # raced under a wider device budget than this call's
    if cached is not None and mesh_env is not None \
            and cached.devices != mesh_env:
        cached = None  # forced device count: the cached winner is moot
    if impl_env is not None:
        # explicit impl: env chunk > cached chunk (same impl) > heuristic
        if chunk_set:
            chunk = chunk_env
        elif cached is not None and cached.impl == impl_env:
            chunk = cached.chunk
        elif impl_env == "pallas":
            chunk = None
        else:
            chunk = default_decision(platform, n, batch).chunk
        if chunk is not None and chunk >= batch:
            chunk = None
        devices = mesh_env if mesh_env is not None else (
            cached.devices if cached is not None else 1)
        return Decision(impl_env, chunk, "env", devices=devices)
    if chunk_set:
        base = cached or default_decision(platform, n, batch)
        chunk = chunk_env if (chunk_env is None or chunk_env < batch) else None
        devices = mesh_env if mesh_env is not None else base.devices
        return Decision(base.impl, chunk, "env", devices=devices)
    if cached is not None:
        return cached
    if mesh_env is not None and mesh_env > 1:
        # forced device count: best raced row at that count when racing
        # is allowed, the plain XLA kernel otherwise (the historical
        # SPACEMESH_MESH=1 behavior)
        if allow_race and not no_race:
            pinned = race(platform, n, batch, dev_cap,
                          pin_devices=mesh_env)
            if pinned is not None:
                return pinned
        return Decision("xla", None, "env", devices=mesh_env)
    if no_race or not allow_race:
        return default_decision(platform, n, batch)
    return race(platform, n, batch, dev_cap)
