"""The platform a process runs on, and where this checkout caches.

A program runs on the platform JAX gives it and says which on stderr at
start (:func:`announce_platform`); ``JAX_PLATFORMS=cpu`` on the command
line is the only way onto the CPU. Nothing here probes for an
accelerator, starts a child, or pins a platform: a chip belongs to one
process, so a probe child would itself take it.

Everything a run generates outside its data directories — the XLA
compile cache, the batch tuner's file that steers verifyd's batch sizes
(verifyd/batchtune.py), spacecheck's findings cache and
the native libraries' build output — lives under ONE git-ignored
directory inside the checkout (:data:`CACHE_ROOT`), so a run is a
function of the checkout and nothing is written under ``~/.cache``.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

CACHE_ROOT = Path(__file__).resolve().parents[2] / ".cache"
ENV_JAX_CACHE = "JAX_COMPILATION_CACHE_DIR"


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    The one placement rule: where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX reads it itself and nothing is set in code; otherwise the cache
    is ``CACHE_ROOT/jax``. The directory is part of JAX's cache key, so
    it must be fixed — one that moves never hits. Idempotent."""
    env = os.environ.get(ENV_JAX_CACHE)
    if env:
        return env
    dir_ = str(CACHE_ROOT / "jax")
    import jax

    if jax.config.jax_compilation_cache_dir != dir_:
        jax.config.update("jax_compilation_cache_dir", dir_)
        # the tiny per-test compiles are worth caching too — loading beats
        # recompiling well below the 1s default threshold
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return dir_


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode — decided HERE, once,
    from the platform: Mosaic compiles them on ``tpu``, everything else
    interprets. Nothing reaches interpret mode on ``tpu``."""
    import jax

    return jax.default_backend() != "tpu"


def device_fields() -> dict:
    """The device this process runs on, as JAX reports it — what every
    printed result is stamped with (bench.py) and what the CLIs announce.
    Opens the backend."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "device_count": len(devs)}


def announce_platform(prog: str) -> None:
    """One stderr line naming the platform this process landed on (the
    ``post`` and ``verifyd`` CLIs call it at start): JAX itself drops to
    the CPU with only a log line when it finds no accelerator, and an
    operator must not have to infer that from a labels/s figure. Also
    turns the persistent compile cache on."""
    fields = " ".join(f"{k}={v!r}" for k, v in device_fields().items())
    print(f"{prog}: {fields} compile_cache={enable_persistent_cache()!r}",
          file=sys.stderr, flush=True)


DEFAULT_HOST_DEVICES = 8  # what tests/conftest.py and the CI jobs force


def ensure_host_devices(count: int | None = None) -> int:
    """Expose ``count`` virtual CPU devices (XLA_FLAGS, this process AND
    children) so a CPU run can lane-shard label batches across them
    (parallel/mesh.py; on the CPU only a forced ``SPACEMESH_MESH`` or
    an explicit ``mesh=`` shards).

    Must run BEFORE the first backend use — the flag is read when the
    CPU client is instantiated; afterwards it is inert (harmless). A
    pre-existing ``xla_force_host_platform_device_count`` flag (tests'
    conftest, the driver entry) is respected, as is
    ``SPACEMESH_HOST_DEVICES`` (0/off disables). Returns the count in
    effect."""
    env = os.environ.get("SPACEMESH_HOST_DEVICES")
    if env is not None and env.lower() in ("0", "off", "none"):
        return 1
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        for part in flags.split():
            if "xla_force_host_platform_device_count" in part:
                try:
                    return int(part.split("=", 1)[1])
                except (IndexError, ValueError):
                    return 1
        return 1
    try:
        n = count if count is not None else int(env or DEFAULT_HOST_DEVICES)
    except ValueError:
        raise ValueError(
            f"SPACEMESH_HOST_DEVICES={env!r}: expected a device count "
            "or 0/off")
    if n <= 1:
        return 1
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}").strip()
    return n
