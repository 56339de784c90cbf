"""Process-wide device topology: ONE mesh, named axes, persistent layouts.

The GSPMD pattern (SNIPPETS.md [1]/[3]; SZKP and CRYPTONITE both locate
throughput in keeping the accelerator saturated across many small
proofs): build the device mesh ONCE with named axes, annotate arrays
with persistent ``NamedSharding`` layouts, and let jit insert the
collectives — so the identical code path serves a 1-chip dev box, an
8-chip v5e, and a multi-host pod slice.

Before this module, every sharded entry point re-derived
``NamedSharding(mesh, P(...))`` per dispatch (and re-``device_put`` the
replicated VRF carry per batch, evicting a donated buffer that was
already resident). This module is the one place sharding objects are
constructed; everything else — parallel/mesh.py's entry points, the
multi-tenant packer, the prover's window scans, the verify farm's
batch recompute — consumes the catalog.
spacecheck rule SC010 holds the line: ``Mesh(`` / ``NamedSharding(``
construction inside functions of the hot-path modules is a finding.

Axes:

* ``data``  — the lane/batch axis every label, prove and verify batch
  shards over (SURVEY.md §2.4: everything is lane arithmetic with no
  cross-lane dataflow except reductions, which XLA lowers to ICI
  all-reduces).
* ``model`` — reserved for V-sharded ROMix (splitting one lane's V
  scratch across devices); size 1 today, so every ``P(...)`` spec that
  does not name it replicates over it for free, and growing it later
  is a topology-only change.

The topology is built lazily on first use from the devices visible at
that moment — entry points that want the virtual host devices call
``accel.ensure_host_devices()`` BEFORE first backend use, exactly as
they already do (tests' conftest, tools/warmcache.py, bench.py probes).
HOW MANY devices a dispatch uses is parallel/mesh.py's ``auto_mesh``
(every visible accelerator, one CPU device, ``SPACEMESH_MESH`` forces
either way); the topology only answers WHICH mesh/layout objects serve
that count, and guarantees each count maps to one Mesh object per
process.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import sanitize

DATA_AXIS = "data"
MODEL_AXIS = "model"


class MeshLayouts:
    """The persistent sharding catalog for one device-count submesh.

    One instance per device count per process (DeviceTopology caches
    them); every field is constructed once and reused by every dispatch,
    so steady-state sharded dispatch allocates no sharding objects.
    """

    def __init__(self, devices) -> None:
        dev = np.asarray(devices, dtype=object).reshape(-1, 1)
        # the one construction site for the process (SC010 polices the
        # hot-path modules; this module is the exemption)
        # spacecheck: ok=SC010 the topology IS the construction site
        self.mesh = Mesh(dev, (DATA_AXIS, MODEL_AXIS))
        # spacecheck: ok=SC010 persistent catalog, built once per count
        self.batch = NamedSharding(self.mesh, P(DATA_AXIS))
        # word-major (words, B) arrays: shard the minor/lane axis
        # spacecheck: ok=SC010 persistent catalog, built once per count
        self.lane = NamedSharding(self.mesh, P(None, DATA_AXIS))
        # spacecheck: ok=SC010 persistent catalog, built once per count
        self.replicated = NamedSharding(self.mesh, P())

    @property
    def devices(self) -> int:
        return self.mesh.size

    # --- placement helpers (the per-dispatch hot path) -----------------

    def put_batch(self, value) -> jax.Array:
        """Place a (B, ...) per-lane array sharded over ``data``."""
        return jax.device_put(jnp.asarray(value), self.batch)

    def put_lane(self, value) -> jax.Array:
        """Place a word-major (words, B) array with the lane axis
        sharded over ``data``."""
        return jax.device_put(jnp.asarray(value), self.lane)

    def replicate(self, value) -> jax.Array:
        """Place ``value`` replicated across the mesh — a NO-OP when it
        already lives there.

        The VRF min-scan carry (and the prover's donated hit state) is
        replicated once at the start of a pass and then DONATED through
        every batch; the jit output comes back resident with this same
        layout. Re-``device_put``-ing it per batch (the pre-topology
        behavior) minted a fresh buffer each call and threw the donated
        residency away; detecting the already-placed case keeps the
        carry on device across the whole pass.
        """
        if isinstance(value, jax.Array) \
                and not isinstance(value, jax.core.Tracer):
            try:
                s = value.sharding
                if s == self.replicated or s.is_equivalent_to(
                        self.replicated, value.ndim):
                    return value
            except Exception:  # noqa: BLE001 — exotic array types: re-place
                pass
        return jax.device_put(jnp.asarray(value), self.replicated)


class DeviceTopology:
    """One mesh family per process, built once, layouts cached forever.

    ``layouts(k)`` returns the catalog for the first ``k`` visible
    devices (``None`` = all of them). Each distinct count constructs its
    Mesh exactly once; repeated calls return the identical objects, so
    jit caches key on stable shardings and executables are reused across
    sessions, tenants and entry points.
    """

    def __init__(self) -> None:
        self._devices = tuple(jax.devices())
        self._layouts: dict[int, MeshLayouts] = {}
        self._foreign: dict[tuple, MeshLayouts] = {}
        self._lock = sanitize.lock("parallel.topology")

    @property
    def device_count(self) -> int:
        return len(self._devices)

    def layouts(self, devices: int | None = None) -> MeshLayouts:
        k = self.device_count if devices is None else int(devices)
        k = max(1, min(k, self.device_count))
        with self._lock:
            lay = self._layouts.get(k)
            if lay is None:
                lay = self._layouts[k] = MeshLayouts(self._devices[:k])
            return lay

    def layouts_for_devices(self, devices) -> MeshLayouts:
        """The catalog covering exactly ``devices`` (a list of jax
        Devices). The common case — a prefix of the visible devices, what
        every auto-routing caller passes — hits the per-count cache; a
        non-prefix selection (an operator pinning specific chips) gets
        its own catalog, still built once per distinct device set."""
        devs = tuple(devices)
        if devs == self._devices[:len(devs)]:
            return self.layouts(len(devs))
        key = tuple(id(d) for d in devs)
        with self._lock:
            lay = self._foreign.get(key)
            if lay is None:
                lay = self._foreign[key] = MeshLayouts(devs)
            return lay

    def layouts_for(self, mesh: Mesh) -> MeshLayouts:
        """The catalog whose layouts place onto ``mesh``'s devices.

        When ``mesh`` came from this topology the lookup returns the
        catalog that owns it; a foreign mesh (built by hand in a test or
        an operator script) resolves by its device set — the returned
        layouts place onto the same devices, which is all a sharding
        is."""
        return self.layouts_for_devices(mesh.devices.flatten().tolist())


_TOPOLOGY: DeviceTopology | None = None
_TOPOLOGY_LOCK = sanitize.lock("parallel.topology.init")


def get() -> DeviceTopology:
    """The process-wide topology, built on first use."""
    global _TOPOLOGY
    t = _TOPOLOGY
    if t is None:
        with _TOPOLOGY_LOCK:
            if _TOPOLOGY is None:
                _TOPOLOGY = DeviceTopology()
            t = _TOPOLOGY
    return t


def reset() -> None:
    """Drop the topology (tests simulating a fresh process ONLY — a live
    process must never rebuild its mesh mid-flight: executables cache on
    the old sharding objects and every one would recompile)."""
    global _TOPOLOGY
    with _TOPOLOGY_LOCK:
        _TOPOLOGY = None
