"""What the chip's compiler accepts, checked without a chip.

The installed libtpu can AOT-compile for a ``v5e:2x2`` topology with no
device attached, so the programs the ``tpu`` default path runs are
lowered and compiled here, under ``JAX_PLATFORMS=cpu``, exactly as the
chip will get them (``interpret=False``, Mosaic for Pallas). It proves
they COMPILE — that they also match their references on the chip is
chip_smoke.py's job.

Everything happens in a SUBPROCESS: a Mosaic layout failure is a SIGABRT
(``Check failed``), not an exception, and must not take pytest down.
The test skips only when the topology itself cannot be built.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
NO_TOPOLOGY = 77

_CHILD = r"""
import json, sys
import jax, jax.numpy as jnp, numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print(f"no v5e topology: {type(e).__name__}: {e}", file=sys.stderr)
    sys.exit(77)

from spacemesh_tpu.ops import pow as k2pow
from spacemesh_tpu.ops import proving, proving_pallas, scrypt
from spacemesh_tpu.post import prover

dev0 = SingleDeviceSharding(topo.devices[0])
u32 = jnp.uint32
N = 8192
out = {"device_kind": topo.devices[0].device_kind,
       "devices": len(topo.devices)}


def sds(shape, dtype=u32, sharding=dev0):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def build(name, fn, *args, **kw):
    c = fn.lower(*args, **kw).compile()
    m = c.memory_analysis()
    out[name] = {"temp": m.temp_size_in_bytes,
                 "all_gather": "all-gather" in c.as_text()}
    return c


# the label program at mainnet N and the widths the cells dispatch: the
# verifier's lane buckets (per-lane commitments, no min-scan) and init's
# 8,192 lanes a chip with the VRF min-scan, on one chip and lane-sharded
# over the four of a 2x2 host
mesh = Mesh(np.array(topo.devices), ("data",))
lanes = NamedSharding(mesh, P("data"))
everywhere = NamedSharding(mesh, P())
for b in (32, 64, 128, 256):
    build(f"labels_{b}", scrypt._labels_fused, sds((8, b)), sds((b,)),
          sds((b,)), n=N)
b = 8192
build(f"labels_{b}", scrypt._labels_min_fused, sds((8,)), sds((b,)),
      sds((b,)), sds((scrypt.VRF_CARRY_WORDS,)), n=N)
# the verifier's widest program: a full lane tile (ops/scrypt.lane_ceiling
# at N=8192 on a v5e), per-lane commitments; and the doubled bucket a
# 256-proof K3=37 batch would ask for untiled, which the chip cannot hold
build(f"labels_verify_{b}", scrypt._labels_fused, sds((8, b)), sds((b,)),
      sds((b,)), n=N)
try:
    build(f"labels_verify_{2 * b}", scrypt._labels_fused, sds((8, 2 * b)),
          sds((2 * b,)), sds((2 * b,)), n=N)
except Exception as e:
    out[f"labels_verify_{2 * b}"] = {"refused": str(e)[:300]}
b = 32768
build(f"labels_{b}x4", scrypt._labels_min_fused,
      sds((8,), sharding=everywhere), sds((b,), sharding=lanes),
      sds((b,), sharding=lanes),
      sds((scrypt.VRF_CARRY_WORDS,), sharding=everywhere), n=N)

# prove: both scan steps the Prover can bind on tpu (Pallas is the
# single-device default, XLA the sharded one and the reference), at the
# CLI's batch width, nonce group and K2
b, ng, cap = 1 << 14, prover.DEFAULT_NONCE_GROUP, 37
step_args = (sds((8,)), sds(()), sds((b,)), sds((b,)), sds((4, b)),
             sds(()), sds((ng,), jnp.int32), sds((2, ng, cap)), sds(()),
             sds(()), sds(()))
build("prove_step_xla", proving.prove_scan_step_jit, *step_args,
      n_nonces=ng, max_hits=cap)
build("prove_mask_pallas", proving_pallas.proving_scan_pallas,
      *step_args[:6], n_nonces=ng, interpret=False)


def computations_of(text):
    # {computation name: its lines} of a compiled module's text
    found, name = {}, None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            name = line.split()[1 if line.startswith("ENTRY") else 0]
            found[name.lstrip("%")] = []
        elif name is not None:
            found[name.lstrip("%")].append(line)
    return found


def is_kernel(line):
    return "custom-call" in line and "_scan_pallas" in line


def is_while(line):     # the op, not a computation's or a value's name
    return " while(" in line


# ... and the window steps a session runs: every nonce group of a pass
# (4 on tpu) in one program over one uploaded batch, indices made on
# the device. The compaction epilogue holds no loop: it finds each
# nonce's j-th hit segment by compare-and-count, where a binary search
# lowered to one `while` an epilogue (four a scan step at bdb9bbf, one
# an epilogue a nonce group; one at 1de6202, one epilogue over all rows)
groups = 4
window_args = (sds((8,)), sds((groups,)), sds((4, b)), sds((3,)), sds(()),
               sds((groups * ng,), jnp.int32), sds((2, groups * ng, cap)))
c = build("prove_window_xla", proving.prove_scan_step_window, *window_args,
          n_nonces=ng, max_hits=cap)
out["prove_window_xla"]["whiles"] = sum(
    map(is_while, c.as_text().splitlines()))
c = build("prove_window_pallas", proving_pallas.prove_scan_step_window_pallas,
          *window_args, n_nonces=ng, max_hits=cap, interpret=False)
lines = c.as_text().splitlines()
out["prove_window_pallas"].update(kernel_calls=sum(map(is_kernel, lines)),
                                  whiles=sum(map(is_while, lines)))
# ... over a FLIGHT of eight batches (post/prover.py FLIGHT_BATCHES): what
# a default Prover runs on any store of eight batches or more. The loop
# over the flight's scan steps is rolled: the kernel's four custom-calls
# sit in ONE while body, whatever the flight holds
flight_args = (window_args[:2] + (sds((4, prover.FLIGHT_BATCHES * b)),)
               + window_args[3:])
c = build("prove_flight_pallas", proving_pallas.prove_scan_step_window_pallas,
          *flight_args, n_nonces=ng, max_hits=cap, batch=b, interpret=False)
text = c.as_text()
lines = text.splitlines()
computations = computations_of(text)
holders = [n for n, body in computations.items() if any(map(is_kernel, body))]
out["prove_flight_pallas"].update(
    module=lines[0].split()[1].rstrip(","),
    kernel_calls=sum(map(is_kernel, lines)),
    kernel_computations=len(holders),
    while_bodies_holding_the_kernel=sum(
        f"body=%{n}," in ln or ln.rstrip().endswith(f"body=%{n}")
        for n in holders for body in computations.values() for ln in body),
    # loops nested in a scan step: the while ops in the computation that
    # holds the kernels (the rolled loop's body)
    whiles_beside_the_kernel=sum(
        map(is_while, (ln for n in holders for ln in computations[n]))),
    whiles=sum(map(is_while, lines)))

# k2pow: search (one 2^16-nonce batch) and batched witness verification
b = 1 << 16
build("pow_hash", k2pow.pow_hash_batch_jit, sds((8,)), sds((b,)), sds((b,)))
build("pow_below_target", k2pow.below_target_jit, sds((8, b)), sds((8,)))
b = 256
build("pow_verify", k2pow.pow_verify_batch_jit, sds((16, b)), sds((b,)),
      sds((b,)), sds((8, b)))

# four chips: the label batch lane-sharded over the 2x2 host
b = 2048
build("labels_single", scrypt._labels_fused, sds((8,)), sds((b,)),
      sds((b,)), n=N)
build("labels_sharded", scrypt._labels_fused,
      sds((8,), sharding=everywhere),
      sds((b,), sharding=lanes), sds((b,), sharding=lanes), n=N)

print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def lowered():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO) + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", _CHILD], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    if r.returncode == NO_TOPOLOGY:
        pytest.skip("libtpu cannot build a v5e:2x2 topology here: "
                    + r.stderr.strip().splitlines()[-1])
    assert r.returncode == 0, (
        f"AOT compile for v5e died (rc={r.returncode}; a negative rc is "
        f"a signal — Mosaic aborts with SIGABRT):\n{r.stderr[-3000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_tpu_default_programs_compile_for_v5e(lowered):
    assert lowered["devices"] == 4
    # the Pallas scan step is the tpu default (and stays reachable via
    # use_pallas=True), so Mosaic must keep compiling it
    for name in ("labels_8192", "prove_step_xla",
                 "prove_mask_pallas", "prove_window_xla",
                 "prove_window_pallas", "prove_flight_pallas", "pow_hash",
                 "pow_below_target", "pow_verify"):
        assert name in lowered, name


def test_window_step_keeps_the_kernel_ops_the_trace_metrics_match(lowered):
    # scan_roofline / scan_kernel_share sum the device time of op events
    # named ``_scan_pallas ... custom-call``: one per nonce group
    assert lowered["prove_window_pallas"]["kernel_calls"] == 4


def test_flight_program_is_one_rolled_loop_around_the_four_kernels(lowered):
    # a flight of eight batches is ONE program whose loop over the scan
    # steps is rolled: the kernel is lowered once a nonce group (four
    # custom-calls, not thirty-two), all in one computation that is the
    # body of one while op; and scan_step_ms finds the program by name
    flight = lowered["prove_flight_pallas"]
    assert flight["kernel_calls"] == 4
    assert flight["kernel_computations"] == 1
    assert flight["while_bodies_holding_the_kernel"] == 1
    assert "prove_scan_step" in flight["module"]


@pytest.mark.parametrize("program, whiles", [
    ("prove_window_xla", 0), ("prove_window_pallas", 0),
    # the flight's rolled loop over its scan steps, and nothing in its body
    ("prove_flight_pallas", 1)])
def test_the_compaction_epilogue_holds_no_loop(lowered, program, whiles):
    # the epilogue counts, for each nonce row and each j <= max_hits, the
    # segment cumsums below j: compares and a sum, no dependent steps.
    # The vmap(searchsorted) it replaced lowered to a while loop of 8-9
    # dependent steps, half the flight program's device time (1 / 1 / 2
    # while ops in these three programs at 1de6202; 4 / 4 / 5 at bdb9bbf,
    # one epilogue a 16-row nonce group); the four kernel custom-calls
    # sit alone in the flight's rolled body
    assert lowered[program]["whiles"] == whiles
    if program == "prove_flight_pallas":
        assert lowered[program]["whiles_beside_the_kernel"] == 0


def test_labels_shard_over_four_chips_without_collectives(lowered):
    single, sharded = lowered["labels_single"], lowered["labels_sharded"]
    assert not sharded["all_gather"], \
        "the lane-sharded label program gathers across chips"
    # V dominates temp and shards with the lanes: a quarter per device
    ratio = sharded["temp"] / single["temp"]
    assert 0.2 < ratio < 0.3, ratio


@pytest.mark.parametrize("lanes,chips", [(32, 1), (64, 1), (128, 1),
                                         (256, 1), (8192, 1), (32768, 4)])
def test_label_program_keeps_v_for_the_whole_batch(lowered, lanes, chips):
    """At every width a cell dispatches the label program compiles, and
    ROMix's V for the WHOLE batch (128 * N bytes a lane: 8 GiB a chip at
    the init cells' shape) is in its temporary memory, under the chip's
    16 GB: a lane chunk (29% slower at 256 lanes on the chip, ROADMAP
    S3) cannot come back unseen."""
    temp = lowered[f"labels_{lanes}" + (f"x{chips}" if chips > 1 else "")
                   ]["temp"]
    assert 128 * 8192 * lanes // chips <= temp < 16e9, temp


def test_the_verifiers_full_lane_tile_compiles_and_its_double_does_not(
        lowered):
    """``_labels_fused`` at 8,192 lanes of N=8192 (the lane ceiling on a
    v5e: 8 GiB of V) compiles for the chip; at 16,384 lanes (what 256
    K3=37 proofs pad to as ONE program) the chip's compiler runs out of
    HBM, which is why post/verifier.py tiles."""
    temp = lowered["labels_verify_8192"]["temp"]
    assert 8 << 30 <= temp < 16e9, temp
    refused = lowered["labels_verify_16384"].get("refused", "")
    assert "hbm" in refused.lower(), lowered["labels_verify_16384"]
