"""The device a run landed on, the table of peaks, and the memory peak.

Peaks are keyed by ``device_kind`` exactly as JAX reports it. A device
that is not in the table is an error, never a default."""

from __future__ import annotations

# source: Google Cloud documentation, "TPU v5e" system architecture page
# (cloud.google.com/tpu/docs/v5e): per chip 197 TFLOP/s bf16, 393 TOP/s
# int8, 16 GB HBM2e at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "bf16_flop_per_s": 197e12,
        "int8_op_per_s": 393e12,
        "source": "Google Cloud documentation, 'TPU v5e' "
                  "(cloud.google.com/tpu/docs/v5e)",
    },
}


class UnknownDevice(KeyError):
    """The run's ``device_kind`` has no row in :data:`PEAKS`."""


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device_kind {device_kind!r}; add a row to "
            "benchmark/lib/device.py PEAKS with its source") from None


def describe(devices) -> dict:
    """platform / kind / count, as JAX reports them."""
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak_bytes(devices, program_bytes: int = 0) -> int:
    """Peak bytes on the fullest chip: the larger of what the runtime
    reports and ``program_bytes``.

    ``memory_stats()["peak_bytes_in_use"]`` on this runtime counts live
    buffers only, not a running program's temporaries (PERF.md, PR 21:
    20 MB reported after an 8 GiB-temp program). ``program_bytes`` is
    COMPUTED, not read from the compiler or the chip: the ROMix scratch
    of the widest label program the cell runs, from its shapes
    (``lib/shapes.romix_v_bytes``: 128*N bytes per lane on a chip),
    which the driver passes in. It leaves out the program's arguments,
    outputs and other temporaries (under 1% of V at these shapes) and
    counts one program, since the runtime takes a program's temp when
    it starts and batches in flight do not hold theirs together."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}      # None on the CPU
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return max(peak, int(program_bytes))
