"""Operations and bytes of the kernels, computed from their shapes (the
arithmetic of ``tools/profiler.romix_roofline``, copied; the peak and
the time come from elsewhere: ``lib/device.py`` and the trace)."""

from __future__ import annotations


def romix_v_bytes(n: int, lanes: int, r: int = 1) -> int:
    """Bytes of ROMix scratch V for ``lanes`` labels: 128*r*N each."""
    return 128 * r * n * lanes


def romix_hbm_bytes(n: int, lanes: int, r: int = 1, p: int = 1) -> int:
    """HBM traffic the algorithm needs for ``lanes`` labels: V is
    written once (fill) and read once (mix): 2 * 128 * r * N * p."""
    return 2 * 128 * r * n * p * lanes


def romix_salsa_cores(n: int, lanes: int, r: int = 1, p: int = 1) -> int:
    """Salsa20/8 core applications: 2 per BlockMix (r=1), N BlockMix in
    fill and N in mix: 4 * N * r * p per label."""
    return 4 * n * r * p * lanes
