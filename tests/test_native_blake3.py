"""Native C++ BLAKE3 vs the pure-Python reference implementation.

The Python module is vector-tested elsewhere (test_core.py); here the
native twin must match it bit-for-bit across the shapes that exercise
every tree rule: sub-block, block boundaries, chunk boundaries, deep
merge stacks, keyed mode, and long XOF outputs."""

import os
import random

import pytest

from spacemesh_tpu import native
from spacemesh_tpu.core import hashing


@pytest.fixture(scope="module")
def lib():
    lib = native.load("blake3")
    if lib is None:
        pytest.skip("native toolchain unavailable")
    return lib


def _python_hash(data: bytes, key=None, length=32) -> bytes:
    h = hashing.Hasher(key=key)
    h.update(data)
    return h.digest(length)


SIZES = [0, 1, 31, 32, 63, 64, 65, 127, 128, 512, 1023, 1024, 1025,
         2048, 3072, 4096, 5000, 16384, 31744, 65536 + 17]


def test_native_matches_python_across_tree_shapes(lib):
    rng = random.Random(42)
    for size in SIZES:
        data = bytes(rng.randrange(256) for _ in range(min(size, 4096)))
        data = (data * (size // max(len(data), 1) + 1))[:size]
        assert hashing._hash_oneshot(data, None, 32) == \
            _python_hash(data), f"size {size} diverged"


def test_native_keyed_and_lengths(lib):
    key = bytes(range(32))
    for size in (0, 65, 1024, 4097):
        data = b"\xab" * size
        for length in (20, 32, 64, 131):
            want = _python_hash(data, key=key, length=length)
            got = hashing._hash_oneshot(data, key, length)
            assert got == want, (size, length)


def test_api_functions_use_native(lib):
    # sum256/sum160/keyed concatenate chunks before dispatch
    a, b = b"hello ", b"world" * 300
    assert hashing.sum256(a, b) == _python_hash(a + b)
    assert hashing.sum160(a, b) == _python_hash(a + b, length=20)
    key = b"k" * 32
    assert hashing.keyed(key, a, b) == _python_hash(a + b, key=key)


def test_native_is_actually_fast(lib):
    import time

    data = b"x" * 512
    n = 20_000
    t0 = time.perf_counter()
    for i in range(n):
        hashing.sum256(data)
    rate = n / (time.perf_counter() - t0)
    # pure python runs ~650/s; native must be orders beyond it
    assert rate > 20_000, f"native path too slow: {rate:,.0f}/s"


def test_rebuild_on_stale_lib(tmp_path):
    """build.py recompiles when the source is newer than the .so."""
    src = native._DIR / "blake3.cpp"
    lib_path = native._OUT / "libsmtpu_blake3.so"
    if not lib_path.exists():
        pytest.skip("no prior build")
    os.utime(src)  # source now newer
    assert native._build("blake3") is not None
    assert lib_path.stat().st_mtime >= src.stat().st_mtime
