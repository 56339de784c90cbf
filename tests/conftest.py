"""Test config: force JAX onto a virtual 8-device CPU platform.

Tests must not require TPU hardware; multi-chip sharding is exercised on a
virtual CPU mesh (SURVEY.md §7 test carry-over (f)). Both settings land in
the environment BEFORE jax is imported (below), so they hold for this
process and for every subprocess a test spawns. What the chip itself
accepts is checked without a chip by tests/test_tpu_lowering.py (AOT
compile for v5e) and with one by chip_smoke.py.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import tempfile  # noqa: E402

# a developer shell with SPACEMESH_TRACE set must not arm the span
# tracer for the whole suite (tests that want a capture call
# tracing.start() themselves — tests/test_tracing.py)
os.environ.pop("SPACEMESH_TRACE", None)

# likewise an operator shell with JSON logging on must not change the
# log format tests parse (tests that want JSON lines call
# logging.configure(json_lines=True) themselves — tests/test_health_engine.py)
os.environ.pop("SPACEMESH_LOG_JSON", None)

# the runtime sanitizers (utils/sanitize.py) must not arm for the whole
# suite from a developer/CI shell: several tests dispatch deliberately
# odd shapes with bucketing disabled (tests that want the sanitizer call
# sanitize.enable() themselves — tests/test_spacecheck.py)
os.environ.pop("SPACEMESH_SANITIZE", None)

# the verifyd batch tuner (verifyd/batchtune.py) must stay deterministic
# and cheap under test: no implicit backend races, and never persist
# measured rates into the developer's real cache root (tests that want
# a race opt back in with monkeypatch)
os.environ.setdefault("SPACEMESH_VERIFYD_TUNE", "off")
os.environ.setdefault(
    "SPACEMESH_VERIFYD_TUNE_CACHE",
    os.path.join(tempfile.gettempdir(),
                 f"spacemesh-test-batchtune-{os.getpid()}.json"))

# spacecheck's incremental findings cache (tools/spacecheck/engine.py)
# must never mix test scratch trees into the developer's real cache
# file (tests/test_racecheck.py point it at their own tmp paths)
os.environ.setdefault(
    "SPACEMESH_SPACECHECK_CACHE",
    os.path.join(tempfile.gettempdir(),
                 f"spacemesh-test-spacecheck-{os.getpid()}.json"))

import jax  # noqa: E402  (import order is the point here)

# belt and braces: holds even if a pytest plugin imported jax before the
# environment above was set
jax.config.update("jax_platforms", "cpu")

# persistent XLA compile cache: the suite's jit compiles are paid once per
# checkout, not once per pytest invocation (utils/accel.py: .cache/jax in
# the checkout, or wherever JAX_COMPILATION_CACHE_DIR says)
from spacemesh_tpu.utils import accel  # noqa: E402

accel.enable_persistent_cache()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: tier-2 heavyweight scenarios (multi-process clusters); "
        "the tier-1 command runs -m 'not slow'")
