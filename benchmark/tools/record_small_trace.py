#!/usr/bin/env python3
"""Record the small trace that benchmark/selftest.py checks the
reduction on. Run on the chip; writes chiprun_out/small_trace/.

Three label programs at N=32 over 128 lanes, each fetched inside an
``init.fetch`` annotation, with a 20 ms sleep inside ``init.write_stall``
between the second and the third, all inside ``bench.window``."""
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from spacemesh_tpu.ops import scrypt  # noqa: E402

out = os.path.join(ROOT, "chiprun_out", "small_trace")
shutil.rmtree(out, ignore_errors=True)
cw = jnp.asarray(scrypt.commitment_to_words(bytes(range(32))))
lo = jnp.asarray(np.arange(128, dtype=np.uint32))
hi = jnp.zeros(128, jnp.uint32)
scrypt.scrypt_labels_jit(cw, lo, hi, n=32).block_until_ready()   # compile
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 1
opts.enable_hlo_proto = False
jax.profiler.start_trace(out, profiler_options=opts)
with jax.profiler.TraceAnnotation("bench.window"):
    for k in range(3):
        w = scrypt.scrypt_labels_jit(cw, lo + jnp.uint32(k), hi, n=32)
        with jax.profiler.TraceAnnotation("init.fetch"):
            np.asarray(w)
        if k == 1:
            with jax.profiler.TraceAnnotation("init.write_stall"):
                time.sleep(0.02)
    time.sleep(0.005)
jax.profiler.stop_trace()
print("recorded", out)
