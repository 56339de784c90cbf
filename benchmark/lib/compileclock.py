"""JAX's own compile events, summed (copied from chip_smoke.py's
CompileClock, PR 21): backend-compile seconds, trace+lower seconds and
persistent-cache hits/misses. The harness snapshots it at the window's
edges and fails the run if a program was compiled (or fetched from the
persistent cache) inside the window.

One kind of compile is counted apart: a single eager primitive
(``fun_name`` is ``jit(<lax primitive>)``, e.g. ``jit(concatenate)``).
The label path pads and trims its lane axis with eager ``jnp`` calls,
so every DISTINCT batch occupancy compiles two such ops (~80 ms each on
the chip, under the persistent cache's threshold, so every process pays
them). The verifyd driver warms every occupancy its traffic can reach
(``drivers/verifyd_service._device_counts``), and the 43 logged runs
since it does had none inside a window (my chip runs, PR 22). What that
enumeration might still miss is reported (``eager_ops``, ``eager_s``)
and fails the run only above ``EAGER_BUDGET`` of the window: softer
than "any compile fails the run", and said so in PERF.md section 7. A
compiled PROGRAM inside the window always fails the run."""

from __future__ import annotations


EAGER_BUDGET = 0.01     # share of the window eager-op compiles may take


def is_eager_primitive(fun_name: str) -> bool:
    import jax.lax

    if not (fun_name.startswith("jit(") and fun_name.endswith(")")):
        return False
    return hasattr(jax.lax, fun_name[4:-1] + "_p")


class CompileClock:
    BACKEND = "/jax/core/compile/backend_compile_duration"
    TRACE = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self) -> None:
        import jax.monitoring

        self.backend_s = 0.0
        self.backend_n = 0
        self.trace_s = 0.0
        self.hits = 0
        self.misses = 0
        self.names: list = []     # fun_name of every backend compile
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_kw) -> None:
        if event == self.BACKEND:
            self.backend_s += secs
            self.backend_n += 1
            self.names.append((str(_kw.get("fun_name", "?")), secs))
        elif event in self.TRACE:
            self.trace_s += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"backend_s": self.backend_s, "backend_n": self.backend_n,
                "trace_s": self.trace_s, "hits": self.hits,
                "misses": self.misses}

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: b[k] - a[k] for k in a}

    def window_report(self, a: dict, b: dict, window_s: float) -> dict:
        """What compiled between two snapshots, and whether it fails
        the run."""
        inside = self.names[a["backend_n"]:b["backend_n"]]
        programs = [n for n, _s in inside if not is_eager_primitive(n)]
        eager_s = sum(s for n, s in inside if is_eager_primitive(n))
        return {"programs": programs[:8],
                "eager_ops": len(inside) - len(programs),
                "eager_s": eager_s,
                "ok": not programs
                and eager_s <= EAGER_BUDGET * window_s}
