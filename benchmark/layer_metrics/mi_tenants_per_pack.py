"""How many identities share one packed label program: the median
``tenants`` of the ``runtime.pack.dispatch`` spans inside the window.
1 where one tenant's lanes fill a pack (cross-tenant packing is then
fair-share rotation)."""
from lib import stats

META = {"layer": "pipeline runtime/scheduler", "unit": "tenants",
        "source": "program_span", "moves": "labels_per_s",
        "better": "higher"}


def read(facts):
    counts = [s["args"]["tenants"]
              for s in facts.spans_named("runtime.pack.dispatch")
              if "tenants" in s["args"]]
    return stats.median(counts) if counts else None
