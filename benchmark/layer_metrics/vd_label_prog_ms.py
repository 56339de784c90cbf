"""label_prog_ms, for the verifyd cells (64-256 lanes, not 8192)."""
from layer_metrics import label_prog_ms as _base

META = dict(_base.META, moves="p50_ms")
read = _base.read
