"""device_idle_share, for the cell judged on a whole proof's p50_ms."""
from layer_metrics import device_idle_share as _base

META = dict(_base.META, moves="p50_ms")
read = _base.read
