"""romix_roofline, for the verifyd cells."""
from layer_metrics import romix_roofline as _base

META = dict(_base.META, moves="p50_ms")
read = _base.read
