"""device_idle_share, for the cells judged on proofs_per_s."""
from layer_metrics import device_idle_share as _base

META = dict(_base.META, moves="proofs_per_s")
read = _base.read
