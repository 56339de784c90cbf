"""post_batch_proofs, for the node-farm batch cell: median ``n`` of the
``farm.batch`` spans of kind post (``max_batch`` = 256 when the backlog
keeps every batch full)."""
from layer_metrics import post_batch_proofs as _base

META = dict(_base.META, moves="proofs_per_s")
read = _base.read
