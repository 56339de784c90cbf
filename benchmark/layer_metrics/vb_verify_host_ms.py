"""post_verify_host_ms, for the node-farm batch cell: median over the
``post.verify`` spans of duration minus their ``device.flight``: the
checks of 256 proofs, the pack of ~9,000 lanes into lane tiles, the one
upload, the threshold."""
from layer_metrics import post_verify_host_ms as _base

META = dict(_base.META, moves="proofs_per_s")
read = _base.read
