"""lane_fill_counted, for the node-farm batch cell: ``lanes_valid`` over
``lanes`` of the ``post.verify`` spans, padding of every lane tile
included (9,472 valid of 10,240 dispatched when no proof of a batch is
rejected on the host)."""
from layer_metrics import lane_fill_counted as _base

META = dict(_base.META, moves="proofs_per_s")
read = _base.read
