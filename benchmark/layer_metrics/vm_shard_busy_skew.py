"""shard_busy_skew for a verify cell on a mesh: (max - min) / max of
each chip's busy time (the union of its program executions) over the
device trace's window. The label program is nearly all of it, and every
chip runs every sharded program, padding lanes and all, so this says
whether one chip's slice, or a program placed on one chip alone, keeps
it busier, not whether it holds real lanes (``vm_chip_fill_min`` says
that). Busy time, and not whole executions, because the four-chip
cell's device trace is ~3 s long (``traffic/batch-four-chip.json``) and
holds one whole label program at most. Nothing to read off a mesh or
without a trace."""
from layer_metrics import shard_busy_skew as _base

META = dict(_base.META, moves="proofs_per_s")
read = _base.read
