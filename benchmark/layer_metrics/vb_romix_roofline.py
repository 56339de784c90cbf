"""romix_roofline where one batch runs label programs of several widths
(the node-farm batch cell): the bytes ROMix needs for the lanes
DISPATCHED (``lib/shapes.romix_hbm_bytes``: 2*128*N a lane, padding
included) over the chip's peak HBM bytes/s, over the summed device time
of the label programs; each execution counted at its own width
(``vb_tile_prog_ms.by_width``), where ``romix_roofline`` takes the mean
width of the window's dispatches. HBM bound assumed, as there."""
from layer_metrics import vb_tile_prog_ms as _tiles
from lib import shapes

META = {"layer": "kernels ops/scrypt", "unit": "%",
        "source": "device_trace", "moves": "proofs_per_s",
        "better": "higher"}


def read(facts):
    split = _tiles.by_width(facts)
    if not split or facts.peaks is None:
        return None
    n = int(_tiles.tile_dispatches(facts)[0]["args"]["n"])
    chips = max(len(facts.reduction.chips), 1)
    need = sum(shapes.romix_hbm_bytes(n, w // chips) * len(durs)
               for w, durs in split.items())
    return 100.0 * (need / facts.peaks["hbm_bytes_per_s"]) \
        / sum(sum(durs) for durs in split.values())
