"""``control.py`` for a Clay pool: run a cell with the pool's own code
broken underneath, at the cell's own size. On the chip:

    python3 -m benchmark.tests.control_clay wrong_pair --workload clay84-4m.degraded-read --seed 7 --seconds 10 --trace 0

``wrong_pair`` changes one coefficient of the pair matrix the program
couples with ((3, 2) becomes (7, 2); still invertible with every other
row, so the program's code stays a code of its own: every read and
every repair returns the right bytes, only the stored parity is not the
reference's: ``shard_mismatch`` on every sampled object). The other
matrix takes the encode off its whole-row program
(``ClayCodec._whole_rows``) onto the plane-by-plane trace, which at the
cell's own size compiles for most of a minute on the chip, so this
control gives the preload 150 s and a client's op 120 s before it
gives up.
``repair_returns_helper`` makes the repair hand back a helper's bytes
for the lost chunk from the middle of the window on (a pool that broke
from the start would fail every read of the objects that repair, and
``check.py`` samples objects that an op of the window read right): the
generator's in-window verification fails the later reads that
repaired, and ``read_mismatch`` after the window.
``control.py``'s table is left as it is; its ``flip_parity`` and
``flip_csum`` run on this cell unchanged. The benchmark's own runs
never run this."""

from __future__ import annotations

import sys

from . import helpers

WRONG_PAIR = '''
import ceph_tpu.codecs.clay as K
_init = K.ClayCodec.init
def init(self, profile):
    _init(self, profile)
    self._g4 = self._g4.copy()
    self._g4[2, 0] ^= 4
K.ClayCodec.init = init
_cell = R.files.cell
def cell(name):
    spec = _cell(name)
    spec["deadlines_s"] = dict(spec["deadlines_s"], preload=150)
    spec["client"] = dict(spec["client"], op_timeout_s=120.0)
    return spec
R.files.cell = cell
'''

REPAIR_RETURNS_HELPER = '''
import threading
import numpy as np
import ceph_tpu.codecs.clay as K
_broken = threading.Event()
_measure = R.measure
def measure(gen, seconds, trace_dir):
    threading.Timer(seconds / 2, _broken.set).start()
    return _measure(gen, seconds, trace_dir)
R.measure = measure
_repair_window = K.ClayCodec.repair_window
def repair_window(self, lost, helper_ids, helpers):
    out = _repair_window(self, lost, helper_ids, helpers)
    if _broken.is_set():
        out = np.tile(helpers[0], (1, self.q))[:, : out.shape[1]]
    return out
K.ClayCodec.repair_window = repair_window
'''

BREAKS = {
    "wrong_pair": WRONG_PAIR,
    "repair_returns_helper": REPAIR_RETURNS_HELPER,
}


def main(argv: list[str]) -> None:
    import benchmark.run as R

    helpers.enlist_queued_cells()
    exec(BREAKS[argv[0]], {"R": R})  # noqa: S102 - our own strings
    R.main(argv[1:])


if __name__ == "__main__":
    main(sys.argv[1:])
