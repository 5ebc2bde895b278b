"""Run one cell with a guarantee broken underneath, at the cell's own
size: the control that ``correct`` has to fail. On the chip:

    python3 -m benchmark.tests.control wrong_row --workload rs84-4m.write --seed 7 --seconds 5 --trace 0

``wrong_row`` is the program encoding with one coefficient of one
generator row changed (the nearest thing to a lower precision that a
system with exact arithmetic has: every read still returns the right
bytes, only the stored parity is wrong); ``kernel_alters_parity`` alters
one parity byte where the fused kernel's wrapper returns it;
``flip_parity`` and ``flip_csum`` flip a stored byte or checksum after
the window; ``leftover_shard`` puts one removed shard's key back in a
store after the window, ``remove_does_nothing`` makes every OSD
acknowledge a remove and remove nothing, and ``zero_carried_crc`` makes
the program restart a shard's cumulative crc at every append onto
hashed bytes (all three for a cell whose mix deletes and appends);
``none`` breaks nothing. A cell that is queued (``queued_cells.json``:
its files are here, its entries not yet in ``BENCHMARK.json``) runs
through this entry as a listed one, which is how it is run on the chip
until a later PR lists it. The benchmark's own runs never run this."""

from __future__ import annotations

import sys

from . import helpers, test_correct

BREAKS = {
    "none": "",
    "wrong_row": test_correct.WRONG_ROW,
    "kernel_alters_parity": test_correct.KERNEL_ALTERS_PARITY,
    "flip_parity": test_correct.FLIP_PARITY,
    "flip_csum": test_correct.FLIP_CSUM,
    "leftover_shard": test_correct.LEFTOVER_SHARD,
    "zero_carried_crc": test_correct.ZERO_CARRIED_CRC,
    "remove_does_nothing": test_correct.REMOVE_DOES_NOTHING,
}


def main(argv: list[str]) -> None:
    import benchmark.run as R

    helpers.enlist_queued_cells()
    exec(BREAKS[argv[0]], {"R": R})  # noqa: S102 - our own strings
    R.main(argv[1:])


if __name__ == "__main__":
    main(sys.argv[1:])
