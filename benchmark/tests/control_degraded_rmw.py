"""``control.py`` for overwrites through a hole: run the cell with the
degraded write path broken underneath, at the cell's own size. On the
chip:

    python3 -m benchmark.tests.control_degraded_rmw skip_live_parity --workload rs84-rbd-degraded.randwrite --seed 7 --seconds 10 --trace 0

``skip_live_parity`` drops one LIVE parity shard's contribution from
every parity delta (the first parity shard of the PG that is up): that
shard keeps its old page under every overwrite that went by delta, and
the stored parity is not the reference's: ``shard_mismatch`` on every
sampled object that a delta touched, and ``read_mismatch`` where the
hole is a data shard, since the client's read then decodes through the
stale page.
``reconstruct_zeros`` takes the old page of the dead shard, where a
write has to rebuild it from the survivors, as zeros: the row is then
re-encoded over a page that holds the patch and nothing else, and the
live parity of an object whose patch hit the dead shard is not the
reference's: ``shard_mismatch`` again, with ``decode_mismatch`` and
``read_mismatch``, since the survivors no longer agree on the row.
Only the decode inside a write's old-data read is broken: the client's
degraded reads decode as they should, from what the writes left.
A program without a live-aware plan (the parent) runs both too: they
patch what ``rs84-rbd.randwrite`` already used. The benchmark's own
runs never run this."""

from __future__ import annotations

import sys

from . import helpers

SKIP_LIVE_PARITY = '''
import numpy as np
import ceph_tpu.pipeline.rmw as W
_resume = W.RMWPipeline._delta_resume
def resume(self, op, new_map, work, new_size, contribs, *rest):
    if (
        work is not None and work.windows is None
        and not isinstance(contribs, BaseException)
    ):
        k, m = self.sinfo.k, self.sinfo.m
        live = self.backend.avail_shards()
        j = next(
            j for j in range(m) if self.sinfo.get_shard(k + j) in live
        )
        contribs = np.array(contribs)
        contribs[:, j] = 0
    return _resume(self, op, new_map, work, new_size, contribs, *rest)
W.RMWPipeline._delta_resume = resume
'''

RECONSTRUCT_ZEROS = '''
import threading
import numpy as np
import ceph_tpu.pipeline.rmw as W
import ceph_tpu.pipeline.shard_map as S
_in_write = threading.local()
_read = W.RMWPipeline._backend_read
def backend_read(self, oid, want):
    _in_write.on = True
    try:
        return _read(self, oid, want)
    finally:
        _in_write.on = False
W.RMWPipeline._backend_read = backend_read
_decode = S.ShardExtentMap.decode
def decode(self, codec, want, object_size):
    lost = [s for s in want if s not in self._bufs]
    _decode(self, codec, want, object_size)
    if getattr(_in_write, "on", False):
        for s in lost:
            for lo, hi in self.get_extent_set(s):
                self.insert(s, lo, np.zeros(hi - lo, np.uint8))
S.ShardExtentMap.decode = decode
'''

BREAKS = {
    "skip_live_parity": SKIP_LIVE_PARITY,
    "reconstruct_zeros": RECONSTRUCT_ZEROS,
}


def main(argv: list[str]) -> None:
    import benchmark.run as R

    helpers.enlist_queued_cells()
    exec(BREAKS[argv[0]], {"R": R})  # noqa: S102 - our own strings
    R.main(argv[1:])


if __name__ == "__main__":
    main(sys.argv[1:])
