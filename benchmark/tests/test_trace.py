"""The reduction from a profiler trace to numbers, on hand-made
intervals and on a small trace recorded on the chip (a 2 s traced
window of ``rs84-4m.write``: my chip run, PR 23)."""

import os

import pytest

from benchmark import files, metrics
from benchmark.metrics.readers import device_idle, kernel_roofline
from benchmark.trace import kernel_cost, peaks, xplane

RECORDED = os.path.join(
    files.HERE, "trace", "recorded", "rs84-4m.write.2s.xplane.pb"
)
#: the traced window's length by the host clock, from that run's line
RECORDED_WINDOW_S = 2.0000618270000246

FUSED = (
    "%_apply_tiled_csum.1 = (u8[128,4,4096]{2,1,0:T(4,128)(4,1)}, "
    "s32[128,1,12,32]{3,2,1,0:T(8,128)S(1)}) custom-call(s8[32,64]{1,0} "
    "%bmat_big.1, u8[128,8,4096]{2,1,0} %data.1)"
)
DECODE = "%_apply_tiled.1 = u8[128,1,4096]{2,1,0:T(4,128)(4,1)S(1)} custom-call(...)"


@pytest.fixture(scope="module")
def recorded():
    return xplane.load(RECORDED, set(files.benchmark_spans()))


def test_union_merges_overlaps_and_keeps_gaps():
    assert xplane.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [
        (0, 2.5), (3, 4)
    ]
    assert xplane.union([]) == []


def test_busy_counts_overlapping_ops_once():
    trace = xplane.Trace(
        {"/device:TPU:0": [("a", 0.0, 1.0), ("b", 0.5, 1.5)],
         "/device:TPU:1": [("a", 0.0, 0.25)]}, [],
    )
    assert xplane.busy_seconds(trace) == {
        "/device:TPU:0": 1.5, "/device:TPU:1": 0.25,
    }


def test_idle_gaps_and_their_attribution():
    trace = xplane.Trace(
        {"/device:TPU:0": [("k", 1.0, 2.0), ("k", 5.0, 6.0)]},
        [("osd_op", 0.0, 4.0), ("ec_write", 2.0, 3.0), ("sub_write", 2.5, 2.75)],
    )
    gaps = xplane.idle_gaps(trace, 0.0, 8.0)
    assert gaps == [(0.0, 1.0), (2.0, 5.0), (6.0, 8.0)]
    by = dict(xplane.attribute_gaps(
        trace, gaps, ["sub_write", "ec_write", "osd_op"]
    ))
    # innermost first: 0.25 s to sub_write, the rest of ec_write's
    # second to ec_write, what is left of osd_op's span to osd_op
    assert by == pytest.approx({
        "sub_write": 0.25, "ec_write": 0.75, "osd_op": 2.0,
        "unattributed": 3.0,
    })


def test_kernel_cost_counts_only_what_the_algorithm_needs():
    cost = kernel_cost.bitmatrix_apply(FUSED, 8, 4096)
    data = 128 * 8 * 4096
    assert cost["data_bytes"] == data
    # k chunks in, m chunks and one crc word per 4 KiB block of all 12 out
    assert cost["bytes"] == data + 128 * 4 * 4096 + 128 * 12 * 4
    assert cost["ops"] == 2 * 64 * 4 * data  # mac_stats: 64*m per byte
    one = kernel_cost.bitmatrix_apply(DECODE, 8)
    assert one["bytes"] == data + 128 * 4096
    assert one["ops"] == 2 * 64 * 1 * data
    assert kernel_cost.bitmatrix_apply("%copy.1 = u8[4]{0} copy(...)", 8) is None
    assert kernel_cost.bitmatrix_cost(data, 8, 4, 4096) == cost


def test_peaks_refuse_an_unknown_device():
    assert peaks.published_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819.0e9
    with pytest.raises(RuntimeError):
        peaks.published_peaks("cpu")


def test_recorded_trace_busy_kernels_and_gaps(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    assert len(recorded.devices["/device:TPU:0"]) == 144
    busy = xplane.busy_seconds(recorded)["/device:TPU:0"]
    assert busy == pytest.approx(0.0010454480, rel=1e-6)
    fused = xplane.kernel_events(recorded, "%_apply_tiled_csum")
    assert len(fused) == 6
    assert sum(s for _n, s in fused) == pytest.approx(0.000693398, rel=1e-6)
    assert xplane.top_ops(recorded, 1)[0][0] == "%_apply_tiled_csum.1"
    lo, hi = xplane.span_bounds(recorded)
    gaps = xplane.idle_gaps(recorded, lo, hi)
    assert sum(e - s for s, e in gaps) == pytest.approx(hi - lo - busy)
    by = dict(xplane.attribute_gaps(recorded, gaps, files.benchmark_spans()))
    assert set(by) == {"unattributed", "sub_write", "client_op"}
    assert sum(by.values()) == pytest.approx(hi - lo - busy)


def test_recorded_trace_through_the_readers(recorded):
    cell = files.cell("rs84-4m.write")
    ctx = metrics.RunContext(
        cell=cell, config=files.config(cell["config"]),
        device_kind="TPU v5 lite", moved={}, compiles=[], trace=recorded,
        window_s=RECORDED_WINDOW_S,
    )
    idle = device_idle.read({}, ctx)
    assert idle == pytest.approx(99.9477292, rel=1e-7)
    share = kernel_roofline.read({"name": "codec_roofline"}, ctx)
    # 6 calls x 6,297,600 B at 819 GB/s over 693.4 us on the device
    assert share == pytest.approx(100 * 6 * 6297600 / 819e9 / 0.000693398, rel=1e-5)
    assert 0 < share < 100
    assert "bound by {'hbm': 6}" in ctx.notes[-1]


def test_readers_return_nothing_where_there_is_nothing_to_read():
    cell = files.cell("rs84-4m.write")
    ctx = metrics.RunContext(
        cell=cell, config=files.config(cell["config"]), device_kind="cpu",
        moved={"ec_dispatch:host_encode_bytes": 0}, compiles=[],
        trace=xplane.Trace({}, []), window_s=1.0,
    )
    assert device_idle.read({}, ctx) is None
    assert kernel_roofline.read({"name": "x"}, ctx) is None
    assert metrics.read(files.metric("codec_device_pct"), ctx) is None
    assert metrics.read(files.metric("compiles_in_window"), ctx) == 0.0


def test_counter_ratio_sums_globs_and_takes_off_the_minus_list():
    ctx = metrics.RunContext(
        cell={}, config={}, device_kind="", compiles=[], trace=None,
        window_s=1.0, moved={
            "ec_dispatch:fused_encode_bytes": 900,
            "ec_dispatch:pallas_decode_bytes": 50,
            "ec_dispatch:host_delta_bytes": 50,
            "ec_dispatch:fused_encode": 9, "ec_dispatch:pallas_decode": 1,
            "ec_dispatch:host_delta": 5,
        },
    )
    assert metrics.read(files.metric("codec_device_pct"), ctx) == 95.0
    assert metrics.read(
        files.metric("bytes_per_dispatch"), ctx
    ) == pytest.approx(950 / 10 / 1024)
