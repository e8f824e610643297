"""The trace reduction, on hand-made event lists with known answers and
on small traces recorded on the chip (``bench/testdata``)."""

from __future__ import annotations

from pathlib import Path

import pytest

import xplane

DATA = Path(__file__).resolve().parent / "testdata"


def test_busy_idle_collectives_and_gaps_by_hand():
    # window 0..100 ns; device 0: ops 10..30 and 20..40 overlap (busy
    # 30), all-gather 60..70; device 1: one op 0..50 and all-reduce
    # 50..60.
    devices = {
        0: [("fusion.1", 10, 30), ("fusion.2", 20, 40),
            ("all-gather.3", 60, 70)],
        1: [("fusion.1", 0, 50), ("all-reduce.1", 50, 60)],
        7: [("fusion.9", 0, 100)],
    }
    host = [("bench.traced", 0, 100), ("bench.run_call", 0, 45),
            ("bench.source_block", 42, 58), ("outside", 200, 300)]
    r = xplane.reduce_events(devices, host, use=(0, 1))
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(100e-9)
    # busy: device 0 = 30 + 10 = 40, device 1 = 60 -> mean 50
    assert r["busy_s"] == pytest.approx(50e-9)
    assert r["idle_share"] == pytest.approx(0.5)
    assert r["idle_s"] == pytest.approx(50e-9)
    # collective share: 10/40 and 10/60, mean
    assert r["collective_share"] == pytest.approx((10 / 40 + 10 / 60) / 2)
    assert r["has_collectives"]
    ops = dict(r["device_ops"])
    # fusion.2 (20..40) overlaps fusion.1 (10..30) without nesting in
    # it, so neither loses time to the other.
    assert ops["fusion.1"] == pytest.approx((20 + 50) / 2 * 1e-9)
    assert ops["fusion.2"] == pytest.approx(20 / 2 * 1e-9)
    gaps = dict(r["idle_gaps"])
    # device 0 gaps: 0..10 (run_call), 40..60 (mid 50: source_block),
    # 70..100 (traced only); device 1: 60..100 (traced only).
    assert gaps["bench.run_call"] == pytest.approx(10 / 2 * 1e-9)
    assert gaps["bench.source_block"] == pytest.approx(20 / 2 * 1e-9)
    assert gaps["bench.traced"] == pytest.approx((30 + 40) / 2 * 1e-9)
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(r["idle_s"])


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce_events({0: [("f", 0, 1)]}, [("other", 0, 5)])


def test_metric_readers_return_nothing_without_their_input():
    import harness

    ctx = {"trace": None, "traced": None,
           "window": {"events": 160, "batches": 10, "seconds": 1.0}}
    for name in ("device_idle_share", "superstep_device_us",
                 "idle_ms_per_block"):
        assert harness.load_module("metrics", name).read(ctx) is None
    assert harness.load_module("metrics", "events_per_superstep").read(
        ctx) == 16.0


def test_self_time_subtracts_nested_operations():
    ops = [("%while.1 = (s32[]) while(s32[] %a)", 0, 100),
           ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %b)", 10, 40),
           ("%copy.3 = f32[8]{0} copy(f32[8]{0} %c)", 50, 60)]
    got = dict(xplane._self_times(ops, 0, 100))
    assert got == {"%while.1 while": 60, "%fusion.2 fusion": 30,
                   "%copy.3 copy": 10}


def _recorded(tmp_path, name):
    import lzma

    out = tmp_path / name.removesuffix(".xz")
    out.write_bytes(lzma.decompress((DATA / name).read_bytes()))
    return str(out)


def test_recorded_one_chip_trace(tmp_path):
    """12 traced PHOLD super-steps on one TPU v5 lite (phold-1m.closed)."""
    r = xplane.reduce_trace(_recorded(tmp_path,
                                      "phold-1m.12steps.xplane.pb.xz"))
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.00968319, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.006428149, rel=1e-9)
    assert r["idle_share"] == pytest.approx(0.3361537881627852, rel=1e-9)
    assert not r["has_collectives"]
    assert r["device_ops"][0] == ["%conditional.4 conditional",
                                  pytest.approx(0.000990834, rel=1e-6)]
    assert len(r["device_ops"]) == 10
    assert r["idle_gaps"][0][0] == "$api.py:3097 block_until_ready"
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(r["idle_s"])
    assert sum(v for _, v in r["device_ops"]) <= r["busy_s"] * (1 + 1e-9)
