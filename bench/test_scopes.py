"""The per-scope reduction (``bench/scopes.py``): its protobuf wire
reader against the profiler's own readers on a recorded chip trace, and
the attribution rules on hand-made event lists with known answers."""

from __future__ import annotations

import lzma
from pathlib import Path

import pytest

import scopes
import xplane

DATA = Path(__file__).resolve().parent / "testdata"
TRACED = [("bench.traced", 0, 100, {})]


def _recorded(tmp_path, name):
    out = tmp_path / name.removesuffix(".xz")
    out.write_bytes(lzma.decompress((DATA / name).read_bytes()))
    return str(out)


def _hlo(name, op="fusion"):
    return f"%{name} = f32[8]{{0}} {op}(f32[8]{{0}} %p)"


def _loop_ops():
    """One device's ops in a 0..100 ns window: a while loop holding
    scoped fusions, the loop guard, two conditionals and layout copies
    with no ``tf_op``, and an absorb op clipped by the window's end."""
    body = "jit(_run)/while/body/"
    return [
        (_hlo("while.1", "while"), 0, 90, None),
        (_hlo("fusion.1"), 5, 15, body + "des.extract/add:"),
        (_hlo("conditional.2", "conditional"), 20, 50, None),
        (_hlo("fusion.3"), 22, 40,
         body + "des.insert/cond/branch_1_fun/des.merge/sort:"),
        (_hlo("copy.4", "copy"), 42, 48, None),
        (_hlo("fusion.5"), 55, 60, "jit(_run)/while/cond/lt:"),
        (_hlo("copy.6", "copy"), 62, 70, None),
        (_hlo("conditional.7", "conditional"), 72, 85, None),
        (_hlo("fusion.8"), 74, 80, body + "des.dispatch/mul:"),
        (_hlo("copy.9", "copy"), 80, 84, None),
        # an insert op the compiler moved into the switch's branch, and
        # a copy it made for the loop itself: neither takes the switch
        (_hlo("fusion.11"), 84, 85, body + "des.insert/slice:"),
        (_hlo("copy.12", "copy"), 72, 73, "jit(_run)/while:"),
        (_hlo("fusion.10"), 92, 110, "jit(absorb)/des.absorb/add:"),
    ]


def _as_devices(ops):
    return {0: [(s, e, n, tf) for (n, s, e, tf) in ops]}


def test_attribution_rules_by_hand():
    r = scopes.reduce_scopes(_as_devices(_loop_ops()), [TRACED])
    got = {k: round(v * 1e9, 6) for k, v in r["scopes"].items()}
    assert got == {
        # own tf_op: the innermost des.* component
        "des.extract": 10,
        # %conditional.2 takes the merge its nested fusion names (the
        # copy nested in it has no tf_op and does not count), and the
        # copy then takes its encloser's: 18 + 6 + 6
        "des.merge": 30,
        # %conditional.7: its nested time is dispatch 6, insert 1, so
        # it takes the dispatch, and so do its copies: 6 + 4 + 1 + 1
        "des.dispatch": 12,
        "des.insert": 1,
        "des.absorb": 8,          # clipped at the window's end
        # the loop guard names no scope; the while holds it, so the
        # while takes no leg, nor does %copy.6 in it; the while's own
        # 24 ns
        "unscoped": 5 + 8 + 24,
    }
    assert dict(r["unscoped_ops"]) == pytest.approx({
        "%while.1 while": 24e-9, "%copy.6 copy": 8e-9,
        "%fusion.5 fusion": 5e-9})


def test_scopes_and_unscoped_sum_to_busy():
    ops = _loop_ops()
    r = scopes.reduce_scopes(_as_devices(ops), [TRACED], steps=20)
    old = xplane.reduce_events({0: [(n, s, e) for (n, s, e, _) in ops]},
                               [("bench.traced", 0, 100)])
    assert r["busy_s"] == pytest.approx(98e-9)
    assert old["busy_s"] == pytest.approx(r["busy_s"])
    assert sum(r["scopes"].values()) == pytest.approx(r["busy_s"])
    assert r["idle_s"] == pytest.approx(2e-9)
    # op events starting in the first and last tenth, and per super-step
    # (20 steps in the window, 2 in each tenth)
    assert r["coverage"] == {"events": [2, 1], "per_step": [1.0, 0.5],
                             "guard": [0.0, 0.0, 0.05]}


def test_overlapping_ops_that_do_not_nest_still_sum_to_busy():
    ops = [("a", 0, 30, "x/des.extract/a:"), ("b", 20, 50, "x/des.insert/b:"),
           ("c", 25, 28, None)]
    r = scopes.reduce_scopes(_as_devices(ops),
                             [[("bench.traced", 0, 60, {})]])
    assert sum(r["scopes"].values()) == pytest.approx(r["busy_s"]) \
        == pytest.approx(50e-9)
    # the innermost open op owns each instant: b from 20 on, c inside it
    assert r["scopes"]["des.extract"] == pytest.approx(20e-9)
    assert r["scopes"]["des.insert"] == pytest.approx(30e-9)


def test_scope_of_a_name_stack():
    assert scopes.scope_of(None) is None
    assert scopes.scope_of("jit(_run)/while/cond/lt:") == "unscoped"
    assert scopes.scope_of(
        "jit(_run)/while/body/des.extract/cond/branch_1_fun/"
        "des.merge/sort:") == "des.merge"
    assert scopes.scope_of("jit(f)/des.feeder.stage/x") == "des.feeder.stage"
    assert scopes.scope_of("jit(f)/undes.merge/x") == "unscoped"
    # named after the construct itself: no scope of its own
    assert scopes.scope_of("jit(_run)/while:") is None
    assert scopes.scope_of("jit(_run)/while/body/cond:") is None
    assert scopes.scope_of("jit(_run)/while/cond/lt:") == "unscoped"


def _loop_and_feeder():
    loop = TRACED + [
        ("des.segment", 0, 30, {}),
        ("des.boundary", 30, 60, {}),
        ("des.absorb", 35, 45, {"rows": 3}),
        ("des.segment", 60, 100, {}),
    ]
    feeder = [("des.feeder.stage", 0, 100, {})]
    other = [("des.fence", 50, 60, {})]
    return [loop, feeder, other]


def test_idle_by_span_reads_only_the_driver_thread():
    devices = {0: [(10, 20, "f1", "x/des.extract/a:"),
                   (40, 50, "f2", "x/des.absorb/a:")]}
    r = scopes.reduce_scopes(devices, _loop_and_feeder(), steps=2)
    got = {k: round(v * 1e9, 6) for k, v in r["idle_by_span"].items()}
    # gaps 0..10, 20..40, 50..100; the feeder's span and the other
    # thread's fence never label them
    assert got == {"des.segment": 60, "des.boundary": 15, "des.absorb": 5}
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])
    assert r["boundaries"] == 1 and r["segments"] == 2
    assert r["absorbed_rows"] == 3
    assert r["boundary_idle_s"] == pytest.approx(20e-9)
    legs = scopes.legs(r, 2)
    assert legs["boundary_idle_ms"] == pytest.approx(20e-6)
    assert legs["extract_us"] == pytest.approx(10e-9 / 2 * 1e6)
    assert legs["merge_us"] == 0.0
    # no loop thread: everything is unspanned
    r = scopes.reduce_scopes(devices, [TRACED])
    assert r["idle_by_span"] == {"unspanned": pytest.approx(80e-9)}
    assert r["boundaries"] == 0
    assert scopes.legs(r, 2)["boundary_idle_ms"] is None


def test_boundary_idle_on_a_clipped_window():
    # Boundaries straddle both window edges; only the one whose middle
    # lies inside counts, with its idle inside the window.
    loop = TRACED + [
        ("des.boundary", -20, 10, {}), ("des.segment", 10, 40, {}),
        ("des.boundary", 40, 60, {}), ("des.segment", 60, 90, {}),
        ("des.boundary", 90, 130, {}),
    ]
    devices = {0: [(10, 45, "f1", "x/des.extract/a:"),
                   (55, 95, "f2", "x/des.insert/b:")]}
    r = scopes.reduce_scopes(devices, [loop])
    assert r["boundaries"] == 1
    assert r["boundary_idle_s"] == pytest.approx(10e-9)
    assert scopes.legs(r, None)["boundary_idle_ms"] == pytest.approx(10e-6)
    assert scopes.legs(r, None)["extract_us"] is None
    got = {k: round(v * 1e9, 6) for k, v in r["idle_by_span"].items()}
    # gaps 0..10, 45..55, 95..100, each under some boundary span
    assert got == {"des.boundary": 25}
    assert r["coverage"] == {"events": [0, 0], "per_step": None,
                             "guard": None}


def test_coverage_counts_loop_guard_runs():
    """Ten super-steps in the window, the last three lost by the
    profiler: the loop guard's runs per step show it."""
    guard = "jit(_run)/while/cond/lt:"
    ops = [(10 * k, 10 * k + 2, _hlo("fusion.g"), guard) for k in range(7)]
    ops += [(10 * k + 3, 10 * k + 9, _hlo("fusion.b"),
             "jit(_run)/while/body/des.extract/x:") for k in range(7)]
    r = scopes.reduce_scopes({0: ops}, [TRACED], steps=10)
    assert r["coverage"]["guard"] == [1.0, 0.0, 0.7]
    assert r["coverage"]["per_step"] == [2.0, 0.0]


def test_window_defaults_to_the_device_ops_extent():
    devices = {0: [(10, 20, "f1", "x/des.extract/a:"),
                   (30, 50, "f2", None)]}
    r = scopes.reduce_scopes(devices, [])
    assert r["window_s"] == pytest.approx(40e-9)
    assert r["idle_s"] == pytest.approx(10e-9)
    assert r["scopes"] == {"des.extract": pytest.approx(10e-9),
                           "unscoped": pytest.approx(20e-9)}


def test_wire_reader_matches_profile_data(tmp_path):
    """Names and times of every event, as ``ProfileData`` reads them."""
    from jax.profiler import ProfileData

    path = _recorded(tmp_path, "phold-1m.12steps.xplane.pb.xz")
    mine = scopes.read_xspace(path)
    theirs = list(ProfileData.from_file(path).planes)
    assert [p.name for p in mine] == [p.name for p in theirs]
    n = 0
    for p, q in zip(mine, theirs):
        lines = list(q.lines)
        assert [ln.name for ln in p.lines] == [ln.name for ln in lines]
        for ln, lq in zip(p.lines, lines):
            want = [(e.name, e.start_ns, e.duration_ns) for e in lq.events]
            got = [(p.names.get(m, ""), s, e - s)
                   for (m, s, e, _) in ln.events]
            assert got == want
            n += len(got)
    assert n == 6883


def test_wire_reader_matches_tensorflow_protos(tmp_path):
    """Metadata ids, names and ``tf_op`` stats, against the protobuf
    classes where they import."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    path = _recorded(tmp_path, "phold-1m.12steps.xplane.pb.xz")
    space = xplane_pb2.XSpace()
    space.ParseFromString(Path(path).read_bytes())
    mine = scopes.read_xspace(path)
    n_tf = 0
    for p, q in zip(mine, space.planes):
        names = {k: v.name for k, v in q.stat_metadata.items()}
        assert p.stat_names == names
        assert p.names == {k: v.name for k, v in q.event_metadata.items()}
        for k, meta in q.event_metadata.items():
            tf = [s.str_value for s in meta.stats
                  if names[s.metadata_id] == "tf_op"]
            assert p.stats.get(k, {}).get("tf_op") == (tf[0] if tf else None)
            n_tf += bool(tf)
        for ln, lq in zip(p.lines, q.lines):
            assert [m for (m, *_) in ln.events] == [
                e.metadata_id for e in lq.events]
    assert n_tf == 375


def test_recorded_unscoped_trace_is_all_unscoped(tmp_path):
    """The 12-step PHOLD trace predates the scopes: all of its busy time
    is unscoped, and busy agrees with ``xplane.py``."""
    path = _recorded(tmp_path, "phold-1m.12steps.xplane.pb.xz")
    r = scopes.reduce_file(path, steps=12)
    old = xplane.reduce_trace(path)
    assert r["busy_s"] == pytest.approx(old["busy_s"], rel=1e-12)
    assert r["window_s"] == pytest.approx(old["window_s"], rel=1e-12)
    assert r["scopes"] == {"unscoped": pytest.approx(old["busy_s"])}
    assert r["unscoped_ops"][0] == ["%conditional.4 conditional",
                                    pytest.approx(0.000990834, rel=1e-6)]
    assert r["idle_by_span"] == {"unspanned": pytest.approx(old["idle_s"])}
    # every one of the 12 super-steps (and the exit check) was kept
    assert r["coverage"]["guard"][2] == pytest.approx(13 / 12)


def test_recorded_scoped_streamed_trace(tmp_path):
    """A streamed admission run on one TPU v5 lite (``admission-64`` in
    blocks of 32 rows), profiled over three segment boundaries with the
    Python tracer off: every leg's scope, the loop's spans, and the
    feeder thread's spans kept apart."""
    path = _recorded(tmp_path, "admission-64.3boundaries-of-32.xplane.pb.xz")
    r = scopes.reduce_file(path, steps=68)
    old = xplane.reduce_trace(path)
    assert r["busy_s"] == pytest.approx(old["busy_s"], rel=1e-12)
    assert r["window_s"] == pytest.approx(old["window_s"], rel=1e-12)
    assert sum(r["scopes"].values()) == pytest.approx(r["busy_s"], rel=1e-12)
    assert r["scopes"] == pytest.approx({
        "des.absorb": 0.00053517, "des.dispatch": 0.003887636,
        "des.extract": 0.001418023, "des.insert": 0.003919474,
        "unscoped": 0.002392054}, rel=1e-6)
    # the dispatch switch takes its branches' scope; the loop does not
    assert r["unscoped_ops"][0] == ["%while.654 while",
                                    pytest.approx(0.001278494, rel=1e-6)]
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])
    assert set(r["idle_by_span"]) == {
        "des.next_time", "des.segment", "des.occupancy", "des.fence",
        "des.absorb", "des.spill", "des.boundary", "unspanned"}
    assert r["idle_by_span"]["des.next_time"] == pytest.approx(
        0.023481393, rel=1e-6)
    # the window opens inside the 5th segment and closes as the 8th
    # starts: three boundaries of 32 rows each, two whole segments
    assert (r["boundaries"], r["segments"], r["absorbed_rows"]) == (3, 2, 96)
    assert scopes.legs(r, 68)["boundary_idle_ms"] == pytest.approx(
        0.038012289 / 3 * 1e3, rel=1e-6)
    feeder = [ln for ln in scopes.host_lines(scopes.read_xspace(path))
              if any(n == "des.feeder.stage" for (n, *_) in ln)]
    assert len(feeder) == 1
    assert not any(n == "des.segment" for (n, *_) in feeder[0])
    # 68 super-steps and the exit check of each of the three segments
    assert r["coverage"]["guard"][2] == pytest.approx(71 / 68)
