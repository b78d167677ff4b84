"""The trace reduction on a small synthesised XSpace, checked by hand."""

import pytest
from jax.profiler import ProfileData

from benchmarks.chip import run, xplane

# one device, times in ns (offsets in ps in the proto):
#   host:  bench:window [0, 10000); bench:call [500, 6500) and [6800, 9500)
#   programs: setup_op [1000, 1500), _scan_iterations [2000, 6000),
#             setup_op [7000, 7200), _scan_iterations [8000, 9000)
#   ops: [1000, 1500), [2000, 4000), [3000, 6000) (overlapping),
#        [7000, 7200), [8000, 9000), and one at [9800, 10400) that the
#        window cuts at 10000
XSPACE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 4000000 }
    events { metadata_id: 1 offset_ps: 7000000 duration_ps: 200000 }
    events { metadata_id: 2 offset_ps: 8000000 duration_ps: 1000000 } }
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 500000 }
    events { metadata_id: 4 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 5 offset_ps: 3000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 7000000 duration_ps: 200000 }
    events { metadata_id: 4 offset_ps: 8000000 duration_ps: 1000000 }
    events { metadata_id: 5 offset_ps: 9800000 duration_ps: 600000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_setup_op(1)" } }
  event_metadata { key: 2 value { id: 2 name: "jit__scan_iterations(7)" } }
  event_metadata { key: 3 value { id: 3 name: "add.1" } }
  event_metadata { key: 4 value { id: 4 name: "fusion.2" } }
  event_metadata { key: 5 value { id: 5 name: "dot.3" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 6000000 }
    events { metadata_id: 2 offset_ps: 6800000 duration_ps: 2700000 }
    events { metadata_id: 3 offset_ps: 600000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "bench:window" } }
  event_metadata { key: 2 value { id: 2 name: "bench:call" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(step)" } }
}
"""


@pytest.fixture(scope="module")
def tr():
    return xplane.from_planes(ProfileData.from_text_proto(XSPACE).planes)


def test_events_sorted_into_kinds(tr):
    assert list(tr.ops) == ["/device:TPU:0"]
    assert len(tr.ops["/device:TPU:0"]) == 6
    assert len(tr.programs["/device:TPU:0"]) == 4
    assert [n for n, _, _ in tr.annotations] == [
        "bench:window", "bench:call", "bench:call"]
    assert tr.window() == (0.0, 10000.0)


def test_busy_union_and_idle_share(tr):
    # union: 500 + [2000, 6000) 4000 + 200 + 1000 + [9800, 10000) 200
    assert xplane.device_busy_s(tr) == pytest.approx(5900e-9)
    assert xplane.window_s(tr) == pytest.approx(10000e-9)
    assert xplane.idle_share(tr) == pytest.approx(0.41)


def test_gaps_cover_the_rest(tr):
    gaps = xplane.idle_gaps([(s, e) for _, s, e in tr.ops["/device:TPU:0"]],
                            0, 10000)
    assert gaps == [(0, 1000), (1500, 2000), (6000, 7000), (7200, 8000),
                    (9000, 9800)]


def test_program_time_and_counts(tr):
    assert xplane.program_time_s(tr, "_scan_iterations") == \
        pytest.approx(5000e-9)
    # per call: ns from its start to the loop program, programs before it
    assert xplane.lead_in(tr, "bench:call", "_scan_iterations") == [
        (1500.0, 1), (1200.0, 1)]


def test_top_ops(tr):
    # the ops overlap ([2000, 4000) and [3000, 6000)) but neither holds
    # the other, so each keeps its own time; dot.3 is cut at the window
    top = dict(xplane.top_ops(tr))
    assert top == pytest.approx({"fusion.2": 3000e-9, "dot.3": 3200e-9,
                                 "add.1": 700e-9})


def test_gaps_attributed_to_host_activity(tr):
    phase, cuts = run.phases("_scan_iterations")
    got = dict(xplane.attributed_gaps(tr, phase, cuts(tr)))
    assert got == pytest.approx({
        # [0, 500), [6500, 6800), [9500, 9800)
        "between calls": 500e-9 + 300e-9 + 300e-9,
        # [500, 1000), [1500, 2000), [6800, 7000), [7200, 8000)
        "call, before the loop program": 2000e-9,
        # [6000, 6500), [9000, 9500)
        "call, after the loop program": 1000e-9,
    })


def test_metric_readers_on_the_trace(tr, tiny_catalog):
    from types import SimpleNamespace

    from benchmarks.chip import counts
    ctx = SimpleNamespace(trace=tr, xplane=xplane, counts=counts,
                          cfg=tiny_catalog.config("tiny"),
                          mix={"loop_program": "_scan_iterations"},
                          work=100, device_kind="TPU v5 lite")
    read = {n: tiny_catalog.reader(n).read(ctx) for n in (
        "idle_share.steps", "step_device_ms", "setup_ms.fit",
        "setup_programs.fit", "step_mfu")}
    assert read["idle_share.steps"] == pytest.approx(41.0)
    assert read["step_device_ms"] == pytest.approx(5000e-6 / 100)
    assert read["setup_ms.fit"] == pytest.approx(1350e-6)
    assert read["setup_programs.fit"] == 1
    least, _ = counts.least_step_s(ctx.cfg, "TPU v5 lite")
    assert read["step_mfu"] == pytest.approx(100 * least / (10000e-9 / 100))


def test_no_device_plane_gives_no_idle_share():
    cpu_only = xplane.Trace({}, {}, [("bench:window", 0.0, 10.0)])
    assert xplane.idle_share(cpu_only) is None
    assert xplane.top_ops(cpu_only) == []


def test_self_time_leaves_out_nested_ops():
    events = [("%while.6 = (s32[]) while(...)", 0, 100),
              ("%fusion.1 = s32[4]{0} fusion(...)", 10, 40),
              ("%add.2 = s32[4]{0} add(...)", 50, 60),
              ("%copy.3 = s32[4]{0} copy(...)", 120, 130)]
    assert dict(xplane.self_times(events)) == {
        events[0][0]: 60, events[1][0]: 30, events[2][0]: 10,
        events[3][0]: 10}
    assert xplane.short_name(events[1][0]) == "%fusion.1 fusion s32[4]"
    assert xplane.short_name(events[0][0]) == "%while.6 while tuple"
