"""The per-scope readers on a small synthesised XSpace and loop HLO, checked
by hand, and the loop program's scopes found in a traced run's process."""

import time
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

from benchmarks.chip import run, scopes, xplane

# one device, times in ns (offsets in ps in the proto):
#   host:  bench:window [0, 10000)
#   programs: setup_op [0, 1000), _scan_iterations [1000, 5000) and
#             [6000, 8000)
#   ops: fusion.1 [0, 1000) in setup_op (an instruction of another
#        program, which the loop's HLO must not claim); in the first loop
#        while.9 [1000, 5000) holding fusion.1 [1100, 2100), fusion.2
#        [2100, 3600), custom-call.3 [3600, 4600), copy.4 [4600, 4800);
#        in the second while.9 [6000, 8000) holding fusion.1 [6000, 6500),
#        fusion.2 [6500, 7000), custom-call.3 [7000, 7900)
XSPACE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 2000000 } }
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 7 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 1100000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 2100000 duration_ps: 1500000 }
    events { metadata_id: 5 offset_ps: 3600000 duration_ps: 1000000 }
    events { metadata_id: 6 offset_ps: 4600000 duration_ps: 200000 }
    events { metadata_id: 7 offset_ps: 6000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 500000 }
    events { metadata_id: 4 offset_ps: 6500000 duration_ps: 500000 }
    events { metadata_id: 5 offset_ps: 7000000 duration_ps: 900000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_setup_op(1)" } }
  event_metadata { key: 2 value { id: 2 name: "jit__scan_iterations(7)" } }
  event_metadata { key: 3 value { id: 3 name:
    "%fusion.1 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop, calls=%f.1" } }
  event_metadata { key: 4 value { id: 4 name:
    "%fusion.2 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop, calls=%f.2" } }
  event_metadata { key: 5 value { id: 5 name:
    "%custom-call.3 = s32[4]{0} custom-call(s32[4]{0} %fusion.2)" } }
  event_metadata { key: 6 value { id: 6 name: "%copy.4 = s32[4]{0} copy()" } }
  event_metadata { key: 7 value { id: 7 name: "%while.9 = (s32[]) while()" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench:window" } }
}
"""

BODY = "jit(_scan_iterations)/while/body/closed_call"
LOOP_HLO = f"""HloModule jit__scan_iterations, is_scheduled=true

%body (p: s32[4]) -> s32[4] {{
  %p = s32[4]{{0}} parameter(0)
  %fusion.1 = s32[4]{{0}} fusion(s32[4]{{0}} %p), kind=kLoop, calls=%f.1, \
metadata={{op_name="{BODY}/copml.encode_model/add" stack_frame_id=1}}
  %fusion.2 = s32[4]{{0}} fusion(s32[4]{{0}} %p), kind=kLoop, calls=%f.2, \
metadata={{op_name="{BODY}/copml.step_rand/mul" stack_frame_id=2}}
  ROOT %custom-call.3 = s32[4]{{0}} custom-call(s32[4]{{0}} %fusion.2), \
metadata={{op_name="{BODY}/copml.fused_step/pallas_call"}}
}}

ENTRY %main.5 (x: s32[4]) -> s32[4] {{
  %x = s32[4]{{0}} parameter(0), metadata={{op_name="state.w_shares"}}
  %copy.4 = s32[4]{{0}} copy(s32[4]{{0}} %x)
  ROOT %while.9 = (s32[]) while(), body=%body, \
metadata={{op_name="jit(_scan_iterations)/while"}}
}}
"""

READERS = ("encode_model_ms.fit", "step_rand_ms.fit", "fused_step_ms.fit")


@pytest.fixture(scope="module")
def tr():
    return xplane.from_planes(ProfileData.from_text_proto(XSPACE).planes)


def ctx_for(trace, work=1):
    # one traced fit of two iterations
    return SimpleNamespace(trace=trace, xplane=xplane, work=work,
                           mix={"loop_program": "_scan_iterations",
                                "iters": 2})


def test_scopes_from_op_name_metadata():
    assert scopes.op_scopes(LOOP_HLO) == {
        "fusion.1": "copml.encode_model", "fusion.2": "copml.step_rand",
        "custom-call.3": "copml.fused_step"}
    assert scopes.instruction("%fusion.610 = s32[50,153650]{1,0} fusion()") \
        == "fusion.610"
    assert scopes.instruction("add.1") == "add.1"


def test_self_time_by_scope_inside_the_loop_program(tr):
    got = scopes.scope_self_ns(tr, "_scan_iterations",
                               scopes.op_scopes(LOOP_HLO))
    assert got == pytest.approx({
        "copml.encode_model": 1000 + 500,   # not setup_op's fusion.1
        "copml.step_rand": 1500 + 500,
        "copml.fused_step": 1000 + 900,
        # while.9's self time (300 + 100) and copy.4, which has no scope
        None: 300 + 100 + 200})


def test_readers_by_hand(tr, tiny_catalog, monkeypatch):
    monkeypatch.setattr(scopes, "program_hlo", lambda program: [LOOP_HLO])
    read = {n: tiny_catalog.reader(n).read(ctx_for(tr)) for n in READERS}
    # ms per iteration, over 1 fit x 2 iterations
    assert read == pytest.approx({"encode_model_ms.fit": 1500e-6 / 2,
                                  "step_rand_ms.fit": 2000e-6 / 2,
                                  "fused_step_ms.fit": 1900e-6 / 2})
    # the three cover the loop program's time but for its 600 ns unscoped
    loop_ms = 1e3 * xplane.program_time_s(tr, "_scan_iterations")
    assert sum(read.values()) * 2 == pytest.approx(loop_ms - 600e-6)


@pytest.mark.parametrize("hlo", [
    [LOOP_HLO.replace("copml.", "other.")],   # a program with no scopes
    [],                                       # no loop program held
    [LOOP_HLO, LOOP_HLO],                     # two: which ran is unknown
], ids=["unscoped", "absent", "ambiguous"])
def test_readers_find_nothing_to_read(tr, tiny_catalog, monkeypatch, hlo):
    monkeypatch.setattr(scopes, "program_hlo", lambda program: hlo)
    for name in READERS:
        assert tiny_catalog.reader(name).read(ctx_for(tr)) is None


def test_no_device_plane_gives_nothing(tiny_catalog, monkeypatch):
    monkeypatch.setattr(scopes, "program_hlo", lambda program: [LOOP_HLO])
    cpu_only = xplane.Trace({}, {}, [("bench:window", 0.0, 10.0)])
    for name in READERS:
        assert tiny_catalog.reader(name).read(ctx_for(cpu_only)) is None


def test_a_traced_fit_leaves_the_scoped_loop_program(tiny_catalog):
    """A traced tiny.fit run through the harness: on the CPU no device
    plane holds the ops, so the readers report nothing and raise nothing;
    the loop program the process still holds carries all three scopes."""
    res = run.run_cell(tiny_catalog, tiny_catalog.cell("tiny.fit"),
                       2**32 + 13, 1.0, True, time.perf_counter())
    assert res["correct"], res["checks"]
    assert not set(READERS) & set(res["metrics"])
    texts = scopes.program_hlo("_scan_iterations")
    assert texts
    for text in texts:
        assert set(scopes.op_scopes(text).values()) == {
            "copml.encode_model", "copml.step_rand", "copml.fused_step"}
