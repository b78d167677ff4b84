"""The setup program's encode time and roofline share on a synthesised
trace, and setup's operation and byte count, checked by hand."""

from types import SimpleNamespace

import pytest

from benchmarks.chip import counts, setup_counts, xplane

ENCODE, ROOFLINE = "setup_encode_ms.fit", "setup_roofline.fit"

BODY = "jit(_setup_program)/while/body"
SETUP_HLO = f"""HloModule jit__setup_program, is_scheduled=true

%body (p: s32[4]) -> s32[4] {{
  %p = s32[4]{{0}} parameter(0)
  %fusion.1 = s32[4]{{0}} fusion(s32[4]{{0}} %p), kind=kLoop, calls=%f.1, \
metadata={{op_name="{BODY}/copml.setup.share/add" stack_frame_id=1}}
  %fusion.2 = s32[4]{{0}} fusion(s32[4]{{0}} %fusion.1), kind=kLoop, \
calls=%f.2, metadata={{op_name="{BODY}/copml.setup.encode/dot_general"}}
  ROOT %fusion.4 = s32[4]{{0}} fusion(s32[4]{{0}} %fusion.2), kind=kLoop, \
calls=%f.4, metadata={{op_name="{BODY}/copml.setup.xty/dot_general"}}
}}

ENTRY %main.6 (x: s32[4]) -> s32[4] {{
  %x = s32[4]{{0}} parameter(0)
  %while.3 = (s32[]) while(), body=%body, \
metadata={{op_name="jit(_setup_program)/while"}}
  ROOT %fusion.5 = s32[4]{{0}} fusion(s32[4]{{0}} %x), kind=kLoop, \
calls=%f.5, metadata={{op_name="jit(_setup_program)/copml.setup.xty/sub"}}
}}
"""

# one device, two fits, times in ns: each runs the setup program (a while
# of two chunks, then the degree reduction) and the loop program, whose own
# `fusion.2` is another program's instruction and must not count
OPS = [("%while.3 = (s32[]) while()", 0, 900),
       ("%fusion.1 = s32[4]{0} fusion()", 0, 100),
       ("%fusion.2 = s32[4]{0} fusion()", 100, 350),
       ("%fusion.4 = s32[4]{0} fusion()", 350, 400),
       ("%fusion.1 = s32[4]{0} fusion()", 400, 500),
       ("%fusion.2 = s32[4]{0} fusion()", 500, 800),
       ("%fusion.4 = s32[4]{0} fusion()", 800, 850),
       ("%fusion.5 = s32[4]{0} fusion()", 900, 1000),
       ("%fusion.2 = s32[4]{0} fusion()", 1000, 4000),
       ("%while.3 = (s32[]) while()", 5000, 5700),
       ("%fusion.2 = s32[4]{0} fusion()", 5000, 5400),
       ("%fusion.5 = s32[4]{0} fusion()", 5700, 6000),
       ("%fusion.2 = s32[4]{0} fusion()", 6000, 9000)]
PROGRAMS = [("jit__setup_program(3)", 0, 1000),
            ("jit__scan_iterations(7)", 1000, 4000),
            ("jit__setup_program(3)", 5000, 6000),
            ("jit__scan_iterations(7)", 6000, 9000)]
TINY = {"n_clients": 7, "k": 2, "t": 1, "m": 16, "d": 24, "mpc_mul": "bh08"}


def trace_of(ops, programs) -> xplane.Trace:
    dev = "/device:TPU:0"
    return xplane.Trace(ops={dev: ops} if ops else {},
                        programs={dev: programs} if programs else {},
                        annotations=[("bench:window", 0.0, 10000.0)])


def ctx_for(trace, work=2):
    return SimpleNamespace(trace=trace, xplane=xplane, counts=counts,
                           work=work, cfg=TINY, device_kind="TPU v5 lite",
                           mix={"loop_program": "_scan_iterations",
                                "iters": 2})


@pytest.fixture
def setup_hlo(monkeypatch):
    from benchmarks.chip import scopes
    held = [SETUP_HLO]
    monkeypatch.setattr(scopes, "program_hlo",
                        lambda program: list(held) if "setup" in program
                        else [])
    return held


def test_encode_ms_per_fit_by_hand(tiny_catalog, setup_hlo):
    got = tiny_catalog.reader(ENCODE).read(ctx_for(trace_of(OPS, PROGRAMS)))
    # fusion.2 inside the setup program: 250 + 300 + 400 ns over 2 fits
    assert got == pytest.approx(475e-6)


@pytest.mark.parametrize("held,ops,work", [
    ([SETUP_HLO.replace("copml.setup.encode", "other")], OPS, 2),
    ([], OPS, 2),
    ([SETUP_HLO, SETUP_HLO], OPS, 2),
    ([SETUP_HLO], [], 2),
    ([SETUP_HLO], OPS, 0),
], ids=["unscoped", "absent", "ambiguous", "no_device", "no_work"])
def test_encode_ms_finds_nothing_to_read(tiny_catalog, setup_hlo, held,
                                         ops, work):
    setup_hlo[:] = held
    ctx = ctx_for(trace_of(ops, PROGRAMS if ops else []), work)
    assert tiny_catalog.reader(ENCODE).read(ctx) is None


def test_setup_macs_equal_a_hand_count():
    # N=3, K=2, T=1, m=3 (m/K rounds up to 2), d=5
    assert setup_counts.setup_macs(3, 2, 1, 3, 5) == {
        "share": 3 * 1 * 3 * (5 + 1), "masks": 3 * 1 * 1 * 2 * 5,
        "encode": 3 * 3 * 3 * 2 * 5, "reconstruct": 3 * 2 * 2 * 5,
        "xty": 3 * 3 * 5, "reduce": (9 + 2 + 1) * 5, "model": 3 * 1 * 5,
        "total": 534}
    # X and y read, X~ written, X^T y and model shares written, 26 bits
    assert setup_counts.setup_bytes(3, 2, 3, 5) == (3 * 6 + 3 * 2 * 5
                                                     + 2 * 3 * 5) * 26 / 8


def test_paper_case2_setup_is_the_encode():
    macs = setup_counts.setup_macs(50, 10, 7, 9019, 3073)
    assert macs["encode"] == 50 * 50 * 17 * 902 * 3073      # 1.18e11
    assert macs["encode"] / macs["total"] > 0.85
    least, bound = setup_counts.least_setup_s(
        {"n_clients": 50, "k": 10, "t": 7, "m": 9019, "d": 3073,
         "mpc_mul": "bh08"}, "TPU v5 lite")
    assert bound == "compute"
    assert least == 2 * macs["total"] / 393e12


def test_roofline_by_hand(tiny_catalog):
    got = tiny_catalog.reader(ROOFLINE).read(ctx_for(trace_of(OPS,
                                                              PROGRAMS)))
    macs = setup_counts.setup_macs(7, 2, 1, 16, 24)["total"]
    assert macs == 38488
    least = (16 * 25 + 7 * 8 * 24 + 2 * 7 * 24) * 26 / 8 / 819e9
    assert least > 2 * macs / 393e12                         # bytes bind
    # 1000 ns of setup program per fit
    assert got == pytest.approx(100 * least / 1e-6)


@pytest.mark.parametrize("programs,work", [
    ([("jit_share(2)", 0, 300), ("jit__scan_iterations(7)", 1000, 4000)], 2),
    (PROGRAMS, 0),
], ids=["op_by_op_setup", "no_work"])
def test_roofline_finds_nothing_to_read(tiny_catalog, programs, work):
    ctx = ctx_for(trace_of(OPS, programs), work)
    assert tiny_catalog.reader(ROOFLINE).read(ctx) is None


def test_other_reductions_are_refused():
    with pytest.raises(ValueError, match="BH08"):
        setup_counts.least_setup_s(dict(TINY, mpc_mul="bgw"), "TPU v5 lite")
