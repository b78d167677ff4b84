"""The setup program's device time per fit on a synthesised trace, checked
by hand, and nothing read where setup ran op by op or no device ran."""

from types import SimpleNamespace

import pytest

from benchmarks.chip import xplane

READER = "setup_device_ms.fit"


def trace_of(programs: dict) -> xplane.Trace:
    return xplane.Trace(ops={dev: [("%fusion.1 = s32[4]{0} fusion()", s, e)
                                   for _, s, e in evs]
                             for dev, evs in programs.items()},
                        programs=programs,
                        annotations=[("bench:window", 0.0, 10000.0)])


def ctx_for(trace, work):
    return SimpleNamespace(trace=trace, xplane=xplane, work=work,
                           mix={"loop_program": "_scan_iterations"})


# two fits on two devices, times in ns: each fit runs the setup program
# (400 ns, then 600 ns on device 0; 500 and 500 ns on device 1), a small
# eager program and the loop; one setup execution starts after the window
TWO_FITS = {
    "/device:TPU:0": [("jit__setup_program(3)", 0, 400),
                      ("jit_fold_in(1)", 400, 450),
                      ("jit__scan_iterations(7)", 500, 4000),
                      ("jit__setup_program(3)", 5000, 5600),
                      ("jit__scan_iterations(7)", 5700, 9000),
                      ("jit__setup_program(3)", 10000, 10900)],
    "/device:TPU:1": [("jit__setup_program(3)", 0, 500),
                      ("jit__scan_iterations(7)", 500, 4000),
                      ("jit__setup_program(3)", 5000, 5500),
                      ("jit__scan_iterations(7)", 5700, 9000)],
}


def test_device_ms_per_fit_by_hand(tiny_catalog):
    got = tiny_catalog.reader(READER).read(ctx_for(trace_of(TWO_FITS), 2))
    # (400 + 600 + 500 + 500) ns over 2 devices and 2 fits
    assert got == pytest.approx(500e-6)


@pytest.mark.parametrize("programs", [
    {"/device:TPU:0": [("jit_concatenate(1)", 0, 100),
                       ("jit_share(2)", 100, 300),
                       ("jit__scan_iterations(7)", 500, 4000)]},
    {},
], ids=["op_by_op_setup", "no_device"])
def test_nothing_to_read(tiny_catalog, programs):
    got = tiny_catalog.reader(READER).read(ctx_for(trace_of(programs), 1))
    assert got is None


def test_no_work_reads_nothing(tiny_catalog):
    assert tiny_catalog.reader(READER).read(
        ctx_for(trace_of(TWO_FITS), 0)) is None
