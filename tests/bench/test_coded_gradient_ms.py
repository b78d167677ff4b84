"""The `field.coded_gradient` reader on a synthesised trace and loop HLO,
checked by hand: the scope nested under `copml.fused_step` (bare, under
vmap, and under a further scope) is read from the loop program alone, and
`fused_step_ms.fit` still files the same ops under its own scope."""

from types import SimpleNamespace

import pytest

from benchmarks.chip import scopes, xplane

READER = "coded_gradient_ms.fit"
SETUP = "jit(_setup_program)"
LOOP = "jit(_scan_iterations)/while/body/closed_call"


def hlo(module: str, ops: dict) -> str:
    lines = "\n".join(
        f'  %{name} = s32[4]{{0}} custom-call(s32[4]{{0}} %p), '
        f'metadata={{op_name="{op_name}"}}' for name, op_name in ops.items())
    return f"HloModule {module}\n\nENTRY %main (p: s32[4]) -> s32[4] {{\n" \
        f"  %p = s32[4]{{0}} parameter(0)\n{lines}\n}}\n"


LOOP_HLO = hlo("jit__scan_iterations", {
    # Phase 3's kernel inside the fused step
    "coded_gradient.1": f"{LOOP}/copml.fused_step/field.coded_gradient"
                        "/jit(coded_gradient)/pallas_call",
    # under vmap, and with a scope of its own inside
    "coded_gradient.2": f"{LOOP}/copml.fused_step"
                        "/vmap(field.coded_gradient)/jit(coded_gradient)"
                        "/pallas_call",
    "transpose.3": f"{LOOP}/copml.fused_step/field.coded_gradient"
                   "/inner/transpose",
    # the fused step's Phase 4, outside the scope
    "fusion.4": f"{LOOP}/copml.fused_step/add",
    # a component that only contains the name is another scope
    "fusion.5": f"{LOOP}/copml.fused_step/field.coded_gradient_x/add",
    "fusion.6": f"{LOOP}/copml.step_rand/mul",
})
# the setup program never holds Phase 3; a scope there is not read
SETUP_HLO = hlo("jit__setup_program", {
    "coded_gradient.1": f"{SETUP}/field.coded_gradient/pallas_call"})

LOOP_OPS = [("coded_gradient.1", 0, 400), ("coded_gradient.2", 400, 500),
            ("transpose.3", 500, 550), ("fusion.4", 550, 700),
            ("fusion.5", 700, 800), ("fusion.6", 800, 1000)]


def two_fits() -> xplane.Trace:
    """One device, two fits, times in ns: each runs the setup program, then
    the loop program; instruction names repeat across the two."""
    ops, programs = [], []
    for start in (0, 3000):
        programs += [("jit__setup_program(3)", start, start + 1000),
                     ("jit__scan_iterations(7)", start + 1000, start + 3000)]
        ops.append(("%coded_gradient.1 = s32[4]{0} custom-call()", start,
                    start + 900))
        ops += [(f"%{n} = s32[4]{{0}} custom-call()", start + 1000 + s,
                 start + 1000 + e) for n, s, e in LOOP_OPS]
    return xplane.Trace(ops={"/device:TPU:0": ops},
                        programs={"/device:TPU:0": programs},
                        annotations=[("bench:window", 0.0, 10000.0)])


def ctx_for(trace, work=2):
    return SimpleNamespace(trace=trace, xplane=xplane, work=work,
                           mix={"loop_program": "_scan_iterations",
                                "iters": 2})


def held(texts: dict):
    return lambda program: [t for name, t in texts.items() if program in name]


def test_coded_gradient_read_under_the_fused_step(tiny_catalog, monkeypatch):
    monkeypatch.setattr(scopes, "program_hlo", held(
        {"_setup_program": SETUP_HLO, "_scan_iterations": LOOP_HLO}))
    tr = two_fits()
    got = tiny_catalog.reader(READER).read(ctx_for(tr))
    # per fit: the loop's 400 + 100 + 50 ns, nothing of setup's 900
    assert got == pytest.approx(550e-6)
    assert scopes.op_scopes(LOOP_HLO)["coded_gradient.2"] == \
        "copml.fused_step"
    # the fused step's time per iteration still holds the kernel:
    # 400 + 100 + 50 + 150 + 100 ns per fit over 2 iterations
    fused = tiny_catalog.reader("fused_step_ms.fit").read(ctx_for(tr))
    assert fused == pytest.approx(800e-6 / 2)


@pytest.mark.parametrize("texts", [
    {"_setup_program": SETUP_HLO,
     "_scan_iterations": LOOP_HLO.replace("field.coded_gradient", "other")},
    {"_scan_iterations": LOOP_HLO, "_scan_iterations_2": LOOP_HLO},
    {},
], ids=["no_kernel_in_the_loop", "two_loop_programs", "no_programs_held"])
def test_coded_gradient_finds_nothing_to_read(tiny_catalog, monkeypatch,
                                              texts):
    monkeypatch.setattr(scopes, "program_hlo", held(texts))
    assert tiny_catalog.reader(READER).read(ctx_for(two_fits())) is None


def test_coded_gradient_empty_trace(tiny_catalog, monkeypatch):
    monkeypatch.setattr(scopes, "program_hlo", held(
        {"_scan_iterations": LOOP_HLO}))
    empty = xplane.Trace(ops={}, programs={}, annotations=[])
    assert tiny_catalog.reader(READER).read(ctx_for(empty)) is None
