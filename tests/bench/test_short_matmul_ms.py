"""The `field.short_matmul` reader on a synthesised trace and setup and loop
HLO, checked by hand: the scope nested under the `copml.*` scopes (and
under vmap) is read from both programs, and the `copml.*` readers still
file the same ops under their own scopes."""

from types import SimpleNamespace

import pytest

from benchmarks.chip import scopes, xplane

READER = "short_matmul_ms.fit"
SETUP = "jit(_setup_program)"
LOOP = "jit(_scan_iterations)/while/body/closed_call"


def hlo(module: str, ops: dict) -> str:
    lines = "\n".join(
        f'  %{name} = s32[4]{{0}} custom-call(s32[4]{{0}} %p), '
        f'metadata={{op_name="{op_name}"}}' for name, op_name in ops.items())
    return f"HloModule {module}\n\nENTRY %main (p: s32[4]) -> s32[4] {{\n" \
        f"  %p = s32[4]{{0}} parameter(0)\n{lines}\n}}\n"


SETUP_HLO = hlo("jit__setup_program", {
    # the LCC encode under vmap, inside setup's chunk loop
    "short_modmatmul.1": f"{SETUP}/while/body/closed_call/copml.setup.encode"
                         "/vmap(field.short_matmul)/jit(short_modmatmul)"
                         "/pallas_call",
    "fusion.2": f"{SETUP}/while/body/closed_call/copml.setup.encode"
                "/concatenate",
    "short_modmatmul.3": f"{SETUP}/copml.setup.share/field.short_matmul"
                         "/jit(short_modmatmul)/pallas_call",
    # the model's initial sharing, under no copml scope
    "short_modmatmul.4": f"{SETUP}/field.short_matmul/jit(short_modmatmul)"
                         "/pallas_call",
    # a component that only contains the name is another scope
    "fusion.5": f"{SETUP}/copml.setup.encode/field.short_matmul_x/add",
})
LOOP_HLO = hlo("jit__scan_iterations", {
    "short_modmatmul.1": f"{LOOP}/copml.encode_model/vmap(field.short_matmul)"
                         "/jit(short_modmatmul)/pallas_call",
    "fusion.2": f"{LOOP}/copml.step_rand/mul",
})

# one device, two fits, times in ns: each fit runs the setup program, then
# the loop program; instruction names repeat across the two programs
SETUP_OPS = [("short_modmatmul.1", 0, 400), ("fusion.2", 400, 500),
             ("short_modmatmul.3", 500, 700), ("short_modmatmul.4", 700, 800),
             ("fusion.5", 800, 900)]
LOOP_OPS = [("short_modmatmul.1", 0, 300), ("fusion.2", 300, 1000)]


def two_fits() -> xplane.Trace:
    ops, programs = [], []
    for start in (0, 3000):
        programs += [("jit__setup_program(3)", start, start + 1000),
                     ("jit__scan_iterations(7)", start + 1000, start + 3000)]
        ops += [(f"%{n} = s32[4]{{0}} custom-call()", start + s, start + e)
                for n, s, e in SETUP_OPS]
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


def test_short_matmul_read_under_the_copml_scopes(tiny_catalog, monkeypatch):
    monkeypatch.setattr(scopes, "program_hlo", held(
        {"_setup_program": SETUP_HLO, "_scan_iterations": LOOP_HLO}))
    tr = two_fits()
    got = tiny_catalog.reader(READER).read(ctx_for(tr))
    # per fit: setup's 400 + 200 + 100 ns and the loop's 300 ns
    assert got == pytest.approx(1000e-6)
    # the encode scope still holds its kernel: 400 + 100 + 100 ns per fit
    encode = tiny_catalog.reader("setup_encode_ms.fit").read(ctx_for(tr))
    assert encode == pytest.approx(600e-6)
    assert scopes.op_scopes(SETUP_HLO) == {
        "short_modmatmul.1": "copml.setup.encode",
        "fusion.2": "copml.setup.encode",
        "short_modmatmul.3": "copml.setup.share",
        "fusion.5": "copml.setup.encode"}
    # encode_model's time per iteration holds the loop's kernel
    model = tiny_catalog.reader("encode_model_ms.fit").read(ctx_for(tr))
    assert model == pytest.approx(300e-6 / 2)


@pytest.mark.parametrize("texts", [
    {"_setup_program": SETUP_HLO.replace("field.short_matmul", "other"),
     "_scan_iterations": LOOP_HLO.replace("field.short_matmul", "other")},
    {},
], ids=["no_short_products", "no_programs_held"])
def test_short_matmul_finds_nothing_to_read(tiny_catalog, monkeypatch, texts):
    monkeypatch.setattr(scopes, "program_hlo", held(texts))
    assert tiny_catalog.reader(READER).read(ctx_for(two_fits())) is None


def test_short_matmul_one_program_alone(tiny_catalog, monkeypatch):
    """A program without the scope adds nothing; the other is still read."""
    monkeypatch.setattr(scopes, "program_hlo", held(
        {"_setup_program": SETUP_HLO,
         "_scan_iterations": LOOP_HLO.replace("field.short_matmul", "x")}))
    got = tiny_catalog.reader(READER).read(ctx_for(two_fits()))
    assert got == pytest.approx(700e-6)
