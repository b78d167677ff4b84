"""The program's own spans and scopes: host spans of one fit in the
profiler's trace, device scopes in the compiled setup's and loop's op
metadata, and nothing recorded outside a profiler session."""

import glob
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import api
from repro.core import protocol, spans

FIT_SPANS = {"repro:fit", "repro:setup", "repro:loop", "repro:finish"}
SETUP_SCOPES = ("copml.setup.share", "copml.setup.encode", "copml.setup.xty")
LOOP_SCOPES = ("copml.encode_model", "copml.step_rand", "copml.fused_step")


def host_spans(log_dir) -> list:
    """[(name, start, end, args)] of the `repro:*` host events."""
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("repro:")]


@pytest.fixture(scope="module")
def traced_fits(tmp_path_factory):
    """The host spans of two traced fits, by fit id."""
    log_dir = tmp_path_factory.mktemp("trace")
    api.fit("smoke", "copml", "jit", iters=2, history=False)   # compile
    with jax.profiler.trace(str(log_dir)):
        for _ in range(2):
            api.fit("smoke", "copml", "jit", iters=2, history=False)
    fits = {}
    for name, s, e, args in host_spans(log_dir):
        fits.setdefault(args["fit"], []).append((name, s, e, args))
    return fits


def test_each_fit_has_its_own_id(traced_fits):
    assert len(traced_fits) == 2 and 0 not in traced_fits
    for spans_of_fit in traced_fits.values():
        assert sorted(n for n, *_ in spans_of_fit) == sorted(FIT_SPANS)


def test_every_span_nests_in_its_fit(traced_fits):
    wl = api.get_workload("smoke")
    for fit_id, spans_of_fit in traced_fits.items():
        by_name = {n: (s, e, a) for n, s, e, a in spans_of_fit}
        lo, hi, _ = by_name["repro:fit"]
        for name, (s, e, _) in by_name.items():
            assert lo <= s <= e <= hi, name
        assert by_name["repro:loop"][2] == {"fit": fit_id, "iters": 2}
        setup = by_name["repro:setup"]
        assert setup[2] == {"fit": fit_id, "m": wl.m, "d": wl.d,
                            "n": wl.n_clients, "chunks": 1}
        assert setup[1] <= by_name["repro:loop"][0]


def test_the_loop_program_names_its_scopes():
    wl = api.get_workload("smoke")
    proto = protocol.Copml(wl.cfg, wl.m, wl.d)
    cx, cy = wl.client_data()
    state = proto.setup(jax.random.PRNGKey(0), cx, cy)
    hlo = protocol._scan_iterations.lower(
        proto, jax.random.PRNGKey(1), state, 2, None, False,
        None).compile().as_text()
    for scope in LOOP_SCOPES:
        assert f"/{scope}/" in hlo, scope


def test_the_setup_program_names_its_scopes():
    wl = api.get_workload("smoke")
    x, y, _, _ = wl.data()
    hlo = protocol._setup_program.lower(
        wl.cfg, wl.objective, wl.m, wl.d,
        jax.random.PRNGKey(0), x, y).compile().as_text()
    for scope in SETUP_SCOPES:
        assert f"/{scope}/" in hlo, scope
    assert not any(f"/{scope}/" in hlo for scope in LOOP_SCOPES)


@pytest.mark.parametrize("m,rows,chunks", [(64, 16, 1), (60, 5, 3)])
def test_the_setup_span_counts_the_chunks_the_program_streams(
        monkeypatch, tmp_path, m, rows, chunks):
    """`repro:setup`'s `chunks` is the trip count of the setup program's
    encode loop (no loop for one chunk), at shapes no other test compiles
    and an encode budget of `rows` rows of a block's m/K."""
    cfg = api.get_workload("smoke").cfg                  # N = 13, K = 4
    d = 10
    monkeypatch.setattr(protocol, "SETUP_ENCODE_CHUNK_BYTES",
                        rows * cfg.n_clients ** 2 * d * 4)
    rng = np.random.default_rng(chunks)
    x = rng.uniform(-1, 1, (m, d)).astype(np.float32)
    y = (rng.uniform(size=m) > 0.5).astype(np.float32)
    proto = protocol.Copml(cfg, m, d)
    with jax.profiler.trace(str(tmp_path)):
        proto.setup(jax.random.PRNGKey(0), [x], [y])
    (args,) = [a for n, _, _, a in host_spans(tmp_path)
               if n == "repro:setup"]
    jaxpr = str(protocol._setup_program.trace(
        cfg, proto.obj, m, d, jax.random.PRNGKey(0), x, y).jaxpr)
    loops = re.findall(r"scan\[|length=(\d+)", jaxpr)
    assert args["chunks"] == chunks
    assert [int(n) for n in loops if n] == ([] if chunks == 1 else [chunks])


def test_a_span_outside_a_session_records_nothing(tmp_path):
    outside = spans.span("outside", x=1)
    assert isinstance(outside, jax.profiler.TraceAnnotation)
    with outside:
        pass
    late = spans.span("late")
    late.__enter__()                 # opened before the session starts
    with jax.profiler.trace(str(tmp_path)):
        late.__exit__(None, None, None)
        with spans.span("inside"):
            pass
    assert [(n, a) for n, _, _, a in host_spans(tmp_path)] == \
        [("repro:inside", {"fit": 0})]
