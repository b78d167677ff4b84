"""proc engine conformance: real OS processes + sockets, bit-exact COPML.

The goldens are the SAME pins (tests/goldens.py: smoke, key=PRNGKey(0),
10 iterations) test_api.py holds the jit engine to.  The proc
engine must reproduce them over real localhost TCP with measured (not
modeled) communication, and a timeout-induced straggler run must decode
from the surviving R-subset to the SAME bits (LCC decode invariance under
real network timing).
"""

import hashlib
import io
import os
import subprocess
import sys
from contextlib import redirect_stdout

import numpy as np
import pytest

from repro import api
from repro.analysis import choreography
from repro.api import engine as engine_mod

from goldens import GOLDEN_HIST_SHA, GOLDEN_SHARES_SHA, GOLDEN_W

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MEASURED_PHASES = {"setup", "encode", "exchange", "trunc_open"}


def _sha(arr, dtype):
    return hashlib.sha256(np.asarray(arr, dtype).tobytes()).hexdigest()


# ------------------------------------------------------ golden conformance

def test_proc_engine_matches_jit_golden():
    """api.fit over proc:4 -- 4 worker subprocesses, real sockets -- lands
    on the exact pinned bits (tests/goldens.py)."""
    res = api.fit("smoke", "copml", "proc:4", key=0, iters=10, history=True)
    np.testing.assert_array_equal(
        np.asarray(res.weights, np.float64), np.asarray(GOLDEN_W))
    assert _sha(res.state.w_shares, np.int32) == GOLDEN_SHARES_SHA
    assert _sha(res.history, np.float32) == GOLDEN_HIST_SHA
    assert res.engine == "proc:4"

    mc = res.measured_comm
    assert mc is not None and mc["procs"] == 4 and mc["iters"] == 10
    # measured, not modeled: real wire bytes in every protocol phase
    assert MEASURED_PHASES <= set(mc["bytes_by_phase"])
    assert all(v > 0 for v in mc["bytes_by_phase"].values())
    assert mc["total_bytes"] == sum(mc["bytes_by_phase"].values())
    assert MEASURED_PHASES - {"setup"} <= set(mc["seconds_by_phase"])
    assert mc["wall_s"] > 0 and mc["setup_wall_s"] > 0
    assert mc["degraded_steps"] == 0          # loopback, no injected delay
    # sent-frame counts are deterministic: they must equal the static
    # choreography budget bit for bit (commlint's COM009 closed forms)
    assert mc["frames_by_phase"] == choreography.frames_by_phase(
        4, 10, history=True)
    assert mc["dropped_frames"] == {}         # nothing stale on loopback
    assert "measured" in res.summary()


def test_proc_straggler_emerges_and_stays_bit_exact():
    """A slow link (not a FaultPlan) makes rank 3 miss the decode
    deadline; the survivors' R-subset decode matches the fault-free jit
    model bit for bit -- LCC decode invariance driven by real timing."""
    ref = api.fit("smoke_straggler", "copml", "jit", key=0, subset="all",
                  history=False)
    net_cfg = api.NetConfig(links=((3, None, 0.35),), decode_timeout_s=0.05)
    res = api.fit("smoke_straggler", "copml",
                  api.EngineSpec("proc", devices=4, net=net_cfg),
                  key=0, subset="all", history=False)
    mc = res.measured_comm
    assert mc["degraded_steps"] >= 1
    # degradation drops frames at the receiver but every frame was still
    # sent: the sent-side budget stays exact while dropped_frames records
    # the stale discards.
    assert mc["frames_by_phase"] == choreography.frames_by_phase(
        mc["procs"], mc["iters"], history=False)
    assert sum(mc["dropped_frames"].values()) >= 1
    np.testing.assert_array_equal(np.asarray(res.weights),
                                  np.asarray(ref.weights))
    np.testing.assert_array_equal(np.asarray(res.state.w_shares),
                                  np.asarray(ref.state.w_shares))


@pytest.mark.slow
def test_proc_multiclass_bit_exact_vs_jit():
    """Nightly: the (d, C) matrix-model path over 4 processes."""
    ref = api.fit("mnist10_like", "copml", "jit", key=0, iters=3,
                  history=False)
    res = api.fit("mnist10_like", "copml", "proc:4", key=0, iters=3,
                  history=False)
    np.testing.assert_array_equal(np.asarray(res.weights),
                                  np.asarray(ref.weights))
    np.testing.assert_array_equal(np.asarray(res.state.w_shares),
                                  np.asarray(ref.state.w_shares))


# ------------------------------------------------------------- spec surface

def test_proc_spec_parsing_and_validation():
    assert api.parse_engine("proc").kind == "proc"
    assert api.parse_engine("proc").label == "proc"
    sp = api.parse_engine("proc:6")
    assert (sp.kind, sp.devices, sp.label) == ("proc", 6, "proc:6")
    assert "proc" in api.ENGINES and "proc" in api.engine_names()
    api.EngineSpec("proc", net=api.NetConfig(latency_s=0.1))   # valid
    with pytest.raises(ValueError, match="takes no net"):
        api.EngineSpec("jit", net=api.NetConfig())
    with pytest.raises(ValueError, match="takes no mesh"):
        api.EngineSpec("proc", mesh=object())
    with pytest.raises(ValueError, match="devices must be"):
        api.parse_engine("proc:0")


def test_proc_rejects_fault_plans():
    """The proc engine has no replay: stragglers come from the network."""
    plan = api.FaultPlan.random(13, 4, seed=0, straggle_p=0.1,
                                min_available=10)
    with pytest.raises(ValueError, match="no FaultPlan replay"):
        api.fit("smoke_straggler", "copml", "proc:4", key=0, faults=plan)


def test_proc_fails_fast_when_coordinator_holds_a_device(monkeypatch):
    """On an accelerator host the coordinator holds the device, so the
    workers could never load it: proc:N refuses at once, naming the
    reason, instead of waiting out the spawn timeout."""
    import time

    from repro.launch.runtime import session
    monkeypatch.setattr(session.jax, "default_backend", lambda: "tpu")
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="holds the tpu device"):
        api.fit("smoke", "copml", "proc:2", key=0, iters=1, history=False)
    assert time.perf_counter() - t0 < 5.0


# ------------------------------------------- CLI listing == engine registry

def _cli_engines_line(out: str) -> list:
    for line in out.splitlines():
        if line.startswith("engines:"):
            return [e.strip() for e in
                    line.split(":", 1)[1].split(",") if e.strip()]
    raise AssertionError(f"no engines line in {out!r}")


def test_cli_listing_matches_registry():
    """repro-fit --list enumerates the LIVE registry, not a hardcoded
    tuple: a kind registered at runtime appears without a CLI edit."""
    from repro.api import cli
    buf = io.StringIO()
    with redirect_stdout(buf):
        cli.main(["--list"])
    assert _cli_engines_line(buf.getvalue()) == list(api.engine_names())

    api.register_engine_kind(engine_mod.EngineKind(
        "testkind", "registered by test_cli_listing_matches_registry"))
    try:
        buf = io.StringIO()
        with redirect_stdout(buf):
            cli.main(["--list"])
        listed = _cli_engines_line(buf.getvalue())
        assert listed == list(api.engine_names())
        assert "testkind" in listed
    finally:
        engine_mod.KINDS.pop("testkind", None)


def test_cli_listing_subprocess_matches_registry():
    """Same check through the real console entry point."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.api.cli", "--list"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert _cli_engines_line(out.stdout) == list(api.engine_names())
