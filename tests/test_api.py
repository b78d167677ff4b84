"""repro.api facade: the (workload, protocol, engine) axes.

The copml goldens (tests/goldens.py) pin the smoke workload's fit -- the
facade must reproduce them bit-for-bit through every engine.
"""

import hashlib
import importlib
import os
import sys

import jax
import numpy as np
import pytest

from repro import api
from repro.core import secure_agg as sa
from repro.core.baselines import MpcBaseline
from repro.core.protocol import Copml

from goldens import GOLDEN_HIST_SHA, GOLDEN_SHARES_SHA, GOLDEN_W

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sha(arr, dtype):
    return hashlib.sha256(np.asarray(arr, dtype).tobytes()).hexdigest()


@pytest.fixture(scope="module")
def copml_jit():
    return api.fit("smoke", "copml", "jit", key=0, iters=10, history=True)


# --------------------------------------------------- copml engine bit-exact


def test_copml_jit_matches_prerefactor_golden(copml_jit):
    res = copml_jit
    np.testing.assert_array_equal(
        np.asarray(res.weights, np.float64), np.asarray(GOLDEN_W))
    assert _sha(res.state.w_shares, np.int32) == GOLDEN_SHARES_SHA
    assert _sha(res.history, np.float32) == GOLDEN_HIST_SHA
    assert res.triple == ("smoke", "copml", "jit")


def test_copml_eager_bit_exact_vs_jit(copml_jit):
    res = api.fit("smoke", "copml", "eager", key=0, iters=10, history=True)
    np.testing.assert_array_equal(res.weights, copml_jit.weights)
    np.testing.assert_array_equal(res.history, copml_jit.history)
    np.testing.assert_array_equal(np.asarray(res.state.w_shares),
                                  np.asarray(copml_jit.state.w_shares))


def test_copml_sharded_matches_prerefactor_golden(copml_jit):
    """The shard_map engine on a 1-device mesh (multi-device parity is the
    slow subprocess test in test_distributed.py)."""
    res = api.fit("smoke", "copml", api.EngineSpec("sharded", devices=1),
                  key=0, iters=10, history=False)
    np.testing.assert_array_equal(res.weights, copml_jit.weights)
    assert _sha(res.state.w_shares, np.int32) == GOLDEN_SHARES_SHA
    assert res.engine == "sharded:1"


# ------------------------------------------------- all protocols, both ways


@pytest.mark.parametrize("protocol", ["copml", "mpc_baseline", "float",
                                      "poly_float", "secure_agg"])
def test_protocol_runs_on_eager_and_jit(protocol):
    """Acceptance grid: 5 protocols x {eager, jit}, one TrainResult schema."""
    results = {}
    for engine in ("eager", "jit"):
        res = api.fit("smoke", protocol, engine, key=0, iters=5)
        assert res.triple == ("smoke", protocol, engine)
        assert res.weights.shape == (12,)
        assert res.history.shape == (5, 12)
        assert res.accuracy.shape == (5,)
        assert 0.0 <= res.final_accuracy <= 1.0
        assert res.wall_time_s > 0
        assert res.iters == 5
        # history rows are snapshots, not views of the trainer's weight
        # buffer: the trajectory must actually move step to step
        assert not np.array_equal(res.history[0], res.history[-1])
        results[engine] = res
    # engines agree on what they computed (bit-exact for the field
    # protocols, float32-vs-float64 tolerance for the float paths) --
    # per step, not just at the end
    np.testing.assert_allclose(results["eager"].weights,
                               results["jit"].weights, atol=1e-5)
    np.testing.assert_allclose(results["eager"].history,
                               results["jit"].history, atol=1e-4)
    # the secured protocols learn the same task: accuracy in family
    assert abs(results["eager"].final_accuracy
               - results["jit"].final_accuracy) <= 0.05


def test_cost_model_attached_per_protocol():
    res_c = api.fit("smoke", "copml", "jit", key=0, iters=5, history=False)
    assert set(res_c.cost) == {"comm_s", "comp_s", "enc_s", "total_s"}
    res_f = api.fit("smoke", "float", "jit", key=0, iters=5, history=False)
    assert res_f.cost is None and res_f.history is None
    # Table I ordering (a PAPER-scale property: at smoke scale the fixed
    # dataset-sharing term dominates): baseline comm >> COPML comm.  The
    # cost models run on shapes only -- no training needed.
    wl = api.get_workload("cifar10_case2")
    cost_c = api.PROTOCOLS["copml"].cost(wl, 50)
    cost_m = api.PROTOCOLS["mpc_baseline"].cost(wl, 50)
    assert cost_m["comm_s"] > cost_c["comm_s"]
    assert cost_m["total_s"] > cost_c["total_s"]


# ------------------------------------------------------- deprecation shims


def test_train_method_shims_warn_and_match_facade():
    wl = api.get_workload("smoke")
    proto = Copml(wl.cfg, wl.m, wl.d)
    cx, cy = wl.client_data()
    key = jax.random.PRNGKey(0)
    res = api.fit("smoke", "copml", "jit", key=0, iters=3, history=False)

    with pytest.warns(DeprecationWarning, match="train_jit is deprecated"):
        st_j, w_j = proto.train_jit(key, cx, cy, 3)
    with pytest.warns(DeprecationWarning, match="train_eager is deprecated"):
        st_e, w_e = proto.train_eager(key, cx, cy, 3)
    with pytest.warns(DeprecationWarning,
                      match="train_sharded is deprecated"):
        st_s, w_s = proto.train_sharded(key, cx, cy, 3,
                                        mesh=None)  # all (1) visible devices
    for w, st in ((w_j, st_j), (w_e, st_e), (w_s, st_s)):
        np.testing.assert_array_equal(np.asarray(w), res.weights)
        np.testing.assert_array_equal(np.asarray(st.w_shares),
                                      np.asarray(res.state.w_shares))


# --------------------------------------- baselines routed through the api


def test_mpc_baseline_api_matches_direct_call():
    wl = api.get_workload("smoke")
    x, y, _, _ = wl.data()
    mb = MpcBaseline(wl.cfg, wl.m, wl.d, groups=3)
    _, w_direct = mb.train(jax.random.PRNGKey(0), x, y, 5)

    res_e = api.fit("smoke", "mpc_baseline", "eager", key=0, iters=5)
    res_j = api.fit("smoke", "mpc_baseline", "jit", key=0, iters=5)
    # same key schedule end-to-end: the facade IS the direct call
    np.testing.assert_array_equal(np.asarray(w_direct), res_e.weights)
    np.testing.assert_array_equal(res_e.weights, res_j.weights)
    assert abs(res_e.final_accuracy - res_j.final_accuracy) < 1e-9


def test_secure_agg_api_matches_direct_call():
    """api.fit('secure_agg') == a hand-rolled loop over
    secure_agg.secure_aggregate with the same per-step fold_in schedule."""
    wl = api.get_workload("smoke")
    cx, cy = wl.client_data()
    cfg = sa.SecureAggConfig(n_clients=wl.n_clients, t=wl.cfg.t)
    xs, ys, mask = sa._padded_clients(cx, cy)
    key = jax.random.PRNGKey(0)
    w = np.zeros(wl.d, np.float32)
    for t in range(5):
        g = np.asarray(sa._client_mean_grads(xs, ys, mask, w))
        grads = [{"g": g[j]} for j in range(cfg.n_clients)]
        mean = sa.secure_aggregate(jax.random.fold_in(key, t), grads, cfg)
        w = w - wl.cfg.eta * np.asarray(mean["g"], np.float32)

    res_e = api.fit("smoke", "secure_agg", "eager", key=0, iters=5)
    res_j = api.fit("smoke", "secure_agg", "jit", key=0, iters=5)
    np.testing.assert_allclose(res_e.weights, w, atol=1e-6)
    np.testing.assert_allclose(res_j.weights, w, atol=1e-5)
    assert abs(res_e.final_accuracy - res_j.final_accuracy) <= 0.05


# ----------------------------------------------------- axes and registries


def test_engine_spec_parsing():
    assert api.parse_engine("eager").kind == "eager"
    assert api.parse_engine("jit").label == "jit"
    sp = api.parse_engine("sharded:4")
    assert (sp.kind, sp.devices) == ("sharded", 4)
    from repro.core import meshutil
    mesh = meshutil.client_mesh(1)
    sp = api.parse_engine(mesh)                    # a Mesh IS an engine spec
    assert sp.kind == "sharded" and sp.resolve_mesh() is mesh
    assert sp.label == "sharded:1"
    with pytest.raises(ValueError):
        api.parse_engine("warp")
    with pytest.raises(ValueError):
        api.parse_engine("jit:4")
    with pytest.raises(ValueError):
        api.EngineSpec("jit", devices=4)
    with pytest.raises(ValueError, match="devices must be >= 1"):
        api.parse_engine("sharded:0")       # not an empty mesh
    with pytest.raises(ValueError):
        api.EngineSpec("jit", devices=0)    # 0 is not "unset"


def test_workload_registry():
    names = api.workload_names()
    for expected in ("smoke", "quickstart", "cifar10_like", "gisette_like",
                     "cifar10_case1", "cifar10_case2", "gisette_case1",
                     "pod512", "smoke_straggler", "engine_micro",
                     "mnist10_like", "linreg_smoke"):
        assert expected in names, expected
    wl = api.get_workload("cifar10_case1")         # paper Section V-A shape
    assert (wl.m, wl.d, wl.n_clients) == (9019, 3073, 50)
    assert wl.cfg.eta == 1.0                       # paper eta fits the field
    # every registered workload must be constructible as a COPML driver
    # (pod512's eta is auto-scaled so the truncation depth fits 26 bits)
    for name in api.workload_names():
        Copml(api.get_workload(name).cfg, api.get_workload(name).m,
              api.get_workload(name).d)
    assert api.WORKLOADS["smoke"] is api.get_workload("smoke")
    with pytest.raises(KeyError, match="unknown workload"):
        api.get_workload("nope")
    # eval split plumbing: *_like workloads hold out test rows
    x, y, xt, yt = api.get_workload("cifar10_like").data()
    assert x.shape == (480, 96) and xt.shape == (160, 96)
    # cached datasets are frozen -- a caller mutating them would silently
    # corrupt every later fit of the same shape
    with pytest.raises(ValueError, match="read-only"):
        x[0, 0] = 1.0
    # ad-hoc instances pass straight through fit's resolution
    assert api.get_workload("smoke").client_data()[0][0].shape[1] == 12


def test_protocol_registry_and_validation():
    assert api.protocol_names() == ("copml", "float", "mpc_baseline",
                                    "poly_float", "secure_agg")
    with pytest.raises(KeyError, match="unknown protocol"):
        api.fit("smoke", "quantum", "jit")
    with pytest.raises(ValueError, match="supports engines"):
        api.fit("smoke", "float", "sharded")       # sharded is copml-only
    # an EXPLICIT straggler subset on a protocol without subset decoding
    # is an error, not a silently-ignored argument ...
    with pytest.raises(ValueError, match="straggler-subset"):
        api.fit("smoke", "float", "jit", subset=(0, 1, 2))
    # ... but a workload's DEFAULT subset only binds protocols that can
    # decode one, so smoke_straggler still fits everywhere
    res = api.fit("smoke_straggler", "mpc_baseline", "jit", iters=2)
    assert res.triple == ("smoke_straggler", "mpc_baseline", "jit")
    with pytest.raises(ValueError, match="subset must be None"):
        api.fit("smoke", "copml", "jit", subset="most")


def test_straggler_subset_workload():
    """smoke_straggler's default subset (last R clients) trains the same
    model as the first-R subset -- recovery threshold via the facade --
    and subset='all' overrides the default with a full-decode fit."""
    res_last = api.fit("smoke_straggler", "copml", "jit", key=0)
    res_first = api.fit("smoke_straggler", "copml", "jit", key=0,
                        subset=tuple(range(10)))
    np.testing.assert_array_equal(res_last.weights, res_first.weights)
    res_all = api.fit("smoke_straggler", "copml", "jit", key=0,
                      subset="all")
    res_empty = api.fit("smoke_straggler", "copml", "jit", key=0, subset=())
    np.testing.assert_array_equal(res_all.weights, res_empty.weights)
    np.testing.assert_array_equal(res_all.weights, res_last.weights)


# ---------------------------------------------- objective conformance grid
#
# The SecureObjective split's acceptance: every protocol trains the two
# new objectives through the same facade, eager and jit agree, and the
# learned model clears a pinned floor (multi-class argmax accuracy /
# linreg R^2; chance is 0.1 / 0.0).  Iteration counts are FIXED so the
# compiled programs are shared with the bit-exactness tests below.

MC_ITERS = 8          # mnist10_like grid + engine-parity iterations
LR_ITERS = 12         # linreg_smoke default


@pytest.mark.parametrize("protocol", ["copml", "mpc_baseline", "float",
                                      "poly_float", "secure_agg"])
@pytest.mark.parametrize("workload,iters,floor,d_model", [
    ("mnist10_like", MC_ITERS, 0.55, (24, 10)),
    ("linreg_smoke", LR_ITERS, 0.60, (12,)),
])
def test_objective_conformance_grid(protocol, workload, iters, floor,
                                    d_model):
    results = {}
    for engine in ("eager", "jit"):
        res = api.fit(workload, protocol, engine, key=0, iters=iters)
        assert res.weights.shape == d_model
        assert res.history.shape == (iters,) + d_model
        assert res.accuracy.shape == (iters,)
        assert np.all(np.isfinite(res.history))
        assert res.final_accuracy >= floor, (protocol, res.final_accuracy)
        if len(d_model) == 2:             # matrix objective: per-class row
            assert res.per_class_accuracy.shape == (d_model[1],)
            assert np.nanmin(res.per_class_accuracy) >= 0.0
        else:
            assert res.per_class_accuracy is None
        results[engine] = res
    np.testing.assert_allclose(results["eager"].weights,
                               results["jit"].weights, atol=1e-4)
    assert abs(results["eager"].final_accuracy
               - results["jit"].final_accuracy) <= 0.05


def test_multiclass_copml_bit_exact_across_engines():
    """The (d, C) matrix-model path is engine-invariant bit for bit:
    eager == jit == sharded (1-device mesh; the 4-device run is the slow
    subprocess in test_distributed.py)."""
    res_j = api.fit("mnist10_like", "copml", "jit", key=0, iters=MC_ITERS,
                    history=True)
    res_e = api.fit("mnist10_like", "copml", "eager", key=0, iters=MC_ITERS,
                    history=True)
    np.testing.assert_array_equal(res_e.weights, res_j.weights)
    np.testing.assert_array_equal(res_e.history, res_j.history)
    np.testing.assert_array_equal(np.asarray(res_e.state.w_shares),
                                  np.asarray(res_j.state.w_shares))
    res_s = api.fit("mnist10_like", "copml",
                    api.EngineSpec("sharded", devices=1), key=0,
                    iters=MC_ITERS, history=True)
    np.testing.assert_array_equal(res_s.weights, res_j.weights)
    np.testing.assert_array_equal(res_s.history, res_j.history)
    # the trajectory moves and the cost model prices the C-wide exchange:
    # dearer than one binary run, far cheaper than C separate runs
    # (encode-once amortization, measured by the `multiclass` bench stage)
    assert not np.array_equal(res_j.history[0], res_j.history[-1])
    import dataclasses

    from repro.core import objectives
    wl = api.get_workload("mnist10_like")
    wl_bin = dataclasses.replace(wl, name="mnist10_bin",
                                 objective=objectives.BINARY_LOGISTIC)
    cost_mc = api.PROTOCOLS["copml"].cost(wl, MC_ITERS)
    cost_bin = api.PROTOCOLS["copml"].cost(wl_bin, MC_ITERS)
    assert cost_mc["comm_s"] > cost_bin["comm_s"]          # C-wide model
    assert cost_mc["comm_s"] < 10 * cost_bin["comm_s"]     # << C separate runs


def test_legacy_accuracy_of_rejects_matrix_models():
    """The pre-objective binary scorer guards against (d, C) weights
    instead of broadcasting into a meaningless mean."""
    x = np.zeros((4, 3))
    with pytest.raises(ValueError, match="objective.score"):
        api.accuracy_of(np.zeros((3, 10)), x, np.zeros(4))


def test_accuracy_curve_matrix_history():
    """Regression: a (iters, d, C) matrix-model history must fail fast
    with the SAME named error BEFORE iterating (it used to crash on the
    first history row inside the loop), and scoring it with the
    workload's objective must work."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(20, 3))
    y = rng.integers(0, 4, size=20)
    hist = rng.normal(size=(5, 3, 4))           # (iters, d, C=4)
    with pytest.raises(ValueError, match="objective.score"):
        api.accuracy_curve(hist, x, y)
    obj = api.multiclass_logistic(4)
    curve = api.accuracy_curve(hist, x, y, objective=obj)
    assert curve.shape == (5,)
    assert curve[0] == obj.score(hist[0], x, y)
    # vector histories keep working without an objective
    yb = rng.integers(0, 2, size=20)
    vec = api.accuracy_curve(rng.normal(size=(5, 3)), x, yb)
    assert vec.shape == (5,) and np.all((0 <= vec) & (vec <= 1))


def test_multiclass_faultplan_bit_exact():
    """A churned multi-class run equals the fault-free run bit for bit
    (LCC decode invariance on the matrix-model path), and adversarial
    contributions are really excluded."""
    from repro.core import objectives
    from repro.core.protocol import CopmlConfig
    wl = api.Workload(name="ovr3_faults", m=78, d=6,
                      cfg=CopmlConfig(n_clients=13, k=3, t=1), seed=2,
                      iters=3, objective=objectives.multiclass_logistic(3))
    plan = api.FaultPlan.random(13, 3, seed=4, straggle_p=0.3,
                                n_adversaries=1, min_available=10)
    assert not plan.is_fault_free and plan.has_adversaries
    base = api.fit(wl, "copml", "jit", key=1, iters=3, history=True)
    churn = api.fit(wl, "copml", "jit", key=1, iters=3, history=True,
                    faults=plan)
    np.testing.assert_array_equal(churn.weights, base.weights)
    np.testing.assert_array_equal(churn.history, base.history)
    np.testing.assert_array_equal(churn.availability, plan.available)
    # eager replays the same plan identically
    churn_e = api.fit(wl, "copml", "eager", key=1, iters=3, history=True,
                      faults=plan)
    np.testing.assert_array_equal(churn_e.weights, churn.weights)


def test_copml_driver_keyed_on_megakernel_gate(monkeypatch):
    """REPRO_FUSED_STEP is read once per Copml, and api.fit caches one
    Copml per (workload, gate): flipping the gate between fits builds a
    new driver instead of silently reusing the old schedule."""
    proto = api.PROTOCOLS["copml"]
    wl = api.get_workload("smoke")
    monkeypatch.setenv("REPRO_FUSED_STEP", "1")
    fused = proto.driver(wl)
    monkeypatch.setenv("REPRO_FUSED_STEP", "kernel")
    kernel = proto.driver(wl)
    assert kernel is not fused
    assert (fused.fused_mode, kernel.fused_mode) == ("1", "kernel")
    monkeypatch.setenv("REPRO_FUSED_STEP", "1")
    assert proto.driver(wl) is fused


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_placement(monkeypatch, env_dir):
    """The entry points' cache helper: JAX_COMPILATION_CACHE_DIR wins and
    nothing else is set; otherwise the fixed <repo>/.jax_cache."""
    monkeypatch.undo()              # the real helper, not conftest's stub
    from repro.api import compile_cache
    updates = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(_REPO, ".jax_cache")
        assert compile_cache.enable() == want
        assert updates == [("jax_compilation_cache_dir", want)]
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert compile_cache.enable() == env_dir
        assert updates == []


# ----------------------------------------------------------- cli + harness


def test_cli_list_and_fit(capsys):
    from repro.api import cli
    cli.main(["--list"])
    out = capsys.readouterr().out
    assert "copml" in out and "sharded" in out and "smoke" in out
    assert "ovr10" in out and "linreg" in out      # objective registry
    cli.main(["smoke", "--protocol", "float", "--engine", "jit",
              "--iters", "5"])
    out = capsys.readouterr().out
    assert "smoke x float x jit" in out


def test_benchmark_stage_registry():
    """benchmarks/run.py discovers stages from a registry and stamps every
    row with its (workload, protocol, engine) triple."""
    if _REPO not in sys.path:
        sys.path.insert(0, _REPO)
    brun = importlib.import_module("benchmarks.run")
    stages = brun.build_stages()
    assert set(stages) >= {"kernel_micro", "engine", "distributed",
                           "resilience",
                           "procnet", "multiclass", "fig3", "fig4",
                           "table1", "table2", "roofline"}
    for s in stages.values():
        assert len(s.triple) == 3, s
        assert s.doc
    # unknown stage names are an error, not silently skipped
    with pytest.raises(SystemExit):
        brun.main(["--stage", "nope"])


def test_benchmark_json_trajectory_files(tmp_path):
    """--json writes one BENCH_<stage>.json per executed stage (stage,
    triple, rows) -- the perf-trajectory artifact CI uploads; a *.json
    target keeps the legacy combined dump."""
    import json
    if _REPO not in sys.path:
        sys.path.insert(0, _REPO)
    brun = importlib.import_module("benchmarks.run")
    stages = brun.build_stages()
    rows = [{"stage": "engine", "name": "engine/jit", "us_per_call": 12.5,
             "derived": "ok", "workload": "engine_micro",
             "protocol": "copml", "engine": "jit"},
            {"stage": "multiclass", "name": "multiclass/modeled_comm_ratio",
             "us_per_call": 0.0, "derived": "3.10x", "workload":
             "mnist10_like", "protocol": "copml", "engine": "jit"}]
    paths = brun.write_json(str(tmp_path), rows,
                            [("roofline", "RuntimeError('x')")], stages)
    names = {os.path.basename(p) for p in paths}
    assert names == {"BENCH_engine.json", "BENCH_multiclass.json",
                     "BENCH_roofline.json"}
    mc = json.load(open(tmp_path / "BENCH_multiclass.json"))
    assert mc["stage"] == "multiclass"
    assert mc["triple"] == ["mnist10_like", "copml", "jit"]
    assert mc["rows"][0]["derived"] == "3.10x" and mc["failure"] is None
    assert json.load(open(tmp_path / "BENCH_roofline.json"))["failure"]
    combined = tmp_path / "all.json"
    brun.write_json(str(combined), rows, [], stages)
    assert len(json.load(open(combined))["rows"]) == 2
