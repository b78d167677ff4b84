"""Protocol registry: the *who-computes-what* axis of a run.

Five interchangeable training protocols over the same workloads, the
paper's Section V comparison as a registry (fit one name against another
and the Fig. 3/4 / Table I artifacts are pure formatting of TrainResults):

  copml         Algorithm 1: LCC-coded secret-shared training, local-only
                hot loop (core/protocol.Copml).  eager | jit | sharded.
  mpc_baseline  the [BGW88]/[BH08] Appendix-D baselines: every multiply
                is a secure multiplication with degree reduction
                (core/baselines.MpcBaseline).  eager | jit.
  float         conventional plaintext logistic regression (the Fig. 4
                reference).  eager | jit.
  poly_float    plaintext GD with the degree-r polynomial sigmoid --
                isolates approximation from quantization error.
                eager | jit.
  secure_agg    gradient-privacy-only training: clear local gradients,
                COPML-coded secure aggregation of the exchange
                (core/secure_agg).  eager | jit.

Every protocol consumes the workload's SecureObjective (core/objectives):
the same registry trains binary logreg, linear regression, and multi-class
one-vs-rest matrix models with no protocol-specific casing beyond shapes.

All protocol drivers and dataset arrays are cached per (hashable)
Workload, so repeated fits of the same shape reuse compiled programs.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from ..core import baselines, cost_model, secure_agg, spans
from ..core import objectives as objectives_mod
from ..core.protocol import Copml, fused_mode
from ..train import elastic
from . import engine as engine_mod
from . import faults as faults_mod
from . import result as result_mod
from . import workloads as workloads_mod

PROTOCOLS: dict = {}


def register(protocol: "Protocol") -> "Protocol":
    PROTOCOLS[protocol.name] = protocol
    return protocol


def get(name: str) -> "Protocol":
    if name not in PROTOCOLS:
        known = ", ".join(sorted(PROTOCOLS))
        raise KeyError(f"unknown protocol {name!r}; registered: {known}")
    return PROTOCOLS[name]


def names() -> tuple:
    return tuple(sorted(PROTOCOLS))


# ---------------------------------------------------------------- the facade


def fit(workload, protocol: str = "copml", engine="jit", *, key=0,
        iters: int | None = None, subset=None, history: bool = True,
        faults=None) -> result_mod.TrainResult:
    """Train `workload` with `protocol` on `engine`; the one front door.

    workload: registry name or an ad-hoc workloads.Workload instance.
    protocol: name in PROTOCOLS.
    engine:   "eager" | "jit" | "sharded[:N]" | EngineSpec | jax Mesh.
    key:      int seed or jax PRNGKey.
    iters:    GD iterations (None = the workload's default).
    subset:   straggler decode subset.  None inherits the workload's
              default (subset-capable protocols only); "all" or () forces
              full decode even when the workload has a default subset.
    history:  keep the per-step opened-model trajectory + accuracy curve.
    faults:   a faults.FaultPlan (per-step straggler/dropout/adversary
              schedule) replayed by the engine; validated against the
              protocol's recovery threshold BEFORE any compute
              (FaultPlanViolation).  Mutually exclusive with `subset`.
    """
    return get(protocol).fit(workload, engine, key=key, iters=iters,
                             subset=subset, history=history, faults=faults)


class Protocol:
    """One training protocol behind the common fit() interface.

    Subclasses implement `_run` (returning the raw engine outputs) and
    optionally `cost`; the base class owns workload/engine resolution,
    timing, and TrainResult assembly."""

    name: str = "?"
    engines: tuple = ("eager", "jit")
    supports_subset: bool = False    # straggler decode subsets
    supports_faults: bool = False    # per-step FaultPlan schedules

    def fit(self, workload, engine="jit", *, key=0, iters=None, subset=None,
            history=True, faults=None) -> result_mod.TrainResult:
        with spans.fit():
            wl = workloads_mod.resolve(workload)
            spec = engine_mod.parse(engine)
            if spec.kind not in self.engines:
                raise ValueError(
                    f"protocol {self.name!r} supports engines {self.engines}, "
                    f"not {spec.kind!r}")
            if isinstance(key, int):
                key = jax.random.PRNGKey(key)
            iters = wl.iters if iters is None else int(iters)
            if faults is not None:
                if subset is not None:
                    raise ValueError(
                        "faults= and subset= are mutually exclusive: the plan "
                        "chooses each step's decode subset")
                plan = self._resolve_plan(wl, iters, faults)
                subset = None                    # the plan drives every step
            else:
                plan = None
                if subset is None:
                    # the workload default only applies where it means
                    # something
                    subset = wl.subset if self.supports_subset else None
                elif isinstance(subset, str):
                    if subset != "all":
                        raise ValueError(f"subset must be None, 'all', or an "
                                         f"iterable of client indices; got "
                                         f"{subset!r}")
                    subset = None                     # force full decode
                else:
                    # () also means full decode
                    subset = tuple(subset) or None
                if subset is not None and not self.supports_subset:
                    raise ValueError(
                        f"protocol {self.name!r} has no straggler-subset "
                        f"decoding; drop the subset argument")

            t0 = time.perf_counter()
            # plan is passed only when present: externally registered protocols
            # written against the pre-fault 6-arg _run contract keep working
            # for fault-free fits (docs/API.md extension example)
            if plan is None:
                out = self._run(wl, spec, key, iters, subset, history)
            else:
                out = self._run(wl, spec, key, iters, subset, history, plan)
            # engines that MEASURE their communication (proc) return a 4th
            # element; the in-process engines keep the 3-tuple contract
            if len(out) == 4:
                w, hist, state, measured = out
            else:
                w, hist, state = out
                measured = None
            w = np.asarray(jax.block_until_ready(w))
            wall = time.perf_counter() - t0

            with spans.span("finish"):
                hist = None if hist is None else np.asarray(hist)
                x_eval, y_eval = wl.eval_set()
                obj = wl.objective    # objective-defined scoring: accuracy for
                #                       the logistic objectives, R^2 for linreg
                acc = None if hist is None else np.asarray(
                    [obj.score(w_t, x_eval, y_eval) for w_t in hist])
                return result_mod.TrainResult(
                    workload=wl.name, protocol=self.name, engine=spec.label,
                    iters=iters, weights=w, wall_time_s=wall, history=hist,
                    accuracy=acc,
                    final_accuracy=obj.score(w, x_eval, y_eval),
                    per_class_accuracy=obj.per_class_accuracy(
                        w, x_eval, y_eval),
                    cost=self.cost(wl, iters), state=state,
                    availability=(None if plan is None
                                  else plan.available.copy()),
                    measured_comm=measured)

    def _resolve_plan(self, wl, iters: int, faults) -> faults_mod.FaultPlan:
        """Check a FaultPlan against this protocol and workload, truncate
        it to the run length, and run the recovery-threshold budget check
        -- all BEFORE any engine work (an invalid plan never compiles)."""
        if not self.supports_faults:
            raise ValueError(
                f"protocol {self.name!r} has no fault injection; drop the "
                f"faults argument")
        if not isinstance(faults, faults_mod.FaultPlan):
            raise TypeError(f"faults must be a FaultPlan, got "
                            f"{type(faults).__name__}")
        if faults.n_clients != wl.n_clients:
            raise ValueError(
                f"plan covers {faults.n_clients} clients; workload "
                f"{wl.name!r} has {wl.n_clients}")
        if faults.iters < iters:
            raise ValueError(
                f"plan covers {faults.iters} steps; the run needs {iters}")
        plan = faults.slice(iters)
        self._validate_plan(wl, plan)        # raises FaultPlanViolation
        return plan

    def fault_threshold(self, wl) -> int:
        """The per-step availability floor a FaultPlan must keep for this
        protocol on `wl` -- the SINGLE source both _validate_plan and
        plan-building callers (cli --straggle-p) derive from."""
        raise NotImplementedError            # supports_faults protocols only

    def _validate_plan(self, wl, plan: faults_mod.FaultPlan):
        raise NotImplementedError            # supports_faults protocols only

    def _run(self, wl, spec, key, iters, subset, history, plan=None):
        """-> (weights, history-or-None, protocol-native state)"""
        raise NotImplementedError

    def cost(self, wl, iters: int) -> dict | None:
        """Modeled per-client comm/comp/enc on the paper's WAN params."""
        return None

    def _cost_workload(self, wl, iters: int) -> cost_model.Workload:
        return cost_model.Workload(m=wl.m, d=wl.d, n=wl.n_clients,
                                   k=wl.cfg.k, t=wl.cfg.t, iters=iters,
                                   r=wl.cfg.r, c=wl.objective.n_outputs)


def _stack_history(rows, w_shape):
    """Collected eager-engine history rows -> the same (iters,) + w_shape
    array the scan engines produce (None stays None; zero iterations give
    (0,) + w_shape, not None, so the TrainResult schema is
    engine-independent)."""
    if rows is None:
        return None
    return np.stack(rows) if rows else \
        np.zeros((0,) + tuple(w_shape), np.float32)


def _history_recorder(history: bool):
    """(rows, callback) for the eager engines: the callback appends each
    step's opened model to rows; both are None when history is off.  The
    copy matters: the numpy trainers (float_logreg et al.) update w in
    place, so an np.asarray view would alias every row to the final
    model."""
    if not history:
        return None, None
    rows: list = []
    return rows, lambda t, w: rows.append(np.array(w, copy=True))


# ------------------------------------------------------------------ copml


def run_copml_engine(proto: Copml, spec, key, client_xs, client_ys,
                     iters: int, subset=None, history: bool = False,
                     callback=None, step_subsets=None, adversaries=None):
    """THE dispatch from an EngineSpec to a Copml engine implementation.

    Both api.fit and the deprecated Copml.train_* shims route through
    here, so shim-vs-facade parity is structural.  Returns
    (state, weights, history-or-None); `callback` is eager-only.
    step_subsets/adversaries carry a FaultPlan's per-step decode subsets
    and corruption mask to whichever engine runs."""
    spec = engine_mod.parse(spec)
    subset = None if subset is None else tuple(subset)
    fault_kw = dict(step_subsets=step_subsets, adversaries=adversaries)
    if spec.kind == "eager":
        hist_rows, rec = _history_recorder(history)

        def cb(t, w):
            if rec is not None:
                rec(t, w)
            if callback is not None:
                callback(t, w)

        state, w = proto._train_eager(
            key, client_xs, client_ys, iters, subset=subset,
            callback=cb if (history or callback) else None, **fault_kw)
        return state, w, _stack_history(hist_rows, proto.w_shape)
    if callback is not None:
        raise ValueError("callback is only supported on the eager engine")
    if spec.kind == "jit":
        out = proto._train_jit(key, client_xs, client_ys, iters,
                               subset=subset, history=history, **fault_kw)
    else:
        out = proto._train_sharded(key, client_xs, client_ys, iters,
                                   mesh=spec.resolve_mesh(), subset=subset,
                                   history=history, **fault_kw)
    if history:
        state, w, hist = out
        return state, w, hist
    state, w = out
    return state, w, None


class CopmlProtocol(Protocol):
    name = "copml"
    engines = ("eager", "jit", "sharded", "proc")
    supports_subset = True           # decode from any R of N clients
    supports_faults = True           # per-step FaultPlan schedules

    def __init__(self):
        self._drivers: dict = {}

    def driver(self, wl) -> Copml:
        """The (cached) Copml instance for a workload and megakernel gate
        (REPRO_FUSED_STEP, read once per instance) -- caching keeps the
        per-instance jit/scan caches warm across fit() calls."""
        key = (wl, fused_mode())
        if key not in self._drivers:
            self._drivers[key] = Copml(wl.cfg, wl.m, wl.d,
                                       objective=wl.objective)
        return self._drivers[key]

    def fault_threshold(self, wl) -> int:
        """R = (2r+1)(K+T-1)+1 honest on-time clients per step."""
        return elastic.straggler_budget(wl.n_clients, wl.cfg.k, wl.cfg.t,
                                        wl.cfg.r).recovery_threshold

    def _validate_plan(self, wl, plan):
        """The paper's recovery threshold as a hard budget (elastic.py)."""
        plan.validate(self.fault_threshold(wl), "COPML decode")

    def _run(self, wl, spec, key, iters, subset, history, plan=None):
        proto = self.driver(wl)
        cx, cy = wl.client_data()
        if spec.kind == "proc":
            if plan is not None:
                raise ValueError(
                    "the proc engine has no FaultPlan replay: stragglers "
                    "emerge from real socket timing -- inject latency / "
                    "deadlines via EngineSpec('proc', net=NetConfig(...)) "
                    "instead")
            from ..launch import runtime
            state, w, hist, measured = runtime.run_copml_proc(
                proto, key, cx, cy, iters, procs=spec.devices,
                net_cfg=spec.net, subset=subset, history=history)
            return w, hist, state, measured
        step_subsets = adversaries = None
        if plan is not None:
            step_subsets = plan.subsets(wl.cfg.recovery_threshold)
            adversaries = plan.adversary if plan.has_adversaries else None
        state, w, hist = run_copml_engine(proto, spec, key, cx, cy, iters,
                                          subset=subset, history=history,
                                          step_subsets=step_subsets,
                                          adversaries=adversaries)
        return w, hist, state

    def cost(self, wl, iters):
        return cost_model.copml_costs(self._cost_workload(wl, iters))


class MpcBaselineProtocol(Protocol):
    name = "mpc_baseline"
    scheme = "bh08"
    groups = 3

    def __init__(self):
        self._drivers: dict = {}

    def driver(self, wl) -> baselines.MpcBaseline:
        if wl not in self._drivers:
            self._drivers[wl] = baselines.MpcBaseline(
                wl.cfg, wl.m, wl.d, groups=self.groups, scheme=self.scheme,
                objective=wl.objective)
        return self._drivers[wl]

    def _run(self, wl, spec, key, iters, subset, history, plan=None):
        mb = self.driver(wl)
        x, y, _, _ = wl.data()
        if spec.kind == "jit":
            out = mb.train_scan(key, x, y, iters, history=history)
            return (out[1], out[2], out[0]) if history else \
                (out[1], None, out[0])
        rows, cb = _history_recorder(history)
        state, w = mb.train(key, x, y, iters, callback=cb)
        return w, _stack_history(rows, wl.w_shape), state

    def cost(self, wl, iters):
        return cost_model.mpc_baseline_costs(
            self._cost_workload(wl, iters), scheme=self.scheme,
            groups=self.groups)


class FloatProtocol(Protocol):
    name = "float"
    poly = False        # PolyFloatProtocol flips this: same float engine,
    #                     ghat's polynomial instead of the exact activation

    def _run(self, wl, spec, key, iters, subset, history, plan=None):
        x, y, _, _ = wl.data()
        obj, eta = wl.objective, wl.cfg.eta
        r, bound = wl.cfg.r, wl.cfg.sigmoid_bound
        if not isinstance(obj, objectives_mod.BinaryLogistic):
            # objective-generic float GD (vector or matrix model)
            if spec.kind == "jit":
                w, hist = baselines.float_objective_scan(
                    obj, x, y, eta, iters, history=history, poly=self.poly,
                    r=r, bound=bound)
                return w, hist, None
            rows, cb = _history_recorder(history)
            w = baselines.float_objective_train(
                obj, x, y, eta, iters, callback=cb, poly=self.poly, r=r,
                bound=bound)
            return w, _stack_history(rows, wl.w_shape), None
        # the paper's binary path keeps its dedicated (pre-objective)
        # trainers -- their compiled programs are shared across the suite
        if spec.kind == "jit":
            if self.poly:
                w, hist = baselines.float_poly_logreg_scan(
                    x, y, eta, iters, r=r, bound=bound, history=history)
            else:
                w, hist = baselines.float_logreg_scan(x, y, eta, iters,
                                                      history=history)
            return w, hist, None
        rows, cb = _history_recorder(history)
        if self.poly:
            w = baselines.float_poly_logreg(x, y, eta, iters, r=r,
                                            bound=bound, callback=cb)
        else:
            w = baselines.float_logreg(x, y, eta, iters, callback=cb)
        return w, _stack_history(rows, wl.w_shape), None


class PolyFloatProtocol(FloatProtocol):
    name = "poly_float"
    poly = True


class SecureAggProtocol(Protocol):
    name = "secure_agg"
    supports_subset = True           # reconstruct from any T+1 holders
    supports_faults = True           # per-step T+1-of-N share selection

    def agg_config(self, wl) -> secure_agg.SecureAggConfig:
        """Privacy threshold T from the workload's COPML parameterization;
        lq/clip at the module defaults (validated against the field)."""
        return secure_agg.SecureAggConfig(n_clients=wl.n_clients, t=wl.cfg.t)

    def _validate_plan(self, wl, plan):
        """Shamir aggregation reconstructs from any T+1 holders' shares
        (elastic.secure_agg_budget); the plan governs which holders'
        shares each round's reconstruction reads.  There is no redundancy
        on the OWNER side (every gradient is summed exactly once), so
        corrupted contributions cannot be excluded -- adversarial plans
        are rejected for this protocol."""
        if plan.has_adversaries:
            raise elastic.FaultPlanViolation(
                "secure_agg tolerates straggling/dropped share holders, "
                "not adversarially corrupted contributions (no decode "
                "redundancy over gradient owners); use the copml protocol "
                "for adversary schedules")
        plan.validate(self.fault_threshold(wl), "secure_agg share")

    def fault_threshold(self, wl) -> int:
        """T+1 share holders per step (Shamir reconstruction)."""
        return elastic.secure_agg_budget(wl.n_clients,
                                         wl.cfg.t).recovery_threshold

    def _run(self, wl, spec, key, iters, subset, history, plan=None):
        cx, cy = wl.client_data()
        cfg, eta = self.agg_config(wl), wl.cfg.eta
        step_subsets = None if plan is None else plan.subsets(cfg.t + 1)
        obj = wl.objective
        if spec.kind == "jit":
            w, hist = secure_agg.secure_logreg_scan(
                key, cx, cy, cfg, eta, iters, subset=subset,
                history=history, step_subsets=step_subsets, objective=obj)
            return w, hist, cfg
        rows, cb = _history_recorder(history)
        w = secure_agg.secure_logreg(key, cx, cy, cfg, eta, iters,
                                     subset=subset, callback=cb,
                                     step_subsets=step_subsets,
                                     objective=obj)
        return w, _stack_history(rows, wl.w_shape), cfg


register(CopmlProtocol())
register(MpcBaselineProtocol())
register(FloatProtocol())
register(PolyFloatProtocol())
register(SecureAggProtocol())
