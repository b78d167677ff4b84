"""Production training loop: pjit + checkpoint/restart + secure aggregation.

Runs on whatever mesh the host provides (launch/train.py wires the
production mesh); the same code path is what the 512-device dry-run lowers.

Fault tolerance:
  * checkpoint every `ckpt_every` steps (async, atomic-rename manifests);
  * restart picks up the newest complete step and replays the deterministic
    data stream from there (data/pipeline.py is keyed by step);
  * on a changed device count, restore() re-places leaves against the new
    mesh (elastic re-mesh);
  * optional COPML-coded secure gradient aggregation across the data axis
    (core/secure_agg.py) -- the paper's technique as a framework feature:
    per-host gradient privacy against T colluders + straggler tolerance.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import secure_agg
from ..data import pipeline
from ..models import model_zoo as MZ
from ..models.config import ModelConfig
from ..optim import optimizers
from ..sharding import partition
from . import checkpoint as ckpt_lib


@dataclasses.dataclass
class TrainConfig:
    steps: int = 100
    global_batch: int = 8
    seq_len: int = 128
    microbatch: int = 0
    loss_chunk: int = 0
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    log_every: int = 10
    seed: int = 0
    secure_agg: Optional[secure_agg.SecureAggConfig] = None


def train(cfg: ModelConfig, tcfg: TrainConfig, mesh=None, callback=None):
    """Returns (params, metrics_history)."""
    bm = MZ.build(cfg, microbatch=tcfg.microbatch,
                  loss_chunk=tcfg.loss_chunk)
    opt = optimizers.make(cfg.optimizer)
    key = jax.random.PRNGKey(tcfg.seed)

    params = bm.init_params(key)
    opt_state = opt.init(params)
    start_step = 0
    ckpt = ckpt_lib.Checkpointer(tcfg.ckpt_dir) if tcfg.ckpt_dir else None
    if ckpt and ckpt.list_steps():
        (restored, _) = ckpt.restore(
            {"params": params, "opt": opt_state, "step": 0})
        params, opt_state = restored["params"], restored["opt"]
        start_step = int(restored["step"]) + 1
        print(f"restored checkpoint, resuming at step {start_step}")

    if mesh is not None:
        pshard = partition.param_shardings(cfg, mesh)
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, s), params, pshard)

    dcfg = pipeline.LmDataConfig(vocab=cfg.vocab, seq_len=tcfg.seq_len,
                                 global_batch=tcfg.global_batch,
                                 seed=tcfg.seed)

    def step_fn(params, opt_state, batch, step):
        return bm.train_step(params, opt_state, batch, step)

    jit_step = jax.jit(step_fn, donate_argnums=(0, 1))
    history = []
    ctx = jax.set_mesh(mesh) if mesh is not None else _nullcontext()
    with ctx:
        for step in range(start_step, tcfg.steps):
            batch = pipeline.lm_batch(dcfg, step)
            t0 = time.perf_counter()
            params, opt_state, metrics = jit_step(
                params, opt_state, batch, jnp.asarray(step, jnp.int32))
            if step % tcfg.log_every == 0 or step == tcfg.steps - 1:
                loss = float(metrics["loss"])
                rec = {"step": step, "loss": loss,
                       "grad_norm": float(metrics["grad_norm"]),
                       "step_time_s": time.perf_counter() - t0}
                history.append(rec)
                print(f"step {step:5d} loss {loss:8.4f} "
                      f"gnorm {rec['grad_norm']:8.3f} "
                      f"dt {rec['step_time_s']:6.2f}s")
                if callback:
                    callback(rec)
                assert np.isfinite(loss), f"loss diverged at step {step}"
            if ckpt and (step % tcfg.ckpt_every == 0 or
                         step == tcfg.steps - 1):
                ckpt.save(step, {"params": params, "opt": opt_state,
                                 "step": step})
    if ckpt:
        ckpt.wait()
    return params, history


def train_secure(cfg: ModelConfig, tcfg: TrainConfig):
    """Beyond-paper path: N virtual DP hosts, each computes its local
    gradient; gradients are combined with COPML-coded secure aggregation
    (information-theoretic privacy of each host's contribution against T
    colluders + straggler tolerance N - (T+1)).
    """
    sa = tcfg.secure_agg
    assert sa is not None
    bm = MZ.build(cfg, loss_chunk=tcfg.loss_chunk)
    opt = optimizers.make(cfg.optimizer)
    key = jax.random.PRNGKey(tcfg.seed)
    params = bm.init_params(key)
    opt_state = opt.init(params)
    dcfg = pipeline.LmDataConfig(vocab=cfg.vocab, seq_len=tcfg.seq_len,
                                 global_batch=tcfg.global_batch,
                                 seed=tcfg.seed)
    per = tcfg.global_batch // sa.n_clients

    @jax.jit
    def local_grads(params, batch):
        mbs = jax.tree.map(
            lambda x: x.reshape((sa.n_clients, per) + x.shape[1:]), batch)
        losses, grads = jax.vmap(
            lambda mb: jax.value_and_grad(
                lambda p: bm.loss_fn(p, mb)[0])(params))(mbs)
        return losses, grads

    @jax.jit
    def apply(params, opt_state, grads, step):
        return opt.update(grads, opt_state, params, step)

    history = []
    for step in range(tcfg.steps):
        batch = pipeline.lm_batch(dcfg, step)
        losses, stacked = local_grads(params, batch)
        per_client = [jax.tree.map(lambda x: x[i], stacked)
                      for i in range(sa.n_clients)]
        agg = secure_agg.secure_aggregate(
            jax.random.fold_in(key, step), per_client, sa)
        params, opt_state, gnorm = apply(
            params, opt_state, agg, jnp.asarray(step, jnp.int32))
        if step % tcfg.log_every == 0 or step == tcfg.steps - 1:
            rec = {"step": step, "loss": float(jnp.mean(losses))}
            history.append(rec)
            print(f"[secure-agg] step {step:4d} loss {rec['loss']:.4f}")
    return params, history


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False
