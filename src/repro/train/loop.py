"""Training loop: jitted step, checkpoint/restart, straggler detection,
fault tolerance, optional int8-compressed DP gradients.

Fault model (exercised by tests):
  * process crash      -> restart with --resume: restore latest atomic
                          checkpoint + data-iterator state; loss curve
                          continues exactly;
  * node-count change  -> elastic: checkpoints restore onto the current
                          mesh (reshard-on-load);
  * straggler steps    -> StragglerMonitor flags steps > k x EWMA and
                          raises a hook (on real fleets: trigger backup
                          step / re-shard away from the slow host).  The
                          M0 metrics (max-vs-mean per-unit load) detect
                          *structural* stragglers (expert/shard imbalance)
                          before they show up in wall-time.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed import collectives, sharding
from repro.models import encdec, lm
from repro.models.encdec import EncDecCfg
from repro.train import checkpoint as ckpt_lib
from repro.train import step as step_lib
from repro.train.optim import Optimizer

from jax import shard_map


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    log_every: int = 10
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    keep: int = 3
    num_microbatches: int = 1
    resume: bool = False
    compress_grads: bool = False        # int8 + error feedback on DP path
    straggler_factor: float = 3.0
    seed: int = 0


class StragglerMonitor:
    """EWMA step-time tracker; flags steps slower than factor x EWMA."""

    def __init__(self, factor: float = 3.0, alpha: float = 0.2):
        self.factor, self.alpha = factor, alpha
        self.ewma: Optional[float] = None
        self.events: list[tuple[int, float, float]] = []

    def record(self, step: int, dt: float) -> bool:
        slow = self.ewma is not None and dt > self.factor * self.ewma
        if slow:
            self.events.append((step, dt, self.ewma))
        self.ewma = dt if self.ewma is None else (
            (1 - self.alpha) * self.ewma + self.alpha * dt)
        return slow


def make_dp_compressed_step(cfg, ctx, optimizer: Optimizer):
    """DP-only train step with int8 error-feedback gradient reduction
    (params replicated; the whole step runs under shard_map over dp)."""
    loss_f = (encdec.loss_fn if isinstance(cfg, EncDecCfg) else lm.loss_fn)
    inner_ctx = dataclasses.replace(ctx, mesh=None)   # per-shard local math

    def local_step(state, batch):
        params, err = state["params"], state["err"]

        def lf(p):
            return loss_f(p, batch, cfg, inner_ctx)
        (_, metrics), grads = jax.value_and_grad(lf, has_aux=True)(params)
        g_mean, new_err = collectives.compressed_grad_mean(
            grads, err, tuple(ctx.dp))
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, tuple(ctx.dp)),
                               metrics)
        new_params, new_opt = optimizer.update(
            g_mean, state["opt"], params, state["step"])
        return ({"params": new_params, "opt": new_opt, "err": new_err,
                 "step": state["step"] + 1}, metrics)

    def step(state, batch):
        rep = P()
        state_specs = jax.tree.map(lambda _: rep, state)
        batch_specs = jax.tree.map(lambda _: P(ctx.dp), batch)
        # metrics structure from the (axis-free) local loss fn
        local_b = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                (x.shape[0] // ctx.dp_size,) + x.shape[1:], x.dtype), batch)
        mshape = jax.eval_shape(lambda p, b: loss_f(p, b, cfg, inner_ctx)[1],
                                state["params"], local_b)
        return shard_map(local_step, mesh=ctx.mesh,
                         in_specs=(state_specs, batch_specs),
                         out_specs=(state_specs,
                                    jax.tree.map(lambda _: rep, mshape)),
                         check_vma=False)(state, batch)
    return step


class Trainer:
    def __init__(self, cfg, mesh, optimizer: Optimizer, data,
                 tcfg: TrainerConfig):
        self.cfg, self.mesh, self.opt = cfg, mesh, optimizer
        self.data, self.tcfg = data, tcfg
        self.ctx = sharding.make_ctx(mesh)
        self.monitor = StragglerMonitor(tcfg.straggler_factor)
        self.history: list[dict] = []
        self.fault_hook: Optional[Callable[[int], None]] = None
        self._build()

    # ------------------------------------------------------------------
    def _build(self):
        cfg, ctx, opt, tcfg = self.cfg, self.ctx, self.opt, self.tcfg
        key = jax.random.PRNGKey(tcfg.seed)
        aparams = jax.eval_shape(
            lambda: (encdec.init_params if isinstance(cfg, EncDecCfg)
                     else lm.init_params)(cfg, key))
        self.pspecs = sharding.param_specs(cfg, ctx)
        sspecs = step_lib.state_spec_tree(cfg, ctx, opt, aparams)
        if tcfg.compress_grads:
            sspecs = {**sspecs, "err": jax.tree.map(
                lambda s: P(), self.pspecs)}
            step_fn = make_dp_compressed_step(cfg, ctx, opt)
        else:
            gspecs = sharding.grad_specs(aparams, self.pspecs, ctx)
            step_fn = step_lib.make_train_step(
                cfg, ctx, opt, num_microbatches=tcfg.num_microbatches,
                grad_spec_tree=gspecs)
        self.state_shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), sspecs)
        self.sspecs = sspecs
        self.step_fn = jax.jit(step_fn, donate_argnums=0)

        # init or resume
        start = 0
        if tcfg.resume and tcfg.ckpt_dir and \
                ckpt_lib.latest_step(tcfg.ckpt_dir) is not None:
            like = jax.eval_shape(
                lambda: self._fresh_state(key))
            state, start, extra = ckpt_lib.restore(
                tcfg.ckpt_dir, like, shardings=self.state_shardings)
            self.state = state
            self.data_step = extra.get("data_step", start)
            print(f"[trainer] resumed from step {start}")
        else:
            # init under jit: distinct output buffers per leaf (identical
            # zeros constants would otherwise alias and break donation)
            self.state = jax.jit(self._fresh_state,
                                 out_shardings=self.state_shardings)(key)
            self.data_step = 0
        self.start_step = start

    def _fresh_state(self, key):
        state = step_lib.init_state(self.cfg, self.opt, key)
        if self.tcfg.compress_grads:
            state["err"] = collectives.init_error_feedback(state["params"])
        return state

    # ------------------------------------------------------------------
    def _put_batch(self, batch_np):
        bspecs = sharding.batch_specs(batch_np, self.ctx)
        return jax.tree.map(
            lambda x, s: jax.device_put(
                jnp.asarray(x), NamedSharding(self.mesh, s)),
            batch_np, bspecs)

    def run(self) -> list[dict]:
        tcfg = self.tcfg
        step = int(self.start_step)
        while step < tcfg.steps:
            try:
                if self.fault_hook:
                    self.fault_hook(step)
                batch = self._put_batch(self.data.batch(self.data_step))
                t0 = time.time()
                self.state, metrics = self.step_fn(self.state, batch)
                jax.block_until_ready(metrics["loss"])
                dt = time.time() - t0
                slow = self.monitor.record(step, dt)
                step += 1
                self.data_step += 1
                if step % tcfg.log_every == 0 or step == tcfg.steps:
                    m = {k: float(v) for k, v in metrics.items()}
                    m.update(step=step, dt=round(dt, 4), straggler=slow)
                    self.history.append(m)
                    print(f"[trainer] step {step} loss {m['loss']:.4f} "
                          f"({dt*1e3:.0f} ms)" + (" STRAGGLER" if slow else ""))
                if tcfg.ckpt_dir and step % tcfg.ckpt_every == 0:
                    ckpt_lib.save(tcfg.ckpt_dir, step, self.state,
                                  extra={"data_step": self.data_step},
                                  keep=tcfg.keep)
            except (KeyboardInterrupt,):
                raise
            except RuntimeError as e:
                # fault-tolerance path: restore last checkpoint and retry
                if not (tcfg.ckpt_dir
                        and ckpt_lib.latest_step(tcfg.ckpt_dir) is not None):
                    raise
                print(f"[trainer] step {step} failed ({e}); restoring")
                like = jax.eval_shape(
                    lambda: self._fresh_state(jax.random.PRNGKey(0)))
                self.state, step, extra = ckpt_lib.restore(
                    tcfg.ckpt_dir, like, shardings=self.state_shardings)
                self.data_step = extra.get("data_step", step)
                self.fault_hook = None
        if tcfg.ckpt_dir:
            ckpt_lib.save(tcfg.ckpt_dir, step, self.state,
                          extra={"data_step": self.data_step},
                          keep=tcfg.keep)
        return self.history
