"""Training loop: jitted step, grad accumulation, checkpoints, recovery.

Single-host by default (CPU tests / examples); the same step function is
what ``launch/dryrun.py`` lowers onto the production meshes.  Fault
tolerance: every ``ckpt_every`` steps an async atomic checkpoint is
written; ``run`` auto-resumes from the latest complete checkpoint, and
the failure-injection hook lets tests kill the loop mid-step and verify
bitwise-identical resume (see tests/test_train_fault.py).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.train import optim
from repro.train.compress import CompressionConfig, compress_decompress, \
    init_residual


@dataclasses.dataclass
class TrainConfig:
    steps: int = 100
    ckpt_every: int = 20
    ckpt_dir: Optional[str] = None
    keep: int = 3
    log_every: int = 10
    accum: int = 1                       # gradient accumulation
    compression: CompressionConfig = CompressionConfig("none")
    remat: bool = False
    seed: int = 0


class Trainer:
    def __init__(self, cfg: ModelConfig, tcfg: TrainConfig,
                 ocfg: Optional[optim.AdamWConfig] = None):
        # one host: no mesh axis splits query heads, so it trains the
        # published ones
        self.cfg = dataclasses.replace(cfg, head_shards=1)
        self.tcfg = tcfg
        self.ocfg = ocfg or optim.AdamWConfig(
            warmup_steps=max(tcfg.steps // 10, 1),
            total_steps=tcfg.steps)
        self.ckpt = (CheckpointManager(tcfg.ckpt_dir, keep=tcfg.keep)
                     if tcfg.ckpt_dir else None)
        self.metrics: List[Dict[str, float]] = []
        self._build()

    # ------------------------------------------------------------------ #
    def _build(self):
        cfg, ocfg, tcfg = self.cfg, self.ocfg, self.tcfg

        def micro_loss(params, tokens, targets):
            return M.loss_fn(params, cfg, tokens, targets,
                             remat=tcfg.remat)

        def train_step(params, opt_state, residual, batch):
            if tcfg.accum > 1:
                B = batch["tokens"].shape[0]
                mb = B // tcfg.accum
                def one(i, acc):
                    g_acc, l_acc = acc
                    sl = lambda x: jax.lax.dynamic_slice_in_dim(
                        x, i * mb, mb, axis=0)
                    l, g = jax.value_and_grad(micro_loss)(
                        params, sl(batch["tokens"]), sl(batch["targets"]))
                    g_acc = jax.tree_util.tree_map(
                        lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                    return g_acc, l_acc + l
                g0 = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                grads, loss = jax.lax.fori_loop(
                    0, tcfg.accum, one, (g0, jnp.zeros(())))
                grads = jax.tree_util.tree_map(
                    lambda g: g / tcfg.accum, grads)
                loss = loss / tcfg.accum
            else:
                loss, grads = jax.value_and_grad(micro_loss)(
                    params, batch["tokens"], batch["targets"])
            # cross-pod gradient compression (EF) before the slow
            # all-reduce; on one host this is the identity wire format.
            grads, residual = compress_decompress(
                tcfg.compression, grads, residual)
            params, opt_state = optim.apply(ocfg, grads, opt_state,
                                            params)
            return params, opt_state, residual, loss

        # No donation here: with fp32 params the master copy and the
        # params tree alias the same buffers (astype is a no-op and XLA
        # CSEs identical outputs), and donating an aliased buffer twice
        # is a runtime error.  The production (dry-run) train step relies
        # on XLA's SPMD buffer reuse instead.
        self.train_step = jax.jit(train_step)

    # ------------------------------------------------------------------ #
    def init_state(self, key=None):
        params = M.init_params(self.cfg, key or jax.random.PRNGKey(
            self.tcfg.seed))
        opt_state = optim.init(self.ocfg, params)
        grads0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        residual = init_residual(grads0)
        return {"params": params, "opt": opt_state,
                "residual": residual}

    def run(self, batches, state=None, start_step: int = 0,
            fail_at: Optional[int] = None) -> Dict[str, Any]:
        """Train from ``start_step``.  ``fail_at`` raises a simulated
        hardware failure AFTER that step's checkpointing window — the
        fault-tolerance tests restart with ``resume()``."""
        if state is None:
            state = self.init_state()
        params, opt_state, residual = (state["params"], state["opt"],
                                       state["residual"])
        t0 = time.perf_counter()
        step = start_step
        for step in range(start_step, self.tcfg.steps):
            batch = batches.batch_at(step)
            params, opt_state, residual, loss = self.train_step(
                params, opt_state, residual, batch)
            if step % self.tcfg.log_every == 0 or \
                    step == self.tcfg.steps - 1:
                self.metrics.append({"step": step,
                                     "loss": float(loss),
                                     "t": time.perf_counter() - t0})
            if self.ckpt and (step + 1) % self.tcfg.ckpt_every == 0:
                self.ckpt.save_async(
                    step + 1,
                    {"params": params, "opt": opt_state,
                     "residual": residual})
            if fail_at is not None and step + 1 == fail_at:
                if self.ckpt:
                    self.ckpt.wait()
                raise SimulatedFailure(step + 1)
        if self.ckpt:
            self.ckpt.wait()
        return {"params": params, "opt": opt_state, "residual": residual,
                "last_step": step}

    def resume(self, batches) -> Dict[str, Any]:
        """Auto-resume from the latest checkpoint and finish training."""
        assert self.ckpt is not None
        template = self.init_state()
        step, state = self.ckpt.restore(template)
        return self.run(batches, state=state, start_step=step)


class SimulatedFailure(RuntimeError):
    def __init__(self, step: int):
        super().__init__(f"simulated node failure at step {step}")
        self.step = step
