"""Elastic trainer — the Nanos++-analogue runtime driving malleable jobs.

The training loop exposes *reconfiguration points* at step boundaries: every
``check_period`` steps it calls the DMR API; on EXPAND/SHRINK it rebuilds
the mesh to the granted slice count and reshards the entire TrainState
(params + AdamW moments + RNG + step) via ``repro.core.reshard`` —
runtime data redistribution, not checkpoint restart.  Checkpoint/restart
is the *fault* path: a runtime failure of a step restores the last
checkpoint, onto a smaller mesh if devices were lost (shrink-to-survivors).
A failure that comes back before the run has passed the step where it
first struck (a compile error, an out-of-memory) is re-raised as it is.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import (DMR, TP_DP_RULES, Action, ReshardPlan, make_mesh,
                        mesh_num_slices, moved_bytes, plan_reshard, reshard)
from repro.core.sharding import logical_to_sharding, tracing_mesh
from repro.data import DataConfig, SyntheticLMData
from repro.checkpoint.store import CheckpointStore
from repro.optim import AdamWConfig, apply_updates, init_state, state_logical


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    grad_accum: int = 1
    check_period: int = 10            # steps between reconfiguration points
    min_slices: int = 1
    max_slices: int = 8
    # slices the job starts on; None = as many as the devices hold, up to
    # max_slices (an elastic job starts on what the RMS granted it)
    slices: Optional[int] = None
    factor: int = 2
    preferred: Optional[int] = None
    model_ways: int = 1               # TP width inside a slice
    ckpt_dir: Optional[str] = None
    ckpt_period: int = 50
    log_period: int = 10
    donate: bool = True


class ElasticTrainer:
    def __init__(self, model, opt_cfg: AdamWConfig, data_cfg: DataConfig,
                 cfg: TrainerConfig, rms=None, job_id: int = 0,
                 devices=None):
        self.model = model
        self.opt_cfg = opt_cfg
        self.data = SyntheticLMData(data_cfg)
        self.data_cfg = data_cfg
        self.cfg = cfg
        self.devices = devices if devices is not None else jax.devices()
        self.slices = cfg.slices or min(cfg.max_slices,
                                        len(self.devices) // cfg.model_ways)
        self.mesh = make_mesh(self.slices, cfg.model_ways,
                              devices=self.devices)
        self.dmr = DMR(rms, job_id, current_slices=self.slices) \
            if rms is not None else None
        self.store = CheckpointStore(cfg.ckpt_dir) if cfg.ckpt_dir else None
        self._step_cache: Dict[int, Callable] = {}
        self._stepped: set = set()     # step functions called at least once
        # (from, to) -> the bytes a resize moves, and how it moves them
        self._plans: Dict[Tuple[int, int], Tuple[int, ReshardPlan]] = {}
        self.metrics: list = []
        self.resize_log: list = []

    # -- sharding ------------------------------------------------------------

    def _state_shardings(self, mesh):
        logical = {
            "params": self.model.logical(),
            "opt": state_logical(
                self.model.logical(),
                jax.tree.map(lambda s: s.shape, self.model.specs(),
                             is_leaf=lambda x: hasattr(x, "shape")
                             and hasattr(x, "logical")),
                mesh, TP_DP_RULES),
            "rng": (None,),
            "step": (),
        }
        shapes = {
            "params": jax.tree.map(lambda s: s.shape, self.model.specs(),
                                   is_leaf=lambda x: hasattr(x, "logical")),
            "opt": {"mu": jax.tree.map(
                        lambda s: s.shape, self.model.specs(),
                        is_leaf=lambda x: hasattr(x, "logical")),
                    "nu": jax.tree.map(
                        lambda s: s.shape, self.model.specs(),
                        is_leaf=lambda x: hasattr(x, "logical")),
                    "step": ()},
            "rng": (2,),
            "step": (),
        }
        return logical_to_sharding(logical, shapes, mesh, TP_DP_RULES)

    def _batch_shardings(self, mesh):
        spec = {"tokens": P(("pod", "data")), "labels": P(("pod", "data"))}
        if self.data_cfg.frontend:
            spec["frontend"] = P(("pod", "data"))
        return jax.tree.map(
            lambda s: NamedSharding(
                mesh, P(*[ax if isinstance(ax, str) else tuple(
                    a for a in ax if a in mesh.shape) or None
                    for ax in s])), spec)

    # -- state ---------------------------------------------------------------

    def _fresh_state(self, seed: int):
        params = self.model.init(jax.random.PRNGKey(seed))
        return {"params": params, "opt": init_state(params),
                "rng": jax.random.PRNGKey(seed + 1),
                "step": jnp.zeros((), jnp.int32)}

    def init_state(self, seed: int = 0):
        shardings = self._state_shardings(self.mesh)
        with self.mesh:
            state = jax.jit(lambda: self._fresh_state(seed),
                            out_shardings=shardings)()
        return state

    def restore(self, step: int):
        """Load checkpoint ``step`` onto the current mesh.  The template is
        abstract (``eval_shape``): nothing but the restored state is placed
        on the devices."""
        template = jax.eval_shape(lambda: self._fresh_state(0))
        return self.store.restore(step, template,
                                  self._state_shardings(self.mesh))

    # -- the jitted step -------------------------------------------------------

    def _build_step(self, mesh):
        model, opt_cfg, accum = self.model, self.opt_cfg, self.cfg.grad_accum
        shardings = self._state_shardings(mesh)
        batch_sh = self._batch_shardings(mesh)

        def loss_fn(params, batch):
            loss, parts = model.loss(params, batch)
            return loss, parts

        def step(state, batch):
            if accum > 1:
                def micro(c, mb):
                    (loss, parts), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(state["params"], mb)
                    g_acc = jax.tree.map(jnp.add, c[0], grads)
                    return (g_acc, c[1] + loss), None
                mbs = jax.tree.map(
                    lambda x: x.reshape((accum, -1) + x.shape[1:]), batch)
                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32),
                    state["params"])
                (grads, loss), _ = jax.lax.scan(
                    micro, (zeros, jnp.zeros((), jnp.float32)), mbs)
                grads = jax.tree.map(lambda g: g / accum, grads)
                loss = loss / accum
                counters = {}
            else:
                (loss, parts), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(state["params"], batch)
                counters = parts.get("counters", {})
            with jax.named_scope("adamw"):
                params, opt, metrics = apply_updates(
                    opt_cfg, state["params"], grads, state["opt"])
            new_state = {"params": params, "opt": opt,
                         "rng": jax.random.fold_in(state["rng"], 0),
                         "step": state["step"] + 1}
            # the model's counters (MoE: moe_held_rows, moe_load_max) come
            # with a single-batch step
            metrics = dict(metrics, loss=loss, **counters)
            return new_state, metrics

        def train_step(state, batch):
            # the model sees the mesh it is traced for (its kernels pick
            # their platform by it)
            with tracing_mesh(mesh):
                return step(state, batch)

        donate = (0,) if self.cfg.donate else ()
        return jax.jit(train_step, in_shardings=(shardings, batch_sh),
                       out_shardings=(shardings, None),
                       donate_argnums=donate)

    def step_fn(self, mesh):
        key = mesh_num_slices(mesh)
        if key not in self._step_cache:
            self._step_cache[key] = self._build_step(mesh)
        return self._step_cache[key]

    # -- reconfiguration (the paper's §5.2 protocol) -----------------------------

    def maybe_reconfigure(self, state):
        if self.dmr is None:
            return state
        action, new_slices, handler = self.dmr.check_status(
            minimum=self.cfg.min_slices, maximum=self.cfg.max_slices,
            factor=self.cfg.factor, preferred=self.cfg.preferred)
        if action is Action.NO_ACTION:
            return state
        # the step is on the enclosing ``train.reconfigure`` span
        span = {"action": action.name, "from": self.slices, "to": new_slices}
        layouts = (self.slices, new_slices)
        t0 = time.perf_counter()
        with TraceAnnotation("reshard.plan", **span):
            new_mesh = make_mesh(new_slices, self.cfg.model_ways,
                                 devices=self.devices)
            new_shardings = self._state_shardings(new_mesh)
            if layouts not in self._plans:
                self._plans[layouts] = (moved_bytes(state, new_shardings),
                                        plan_reshard(state, new_shardings))
            moved, plan = self._plans[layouts]
        t1 = time.perf_counter()
        # the wait for the steps queued before this point stays inside the
        # transfer: on TPU v5e, with the profiler on, a state drained first
        # moved about three times faster than untraced, so a traced resize
        # would not be the one an untraced job gets
        with TraceAnnotation("reshard.transfer", **span):
            state = reshard(state, new_shardings, plan=plan, span=span)
            jax.block_until_ready(state)
        t2 = time.perf_counter()
        if handler is not None:
            handler.new_mesh = new_mesh
            handler.resize_time_s = t2 - t0
            handler.transfer_s = t2 - t1
            handler.moved_bytes = moved
            handler.host_leaves = plan.host_leaves
        self.resize_log.append(
            {"step": int(state["step"]), "action": action.name,
             "from": self.slices, "to": new_slices, "resize_s": t2 - t0,
             "host_leaves": plan.host_leaves})
        self.mesh = new_mesh
        self.slices = new_slices
        return state

    # -- loop -----------------------------------------------------------------

    def train(self, state=None, seed: int = 0,
              on_step: Optional[Callable[[int], None]] = None):
        """Run to ``cfg.steps``.  ``on_step(step)`` is called before each
        step's reconfiguration point (e.g. to script the cluster)."""
        if state is None:
            state = self.init_state(seed)
        start = int(state["step"])
        step = start
        failed_at = None
        while step < self.cfg.steps:
            if on_step is not None:
                on_step(step)
            if self.dmr is not None and step > start and \
                    step % self.cfg.check_period == 0:
                # the old layout's buffers are released at the rebinding
                with TraceAnnotation("train.reconfigure", step=step):
                    state = self.maybe_reconfigure(state)
            with TraceAnnotation("train.batch", step=step):
                batch = self.data.batch(step)
            fn = self.step_fn(self.mesh)
            # a step function's first call traces and compiles it
            name = "train.step" if fn in self._stepped else "train.compile"
            try:
                with TraceAnnotation(name, step=step), self.mesh:
                    state, metrics = fn(state, batch)
            except jax.errors.JaxRuntimeError:
                restart = self._restart_step()
                if restart is None or (failed_at is not None
                                       and step <= failed_at):
                    raise
                failed_at = step
                with TraceAnnotation("train.restore", step=restart):
                    state = self.restore(restart)
                step = restart
                continue
            self._stepped.add(fn)
            step += 1
            if step % self.cfg.log_period == 0 or step == self.cfg.steps:
                with TraceAnnotation("train.log_sync", step=step):
                    m = {k: float(v) for k, v in metrics.items()}
                m["step"] = step
                m["slices"] = self.slices
                self.metrics.append(m)
            if self.store is not None and step % self.cfg.ckpt_period == 0:
                with TraceAnnotation("train.save", step=step):
                    self.store.save_async(step, state)
        if self.store is not None:
            self.store.wait()
        return state

    def _restart_step(self) -> Optional[int]:
        """Fault path: the checkpoint to restore onto the current (possibly
        shrunken) mesh, or None when there is none to restore."""
        if self.store is None:
            return None
        self.store.wait()
        return self.store.latest_step()
