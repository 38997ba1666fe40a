"""Distributed trainer: step construction + fault-tolerant run loop.

Step construction supports four gradient-reduction modes (the C4 ablation
axis — see EXPERIMENTS.md §Perf):

  * ``gspmd``    — plain ``jit``; XLA inserts the DP all-reduce (baseline).
  * ``hier``     — ``shard_map`` (manual over pod+data, auto over model):
                   intra-pod reduce-scatter → inter-pod all-reduce →
                   intra-pod all-gather (paper C4, Ara's 3-step reduction).
  * ``hier_tree``— as ``hier`` with the inter-pod step as an explicit
                   ppermute butterfly (the slide-unit schedule, paper-exact).
  * ``hier_ef8`` — as ``hier`` with error-feedback int8 compression on the
                   inter-pod hop (beyond-paper; optim/compress.py).

Fault tolerance in the run loop: checkpoint-restart (atomic + async),
straggler detection (per-step EWMA with slack factor), and data that is a
pure function of the step index so restarts/elastic re-meshes never replay
or skip a batch.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import chaining, lanes, reduction
from repro.models import partition
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         cosine_schedule, ef_int8_init, ef_int8_compress_psum)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    num_steps: int = 100
    microbatches: int = 1
    reduction: str = "gspmd"          # gspmd | hier | hier_tree | hier_ef8
    remat: str = "full"               # none | full | dots
    zero1: bool = True
    peak_lr: float = 3e-4
    warmup_steps: int = 10
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    seed: int = 0
    # run-loop
    log_every: int = 10
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    ckpt_keep: int = 3
    straggler_slack: float = 2.0      # step > slack × EWMA ⇒ straggler event
    dispatch_depth: int = 2


# ---------------------------------------------------------------------------
# reduction-mode plumbing
# ---------------------------------------------------------------------------

def _flat_reduce(g: jax.Array, reduce_fn: Callable, data_size: int):
    """Flatten + pad so tiled reduce-scatter/all-gather divide evenly.

    The wire dtype is f32: gradient summation across up to 64 DP replicas in
    bf16 loses ~3 bits of mantissa (and the CPU XLA backend miscompiles bf16
    tiled collectives).  A bf16-wire variant is a §Perf iteration knob on
    real TPU hardware.
    """
    flat = g.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % data_size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    out = reduce_fn(flat)
    return out[: g.size].reshape(g.shape)


def _reduce_grads(grads, mode: str, *, pod_axis, data_axis, data_size,
                  ef_state=None):
    """Apply the selected hierarchical schedule to every gradient leaf."""
    if mode == "hier":
        fn = partial(reduction.hier_psum, pod_axis=pod_axis,
                     data_axis=data_axis)
        return jax.tree.map(
            lambda g: _flat_reduce(g, fn, data_size), grads), ef_state
    if mode == "hier_tree":
        fn = partial(reduction.hier_psum_tree, pod_axis=pod_axis,
                     data_axis=data_axis)
        return jax.tree.map(
            lambda g: _flat_reduce(g, fn, data_size), grads), ef_state
    if mode == "hier_ef8":
        # intra-pod exact reduce-scatter, int8 EF on the inter-pod hop only
        def one(g, e):
            def fn(flat_g_and_e):
                fg, fe = flat_g_and_e
                shard = lax.psum_scatter(fg, data_axis, scatter_dimension=0,
                                         tiled=True)
                eshard = fe   # residual is already shard-local
                if pod_axis is not None:
                    shard, eshard = ef_int8_compress_psum(
                        shard, eshard, pod_axis)
                full = lax.all_gather(shard, data_axis, axis=0, tiled=True)
                return full, eshard
            flat = g.reshape(-1).astype(jnp.float32)
            pad = (-flat.size) % data_size
            if pad:
                flat = jnp.pad(flat, (0, pad))
            full, eshard = fn((flat, e))
            return full[: g.size].reshape(g.shape), eshard
        flat_g, tdef = jax.tree.flatten(grads)
        flat_e = tdef.flatten_up_to(ef_state)
        outs = [one(g, e) for g, e in zip(flat_g, flat_e)]
        return (tdef.unflatten([o[0] for o in outs]),
                tdef.unflatten([o[1] for o in outs]))
    raise ValueError(f"unknown reduction mode {mode!r}")


def ef_state_template(params, mesh: Mesh, data_axis="data"):
    """EF residuals for hier_ef8: one flat (padded_size,) leaf per param.

    Stored sharded P(data): each data rank owns the residual of exactly the
    gradient shard it quantizes (the shard_map local view matches the
    psum_scatter output shard).
    """
    data_size = mesh.shape[data_axis]

    def leaf(p):
        n = int(np.prod(p.shape)) if p.ndim else 1
        padded = n + ((-n) % data_size)
        return jnp.zeros((padded,), jnp.float32)

    return jax.tree.map(leaf, params)


# ---------------------------------------------------------------------------
# train-step construction
# ---------------------------------------------------------------------------

def make_train_step(model, mesh: Mesh, tcfg: TrainConfig,
                    rules: Optional[lanes.LogicalRules] = None,
                    adamw: Optional[AdamWConfig] = None,
                    donate: bool = True):
    """Build the jitted train step for ``model`` on ``mesh``.

    Returns (step_fn, in_shardings_dict).  ``step_fn(params, opt, [ef,]
    batch) -> (params, opt, [ef,] metrics)``.
    """
    rules = (rules or lanes.LogicalRules()).for_mesh(mesh)
    adamw = adamw or AdamWConfig(weight_decay=tcfg.weight_decay,
                                 clip_norm=tcfg.clip_norm)
    lr_fn = partial(cosine_schedule, peak_lr=tcfg.peak_lr,
                    warmup_steps=tcfg.warmup_steps,
                    total_steps=tcfg.num_steps)
    pod_axis = "pod" if "pod" in mesh.axis_names else None
    data_axis = "data"
    data_size = mesh.shape[data_axis]
    batch_spec = rules.spec("batch", None)

    def loss_of(params, batch):
        loss, _ = model.loss_fn(params, batch, remat=tcfg.remat)
        return loss

    def grads_of(params, batch):
        return chaining.grad_accum_chained(
            loss_of, params, batch, num_microbatches=tcfg.microbatches)

    def finish(params, opt, loss, grads):
        lr = lr_fn(opt["step"])
        params, opt, metrics = adamw_update(params, grads, opt, lr, adamw)
        metrics.update(loss=loss, lr=lr)
        return params, opt, metrics

    if tcfg.reduction == "gspmd":
        def step(params, opt, batch):
            loss, grads = grads_of(params, batch)
            return finish(params, opt, loss, grads)
    else:
        # manual over (pod, data); model axis stays auto (GSPMD handles TP)
        dp_axes = tuple(a for a in (pod_axis, data_axis) if a)
        auto = frozenset(mesh.axis_names) - frozenset(dp_axes)
        rep_wrt_dp = P()              # params replicated w.r.t. DP axes

        if tcfg.reduction == "hier_ef8":
            def step(params, opt, ef, batch):
                def shard_fn(params, ef, batch):
                    loss, grads = grads_of(params, batch)
                    grads, ef = _reduce_grads(
                        grads, "hier_ef8", pod_axis=pod_axis,
                        data_axis=data_axis, data_size=data_size,
                        ef_state=ef)
                    loss = lax.pmean(loss, dp_axes)
                    return loss, grads, ef

                ef_spec = jax.tree.map(lambda _: P(data_axis), ef)
                loss, grads, ef = jax.shard_map(
                    shard_fn, mesh=mesh,
                    in_specs=(rep_wrt_dp, ef_spec, batch_spec),
                    out_specs=(P(), rep_wrt_dp, ef_spec),
                    check_vma=False, axis_names=set(dp_axes))(
                        params, ef, batch)
                params, opt, metrics = finish(params, opt, loss, grads)
                return params, opt, ef, metrics
        else:
            mode = tcfg.reduction

            def step(params, opt, batch):
                def shard_fn(params, batch):
                    loss, grads = grads_of(params, batch)
                    grads, _ = _reduce_grads(
                        grads, mode, pod_axis=pod_axis, data_axis=data_axis,
                        data_size=data_size)
                    loss = lax.pmean(loss, dp_axes)
                    return loss, grads

                loss, grads = jax.shard_map(
                    shard_fn, mesh=mesh,
                    in_specs=(rep_wrt_dp, batch_spec),
                    out_specs=(P(), rep_wrt_dp),
                    check_vma=False, axis_names=set(dp_axes))(params, batch)
                return finish(params, opt, loss, grads)

    # shardings for jit
    aparams = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pspecs = partition.param_specs(aparams, rules, mesh=mesh)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    ospecs = {
        "m": partition.opt_state_specs(aparams, rules, zero1=tcfg.zero1,
                                       mesh=mesh),
        "v": partition.opt_state_specs(aparams, rules, zero1=tcfg.zero1,
                                       mesh=mesh),
        "step": P(),
    }
    oshard = jax.tree.map(lambda s: NamedSharding(mesh, s), ospecs,
                          is_leaf=lambda x: isinstance(x, P))
    bshard = NamedSharding(mesh, batch_spec)
    shardings = {"params": pshard, "opt": oshard, "batch": bshard}

    if tcfg.reduction == "hier_ef8":
        ef_t = jax.eval_shape(
            lambda: ef_state_template(aparams, mesh, data_axis))
        efshard = jax.tree.map(
            lambda _: NamedSharding(mesh, P(data_axis)), ef_t)
        shardings["ef"] = efshard
        jstep = jax.jit(
            step,
            in_shardings=(pshard, oshard, efshard, bshard),
            out_shardings=(pshard, oshard, efshard, None),
            donate_argnums=(0, 1, 2) if donate else ())
    else:
        jstep = jax.jit(
            step,
            in_shardings=(pshard, oshard, bshard),
            out_shardings=(pshard, oshard, None),
            donate_argnums=(0, 1) if donate else ())
    return jstep, shardings


# ---------------------------------------------------------------------------
# straggler monitor
# ---------------------------------------------------------------------------

class StragglerMonitor:
    """Per-step wall-time EWMA; flags steps slower than ``slack``× the mean.

    On a real cluster the flag feeds the controller's replica-eviction /
    re-mesh hook (see ``elastic.elastic_remesh``); here it is recorded in
    the trainer metrics (and asserted on in tests via a fault-injection
    hook).
    """

    def __init__(self, *, slack: float = 2.0, alpha: float = 0.1):
        self.slack = slack
        self.alpha = alpha
        self.ewma: Optional[float] = None
        self.events: list[tuple[int, float, float]] = []

    def observe(self, step: int, dt: float) -> bool:
        is_straggler = (self.ewma is not None
                        and dt > self.slack * self.ewma)
        if is_straggler:
            self.events.append((step, dt, self.ewma))
        else:   # stragglers don't poison the baseline estimate
            self.ewma = dt if self.ewma is None \
                else (1 - self.alpha) * self.ewma + self.alpha * dt
        return is_straggler


# ---------------------------------------------------------------------------
# run loop
# ---------------------------------------------------------------------------

class Trainer:
    """Checkpoint-restarting training driver for one model bundle."""

    def __init__(self, model, mesh: Mesh, tcfg: TrainConfig,
                 rules: Optional[lanes.LogicalRules] = None):
        self.model = model
        self.mesh = mesh
        self.tcfg = tcfg
        self.rules = (rules or lanes.LogicalRules()).for_mesh(mesh)
        self.step_fn, self.shardings = make_train_step(
            model, mesh, tcfg, rules=self.rules)
        self.monitor = StragglerMonitor(slack=tcfg.straggler_slack)
        self._ckpt = None
        if tcfg.ckpt_dir:
            from repro.checkpoint import CheckpointManager
            self._ckpt = CheckpointManager(tcfg.ckpt_dir, keep=tcfg.ckpt_keep)

    # -- state ---------------------------------------------------------------
    def init_state(self) -> dict:
        key = jax.random.PRNGKey(self.tcfg.seed)
        with jax.set_mesh(self.mesh):
            params = jax.jit(
                self.model.init,
                out_shardings=self.shardings["params"])(key)
            opt = jax.jit(
                adamw_init, out_shardings=self.shardings["opt"])(params)
        state = {"params": params, "opt": opt}
        if self.tcfg.reduction == "hier_ef8":
            state["ef"] = jax.jit(
                lambda p: ef_state_template(p, self.mesh),
                out_shardings=self.shardings["ef"])(params)
        return state

    def state_shardings(self, state):
        out = {"params": self.shardings["params"],
               "opt": self.shardings["opt"]}
        if "ef" in state:
            out["ef"] = self.shardings["ef"]
        return out

    def abstract_state(self) -> dict:
        """ShapeDtypeStruct pytree matching ``init_state`` (no allocation)."""
        key = jax.random.PRNGKey(self.tcfg.seed)
        params = jax.eval_shape(self.model.init, key)
        state = {"params": params, "opt": jax.eval_shape(adamw_init, params)}
        if self.tcfg.reduction == "hier_ef8":
            state["ef"] = jax.eval_shape(
                lambda p: ef_state_template(p, self.mesh), params)
        return state

    # -- checkpointing ---------------------------------------------------------
    def maybe_restore(self):
        """(state, start_step): restored or fresh."""
        template = self.abstract_state()
        if self._ckpt is not None:
            state, meta, step = self._ckpt.restore_latest(
                template, shardings=self.state_shardings(template))
            if state is not None:
                return state, int(meta["step"])
        return self.init_state(), 0

    # -- the loop --------------------------------------------------------------
    def run(self, batches, *, start_step: int = 0, state: Optional[dict] = None,
            hooks: Optional[list[Callable]] = None) -> dict:
        """Train until tcfg.num_steps. ``batches``: iterator of device
        batches aligned with ``start_step``.  Returns the final state (with
        host metrics history under "_history")."""
        tcfg = self.tcfg
        if state is None:
            state, start_step = self.maybe_restore()
        history = []
        it = iter(batches)
        with jax.set_mesh(self.mesh):
            for step in range(start_step, tcfg.num_steps):
                batch = next(it)
                t0 = time.perf_counter()
                if "ef" in state:
                    p, o, e, metrics = self.step_fn(
                        state["params"], state["opt"], state["ef"], batch)
                    state = {"params": p, "opt": o, "ef": e}
                else:
                    p, o, metrics = self.step_fn(
                        state["params"], state["opt"], batch)
                    state = {"params": p, "opt": o}
                jax.block_until_ready(metrics["loss"])
                dt = time.perf_counter() - t0
                straggler = self.monitor.observe(step, dt)
                if hooks:
                    for h in hooks:
                        h(step, state, metrics)
                if step % tcfg.log_every == 0 or straggler:
                    rec = {k: float(v) for k, v in metrics.items()}
                    rec.update(step=step, dt=dt, straggler=straggler)
                    history.append(rec)
                if (self._ckpt is not None and step > 0
                        and step % tcfg.ckpt_every == 0):
                    self._ckpt.save(step + 1, state, meta={"step": step + 1})
        if self._ckpt is not None:
            self._ckpt.save(tcfg.num_steps, state,
                            meta={"step": tcfg.num_steps})
            self._ckpt.wait()
        state["_history"] = history
        return state
