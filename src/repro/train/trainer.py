"""The distributed training step: manual-SPMD end to end.

Structure of one step (all collectives through ``repro.core.api``):

1. microbatch scan with gradient accumulation (overlaps the per-microbatch
   backward reduce-scatters with the next microbatch's compute under XLA's
   latency-hiding scheduler),
2. FSDP: per-layer all-gather fwd / reduce-scatter bwd (custom VJPs in
   dist/ops.py) — grads for "data"-sharded leaves arrive already summed
   over the data axis,
3. cross-pod sync: one tunable all-reduce over the "pod" axis per leaf —
   combined with (2) this IS the hierarchical RS→AR→AG schedule, at 1/|data|
   of the naive cross-pod payload,  optionally compressed to bf16,
4. replicated-leaf grads pmean'd over "data",
5. optimizer update (sharded states).

The paper's tuning enters at trace time: pass ``profiles=`` (offline-tuned
``ProfileStore``) or ``force={"allreduce": "allreduce_as_rsb_allgather"}``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core import api
from repro.dist.axes import AXES, axis_size_or_1, has_axis
from repro.models import lm
from repro.models.config import ModelConfig
from repro.models.params import (ParamSpec, init_tree, tree_map_specs,
                                 tree_pspecs)
from repro.optim import get_optimizer, lr_schedule


# ---------------------------------------------------------------------------
# gradient finalization
# ---------------------------------------------------------------------------


def finalize_grads(grads, spec_tree, *, compress: str = "none"):
    """Cross-shard gradient reduction (see module docstring)."""
    d = axis_size_or_1(AXES.data)
    pod = axis_size_or_1(AXES.pod)

    def fin(g, spec: ParamSpec):
        fsdp = "data" in spec.dims
        if has_axis(AXES.data) and not fsdp:
            g = api.allreduce(g, AXES.data)
        if has_axis(AXES.pod):
            if compress == "bf16":
                g = api.allreduce(g.astype(jnp.bfloat16), AXES.pod).astype(
                    jnp.float32)
            else:
                g = api.allreduce(g, AXES.pod)
        return g / (d * pod if not fsdp else pod)

    return _map_with_specs(fin, grads, spec_tree)


def _map_with_specs(fn, tree, spec_tree):
    flat_s, treedef = jax.tree.flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))
    flat_t = treedef.flatten_up_to(tree)
    return jax.tree.unflatten(treedef, [fn(t, s) for t, s in
                                        zip(flat_t, flat_s)])


def _replicas(spec: ParamSpec) -> int:
    """How many devices of the bound axes hold the same shard of a leaf."""
    n = 1
    for ax in (AXES.data, AXES.model, AXES.pod):
        if ax not in spec.dims:
            n *= axis_size_or_1(ax)
    return n


def _fsdp_mean(grads, spec_tree):
    """FSDP leaves got SUM over data from the reduce-scatter; divide."""
    d = axis_size_or_1(AXES.data)

    def fin(g, spec: ParamSpec):
        return g / d if "data" in spec.dims else g

    return _map_with_specs(fin, grads, spec_tree)


# ---------------------------------------------------------------------------
# optimizer-state sharding
# ---------------------------------------------------------------------------


def opt_state_pspecs(opt_name: str, spec_tree):
    """PartitionSpecs of the optimizer state, mirroring the params."""
    if opt_name == "adamw":
        ms = tree_map_specs(lambda s: s.pspec(), spec_tree)
        return {"m": ms, "v": ms, "count": P()}
    if opt_name == "adafactor":
        def fac(s: ParamSpec):
            if len(s.shape) >= 2:
                return {"vr": P(*s.dims[:-1]),
                        "vc": P(*(s.dims[:-2] + s.dims[-1:]))}
            return {"v": s.pspec()}
        return {"f": tree_map_specs(fac, spec_tree), "count": P()}
    raise ValueError(opt_name)


# ---------------------------------------------------------------------------
# step functions (to be wrapped in shard_map by the caller)
# ---------------------------------------------------------------------------


def make_grad_fn(cfg: ModelConfig, *, n_micro: int = 1,
                 compress: str = "none"):
    """Returns grad_fn(params, batch) -> (loss, grads) on SHARD-LOCAL
    values: this shard's mean loss, and the global mean gradient of every
    leaf, synchronized across shards and sharded like the params."""

    def grad_fn(params, batch):
        specs = lm.model_specs(cfg, axis_size_or_1(AXES.model))

        def loss_of(p, mb):
            return lm.loss_fn(p, cfg, mb)[0]

        if n_micro > 1:
            def micro(carry, mb):
                acc, = carry
                l, g = jax.value_and_grad(loss_of)(params, mb)
                acc = jax.tree.map(jnp.add, acc,
                                   jax.tree.map(
                                       lambda x: x / n_micro, g))
                return (acc,), l

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params)
            mbs = jax.tree.map(
                lambda x: x.reshape(n_micro, x.shape[0] // n_micro,
                                    *x.shape[1:]), batch)
            (grads,), losses = lax.scan(micro, (zeros,), mbs)
            loss = jnp.mean(losses)
        else:
            loss, grads = jax.value_and_grad(loss_of)(params, batch)

        # grad sync is backward-phase traffic: the trace-replay tuner may
        # give these allreduces a different profile than fwd collectives
        with jax.named_scope("grad_sync"), api.phase("bwd"):
            grads = _fsdp_mean(grads, specs)
            grads = finalize_grads(grads, specs, compress=compress)
        return loss, grads

    return grad_fn


def make_step_fns(cfg: ModelConfig, *, n_micro: int = 1,
                  compress: str = "none", base_lr: float = 3e-4,
                  warmup: int = 100, total_steps: int = 10_000):
    """Returns (init_fn, train_fn) operating on SHARD-LOCAL values.

    init_fn(key)                     -> (params, opt_state)
    train_fn(params, opt, batch, i)  -> (params, opt, metrics)
    """
    opt_init, opt_update = get_optimizer(cfg.optimizer)
    grad_fn = make_grad_fn(cfg, n_micro=n_micro, compress=compress)

    def spec_tree():
        return lm.model_specs(cfg, axis_size_or_1(AXES.model))

    def init_fn(key):
        params = init_tree(spec_tree(), key)
        return params, opt_init(params)

    def train_fn(params, opt_state, batch, step_idx):
        specs = spec_tree()
        loss, grads = grad_fn(params, batch)
        lr = lr_schedule(step_idx, base_lr=base_lr, warmup=warmup,
                         total=total_steps)
        with jax.named_scope("optimizer"):
            params, opt_state = opt_update(grads, opt_state, params, lr=lr)

        # metrics: global mean loss + grad-norm (cheap diagnostics); each
        # leaf's local sum is split over its replicas, so that the psums
        # below count every global element once
        gsq = sum(jax.tree.leaves(_map_with_specs(
            lambda g, s: jnp.sum(jnp.square(g.astype(jnp.float32)))
            / _replicas(s), grads, specs)))
        for ax in (AXES.data, AXES.model, AXES.pod):
            if has_axis(ax):
                gsq = api.allreduce(gsq[None], ax)[0]
                if ax == AXES.data:
                    loss = api.allreduce(loss[None], ax)[0] / \
                        axis_size_or_1(ax)
                if ax == AXES.pod:
                    loss = api.allreduce(loss[None], ax)[0] / \
                        axis_size_or_1(ax)
        metrics = {"loss": loss, "grad_norm": jnp.sqrt(gsq), "lr": lr}
        return params, opt_state, metrics

    return init_fn, train_fn


# ---------------------------------------------------------------------------
# host-side trainer (single- or multi-device via shard_map)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Trainer:
    cfg: ModelConfig
    mesh: Mesh | None = None
    n_micro: int = 1
    compress: str = "none"
    profiles: Any = None
    phase_profiles: dict | None = None   # phase tag -> ProfileStore
    force: dict | None = None
    base_lr: float = 3e-4
    warmup: int = 100
    record: list | None = None           # shared dispatch-record sink
    # executables built by ``step`` calls after the first, and the step
    # that built the last of them
    recompiles: int = dataclasses.field(default=0, init=False)
    recompile_step: int | None = dataclasses.field(default=None, init=False)
    _stepped: bool = dataclasses.field(default=False, init=False)

    def _tuned(self):
        return api.tuned(profiles=self.profiles,
                         phase_profiles=self.phase_profiles,
                         force=self.force, record=self.record)

    def __post_init__(self):
        from jax import shard_map
        self.tp = (self.mesh.shape.get("model", 1) if self.mesh else 1)
        self.specs = lm.model_specs(self.cfg, self.tp)
        self.pspecs = tree_pspecs(self.specs)
        opt_ps = opt_state_pspecs(self.cfg.optimizer, self.specs)
        init_fn, train_fn = make_step_fns(self.cfg, n_micro=self.n_micro,
                                          compress=self.compress,
                                          base_lr=self.base_lr,
                                          warmup=self.warmup)
        dp_axes = self._dp_axes()
        batch_p = P(dp_axes)

        if self.mesh is None:
            self._init = jax.jit(init_fn)
            self._step = jax.jit(train_fn, donate_argnums=(0, 1))
            return

        with self._tuned():
            sm_init = shard_map(
                init_fn, mesh=self.mesh, in_specs=P(),
                out_specs=(self.pspecs, opt_ps), check_vma=False)

            def batch_specs_tree(batch):
                return jax.tree.map(lambda _: batch_p, batch)

            def step(params, opt, batch, i):
                sm = shard_map(
                    train_fn, mesh=self.mesh,
                    in_specs=(self.pspecs, opt_ps,
                              batch_specs_tree(batch), P()),
                    out_specs=(self.pspecs, opt_ps,
                               {"loss": P(), "grad_norm": P(), "lr": P()}),
                    check_vma=False)
                return sm(params, opt, batch, i)

            self._init = jax.jit(sm_init)
            self._step = jax.jit(step, donate_argnums=(0, 1))

    def _dp_axes(self):
        if self.mesh is None:
            return None
        axes = [a for a in ("pod", "data") if a in self.mesh.shape]
        return tuple(axes) if axes else None

    def init(self, seed: int = 0):
        with self._tuned():
            return self._init(jax.random.key(seed))

    def step(self, params, opt_state, batch, i):
        built, _ = obs.compiles()
        with jax.profiler.TraceAnnotation("train.step", step=i), \
                self._tuned():
            out = self._step(params, opt_state, batch,
                             jnp.asarray(i, jnp.int32))
        built = obs.compiles()[0] - built
        if built and self._stepped:
            self.recompiles += built
            self.recompile_step = i
        self._stepped = True
        return out

    def put_batch(self, batch):
        with jax.profiler.TraceAnnotation("train.put_batch"):
            if self.mesh is None:
                return jax.tree.map(jnp.asarray, batch)
            sp = NamedSharding(self.mesh, P(self._dp_axes()))
            return jax.tree.map(lambda x: jax.device_put(x, sp), batch)
