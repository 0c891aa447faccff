"""Whole training steps of a zoo model through the program's trainer.

The entry is ``repro.train.Trainer.step``, built as ``launch/train.py``
builds it: the (data, model) mesh of the traffic file, tuned dispatch
with the profiles the program resolves itself.  The benchmark makes the
weights on the device from the seed (the reference module's
``init_leaf``, in the dtypes of the program's parameter layout) and zero
AdamW state.

Set-up drives that one step object through its first ``CHECK_STEPS``
steps on the seed's batches and keeps what the check needs: each step's
loss, the norm of each leaf's first gradient as the optimizer got it
(AdamW's first moment after one step, over ``1 - b1``), and the norm of
each leaf's change over the three steps.  The window then goes on with
the same object.  After the window the reference (float32, the
configuration's optimizer, parameters rounded to their stored dtype after
each update) runs the same three steps.  ``gaps`` gives every number a
run can compare; the cell's limits file names the ones it does:

* ``loss_gap``, ``loss_worst``   relative gap of the first step's loss,
                                 and the largest of the three steps';
* ``grad_gap``                   relative gap of the first gradient's
                                 norm, all leaves together;
* ``grad_median``, ``grad_worst``  the median and the worst leaf's gap of
                                 first-gradient norms;
* ``change_gap``, ``change_worst``  the median and the worst leaf's gap of
                                 change norms, over the leaves whose
                                 reference gradient is at least a
                                 thousandth of the median leaf's (leaves
                                 that move by round-off alone are left out
                                 by that rule, not by name).

A leaf's gap is measured against the larger of its reference norm and the
median leaf's, so that a leaf whose gradient is all but zero does not
dominate.  The numbers not compared are printed beside the others;
PERF.md (section 6) gives the readings each compared number was chosen
and limited by.
"""
from __future__ import annotations

import collections
import dataclasses
import importlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import gen
from bench import trace as trace_mod
from bench.seeds import seed_key

CHECK_STEPS = 3
IN_FLIGHT = 4
F32 = jnp.float32


def _name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


@jax.jit
def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
            for x in jax.tree.leaves(tree)]


@jax.jit
def change_norms(new, old):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(F32) - b.astype(F32))))
            for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old))]


def stored(x, dtype):
    """``x`` rounded to what a parameter of ``dtype`` holds, kept in
    float32.  ``reduce_precision`` and not a round trip through ``dtype``:
    on the TPU the compiler may keep the excess precision of such a round
    trip, and the reference's parameters then move by updates that the
    program's cannot hold."""
    fi = jnp.finfo(dtype)
    if fi.bits >= 32:
        return x
    return lax.reduce_precision(x, exponent_bits=fi.nexp,
                                mantissa_bits=fi.nmant)


def half_batch(batch: dict) -> dict:
    """Half of a batch: its first half of rows, or of the tokens of its
    one row.  The mean is then taken over the rest: a fault."""
    def cut(a):
        return a[: a.shape[0] // 2] if a.shape[0] > 1 else a[:, : a.shape[1] // 2]
    return {k: cut(v) for k, v in batch.items()}


def lr_at(opt: dict, step: int) -> float:
    """The configuration's schedule: linear warm-up, cosine to a tenth."""
    warm = step / max(opt["warmup"], 1)
    prog = min(max((step - opt["warmup"]) / max(opt["total_steps"]
                                                - opt["warmup"], 1), 0.0), 1.0)
    cos = 0.5 * (1 + np.cos(np.pi * prog))
    return opt["lr"] * min(warm, 1.0) * max(cos, 0.1)


def leaf_gaps(prog: dict, ref: dict) -> dict:
    """Per-leaf gaps of gradient and change norms (``nan`` for a leaf
    that the rule leaves out of the change)."""
    gp, gr = np.asarray(prog["grad"]), np.asarray(ref["grad"])
    dp, dr = np.asarray(prog["change"]), np.asarray(ref["change"])
    keep = gr >= 1e-3 * np.median(gr)
    change = np.abs(dp - dr) / np.maximum(dr, np.median(dr[keep]))
    return {"grad": np.abs(gp - gr) / np.maximum(gr, np.median(gr)),
            "change": np.where(keep, change, np.nan)}


def gaps(prog: dict, ref: dict) -> dict:
    """Every number a run can compare (see the module docstring); the
    cell's limits file names the ones it does."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    gp, gr = np.asarray(prog["grad"]), np.asarray(ref["grad"])
    per = leaf_gaps(prog, ref)
    total = np.sqrt(np.sum(gr ** 2))
    return {"loss_gap": float(abs(lp[0] - lr[0]) / abs(lr[0])),
            "loss_worst": float(np.max(np.abs(lp - lr) / np.abs(lr))),
            "grad_gap": float(abs(np.sqrt(np.sum(gp ** 2)) - total) / total),
            "grad_median": float(np.median(per["grad"])),
            "grad_worst": float(np.max(per["grad"])),
            "change_gap": float(np.nanmedian(per["change"])),
            "change_worst": float(np.nanmax(per["change"]))}


class Cell:
    def __init__(self, config, traffic, limits, *, seed, devices):
        self.config, self.traffic, self.limits = config, traffic, limits
        self.seed = seed
        self.devices = list(devices)
        self.ref = importlib.import_module(f"bench.refs.{config['reference']}")
        self.opt_cfg = config["adamw"]
        self.record: list = []
        self.losses: list = []
        self.attempted = 0
        self.failed = 0

    # -- the program --------------------------------------------------------
    def _program_config(self):
        from repro.configs import get_config
        from repro.models.config import SSMConfig
        over = {k: SSMConfig(**self.config[k]) if k == "ssm" else self.config[k]
                for k in self.config["overrides"]}
        cfg = dataclasses.replace(get_config(self.config["arch"]), **over)
        for k, v in self.config.items():
            if k == "ssm":
                have = dataclasses.asdict(cfg.ssm)
            elif k in {f.name for f in dataclasses.fields(cfg)}:
                have = getattr(cfg, k)
            else:
                continue
            if have != v:
                raise ValueError(f"{self.config['arch']}: the program has "
                                 f"{k} = {have!r}, the configuration {v!r}")
        return cfg

    def layout(self, tp: int = 1):
        """The program's parameter layout (shapes, dtypes, shardings), for
        runs of the reference alone."""
        from repro.models import lm
        self.specs = lm.model_specs(self._program_config(), tp)

    def batch(self, step: int) -> dict:
        return gen.tokens(self.traffic, self.config["vocab_size"], self.seed,
                          step)

    def _init(self, shardings=None):
        """The benchmark's weights in the program's layout, from the seed,
        in one jitted call."""
        from repro.models.params import ParamSpec
        flat, tdef = jax.tree.flatten_with_path(
            self.specs, is_leaf=lambda x: isinstance(x, ParamSpec))

        def make(key):
            return jax.tree.unflatten(tdef, [
                self.ref.init_leaf(_name(path), s.shape, jnp.dtype(s.dtype),
                                   jax.random.fold_in(key, i))
                for i, (path, s) in enumerate(flat)])

        return jax.jit(make, out_shardings=shardings)(seed_key(self.seed))

    def setup(self):
        from repro.core.profiles import resolve_stores
        from repro.launch.mesh import make_host_mesh
        from repro.train import Trainer
        marks = [("start", time.perf_counter())]
        cfg = self._program_config()
        if self.traffic["seq"] > self.config.get("ctx_len", self.traffic["seq"]):
            raise ValueError(f"rows of {self.traffic['seq']} tokens exceed "
                             f"the configuration's ctx_len")
        mesh = make_host_mesh(tuple(self.traffic["mesh"]), ("data", "model"))
        profiles, phases = resolve_stores()
        opt = self.opt_cfg
        self.trainer = Trainer(cfg, mesh=mesh, profiles=profiles,
                               phase_profiles=phases or None,
                               base_lr=opt["lr"], warmup=opt["warmup"],
                               record=self.record)
        self.specs = self.trainer.specs
        sh = jax.tree.map(lambda ps: NamedSharding(mesh, ps),
                          self.trainer.pspecs)
        marks.append(("trainer", time.perf_counter()))
        self.params = self._init(sh)
        zeros = jax.jit(lambda t: jax.tree.map(lambda a: jnp.zeros(a.shape, F32),
                                               t), out_shardings=sh)
        self.opt = {"m": zeros(self.params), "v": zeros(self.params),
                    "count": jax.device_put(jnp.zeros((), jnp.int32),
                                            NamedSharding(mesh, P()))}
        jax.block_until_ready(self.opt)
        marks.append(("weights", time.perf_counter()))
        prog = {"losses": []}
        for i in range(1, CHECK_STEPS + 1):
            self._step(i)
            if i == 1:
                prog["grad"] = [float(n) / (1 - opt["b1"])
                                for n in leaf_norms(self.opt["m"])]
                marks.append(("step1", time.perf_counter()))
        jax.block_until_ready(self.params)
        marks.append(("steps2-3", time.perf_counter()))
        # the first weights are made again rather than kept: a copy would
        # hold 1.3 GB more through the checked steps, next to the step's
        # 13.75 GiB of 15.75
        p0 = self._init(sh)
        prog["change"] = [float(n) for n in change_norms(self.params, p0)]
        del p0
        marks.append(("change", time.perf_counter()))
        prog["losses"] = [float(x) for x in self.losses]
        self.prog = prog
        self.next_step = CHECK_STEPS + 1
        self.hlo_names = None
        self.setup_phases = {b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])}

    def _step(self, i):
        b = self.trainer.put_batch(self.batch(i))
        self.params, self.opt, m = self.trainer.step(self.params, self.opt,
                                                     b, i)
        self.losses.append(m["loss"])
        self.attempted += 1
        return m["loss"]

    def _run(self, until):
        """Steps back to back, at most ``IN_FLIGHT`` dispatched ahead of
        the device; ``until(n_done, elapsed)`` says when to stop.
        ``launch/train.py`` keeps two in flight; four let the device ride
        out a stall of the host of up to three steps."""
        n, pending = 0, collections.deque()
        t0 = time.perf_counter()
        while True:
            pending.append(self._step(self.next_step))
            self.next_step += 1
            n += 1
            if len(pending) >= IN_FLIGHT:
                jax.block_until_ready(pending.popleft())
            if until(n, time.perf_counter() - t0):
                break
        jax.block_until_ready((self.params, self.opt))
        return n, time.perf_counter() - t0

    def window(self, seconds):
        n, dt = self._run(lambda n, el: el >= seconds)
        return {"step_ms": dt / n * 1e3}

    def traced_window(self, seconds):
        n, _ = self._run(lambda n, el: n >= self.traffic["traced_steps"])
        tokens = self.traffic["global_batch"] * self.traffic["seq"]
        return {"steps": n, "chips": len(self.devices),
                "model_flops": 3 * self.ref.flops_per_token(self.config, self.traffic["seq"])
                * tokens * n}

    def op_label(self, hlo_name):
        if self.hlo_names is None:
            with self.trainer._tuned():
                text = self.trainer._step.lower(
                    self.params, self.opt,
                    self.trainer.put_batch(self.batch(0)),
                    jnp.asarray(0, jnp.int32)).compile().as_text()
            self.hlo_names = trace_mod.hlo_op_names(text)
        stack = self.hlo_names.get(hlo_name)
        if stack is None:
            return "other"
        phase = "bwd" if "transpose" in stack else "fwd"
        return f"{phase}:{stack.rsplit('/', 1)[-1]}"

    # -- the check ----------------------------------------------------------
    def release(self):
        losses = np.asarray(jax.device_get(self.losses), np.float64)
        self.failed = int(np.sum(~np.isfinite(losses)))
        self.losses = []
        del self.params, self.opt, self.trainer

    def reference_run(self, cast=None, keep=None) -> dict:
        """The reference's three steps on the seed's weights and batches
        (``keep`` takes part of each batch: a fault for the tests)."""
        cast = cast or (lambda a: a)
        dev = self.devices[0]
        dtypes = [a.dtype for a in jax.tree.leaves(self._init())]
        o = self.opt_cfg
        b1, b2 = o["b1"], o["b2"]

        def step(p, m, v, batch, lr, count):
            loss, g = jax.value_and_grad(self.ref.loss)(p, batch, self.config,
                                                        cast)
            gn = [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(g)]
            m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
            v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
            flat_p, tdef = jax.tree.flatten(p)
            new = []
            for x, mm, vv, dt in zip(flat_p, jax.tree.leaves(m),
                                     jax.tree.leaves(v), dtypes):
                upd = (mm / (1 - b1 ** count))
                upd = upd / (jnp.sqrt(vv / (1 - b2 ** count)) + o["eps"])
                x = x - lr * (upd + o["weight_decay"] * x)
                new.append(stored(x, dt))
            return jax.tree.unflatten(tdef, new), m, v, loss, gn

        with jax.default_matmul_precision("highest"):
            fn = jax.jit(step, donate_argnums=(0, 1, 2))
            p = jax.tree.map(lambda a: a.astype(F32),
                             jax.device_put(self._init(), dev))
            m = jax.tree.map(jnp.zeros_like, p)
            v = jax.tree.map(jnp.zeros_like, p)
            out = {"losses": []}
            for i in range(1, CHECK_STEPS + 1):
                batch = self.batch(i)
                batch = jax.device_put(keep(batch) if keep else batch, dev)
                p, m, v, loss, gn = fn(p, m, v, batch, jnp.float32(
                    lr_at(o, i)), jnp.float32(i))
                out["losses"].append(float(loss))
                if i == 1:
                    out["grad"] = [float(x) for x in gn]
            del m, v
            p0 = jax.device_put(self._init(), dev)
            out["change"] = [float(x) for x in change_norms(p, p0)]
        return out

    def leaf_names(self):
        from repro.models.params import ParamSpec
        flat, _ = jax.tree.flatten_with_path(
            self.specs, is_leaf=lambda x: isinstance(x, ParamSpec))
        return [jax.tree_util.keystr(path) for path, _ in flat]

    def report(self, ref, out=sys.stderr):
        """The worst leaves of each number, with both readings."""
        per = leaf_gaps(self.prog, ref)
        names = self.leaf_names()
        for what in ("grad", "change"):
            order = np.argsort(-np.nan_to_num(per[what], nan=-1.0))[:4]
            for i in order:
                print(f"leaf {what} {names[i]} gap {per[what][i]:.4g} "
                      f"program {self.prog[what][i]:.6g} "
                      f"reference {ref[what][i]:.6g}", file=out)
        print(f"losses program {self.prog['losses']} reference "
              f"{ref['losses']}", file=out)

    def check(self):
        ref = self.reference_run()
        self.report(ref)
        g = gaps(self.prog, ref)
        print("not compared: " + ", ".join(
            f"{k} {v!r}" for k, v in g.items() if k not in self.limits),
            file=sys.stderr)
        return [(k, g[k], self.limits[k]) for k in self.limits]
