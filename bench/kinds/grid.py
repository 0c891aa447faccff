"""The paper's experiment: every collective at every payload, on one axis.

One launch runs the ladder (each op of the configuration at each payload
of the traffic mix) ``ladders_per_launch`` times in one jitted
``shard_map`` program.  Every call goes through the program's dispatching
entry (``repro.core.api``) under ``api.tuned`` with the profiles the
program resolves itself, so "tuned" means whatever the program would
dispatch.  Each call's input is tied to the previous call's output by an
``optimization_barrier``, so the calls run one after another and XLA
neither overlaps nor combines them.

Payloads are integers in ``[-value_range, value_range]`` held as the
configuration's float dtype: every sum of ``p`` of them is exact, so each
call's output has one right answer whatever the reduction order, and the
check against the numpy MPI model (``bench/refs/mpi.py``) is exact.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import trace as trace_mod
from bench.refs import mpi
from bench.seeds import seed_key


class Cell:
    def __init__(self, config, traffic, limits, *, seed, devices,
                 wire_dtype=None):
        self.config, self.traffic, self.limits = config, traffic, limits
        self.seed = seed
        self.p = config["p"]
        self.axis = config["axis"]
        self.root = config["root"]
        self.dtype = jnp.dtype(config["dtype"])
        # the control: the same calls with the payload on a narrower wire
        self.wire_dtype = jnp.dtype(wire_dtype) if wire_dtype else None
        self.devices = list(devices)[:self.p]
        self.reps = traffic["ladders_per_launch"]
        self.calls = [(op, nb) for nb in traffic["payload_bytes"]
                      for op in config["ops"]]
        self.attempted = 0
        self.failed = 0
        self.record: list = []
        self.kept: list = []

    # -- shapes ---------------------------------------------------------
    def _rows_cols(self, nbytes):
        e = nbytes // self.dtype.itemsize
        cols = min(e, 128)
        return e // cols, cols

    def in_shape(self, op, nbytes):
        n, c = self._rows_cols(nbytes)
        return (self.p * n if op in mpi.V_IN else n, c)

    def out_shape(self, op, nbytes):
        n, c = self._rows_cols(nbytes)
        return (self.p * n if op in mpi.V_OUT else n, c)

    # -- the program ----------------------------------------------------
    def _one(self, op, x):
        from repro.core import api
        fn = getattr(api, op)
        if self.wire_dtype is not None:
            x = x.astype(self.wire_dtype)
        y = (fn(x, self.axis, root=self.root) if op in mpi.ROOTED
             else fn(x, self.axis))
        return y.astype(self.dtype)

    def _ladder(self, *xs):
        def one_pass(_, outs):
            prev = outs[-1]
            new = []
            for i, (op, nb) in enumerate(self.calls):
                with jax.named_scope(f"c{i:03d}"):
                    _, x = lax.optimization_barrier((prev, xs[i]))
                    prev = self._one(op, x)
                new.append(prev)
            return tuple(new)

        init = tuple(jnp.zeros(self.out_shape(op, nb), self.dtype)
                     for op, nb in self.calls)
        return lax.fori_loop(0, self.reps, one_pass, init)

    def _inputs(self, mesh):
        shapes = [(self.p * r, c) for r, c in
                  (self.in_shape(op, nb) for op, nb in self.calls)]
        v = self.traffic["value_range"]
        sharding = NamedSharding(mesh, P(self.axis))

        def gen(key):
            return tuple(
                jax.random.randint(jax.random.fold_in(key, i), s, -v, v + 1,
                                   jnp.int32).astype(self.dtype)
                for i, s in enumerate(shapes))

        return jax.jit(gen, out_shardings=(sharding,) * len(shapes))(
            seed_key(self.seed))

    def setup(self):
        from repro.core import api
        from repro.core.profiles import resolve_stores
        mesh = Mesh(np.asarray(self.devices), (self.axis,))
        self.xs = self._inputs(mesh)
        spec = (P(self.axis),) * len(self.calls)
        fn = jax.jit(jax.shard_map(self._ladder, mesh=mesh, in_specs=spec,
                                   out_specs=spec, check_vma=False))
        profiles, phases = resolve_stores()
        with api.tuned(profiles=profiles, phase_profiles=phases or None,
                       record=self.record):
            self.compiled = fn.lower(*self.xs).compile()
        self._call_of = self._attribute(self.compiled.as_text())
        jax.block_until_ready(self.compiled(*self.xs))
        self.x_host = [np.asarray(jax.device_get(x)) for x in self.xs]

    @staticmethod
    def _attribute(hlo_text):
        """Instruction -> call, from the ``c<i>`` scope of its name stack;
        an instruction without one belongs to the next call in the
        schedule (the barrier's copy, a rewritten reduce-scatter)."""
        out = {}
        for comp in trace_mod.hlo_schedule(hlo_text):
            nxt = None
            for name, stack in reversed(comp):
                parts = [q for q in (stack or "").split("/")
                         if len(q) == 4 and q[0] == "c" and q[1:].isdigit()]
                if parts:
                    nxt = int(parts[-1][1:])
                if nxt is not None:
                    out[name] = nxt
        return out

    def _launch(self):
        out = self.compiled(*self.xs)
        jax.block_until_ready(out)
        return out

    def window(self, seconds):
        """Launches back to back until ``seconds`` have passed; each waits
        for the one before it."""
        n, first, out = 0, None, None
        t0 = time.perf_counter()
        while True:
            out = self._launch()
            n += 1
            if first is None:
                first = out
            if time.perf_counter() - t0 >= seconds:
                break
        dt = time.perf_counter() - t0
        self.kept = [first, out] if n > 1 else [out]
        return {"grid_us": dt / (n * self.reps) * 1e6}

    def traced_window(self, seconds):
        n = self.traffic["traced_launches"]
        out = None
        for _ in range(n):
            out = self._launch()
            if not self.kept:
                self.kept = [out]
        self.kept.append(out)
        return {"launches": n, "ladders": n * self.reps}

    # -- what the readers ask --------------------------------------------
    def call_of(self, hlo_name):
        """Index into ``calls`` of the call an instruction belongs to."""
        return self._call_of.get(hlo_name)

    def op_label(self, hlo_name):
        i = self.call_of(hlo_name)
        if i is None:
            return "between calls"
        op, nb = self.calls[i]
        return f"{op}.{nb}B"

    # -- the check --------------------------------------------------------
    def release(self):
        self.kept = [[np.asarray(a) for a in jax.device_get(o)]
                     for o in self.kept]
        del self.compiled, self.xs

    def check(self):
        bad = 0
        for outs in self.kept:
            for i, (op, nb) in enumerate(self.calls):
                x = np.split(self.x_host[i], self.p)
                want = mpi.expected(op, x, self.root)
                got = np.split(outs[i], self.p)
                wrong = sum(int(np.sum(g != w)) for g, w in zip(got, want)
                            if w is not None)
                self.attempted += 1
                if wrong:
                    self.failed += 1
                    bad += wrong
        self.kept = []
        return [("grid_bad_elems", bad, self.limits["grid_bad_elems"])]
