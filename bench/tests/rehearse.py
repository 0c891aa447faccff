"""A whole run of a cell on the CPU at a tiny size, with no chip.

    python -m bench.tests.rehearse --workload <name> [--trace 1]
        [--fault exchange|answer|unchanged|half_batch] [--control]

The harness's look for a chip is skipped; everything else is the run the
benchmark makes, down to the last line, at sizes a test can hold: the
grid keeps its ops and takes the four smallest payloads, the train cells
keep their architecture at small widths.  ``--fault`` breaks the timed
path underneath the harness; ``--control`` runs the cell's control in
the program's place.  The cell's own limits decide ``correct``.
"""
import argparse
import os
import sys
import time

T0 = time.perf_counter()

TINY_TRAIN = {"n_layers": 2, "d_model": 256, "d_ff": 512, "vocab_size": 1024,
              "n_heads": 4, "n_kv_heads": 4}


def _fault_exchange(monkeypatch_api):
    """Every collective keeps its own data: the exchange left out."""
    import jax.numpy as jnp
    from jax import lax

    def local(op, x, axis, impl=None, /, **kw):
        p, i = lax.axis_size(axis), lax.axis_index(axis)
        n = x.shape[0] // p
        own = lax.dynamic_slice_in_dim(x, i * n, n) if n else x
        return {"allgather": lambda: jnp.tile(x, (p, 1)),
                "gather": lambda: jnp.tile(x, (p, 1)),
                "allreduce": lambda: x * p, "reduce": lambda: x * p,
                "reducescatter": lambda: own * p, "alltoall": lambda: x,
                "scatter": lambda: own, "bcast": lambda: x,
                "scan": lambda: x * (i + 1), "exscan": lambda: x * i}[op]()

    monkeypatch_api(local)


def _fault_answer(monkeypatch_api, original):
    """One element of each call's answer altered on rank 0."""
    from jax import lax

    def altered(op, x, axis, impl=None, /, **kw):
        y = original(op, x, axis, impl, **kw)
        bump = (lax.axis_index(axis) == 0).astype(y.dtype)
        return y.at[0, 0].add(bump)

    monkeypatch_api(altered)


def _fault_unchanged():
    """The step computes its loss but returns the state it was given."""
    import jax
    import jax.numpy as jnp
    from repro.train import Trainer
    step = Trainer.step

    def unchanged(self, params, opt, batch, i):
        cp = jax.tree.map(jnp.copy, (params, opt))
        _, _, m = step(self, *cp, batch, i)
        return params, opt, m

    Trainer.step = unchanged


def _fault_half_batch():
    """The step sees the first half of the batch: the mean is taken over
    the rest."""
    from bench.kinds.train import half_batch
    from repro.train import Trainer
    put = Trainer.put_batch

    def half(self, batch):
        return put(self, half_batch(batch))

    Trainer.put_batch = half


def benchmark_with_staged(harness):
    """``BENCHMARK.json`` with the cells of ``staged.json`` beside this
    file: cells whose code is here and that wait for their chip runs."""
    bm = harness.load_json(harness.ROOT / "BENCHMARK.json")
    staged = harness.load_json(harness.BENCH / "tests" / "staged.json")
    for key, entries in staged.items():
        bm[key] = bm[key] + entries
    return bm


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--fault", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--seed", type=int, default=2**31 + 99)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from bench import harness
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax
    spec = harness.cell_spec(benchmark_with_staged(harness), args.workload)
    kind = spec.config["kind"]
    if kind == "grid":
        spec.traffic = dict(spec.traffic, payload_bytes=[8, 64, 512, 4096],
                            ladders_per_launch=3)
    else:
        tiny = dict(TINY_TRAIN,
                    n_layers=2 * spec.config.get("hybrid_period", 1))
        spec.config = dict(spec.config, **tiny,
                           overrides=sorted(set(spec.config["overrides"]) | set(tiny)))
        spec.traffic = dict(spec.traffic, seq=64,
                            traced_steps=2)
    if args.fault in ("exchange", "answer"):
        from repro.core import api
        original = api._dispatch

        def patch(fn):
            api._dispatch = fn

        if args.fault == "exchange":
            _fault_exchange(patch)
        else:
            _fault_answer(patch, original)
    elif args.fault == "unchanged":
        _fault_unchanged()
    elif args.fault == "half_batch":
        _fault_half_batch()
    elif args.fault:
        raise SystemExit(f"unknown fault {args.fault!r}")
    if args.control and kind == "grid":
        from bench.kinds import grid
        cls = grid.Cell
        grid.Cell = lambda *a, **kw: cls(*a, wire_dtype="bfloat16", **kw)
    if args.control and kind == "train":
        from bench.kinds import train
        from bench.tools.control import fp8
        run = train.Cell.reference_run

        def check(self):
            g = train.gaps(run(self, cast=fp8), run(self))
            return [(k, g[k], self.limits[k]) for k in self.limits]

        train.Cell.check = check
    devices = jax.devices()[:spec.chips]
    if len(devices) < spec.chips:
        raise SystemExit(f"needs {spec.chips} host devices")
    harness.emit(harness.run_cell(spec, devices, seed=args.seed, seconds=0.5,
                                  trace=bool(args.trace), t_start=T0))
    return 0


if __name__ == "__main__":
    sys.exit(main())
