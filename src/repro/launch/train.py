"""Distributed training CLI — the production entry point.

Composes the tested pieces: mesh construction, tuned-profile loading
(PGMPITuneD), the manual-SPMD Trainer, deterministic sharded data,
async checkpointing, straggler watchdog, and crash-resume.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
        --smoke --steps 50 --profile-dir results/profiles_v5e

At published widths on one TPU v5e chip, cut in depth only, with the
``shard_map`` step and tuned dispatch traced exactly as on a pod:

    PYTHONPATH=src python -m repro.launch.train --arch rwkv6-3b \
        --layers 4 --mesh 1x1 --global-batch 2 --seq 1024 --steps 5

On a pod pass ``--mesh 16x16`` / ``--mesh 2x16x16``; ``--mesh DxT`` builds a
(data, model) mesh over the first D*T local devices.  Without ``--mesh``
the step is a plain ``jit`` and every ``dist.ops`` collective degrades to
its local meaning, so the tuner sees no traffic.

``--trace-dir DIR --trace-steps A:B`` writes a JAX profile of steps A to B
under ``DIR`` (TensorBoard's profile plugin or ``jax.profiler.ProfileData``
read it): the profiler starts before step A and stops once step B has
finished.  Its device ops carry the program's scopes in their ``op_name``
(``embed``, ``head``, one per block kind such as ``rwkv`` or ``mamba``,
``wkv``, ``ssd``, ``grad_sync``, ``optimizer``, and ``pgtune.<op>.<impl>``
at each dispatched collective); its host line holds the spans
``train.step`` (with the step number), ``train.put_batch``, ``train.wait``
and ``ckpt.save``.  A step that builds an executable after the first step
prints a ``recompile:`` line.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import time


def dispatch_summary(record) -> str:
    """One line on the collectives the traced step dispatched: how many
    sites, and the impl at each site that did not take the default."""
    picked = collections.Counter((r.op, r.impl) for r in record
                                 if r.impl != "default")
    line = (f"dispatch: {len(record)} sites traced, "
            f"{sum(picked.values())} not default")
    if picked:
        line += ": " + ", ".join(f"{op}={impl} x{n}"
                                 for (op, impl), n in sorted(picked.items()))
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--layers", type=int, default=0,
                    help="keep only the first N layers (depth cut; every"
                         " width stays as published); 0 = all")
    ap.add_argument("--mesh", default="",
                    help="'16x16' | '2x16x16' | 'dxt' over host devices;"
                         " empty = single device")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--compress", choices=("none", "bf16"), default="none")
    ap.add_argument("--profile-dir", default="",
                    help="tuned-profile directory (flat files = base store,"
                         " per-phase subdirs from tuner.tune_trace);"
                         " default: $PGTUNE_PROFILE_DIR")
    ap.add_argument("--force", default="", help="op:alg=...;... override")
    ap.add_argument("--ckpt-dir", default="results/train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--trace-dir", default="",
                    help="write a profiler trace of --trace-steps here")
    ap.add_argument("--trace-steps", default="1:2",
                    help="A:B, the first and last step of the trace")
    args = ap.parse_args(argv)
    trace_a, trace_b = (int(x) for x in args.trace_steps.split(":"))
    if args.trace_dir and not 0 <= trace_a <= trace_b:
        ap.error(f"--trace-steps {args.trace_steps}: want 0 <= A <= B")

    import jax

    from repro.jax_cache import enable_compile_cache
    enable_compile_cache()

    from repro.ckpt import AsyncCheckpointer, checkpoint as ck
    from repro.configs import get_config
    from repro.core.api import parse_module_spec
    from repro.core.profiles import resolve_stores
    from repro.data import make_batch
    from repro.ft import StepWatchdog
    from repro.launch.mesh import make_host_mesh, make_production_mesh
    from repro.train import Trainer

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)

    mesh = None
    if args.mesh == "16x16":
        mesh = make_production_mesh()
    elif args.mesh == "2x16x16":
        mesh = make_production_mesh(multi_pod=True)
    elif args.mesh:
        d, t = (int(x) for x in args.mesh.split("x"))
        mesh = make_host_mesh((d, t), ("data", "model"))

    # precedence: --profile-dir > $PGTUNE_PROFILE_DIR > none
    profiles, phase_stores = resolve_stores(args.profile_dir or None)
    if profiles is not None or phase_stores:
        print(f"profiles: base={len(profiles) if profiles else 0} "
              f"phases={sorted(phase_stores)}")
    force = parse_module_spec(args.force) if args.force else None

    record: list = []
    tr = Trainer(cfg, mesh=mesh, n_micro=args.n_micro,
                 compress=args.compress, profiles=profiles,
                 phase_profiles=phase_stores or None, force=force,
                 base_lr=args.lr, warmup=args.warmup, record=record)
    params, opt = tr.init(0)
    start = ck.latest_step(args.ckpt_dir) or 0
    if start:
        state = ck.restore(args.ckpt_dir, start,
                           {"params": params, "opt": opt})
        params, opt = state["params"], state["opt"]
        print(f"resumed from step {start}")

    acp = AsyncCheckpointer(args.ckpt_dir)
    wd = StepWatchdog(ratio=4.0)

    def report(i, m):
        # a step's time runs from the previous step's result to its own, so
        # the host prepares step i + 1 while the device runs step i
        with jax.profiler.TraceAnnotation("train.wait"):
            jax.block_until_ready(m["loss"])
        straggler = wd.end_step()
        wd.start_step()
        if i % args.log_every == 0 or straggler:
            note = "  [STRAGGLER]" if straggler else ""
            print(f"step {i:5d}  loss {float(m['loss']):.4f}  "
                  f"gnorm {float(m['grad_norm']):.2f}  "
                  f"lr {float(m['lr']):.2e}  "
                  f"{wd.times[-1]*1e3:.1f} ms  "
                  f"(median {wd.median*1e3:.1f} ms){note}", flush=True)

    t0 = time.time()
    wd.start_step()
    pending = None
    tracing = False
    for i in range(start, args.steps):
        if args.trace_dir and i == trace_a:
            jax.profiler.start_trace(args.trace_dir)
            tracing = True
        batch = tr.put_batch(make_batch(cfg, args.global_batch, args.seq, i))
        params, opt, m = tr.step(params, opt, batch, i)
        if i == start:
            print(dispatch_summary(record), flush=True)
        if tr.recompile_step == i:
            print(f"recompile: step {i} built a program; {tr.recompiles} "
                  f"executable(s) built after the first step", flush=True)
        if pending is not None:
            report(*pending)
        pending = (i, m)
        if (i + 1) % args.ckpt_every == 0:
            with jax.profiler.TraceAnnotation("ckpt.save"):
                acp.save(i + 1, {"params": params, "opt": opt})
        if tracing and i == trace_b:
            jax.block_until_ready(m)
            jax.profiler.stop_trace()
            tracing = False
    if pending is not None:
        report(*pending)
    if tracing:
        jax.profiler.stop_trace()
    acp.wait()
    ck.save(args.ckpt_dir, args.steps, {"params": params, "opt": opt})
    dt = time.time() - t0
    tok = (args.steps - start) * args.global_batch * args.seq
    print(f"done: {args.steps - start} steps, {tok/dt:.0f} tok/s, "
          f"stragglers={len(wd.straggler_steps)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
