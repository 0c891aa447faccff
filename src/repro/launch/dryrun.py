import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first — jax locks the device count on first
backend initialization (see the module-level guard below).

Usage:
    python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
    python -m repro.launch.dryrun --arch all --shape all --multi-pod both \
        --out results/dryrun
    python -m repro.launch.dryrun ... --force allreduce=allreduce_as_rsb_allgather

Per cell: jit(...).lower(*input_specs).compile() on the production mesh,
then print/record memory_analysis(), cost_analysis() and the HLO collective
schedule (payload bytes per collective class) for §Roofline.
"""
import argparse
import dataclasses
import json
import pathlib
import sys
import time
import traceback


def _check_device_count():
    import jax
    n = len(jax.devices())
    if n < 512:
        raise RuntimeError(
            f"dry-run needs 512 host devices, got {n}; something imported "
            "jax before the XLA_FLAGS lines at the top of this module")


def run_cell(arch: str, shape: str, *, multi_pod: bool,
             force: dict | None = None, profiles=None,
             hlo_dir: str | None = None, attn_impl: str | None = None,
             n_micro: int | None = None, capacity_factor: float | None = None,
             donate: bool = False, unroll: bool = False,
             tag: str = "") -> dict:
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.analysis.hlo import collective_bytes, program_costs
    from repro.analysis.roofline import roofline_terms
    from repro.configs import get_config
    from repro.core import api
    from repro.launch.mesh import make_production_mesh
    from repro.launch.shapes import SHAPES, applicable, input_specs
    from repro.models import lm
    from repro.train.trainer import make_step_fns

    cfg = get_config(arch)
    if attn_impl:
        cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
    if unroll:
        cfg = dataclasses.replace(cfg, scan_layers=False)
    if capacity_factor and cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=capacity_factor))
    cell = SHAPES[shape]
    if n_micro:
        cell = dataclasses.replace(cell, n_micro=n_micro)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    ok, why = applicable(cfg, cell)
    if not ok:
        return {"arch": arch, "shape": shape, "mesh": mesh_name,
                "status": "skip", "reason": why}

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size

    with mesh, api.tuned(profiles=profiles, force=force) as tune_ctx:
        args_sds, in_ps = input_specs(cfg, cell, mesh)
        if cell.kind == "train":
            _, train_fn = make_step_fns(cfg, n_micro=cell.n_micro)
            out_ps = (in_ps[0], in_ps[1],
                      {"loss": P(), "grad_norm": P(), "lr": P()})
            fn = shard_map(train_fn, mesh=mesh, in_specs=in_ps,
                           out_specs=out_ps, check_vma=False)
        elif cell.kind == "prefill":
            def pf(params, batch, caches):
                return lm.prefill(params, cfg, batch, caches,
                                  seq_sharded=cell.seq_sharded)
            from repro.launch.shapes import dp_axes
            out_ps = (P(dp_axes(mesh)), in_ps[2])
            fn = shard_map(pf, mesh=mesh, in_specs=in_ps, out_specs=out_ps,
                           check_vma=False)
        else:
            def dc(params, token, caches, t):
                return lm.decode_step(params, cfg, token, caches, t,
                                      seq_sharded=cell.seq_sharded)
            out_ps = (in_ps[1], in_ps[2])
            fn = shard_map(dc, mesh=mesh, in_specs=in_ps, out_specs=out_ps,
                           check_vma=False)

        if donate and cell.kind == "train":
            jfn = jax.jit(fn, donate_argnums=(0, 1))
        elif donate and cell.kind == "decode":
            jfn = jax.jit(fn, donate_argnums=(2,))
        else:
            jfn = jax.jit(fn)
        lowered = jfn.lower(*args_sds)
        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    # trip-count-aware program costs (XLA cost_analysis counts scan bodies
    # once; see analysis/hlo.py docstring)
    pc = program_costs(hlo)
    if hlo_dir:
        d = pathlib.Path(hlo_dir)
        d.mkdir(parents=True, exist_ok=True)
        (d / f"{arch}_{shape}_{mesh_name}.hlo.txt").write_text(hlo)

    rl = roofline_terms(arch, shape, mesh_name, cost=cost, coll=coll,
                        cfg=cfg, cell=cell, n_devices=n_dev,
                        flops_override=pc["dot_flops"],
                        bytes_override=pc["bytes"])
    mem_d = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
        "output_bytes": getattr(mem, "output_size_in_bytes", 0),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
        "code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
    }
    res = {
        "arch": arch, "shape": shape, "mesh": mesh_name, "status": "ok",
        "variant": tag or "baseline",
        "pgmpi_footer": api.format_footer(tune_ctx),
        "modeled_collective_latency_us": _modeled_latency(tune_ctx),
        "devices": int(n_dev),
        "compile_s": round(time.time() - t0, 1),
        "memory": mem_d,
        "cost": {k: cost.get(k) for k in ("flops", "bytes accessed")
                 if k in cost},
        "program_costs": pc,
        "collectives": coll,
        "roofline": rl.row(),
    }
    return res


def _modeled_latency(ctx) -> dict:
    """Cost-model latency of the dispatched collective schedule vs the
    all-default schedule (v5e ICI; the paper's tuned-vs-default panel)."""
    from repro.core import costmodel as cm
    t_sel = 0.0
    t_def = 0.0
    for rec in ctx.record:
        try:
            t_sel += cm.latency_cell(rec.cell, rec.impl, cm.V5E_ICI)
            t_def += cm.latency_cell(rec.cell, "default", cm.V5E_ICI)
        except KeyError:
            pass
    return {"selected": round(t_sel * 1e6, 2), "default": round(t_def * 1e6, 2)}


def main(argv=None) -> int:
    _check_device_count()
    from repro.configs import ARCHS
    from repro.core.api import parse_module_spec
    from repro.launch.shapes import SHAPES

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", choices=("on", "off", "both"),
                    default="off")
    ap.add_argument("--force", default="",
                    help="op:alg=...;op:alg=... (PGMPITuneCLI syntax)")
    ap.add_argument("--profile-dir", default="",
                    help="load tuned profiles (PGMPITuneD mode)")
    ap.add_argument("--out", default="", help="write one JSON per cell here")
    ap.add_argument("--hlo-dir", default="", help="dump compiled HLO text")
    ap.add_argument("--attn-impl", default="", choices=("", "ref", "flash"))
    ap.add_argument("--n-micro", type=int, default=0)
    ap.add_argument("--cf", type=float, default=0.0,
                    help="MoE capacity factor override")
    ap.add_argument("--donate", action="store_true")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll layer stacks (serving: in-place caches)")
    ap.add_argument("--tag", default="", help="variant tag for the JSON")
    args = ap.parse_args(argv)

    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    pods = {"on": [True], "off": [False], "both": [False, True]}[
        args.multi_pod]
    force = parse_module_spec(args.force.replace(";", ";")) if args.force \
        else None
    profiles = None
    if args.profile_dir:
        from repro.core.profiles import ProfileStore
        profiles = ProfileStore.load(args.profile_dir)

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                try:
                    res = run_cell(arch, shape, multi_pod=mp, force=force,
                                   profiles=profiles,
                                   hlo_dir=args.hlo_dir or None,
                                   attn_impl=args.attn_impl or None,
                                   n_micro=args.n_micro or None,
                                   capacity_factor=args.cf or None,
                                   donate=args.donate, unroll=args.unroll,
                                   tag=args.tag)
                except Exception as e:
                    traceback.print_exc()
                    res = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "error", "error": f"{type(e).__name__}:"
                           f" {str(e)[:500]}"}
                    failures += 1
                line = json.dumps(res)
                print(line, flush=True)
                if args.out:
                    d = pathlib.Path(args.out)
                    d.mkdir(parents=True, exist_ok=True)
                    sfx = f"_{args.tag}" if args.tag else ""
                    (d / (f"{res['arch']}_{res['shape']}_"
                          f"{res['mesh']}{sfx}.json")
                     ).write_text(json.dumps(res, indent=1))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
