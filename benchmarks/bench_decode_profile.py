"""Profile-driven decode serving: record → tune_trace → re-serve.

The first end-to-end run of the paper's offline→online pipeline against
*real model traffic* rather than synthetic size sweeps:

1. Serve a smoke LM with tensor parallelism emulated over
   ``vmap(axis_name="model")`` (the CPU stand-in for a TP mesh — the same
   dispatcher path shard_map takes) and record a phase-tagged workload
   trace: prefill-phase collectives + decode-phase collectives.
2. ``tuner.tune_trace`` replays the recorded (op, p, nbytes, phase) mix
   against the cost-model backend and emits per-phase ``ProfileStore``s.
3. Re-serve with ``api.tuned(phase_profiles=...)``: the decode steps now
   dispatch to the tuned mock-ups (visible in the Listing-2 footer), and
   the modeled per-step collective latency drops.

Wall-clock numbers on this CPU container measure emulation overhead, not
fabric time — the decision-quality number is the cost-model latency, same
as launch/dryrun's tuned-vs-default panel.  Artifacts (trace JSONL, tuned
``.pgtune`` profiles, dispatch footers) are written to ``--out`` so CI can
catch profile-format drift.

  PYTHONPATH=src python benchmarks/bench_decode_profile.py --smoke
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT))
sys.path.insert(0, str(_ROOT / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, header
from repro.configs import get_config
from repro.core import api, costmodel as cm, tuner
from repro.core.trace import Trace
from repro.models import lm
from repro.models.params import init_tree


def serve_once(cfg, tp, params, prompts, s_max, n_tokens, *,
               phase_profiles=None, profiles=None):
    """One prefill + greedy-decode pass under a fresh tuned context.

    Fresh local closures per call → fresh jit caches, so dispatch re-runs
    (and re-records) for each serving variant.
    """
    batch = prompts.shape[0]

    def init_c(_):
        return lm.init_caches(cfg, batch, s_max)

    def pf(p, c):
        return lm.prefill(p, cfg, {"tokens": prompts}, c)

    def dc(p, t, c, i):
        return lm.decode_step(p, cfg, t, c, i)

    vmap = jax.vmap
    j_init = jax.jit(vmap(init_c, axis_name="model", axis_size=tp,
                          in_axes=None, out_axes=0))
    j_pf = jax.jit(vmap(pf, axis_name="model"))
    j_dc = jax.jit(vmap(dc, axis_name="model", in_axes=(0, None, 0, None)))

    with api.tuned(profiles=profiles, phase_profiles=phase_profiles) as ctx:
        caches = j_init(0)
        with api.phase("prefill"):
            t0 = time.perf_counter()
            logits, caches = j_pf(params, caches)
            logits.block_until_ready()
            t_prefill = time.perf_counter() - t0
        tok = (jnp.argmax(logits[0][:, -1], axis=-1).astype(jnp.int32)
               [:, None] % cfg.vocab_size)
        out = [tok]
        with api.phase("decode"):
            t0 = time.perf_counter()
            for step in range(n_tokens - 1):
                lg, caches = j_dc(params, tok, caches,
                                  jnp.int32(prompts.shape[1] + step))
                tok = (jnp.argmax(lg[0][:, -1], axis=-1).astype(jnp.int32)
                       [:, None] % cfg.vocab_size)
                out.append(tok)
            tok.block_until_ready()
            t_decode = time.perf_counter() - t0
    gen = jnp.concatenate(out, axis=1)
    return t_prefill, t_decode / max(n_tokens - 1, 1), gen, ctx


def modeled_step_latency(record, topo, phase):
    """Cost-model collective seconds of the recorded dispatches in one
    phase (the first traced step — jit caches mean each step dispatches
    once)."""
    total = 0.0
    for rec in record:
        if rec.phase != phase:
            continue
        try:
            total += cm.latency_cell(rec.cell, rec.impl, topo)
        except KeyError:
            pass
    return total


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--tp", type=int, default=4,
                    help="emulated model-axis size")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--topo", default="bgq-like",
                    choices=sorted(cm.PRESETS),
                    help="fabric preset for the tuning backend")
    ap.add_argument("--min-win", type=float, default=0.10)
    ap.add_argument("--out", default="results/decode_profile")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (tiny batch/seq/token budget)")
    args = ap.parse_args(argv)

    if args.smoke:
        args.batch, args.prompt_len, args.tokens = 2, 8, 4
        args.tp = min(args.tp, 2)

    topo = cm.PRESETS[args.topo]
    cfg = get_config(args.arch).smoke()
    s_max = args.prompt_len + args.tokens + 8
    specs = lm.model_specs(cfg, tp=args.tp)

    def init(key):
        return init_tree(specs, key)

    params = jax.jit(jax.vmap(init, axis_name="model", axis_size=args.tp,
                              in_axes=None, out_axes=0))(jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)

    header()
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    # -- 1. default serve: record the workload trace -------------------------
    pf_d, dc_d, gen_d, ctx_d = serve_once(cfg, args.tp, params, prompts,
                                          s_max, args.tokens)
    trace = Trace.from_context(ctx_d)
    trace.save(out / "decode_trace.jsonl")
    (out / "footer_default.txt").write_text(api.format_footer(ctx_d) + "\n")
    emit("decode_profile/default/prefill_ms", pf_d * 1e3)
    emit("decode_profile/default/step_us", dc_d * 1e6, "wall-clock emulation")

    # -- 2. trace-replay tuning ----------------------------------------------
    rep = tuner.tune_trace(trace, backend=tuner.CostModelBackend(topo),
                           min_win=args.min_win)
    rep.save(out / "profiles")
    for line in rep.summary().splitlines():
        print(f"# {line}")

    # -- 3. tuned serve -------------------------------------------------------
    pf_t, dc_t, gen_t, ctx_t = serve_once(cfg, args.tp, params, prompts,
                                          s_max, args.tokens,
                                          phase_profiles=rep.phase_profiles)
    footer = api.format_footer(ctx_t)
    (out / "footer_tuned.txt").write_text(footer + "\n")
    emit("decode_profile/tuned/prefill_ms", pf_t * 1e3)
    emit("decode_profile/tuned/step_us", dc_t * 1e6, "wall-clock emulation")

    same = bool(jnp.array_equal(gen_d, gen_t))
    emit("decode_profile/tokens_identical", 0.0, str(same))

    m_def = modeled_step_latency(ctx_d.record, topo, "decode")
    m_tun = modeled_step_latency(ctx_t.record, topo, "decode")
    emit("decode_profile/modeled_decode_collectives_default_us", m_def * 1e6)
    emit("decode_profile/modeled_decode_collectives_tuned_us", m_tun * 1e6,
         f"{m_def / m_tun:.2f}x" if m_tun > 0 else "")

    # v1-sunset criterion, machine-checked (ROADMAP "Trace v1 sunset"):
    # artifacts freshly written by THIS pipeline must re-load without any
    # deprecation path firing — scoped to our own artifacts so unrelated
    # library DeprecationWarnings can't fail the job
    import warnings

    from repro.core.profiles import ProfileStore
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        try:
            Trace.load(out / "decode_trace.jsonl")
            for sub in sorted((out / "profiles").iterdir()):
                if sub.is_dir():
                    ProfileStore.load(sub)
        except DeprecationWarning as w:
            print(f"ERROR: freshly written artifact re-loads through a "
                  f"deprecated parse path: {w}", file=sys.stderr)
            return 1
    emit("decode_profile/artifacts_current_schema", 1.0)

    tuned_decode = [r for r in ctx_t.record if r.phase == "decode"]
    nondefault = sorted({r.impl for r in tuned_decode if r.impl != "default"})
    emit("decode_profile/tuned_nondefault_impls", float(len(nondefault)),
         ";".join(nondefault))
    print(footer)

    (out / "summary.json").write_text(json.dumps({
        "arch": cfg.name, "tp": args.tp, "topo": args.topo,
        "trace_cells": len(trace), "trace_dispatches": trace.total(),
        "phases": trace.phases(),
        "modeled_decode_us": {"default": m_def * 1e6, "tuned": m_tun * 1e6},
        "wall_step_us": {"default": dc_d * 1e6, "tuned": dc_t * 1e6},
        "tuned_nondefault_impls": nondefault,
        "tokens_identical": same,
    }, indent=1))

    if not nondefault:
        print("ERROR: tuned decode run selected no non-default mock-ups "
              "(profile pipeline regressed)", file=sys.stderr)
        return 1
    if not same:
        print("ERROR: tuned serving changed the generated tokens",
              file=sys.stderr)
        return 1
    return 0


def run():
    # benchmarks/run.py entry point: smoke-sized so the suite stays fast
    rc = main(["--smoke"])
    if rc:
        raise RuntimeError("bench_decode_profile smoke failed")


if __name__ == "__main__":
    raise SystemExit(main())
