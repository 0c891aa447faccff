"""Serving example: profile-driven decode, end to end.

Prefill a batch of prompts and greedy-decode with tensor parallelism
emulated over ``vmap(axis_name="model")`` (the CPU stand-in for a TP mesh;
the dispatcher path is identical to shard_map), then close the paper's
offline→online loop against the *recorded* traffic:

1. default serve — every collective is recorded with its phase tag
   (``prefill`` / ``decode`` / ``bwd``-free here);
2. ``tuner.tune_trace`` tunes exactly the recorded (op, p, nbytes, phase)
   mix on the cost-model backend and writes per-phase profiles;
3. re-serve with ``api.tuned(phase_profiles=...)`` — decode steps now
   dispatch to the tuned mock-ups (see the Listing-2 footer).

  PYTHONPATH=src python examples/serve_decode.py --arch gemma3-1b --tokens 24
"""
import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import api, costmodel, tuner
from repro.core.trace import Trace
from repro.models import lm
from repro.models.params import init_tree


def serve(cfg, tp, params, prompts, s_max, n_tokens, *, phase_profiles=None):
    batch = prompts.shape[0]
    j_init = jax.jit(jax.vmap(lambda _: lm.init_caches(cfg, batch, s_max),
                              axis_name="model", axis_size=tp,
                              in_axes=None, out_axes=0))
    j_pf = jax.jit(jax.vmap(
        lambda p, c: lm.prefill(p, cfg, {"tokens": prompts}, c),
        axis_name="model"))
    j_dc = jax.jit(jax.vmap(
        lambda p, t, c, i: lm.decode_step(p, cfg, t, c, i),
        axis_name="model", in_axes=(0, None, 0, None)))

    with api.tuned(phase_profiles=phase_profiles) as ctx:
        caches = j_init(0)
        t0 = time.time()
        with api.phase("prefill"):
            logits, caches = j_pf(params, caches)
        tok = (jnp.argmax(logits[0][:, -1], axis=-1).astype(jnp.int32)
               [:, None] % cfg.vocab_size)
        out = [tok]
        with api.phase("decode"):
            for step in range(n_tokens - 1):
                lg, caches = j_dc(params, tok, caches,
                                  jnp.int32(prompts.shape[1] + step))
                tok = (jnp.argmax(lg[0][:, -1], axis=-1).astype(jnp.int32)
                       [:, None] % cfg.vocab_size)
                out.append(tok)
        gen = jnp.concatenate(out, axis=1)
        gen.block_until_ready()
        dt = time.time() - t0
    return gen, dt, ctx


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tp", type=int, default=2,
                    help="emulated model-parallel degree")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=24)
    ap.add_argument("--topo", default="bgq-like",
                    choices=sorted(costmodel.PRESETS))
    ap.add_argument("--out", default="results/serve_decode")
    args = ap.parse_args()

    cfg = get_config(args.arch).smoke()
    s_max = args.prompt_len + args.tokens + 8
    specs = lm.model_specs(cfg, tp=args.tp)
    params = jax.jit(jax.vmap(
        lambda key: init_tree(specs, key),
        axis_name="model", axis_size=args.tp, in_axes=None,
        out_axes=0))(jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)

    # 1. default serve, recording the phase-tagged workload trace
    gen, dt, ctx = serve(cfg, args.tp, params, prompts, s_max, args.tokens)
    trace = Trace.from_context(ctx)
    out = pathlib.Path(args.out)
    trace.save(out / "trace.jsonl")
    print(trace.summary())

    # 2. tune the recorded op mix, per phase
    rep = tuner.tune_trace(
        trace, backend=tuner.CostModelBackend(costmodel.PRESETS[args.topo]))
    rep.save(out / "profiles")
    print(rep.summary())

    # 3. re-serve with the tuned per-phase stores
    gen_t, dt_t, ctx_t = serve(cfg, args.tp, params, prompts, s_max,
                               args.tokens, phase_profiles=rep.phase_profiles)
    assert bool(jnp.array_equal(gen, gen_t)), "tuning changed the tokens!"

    print(f"arch={cfg.name} batch={args.batch} tp={args.tp} "
          f"prompt={args.prompt_len} generated={gen.shape[1]} tokens; "
          f"default {dt:.2f}s, tuned {dt_t:.2f}s (CPU emulation)")
    print("sample ids:", np.asarray(gen[0][:12]))
    print("tuned-run dispatch footer:")
    print(api.format_footer(ctx_t))


if __name__ == "__main__":
    main()
