"""Fleet-scale online retuning: shards → merged profile → live hot-swap.

The continuous-retuning loop at serving scale, simulated on one host:

1. FLEET RECORDING — four "servers" serve a smoke LM over emulated tensor
   parallelism (``vmap(axis_name="model")``, the CPU stand-in for a TP
   mesh), each with a different traffic mix (batch / prompt length), each
   recording into a bounded ``trace.ShardRecorder`` and flushing an
   epoch-stamped shard file.
2. MERGE + TUNE — ``Trace.merge_shards`` folds the shard directory into
   one fleet trace (count summation, weight preserved);
   ``tuner.tune_trace`` emits per-phase profiles from the union workload.
   Gate: on the union workload, the merged-trace profile's modeled cost
   is <= every single-shard profile's (a shard only sees its own slice,
   so its profile leaves the other servers' cells untuned).
3. HOT SWAP — a server serves under ``api.tuned(store_ref=...)`` while
   the tuned generation is published to ``$PGTUNE_PROFILE_DIR`` (profiles
   first, ``MANIFEST.json`` last).  ``StoreRef.poll`` adopts the new
   epoch and the server rebuilds its steps: dispatch reads the ref at
   trace time, so the new epoch reaches the next trace.  Gate: the
   rebuilt steps' recorded dispatches are the new epoch's selection (at
   least one tuned impl), and the generated tokens are unchanged.
4. STALENESS — a delayed writer regressing the manifest to an older epoch
   is refused (warning, live generation keeps serving).
5. FEEDBACK — latencies of the impls the epoch-1 pass served are fed back
   via ``ShardRecorder.observe`` → ``#@lat`` shard lines →
   ``tuner.FeedbackBackend``, and the next epoch is tuned from the
   fleet's own measurements and adopted in the same way.

Wall-clock on this CPU container measures emulation overhead; decision
quality is the cost-model latency (same convention as the other
benchmarks).  The fed-back "measurements" are therefore cost-model
samples with noise — the plumbing (shards, reservoirs, feedback override)
is what this benchmark exercises end to end.

  PYTHONPATH=src python benchmarks/bench_fleet_retune.py --smoke
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import warnings

_ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT))
sys.path.insert(0, str(_ROOT / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, header
from repro.configs import get_config
from repro.core import api, costmodel as cm, tuner
from repro.core.profiles import PROFILE_DIR_ENV, resolve_stores
from repro.core.trace import (ShardRecorder, Trace, load_shard_latencies,
                              shard_digest)
from repro.models import lm
from repro.models.params import init_tree


def make_params(cfg, tp):
    specs = lm.model_specs(cfg, tp=tp)

    def init(key):
        return init_tree(specs, key)

    return jax.jit(jax.vmap(init, axis_name="model", axis_size=tp,
                            in_axes=None, out_axes=0))(jax.random.key(0))


def make_steps(cfg, tp, s_max, batch):
    """Cache-init, prefill and decode jits over the emulated model axis.
    Each call builds fresh jits, so their first call traces under the
    ambient ``api.tuned`` context."""

    def init_c(_):
        return lm.init_caches(cfg, batch, s_max)

    def pf(p, c, prompts):
        return lm.prefill(p, cfg, {"tokens": prompts}, c)

    def dc(p, t, c, i):
        return lm.decode_step(p, cfg, t, c, i)

    j_init = jax.jit(jax.vmap(init_c, axis_name="model", axis_size=tp,
                              in_axes=None, out_axes=0))
    j_pf = jax.jit(jax.vmap(pf, axis_name="model", in_axes=(0, 0, None)))
    j_dc = jax.jit(jax.vmap(dc, axis_name="model",
                            in_axes=(0, None, 0, None)))
    return j_init, j_pf, j_dc


def serve_pass(cfg, steps, params, prompts, n_tokens):
    """One prefill + greedy decode pass, phase-tagged like launch/serve."""
    j_init, j_pf, j_dc = steps
    caches = j_init(0)
    with api.phase("prefill"):
        logits, caches = j_pf(params, caches, prompts)
    tok = (jnp.argmax(logits[0][:, -1], axis=-1).astype(jnp.int32)[:, None]
           % cfg.vocab_size)
    out = [tok]
    with api.phase("decode"):
        for step in range(n_tokens - 1):
            lg, caches = j_dc(params, tok, caches,
                              jnp.int32(prompts.shape[1] + step))
            tok = (jnp.argmax(lg[0][:, -1], axis=-1).astype(jnp.int32)
                   [:, None] % cfg.vocab_size)
            out.append(tok)
    return jnp.concatenate(out, axis=1)


def live_pass(cfg, tp, s_max, params, prompts, n_tokens, ref):
    """One pass on freshly built steps under the live ``ref`` — how a
    server adopts an epoch, since dispatch reads the ref at trace time.
    Returns the generated tokens and the dispatches the trace recorded."""
    record: list = []
    steps = make_steps(cfg, tp, s_max, prompts.shape[0])
    with api.tuned(store_ref=ref, record=record):
        gen = serve_pass(cfg, steps, params, prompts, n_tokens)
        gen.block_until_ready()
    return gen, record


def _served_mismatch(record, ref):
    """The recorded dispatches whose impl is not what the live stores
    select (``_selection``) — empty when the epoch reached dispatch."""
    return sorted({f"{r.op} {r.nbytes}B {r.phase}: {r.impl}"
                   for r in record
                   if r.impl != _selection(r.cell, r.phase, ref)})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--tp", type=int, default=2,
                    help="emulated model-axis size")
    ap.add_argument("--tokens", type=int, default=6)
    ap.add_argument("--topo", default="bgq-like", choices=sorted(cm.PRESETS))
    ap.add_argument("--min-win", type=float, default=0.10)
    ap.add_argument("--out", default="results/fleet_retune")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (tiny fleet / token budget)")
    ap.add_argument("--chaos", action="store_true",
                    help="chaos-injected run: quarantine, rollback and "
                         "coordinator gates (see chaos_main)")
    args = ap.parse_args(argv)

    if args.chaos:
        if args.out == "results/fleet_retune":
            args.out = "results/fleet_chaos"
        return chaos_main(args)

    if args.smoke:
        args.tokens = 4

    topo = cm.PRESETS[args.topo]
    cfg = get_config(args.arch).smoke()
    # four servers, four traffic mixes: (batch, prompt_len)
    fleet = [(1, 8), (2, 16), (1, 32), (2, 8)]
    s_max = max(pl for _, pl in fleet) + args.tokens + 8
    backend = tuner.CostModelBackend(topo)

    header()
    out = pathlib.Path(args.out)
    shard_dir = out / "shards"
    live_dir = out / "live_profiles"
    import shutil
    for d in (shard_dir, live_dir):
        shutil.rmtree(d, ignore_errors=True)
    for d in (out, shard_dir, live_dir):
        d.mkdir(parents=True, exist_ok=True)
    failures: list[str] = []

    # -- 1. fleet recording: one bounded shard per server --------------------
    rng = np.random.default_rng(0)
    for i, (batch, plen) in enumerate(fleet):
        params = make_params(cfg, args.tp)
        prompts = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, plen)), jnp.int32)
        rec = ShardRecorder(f"srv{i}", seed=i)
        steps = make_steps(cfg, args.tp, s_max, batch)
        with api.tuned(record=rec):
            serve_pass(cfg, steps, params, prompts, args.tokens)
        path = rec.flush(shard_dir, epoch=1)
        emit(f"fleet_retune/shard{i}/dispatches",
             float(Trace.load(path).total()), path.name)

    # -- 2. merge + tune: fleet profile must cover every server's slice ------
    fleet_trace = Trace.merge_shards(shard_dir).trace
    shard_traces = [Trace.load(p)
                    for p in sorted(shard_dir.glob("shard-*.jsonl"))]
    assert fleet_trace.total() == sum(t.total() for t in shard_traces)
    emit("fleet_retune/merged/cells", float(len(fleet_trace)))
    emit("fleet_retune/merged/dispatches", float(fleet_trace.total()))

    rep = tuner.tune_trace(fleet_trace, backend=backend,
                           min_win=args.min_win)
    cost_merged = sum(tuner.estimate_trace_cost(
        fleet_trace, backend, phases=rep.phase_profiles).values())
    cost_default = sum(tuner.estimate_trace_cost(fleet_trace,
                                                 backend).values())
    emit("fleet_retune/union_cost_default_us", cost_default * 1e6)
    emit("fleet_retune/union_cost_merged_us", cost_merged * 1e6,
         f"{cost_default / cost_merged:.2f}x" if cost_merged else "")
    for i, t in enumerate(shard_traces):
        rep_i = tuner.tune_trace(t, backend=backend, min_win=args.min_win)
        cost_i = sum(tuner.estimate_trace_cost(
            fleet_trace, backend, phases=rep_i.phase_profiles).values())
        emit(f"fleet_retune/union_cost_shard{i}_us", cost_i * 1e6)
        if cost_merged > cost_i * (1 + 1e-9):
            failures.append(
                f"merged profile costs {cost_merged:.3e}s on the union "
                f"workload, worse than shard {i}'s profile ({cost_i:.3e}s)")

    # -- 3. live serve + hot swap (rebuild the steps on adoption) ----------
    os.environ[PROFILE_DIR_ENV] = str(live_dir)
    ref = resolve_stores(watch=True)
    if ref.epoch >= 0:
        failures.append(f"empty live dir resolved to epoch {ref.epoch}")
    batch, plen = fleet[1]
    params = make_params(cfg, args.tp)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, plen)), jnp.int32)

    def live():
        return live_pass(cfg, args.tp, s_max, params, prompts, args.tokens,
                         ref)

    gen0, _ = live()

    # publish epoch 1 (profiles first, MANIFEST last), then poll
    rep.save(live_dir, epoch=1, source_digest=shard_digest(shard_dir))
    swapped = ref.poll()
    if not swapped or ref.epoch != 1:
        failures.append(f"poll did not adopt epoch 1 "
                        f"(swapped={swapped}, epoch={ref.epoch})")
    gen1, rec_e1 = live()
    tuned_sites = sum(r.impl != "default" for r in rec_e1)
    emit("fleet_retune/epoch1_tuned_dispatches", float(tuned_sites),
         f"of {len(rec_e1)} recorded")
    if not tuned_sites:
        failures.append("no tuned selection of epoch 1 reached dispatch")
    bad = _served_mismatch(rec_e1, ref)
    if bad:
        failures.append(f"epoch 1 dispatches differ from its selection: "
                        f"{bad}")
    if not bool(jnp.array_equal(gen0, gen1)):
        failures.append("tuned epoch changed the generated tokens")

    # -- 4. staleness: a delayed epoch-0 writer must be refused --------------
    from repro.core import profiles as profiles_mod
    profiles_mod.write_manifest(live_dir, 0)
    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always")
        stale_swapped = ref.poll()
    if stale_swapped or ref.epoch != 1:
        failures.append("stale epoch 0 manifest was adopted")
    if not any("stale" in str(w.message) for w in wlog):
        failures.append("stale manifest refused without a warning")
    emit("fleet_retune/stale_epoch_refused",
         float(not stale_swapped and ref.epoch == 1))

    # -- 5. served-impl latencies -> feedback -> epoch 2 ---------------------
    noise_rng = np.random.default_rng(1)
    rec2 = ShardRecorder("live", seed=99)
    for r in rec_e1:
        rec2.append(r)
        impl = _selection(r.cell, r.phase, ref)
        # stand-in for wall clock: cost-model latency + measurement noise
        base_t = backend.latency(r.cell, impl)
        for _ in range(4):
            rec2.observe(r.cell, impl,
                         base_t * float(noise_rng.normal(1.0, 0.02)))
    rec2.flush(shard_dir, epoch=2)
    observed = load_shard_latencies(shard_dir)
    if rec_e1 and not observed:
        failures.append("served-impl measurements did not round-trip "
                        "through the shard files")
    emit("fleet_retune/feedback_pairs", float(len(observed)))

    fb = tuner.FeedbackBackend(backend, observed)
    rep2 = tuner.tune_trace(Trace.merge_shards(shard_dir).trace,
                            backend=fb, min_win=args.min_win)
    rep2.save(live_dir, epoch=2, source_digest=shard_digest(shard_dir))
    if not ref.poll() or ref.epoch != 2:
        failures.append(f"epoch 2 not adopted (epoch={ref.epoch})")
    _, rec_e2 = live()
    bad = _served_mismatch(rec_e2, ref)
    if bad:
        failures.append(f"epoch 2 dispatches differ from its selection: "
                        f"{bad}")
    emit("fleet_retune/final_epoch", float(ref.epoch))

    (out / "summary.json").write_text(json.dumps({
        "arch": cfg.name, "tp": args.tp, "topo": args.topo,
        "fleet": fleet, "merged_cells": len(fleet_trace),
        "merged_dispatches": fleet_trace.total(),
        "union_cost_us": {"default": cost_default * 1e6,
                          "merged": cost_merged * 1e6},
        "epoch1_tuned_dispatches": tuned_sites,
        "feedback_pairs": len(observed), "final_epoch": ref.epoch,
        "failures": failures,
    }, indent=1))

    for f in failures:
        print(f"ERROR: {f}", file=sys.stderr)
    return 1 if failures else 0


def _selection(cell, phase, ref):
    """The impl the live stores would dispatch for ``cell`` — mirrors
    ``estimate_trace_cost``'s resolution so synthesized fleet
    observations land on the (cell, impl) pairs drift is priced on."""
    from repro.core.collectives import REGISTRY
    name = ref.lookup(cell, phase)
    if name is None or name not in REGISTRY[cell.op]:
        return "default"
    impl = REGISTRY[cell.op][name]
    if (name != "default" and impl.requires_pow2
            and (cell.p & (cell.p - 1)) != 0):
        return "default"
    return name


def _worst_stores(trace, backend):
    """Per-phase stores that pick the WORST admissible impl for each
    (op, p) in the trace — a well-formed but genuinely bad generation,
    the kind a tune over poisoned measurements publishes."""
    import math
    from repro.core.collectives import REGISTRY
    from repro.core.profiles import Profile, ProfileStore, Range
    phases = {}
    for ph in trace.phases():
        profs = {}
        for cell in trace.cells(phase=ph):
            key = (cell.op, cell.p)
            if key in profs:
                continue
            worst, worst_t = None, -1.0
            for name, impl in REGISTRY[cell.op].items():
                if name == "default":
                    continue
                if impl.requires_pow2 and (cell.p & (cell.p - 1)) != 0:
                    continue
                t = backend.latency(cell, name)
                if math.isfinite(t) and t > worst_t:
                    worst, worst_t = name, t
            if worst is not None:
                profs[key] = Profile(cell.op, cell.p,
                                     [Range(0, 1 << 62, worst)])
        if profs:
            phases[ph] = ProfileStore(list(profs.values()))
    return phases


def chaos_main(args) -> int:
    """The chaos-injected fleet run (CI ``fleet-chaos`` job).

    Same loop as ``main``, under ``ft.ChaosMonkey`` fire.  Gates:

    A. torn + corrupt shards are QUARANTINED with exact weight
       accounting — the merged trace's total equals the surviving
       shards' sum, and the dropped weight equals the quarantined
       headers' claims;
    B. a manifest/profile-skewed publish is refused; the repaired
       republish (same epoch number, different manifest text — the case
       the content stamp exists for) is adopted;
    C. a published-but-regressing epoch trips ``EpochTripwire``, which
       rolls back; the rebuilt steps dispatch the restored epoch's impls
       and generate unchanged tokens, and the poisoned epoch is refused
       on re-publish;
    D. the coordinator flags the killed server and recommends a drift
       retune whose ratio reflects the MAD-filtered fleet observations
       (latency spikes rejected, not averaged in).

    Everything is seeded; the fault schedule and every gate are
    deterministic.
    """
    from repro.core import profiles as profiles_mod
    from repro.core.api import DispatchRecord
    from repro.core.profiles import EpochTripwire
    from repro.ft import ChaosMonkey, FleetCoordinator

    topo = cm.PRESETS[args.topo]
    cfg = get_config(args.arch).smoke()
    tokens = 4
    fleet = [(1, 8), (2, 16), (1, 32), (2, 8)]
    s_max = max(pl for _, pl in fleet) + tokens + 8
    backend = tuner.CostModelBackend(topo)
    monkey = ChaosMonkey(seed=20170701)

    header()
    out = pathlib.Path(args.out)
    shard_dir = out / "shards"
    live_dir = out / "live_profiles"
    import shutil
    for d in (shard_dir, live_dir):
        shutil.rmtree(d, ignore_errors=True)
    for d in (out, shard_dir, live_dir):
        d.mkdir(parents=True, exist_ok=True)
    failures: list[str] = []

    # -- A. fleet recording under fire: tear srv1, corrupt srv2 --------------
    rng = np.random.default_rng(0)
    paths, clean_totals, claims = [], [], []
    for i, (batch, plen) in enumerate(fleet):
        params = make_params(cfg, args.tp)
        prompts = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, plen)), jnp.int32)
        rec = ShardRecorder(f"srv{i}", seed=i)
        steps = make_steps(cfg, args.tp, s_max, batch)
        with api.tuned(record=rec):
            serve_pass(cfg, steps, params, prompts, tokens)
        claims.append(rec.total())
        paths.append(rec.flush(shard_dir, epoch=1))
        clean_totals.append(Trace.load(paths[i]).total())
    monkey.tear_shard(paths[1], keep_frac=0.5)
    monkey.corrupt_line(paths[2])

    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        report = Trace.merge_shards(shard_dir)
    bad_names = sorted(n.path.name for n in report.quarantined)
    want_bad = sorted(p.name for p in (paths[1], paths[2]))
    emit("fleet_chaos/shards_quarantined", float(len(report.quarantined)),
         ", ".join(bad_names))
    if bad_names != want_bad:
        failures.append(f"quarantined {bad_names}, expected {want_bad}")
    surviving = clean_totals[0] + clean_totals[3]
    emit("fleet_chaos/merged_dispatches", float(report.trace.total()),
         f"surviving shards sum to {surviving}")
    if report.trace.total() != surviving:
        failures.append(
            f"merged weight {report.trace.total()} != surviving shards' "
            f"{surviving} — quarantine accounting is inexact")
    want_dropped = claims[1] + claims[2]
    emit("fleet_chaos/dropped_weight", float(report.dropped_weight),
         f"claimed {want_dropped}")
    if report.dropped_weight != want_dropped:
        failures.append(
            f"dropped_weight {report.dropped_weight} != quarantined "
            f"headers' claims {want_dropped}")
    print(report.summary())

    # -- live serving over the surviving fleet trace -------------------------
    fleet_trace = report.trace
    rep = tuner.tune_trace(fleet_trace, backend=backend,
                           min_win=args.min_win)
    os.environ[PROFILE_DIR_ENV] = str(live_dir)
    ref = resolve_stores(watch=True)
    batch, plen = fleet[0]
    params = make_params(cfg, args.tp)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, plen)), jnp.int32)

    def live():
        return live_pass(cfg, args.tp, s_max, params, prompts, tokens, ref)

    rep.save(live_dir, epoch=1, source_digest=shard_digest(shard_dir))
    if not ref.poll() or ref.epoch != 1:
        failures.append(f"epoch 1 not adopted (epoch={ref.epoch})")

    # -- B. manifest/profile skew refused; repaired republish lands ----------
    rep.save(live_dir, epoch=2, source_digest="sha256:chaos-e2")
    monkey.skew_profiles(live_dir)
    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always")
        skew_swapped = ref.poll()
    skew_refused = (not skew_swapped and ref.epoch == 1
                    and any("skew" in str(w.message) for w in wlog))
    emit("fleet_chaos/manifest_skew_refused", float(skew_refused))
    if not skew_refused:
        failures.append("manifest/profile skew was not refused "
                        f"(swapped={skew_swapped}, epoch={ref.epoch})")
    rep.save(live_dir, epoch=2, source_digest="sha256:chaos-e2-fixed")
    if not ref.poll() or ref.epoch != 2:
        failures.append(f"repaired epoch 2 not adopted "
                        f"(epoch={ref.epoch})")
    gen2, rec_e2 = live()

    # -- C. regressing epoch 3 -> tripwire rollback, steps rebuilt -----------
    def live_cost():
        return sum(tuner.estimate_trace_cost(
            fleet_trace, backend, base=ref.base,
            phases=ref.phases).values())

    cost_good = live_cost()
    tw = EpochTripwire(ref, threshold=1.3, window=4, min_samples=2)
    for _ in range(3):
        tw.observe(cost_good)
    bad_phases = _worst_stores(fleet_trace, backend)
    for sub in [p for p in live_dir.iterdir() if p.is_dir()]:
        shutil.rmtree(sub)      # epoch 3 replaces the phase stores
    for ph, store in bad_phases.items():
        store.save(live_dir / ph)
    profiles_mod.write_manifest(live_dir, 3,
                                source_digest="sha256:chaos-e3")
    if not ref.poll() or ref.epoch != 3:
        failures.append(f"bad epoch 3 not adopted (epoch={ref.epoch})")
    cost_bad = live_cost()
    emit("fleet_chaos/bad_epoch_regression",
         cost_bad / cost_good if cost_good else 0.0,
         f"{cost_good * 1e6:.1f} -> {cost_bad * 1e6:.1f} us")
    if cost_bad <= 1.3 * cost_good:
        failures.append(
            f"injected epoch 3 does not regress past the tripwire "
            f"threshold ({cost_bad:.3e} vs {cost_good:.3e})")
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        fired = [tw.observe(cost_bad) for _ in range(3)]
    emit("fleet_chaos/rollback_fired", float(any(fired)),
         f"fired={tw.fired}")
    if tw.fired != [(3, 2)]:
        failures.append(f"tripwire fired {tw.fired}, expected "
                        "[(3, 2)] (bad epoch 3 -> restored 2)")
    # the rollback reaches dispatch when the server rebuilds its steps
    gen_r, rec_r = live()
    if [r.impl for r in rec_r] != [r.impl for r in rec_e2]:
        failures.append("rolled-back dispatches differ from the restored "
                        "epoch's")
    if not bool(jnp.array_equal(gen_r, gen2)):
        failures.append("rollback changed the generated tokens")
    # the poisoned epoch must be refused even on a fresh republish
    profiles_mod.write_manifest(live_dir, 3,
                                source_digest="sha256:chaos-e3-retry")
    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always")
        re_swapped = ref.poll()
    poisoned_refused = (not re_swapped and ref.epoch == 2
                        and any("poisoned" in str(w.message)
                                for w in wlog))
    emit("fleet_chaos/poisoned_epoch_refused", float(poisoned_refused))
    if not poisoned_refused:
        failures.append("poisoned epoch 3 re-publish was adopted "
                        "(or refused without a warning)")

    # -- D. coordinator: killed server + MAD-robust drift retune -------------
    now = [0.0]
    coord = FleetCoordinator(shard_dir, ref, backend=backend,
                             heartbeat_timeout=30.0,
                             drift_threshold=1.5,
                             clock=lambda: now[0])
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        st1 = coord.scan()     # everyone beat at epoch 1
    monkey.kill_server("srv3", at_epoch=2)
    now[0] += 60.0
    spiked = 0
    for i in range(len(fleet)):
        if not monkey.alive(f"srv{i}", 2):
            continue
        rec = ShardRecorder(f"srv{i}", seed=100 + i)
        for (cell, ph), _n in sorted(fleet_trace.histogram().items()):
            rec.append(DispatchRecord(cell, "default", ph))
            name = _selection(cell, ph, ref)
            for _ in range(3):           # hardware drifted 2x slower
                rec.observe(cell, name, 2.0 * backend.latency(cell, name))
        p = rec.flush(shard_dir, epoch=2)
        if i == 0:                        # one server caught a hiccup
            spiked = monkey.spike_latencies(p, factor=100.0)
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        st2 = coord.scan()
    print(st1.summary())
    print(st2.summary())
    emit("fleet_chaos/dead_servers", float(len(st2.dead)),
         ", ".join(st2.dead) or "-")
    if st2.dead != ["srv3"]:
        failures.append(f"coordinator flagged dead={st2.dead}, "
                        "expected ['srv3']")
    emit("fleet_chaos/drift", float(st2.drift or 0.0),
         f"{spiked} spiked sample(s) MAD-rejected")
    if st2.drift is None or not (1.5 < st2.drift < 3.0):
        failures.append(
            f"drift {st2.drift} outside (1.5, 3.0) — 2x-slower fleet "
            "observations should dominate; spikes must be rejected")
    if not (st2.retune and any("dead" in r for r in st2.reasons)
            and any("drift" in r for r in st2.reasons)):
        failures.append(f"coordinator did not recommend a retune for "
                        f"both failure and drift: {st2.reasons}")
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        obs = load_shard_latencies(
            shard_dir, skip=[n.path for n in report.quarantined])
    fb = tuner.FeedbackBackend(backend, obs)
    emit("fleet_chaos/mad_rejected", float(fb.rejected),
         f"{spiked} injected")
    if spiked and fb.rejected < spiked:
        failures.append(f"MAD filter rejected {fb.rejected} < {spiked} "
                        "injected spike(s)")

    (out / "summary.json").write_text(json.dumps({
        "arch": cfg.name, "tp": args.tp, "topo": args.topo,
        "chaos_events": [dataclasses.asdict(e) for e in monkey.events],
        "quarantined": bad_names,
        "merged_dispatches": report.trace.total(),
        "dropped_weight": report.dropped_weight,
        "rollback_fired": tw.fired,
        "dead_servers": st2.dead,
        "drift": st2.drift,
        "mad_rejected": fb.rejected,
        "failures": failures,
    }, indent=1))

    for f in failures:
        print(f"ERROR: {f}", file=sys.stderr)
    return 1 if failures else 0


def run():
    # benchmarks/run.py entry point: smoke-sized so the suite stays fast
    rc = main(["--smoke"])
    if rc:
        raise RuntimeError("bench_fleet_retune smoke failed")


if __name__ == "__main__":
    raise SystemExit(main())
