"""Prove that the tuned training path runs on TPU chips.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the multi-chip path, on four chips

One chip: ``launch/train.py`` trains rwkv6-3b at its published widths,
cut to ``ONE_CHIP["layers"]`` layers, on a 1x1 (data, model) mesh, so the
``shard_map`` step and the tuned dispatch are traced as on a pod; then the
compiled ``pallas_matmul`` runs once at the model's chunk shape against
``jnp.matmul``.

Four chips: ``core.selfcheck`` checks every registered mock-up on the
4-chip axis and on the (2, 2) mesh.  The rwkv6-3b step, cut to
``FOUR_CHIPS["layers"]`` layers on a 2x2 (data, model) mesh, is traced for
its dispatch sites; ``tuner.tune_trace`` measures its small
plain-collective sites on a 2-chip sub-mesh, at the step's own axis size,
and writes a profile directory.  The step then trains three times: default
dispatch, that profile, and forced mock-ups (``FORCE``).  The tuned and forced runs must dispatch
mock-ups, and their losses must agree with the default run's.  Last, at
the one-chip cut, the 2x2 step's per-leaf gradients must agree with the
unsharded gradients on one chip, from the same parameters and batch.

Everything runs in this one process.  It exits non-zero, without a result
line, when JAX finds no TPU or a phase fails.  Step times and peak memory
are printed for information; they are not benchmark metrics.  The last
line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import math
import pathlib
import re
import sys
import tempfile
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent

ARCH = "rwkv6-3b"
VOCAB = 65_536
#: depth cut and token batch per chip count, chosen from the step's
#: ``compiled.memory_analysis()`` for a described v5e (see CHANGES.md):
#: 4 layers = 677M parameters, ~13.7 GiB of arguments + temporaries on
#: one 16 GiB chip; 8 layers over 2 (data) x 2 (model) = 1019M
#: parameters, ~12.7 GiB a chip
ONE_CHIP = {"layers": 4, "global_batch": 2, "seq": 1024, "steps": 4}
FOUR_CHIPS = {"layers": 8, "global_batch": 12, "seq": 1024, "steps": 3}

#: step-0 loss: the head is N(0, 1/d_model) against a unit-RMS final norm,
#: so every logit is ~N(0, 1) and the expected cross-entropy is
#: ln(V) + 1/2 (11.59).  On a TPU v5e the 4-layer cut reads 11.52 at
#: step 0; a wrong vocabulary, normalisation or init moves it by more
#: than 0.25.
LOSS0 = math.log(VOCAB) + 0.5
LOSS0_TOL = 0.25
#: default vs tuned or forced dispatch on the same data and init.  The
#: exact mock-ups reorder sums, and the fused rings round each chunk's
#: bf16 partial product before adding it: at smoke widths on the CPU the
#: forced run's losses moved by up to 3.9e-3 in bf16 and by 0 in f32.
#: One ring chunk doubled moved the step-0 loss by 0.72 there.  The lossy
#: quantized-wire mock-ups are held out of these runs (selfcheck bounds
#: their error).
TUNED_LOSS_TOL = 1e-2
#: the step-0 grad norm (same parameters, lr 0) of those runs, relative:
#: 6.5e-3 apart at smoke widths in bf16; a backward collective that drops
#: or doubles a shard moves it by far more
TUNED_GNORM_TOL = 5e-2
#: the tuned run takes any mock-up that measured faster than the default
#: at a site (the paper's rule asks for 10 %, which XLA's collectives on
#: the chip leave no mock-up to meet at these sites), so that the profile
#: reaches the step and its mock-ups run in it; this claims no gain
SMOKE_MIN_WIN = 0.0
#: the tune phase measures the step's plain-collective cells of up to
#: 1 MiB: 8 of its 18 p = 2 cells, about 50 small programs.  All 18, with
#: the fused rings' GEMMs and the 84-168 MB gathers, took 571 s on four
#: v5e chips, mostly compiling; the forced run puts the fused rings in the
#: step instead
TUNE_OPS = ("allreduce", "allgather", "reducescatter")
TUNE_MAX_BYTES = 1 << 20
#: mock-ups forced at every site of their op in the third 2x2 run: the
#: fused rings at the fused-matmul sites, and non-default plain collectives
FORCE = ";".join(f"{op}:alg={impl}" for op, impl in (
    ("allgather_matmul", "fused_ring"),
    ("matmul_reducescatter", "fused_ring"),
    ("matmul_accumulate", "fused_ring"),
    ("matmul_reducescatter_2d", "fused_ring2d"),
    ("allreduce", "allreduce_as_rsb_allgather"),
    ("allgather", "allgather_as_allgatherv"),
    ("reducescatter", "rsb_as_reduce_scatter_irr")))
#: 2x2 vs one chip, per leaf, ||g_2x2 - g_1|| / ||g_1||, same parameters
#: and batch: the two differ only in how bf16 activations are split and
#: summed (at most 2.0e-2 at smoke widths on the CPU; in f32 the CPU test
#: tests/test_spmd_grads.py holds them to 1e-4); a leaf whose gradient is
#: counted once per model shard, or lost on one, is off by 0.5 or more
LEAF_GRAD_TOL = 0.2
#: and the step-0 losses, as TUNED_LOSS_TOL (3e-4 apart at smoke widths)
GRAD_LOSS_TOL = 1e-2

#: per-chunk matmul of the fused rings at rwkv6-3b's widths: the
#: channel-mix key projection w_ck [2560, 8960] split over a 2-way data
#: (K) and 2-way model (N) axis, at the one-chip run's 2048 tokens
CHUNK_MKN = (2048, 1280, 4480)
#: both sides multiply bf16 operands exactly and accumulate in f32; they
#: differ in summation order and in the final rounding to bf16, i.e. by
#: about one bf16 ulp (<= 2^-7 of the magnitude).  Two ulps of the largest
#: output; a wrong tile or index is off by O(1) of it.
CHUNK_REL_TOL = 2.0 ** -6

STEP_LINE = re.compile(r"step\s+(\d+)\s+loss\s+(\S+)\s+gnorm\s+(\S+)"
                       r"\s+lr\s+\S+\s+([\d.]+) ms")
DISPATCH_LINE = re.compile(r"dispatch: (\d+) sites traced, (\d+) not default"
                           r"(?:: (.*))?")


class _Tee(io.TextIOBase):
    def __init__(self, *streams):
        self.streams = streams

    def write(self, s):
        for st in self.streams:
            st.write(s)
        return len(s)

    def flush(self):
        for st in self.streams:
            st.flush()


def train(mesh: str, cut: dict, ckpt_dir: str, *, profile_dir: str = "",
          force: str = ""):
    """``launch/train.py`` main; returns its ``[(step, loss, gnorm, ms)]``
    log and
    the ``{"op=impl", ...}`` mock-ups its traced step dispatched."""
    from repro.launch import train as launch_train
    argv = ["--arch", ARCH, "--layers", str(cut["layers"]), "--mesh", mesh,
            "--global-batch", str(cut["global_batch"]),
            "--seq", str(cut["seq"]), "--steps", str(cut["steps"]),
            "--log-every", "1", "--ckpt-every", str(cut["steps"] + 1),
            "--ckpt-dir", ckpt_dir]
    if profile_dir:
        argv += ["--profile-dir", profile_dir]
    if force:
        argv += ["--force", force]
    buf = io.StringIO()
    with contextlib.redirect_stdout(_Tee(sys.stdout, buf)):
        rc = launch_train.main(argv)
    if rc != 0:
        raise RuntimeError(f"launch/train.py exited {rc}")
    log = [(int(m[1]), float(m[2]), float(m[3]), float(m[4]))
           for m in STEP_LINE.finditer(buf.getvalue())]
    if len(log) != cut["steps"]:
        raise RuntimeError(f"expected {cut['steps']} step lines, got {log}")
    bad = [r for r in log if not math.isfinite(r[1])]
    if bad:
        raise RuntimeError(f"non-finite loss: {bad}")
    if abs(log[0][1] - LOSS0) > LOSS0_TOL:
        raise RuntimeError(f"step-0 loss {log[0][1]} not within {LOSS0_TOL}"
                           f" of ln({VOCAB}) + 1/2 = {LOSS0:.4f}")
    m = DISPATCH_LINE.search(buf.getvalue())
    if m is None:
        raise RuntimeError("launch/train.py printed no dispatch line")
    picked = {s.rsplit(" x", 1)[0] for s in (m[3] or "").split(", ") if s}
    return log, picked


def _report_memory():
    import jax
    for d in jax.devices():
        stats = d.memory_stats() or {}
        print(f"peak_bytes_in_use[{d.id}] = {stats.get('peak_bytes_in_use')}"
              f"  (bytes_limit {stats.get('bytes_limit')})")
        print(f"memory_stats[{d.id}] = {json.dumps(stats, sort_keys=True)}")


def phase_train_one_chip():
    with tempfile.TemporaryDirectory() as ckpt:
        log, _ = train("1x1", ONE_CHIP, ckpt)
    print("step times ms:", [r[3] for r in log])
    _report_memory()


def phase_pallas_matmul():
    import jax
    import jax.numpy as jnp
    from repro.kernels.collective_matmul import pallas_matmul
    m, k, n = CHUNK_MKN
    kx, kw = jax.random.split(jax.random.key(0))
    x = jax.random.normal(kx, (m, k), jnp.bfloat16)
    w = jax.random.normal(kw, (k, n), jnp.bfloat16)
    got = pallas_matmul(x, w, interpret=False).astype(jnp.float32)
    want = jnp.matmul(x, w).astype(jnp.float32)
    rel = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    print(f"pallas_matmul {m}x{k}x{n} bf16: max|diff|/max|ref| = {rel}"
          f" (tol {CHUNK_REL_TOL})")
    if not rel <= CHUNK_REL_TOL:
        raise RuntimeError("pallas_matmul disagrees with jnp.matmul")


def phase_selfcheck():
    from repro.core import selfcheck
    rc = selfcheck.main(["--devices", "4", "--json"])
    if rc != 0:
        raise RuntimeError(f"selfcheck exited {rc}")


def _cut_config(cut: dict):
    from repro.configs import get_config
    return dataclasses.replace(get_config(ARCH), n_layers=cut["layers"])


def record_step_trace(cut: dict, keep):
    """The dispatch trace of the 2x2 training step at ``cut``, of the cells
    ``keep(cell)`` admits: the step is traced for its shapes, not run."""
    import jax
    from repro.core.trace import Trace
    from repro.data import make_batch
    from repro.launch.mesh import make_host_mesh
    from repro.train import Trainer
    cfg = _cut_config(cut)
    record: list = []
    tr = Trainer(cfg, mesh=make_host_mesh((2, 2), ("data", "model")),
                 record=record)
    params, opt = jax.eval_shape(tr.init, 0)
    batch = make_batch(cfg, cut["global_batch"], cut["seq"], 0)
    jax.eval_shape(tr.step, params, opt, batch, 0)
    return Trace.from_record([r for r in record if keep(r.cell)])


def hold_out_lossy_mockups():
    from repro.core import collectives as C
    for op, impls in C.REGISTRY.items():
        for name, impl in impls.items():
            if impl.wire_dtype:
                C.demote(op, name, "lossy wire; chip_smoke compares losses")


def phase_tune(profile_dir: str):
    from repro.core import tuner
    hold_out_lossy_mockups()
    trace = record_step_trace(FOUR_CHIPS, lambda c: (
        c.op in TUNE_OPS and c.nbytes <= TUNE_MAX_BYTES))
    rep = tuner.tune_trace(trace, min_win=SMOKE_MIN_WIN,
                           backend=tuner.MeasuredBackend(K=2, max_nrep=10,
                                                         axis_size=2))
    print(rep.summary())
    for ms in rep.measurements:
        c = ms.cell
        print(f"  {c.op:24s} p={c.p} {c.nbytes:>10d} B {ms.impl:28s} "
              f"{ms.latency * 1e6:10.1f} us  nrep={ms.nrep}")
    rep.save(profile_dir)
    files = sorted(str(p.relative_to(profile_dir))
                   for p in pathlib.Path(profile_dir).rglob("*")
                   if p.is_file())
    print(f"profiles written: {files}")
    if not files:
        raise RuntimeError("measured tuning wrote no profile")


def phase_train_default_tuned_forced(profile_dir: str):
    runs = {}
    for name, kw in (("default", {}), ("tuned", {"profile_dir": profile_dir}),
                     ("forced", {"force": FORCE})):
        with tempfile.TemporaryDirectory() as ckpt:
            runs[name] = train("2x2", FOUR_CHIPS, ckpt, **kw)
        log, picked = runs[name]
        print(f"{name}: step times ms {[r[3] for r in log]}; "
              f"mock-ups dispatched {sorted(picked)}")
    _report_memory()
    if runs["default"][1]:
        raise RuntimeError("default dispatch picked mock-ups")
    forced = {s.replace(":alg=", "=") for s in FORCE.split(";")}
    for name, allowed in (("tuned", None), ("forced", forced)):
        log, picked = runs[name]
        if not picked:
            raise RuntimeError(f"the {name} run dispatched no mock-up")
        if allowed is not None and not picked <= allowed:
            raise RuntimeError(f"{name} run dispatched {picked - allowed}")
        base = runs["default"][0]
        diffs = [abs(a[1] - b[1]) for a, b in zip(base, log)]
        gdiff = abs(log[0][2] - base[0][2]) / base[0][2]
        print(f"|loss default - loss {name}| per step: {diffs}"
              f" (tol {TUNED_LOSS_TOL}); step-0 grad norm rel diff {gdiff}"
              f" (tol {TUNED_GNORM_TOL})")
        if max(diffs) > TUNED_LOSS_TOL or not gdiff <= TUNED_GNORM_TOL:
            raise RuntimeError(f"default and {name} dispatch disagree")


def phase_grads_2x2_vs_one_chip():
    """Per-leaf gradients of the 2x2 step against the unsharded step on
    one chip, at the one-chip cut, from the same parameters and batch."""
    import jax
    from repro.data import make_batch
    from repro.launch.mesh import make_host_mesh
    from repro.models import lm
    from repro.models.params import init_tree
    from repro.train.gradcheck import leaf_grad_check

    cfg = _cut_config(ONE_CHIP)
    specs = lm.model_specs(cfg, 1)
    params = jax.jit(lambda k: init_tree(specs, k))(jax.random.key(0))
    out = leaf_grad_check(
        cfg, params,
        make_batch(cfg, ONE_CHIP["global_batch"], ONE_CHIP["seq"], 0),
        make_host_mesh((2, 2), ("data", "model")))
    gnorm1 = sum(n ** 2 for n in out["norm1"].values()) ** 0.5
    print(f"step-0 loss one chip {out['loss1']}, 2x2 {out['loss']}; "
          f"grad norm one chip {gnorm1}")
    for k, r in sorted(out["rel"].items(), key=lambda kv: -kv[1]):
        print(f"  {r:.3e}  |g|={out['norm1'][k]:.4e}  {k}")
    bad = {k: r for k, r in out["rel"].items() if not r <= LEAF_GRAD_TOL}
    if bad:
        raise RuntimeError(f"leaf gradients off by more than "
                           f"{LEAF_GRAD_TOL}: {bad}")
    if out["split"]:
        raise RuntimeError(f"replicas disagree: {out['split']}")
    if abs(out["loss1"] - out["loss"]) > GRAD_LOSS_TOL:
        raise RuntimeError("step-0 losses disagree")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips}, but JAX sees {len(devs)}",
              file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.jax_cache import enable_compile_cache
    print(f"{devs[0].device_kind} x{len(devs)}; compile cache "
          f"{enable_compile_cache()}", flush=True)

    with tempfile.TemporaryDirectory() as profile_dir:
        if args.chips == 1:
            phases = [("train 1x1", phase_train_one_chip),
                      ("pallas_matmul", phase_pallas_matmul)]
        else:
            phases = [("selfcheck", phase_selfcheck),
                      ("tune", lambda: phase_tune(profile_dir)),
                      ("train 2x2 default, tuned, forced",
                       lambda: phase_train_default_tuned_forced(profile_dir)),
                      ("grads 2x2 vs one chip", phase_grads_2x2_vs_one_chip)]
        failed = []
        for name, fn in phases:
            t0 = time.perf_counter()
            try:
                fn()
            except Exception:
                traceback.print_exc()
                failed.append(name)
            status = "FAILED" if name in failed else "ok"
            print(f"phase {name}: {status} "
                  f"({time.perf_counter() - t0:.1f} s)", flush=True)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
