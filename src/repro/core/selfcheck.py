"""Multi-device SPMD validation of every mock-up against the numpy oracle.

On the CPU (``JAX_PLATFORMS=cpu``) run it as a subprocess, so the forced
host-device count never leaks into the parent; on chips it runs in the
process that holds them (``chip_smoke.py --chips 4``).  It fails when the
process sees fewer devices than ``--devices``:

    JAX_PLATFORMS=cpu python -m repro.core.selfcheck --devices 8 [--json]

Exercises every registered implementation through a REAL ``shard_map`` over a
multi-device mesh (the vmap semantic tests cover tracing; this covers SPMD
lowering + execution), comparing against dense numpy references.

Quantized-wire mock-ups (``wire_q8``/``wire_fp8``) are checked against a
PER-WIRE-DTYPE relative-error bound instead of the exact atol: a wire impl
whose max-norm relative error exceeds ``wire_tol(dtype, hops)`` is DEMOTED
from the admissible set (``collectives.demote``) exactly like a failed
guideline — reported under ``"demoted"`` in the JSON, not as a suite
failure.  ``run_gate`` exposes the same gate in-process for arbitrary
payloads (the adversarial-demotion tests and the bench gates use it).
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def wire_hops(op: str, p: int, p2: int = 0) -> int:
    """Number of independently-quantized error terms that can ADD into one
    output element of a wire impl — the multiplier on the single-roundtrip
    ``wire_tol`` base bound.

    Gather-style rings quantize each block once at its origin and no two
    blocks' errors meet (hops=1).  Reduction rings accumulate: the
    travelling-accumulator reduce-scatter requantizes the partial sum on
    every one of its p-1 hops, and the wire allreduce adds the AG
    re-quantize on top.  ``matmul_accumulate`` streams WEIGHT blocks that
    are each quantized only once — but the stationary-x contraction sums
    all p-1 wire-crossed blocks' independent errors into every output
    element, so the additive count is p-1, not 1 (counting requantize
    events of the travelling data, the old rule, under-bounds it and
    spuriously demotes benign payloads as p grows).  A 2-D cell's error
    budget is set by its inner reduction ring of size ``p2`` (the outer
    stream is gather-style); pass ``p2`` for those."""
    if op in ("reducescatter", "matmul_reducescatter", "matmul_accumulate"):
        return max(p - 1, 1)
    if op == "allreduce":
        return max(p, 1)
    if op == "matmul_reducescatter_2d":
        q = p2 if p2 else p
        return max(q - 1, 1)
    return 1


def rel_err(got, want) -> float:
    """Max-norm relative error — the wire-tolerance metric."""
    import numpy as np
    g = np.asarray(got, np.float64)
    w = np.asarray(want, np.float64)
    return float(np.max(np.abs(g - w)) / max(np.max(np.abs(w)), 1e-30))


def run_gate(op: str, name: str, x, *, w=None, demote: bool = True,
             p2: int = 0):
    """Run one impl of ``op`` on a CONCRETE stacked payload ``x`` ([p, ...],
    one leading row block per rank) under ``vmap`` and apply the wire
    tolerance gate against the dense numpy oracle.

    Returns ``(ok, rel, tol)``.  For a quantized-wire impl that breaks its
    tolerance the impl is demoted (unless ``demote=False``); non-wire impls
    are gated at the wire-agnostic 1e-5 bound and never demoted.  For a
    hierarchical (``Impl.hier``) mock-up pass ``p2`` (inner axis size,
    dividing ``p``): the p ranks run as a nested (p//p2, p2) vmap mesh in
    outer-major order.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import collectives as C
    from repro.kernels.quant import wire_tol

    impl = C.REGISTRY[op][name]
    p = x.shape[0]
    xs = jnp.asarray(x)
    xn = np.asarray(x, np.float64)
    if getattr(impl, "hier", False):
        if op not in ("allgather", "allreduce", "reducescatter"):
            raise KeyError(f"run_gate does not model hier {op!r}")
        if p2 <= 1 or p % p2:
            raise ValueError(
                f"hier impl {name!r} needs p2 in (1, p) dividing p={p}")
        nested = xs.reshape((p // p2, p2) + x.shape[1:])
        got = jax.vmap(jax.vmap(
            lambda s: impl.fn(s, "o", inner_axis="i"), axis_name="i"),
            axis_name="o")(nested)
        got = np.asarray(got).reshape((p,) + got.shape[2:])
        if op == "allgather":
            full = xn.reshape((-1,) + xn.shape[2:])
            want = np.broadcast_to(full, (p,) + full.shape)
        elif op == "allreduce":
            want = np.broadcast_to(xn.sum(0), (p,) + xn.shape[1:])
        else:
            want = xn.sum(0).reshape((p, -1) + xn.shape[2:])
    elif op in ("allgather", "allreduce", "reducescatter"):
        got = jax.vmap(lambda s: impl.fn(s, "x"), axis_name="x")(xs)
        if op == "allgather":
            full = xn.reshape((-1,) + xn.shape[2:])
            want = np.broadcast_to(full, (p,) + full.shape)
        elif op == "allreduce":
            want = np.broadcast_to(xn.sum(0), (p,) + xn.shape[1:])
        else:
            want = xn.sum(0).reshape((p, -1) + xn.shape[2:])
    elif op in ("allgather_matmul", "matmul_reducescatter"):
        wj = jnp.asarray(w)
        got = jax.vmap(lambda s: impl.fn(s, "x", w=wj), axis_name="x")(xs)
        wn = np.asarray(w, np.float64)
        if op == "allgather_matmul":
            full = xn.reshape(-1, xn.shape[-1]) @ wn
            want = np.broadcast_to(full, (p,) + full.shape)
        else:
            want = (xn @ wn).sum(0).reshape(p, -1, wn.shape[-1])
    elif op == "matmul_accumulate":
        # x = stacked weight K-blocks [p, k_loc, m]; w = stationary [T, K]
        stat = jnp.asarray(w)
        got = jax.vmap(lambda s: impl.fn(s, "x", x=stat), axis_name="x")(xs)
        full_w = xn.reshape(-1, xn.shape[-1])
        wantv = np.asarray(w, np.float64) @ full_w
        want = np.broadcast_to(wantv, (p,) + wantv.shape)
    else:
        raise KeyError(f"run_gate does not model {op!r}")
    rel = rel_err(got, want)
    if impl.wire_dtype is None:
        return rel <= 1e-5, rel, 1e-5
    tol = wire_tol(impl.wire_dtype, wire_hops(op, p))
    ok = rel <= tol
    if not ok and demote:
        C.demote(op, name, reason=f"tolerance rel={rel:.3g} > {tol:.3g}")
    return ok, rel, tol


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # host devices stand in for chips only on the CPU backend
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={args.devices}")

    import jax

    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    have = len(jax.devices())
    if have < args.devices:
        print(f"selfcheck: --devices {args.devices}, but this process sees "
              f"{have} {jax.default_backend()} device(s)", file=sys.stderr)
        return 2
    # the checks compare with float64 numpy at atol 1e-5: f32 matmuls run
    # at full f32 precision (a TPU's default is a bf16 pass)
    with jax.default_matmul_precision("highest"):
        return _checks(args.devices, args.json)


def _checks(P_: int, as_json: bool) -> int:
    """Every registered impl through a real ``shard_map`` over the first
    ``P_`` devices (and a 2 x P_/2 mesh); returns the exit code."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    from repro.core import collectives as C

    mesh = Mesh(np.array(jax.devices()[:P_]), ("x",))
    rng = np.random.default_rng(42)

    def run(fn, x, **kw):
        sm = shard_map(lambda a: fn(a, "x", **kw), mesh=mesh,
                       in_specs=P("x"), out_specs=P("x"), check_vma=False)
        return np.asarray(jax.jit(sm)(x)).reshape((P_, -1) + x.shape[1:])

    n, w = 6, 3
    x = rng.normal(size=(P_, n, w)).astype(np.float32)
    xb = rng.normal(size=(P_, P_ * n, w)).astype(np.float32)
    xf = jnp.asarray(x.reshape(P_ * n, w))
    xbf = jnp.asarray(xb.reshape(P_ * P_ * n, w))
    full = x.reshape(P_ * n, w)

    from repro.kernels.quant import wire_tol

    results = {}
    demoted = []

    def rtol_for(op, nm):
        wd = C.REGISTRY[op][nm].wire_dtype
        return None if wd is None else wire_tol(wd, wire_hops(op, P_))

    def check(name, got, want, rank=None, *, rtol=None, key=None):
        g = got if rank is None else got[rank]
        if rtol is None:
            ok = bool(np.allclose(g, want, atol=1e-5))
        else:
            # wire tolerance gate: max-norm relative error per wire dtype;
            # breaking it demotes the impl, it does not fail the suite.
            rel = rel_err(g, want)
            ok = rel <= rtol
            if not ok and key is not None:
                C.demote(key[0], key[1],
                         reason=f"tolerance rel={rel:.3g} > {rtol:.3g}")
                demoted.append(name)
        results[name] = ok
        if not as_json:
            tag = "OK" if ok else ("DEMOTED" if name in demoted else "FAIL")
            print(f"{name:44s} {tag}")

    for nm in C.impl_names("allgather"):
        if C.REGISTRY["allgather"][nm].hier:
            continue                     # needs inner_axis — hier section
        y = run(C.REGISTRY["allgather"][nm].fn, xf)
        check(f"allgather/{nm}", y, np.broadcast_to(full, (P_,) + full.shape),
              rtol=rtol_for("allgather", nm), key=("allgather", nm))
    want = x.sum(0)
    for nm in C.impl_names("allreduce"):
        if C.REGISTRY["allreduce"][nm].hier:
            continue
        y = run(C.REGISTRY["allreduce"][nm].fn, xf, chunk=2)
        check(f"allreduce/{nm}", y, np.broadcast_to(want, (P_,) + want.shape),
              rtol=rtol_for("allreduce", nm), key=("allreduce", nm))
    wantrs = xb.sum(0).reshape(P_, n, w)
    for nm in C.impl_names("reducescatter"):
        if C.REGISTRY["reducescatter"][nm].hier:
            continue
        check(f"reducescatter/{nm}", run(C.REGISTRY["reducescatter"][nm].fn, xbf),
              wantrs, rtol=rtol_for("reducescatter", nm),
              key=("reducescatter", nm))

    # hierarchical MPIX mock-ups (and the defaults' inner_axis path): a
    # REAL two-axis ("o" outer/slow, "i" inner/fast) mesh; the joint-group
    # result in outer-major block order must match the flat oracle exactly
    d_h = 2
    mesh_h = Mesh(np.array(jax.devices()[:P_]).reshape(d_h, P_ // d_h),
                  ("o", "i"))

    def run_h(fn, xin):
        sm = shard_map(lambda a: fn(a, "o", inner_axis="i"), mesh=mesh_h,
                       in_specs=P(("o", "i")), out_specs=P(("o", "i")),
                       check_vma=False)
        return np.asarray(jax.jit(sm)(xin)).reshape(
            (P_, -1) + xin.shape[1:])

    for op, xin, wanth in (
            ("allgather", xf, np.broadcast_to(full, (P_,) + full.shape)),
            ("allreduce", xf, np.broadcast_to(want, (P_,) + want.shape)),
            ("reducescatter", xbf, wantrs)):
        for nm in C.impl_names(op):
            impl = C.REGISTRY[op][nm]
            if not (impl.hier or nm == "default"):
                continue
            check(f"{op}@{d_h}x{P_ // d_h}/{nm}", run_h(impl.fn, xin),
                  wanth)
    wanta2a = xb.reshape(P_, P_, n, w).transpose(1, 0, 2, 3).reshape(
        P_, P_ * n, w)
    for nm in C.impl_names("alltoall"):
        check(f"alltoall/{nm}", run(C.REGISTRY["alltoall"][nm].fn, xbf), wanta2a)
    # non-zero roots (exercise the relative-rank schedules) on any P_
    r_b, r_g, r_s = 3 % P_, 2 % P_, 5 % P_
    for nm in C.impl_names("bcast"):
        y = run(C.REGISTRY["bcast"][nm].fn, xf, root=r_b)
        check(f"bcast/{nm}", y, np.broadcast_to(x[r_b], (P_, n, w)))
    for nm in C.impl_names("gather"):
        y = run(C.REGISTRY["gather"][nm].fn, xf, root=r_g)
        check(f"gather/{nm}", y, full, rank=r_g)
    wantsc = xb[r_s].reshape(P_, n, w)
    for nm in C.impl_names("scatter"):
        check(f"scatter/{nm}", run(C.REGISTRY["scatter"][nm].fn, xbf, root=r_s),
              wantsc)
    for nm in C.impl_names("reduce"):
        y = run(C.REGISTRY["reduce"][nm].fn, xf, root=1, chunk=2)
        check(f"reduce/{nm}", y, x.sum(0), rank=1)
    wantscan = np.cumsum(x, axis=0)
    for nm in C.impl_names("scan"):
        check(f"scan/{nm}", run(C.REGISTRY["scan"][nm].fn, xf), wantscan)
    check("exscan/default", run(C.REGISTRY["exscan"]["default"].fn, xf),
          wantscan - x)

    # fused collective-matmul ops: w is a shard-local (replicated) closure
    # operand; output width differs from the input so run() can't reshape
    wm = rng.normal(size=(w, 4)).astype(np.float32)

    def run_mm(fn, xin, out_shape):
        sm = shard_map(lambda a: fn(a, "x", w=jnp.asarray(wm)), mesh=mesh,
                       in_specs=P("x"), out_specs=P("x"), check_vma=False)
        return np.asarray(jax.jit(sm)(xin)).reshape((P_,) + out_shape)

    want_agmm = full @ wm
    for nm in C.impl_names("allgather_matmul"):
        y = run_mm(C.REGISTRY["allgather_matmul"][nm].fn, xf,
                   want_agmm.shape)
        check(f"allgather_matmul/{nm}", y,
              np.broadcast_to(want_agmm, (P_,) + want_agmm.shape),
              rtol=rtol_for("allgather_matmul", nm),
              key=("allgather_matmul", nm))
    want_mmrs = (xb @ wm).sum(0).reshape(P_, n, 4)
    for nm in C.impl_names("matmul_reducescatter"):
        y = run_mm(C.REGISTRY["matmul_reducescatter"][nm].fn, xbf, (n, 4))
        check(f"matmul_reducescatter/{nm}", y, want_mmrs,
              rtol=rtol_for("matmul_reducescatter", nm),
              key=("matmul_reducescatter", nm))

    # matmul_accumulate: the SHARDED operand is the K-dim weight block; the
    # stationary x [T, K] is a shard-local closure operand
    k_loc, t_rows = 2, 5
    wacc = rng.normal(size=(P_ * k_loc, 4)).astype(np.float32)
    xacc = rng.normal(size=(t_rows, P_ * k_loc)).astype(np.float32)
    want_acc = xacc @ wacc

    def run_acc(fn):
        sm = shard_map(lambda wb: fn(wb, "x", x=jnp.asarray(xacc)),
                       mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                       check_vma=False)
        return np.asarray(jax.jit(sm)(jnp.asarray(wacc))).reshape(
            (P_, t_rows, 4))

    for nm in C.impl_names("matmul_accumulate"):
        y = run_acc(C.REGISTRY["matmul_accumulate"][nm].fn)
        check(f"matmul_accumulate/{nm}", y,
              np.broadcast_to(want_acc, (P_,) + want_acc.shape),
              rtol=rtol_for("matmul_accumulate", nm),
              key=("matmul_accumulate", nm))

    # matmul_reducescatter_2d: a REAL two-axis mesh ("a" = the outer
    # weight-stream/gather axis, "b" = the inner reduce-scatter axis).
    # Forward: shard (a=i, b=j) holds x's j-th K-slice and W's (j K-rows,
    # i col-block) — the row_matmul(fsdp_dim=1) layout — so the inner RS
    # performs the model-axis contraction sum and the outer stream the
    # data-axis weight gather.  Xpose: the cotangent's rows shard over
    # "a" (gathered + CONTRACTED), each "b" rank contributes a different
    # stationary x (the data-batch sum of the dw schedule).
    d2 = 2
    q2 = P_ // d2
    mesh2 = Mesh(np.array(jax.devices()[:P_]).reshape(d2, q2), ("a", "b"))
    t2, kl2, ml2 = 2 * q2, 3, 4
    x2d = rng.normal(size=(t2, q2 * kl2)).astype(np.float32)
    w2d = rng.normal(size=(q2 * kl2, d2 * ml2)).astype(np.float32)
    g2d = rng.normal(size=(t2, d2 * ml2)).astype(np.float32)
    xb2d = rng.normal(size=(t2, q2 * kl2)).astype(np.float32)
    want_2d = x2d @ w2d                                       # [t2, M]
    want_2dt = sum(                                           # [M, kl2]
        g2d.T @ xb2d[:, j * kl2:(j + 1) * kl2] for j in range(q2))

    for nm in C.impl_names("matmul_reducescatter_2d"):
        fn = C.REGISTRY["matmul_reducescatter_2d"][nm].fn

        def body_f(xb, wb, fn=fn):
            return fn(wb, "a", x=xb, rs_axis="b")

        sm = shard_map(body_f, mesh=mesh2,
                       in_specs=(P(None, "b"), P("b", "a")),
                       out_specs=P("b", None), check_vma=False)
        y = np.asarray(jax.jit(sm)(jnp.asarray(x2d), jnp.asarray(w2d)))
        check(f"matmul_reducescatter_2d/{nm}", y, want_2d)

        def body_t(gb, xb, fn=fn):
            return fn(gb, "a", x=xb, rs_axis="b", xpose=True)

        sm_t = shard_map(body_t, mesh=mesh2,
                         in_specs=(P("a", None), P(None, "b")),
                         out_specs=P("b", None), check_vma=False)
        yt = np.asarray(jax.jit(sm_t)(jnp.asarray(g2d), jnp.asarray(xb2d)))
        check(f"matmul_reducescatter_2d/{nm}/xpose", yt, want_2dt)

    fails = [k for k, v in results.items() if not v and k not in demoted]
    if as_json:
        print(json.dumps({"devices": P_, "total": len(results),
                          "failures": fails, "demoted": demoted}))
    else:
        print(f"\n{len(results)} checks, failures: {fails or 'none'}, "
              f"demoted: {demoted or 'none'}")
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
