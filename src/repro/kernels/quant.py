"""Quantized wire format for the ring collectives (per-block symmetric scales).

The ``wire_q8`` / ``wire_fp8`` mock-up families (core/collectives.py) compress
the TRAVELLING operand of a ring schedule to an 8-bit wire dtype; this module
owns the wire format and the quantize/dequantize tiers:

Wire format
-----------
A payload ``[n, ...]`` is split into blocks of ``BLOCK_ROWS`` leading rows
(the last block may be short).  Each block carries one f32 symmetric scale::

    scale_b = max(|x_b|) / QMAX[wire_dtype]        (>= a tiny floor)
    q_b     = round(x_b / scale_b)   as int8       (wire_q8)
            = (x_b / scale_b)        as e4m3 fp8   (wire_fp8)

Dequantization is ``q.astype(f32) * scale``; REDUCTIONS ALWAYS ACCUMULATE IN
f32 AFTER DEQUANT (the rule the selfcheck tolerance gate assumes — see
DESIGN_KERNELS.md "Quantized wire").  The per-element error of one
quantize/dequantize round trip is bounded by half a quantization step::

    |x - deq(q)| <= scale_b / 2 = max(|x_b|) / (2 * QMAX)   (int8)
    |x - deq(q)| <= |x| * 2**-4                             (e4m3 fp8)

so a gather-style wire (one quantization at the origin, the pair travels
as-is) has max-norm relative error ~``1/(2*QMAX)``, while a travelling
ACCUMULATOR (reduce-scatter/allreduce) requantizes per hop and the bound
scales with the hop count — ``wire_tol`` encodes both regimes.

Execution tiers (same split as kernels/collective_matmul.py):

1. ``quantize``/``dequantize`` — pure jnp, usable inside shard_map / vmap
   ring steps on any backend (CPU CI included); XLA fuses them into the
   surrounding ring step.
2. ``quant_pack``/``dequant_unpack`` — the per-block Pallas kernels in the
   kernels/pack.py style (grid over blocks, one scale per grid step), the
   TPU tier; exercised on CPU via ``interpret=True``.  The scales live
   whole in SMEM as a flat ``[nblocks]`` vector (one scalar store/load per
   grid step) — a ``(1, 1)`` VMEM block breaks the (8, 128) tiling rule.
   SMEM is small, so one call handles payloads up to a few thousand blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["WIRE_DTYPES", "WIRE_ITEMSIZE", "QMAX", "BLOCK_ROWS", "BASE_TOL",
           "wire_tol", "quantize", "dequantize", "wire_roundtrip",
           "quant_pack", "dequant_unpack"]

#: wire dtypes of the quantized mock-up families (impl name -> dtype lives in
#: collectives.REGISTRY[op][name].wire_dtype)
WIRE_DTYPES = ("int8", "float8_e4m3fn")

#: bytes per wire element — the costmodel's wire_width term
WIRE_ITEMSIZE = {"int8": 1, "float8_e4m3fn": 1}

#: largest representable magnitude per wire dtype (e4m3 max finite = 448)
QMAX = {"int8": 127.0, "float8_e4m3fn": 448.0}

#: rows per scale block (one f32 scale per BLOCK_ROWS leading rows)
BLOCK_ROWS = 8

#: single-roundtrip max-norm relative error bound per wire dtype, with ~4x
#: headroom over the analytic half-step bound (1/254 for int8; 2**-4 for the
#: 3-bit e4m3 mantissa) so benign rounding never trips the gate while a
#: payload the format cannot represent (cancellation, huge in-block dynamic
#: range) still does.
BASE_TOL = {"int8": 4.0 / 254.0, "float8_e4m3fn": 4.0 * 2.0 ** -4}

_SCALE_FLOOR = 1e-30


def wire_tol(wire_dtype: str, hops: int = 1) -> float:
    """Max-norm relative error bound for a wire impl whose travelling data
    is (re)quantized ``hops`` times: gather-style rings quantize once at the
    origin (hops=1); travelling accumulators requantize per hop (hops=p-1)
    and worst-case errors add."""
    return BASE_TOL[wire_dtype] * max(int(hops), 1)


def _nblocks(n: int, block_rows: int) -> int:
    return -(-n // block_rows)


def _row_scales(scales, n: int, block_rows: int, ndim: int):
    """Per-row scale vector [n, 1, ..] from the per-block scales [nb, 1]."""
    per_row = scales.reshape(-1)[jnp.arange(n) // block_rows]
    return per_row.reshape((n,) + (1,) * (ndim - 1))


# ---------------------------------------------------------------------------
# tier 1: pure-jnp quantize/dequantize (any backend, inside ring steps)
# ---------------------------------------------------------------------------


def quantize(x, wire_dtype: str = "int8", *, block_rows: int = BLOCK_ROWS):
    """Per-block symmetric quantization of ``x`` ``[n, ...]``.

    Returns ``(q, scales)``: ``q`` has x's shape in the wire dtype, and
    ``scales`` is ``[nblocks, 1]`` f32 (one scale per BLOCK_ROWS leading
    rows) — the pair IS the wire format a ring step ppermutes.
    """
    qmax = QMAX[wire_dtype]
    n = x.shape[0]
    nb = _nblocks(n, block_rows)
    xf = x.astype(jnp.float32)
    pad = nb * block_rows - n
    xb = jnp.pad(xf, [(0, pad)] + [(0, 0)] * (x.ndim - 1)) if pad else xf
    amax = jnp.max(jnp.abs(xb).reshape(nb, -1), axis=1)
    scales = (jnp.maximum(amax, _SCALE_FLOOR) / qmax).reshape(nb, 1)
    s = _row_scales(scales, n, block_rows, x.ndim)
    if wire_dtype == "int8":
        q = jnp.clip(jnp.round(xf / s), -qmax, qmax).astype(jnp.int8)
    else:
        q = (xf / s).astype(jnp.dtype(wire_dtype))
    return q, scales


def dequantize(q, scales, out_dtype=jnp.float32, *,
               block_rows: int = BLOCK_ROWS):
    """Inverse of :func:`quantize`: ``q.astype(f32) * scale`` per block.
    Reductions must add the f32 result BEFORE any cast to ``out_dtype``."""
    n = q.shape[0]
    s = _row_scales(scales, n, block_rows, q.ndim)
    return (q.astype(jnp.float32) * s).astype(out_dtype)


def wire_roundtrip(x, wire_dtype: str = "int8", *,
                   block_rows: int = BLOCK_ROWS):
    """One quantize/dequantize round trip (what a single wire hop does to
    the payload values) — the reference for error-bound tests."""
    q, scales = quantize(x, wire_dtype, block_rows=block_rows)
    return dequantize(q, scales, x.dtype, block_rows=block_rows)


# ---------------------------------------------------------------------------
# tier 2: per-block Pallas kernels (kernels/pack.py style; TPU, interpret on
# CPU) — grid over scale blocks, one scale computed per grid step
# ---------------------------------------------------------------------------


def _quant_kernel(x_ref, q_ref, s_ref, *, qmax: float, wire_dtype: str):
    xb = x_ref[...].astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xb)), _SCALE_FLOOR) / qmax
    s_ref[pl.program_id(0)] = scale
    if wire_dtype == "int8":
        q_ref[...] = jnp.clip(jnp.round(xb / scale), -qmax, qmax).astype(
            jnp.int8)
    else:
        q_ref[...] = (xb / scale).astype(jnp.dtype(wire_dtype))


@functools.partial(jax.jit,
                   static_argnames=("wire_dtype", "block_rows", "interpret"))
def quant_pack(x, *, wire_dtype: str = "int8",
               block_rows: int = BLOCK_ROWS, interpret: bool = False):
    """Pallas quantize-on-send: ``[n, d]`` -> ``([n, d] wire dtype,
    [nblocks, 1] f32 scales)``.  Non-divisible ``n`` is zero-padded up to
    the block grid (pad rows never raise a block's abs-max) and sliced
    back, mirroring pallas_matmul's pad behaviour."""
    n, d = x.shape
    nb = _nblocks(n, block_rows)
    pad = nb * block_rows - n
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    q, scales = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=QMAX[wire_dtype],
                          wire_dtype=wire_dtype),
        grid=(nb,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda j: (j, 0))],
        out_specs=[pl.BlockSpec((block_rows, d), lambda j: (j, 0)),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct((nb * block_rows, d),
                                        jnp.dtype(wire_dtype)),
                   jax.ShapeDtypeStruct((nb,), jnp.float32)],
        interpret=interpret,
    )(xp)
    return q[:n], scales.reshape(nb, 1)


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = (q_ref[...].astype(jnp.float32)
                  * s_ref[pl.program_id(0)]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "block_rows", "interpret"))
def dequant_unpack(q, scales, *, out_dtype=jnp.float32,
                   block_rows: int = BLOCK_ROWS, interpret: bool = False):
    """Pallas dequantize-on-receive: inverse of :func:`quant_pack`."""
    n, d = q.shape
    nb = _nblocks(n, block_rows)
    pad = nb * block_rows - n
    qp = jnp.pad(q, ((0, pad), (0, 0))) if pad else q
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda j: (j, 0)),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((block_rows, d), lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * block_rows, d),
                                       jnp.dtype(out_dtype)),
        interpret=interpret,
    )(qp, scales.reshape(nb))
    return out[:n]
