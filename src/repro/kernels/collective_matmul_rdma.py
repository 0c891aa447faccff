"""Tier-3 collective matmul: the single-kernel RDMA ring (TPU only) + its
interpret-mode CPU test tier.

A Pallas kernel that drives ``make_async_remote_copy`` sends itself
(double-buffered comm scratch, per-slot DMA semaphores, neighbour barrier) —
the full latency-hiding schedule with no XLA scheduling dependence.

The REAL kernel (``ring_allgather_matmul_rdma``) is TPU-only —
``make_async_remote_copy`` has no host interpret path across shard_map
devices — and is not on the dispatch path: ``fused_ring``
(core/collectives.py) is the ppermute ring on every backend.  It keeps
``x``, ``w``, the output and the gathered copy whole in VMEM, so it only
compiles at sizes that fit there (tests/test_tpu_compile.py compiles one).
The module is importable anywhere so CPU CI can exercise the ring's BLOCK
logic:

* ``ring_step_src`` / ``ring_step_slots`` — the per-step rank/double-buffer
  indexing, shared verbatim by the RDMA kernel, the interpret tier, and
  the protocol simulation (works on traced ints and Python ints alike).
* ``ring_schedule`` — the flow-control protocol (sends, DMA waits, credit
  waits/grants per step) as plain data, mirroring the kernel's ``pl.when``
  predicates; the CPU test simulates it and checks credits balance and no
  slot is overwritten before its reader consumed it.
* ``ring_allgather_matmul_blocks`` — one rank's grid schedule as a
  single-device Pallas kernel with the DMA arrivals emulated from the full
  chunk array (``interpret=True`` on CPU): same src/slot/output-row
  indexing, no semaphores or remote copies — grid/indexing equivalence vs
  the ppermute reference without TPU hardware.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core._axis import axis_size

__all__ = ["ring_allgather_matmul_rdma", "ring_allgather_matmul_blocks",
           "ring_step_src", "ring_step_slots", "ring_schedule"]

def ring_step_src(my, s, p: int):
    """Originating rank of the chunk resident at ring step ``s`` on rank
    ``my`` — the output-row placement index.  Works traced (``my``/``s``
    jax ints inside a kernel) and as plain Python ints (simulation)."""
    return (my - s + p) % p


def ring_step_slots(s):
    """(consume, send-target) double-buffer slots of ring step ``s``."""
    return s % 2, (s + 1) % 2


def ring_schedule(p: int) -> list[dict]:
    """The RDMA ring's per-step flow-control protocol as data — one dict
    per grid step, mirroring the kernel's ``pl.when`` predicates:

    ``slot``/``nxt``   consume / send-target buffer slots,
    ``send``           issue an RDMA to the right neighbour (s < p-1),
    ``wait_credit``    burn a credit from the right neighbour before the
                       send may re-target its slot (1 <= s < p-1),
    ``wait_dma``       block on the send+recv semaphores (s < p-1),
    ``grant_credit``   tell the left neighbour our slot is consumed
                       (s < p-2 — the final slots are never reused).

    The CPU protocol simulation replays this against a p-device model and
    asserts safety (no overwrite of an unconsumed slot) and liveness
    (credits balance to zero, every chunk delivered)."""
    steps = []
    for s in range(p):
        slot, nxt = ring_step_slots(s)
        steps.append({"s": s, "slot": slot, "nxt": nxt,
                      "send": s < p - 1,
                      "wait_credit": 1 <= s < p - 1,
                      "wait_dma": s < p - 1,
                      "grant_credit": s < p - 2})
    return steps


def _agmm_rdma_kernel(x_ref, w_ref, o_ref, gath_ref, comm_buf, send_sem,
                      recv_sem, credit_sem, acc_scr, *, p: int, axis: str):
    """One grid step per ring hop: RDMA-send the resident chunk to the right
    neighbour, matmul it into its output rows, then wait on the transfers —
    compute and ICI traffic overlap inside a single kernel invocation.

    Buffer-reuse flow control: the send at step s lands in the right
    neighbour's slot ``(s+1) % 2`` — the buffer that neighbour last read at
    its step s-1.  Each device therefore grants one CREDIT to its left
    neighbour when it finishes consuming a slot, and a sender must burn one
    credit (from the right neighbour) before re-targeting that slot; the
    step-0 send needs none (both slots start free)."""
    s = pl.program_id(0)
    my = lax.axis_index(axis)
    right = lax.rem(my + 1, p)
    left = lax.rem(my + p - 1, p)

    @pl.when(s == 0)
    def _seed():
        # neighbour barrier so nobody RDMAs into a peer still setting up
        bar = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(bar, inc=1, device_id=left,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_signal(bar, inc=1, device_id=right,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(bar, 2)
        comm_buf[0] = x_ref[...]

    slot, nxt = ring_step_slots(s)

    @pl.when(jnp.logical_and(s >= 1, s < p - 1))
    def _flow_control():
        # right neighbour finished reading its slot `nxt` at its step s-1
        pltpu.semaphore_wait(credit_sem, 1)

    rdma = pltpu.make_async_remote_copy(
        src_ref=comm_buf.at[slot],
        dst_ref=comm_buf.at[nxt],
        send_sem=send_sem.at[slot],
        recv_sem=recv_sem.at[nxt],
        device_id=right,
        device_id_type=pltpu.DeviceIdType.LOGICAL,
    )

    @pl.when(s < p - 1)
    def _send():
        rdma.start()

    # matmul the chunk we hold while the RDMA is in flight
    src = ring_step_src(my, s, p)
    n = x_ref.shape[0]
    blk = comm_buf[slot]
    acc_scr[...] = jax.lax.dot_general(
        blk, w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[pl.ds(src * n, n), :] = acc_scr[...].astype(o_ref.dtype)
    gath_ref[pl.ds(src * n, n), :] = blk

    @pl.when(s < p - 1)
    def _wait():
        # our send out of `slot` left, and the left neighbour's landed
        # in our `nxt`
        rdma.wait_send()
        rdma.wait_recv()

    @pl.when(s < p - 2)
    def _grant():
        # slot `slot` is fully consumed (matmul done AND our outgoing DMA
        # from it delivered): the left neighbour may target it again with
        # its step-s+1 send.  Credits exactly balance the waits above, so
        # the semaphore drains to zero by kernel exit.
        pltpu.semaphore_signal(credit_sem, inc=1, device_id=left,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)


def ring_allgather_matmul_rdma(x, w, axis: str, *,
                               return_gathered: bool = False,
                               collective_id: int = 7):
    """The tier-3 Pallas kernel: ring allgather-matmul with in-kernel RDMA."""
    p = axis_size(axis)
    n, k = x.shape
    m = w.shape[-1]
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    if p == 1:
        out = jnp.matmul(x, w)
        return (out, x) if return_gathered else out
    out, gath = pl.pallas_call(
        functools.partial(_agmm_rdma_kernel, p=p, axis=axis),
        grid=(p,),
        in_specs=[pl.BlockSpec((n, k), lambda s: (0, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((k, m), lambda s: (0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec((p * n, m), lambda s: (0, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((p * n, k), lambda s: (0, 0),
                                memory_space=pltpu.VMEM)),
        out_shape=(jax.ShapeDtypeStruct((p * n, m), out_dtype),
                   jax.ShapeDtypeStruct((p * n, k), x.dtype)),
        scratch_shapes=[
            pltpu.VMEM((2, n, k), x.dtype),        # double-buffered chunks
            pltpu.SemaphoreType.DMA((2,)),         # send slots
            pltpu.SemaphoreType.DMA((2,)),         # recv slots
            pltpu.SemaphoreType.REGULAR,           # buffer-reuse credits
            pltpu.VMEM((n, m), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=collective_id),
    )(x, w)
    return (out, gath) if return_gathered else out


# ---------------------------------------------------------------------------
# interpret-mode CPU tier: the same grid schedule, DMA arrivals emulated
# ---------------------------------------------------------------------------


def _agmm_block_kernel(xall_ref, w_ref, o_ref, gath_ref, comm_buf, acc_scr,
                       *, p: int, my: int):
    """One rank's view of the RDMA grid: identical slot/src/output-row
    indexing (shared helpers), with the remote copy replaced by reading
    the chunk the DMA WOULD deliver from the full chunk array — so a wrong
    slot rotation or src formula scrambles the output vs the reference."""
    s = pl.program_id(0)
    slot, nxt = ring_step_slots(s)

    @pl.when(s == 0)
    def _seed():
        comm_buf[0] = xall_ref[my]

    @pl.when(s < p - 1)
    def _send():
        # the step-s RDMA targets slot `nxt` with the chunk this rank will
        # consume at step s+1 (originated by ring_step_src(my, s+1, p))
        comm_buf[nxt] = xall_ref[ring_step_src(my, s + 1, p)]

    src = ring_step_src(my, s, p)
    n = xall_ref.shape[1]
    blk = comm_buf[slot]
    acc_scr[...] = jax.lax.dot_general(
        blk, w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[pl.ds(src * n, n), :] = acc_scr[...].astype(o_ref.dtype)
    gath_ref[pl.ds(src * n, n), :] = blk


def ring_allgather_matmul_blocks(x_all, w, my: int, *,
                                 interpret: bool = True):
    """CPU tier of the RDMA ring: rank ``my``'s (p,)-grid block schedule
    over the full chunk array ``x_all [p, n, K]`` — exercised with
    ``interpret=True`` in CI so the block logic is covered without TPU
    hardware.  Returns ``(out [p·n, M], gathered [p·n, K])`` exactly like
    ``ring_allgather_matmul_rdma(..., return_gathered=True)``."""
    p, n, k = x_all.shape
    m = w.shape[-1]
    out_dtype = jnp.result_type(x_all.dtype, w.dtype)
    return pl.pallas_call(
        functools.partial(_agmm_block_kernel, p=p, my=my),
        grid=(p,),
        in_specs=[pl.BlockSpec((p, n, k), lambda s: (0, 0, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((k, m), lambda s: (0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec((p * n, m), lambda s: (0, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((p * n, k), lambda s: (0, 0),
                                memory_space=pltpu.VMEM)),
        out_shape=(jax.ShapeDtypeStruct((p * n, m), out_dtype),
                   jax.ShapeDtypeStruct((p * n, k), x_all.dtype)),
        scratch_shapes=[
            pltpu.VMEM((2, n, k), x_all.dtype),    # double-buffered chunks
            pltpu.VMEM((n, m), jnp.float32),
        ],
        interpret=interpret,
    )(x_all, w)
