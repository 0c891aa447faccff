"""Interpret-mode CPU tier for the TPU RDMA ring (satellite of the 2-D PR).

The real ``ring_allgather_matmul_rdma`` kernel drives
``make_async_remote_copy`` itself and can only execute on TPU — but its
BLOCK logic (per-step source rank, double-buffer slot rotation, output-row
placement) and its flow-control protocol (credit waits/grants) are pure
schedules.  These tests exercise both on CPU:

* ``ring_allgather_matmul_blocks`` runs one rank's grid schedule as an
  ``interpret=True`` Pallas kernel sharing the indexing helpers with the
  real kernel, and must agree with the ppermute reference ring and the
  dense oracle for every rank.
* a discrete-event simulation replays ``ring_schedule`` over p emulated
  devices and asserts the protocol is safe (no slot overwritten before its
  reader consumed it) and live (credits balance, every chunk delivered).

The real path stays gated behind ``on_tpu()`` — the dispatcher never
routes CPU traffic here (checked below).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import collective_matmul as cmm
from repro.kernels.collective_matmul_rdma import (
    ring_allgather_matmul_blocks, ring_schedule, ring_step_slots,
    ring_step_src)

PS = (2, 3, 4, 8)


@pytest.fixture()
def rng():
    """Module-local PRNG: keeps the session fixture's draw sequence
    untouched for data-dependent tests elsewhere in the suite."""
    return np.random.default_rng(20170701)


# ---------------------------------------------------------------------------
# shared indexing helpers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p", PS)
def test_ring_step_src_covers_all_ranks(p):
    """Across the p grid steps every rank consumes every origin exactly
    once, and step 0 is its own chunk — the all-gather contract."""
    for my in range(p):
        srcs = [ring_step_src(my, s, p) for s in range(p)]
        assert srcs[0] == my
        assert sorted(srcs) == list(range(p))


def test_ring_step_slots_alternate():
    slots = [ring_step_slots(s) for s in range(6)]
    assert slots[0] == (0, 1)
    for s, (slot, nxt) in enumerate(slots):
        assert slot == s % 2 and nxt == (s + 1) % 2
        assert slot != nxt


def test_helpers_accept_traced_ints():
    """The same helper source must serve the TPU kernel (traced ints) and
    the simulation (Python ints)."""
    out = jax.jit(lambda my, s: ring_step_src(my, s, 4))(
        jnp.int32(1), jnp.int32(3))
    assert int(out) == (1 - 3 + 4) % 4


# ---------------------------------------------------------------------------
# protocol simulation (credits / double-buffer safety)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p", PS)
def test_ring_protocol_simulation(p):
    """Replay ``ring_schedule`` over p emulated devices step-locked (the
    grid is globally synchronous per step on TPU): the send at step s must
    target a slot its receiver has already consumed, credits must balance
    to zero, and the delivered chunk sequence must equal the ppermute
    reference ring's (chunk s on rank r originates from rank r-s)."""
    sched = ring_schedule(p)
    assert len(sched) == p
    # slot state per device: buffers[dev][slot] = origin rank held, or None
    buffers = [[None, None] for _ in range(p)]
    consumed = [[True, True] for _ in range(p)]   # both slots start free
    credits = [0] * p                             # credits FROM the right
    delivered = [[] for _ in range(p)]
    for my in range(p):
        buffers[my][0] = my                       # step-0 seed
        consumed[my][0] = False
    for st in sched:
        s, slot, nxt = st["s"], st["slot"], st["nxt"]
        if st["wait_credit"]:
            for my in range(p):
                assert credits[my] > 0, (p, s, my, "credit deadlock")
                credits[my] -= 1
        if st["send"]:
            for my in range(p):
                right = (my + 1) % p
                # safety: the receiver must have consumed the target slot
                assert consumed[right][nxt], (p, s, my, "overwrite")
            for my in range(p):
                right = (my + 1) % p
                buffers[right][nxt] = buffers[my][slot]
                consumed[right][nxt] = False
        # every rank consumes its resident chunk (matmul + placement)
        for my in range(p):
            origin = buffers[my][slot]
            assert origin == ring_step_src(my, s, p), (p, s, my)
            delivered[my].append(origin)
            consumed[my][slot] = True
        if st["grant_credit"]:
            for my in range(p):
                left = (my - 1) % p
                credits[left] += 1
    assert all(c == 0 for c in credits), "credits did not drain"
    for my in range(p):
        assert sorted(delivered[my]) == list(range(p))


# ---------------------------------------------------------------------------
# interpret-mode grid equivalence vs the ppermute reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("p", (2, 4))
def test_interpret_blocks_match_reference_ring(rng, p):
    n, k, m = 3, 5, 4
    x_all = jnp.asarray(rng.normal(size=(p, n, k)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(k, m)).astype(np.float32))
    ref_out, ref_gath = jax.vmap(
        lambda xs: cmm.ring_allgather_matmul(xs, w, "x", mm="jnp",
                                             return_gathered=True),
        axis_name="x")(x_all)
    want = np.asarray(x_all).reshape(p * n, k) @ np.asarray(w)
    for my in range(p):
        out, gath = ring_allgather_matmul_blocks(x_all, w, my,
                                                 interpret=True)
        np.testing.assert_allclose(np.asarray(out), want, atol=1e-5)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref_out)[my], atol=1e-5)
        np.testing.assert_array_equal(np.asarray(gath),
                                      np.asarray(x_all).reshape(p * n, k))


def test_interpret_blocks_nontrivial_dtype(rng):
    p, n, k, m = 4, 2, 3, 2
    x_all = jnp.asarray(rng.normal(size=(p, n, k)).astype(np.float16))
    w = jnp.asarray(rng.normal(size=(k, m)).astype(np.float16))
    out, _ = ring_allgather_matmul_blocks(x_all, w, 1, interpret=True)
    want = np.asarray(x_all, np.float32).reshape(p * n, k) @ \
        np.asarray(w, np.float32)
    np.testing.assert_allclose(np.asarray(out, np.float32), want, atol=2e-2)


def test_real_rdma_path_stays_tpu_gated():
    """The dispatcher's fused_ring impl is the ppermute ring on every
    backend: it never reaches the RDMA kernel, whose
    make_async_remote_copy would fail to lower here."""
    from repro.core import collectives as C
    x = jnp.ones((4, 2, 3), jnp.float32)
    w = jnp.ones((3, 2), jnp.float32)
    out = jax.vmap(lambda a: C.REGISTRY["allgather_matmul"]["fused_ring"].fn(
        a, "x", w=w), axis_name="x")(x)
    np.testing.assert_allclose(np.asarray(out)[0],
                               np.full((8, 2), 3.0), atol=1e-6)
