"""Mixture-of-Experts with expert parallelism over the TP axis.

GShard-style capacity dispatch, but position-in-expert is computed with
cumsum over flattened (token, slot) choices — no [T, E, C] one-hot tensor is
ever materialized (T·E·C would be terabytes at DeepSeek scale).  Tokens over
capacity are dropped (standard capacity-factor routing).  With
``cfg.moe.global_capacity`` the keep decision uses the token's position in
the GLOBAL per-expert order (one extra tunable ``api.allreduce`` of router
stats over the data axis), making data-sharded drops identical to a
single-device run.

The expert shuffle is TWO all-to-alls over the model axis through
``ops.ep_alltoall`` — i.e. GL8 territory for the tuner, and the single
largest collective payload in MoE training.

Shared experts (DeepSeek) run as a dense TP MLP on every token.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import api
from repro.core._axis import axis_index
from repro.dist import ops
from repro.dist.axes import AXES, axis_size_or_1, has_axis
from repro.models.config import ModelConfig
from repro.models.layers import mlp, mlp_specs
from repro.models.params import ParamSpec


def moe_specs(cfg: ModelConfig) -> dict:
    m = cfg.moe
    d, dt = cfg.d_model, cfg.dtype
    specs = {
        "router": ParamSpec((d, m.n_experts), ("data", None),
                            dtype="float32"),
        # experts sharded over TP on the expert dim, FSDP on d_model
        "w_in": ParamSpec((m.n_experts, d, m.d_ff_expert),
                          ("model", "data", None), dtype=dt),
        "w_gate": ParamSpec((m.n_experts, d, m.d_ff_expert),
                            ("model", "data", None), dtype=dt),
        "w_out": ParamSpec((m.n_experts, m.d_ff_expert, d),
                           ("model", None, "data"), dtype=dt),
    }
    if m.n_shared:
        specs["shared"] = mlp_specs(d, m.n_shared * m.d_ff_expert, dt)
    return specs


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = int(n_tokens * m.top_k * m.capacity_factor / m.n_experts)
    return max(4, -(-c // 4) * 4)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _one_copy_grad(copies: int, w):
    return w


def _one_copy_grad_fwd(copies, w):
    return w, None


def _one_copy_grad_bwd(copies, _, g):
    return (g / copies,)


_one_copy_grad.defvjp(_one_copy_grad_fwd, _one_copy_grad_bwd)


def moe_block(p: dict, cfg: ModelConfig, x) -> tuple[jax.Array, jax.Array]:
    """x: [B, S, D] -> (y, aux_loss).  Experts sharded over the model axis."""
    m = cfg.moe
    tp = axis_size_or_1(AXES.model)
    e_loc = m.n_experts // tp
    b, s, d = x.shape
    t = b * s
    cap = _capacity(t, cfg)
    xt = x.reshape(t, d)

    # --- routing (fp32, replicated over TP) ---------------------------------
    logits = ops.matmul_accumulate(xt.astype(jnp.float32),
                                   p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                      # [T, E]
    gate_vals, expert_ids = lax.top_k(probs, m.top_k)            # [T, k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # load-balance aux (Switch): E * sum_e f_e * P_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((m.n_experts,), jnp.float32).at[
        expert_ids.reshape(-1)].add(1.0) / (t * m.top_k)
    aux = m.n_experts * jnp.sum(me * ce)

    # --- position-in-expert via cumsum over flattened (token, slot) ---------
    flat_e = expert_ids.reshape(-1)                              # [T*k]
    onehot = jax.nn.one_hot(flat_e, m.n_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1                         # [T*k, E]
    pos_in_e = jnp.sum(pos * onehot, axis=-1)                    # [T*k]
    if m.global_capacity and has_axis(AXES.data):
        # global-capacity mode: keep decisions use the token's position in
        # the GLOBAL per-expert order.  The batch is split contiguously
        # over the data axis, so global position = local cumsum + the
        # preceding shards' per-expert counts — one tiny tunable allreduce
        # of one-hot-placed router stats (dp x E int32).  Kept-token sets
        # then match the single-device run exactly.
        dp = axis_size_or_1(AXES.data)
        cap = _capacity(t * dp, cfg)
        counts = jnp.sum(onehot, axis=0, dtype=jnp.int32)        # [E] local
        placed = lax.dynamic_update_slice(
            jnp.zeros((dp, m.n_experts), jnp.int32), counts[None],
            (axis_index(AXES.data), 0))
        # one-hot-placed allreduce (the GL3 allgather-as-allreduce shape):
        # an allgather of the [E] counts would move dp x less, but the
        # ROADMAP item specifies the stats exchange as a tunable allreduce
        # and the payload is tiny (dp*E ints, latency-regime territory —
        # exactly where the tuner's doubling mock-up earns its keep)
        all_counts = api.allreduce(placed, AXES.data)            # [dp, E]
        before = jnp.arange(dp)[:, None] < axis_index(AXES.data)
        offset = jnp.sum(jnp.where(before, all_counts, 0), axis=0)
        pos_keep = pos_in_e + offset[flat_e]                     # global pos
        # local buffer only ever holds this shard's kept tokens
        cap_buf = min(cap, max(4, -(-(t * m.top_k) // 4) * 4))
    else:
        pos_keep = pos_in_e
        cap_buf = cap
    keep = pos_keep < cap
    slot = jnp.where(keep, flat_e * cap_buf + pos_in_e,
                     m.n_experts * cap_buf)

    # --- dispatch: scatter tokens into [E*cap_buf, D] ------------------------
    xk = jnp.repeat(xt, m.top_k, axis=0)                         # [T*k, D]
    buf = jnp.zeros((m.n_experts * cap_buf + 1, d), x.dtype)
    buf = buf.at[slot].add(xk * keep[:, None].astype(x.dtype))
    buf = buf[:-1]                                               # drop bin

    # --- EP all-to-all: expert-major buffer is already shard-tiled ----------
    buf = ops.ep_alltoall(buf)                                   # [tp*Eloc*cap, D]
    buf = buf.reshape(tp, e_loc, cap_buf, d).transpose(1, 0, 2, 3)
    buf = buf.reshape(e_loc, tp * cap_buf, d)

    # --- expert FFN ----------------------------------------------------------
    # every model shard sends the same tokens, so each expert sees each
    # token tp times: its weight grads count one copy
    w_in = _one_copy_grad(tp, ops.fsdp_gather(p["w_in"], 1))     # [Eloc, D, F]
    w_gate = _one_copy_grad(tp, ops.fsdp_gather(p["w_gate"], 1))
    w_out = _one_copy_grad(tp, ops.fsdp_gather(p["w_out"], 2))   # [Eloc, F, D]
    h = jnp.einsum("ecd,edf->ecf", buf, w_in)
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, w_out)

    # --- reverse all-to-all + combine ---------------------------------------
    y = y.reshape(e_loc, tp, cap_buf, d).transpose(1, 0, 2, 3).reshape(
        tp * e_loc * cap_buf, d)
    y = ops.ep_alltoall(y)                                       # [E*cap, D]
    y = jnp.concatenate([y, jnp.zeros((1, d), y.dtype)], axis=0)
    gathered = y[slot]                                           # [T*k, D]
    gathered = gathered * (gate_vals.reshape(-1)[:, None].astype(y.dtype)
                           * keep[:, None].astype(y.dtype))
    out = jnp.sum(gathered.reshape(t, m.top_k, d), axis=1)

    if m.n_shared:
        out = out + mlp(p["shared"], xt)
    return out.reshape(b, s, d), aux
