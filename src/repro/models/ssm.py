"""SSM blocks: RWKV6 ("Finch", data-dependent decay) and Mamba2 (SSD).

TP contract: SSM heads are sharded over the model axis (RWKV6 heads padded
up to a multiple of tp).  B/C (mamba) and the decay-LoRA down-projection
(rwkv) are replicated.  Mamba's B/C feed the sharded heads, so their
weight grads are partial per shard and carry ``tp_psum_grad`` markers, and
their input is marked ``tp_copy``.  RWKV's decay LoRA feeds ``col_matmul``,
whose backward already sums the input grad over the shards, so it needs
no marker.

Reference semantics here are pure JAX:
* mamba2 — chunked SSD (scalar per-head decay ⇒ the [L, L] pairwise decay
  matrix is stable and cheap);
* rwkv6  — ``lax.scan`` over time (channel-wise decay cannot be factored
  into one stable matmul; the chunked/blocked version is exactly what the
  Pallas kernel implements in VMEM).

Decode carries O(1) state: (conv tail / last token, S).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from repro.dist import ops
from repro.dist.axes import AXES, axis_size_or_1
from repro.models.config import ModelConfig
from repro.models.layers import rms_norm
from repro.models.params import ParamSpec


# ===========================================================================
# RWKV6
# ===========================================================================


def rwkv_heads_padded(cfg: ModelConfig, tp: int) -> int:
    h = cfg.d_model // cfg.ssm.head_dim
    return -(-h // tp) * tp


def rwkv_specs(cfg: ModelConfig, tp: int) -> dict:
    d, dt = cfg.d_model, cfg.dtype
    hd = cfg.ssm.head_dim
    da = rwkv_heads_padded(cfg, tp) * hd          # attention width (padded)
    r = cfg.ssm.decay_lora_rank
    return {
        "ln1": ParamSpec((d,), (None,), init="zeros", dtype="float32"),
        "ln2": ParamSpec((d,), (None,), init="zeros", dtype="float32"),
        # time-mix
        "mu_r": ParamSpec((d,), (None,), init="zeros", dtype=dt),
        "mu_k": ParamSpec((d,), (None,), init="zeros", dtype=dt),
        "mu_v": ParamSpec((d,), (None,), init="zeros", dtype=dt),
        "mu_w": ParamSpec((d,), (None,), init="zeros", dtype=dt),
        "mu_g": ParamSpec((d,), (None,), init="zeros", dtype=dt),
        "w_r": ParamSpec((d, da), ("data", "model"), dtype=dt),
        "w_k": ParamSpec((d, da), ("data", "model"), dtype=dt),
        "w_v": ParamSpec((d, da), ("data", "model"), dtype=dt),
        "w_g": ParamSpec((d, da), ("data", "model"), dtype=dt),
        "w0": ParamSpec((da,), ("model",), init="zeros", dtype="float32"),
        "wA": ParamSpec((d, r), ("data", None), dtype=dt),
        "wB": ParamSpec((r, da), (None, "model"), dtype=dt),
        "u": ParamSpec((da,), ("model",), init="zeros", dtype="float32"),
        "ln_x": ParamSpec((da,), ("model",), init="zeros", dtype="float32"),
        "w_o": ParamSpec((da, d), ("model", "data"), dtype=dt),
        # channel-mix
        "mu_ck": ParamSpec((d,), (None,), init="zeros", dtype=dt),
        "mu_cr": ParamSpec((d,), (None,), init="zeros", dtype=dt),
        "w_ck": ParamSpec((d, cfg.d_ff), ("data", "model"), dtype=dt),
        "w_cv": ParamSpec((cfg.d_ff, d), ("model", "data"), dtype=dt),
        "w_cr": ParamSpec((d, d), ("data", "model"), dtype=dt),
    }


def _token_shift(x, last):
    """x: [B,S,D]; last: [B,1,D] previous token (zeros at t=0 of sequence)."""
    prev = jnp.concatenate([last, x[:, :-1]], axis=1)
    return prev


def _lerp(x, prev, mu):
    return x + (prev - x) * mu


def _wkv_scan(r, k, v, w, u, s0):
    """RWKV6 recurrence over time.

    r,k,v: [B,S,H,hd]; w: [B,S,H,hd] decay in (0,1); u: [H,hd] bonus.
    s0: [B,H,hd,hd].  Returns y [B,S,H,hd], s_final.
    """
    def step(s, rkvw):
        rt, kt, vt, wt = rkvw                      # [B,H,hd]
        kv = jnp.einsum("bhk,bhv->bhkv", kt, vt)
        y = (jnp.einsum("bhk,bhkv->bhv", rt, s)
             + jnp.einsum("bhk,bhkv->bhv", rt * u[None], kv))
        s = wt[..., None] * s + kv
        return s, y

    xs = (r.transpose(1, 0, 2, 3), k.transpose(1, 0, 2, 3),
          v.transpose(1, 0, 2, 3), w.transpose(1, 0, 2, 3))
    s_fin, ys = lax.scan(step, s0, xs)
    return ys.transpose(1, 0, 2, 3), s_fin


def rwkv_block(p: dict, cfg: ModelConfig, x, *, state=None):
    """Time-mix + channel-mix.  state (decode): {"last_tm","last_cm","s"}."""
    tp = axis_size_or_1(AXES.model)
    hd = cfg.ssm.head_dim
    h_loc = rwkv_heads_padded(cfg, tp) // tp
    b, s, d = x.shape
    f32 = jnp.float32

    # ---- time mix ----------------------------------------------------------
    xn = rms_norm(x, p["ln1"], cfg.norm_eps)
    last_tm = state["last_tm"] if state else jnp.zeros((b, 1, d), x.dtype)
    prev = _token_shift(xn, last_tm)
    xr = _lerp(xn, prev, p["mu_r"])
    xk = _lerp(xn, prev, p["mu_k"])
    xv = _lerp(xn, prev, p["mu_v"])
    xw = _lerp(xn, prev, p["mu_w"])
    xg = _lerp(xn, prev, p["mu_g"])

    r = ops.col_matmul(xr, p["w_r"], fsdp_dim=0)
    k = ops.col_matmul(xk, p["w_k"], fsdp_dim=0)
    v = ops.col_matmul(xv, p["w_v"], fsdp_dim=0)
    g = ops.col_matmul(xg, p["w_g"], fsdp_dim=0)
    # data-dependent decay (the Finch headline feature)
    low = jnp.tanh(ops.matmul_accumulate(xw, p["wA"]))
    dec_raw = p["w0"].astype(f32) + ops.col_matmul(
        low, p["wB"]).astype(f32)
    w = jnp.exp(-jnp.exp(dec_raw))                   # (0,1), per channel

    rh = r.reshape(b, s, h_loc, hd).astype(f32)
    kh = k.reshape(b, s, h_loc, hd).astype(f32)
    vh = v.reshape(b, s, h_loc, hd).astype(f32)
    wh = w.reshape(b, s, h_loc, hd)
    u = p["u"].astype(f32).reshape(h_loc, hd)
    s0 = (state["s"].astype(f32) if state
          else jnp.zeros((b, h_loc, hd, hd), f32))
    y, s_fin = _wkv_scan(rh, kh, vh, wh, u, s0)
    # per-head group norm (RWKV GroupNorm(n_heads)) — invariant under TP
    yh = y.astype(x.dtype)
    scale = p["ln_x"].reshape(h_loc, hd)
    yh = rms_norm(yh, scale, cfg.norm_eps)
    y = yh.reshape(b, s, h_loc * hd)
    y = y * jax.nn.silu(g)
    att = ops.row_matmul(y, p["w_o"], fsdp_dim=1)

    x_in_last = xn[:, -1:]         # time-mix shifts against the NORMED input
    x = x + att

    # ---- channel mix --------------------------------------------------------
    xn2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    last_cm = state["last_cm"] if state else jnp.zeros((b, 1, d), x.dtype)
    prevc = _token_shift(xn2, last_cm)
    xck = _lerp(xn2, prevc, p["mu_ck"])
    xcr = _lerp(xn2, prevc, p["mu_cr"])
    kk = ops.col_matmul(xck, p["w_ck"], fsdp_dim=0)
    kk = jnp.square(jax.nn.relu(kk))
    cv = ops.row_matmul(kk, p["w_cv"], fsdp_dim=1)
    r_loc = ops.col_matmul(xcr, p["w_cr"], fsdp_dim=0)
    r_full = ops.tp_allgather_split(r_loc, r_loc.ndim - 1)
    y = jax.nn.sigmoid(r_full) * cv
    out = x + y

    new_state = None
    if state is not None:
        # time-mix shifts against the block input; channel-mix against the
        # post-attention residual stream (its own input), per RWKV layout
        new_state = {"last_tm": x_in_last, "last_cm": xn2[:, -1:],
                     "s": s_fin}
    return out, new_state


# ===========================================================================
# Mamba2 (SSD, chunked)
# ===========================================================================


def mamba_specs(cfg: ModelConfig, tp: int) -> dict:
    d, dt = cfg.d_model, cfg.dtype
    c = cfg.ssm
    di = c.expand * d                     # d_inner
    nh = di // c.head_dim                 # heads
    assert nh % tp == 0, f"mamba heads {nh} not divisible by tp {tp}"
    n = c.state_dim
    return {
        "ln": ParamSpec((d,), (None,), init="zeros", dtype="float32"),
        "w_in_z": ParamSpec((d, di), ("data", "model"), dtype=dt),
        "w_in_x": ParamSpec((d, di), ("data", "model"), dtype=dt),
        "w_bc": ParamSpec((d, 2 * n), ("data", None), dtype=dt),
        "w_dt": ParamSpec((d, nh), ("data", "model"), dtype=dt),
        "dt_bias": ParamSpec((nh,), ("model",), init="zeros",
                             dtype="float32"),
        "a_log": ParamSpec((nh,), ("model",), init="zeros", dtype="float32"),
        "d_skip": ParamSpec((nh,), ("model",), init="ones", dtype="float32"),
        "conv_x": ParamSpec((c.conv_kernel, di), (None, "model"),
                            scale=0.5, dtype=dt),
        "conv_bc": ParamSpec((c.conv_kernel, 2 * n), (None, None),
                             scale=0.5, dtype=dt),
        "gate_norm": ParamSpec((di,), ("model",), init="zeros",
                               dtype="float32"),
        "w_out": ParamSpec((di, d), ("model", "data"), dtype=dt),
    }


def _causal_conv(x, w, tail=None):
    """Depthwise causal conv via K shifted adds.  x: [B,S,C], w: [K,C].
    ``tail``: [B,K-1,C] previous context (decode)."""
    k = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[k - 1 - i][None, None]
            for i in range(k))
    new_tail = xp[:, -(k - 1):] if k > 1 else tail
    return y, new_tail


def _ssd_chunked(xh, dt, a, B, C, s0, chunk: int):
    """Chunked SSD.  xh: [b,S,H,P]; dt: [b,S,H] (softplus'ed); a: [H] (>0);
    B, C: [b,S,N]; s0: [b,H,N,P].  Returns y [b,S,H,P], s_fin."""
    b, S, H, P = xh.shape
    N = B.shape[-1]
    L = min(chunk, S)
    while S % L:
        L //= 2
    nc = S // L
    f32 = jnp.float32

    la_step = (-dt * a[None, None]).astype(f32)          # log a_t  [b,S,H]
    xbar = xh * dt[..., None]                            # dt-scaled input

    lac = la_step.reshape(b, nc, L, H)
    cum = jnp.cumsum(lac, axis=2)                        # within-chunk
    Bc = B.reshape(b, nc, L, N)
    Cc = C.reshape(b, nc, L, N)
    Xc = xbar.reshape(b, nc, L, H, P)

    # intra-chunk: M[t,s] = (C_t.B_s)·exp(cum_t - cum_s), s<=t
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # [b,nc,L,L,H]
    tri = jnp.tril(jnp.ones((L, L), bool))
    dmat = jnp.where(tri[None, None, :, :, None], jnp.exp(diff), 0.0)
    cb = jnp.einsum("bctn,bcsn->bcts", Cc.astype(f32), Bc.astype(f32))
    m = cb[..., None] * dmat                              # [b,nc,L,L,H]
    y_intra = jnp.einsum("bctsh,bcshp->bcthp", m, Xc.astype(f32))

    # per-chunk aggregates for the inter-chunk scan
    # state in := sum_s exp(cum_L - cum_s) B_s xbar_s^T ; decay = exp(cum_L)
    wlast = cum[:, :, -1:, :]                             # [b,nc,1,H]
    kdec = jnp.exp(wlast - cum)                           # [b,nc,L,H]
    s_in = jnp.einsum("bcln,bclh,bclhp->bchnp",
                      Bc.astype(f32), kdec, Xc.astype(f32))
    chunk_decay = jnp.exp(wlast[:, :, 0, :])              # [b,nc,H]

    def step(s, inp):
        dec, sin, cdec, cq = inp
        # y_inter[t] = C_t · (exp(cum_t) ⊙ s)   (decay applied to carry)
        y = jnp.einsum("bln,blh,bhnp->blhp", cq, dec, s)
        s = cdec[..., None, None] * s + sin
        return s, y

    xs = (jnp.exp(cum).transpose(1, 0, 2, 3),             # [nc,b,L,H]
          s_in.transpose(1, 0, 2, 3, 4),                  # [nc,b,H,N,P]
          chunk_decay.transpose(1, 0, 2),                 # [nc,b,H]
          Cc.astype(f32).transpose(1, 0, 2, 3))           # [nc,b,L,N]
    s_fin, y_inter = lax.scan(step, s0.astype(f32), xs)
    y_inter = y_inter.transpose(1, 0, 2, 3, 4).reshape(b, S, H, P)
    y = y_intra.reshape(b, S, H, P) + y_inter
    return y, s_fin


def mamba_block(p: dict, cfg: ModelConfig, x, *, state=None):
    """Mamba2 mixer.  state (decode): {"conv_x","conv_bc","s"}."""
    c = cfg.ssm
    tp = axis_size_or_1(AXES.model)
    di_loc = c.expand * cfg.d_model // tp
    h_loc = di_loc // c.head_dim
    n = c.state_dim
    b, s, d = x.shape
    f32 = jnp.float32

    xn = rms_norm(x, p["ln"], cfg.norm_eps)
    z = ops.col_matmul(xn, p["w_in_z"], fsdp_dim=0)
    xin = ops.col_matmul(xn, p["w_in_x"], fsdp_dim=0)
    bc = ops.matmul_accumulate(ops.tp_copy(xn), ops.tp_psum_grad(p["w_bc"]))
    dt_raw = ops.col_matmul(xn, p["w_dt"], fsdp_dim=0)

    conv_x_w = p["conv_x"]
    conv_bc_w = ops.tp_psum_grad(p["conv_bc"])
    xin, tail_x = _causal_conv(xin, conv_x_w,
                               state["conv_x"] if state else None)
    bc, tail_bc = _causal_conv(bc, conv_bc_w,
                               state["conv_bc"] if state else None)
    xin = jax.nn.silu(xin)
    bc = jax.nn.silu(bc)
    B, C = bc[..., :n], bc[..., n:]

    dt = jax.nn.softplus(dt_raw.astype(f32) + p["dt_bias"][None, None])
    a = jnp.exp(p["a_log"].astype(f32))                  # per-head decay rate
    xh = xin.reshape(b, s, h_loc, c.head_dim)

    s0 = (state["s"].astype(f32) if state
          else jnp.zeros((b, h_loc, n, c.head_dim), f32))
    y, s_fin = _ssd_chunked(xh.astype(f32), dt, a, B, C, s0, c.chunk)
    y = y + xh.astype(f32) * p["d_skip"].astype(f32)[None, None, :, None]
    yh = y.astype(x.dtype)                      # [b,s,h_loc,P]
    scale = p["gate_norm"].reshape(h_loc, c.head_dim)
    yh = rms_norm(yh, scale, cfg.norm_eps)      # per-head (TP-invariant)
    y = yh.reshape(b, s, di_loc) * jax.nn.silu(z)
    out = x + ops.row_matmul(y, p["w_out"], fsdp_dim=1)

    new_state = None
    if state is not None:
        new_state = {"conv_x": tail_x, "conv_bc": tail_bc, "s": s_fin}
    return out, new_state
