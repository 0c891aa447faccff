"""SSM blocks: RWKV6 ("Finch", data-dependent decay) and Mamba2 (SSD).

TP contract: SSM heads are sharded over the model axis (RWKV6 heads padded
up to a multiple of tp).  B/C (mamba) and the decay-LoRA down-projection
(rwkv) are replicated.  Mamba's B/C feed the sharded heads, so their
weight grads are partial per shard and carry ``tp_psum_grad`` markers, and
their input is marked ``tp_copy``.  RWKV's decay LoRA feeds ``col_matmul``,
whose backward already sums the input grad over the shards, so it needs
no marker.

Reference semantics here are pure JAX:
* mamba2 — chunked SSD (``_ssd_chunked``): a scalar decay per head, so a
  chunk's [L, L] pairwise decay matrix, clamped before ``exp``, is stable
  and cheap;
* rwkv6  — chunked in XLA (``_wkv_scan``): channel-wise decay cannot be
  factored into one stable matmul, so each chunk forms its pairwise decay
  differences (every exponent ≤ 0), and a ``lax.scan`` carries one state
  per chunk.  The Pallas kernel ``kernels/rwkv6_scan.py`` does the same
  math in VMEM; it has no backward and is on no path.

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


# Tokens per chunk of the WKV recurrence (measured on the chip, PERF.md).
# The pairwise decay tensor of a chunk grows with its square.
WKV_CHUNK = 16
# Products run in full f32: the WKV scan's three chunk products (at the
# default, one bf16 pass, the bonus ``u``'s gradient strays several times
# further from a float32 reference than the per-token scan's did) and the
# SSD's intra-chunk pair; the SSD's chunk-state products run at the
# default (PERF.md has the readings of each form).
_EXACT = lax.Precision.HIGHEST


def _wkv_scan(r, k, v, logw, u, s0):
    """RWKV6 recurrence, chunked.  Per token:
      y_t = r_t·S_{t-1} + (r_t·(u⊙k_t)) v_t
      S_t = e^{logw_t} ⊙ S_{t-1} + k_tᵀv_t

    r,k,v: [B,S,H,hd]; logw: [B,S,H,hd] log-decay (≤ 0); u: [H,hd] bonus;
    s0: [B,H,hd,hd].  Returns y [B,S,H,hd], s_final.

    The row is cut into chunks of L = min(WKV_CHUNK, S) tokens, the tail
    padded with k = v = r = 0 and log-decay 0, which leave y and the state
    as they are.  Inside a chunk, with cum the log-decay summed from the
    chunk's start and cum_prev = cum - logw:
      intra:  y_t += Σ_{s<t} [Σ_c r_tc k_sc e^{cum_prev_tc - cum_sc}] v_s
              + bonus diagonal
      state:  y_t += (r_t ⊙ e^{cum_prev_t}) S_in, S_in the state the
              chunk starts from, carried by a ``lax.scan`` over chunks:
              S ← e^{cum_L} ⊙ S + (k ⊙ e^{cum_L - cum})ᵀ v
    Every exponent is ≤ 0: the pairwise one is clamped before ``exp`` and
    masked after it, so no gradient meets an overflow.
    """
    b, S, H, hd = r.shape
    L = min(WKV_CHUNK, S)
    nc = -(-S // L)

    def chunked(x):                                # -> [nc,B,H,L,hd]
        x = jnp.pad(x, ((0, 0), (0, nc * L - S), (0, 0), (0, 0)))
        return x.reshape(b, nc, L, H, hd).transpose(1, 0, 3, 2, 4)

    r, k, v, logw = map(chunked, (r, k, v, logw))
    cum = jnp.cumsum(logw, axis=3)                 # ≤ 0, falls along L
    cum_prev = cum - logw

    with jax.named_scope("intra"):
        diff = cum_prev[..., :, None, :] - cum[..., None, :, :]  # [..,t,s,c]
        past = jnp.tril(jnp.ones((L, L), bool), -1)[..., None]  # s < t
        dec = jnp.where(past, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
        a = jnp.sum(r[..., :, None, :] * k[..., None, :, :] * dec, axis=-1)
        y = jnp.einsum("...ts,...sv->...tv", a, v, precision=_EXACT)
        y = y + jnp.sum(r * u[:, None] * k, -1, keepdims=True) * v

    with jax.named_scope("state"):
        cum_l = cum[..., -1:, :]                   # [nc,B,H,1,hd]
        kv = jnp.einsum("...tk,...tv->...kv", k * jnp.exp(cum_l - cum), v,
                        precision=_EXACT)

        def step(s, x):
            d, kv_c, rq = x
            return (d[..., None] * s + kv_c,
                    jnp.einsum("bhtk,bhkv->bhtv", rq, s, precision=_EXACT))

        s_fin, y_in = lax.scan(step, s0, (
            jnp.exp(cum_l[..., 0, :]), kv, r * jnp.exp(cum_prev)),
            unroll=8)              # 8 chunks an iteration: faster on the chip
        y = y + y_in

    y = y.transpose(1, 0, 3, 2, 4).reshape(b, nc * L, H, hd)[:, :S]
    return y, s_fin


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
    logw = -jnp.exp(dec_raw)        # log of the decay w in (0,1), per channel

    rh = r.reshape(b, s, h_loc, hd).astype(f32)
    kh = k.reshape(b, s, h_loc, hd).astype(f32)
    vh = v.reshape(b, s, h_loc, hd).astype(f32)
    logwh = logw.reshape(b, s, h_loc, hd)
    u = p["u"].astype(f32).reshape(h_loc, hd)
    s0 = (state["s"].astype(f32) if state
          else jnp.zeros((b, h_loc, hd, hd), f32))
    with jax.named_scope("wkv"):
        y, s_fin = _wkv_scan(rh, kh, vh, logwh, u, s0)
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
        "dt_bias": ParamSpec((nh,), ("model",), init="dt_bias",
                             dtype="float32"),
        "a_log": ParamSpec((nh,), ("model",), init="a_log", dtype="float32"),
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
    B, C: [b,S,N]; s0: [b,H,N,P].  Returns y [b,S,H,P], s_fin.

    Per token and head: h_t = e^{-dt_t a} h_{t-1} + dt_t B_tᵀ x_t,
    y_t = C_t h_t.  Inside a chunk of L tokens, with cum the log-decay
    summed from the chunk's start:
      intra:  y_t += Σ_{s≤t} (C_t·B_s) e^{cum_t - cum_s} dt_s x_s
      state:  y_t += C_t (e^{cum_t} ⊙ S_in), S_in the state the chunk
              starts from, carried by a ``lax.scan`` over chunks.
    Every exponent is ≤ 0: the pairwise one is clamped before ``exp`` and
    masked after it, so no gradient meets an overflow (over s > t it
    would reach e^{L·dt·a}, inf at Mamba2's published decay rates).
    """
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
    cum = jnp.cumsum(lac, axis=2)                        # ≤ 0, falls along L
    Bc = B.reshape(b, nc, L, N)
    Cc = C.reshape(b, nc, L, N)
    Xc = xbar.reshape(b, nc, L, H, P)

    with jax.named_scope("intra"):
        # M[t,s] = (C_t·B_s) e^{cum_t - cum_s}, s ≤ t
        diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # [b,nc,L,L,H]
        tri = jnp.tril(jnp.ones((L, L), bool))[None, None, :, :, None]
        dmat = jnp.where(tri, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
        cb = jnp.einsum("bctn,bcsn->bcts", Cc.astype(f32), Bc.astype(f32),
                        precision=_EXACT)
        m = cb[..., None] * dmat                          # [b,nc,L,L,H]
        y_intra = jnp.einsum("bctsh,bcshp->bcthp", m, Xc.astype(f32),
                             precision=_EXACT)

    with jax.named_scope("state"):
        # S_in of the next chunk += Σ_s e^{cum_L - cum_s} B_sᵀ xbar_s;
        # the carried state decays by e^{cum_L} a chunk
        wlast = cum[:, :, -1:, :]                         # [b,nc,1,H]
        kdec = jnp.exp(wlast - cum)                       # [b,nc,L,H]
        s_in = jnp.einsum("bcln,bclh,bclhp->bchnp",
                          Bc.astype(f32), kdec, Xc.astype(f32))
        chunk_decay = jnp.exp(wlast[:, :, 0, :])          # [b,nc,H]

        def step(s, inp):
            dec, sin, cdec, cq = inp
            # y_inter[t] = C_t · (e^{cum_t} ⊙ s)
            y = jnp.einsum("bln,blh,bhnp->blhp", cq, dec, s)
            s = cdec[..., None, None] * s + sin
            return s, y

        xs = (jnp.exp(cum).transpose(1, 0, 2, 3),         # [nc,b,L,H]
              s_in.transpose(1, 0, 2, 3, 4),              # [nc,b,H,N,P]
              chunk_decay.transpose(1, 0, 2),             # [nc,b,H]
              Cc.astype(f32).transpose(1, 0, 2, 3))       # [nc,b,L,N]
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
    with jax.named_scope("ssd"):
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
