"""Plain float32 Zamba2 language model: weights, loss, model FLOPs.

Written out from the equations of the configuration as the repository
runs it, with nothing of the program imported (``rms`` as in
``rwkv6.py``; ``x'`` is the causal depthwise convolution
``y_t = sum_j w_j x_{t-j}``, zero before the first token):

Mamba2 layer  ``xn = rms(x, ln)``; ``z, u, bc, dt = xn W_z, xn W_x, xn W_bc,
              xn W_dt``; ``u = silu(u')``, ``B, C = split(silu(bc'))``;
              ``dt = softplus(dt + dt_bias)``, ``a = exp(a_log)``; per head
              ``h_t = exp(-dt_t a) h_{t-1} + dt_t B_t^T u_t``,
              ``y_t = C_t h_t + d_skip u_t`` (a plain recurrence over time,
              not the program's chunked form); ``y = rms_head(y, gate_norm)``;
              ``x += (y * silu(z)) W_out``
shared block  after every ``hybrid_period`` Mamba2 layers, with one set of
              weights: ``h = [x, x_0] W_in`` (``x_0`` the embeddings);
              ``h += attn(rms(h, ln1))`` (causal, RoPE, full heads);
              ``h += mlp(rms(h, ln2))`` (``silu(h W_gate) * h W_in``,
              then ``W_out``); ``x += h``
head          ``logits = rms(x, final_norm) W_head``; mean next-token
              cross-entropy.

Departures from the published Zamba2 (arXiv:2411.15242) that the program
makes, and this reference follows: one shared block (Zamba2 alternates
two, with LoRA adapters per use), RMS norms with ``1 + scale``, and no
grouping of B and C over head groups.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from bench.refs.rwkv6 import rms

F32 = jnp.float32
QUERY_BLOCK = 512


def init_leaf(name: str, shape, dtype, key):
    """The benchmark's weights: matrices N(0, 1/fan_in); decay rates
    ``exp(a_log)`` in [1, 16] and steps ``softplus(dt_bias)`` log-uniform
    in [1e-3, 1e-1], as Mamba2 initialises them; norm scales small."""
    if name == "a_log":
        x = jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    elif name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3),
                                        math.log(1e-1)))
        x = jnp.log(jnp.expm1(dt))
    elif name == "d_skip":
        x = 1.0 + 0.1 * jax.random.normal(key, shape, F32)
    elif name.startswith("ln") or name.endswith("norm"):
        x = 0.1 * jax.random.normal(key, shape, F32)
    else:
        x = jax.random.normal(key, shape, F32) * shape[-2] ** -0.5
    return x.astype(dtype)


def _conv(x, w):
    k = w.shape[0]
    xp = jnp.concatenate([jnp.zeros_like(x[:, :k - 1]), x], axis=1)
    s = x.shape[1]
    return sum(w[j] * xp[:, k - 1 - j:k - 1 - j + s] for j in range(k))


def _ssm(u, dt, a, B, C, chunk=64):
    """Recurrence over time; ``u [b,S,H,P]``, ``dt [b,S,H]``, ``B, C
    [b,S,N]``.  Checkpointed every ``chunk`` steps so that the backward
    keeps one state a chunk."""
    b, s, h, p = u.shape
    n = B.shape[-1]

    def step(hs, t):
        ut, dtt, bt, ct = t
        hs = (jnp.exp(-dtt * a)[..., None, None] * hs
              + dtt[..., None, None] * bt[:, None, :, None] * ut[:, :, None, :])
        return hs, jnp.einsum("bn,bhnp->bhp", ct, hs)

    @jax.checkpoint
    def block(hs, t):
        return lax.scan(step, hs, t)

    xs = tuple(a_.swapaxes(0, 1).reshape(s // chunk, chunk, *a_.shape[:1],
                                          *a_.shape[2:])
               for a_ in (u, dt, B, C))
    _, y = lax.scan(block, jnp.zeros((b, h, n, p), F32), xs)
    return y.reshape(s, b, h, p).swapaxes(0, 1)


def mamba(p, x, cfg, cast):
    c, eps = cfg["ssm"], cfg["norm_eps"]
    mm = lambda a, w: jnp.dot(cast(a), cast(w))  # noqa: E731
    b, s, _ = x.shape
    hd, n = c["head_dim"], c["state_dim"]
    xn = rms(x, p["ln"], eps)
    z, u = mm(xn, p["w_in_z"]), mm(xn, p["w_in_x"])
    bc, dt = mm(xn, p["w_bc"]), mm(xn, p["w_dt"])
    u = jax.nn.silu(_conv(u, p["conv_x"]))
    bc = jax.nn.silu(_conv(bc, p["conv_bc"]))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    heads = u.shape[-1] // hd
    uh = u.reshape(b, s, heads, hd)
    y = _ssm(uh, dt, jnp.exp(p["a_log"]), bc[..., :n], bc[..., n:])
    y = y + uh * p["d_skip"][:, None]
    y = rms(y, p["gate_norm"].reshape(heads, hd), eps).reshape(b, s, -1)
    return x + mm(y * jax.nn.silu(z), p["w_out"])


def _rope(x, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, cfg, cast):
    """Causal multi-head attention, queries in blocks of ``QUERY_BLOCK``."""
    mm = lambda a, w: jnp.dot(cast(a), cast(w))  # noqa: E731
    b, s, _ = x.shape
    hd = cfg["head_dim"]
    q, k, v = (mm(x, p[w]).reshape(b, s, -1, hd) for w in ("w_q", "w_k", "w_v"))
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    out = []
    for q0 in range(0, s, QUERY_BLOCK):
        qb = q[:, q0:q0 + QUERY_BLOCK]
        sc = jnp.einsum("bqhd,bkhd->bhqk", cast(qb), cast(k)) / math.sqrt(hd)
        qi = q0 + jnp.arange(qb.shape[1])[:, None]
        sc = jnp.where(jnp.arange(s)[None, :] <= qi, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        out.append(jnp.einsum("bhqk,bkhd->bqhd", cast(pr), cast(v)))
    o = jnp.concatenate(out, axis=1).reshape(b, s, -1)
    return mm(o, p["w_o"])


def shared(p, x, x0, cfg, cast):
    mm = lambda a, w: jnp.dot(cast(a), cast(w))  # noqa: E731
    eps = cfg["norm_eps"]
    h = mm(jnp.concatenate([x, x0], axis=-1), p["proj_in"])
    h = h + attention(p["attn"], rms(h, p["ln1"], eps), cfg, cast)
    hn = rms(h, p["ln2"], eps)
    f = p["ffn"]
    h = h + mm(jax.nn.silu(mm(hn, f["w_gate"])) * mm(hn, f["w_in"]),
               f["w_out"])
    return x + h


def loss(params, batch, cfg, cast=lambda a: a):
    x0 = params["embed"]["table"][batch["tokens"]]
    mam = jax.checkpoint(lambda p, h: mamba(p, h, cfg, cast))
    sha = jax.checkpoint(lambda p, h, h0: shared(p, h, h0, cfg, cast))
    x = x0
    for g in sorted(params["stack"]):
        group = params["stack"][g]
        keys = sorted(group, key=lambda k: int(k[1:].split("_")[0]))
        reps = group[keys[0]]["w_out"].shape[0] \
            if group[keys[0]]["w_out"].ndim == 3 else 1
        for i in range(reps):
            for k in keys:
                p = (jax.tree.map(lambda a: a[i], group[k])
                     if group[k]["w_out"].ndim == 3 else group[k])
                x = mam(p, x)
            if len(keys) == cfg["hybrid_period"]:
                x = sha(params["shared_attn"], x, x0)
    x = rms(x, params["final_norm"], cfg["norm_eps"])
    logits = jnp.dot(cast(x[:, :-1]), cast(params["head"]["w"]))
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, batch["labels"][:, 1:, None], -1)[..., 0]
    return jnp.mean(lse - tgt)


def flops_per_token(cfg, seq: int) -> float:
    """Forward FLOPs of one token at sequence length ``seq``: 2 per
    multiply-add of every matrix product (head included), 4 N P a head
    for the recurrence (state update and read-out), and for each use of
    the shared block 4 S d_attn / 2 for causal scores and values."""
    d, c = cfg["d_model"], cfg["ssm"]
    di, n = c["expand"] * d, c["state_dim"]
    heads = di // c["head_dim"]
    mamba = 2 * (d * (2 * di + 2 * n + heads) + di * d) + 4 * heads * n * c["head_dim"]
    da = cfg["n_heads"] * cfg["head_dim"]
    attn = 2 * (2 * d * d + 4 * d * da + 3 * d * cfg["d_ff"]) + 2 * seq * da
    uses = cfg["n_layers"] // cfg["hybrid_period"]
    return cfg["n_layers"] * mamba + uses * attn + 2 * d * cfg["vocab_size"]
