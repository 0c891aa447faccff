"""Plain float32 RWKV-6 language model: weights, loss, model FLOPs.

Written out from the equations of the configuration as the repository
runs it, with nothing of the program imported.  Per layer ``l`` (token
shift ``x'_t = x_{t-1}``, zero before the first token; ``rms(x, s)`` is
``x / sqrt(mean(x^2) + eps) * (1 + s)``):

time mix     ``xn = rms(x, ln1)``; ``x_c = xn + (xn' - xn) * mu_c`` for
             ``c`` in r, k, v, w, g; ``r, k, v, g = x_c W_c``;
             ``w = exp(-exp(w0 + tanh(x_w wA) wB))`` (data-dependent decay);
             per head, ``y_t = r_t (S + diag(u) k_t^T v_t)``,
             ``S <- diag(w_t) S + k_t^T v_t``; ``y = rms_head(y, ln_x)``;
             ``x += (y * silu(g)) W_o``
channel mix  ``xn = rms(x, ln2)``; ``k = relu(x_k W_ck)^2``;
             ``x += sigmoid(x_r W_cr) * (k W_cv)``
head         ``logits = rms(x, final_norm) W_head``; loss = mean next-token
             cross-entropy over the batch.

Departures from the published Finch block (arXiv:2404.05892) that the
program makes, and this reference follows: the token-shift mixes ``mu_c``
are static (Finch makes them data-dependent through a second LoRA), the
norms are RMS norms with ``1 + scale`` (Finch: LayerNorm), and the
per-head group norm is an RMS norm.

``cast`` is applied to both operands of every matrix product: the
identity for the reference, a narrower type for the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


# ---------------------------------------------------------------------------
# weights: one rule per leaf name
# ---------------------------------------------------------------------------


def init_leaf(name: str, shape, dtype, key):
    """The benchmark's weights.  Matrices N(0, 1/fan_in); norm scales
    small; token-shift mixes in (0, 1), decay offsets in (-6, -1) (decays
    ``exp(-exp(w0))`` from 0.69 to 0.998) and the bonus in (-0.1, 1), the
    ranges of Finch's own initialisation.  The embedding is N(0, 1): Finch
    normalises it (``ln0``) before the first block, so that the residual
    stream starts at unit scale; the model here has no ``ln0``.  A small
    embedding leaves the first position's residual tiny, and the norms'
    backward then magnifies rounding by the inverse of its scale."""
    if name == "table":
        x = jax.random.normal(key, shape, F32)
    elif name.startswith("mu_"):
        x = jax.random.uniform(key, shape, F32)
    elif name == "w0":
        x = jax.random.uniform(key, shape, F32, -6.0, -1.0)
    elif name == "u":
        x = jax.random.uniform(key, shape, F32, -0.1, 1.0)
    elif name.startswith("ln") or name.endswith("norm"):
        x = 0.1 * jax.random.normal(key, shape, F32)
    else:
        x = jax.random.normal(key, shape, F32) * shape[-2] ** -0.5
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + scale)


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def _wkv(r, k, v, w, u, chunk=32):
    """Per-head recurrence over time; all ``[B, S, H, hd]``, ``u [H, hd]``.
    Checkpointed every ``chunk`` steps, so that the backward keeps one
    state a chunk and not one a step."""
    b, s, h, hd = r.shape
    while s % chunk:
        chunk //= 2

    def step(st, t):
        rt, kt, vt, wt = t
        kv = kt[..., :, None] * vt[..., None, :]            # [B, H, hd, hd]
        y = jnp.einsum("bhk,bhkv->bhv", rt, st + u[None, :, :, None] * kv)
        return wt[..., :, None] * st + kv, y

    @jax.checkpoint
    def block_of_steps(st, t):
        return lax.scan(step, st, t)

    xs = tuple(a.swapaxes(0, 1).reshape(s // chunk, chunk, b, h, hd)
               for a in (r, k, v, w))
    _, y = lax.scan(block_of_steps, jnp.zeros((b, h, hd, hd), F32), xs)
    return y.reshape(s, b, h, hd).swapaxes(0, 1)


def block(p, x, cfg, cast):
    eps, hd = cfg["norm_eps"], cfg["ssm"]["head_dim"]
    mm = lambda a, w: jnp.dot(cast(a), cast(w))  # noqa: E731
    b, s, d = x.shape
    xn = rms(x, p["ln1"], eps)
    prev = _shift(xn)
    mix = {c: xn + (prev - xn) * p[f"mu_{c}"] for c in "rkvwg"}
    r, k, v, g = (mm(mix[c], p[f"w_{c}"]) for c in "rkvg")
    w = jnp.exp(-jnp.exp(p["w0"] + mm(jnp.tanh(mm(mix["w"], p["wA"])),
                                      p["wB"])))
    heads = r.shape[-1] // hd
    split = lambda a: a.reshape(b, s, heads, hd)  # noqa: E731
    y = _wkv(split(r), split(k), split(v), split(w),
             p["u"].reshape(heads, hd))
    y = rms(y, p["ln_x"].reshape(heads, hd), eps).reshape(b, s, heads * hd)
    x = x + mm(y * jax.nn.silu(g), p["w_o"])
    xn = rms(x, p["ln2"], eps)
    prev = _shift(xn)
    xk = xn + (prev - xn) * p["mu_ck"]
    xr = xn + (prev - xn) * p["mu_cr"]
    kk = jnp.square(jax.nn.relu(mm(xk, p["w_ck"])))
    return x + jax.nn.sigmoid(mm(xr, p["w_cr"])) * mm(kk, p["w_cv"])


def layers(stack):
    """Per-layer weights in execution order from the stacked layout
    (``stack[group][b<i>_<kind>]``, a leading layer axis where a group
    repeats)."""
    out = []
    for g in sorted(stack):
        blocks = sorted(stack[g], key=lambda k: int(k[1:].split("_")[0]))
        reps = {stack[g][k]["w_r"].ndim == 3 and stack[g][k]["w_r"].shape[0]
                for k in blocks}
        n = reps.pop() or 1
        for i in range(n):
            for k in blocks:
                out.append(jax.tree.map(lambda a: a[i], stack[g][k])
                           if stack[g][k]["w_r"].ndim == 3 else stack[g][k])
    return out


def loss(params, batch, cfg, cast=lambda a: a):
    """Mean next-token cross-entropy; ``params`` float32."""
    x = params["embed"]["table"][batch["tokens"]]
    body = jax.checkpoint(lambda p, h: block(p, h, cfg, cast))
    for p in layers(params["stack"]):
        x = body(p, x)
    x = rms(x, params["final_norm"], cfg["norm_eps"])
    logits = jnp.dot(cast(x[:, :-1]), cast(params["head"]["w"]))
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, batch["labels"][:, 1:, None], -1)[..., 0]
    return jnp.mean(lse - tgt)


# ---------------------------------------------------------------------------
# model FLOPs
# ---------------------------------------------------------------------------


def flops_per_token(cfg, seq: int) -> float:
    """Forward FLOPs of one token: 2 per multiply-add of every matrix
    product (head included) and 4 hd^2 a head for the recurrence
    (``r S`` and the state update); training is three times this."""
    d, f, r = cfg["d_model"], cfg["d_ff"], cfg["ssm"]["decay_lora_rank"]
    hd = cfg["ssm"]["head_dim"]
    da = cfg["n_heads"] * hd
    mats = 4 * d * da + d * r + r * da + da * d + 2 * d * f + d * d
    per_layer = 2 * mats + 4 * cfg["n_heads"] * hd * hd
    return cfg["n_layers"] * per_layer + 2 * d * cfg["vocab_size"]
