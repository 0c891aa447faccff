"""The chunked SSD scan (``models/ssm.py:_ssd_chunked``) against a plain
per-step recurrence: outputs, final state and the gradients with respect
to every input, with decay rates and steps drawn from Mamba2's published
ranges and from their extremes, at several chunk lengths; decode at S = 1
with a state, and a prefill with an incoming state followed by decode
steps against the whole row."""
import math

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro.models.ssm import _ssd_chunked

B, H, P, N = 2, 3, 8, 4
#: float32 throughout: sums over a few hundred terms, relative to the
#: largest element of each result
TOL = 1e-4
#: (a, dt): Mamba2's published ranges, a in U[1, 16] and dt log-uniform
#: in [1e-3, 1e-1]; the strongest decay of that range on every token
#: (dt·a = 1.6 a step, e^-102 over a chunk of 64); the weakest
DECAYS = ("published", "strongest", "weakest")
#: (row length, chunk): chunks of 64 with a row of one chunk, of part of
#: one, and of one and a half (the chunk halves to 32); chunks of 16
CASES = ([(s, 64, "published") for s in (1, 24, 64, 96)]
         + [(64, 16, "published")]
         + [(s, 64, "strongest") for s in (64, 96)]
         + [(64, 16, "strongest"), (64, 64, "weakest")])


def _inputs(seq, decay, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    xh = jax.random.normal(ks[0], (B, seq, H, P))
    bm = jax.random.normal(ks[1], (B, seq, N))
    cm = jax.random.normal(ks[2], (B, seq, N))
    s0 = jax.random.normal(ks[3], (B, H, N, P))
    if decay == "published":
        a = jax.random.uniform(ks[4], (H,), minval=1.0, maxval=16.0)
        dt = jnp.exp(jax.random.uniform(ks[5], (B, seq, H),
                                        minval=math.log(1e-3),
                                        maxval=math.log(1e-1)))
    else:
        a_v, dt_v = (16.0, 1e-1) if decay == "strongest" else (1.0, 1e-3)
        a = jnp.full((H,), a_v)
        dt = jnp.full((B, seq, H), dt_v)
    return xh, dt, a, bm, cm, s0


def _ref(xh, dt, a, bm, cm, s0):
    """h_t = e^{-dt_t a} h_{t-1} + dt_t B_tᵀ x_t,  y_t = C_t h_t."""
    def step(h, t):
        x, d, b_, c_ = t
        h = (jnp.exp(-d * a)[..., None, None] * h
             + d[..., None, None] * b_[:, None, :, None] * x[:, :, None, :])
        return h, jnp.einsum("bn,bhnp->bhp", c_, h,
                             precision=lax.Precision.HIGHEST)

    xs = tuple(v.swapaxes(0, 1) for v in (xh, dt, bm, cm))
    h, y = lax.scan(step, s0, xs)
    return y.swapaxes(0, 1), h


def _close(got, want):
    assert bool(jnp.all(jnp.isfinite(got)))
    err = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want)))
    assert err <= TOL * max(scale, 1e-30), (err, scale)


@pytest.mark.parametrize("seq,chunk,decay", CASES)
def test_ssd_chunked_matches_recurrence(seq, chunk, decay):
    args = _inputs(seq, decay)
    y, sf = jax.jit(_ssd_chunked, static_argnums=6)(*args, chunk)
    yr, sr = jax.jit(_ref)(*args)
    assert y.shape == yr.shape and sf.shape == sr.shape
    _close(y, yr)
    _close(sf, sr)


@pytest.mark.parametrize("seq,chunk,decay", CASES)
def test_ssd_chunked_grads_match_recurrence(seq, chunk, decay):
    args = _inputs(seq, decay, seed=1)

    def loss(fn):
        def f(*a):
            y, sf = fn(*a)
            return jnp.sum(jnp.sin(y)) + jnp.sum(jnp.cos(sf))
        return f

    argnums = tuple(range(6))             # xh, dt, a, B, C, s0
    got = jax.jit(jax.grad(loss(lambda *a: _ssd_chunked(*a, chunk)),
                           argnums))(*args)
    want = jax.jit(jax.grad(loss(_ref), argnums))(*args)
    for g, w in zip(got, want):
        _close(g, w)


@pytest.mark.parametrize("decay", ["published", "strongest"])
def test_ssd_prefill_then_decode_matches_whole_row(decay):
    seq, prompt, chunk = 80, 48, 16
    xh, dt, a, bm, cm, s0 = _inputs(seq, decay, seed=2)
    scan = jax.jit(_ssd_chunked, static_argnums=6)
    y_row, s_row = scan(xh, dt, a, bm, cm, s0, chunk)
    y, s = scan(xh[:, :prompt], dt[:, :prompt], a, bm[:, :prompt],
                cm[:, :prompt], s0, chunk)
    ys = [y]
    for t in range(prompt, seq):
        y, s = scan(xh[:, t:t + 1], dt[:, t:t + 1], a, bm[:, t:t + 1],
                    cm[:, t:t + 1], s, chunk)
        ys.append(y)
    _close(s, s_row)
    _close(jnp.concatenate(ys, axis=1), y_row)
