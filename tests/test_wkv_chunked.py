"""The chunked WKV recurrence (``models/ssm.py:_wkv_scan``) against the
sequential reference ``kernels/ref.rwkv6_ref``: outputs, final state and
every gradient, at row lengths around the chunk length, with a nonzero
initial state, Finch-range and strong decays; and a prefill followed by
decode steps against the whole row."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.ref import rwkv6_ref
from repro.models.ssm import WKV_CHUNK, _wkv_scan

L = WKV_CHUNK
B, H, HD = 2, 3, 8
#: float32 throughout: sums over a few hundred terms, relative to the
#: largest element of each result
TOL = 1e-4
#: the decay LoRA's output ``dec_raw``, log-decay = -exp(dec_raw): Finch's
#: range, and a strong decay (a token's state kept at e^-20)
DECAYS = {"finch": (-6.0, 1.0), "strong": (-1.0, 3.0)}
CASES = ([(s, "finch") for s in (1, L - 1, L, 3 * L + 5)]
         + [(3 * L + 5, "strong")])


def _inputs(seq, decay, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    r, k, v = (jax.random.normal(ks[i], (B, seq, H, HD)) for i in range(3))
    lo, hi = DECAYS[decay]
    logw = -jnp.exp(jax.random.uniform(ks[3], (B, seq, H, HD),
                                       minval=lo, maxval=hi))
    u = jax.random.normal(ks[4], (H, HD))
    s0 = jax.random.normal(ks[5], (B, H, HD, HD))
    return r, k, v, logw, u, s0


def _ref(r, k, v, logw, u, s0):
    """``rwkv6_ref`` on the [B,S,H,hd] layout: heads folded into rows."""
    b, s, h, hd = r.shape

    def rows(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, hd)

    y, sf = rwkv6_ref(rows(r), rows(k), rows(v), rows(jnp.exp(logw)),
                      jnp.tile(u, (b, 1)), s0.reshape(b * h, hd, hd))
    return (y.reshape(b, h, s, hd).transpose(0, 2, 1, 3),
            sf.reshape(b, h, hd, hd))


def _close(got, want):
    err = float(jnp.max(jnp.abs(got - want)))
    scale = float(jnp.max(jnp.abs(want)))
    assert err <= TOL * max(scale, 1e-30), (err, scale)


@pytest.mark.parametrize("seq,decay", CASES)
def test_wkv_chunked_matches_reference(seq, decay):
    args = _inputs(seq, decay)
    y, sf = jax.jit(_wkv_scan)(*args)
    yr, sr = jax.jit(_ref)(*args)
    assert y.shape == yr.shape and sf.shape == sr.shape
    _close(y, yr)
    _close(sf, sr)


@pytest.mark.parametrize("seq,decay", CASES)
def test_wkv_chunked_grads_match_reference(seq, decay):
    args = _inputs(seq, decay, seed=1)

    def loss(fn):
        def f(*a):
            y, sf = fn(*a)
            return jnp.sum(jnp.sin(y)) + jnp.sum(jnp.cos(sf))
        return f

    argnums = tuple(range(6))             # r, k, v, log-decay, u, s0
    got = jax.jit(jax.grad(loss(_wkv_scan), argnums))(*args)
    want = jax.jit(jax.grad(loss(_ref), argnums))(*args)
    for g, w in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(g)))
        _close(g, w)


def test_wkv_prefill_then_decode_matches_whole_row():
    seq, prompt = 2 * L + 3, L + 2
    r, k, v, logw, u, s0 = _inputs(seq, "finch", seed=2)
    scan = jax.jit(_wkv_scan)
    y_row, s_row = scan(r, k, v, logw, u, s0)
    y, s = scan(r[:, :prompt], k[:, :prompt], v[:, :prompt],
                logw[:, :prompt], u, s0)
    ys = [y]
    for t in range(prompt, seq):
        y, s = scan(r[:, t:t + 1], k[:, t:t + 1], v[:, t:t + 1],
                    logw[:, t:t + 1], u, s)
        ys.append(y)
    _close(s, s_row)
    _close(jnp.concatenate(ys, axis=1), y_row)
