"""Attention: GQA (+windows/softcap/prefix), MLA, caches, seq-sharded decode.

Head sharding contract (TP degree ``t``):

* q heads padded up to a multiple of ``t``; each shard owns ``Hq_pad/t``.
* kv heads: if ``kv % t == 0`` the kv projections are model-sharded like q;
  otherwise (kv < t, e.g. gemma MQA) kv projections are REPLICATED, every
  shard computes all kv heads, and ``tp_psum_grad`` sums the partial weight
  grads, as ``tp_copy`` on their input sums its partial grads.  The
  per-shard q-head block picks its kv group by index.

Cache modes:

* batch-sharded  — cache [B_loc, S_max, KVloc, hd]; standard decode.
* seq-sharded    — cache [B, S_max/d, KVloc, hd] over the data axis
  (long-context, batch < data size); decode uses flash-decoding partials
  combined with a tunable all-reduce over "data" (GL6/GL7 territory).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import api
from repro.dist import ops
from repro.dist.axes import AXES, axis_size_or_1, has_axis
from repro.models.config import ModelConfig
from repro.models.layers import rms_norm, rope
from repro.models.params import ParamSpec

NEG = -1e30


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------


def attn_specs(cfg: ModelConfig, tp: int) -> dict:
    d, hd, dt = cfg.d_model, cfg.hd, cfg.dtype
    hq = cfg.heads_padded(tp)
    if cfg.mla is not None:
        m = cfg.mla
        qk_hd = m.nope_head_dim + m.rope_head_dim
        return {
            "w_dq": ParamSpec((d, m.q_lora_rank), ("data", None), dtype=dt),
            "q_norm": ParamSpec((m.q_lora_rank,), (None,), init="zeros",
                                dtype="float32"),
            "w_uq": ParamSpec((m.q_lora_rank, hq * qk_hd), ("data", "model"),
                              dtype=dt),
            "w_dkv": ParamSpec((d, m.kv_lora_rank + m.rope_head_dim),
                               ("data", None), dtype=dt),
            "kv_norm": ParamSpec((m.kv_lora_rank,), (None,), init="zeros",
                                 dtype="float32"),
            "w_ukv": ParamSpec(
                (m.kv_lora_rank, hq * (m.nope_head_dim + m.v_head_dim)),
                ("data", "model"), dtype=dt),
            "w_o": ParamSpec((hq * m.v_head_dim, d), ("model", "data"),
                             dtype=dt),
        }
    kv_sharded = cfg.n_kv_heads % tp == 0
    kv_dim = ("model" if kv_sharded else None)
    n_kv = cfg.n_kv_heads
    specs = {
        "w_q": ParamSpec((d, hq * hd), ("data", "model"), dtype=dt),
        "w_k": ParamSpec((d, n_kv * hd), ("data", kv_dim), dtype=dt),
        "w_v": ParamSpec((d, n_kv * hd), ("data", kv_dim), dtype=dt),
        "w_o": ParamSpec((hq * hd, d), ("model", "data"), dtype=dt),
    }
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((hd,), (None,), init="zeros",
                                    dtype="float32")
        specs["k_norm"] = ParamSpec((hd,), (None,), init="zeros",
                                    dtype="float32")
    return specs


def cross_attn_specs(cfg: ModelConfig, tp: int) -> dict:
    """Decoder cross-attention (whisper): q from decoder, kv from encoder."""
    return attn_specs(dataclasses.replace(cfg, mla=None), tp)


# ---------------------------------------------------------------------------
# mask construction
# ---------------------------------------------------------------------------


def make_mask(q_pos, kv_pos, *, kind: str, window: int = 0,
              n_prefix: int = 0, kv_len_valid=None):
    """Boolean [.., Sq, Skv] attend-mask.

    kind: "causal" | "local" (causal & window) | "prefix" (bidirectional
    for kv_pos < n_prefix, else causal) | "full" (encoder).
    ``kv_len_valid``: scalar — positions >= it are invalid (unfilled cache).
    """
    q = q_pos[..., :, None]
    k = kv_pos[..., None, :]
    if kind == "full":
        m = jnp.ones(jnp.broadcast_shapes(q.shape, k.shape), bool)
    elif kind == "causal":
        m = k <= q
    elif kind == "local":
        m = (k <= q) & (k > q - window)
    elif kind == "prefix":
        m = (k <= q) | (k < n_prefix)
    else:
        raise ValueError(kind)
    if kv_len_valid is not None:
        m = m & (k < kv_len_valid)
    return m


# ---------------------------------------------------------------------------
# core attention math (jnp reference; kernels/ has the Pallas path)
# ---------------------------------------------------------------------------


def _sdpa(q, k, v, mask, *, softcap=None, scale=None):
    """q:[B,Sq,H,dh] k/v:[B,Skv,H,dh(v)] mask:[B?,1?,Sq,Skv] -> [B,Sq,H,dv]"""
    dh = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s * (scale if scale is not None else 1.0 / math.sqrt(dh))
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(mask[:, None, :, :] if mask.ndim == 3 else mask, s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _sdpa_partial(q, k, v, mask, *, softcap=None):
    """Flash-decoding local partial: returns (o_raw, l, m) over local kv."""
    dh = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(dh)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(mask[:, None, :, :] if mask.ndim == 3 else mask, s, NEG)
    m = jnp.max(s, axis=-1)                              # [B,H,Sq]
    w = jnp.exp(s - m[..., None])
    l = jnp.sum(w, axis=-1)                              # [B,H,Sq]
    o = jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)
    return o, l, m


def _chunk_mask(q_pos, kv_pos_chunk, *, kind, window, n_prefix, kv_valid):
    """Mask [B, Sq, C] for one KV chunk, built from positions (never a dense
    [Sq, Skv] tensor — that materialization is what the flash path removes).
    """
    q = q_pos[..., :, None]
    kp = kv_pos_chunk[None, None, :]
    if kind == "full":
        m = jnp.ones(jnp.broadcast_shapes(q.shape, kp.shape), bool)
    elif kind == "causal":
        m = kp <= q
    elif kind == "local":
        m = (kp <= q) & (kp > q - window)
    elif kind == "prefix":
        m = (kp <= q) | (kp < n_prefix)
    else:
        raise ValueError(kind)
    if kv_valid is not None:
        m = m & (kp < kv_valid)
    return m


def _flash_jnp(q, k, v, q_pos, kv_pos, *, kind, window=0, n_prefix=0,
               kv_valid=None, softcap=None, scale=None, chunk=1024):
    """Pure-JAX flash attention: online softmax over KV chunks, grouped GQA
    (no repeated-KV materialization).  Matches the Pallas kernel's schedule;
    used as the optimized attention path in §Perf.

    q: [B, Sq, HK, G, dh]; k, v: [B, Skv, HK, dh]; kv_pos: [Skv].
    Returns [B, Sq, HK, G, dh] in q's dtype.
    """
    b, sq, hk, g, dh = q.shape
    skv = k.shape[1]
    c = min(chunk, skv)
    while skv % c:
        c //= 2
    nc = skv // c
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)

    pc = kv_pos.reshape(nc, c)

    dv = v.shape[-1]
    m0 = jnp.full((b, hk, g, sq), NEG, jnp.float32)
    l0 = jnp.zeros((b, hk, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hk, g, sq, dv), jnp.float32)

    def body2(carry, ci):
        m, l, acc = carry
        # slice chunks in-body: no transposed copy of the whole cache
        kb = lax.dynamic_slice_in_dim(k, ci * c, c, axis=1)
        vb = lax.dynamic_slice_in_dim(v, ci * c, c, axis=1)
        pb = lax.dynamic_slice_in_dim(kv_pos, ci * c, c, axis=0)
        s = jnp.einsum("bqhgd,bchd->bhgqc", q, kb,
                       preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        mask = _chunk_mask(q_pos, pb, kind=kind, window=window,
                           n_prefix=n_prefix, kv_valid=kv_valid)
        s = jnp.where(mask[:, None, None, :, :], s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        # cast p (scores) down, never the cache-sized v chunk up
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqc,bchd->bhgqd", p.astype(v.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = lax.scan(body2, (m0, l0, a0), jnp.arange(nc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    # [B,HK,G,Sq,dh] -> [B,Sq,HK,G,dh]
    return out.transpose(0, 3, 1, 2, 4).astype(q.dtype)


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def _grouped_kv(k_loc, v_loc, cfg: ModelConfig, tp: int, hq_loc: int,
                kv_sharded: bool):
    """(k_sel, v_sel, group_size) for the no-repeat grouped flash path.

    kv_sharded: kv already local -> group = hq_loc / kv_loc.
    replicated kv (kv < tp): every arch here maps a shard's contiguous q
    block to exactly ONE kv head -> slice it (asserted)."""
    if kv_sharded:
        kv_loc = k_loc.shape[2]
        assert hq_loc % kv_loc == 0
        return k_loc, v_loc, hq_loc // kv_loc
    hq = cfg.heads_padded(tp)
    g_all = max(hq // cfg.n_kv_heads, 1)
    assert hq_loc <= g_all, (
        "local q block spans multiple kv heads; grouped flash path "
        "requires hq_loc <= hq/n_kv for replicated kv")
    t_idx = lax.axis_index(AXES.model) if has_axis(AXES.model) else 0
    kv_head = (t_idx * hq_loc) // g_all
    k_sel = lax.dynamic_slice_in_dim(k_loc, kv_head, 1, axis=2)
    v_sel = lax.dynamic_slice_in_dim(v_loc, kv_head, 1, axis=2)
    return k_sel, v_sel, hq_loc


def _local_kv_select(k_all, cfg: ModelConfig, tp: int):
    """From replicated all-kv-heads tensor, build per-local-q-head kv."""
    hq = cfg.heads_padded(tp)
    hq_loc = hq // tp
    n_kv = cfg.n_kv_heads
    rep = hq // n_kv if hq % n_kv == 0 else -1
    t_idx = lax.axis_index(AXES.model) if has_axis(AXES.model) else 0
    full = _repeat_kv(k_all, max(rep, 1))                # [B,S,hq,hd]
    if full.shape[2] < hq:                               # ragged: tile
        reps = -(-hq // full.shape[2])
        full = jnp.tile(full, (1, 1, reps, 1))[:, :, :hq]
    return lax.dynamic_slice_in_dim(full, t_idx * hq_loc, hq_loc, axis=2)


# ---------------------------------------------------------------------------
# the attention block
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class AttnOut:
    y: jax.Array
    cache: dict | None = None


def attention(p: dict, cfg: ModelConfig, x, *, pos, kind: str = "causal",
              n_prefix: int = 0, cache: dict | None = None,
              mode: str = "train", cross_kv=None,
              use_rope: bool = True, seq_sharded: bool = False) -> AttnOut:
    """One attention sub-block (no residual/norm — the stack handles those).

    x: [B, S, D] replicated over TP.  pos: [B, S] absolute positions.
    mode: train | prefill | decode.  cache (prefill out / decode in-out):
      {"k","v": [B, S_max, KVloc, hd], "len": scalar int32}
      (seq-sharded variant: [B, S_max/d, KVloc, hd] + {"seq_sharded": 1}).
    cross_kv: (k, v) precomputed encoder kv for cross-attention.
    """
    if cfg.mla is not None and cross_kv is None:
        return _attention_mla(p, cfg, x, pos=pos, kind=kind, cache=cache,
                              mode=mode)
    tp = axis_size_or_1(AXES.model)
    hq = cfg.heads_padded(tp)
    hq_loc = hq // tp
    hd = cfg.hd
    kv_sharded = cfg.n_kv_heads % tp == 0

    q = ops.col_matmul(x, p["w_q"], fsdp_dim=0)
    q = q.reshape(*x.shape[:-1], hq_loc, hd)

    if cross_kv is not None:
        k_loc, v_loc = cross_kv
        kv_pos = jnp.arange(k_loc.shape[1])[None]
        kv_valid = None
    else:
        if kv_sharded:
            k = ops.col_matmul(x, p["w_k"], fsdp_dim=0)
            v = ops.col_matmul(x, p["w_v"], fsdp_dim=0)
        else:
            xkv = ops.tp_copy(x)
            k = ops.matmul_accumulate(xkv, ops.tp_psum_grad(p["w_k"]))
            v = ops.matmul_accumulate(xkv, ops.tp_psum_grad(p["w_v"]))
        n_kv_loc = (cfg.n_kv_heads // tp) if kv_sharded else cfg.n_kv_heads
        k = k.reshape(*x.shape[:-1], n_kv_loc, hd)
        v = v.reshape(*x.shape[:-1], n_kv_loc, hd)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)
        if use_rope:
            q = rope(q, pos, cfg.rope_theta)
            k = rope(k, pos, cfg.rope_theta)

    new_cache = None
    if cross_kv is not None:
        pass
    elif mode == "train":
        kv_pos = pos
        kv_valid = None
        k_loc, v_loc = k, v
    elif mode == "prefill":
        s_max = cache["k"].shape[1]
        kc = lax.dynamic_update_slice_in_dim(cache["k"], k.astype(
            cache["k"].dtype), 0, axis=1)
        vc = lax.dynamic_update_slice_in_dim(cache["v"], v.astype(
            cache["v"].dtype), 0, axis=1)
        new_cache = {"k": kc, "v": vc,
                     "len": jnp.int32(x.shape[1])}
        kv_pos = pos
        kv_valid = None
        k_loc, v_loc = k, v
    elif mode == "decode":
        if seq_sharded:
            o, new_cache = _decode_seq_sharded(cfg, q, k, v, cache, pos,
                                               kind=kind)
            return AttnOut(y=ops.row_matmul(o, p["w_o"], fsdp_dim=1),
                           cache=new_cache)
        t = cache["len"]
        kc = _cache_write(cache["k"], k, t)
        vc = _cache_write(cache["v"], v, t)
        new_cache = {"k": kc, "v": vc, "len": t + x.shape[1]}
        k_loc, v_loc = kc, vc
        kv_pos = jnp.arange(kc.shape[1])[None]
        kv_valid = t + x.shape[1]
    else:
        raise ValueError(mode)

    mask_kind = kind if cross_kv is None else "full"
    if (cfg.attn_impl == "flash" and mode == "decode" and cross_kv is None
            and mask_kind == "local" and cfg.window < k_loc.shape[1]):
        # decode only attends inside the window: slice the cache instead of
        # streaming all S_max slots (§Perf "windowed decode")
        t0 = cache["len"]
        start = jnp.clip(t0 + x.shape[1] - cfg.window, 0,
                         k_loc.shape[1] - cfg.window)
        k_loc = lax.dynamic_slice_in_dim(k_loc, start, cfg.window, axis=1)
        v_loc = lax.dynamic_slice_in_dim(v_loc, start, cfg.window, axis=1)
        kv_pos = start + jnp.arange(cfg.window)[None]
    if cfg.attn_impl == "flash":
        k_sel, v_sel, g = _grouped_kv(k_loc, v_loc, cfg, tp, hq_loc,
                                      kv_sharded or cross_kv is not None)
        qg = q.reshape(*q.shape[:2], k_sel.shape[2], g, hd)
        kvp = kv_pos.reshape(-1)
        o = _flash_jnp(qg, k_sel, v_sel, pos, kvp, kind=mask_kind,
                       window=cfg.window, n_prefix=n_prefix,
                       kv_valid=kv_valid, softcap=cfg.attn_softcap)
        o = o.reshape(*x.shape[:-1], hq_loc * hd)
    else:
        if kv_sharded:
            k_use = _repeat_kv(k_loc, hq_loc // k_loc.shape[2])
            v_use = _repeat_kv(v_loc, hq_loc // v_loc.shape[2])
        else:
            k_use = _local_kv_select(k_loc, cfg, tp)
            v_use = _local_kv_select(v_loc, cfg, tp)
        mask = make_mask(pos, kv_pos, kind=mask_kind,
                         window=cfg.window, n_prefix=n_prefix,
                         kv_len_valid=kv_valid)
        o = _sdpa(q, k_use, v_use, mask, softcap=cfg.attn_softcap)
        o = o.reshape(*x.shape[:-1], hq_loc * hd)
    # fsdp_dim=1 fuses the data-axis w_o gather AND the model-axis
    # reduce-scatter around the o-projection (the 2-D collective matmul)
    y = ops.row_matmul(o, p["w_o"], fsdp_dim=1)
    return AttnOut(y=y, cache=new_cache)


def _cache_write(buf, kv, t):
    """Write a [B,1,...] (or [B,s,...]) update at position t."""
    return lax.dynamic_update_slice_in_dim(buf, kv.astype(buf.dtype), t,
                                           axis=1)


def _decode_seq_sharded(cfg, q, k_new, v_new, cache, pos, *, kind):
    """Flash-decoding over a sequence-sharded cache (data axis).

    cache k/v: [B, S_loc, KV, hd]; this shard owns absolute positions
    [d_idx*S_loc, (d_idx+1)*S_loc).  The new token is written to its owner
    shard; partial softmax stats combine with tunable all-reduces.
    """
    d_idx = lax.axis_index(AXES.data) if has_axis(AXES.data) else 0
    s_loc = cache["k"].shape[1]
    t = cache["len"]                       # global length before this token
    local_t = t - d_idx * s_loc
    owner = (local_t >= 0) & (local_t < s_loc)
    wpos = jnp.clip(local_t, 0, s_loc - 1)
    kc = lax.dynamic_update_slice_in_dim(
        cache["k"],
        jnp.where(owner, k_new, lax.dynamic_slice_in_dim(
            cache["k"], wpos, k_new.shape[1], axis=1).astype(k_new.dtype)
        ).astype(cache["k"].dtype), wpos, axis=1)
    vc = lax.dynamic_update_slice_in_dim(
        cache["v"],
        jnp.where(owner, v_new, lax.dynamic_slice_in_dim(
            cache["v"], wpos, v_new.shape[1], axis=1).astype(v_new.dtype)
        ).astype(cache["v"].dtype), wpos, axis=1)
    new_cache = {"k": kc, "v": vc, "len": t + 1}

    tp = axis_size_or_1(AXES.model)
    hq_loc = cfg.heads_padded(tp) // tp
    kv_sharded = cfg.n_kv_heads % tp == 0
    if kv_sharded:
        k_use = _repeat_kv(kc, hq_loc // kc.shape[2])
        v_use = _repeat_kv(vc, hq_loc // vc.shape[2])
    else:
        k_use = _local_kv_select(kc, cfg, tp)
        v_use = _local_kv_select(vc, cfg, tp)

    kv_pos = d_idx * s_loc + jnp.arange(s_loc)[None]
    mask = make_mask(pos, kv_pos, kind=kind, window=cfg.window,
                     kv_len_valid=t + 1)
    o, l, m = _sdpa_partial(q, k_use, v_use, mask,
                            softcap=cfg.attn_softcap)
    # combine partials over the data axis (the tunable collective)
    if has_axis(AXES.data):
        g_m = lax.pmax(m, AXES.data)
        a = jnp.exp(m - g_m)
        num = api.allreduce(o * a[..., None].transpose(0, 2, 1, 3
                                                       ).astype(o.dtype),
                            AXES.data)
        den = api.allreduce(l * a, AXES.data)
    else:
        num, den = o, l
    o = num / jnp.maximum(den, 1e-30).transpose(0, 2, 1)[..., None].astype(
        num.dtype)
    o = o.reshape(*q.shape[:2], hq_loc * cfg.hd)
    return o, new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# ---------------------------------------------------------------------------


def _attention_mla(p, cfg: ModelConfig, x, *, pos, kind, cache, mode):
    m = cfg.mla
    tp = axis_size_or_1(AXES.model)
    hq = cfg.heads_padded(tp)
    hq_loc = hq // tp
    qk_hd = m.nope_head_dim + m.rope_head_dim

    c_q = rms_norm(ops.matmul_accumulate(x, p["w_dq"]), p["q_norm"],
                   cfg.norm_eps)
    q = ops.col_matmul(c_q, p["w_uq"], fsdp_dim=0).reshape(
        *x.shape[:-1], hq_loc, qk_hd)
    q_nope, q_rope = q[..., :m.nope_head_dim], q[..., m.nope_head_dim:]
    q_rope = rope(q_rope, pos, cfg.rope_theta)

    ckv_kr = ops.matmul_accumulate(
        x, ops.tp_psum_grad(p["w_dkv"]))                # [B,S,kvr+dr]
    c_kv = rms_norm(ckv_kr[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = rope(ckv_kr[..., None, m.kv_lora_rank:], pos, cfg.rope_theta)

    new_cache = None
    if mode == "prefill":
        cc = lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), 0, axis=1)
        kr = lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope[..., 0, :].astype(cache["k_rope"].dtype),
            0, axis=1)
        new_cache = {"c_kv": cc, "k_rope": kr, "len": jnp.int32(x.shape[1])}
        kv_pos, kv_valid = pos, None
    elif mode == "decode":
        t = cache["len"]
        cc = lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), t, axis=1)
        kr = lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope[..., 0, :].astype(cache["k_rope"].dtype),
            t, axis=1)
        new_cache = {"c_kv": cc, "k_rope": kr, "len": t + x.shape[1]}
        c_kv, k_rope = cc, kr[..., None, :]
        kv_pos = jnp.arange(cc.shape[1])[None]
        kv_valid = t + x.shape[1]
    else:
        kv_pos, kv_valid = pos, None

    if cfg.attn_impl == "flash":
        # the absorbed path reshapes the FULL up-projection weight into
        # per-head factors, so it keeps the unfused gather
        w_ukv = ops.fsdp_gather(p["w_ukv"], 0)
        # ABSORBED MLA (+ flash): fold W_uk into q and W_uv into the output
        # so the latent cache itself is the KV — no [B,S,H,dh] k/v ever
        # materializes (DeepSeek's own inference optimization, §Perf).
        w_ukv_h = w_ukv.reshape(m.kv_lora_rank, hq_loc,
                                m.nope_head_dim + m.v_head_dim)
        w_uk = w_ukv_h[..., :m.nope_head_dim]      # [kvr, H, dn]
        w_uv = w_ukv_h[..., m.nope_head_dim:]      # [kvr, H, dv]
        q_eff = jnp.einsum("bshd,khd->bshk", q_nope, w_uk)
        qf = jnp.concatenate([q_eff, q_rope.astype(q_eff.dtype)], axis=-1)
        keys = jnp.concatenate(
            [c_kv, (k_rope[..., 0, :] if k_rope.ndim == 4 else k_rope
                    ).astype(c_kv.dtype)], axis=-1)[:, :, None, :]
        vals = c_kv[:, :, None, :]
        o_lat = _flash_jnp(
            qf[:, :, None, :, :], keys, vals, pos, kv_pos.reshape(-1),
            kind=kind, window=cfg.window, kv_valid=kv_valid,
            softcap=cfg.attn_softcap, scale=1.0 / math.sqrt(qk_hd))
        o_lat = o_lat[:, :, 0]                     # [B,S,H,kvr]
        o = jnp.einsum("bshk,khd->bshd", o_lat, w_uv)
        o = o.reshape(*x.shape[:-1], hq_loc * m.v_head_dim)
    else:
        # naive MLA: up-project latent kv for local heads per use
        kv = ops.col_matmul(c_kv.astype(x.dtype), p["w_ukv"],
                            fsdp_dim=0).reshape(
            *c_kv.shape[:-1], hq_loc, m.nope_head_dim + m.v_head_dim)
        k_nope, v = kv[..., :m.nope_head_dim], kv[..., m.nope_head_dim:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(
                k_rope.astype(k_nope.dtype),
                (*k_nope.shape[:-1], m.rope_head_dim))], axis=-1)
        qf = jnp.concatenate([q_nope, q_rope], axis=-1)
        mask = make_mask(pos, kv_pos, kind=kind, window=cfg.window,
                         kv_len_valid=kv_valid)
        o = _sdpa(qf, k, v, mask, softcap=cfg.attn_softcap,
                  scale=1.0 / math.sqrt(qk_hd))
        o = o.reshape(*x.shape[:-1], hq_loc * m.v_head_dim)
    y = ops.row_matmul(o, p["w_o"], fsdp_dim=1)
    return AttnOut(y=y, cache=new_cache)
