"""Model assembly: spec trees, forward pass, loss, prefill/decode.

The same code path serves all ten architectures:

* dense / MoE decoder-only LMs  (llama / gemma / phi / deepseek)
* SSM (rwkv6) and hybrid (zamba2: mamba + shared attention block)
* encoder-decoder (whisper: stub frame embeddings + cross-attention)
* VLM (paligemma: stub patch embeddings + prefix-LM mask)

Layer stacks are grouped into ``lax.scan``s over stacked parameters (compile
time stays flat in depth); heterogeneous patterns (gemma local:global cycles)
scan over the repeating unit, with a remainder group.

Everything is written for manual-SPMD: call inside ``shard_map`` (or plain
jit on one device — every dist op degrades to identity).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.dist import ops
from repro.dist.axes import AXES, axis_size_or_1
from repro.models import ssm as ssm_mod
from repro.models.attention import attention, attn_specs, cross_attn_specs
from repro.models.config import ModelConfig
from repro.models.layers import (embed_lookup, embed_specs, head_specs,
                                 lm_logits, mlp, mlp_specs, rms_norm,
                                 sharded_xent, sincos_positions)
from repro.models.moe import moe_block, moe_specs
from repro.models.params import ParamSpec, stacked, tree_map_specs

#: What a rematerialised layer scan keeps for its backward: the products
#: with no batch dimension, which in these blocks are the weight
#: projections (``dist/ops.py``'s matmuls, the LoRAs).  The batched
#: products (WKV and SSD chunks, attention scores), norms and elementwise
#: work are recomputed.
REMAT_POLICY = jax.checkpoint_policies.dots_with_no_batch_dims_saveable


# ---------------------------------------------------------------------------
# stack plan: group the layer pattern into scannable units
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Group:
    name: str
    unit: tuple[str, ...]     # block kinds executed per scan step
    n_rep: int                # scan length


def stack_plan(cfg: ModelConfig) -> list[Group]:
    if not cfg.scan_layers:
        pat = list(cfg.pattern())
        if cfg.hybrid_period:
            out, cnt = [], 0
            for k in pat:
                out.append(k)
                cnt += 1
                if cnt % cfg.hybrid_period == 0:
                    out.append("shared_attn")
            pat = out
        return [Group(f"u{i}", (k,), 1) for i, k in enumerate(pat)]
    pat = list(cfg.pattern())
    if cfg.hybrid_period:
        # zamba2: insert a shared_attn marker after every k SSM layers
        out, cnt = [], 0
        for k in pat:
            out.append(k)
            cnt += 1
            if cnt % cfg.hybrid_period == 0:
                out.append("shared_attn")
        pat = out
    unit = list(cfg.layer_pattern)
    if cfg.hybrid_period:
        unit = list(cfg.layer_pattern) * cfg.hybrid_period + ["shared_attn"]
    # largest prefix of full units
    u = len(unit)
    n_rep = 0
    while (n_rep + 1) * u <= len(pat) and \
            pat[n_rep * u:(n_rep + 1) * u] == unit:
        n_rep += 1
    groups = []
    if n_rep:
        groups.append(Group("g0", tuple(unit), n_rep))
    rem = pat[n_rep * u:]
    if rem:
        groups.append(Group("g1", tuple(rem), 1))
    return groups


# ---------------------------------------------------------------------------
# per-block specs
# ---------------------------------------------------------------------------


def _block_specs(kind: str, cfg: ModelConfig, tp: int) -> dict:
    if kind in ("attn", "attn_local"):
        s = {
            "ln1": ParamSpec((cfg.d_model,), (None,), init="zeros",
                             dtype="float32"),
            "attn": attn_specs(cfg, tp),
            "ln2": ParamSpec((cfg.d_model,), (None,), init="zeros",
                             dtype="float32"),
        }
        s["ffn"] = (moe_specs(cfg) if cfg.moe is not None
                    else mlp_specs(cfg.d_model, cfg.d_ff, cfg.dtype))
        if cfg.encdec is not None:
            s["ln_x"] = ParamSpec((cfg.d_model,), (None,), init="zeros",
                                  dtype="float32")
            s["xattn"] = cross_attn_specs(cfg, tp)
        return s
    if kind == "rwkv":
        return ssm_mod.rwkv_specs(cfg, tp)
    if kind == "mamba":
        return ssm_mod.mamba_specs(cfg, tp)
    raise ValueError(kind)


def _enc_block_specs(cfg: ModelConfig, tp: int) -> dict:
    return {
        "ln1": ParamSpec((cfg.d_model,), (None,), init="zeros",
                         dtype="float32"),
        "attn": attn_specs(dataclasses.replace(cfg, mla=None), tp),
        "ln2": ParamSpec((cfg.d_model,), (None,), init="zeros",
                         dtype="float32"),
        "ffn": mlp_specs(cfg.d_model, cfg.d_ff, cfg.dtype),
    }


def model_specs(cfg: ModelConfig, tp: int) -> dict:
    """The full parameter tree (ParamSpec leaves)."""
    specs: dict[str, Any] = {"embed": embed_specs(
        cfg.vocab_padded, cfg.d_model, cfg.dtype)}
    if not cfg.tie_embeddings:
        specs["head"] = head_specs(cfg.d_model, cfg.vocab_padded, cfg.dtype)
    specs["final_norm"] = ParamSpec((cfg.d_model,), (None,), init="zeros",
                                    dtype="float32")
    stack: dict[str, Any] = {}
    for g in stack_plan(cfg):
        sub = {}
        for i, kind in enumerate(g.unit):
            if kind == "shared_attn":
                continue  # shared params live outside the scan
            sub[f"b{i}_{kind}"] = tree_map_specs(
                functools.partial(_stk, g.n_rep),
                _block_specs(kind, cfg, tp)) if g.n_rep > 1 else \
                _block_specs(kind, cfg, tp)
        stack[g.name] = sub
    specs["stack"] = stack
    if cfg.hybrid_period:
        shared_cfg = dataclasses.replace(cfg, moe=None, mla=None)
        specs["shared_attn"] = {
            "proj_in": ParamSpec((2 * cfg.d_model, cfg.d_model),
                                 ("data", None), dtype=cfg.dtype),
            **_block_specs("attn", shared_cfg, tp),
        }
    if cfg.encdec is not None:
        specs["encoder"] = tree_map_specs(
            functools.partial(_stk, cfg.encdec.n_enc_layers),
            _enc_block_specs(cfg, tp))
        specs["enc_final_norm"] = ParamSpec((cfg.d_model,), (None,),
                                            init="zeros", dtype="float32")
    if cfg.vlm is not None:
        specs["img_proj"] = ParamSpec((cfg.vlm.patch_dim, cfg.d_model),
                                      ("data", None), dtype=cfg.dtype)
    return specs


def _stk(n, spec: ParamSpec) -> ParamSpec:
    return stacked(n, spec)


# ---------------------------------------------------------------------------
# cache specs
# ---------------------------------------------------------------------------


def cache_specs(cfg: ModelConfig, batch: int, s_max: int, tp: int,
                *, seq_sharded: bool = False) -> dict:
    """ParamSpec tree for the KV/SSM cache (global shapes + shardings)."""
    hd = cfg.hd
    kv_sharded = cfg.n_kv_heads % tp == 0 if cfg.n_kv_heads else False
    n_kv = cfg.n_kv_heads
    kv_dim = "model" if kv_sharded else None
    bdim, sdim = ("data", None) if not seq_sharded else (None, "data")
    dt = cfg.dtype

    def attn_cache():
        if cfg.mla is not None:
            m = cfg.mla
            return {
                "c_kv": ParamSpec((batch, s_max, m.kv_lora_rank),
                                  (bdim, sdim, None), dtype=dt),
                "k_rope": ParamSpec((batch, s_max, m.rope_head_dim),
                                    (bdim, sdim, None), dtype=dt),
                "len": ParamSpec((), (), init="zeros", dtype="int32"),
            }
        return {
            "k": ParamSpec((batch, s_max, n_kv, hd),
                           (bdim, sdim, kv_dim, None), dtype=dt),
            "v": ParamSpec((batch, s_max, n_kv, hd),
                           (bdim, sdim, kv_dim, None), dtype=dt),
            "len": ParamSpec((), (), init="zeros", dtype="int32"),
        }

    # SSM states have no sequence dim: when the cell seq-shards (batch=1,
    # long-context), the state is replicated over "data" instead.
    sb = None if seq_sharded else "data"

    def ssm_cache(kind):
        if kind == "rwkv":
            h = ssm_mod.rwkv_heads_padded(cfg, tp)
            sd = cfg.ssm.head_dim
            return {
                "last_tm": ParamSpec((batch, 1, cfg.d_model),
                                     (sb, None, None), dtype=dt),
                "last_cm": ParamSpec((batch, 1, cfg.d_model),
                                     (sb, None, None), dtype=dt),
                "s": ParamSpec((batch, h, sd, sd),
                               (sb, "model", None, None),
                               dtype="float32"),
            }
        di = cfg.ssm.expand * cfg.d_model
        nh = di // cfg.ssm.head_dim
        k = cfg.ssm.conv_kernel
        return {
            "conv_x": ParamSpec((batch, k - 1, di),
                                (sb, None, "model"), dtype=dt),
            "conv_bc": ParamSpec((batch, k - 1, 2 * cfg.ssm.state_dim),
                                 (sb, None, None), dtype=dt),
            "s": ParamSpec((batch, nh, cfg.ssm.state_dim, cfg.ssm.head_dim),
                           (sb, "model", None, None), dtype="float32"),
        }

    def block_cache(kind):
        if kind in ("attn", "attn_local"):
            c = {"self": attn_cache()}
            if cfg.encdec is not None:
                enc_len = s_max  # encoder length == s_max convention
                c["cross_k"] = ParamSpec(
                    (batch, enc_len, n_kv, hd),
                    (bdim, None, kv_dim, None), dtype=dt)
                c["cross_v"] = ParamSpec(
                    (batch, enc_len, n_kv, hd),
                    (bdim, None, kv_dim, None), dtype=dt)
            return c
        if kind == "shared_attn":
            return {"self": attn_cache()}
        return ssm_cache(kind)

    out: dict[str, Any] = {"stack": {}}
    for g in stack_plan(cfg):
        sub = {}
        for i, kind in enumerate(g.unit):
            bc = block_cache(kind)
            sub[f"b{i}_{kind}"] = (tree_map_specs(
                functools.partial(_stk, g.n_rep), bc)
                if g.n_rep > 1 else bc)
        out["stack"][g.name] = sub
    return out


# ---------------------------------------------------------------------------
# block execution
# ---------------------------------------------------------------------------


def _run_attn_block(p, cfg: ModelConfig, x, *, kind, pos, mode, cache,
                    n_prefix, enc_out, use_rope, seq_sharded=False):
    aux = jnp.float32(0.0)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    mask_kind = ("local" if kind == "attn_local" else
                 ("prefix" if n_prefix else "causal"))
    a = attention(p["attn"], cfg, h, pos=pos, kind=mask_kind,
                  n_prefix=n_prefix,
                  cache=None if cache is None else cache.get("self"),
                  mode=mode, use_rope=use_rope, seq_sharded=seq_sharded)
    x = x + a.y
    new_cache = {"self": a.cache} if a.cache is not None else None

    if cfg.encdec is not None:
        hx = rms_norm(x, p["ln_x"], cfg.norm_eps)
        if enc_out is not None:      # train/prefill: build cross kv now
            ck, cv = _cross_kv(p["xattn"], cfg, enc_out)
        else:                        # decode: cached
            ck, cv = cache["cross_k"], cache["cross_v"]
        ca = attention(p["xattn"], cfg, hx, pos=pos, cross_kv=(ck, cv),
                       mode="train", use_rope=False)
        x = x + ca.y
        if new_cache is not None:
            new_cache["cross_k"], new_cache["cross_v"] = ck, cv
        elif cache is not None:
            new_cache = {"self": cache.get("self"), "cross_k": ck,
                         "cross_v": cv}

    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        y, aux = moe_block(p["ffn"], cfg, h2)
    else:
        y = mlp(p["ffn"], h2)
    x = x + y
    return x, new_cache, aux


def _cross_kv(p, cfg: ModelConfig, enc_out):
    tp = axis_size_or_1(AXES.model)
    hd = cfg.hd
    kv_sharded = cfg.n_kv_heads % tp == 0
    if kv_sharded:
        k = ops.col_matmul(enc_out, p["w_k"], fsdp_dim=0)
        v = ops.col_matmul(enc_out, p["w_v"], fsdp_dim=0)
    else:
        enc_kv = ops.tp_copy(enc_out)
        k = ops.matmul_accumulate(enc_kv, ops.tp_psum_grad(p["w_k"]))
        v = ops.matmul_accumulate(enc_kv, ops.tp_psum_grad(p["w_v"]))
    n_loc = (cfg.n_kv_heads // tp) if kv_sharded else cfg.n_kv_heads
    k = k.reshape(*enc_out.shape[:-1], n_loc, hd)
    v = v.reshape(*enc_out.shape[:-1], n_loc, hd)
    return k, v


def _run_block(kind, p, cfg, x, *, pos, mode, cache, n_prefix, enc_out,
               use_rope, shared_p=None, resid0=None, seq_sharded=False):
    with jax.named_scope(kind):
        if kind in ("attn", "attn_local"):
            return _run_attn_block(p, cfg, x, kind=kind, pos=pos, mode=mode,
                                   cache=cache, n_prefix=n_prefix,
                                   enc_out=enc_out, use_rope=use_rope,
                                   seq_sharded=seq_sharded)
        if kind == "shared_attn":
            # zamba2: shared transformer block on concat(x, resid0),
            # projected in
            h = ops.matmul_accumulate(jnp.concatenate([x, resid0], axis=-1),
                                      shared_p["proj_in"])
            shared_cfg = dataclasses.replace(cfg, moe=None, mla=None)
            y, c, aux = _run_attn_block(
                shared_p, shared_cfg, h, kind="attn", pos=pos, mode=mode,
                cache=cache, n_prefix=n_prefix, enc_out=None,
                use_rope=use_rope, seq_sharded=seq_sharded)
            return x + y, c, aux
        if kind == "rwkv":
            y, st = ssm_mod.rwkv_block(p, cfg, x, state=cache)
            return y, st, jnp.float32(0.0)
        if kind == "mamba":
            y, st = ssm_mod.mamba_block(p, cfg, x, state=cache)
            return y, st, jnp.float32(0.0)
        raise ValueError(kind)


def _run_stack(params, cfg: ModelConfig, x, *, pos, mode, caches,
               n_prefix, enc_out, use_rope, seq_sharded=False):
    """Execute all groups; returns (x, new_caches, aux_sum)."""
    aux_total = jnp.float32(0.0)
    new_caches: dict[str, Any] = {"stack": {}}
    resid0 = x
    shared_p = params.get("shared_attn")

    for g in stack_plan(cfg):
        gp = params["stack"][g.name]
        gc = None if caches is None else caches["stack"][g.name]

        if g.n_rep == 1:
            ncs = {}
            for i, kind in enumerate(g.unit):
                key = f"b{i}_{kind}"
                bc = None if gc is None else gc.get(key)
                x, nc, aux = _run_block(
                    kind, gp.get(key), cfg, x, pos=pos, mode=mode, cache=bc,
                    n_prefix=n_prefix, enc_out=enc_out, use_rope=use_rope,
                    shared_p=shared_p, resid0=resid0,
                    seq_sharded=seq_sharded)
                aux_total = aux_total + aux
                if nc is not None:
                    ncs[key] = nc
            new_caches["stack"][g.name] = ncs
            continue

        # scanned group: params (and caches) have leading dim n_rep
        def _unit(xc, auxc, lp, lc):
            ncs = {}
            for i, kind in enumerate(g.unit):
                key = f"b{i}_{kind}"
                bc = None if lc is None else lc.get(key)
                xc, nc, aux = _run_block(
                    kind, lp.get(key), cfg, xc, pos=pos, mode=mode,
                    cache=bc, n_prefix=n_prefix, enc_out=enc_out,
                    use_rope=use_rope, shared_p=shared_p, resid0=resid0,
                    seq_sharded=seq_sharded)
                auxc = auxc + aux
                if nc is not None:
                    ncs[key] = nc
            return xc, auxc, ncs

        if gc is None:
            def body(carry, lp):
                xc, auxc, _ = _unit(carry[0], carry[1], lp, None)
                return (xc, auxc), None
        else:
            def body(carry, layer_in):
                lp, lc = layer_in
                xc, auxc, ncs = _unit(carry[0], carry[1], lp, lc)
                return (xc, auxc), ncs

        if cfg.remat and mode == "train":
            body = jax.checkpoint(body, policy=REMAT_POLICY)

        with jax.named_scope("layers"):
            (x, aux_total), ncs = lax.scan(
                body, (x, aux_total), gp if gc is None else (gp, gc))
        new_caches["stack"][g.name] = ncs if gc is not None else None

    return x, (new_caches if caches is not None else None), aux_total


# ---------------------------------------------------------------------------
# embedding front-ends per family
# ---------------------------------------------------------------------------


def _embed_inputs(params, cfg: ModelConfig, batch, *, pos0=0):
    """Returns (x, pos, n_prefix, labels_mask_extra)."""
    scale = (cfg.d_model ** 0.5) if cfg.scale_embed else None
    if cfg.vlm is not None and "patches" in batch:
        img = ops.matmul_accumulate(batch["patches"], params["img_proj"])
        img = img.astype(jnp.dtype(cfg.dtype))
        txt = embed_lookup(params["embed"], batch["tokens"], scale=scale)
        x = jnp.concatenate([img, txt], axis=1)
        n_prefix = img.shape[1]
        pos = pos0 + jnp.arange(x.shape[1])[None, :]
        return x, pos, n_prefix
    x = embed_lookup(params["embed"], batch["tokens"], scale=scale)
    pos = pos0 + jnp.arange(x.shape[1])[None, :]
    if cfg.encdec is not None:
        x = x + sincos_positions(pos, cfg.d_model).astype(x.dtype)
    return x, pos, 0


def _encode(params, cfg: ModelConfig, frames):
    """Whisper encoder over stub frame embeddings [B, S_enc, D]."""
    pos = jnp.arange(frames.shape[1])[None, :]
    x = frames.astype(jnp.dtype(cfg.dtype))
    x = x + sincos_positions(pos, cfg.d_model).astype(x.dtype)

    def body(carry, lp):
        h = rms_norm(carry, lp["ln1"], cfg.norm_eps)
        a = attention(lp["attn"], cfg, h, pos=pos, kind="full",
                      mode="train", use_rope=False)
        xc = carry + a.y
        h2 = rms_norm(xc, lp["ln2"], cfg.norm_eps)
        xc = xc + mlp(lp["ffn"], h2)
        return xc, None

    if cfg.remat:
        body = jax.checkpoint(body, policy=REMAT_POLICY)
    x, _ = lax.scan(body, x, params["encoder"])
    return rms_norm(x, params["enc_final_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def forward(params, cfg: ModelConfig, batch, *, mode="train", caches=None,
            pos0=0, seq_sharded=False):
    """Full forward.  Returns (logits [B,S,V_t], new_caches, aux)."""
    use_rope = cfg.encdec is None
    enc_out = None
    if cfg.encdec is not None and "frames" in batch:
        enc_out = _encode(params, cfg, batch["frames"])
    with jax.named_scope("embed"):
        x, pos, n_prefix = _embed_inputs(params, cfg, batch, pos0=pos0)
    x, new_caches, aux = _run_stack(
        params, cfg, x, pos=pos, mode=mode, caches=caches,
        n_prefix=n_prefix, enc_out=enc_out, use_rope=use_rope,
        seq_sharded=seq_sharded)
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = lm_logits(params["embed"], x,
                           params.get("head") if not cfg.tie_embeddings
                           else None, final_softcap=cfg.final_softcap)
    return logits, new_caches, aux


def loss_fn(params, cfg: ModelConfig, batch):
    """Next-token CE (text positions only for VLM).  Scalar local mean."""
    logits, _, aux = forward(params, cfg, batch, mode="train")
    labels = batch["labels"]
    with jax.named_scope("head"):
        if cfg.vlm is not None:
            n_img = cfg.vlm.n_patches
            logits = logits[:, n_img:]
        mask = batch.get("mask")
        loss = sharded_xent(logits[:, :-1], labels[:, 1:],
                            None if mask is None else mask[:, 1:])
    return loss + 0.01 * aux, {"nll": loss, "aux": aux}


def init_caches(cfg: ModelConfig, batch_size: int, s_max: int,
                *, seq_sharded: bool = False):
    """Zero caches with SHARD-LOCAL shapes (call inside shard_map/jit)."""
    from repro.dist.axes import axis_size_or_1 as _as
    tp = _as(AXES.model)
    sizes = {"model": tp, "data": _as(AXES.data)}
    specs = cache_specs(cfg, batch_size, s_max, tp, seq_sharded=seq_sharded)

    def mk(s: ParamSpec):
        return jnp.zeros(s.local_shape(sizes), jnp.dtype(s.dtype))

    return tree_map_specs(mk, specs)


def prefill(params, cfg: ModelConfig, batch, caches, *, seq_sharded=False):
    """Fill caches from a prompt; returns (last-token logits, caches)."""
    logits, new_caches, _ = forward(params, cfg, batch, mode="prefill",
                                    caches=caches, seq_sharded=seq_sharded)
    return logits[:, -1:], new_caches


def decode_step(params, cfg: ModelConfig, token, caches, t, *,
                seq_sharded=False):
    """One-token step.  token: [B,1] int32; t: current length (scalar)."""
    batch = {"tokens": token}
    logits, new_caches, _ = forward(params, cfg, batch, mode="decode",
                                    caches=caches, pos0=t,
                                    seq_sharded=seq_sharded)
    return logits, new_caches
