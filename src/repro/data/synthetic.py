"""Deterministic synthetic data pipeline.

Every (step, arch, shape) produces the same batch on every host — each
process could generate only its shard (seeded by (step, shard_index)) with
no I/O or inter-host coordination, which is how the launcher would feed
thousands of workers.  Token streams are Zipf-ish (structured enough that
loss decreases during the example runs, unlike uniform noise).
"""
from __future__ import annotations

import zlib

import numpy as np

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig


def batch_specs(cfg: ModelConfig, batch: int, seq: int) -> dict:
    """ShapeDtypeStructs of one global batch (dry-run / jit signature)."""
    specs = {
        "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
    }
    if cfg.encdec is not None:
        specs["frames"] = jax.ShapeDtypeStruct(
            (batch, seq, cfg.d_model), jnp.bfloat16)
        dec = max(seq // cfg.encdec.dec_ratio, 16)
        specs["tokens"] = jax.ShapeDtypeStruct((batch, dec), jnp.int32)
        specs["labels"] = jax.ShapeDtypeStruct((batch, dec), jnp.int32)
    if cfg.vlm is not None:
        specs["patches"] = jax.ShapeDtypeStruct(
            (batch, cfg.vlm.n_patches, cfg.vlm.patch_dim), jnp.bfloat16)
        txt = max(seq - cfg.vlm.n_patches, 16)
        specs["tokens"] = jax.ShapeDtypeStruct((batch, txt), jnp.int32)
        specs["labels"] = jax.ShapeDtypeStruct((batch, txt), jnp.int32)
    return specs


def make_batch(cfg: ModelConfig, batch: int, seq: int, step: int,
               *, shard: int = 0, n_shards: int = 1) -> dict:
    """Host-side numpy batch (the given shard slice of the global batch)."""
    assert batch % n_shards == 0
    b_loc = batch // n_shards
    # crc32, not hash(): str hashes are salted per process
    rng = np.random.default_rng((zlib.crc32(cfg.name.encode()) & 0xFFFF,
                                 step, shard))
    specs = batch_specs(cfg, batch, seq)
    t_shape = (b_loc,) + specs["tokens"].shape[1:]
    # Zipf-distributed ids with per-sequence offset => learnable structure
    base = rng.zipf(1.3, size=t_shape).astype(np.int64)
    offs = rng.integers(0, 97, size=(b_loc, 1))
    toks = ((base + offs) % cfg.vocab_size).astype(np.int32)
    out = {"tokens": toks, "labels": toks.copy()}
    if cfg.encdec is not None:
        out["frames"] = rng.standard_normal(
            (b_loc, seq, cfg.d_model), dtype=np.float32)
    if cfg.vlm is not None:
        out["patches"] = rng.standard_normal(
            (b_loc, cfg.vlm.n_patches, cfg.vlm.patch_dim), dtype=np.float32)
    return out
