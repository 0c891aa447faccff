"""Serving step builders (prefill / decode) as shard_map'd jits.

Profile-driven serving: each builder resolves a tuned ``ProfileStore`` with
precedence  explicit ``profiles=``/``phase_profiles=`` args  >
``profile_dir=`` (or ``$PGTUNE_PROFILE_DIR``)  >  none — and activates it
*inside* the step function, so the PGMPITuneD redirection happens when jit
actually traces (first call), not at builder time.  Dispatches are tagged
``api.phase("prefill")`` / ``api.phase("decode")``, which (a) records a
phase-split workload trace into ``record=`` and (b) lets per-phase stores
from ``tuner.tune_trace`` pick different mock-ups for prefill vs decode.

When no tuning inputs are given the step functions run under whatever
``api.tuned`` context is ambient at call time (e.g. launch/dryrun's), so
callers that manage their own context keep full control.

Fleet mode: pass ``store_ref=`` (a ``profiles.StoreRef``, e.g. from
``resolve_stores(watch=True)``) and ``plan=`` (an ``api.Plan``).  The step
then takes one TRAILING replicated argument — the plan vector — and every
multi-impl dispatch site compiles to a runtime switch read from it.  A new
profile epoch is adopted by feeding ``plan.vector(store_ref)`` on the next
step call: contents change, shape doesn't, so the jit cache stays warm
(zero re-trace — the hot-swap demo in bench_fleet_retune.py counts).
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import api
from repro.core.profiles import resolve_stores
from repro.models import lm
from repro.models.config import ModelConfig


def _resolve(profiles, phase_profiles, profile_dir):
    """Explicit stores win; otherwise load from profile_dir / env."""
    if profiles is None and phase_profiles is None:
        base, phases = resolve_stores(profile_dir)
        return base, (phases or None)
    return profiles, phase_profiles


@contextlib.contextmanager
def _serving_ctx(tag, profiles, phase_profiles, force, record,
                 store_ref=None, plan=None):
    """Phase-tag the step; open a tuned context only when the builder was
    given tuning inputs (else the caller's ambient context applies)."""
    if (profiles, phase_profiles, force, store_ref,
            plan) == (None, None, None, None, None):
        if record is None:
            with api.phase(tag):
                yield
            return
        # record-only: a fresh context would silently shadow a caller-
        # managed api.tuned — inherit its tuning inputs, swap the sink
        amb = api._ctx()
        if amb is not None:
            with api.tuned(profiles=amb.profiles,
                           phase_profiles=amb.phase_profiles,
                           force=amb.force or None,
                           scratch_budget_bytes=amb.scratch_budget_bytes,
                           chunk_bytes=amb.chunk_bytes,
                           store_ref=amb.store_ref, plan=amb.plan,
                           record=record), api.phase(tag):
                yield
            return
    with api.tuned(profiles=profiles, phase_profiles=phase_profiles,
                   force=force, record=record, store_ref=store_ref,
                   plan=plan), api.phase(tag):
        yield


def build_prefill(cfg: ModelConfig, mesh, cell, *, profiles=None,
                  force=None, phase_profiles=None, profile_dir=None,
                  record=None, store_ref=None, plan=None):
    from repro.launch.shapes import input_specs

    profiles, phase_profiles = _resolve(profiles, phase_profiles,
                                        profile_dir)
    (p_sds, b_sds, c_sds), (p_ps, b_ps, c_ps) = input_specs(cfg, cell, mesh)

    if plan is None:
        def fn(params, batch, caches):
            with _serving_ctx("prefill", profiles, phase_profiles, force,
                              record, store_ref):
                logits, new_caches = lm.prefill(params, cfg, batch, caches,
                                                seq_sharded=cell.seq_sharded)
            return logits, new_caches

        in_specs, extra_sds = (p_ps, b_ps, c_ps), ()
    else:
        def fn(params, batch, caches, plan_vec):
            with _serving_ctx("prefill", profiles, phase_profiles, force,
                              record, store_ref, plan), \
                    api.plan_input(plan_vec):
                logits, new_caches = lm.prefill(params, cfg, batch, caches,
                                                seq_sharded=cell.seq_sharded)
            return logits, new_caches

        in_specs = (p_ps, b_ps, c_ps, P())
        extra_sds = (jax.ShapeDtypeStruct((plan.capacity,), jnp.int32),)

    sm = shard_map(fn, mesh=mesh, in_specs=in_specs,
                   out_specs=(P(_dp(mesh, cell)), c_ps),
                   check_vma=False)
    return jax.jit(sm), (p_sds, b_sds, c_sds, *extra_sds)


def build_decode(cfg: ModelConfig, mesh, cell, *, profiles=None, force=None,
                 phase_profiles=None, profile_dir=None, record=None,
                 store_ref=None, plan=None):
    from repro.launch.shapes import input_specs

    profiles, phase_profiles = _resolve(profiles, phase_profiles,
                                        profile_dir)
    (p_sds, t_sds, c_sds, i_sds), (p_ps, t_ps, c_ps, i_ps) = \
        input_specs(cfg, cell, mesh)

    if plan is None:
        def fn(params, token, caches, t):
            with _serving_ctx("decode", profiles, phase_profiles, force,
                              record, store_ref):
                return lm.decode_step(params, cfg, token, caches, t,
                                      seq_sharded=cell.seq_sharded)

        in_specs, extra_sds = (p_ps, t_ps, c_ps, i_ps), ()
    else:
        def fn(params, token, caches, t, plan_vec):
            with _serving_ctx("decode", profiles, phase_profiles, force,
                              record, store_ref, plan), \
                    api.plan_input(plan_vec):
                return lm.decode_step(params, cfg, token, caches, t,
                                      seq_sharded=cell.seq_sharded)

        in_specs = (p_ps, t_ps, c_ps, i_ps, P())
        extra_sds = (jax.ShapeDtypeStruct((plan.capacity,), jnp.int32),)

    sm = shard_map(fn, mesh=mesh, in_specs=in_specs,
                   out_specs=(t_ps if cell.seq_sharded
                              else P(_dp(mesh, cell)), c_ps),
                   check_vma=False)
    return (jax.jit(sm, donate_argnums=(2,)),
            (p_sds, t_sds, c_sds, i_sds, *extra_sds))


def _dp(mesh, cell):
    if cell.seq_sharded:
        return None
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
