"""Device time of one of the program's named scopes, from the traced window.

The program puts ``jax.named_scope``s around its layers (``wkv``, ``head``,
``optimizer``, ...).  A scope is a component of the ``op_name`` metadata
(JAX's name stack) of every instruction compiled inside it: bare inside a
scanned body (``.../while/body/closed_call/rwkv/wkv/dot_general``), or
wrapped by autodiff where it was entered outside one (``jvp(head)``,
``transpose(jvp(head))``).  The compiled step gives each instruction's
``op_name`` (``op_names``); an instruction counts for a scope when one
component of its name stack, its wrappers taken off, is the scope's name.
A program without the scope (an older commit) reads nothing, not 0.
"""
from __future__ import annotations

import contextlib
import re

from bench import trace as trace_mod

_WRAPPED = re.compile(r"^(?:[\w.]+\()*([^()]*)\)*$")


def scopes_of(stack: str) -> set[str]:
    """The scope names in a name stack, wrappers taken off:
    ``'jit(step)/transpose(jvp(head))/dot_general'`` ->
    ``{'step', 'head', 'dot_general'}``."""
    out = set()
    for part in stack.split("/"):
        m = _WRAPPED.match(part)
        out.add(m.group(1) if m else part)
    return out


@contextlib.contextmanager
def _persistent_cache_off():
    """JAX's persistent compile cache off for the block, then as it was."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _step_text(cell) -> str:
    """The compiled text of the train driver's step, as its ``op_label``
    lowers it."""
    import jax.numpy as jnp
    tr = cell.trainer
    with tr._tuned():
        return tr._step.lower(cell.params, cell.opt,
                              tr.put_batch(cell.batch(0)),
                              jnp.asarray(0, jnp.int32)).compile().as_text()


def carry_names(ran: str, own: str) -> dict[str, str]:
    """Instruction of the module ``ran`` -> ``op_name`` of the instruction
    at its place in ``own``, the same program compiled with other
    metadata: instructions match by their place in each computation's
    schedule, their numbering aside.  Where the two do not line up, the
    names of ``own``."""
    a, b = trace_mod.hlo_schedule(ran), trace_mod.hlo_schedule(own)

    def shape(comps):
        return [[re.sub(r"\.\d+", "", n) for n, _ in c] for c in comps]

    if shape(a) != shape(b):
        return trace_mod.hlo_op_names(own)
    return {n: op for ca, cb in zip(a, b)
            for (n, _), (_, op) in zip(ca, cb) if op is not None}


def op_names(cell) -> dict[str, str]:
    """Instruction name of the step that ran -> ``op_name`` as this
    checkout's program names it; built once a cell.

    The persistent cache's key leaves debug information out, ``op_name``
    with it: a step that differs from another checkout's only in its
    scopes loads that checkout's executable, with its names, where the
    same cache saw that checkout first.  So the names come from a second
    compile, with the persistent cache off and JAX's in-memory caches
    cleared (the readers run after the window: no step pays for it), and
    are carried over to the executable that ran, whose instructions may
    be numbered otherwise (``carry_names``)."""
    if getattr(cell, "scope_names", None) is None:
        import jax
        ran = _step_text(cell)
        with _persistent_cache_off():
            jax.clear_caches()
            own = _step_text(cell)
        cell.scope_names = carry_names(ran, own)
    return cell.scope_names


def scope_ms(ctx, scope: str) -> float | None:
    """Device ms a step, mean over the cell's chips, of the instructions
    that carry ``scope``; None without device ops, steps or such
    instructions."""
    tr, steps = ctx.trace, ctx.info.get("steps")
    if not tr.ops or not steps:
        return None
    tagged = {n for n, stack in op_names(ctx.cell).items()
              if scope in scopes_of(stack)}
    secs = tr.op_seconds(lambda n: "in" if n in tagged else None)
    if "in" not in secs:
        return None
    return secs["in"] / steps * 1e3
