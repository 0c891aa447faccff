"""Device ms a step in the layer scan's rematerialisation: the second
forward that ``jax.checkpoint`` in ``models/lm.py:_run_stack`` runs inside
the backward, whose instructions JAX names ``rematted_computation``
(device trace)."""

from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "rematted_computation")
