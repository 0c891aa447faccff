"""Device ms a step in zamba2's shared attention block (scope
``shared_attn`` of ``models/lm.py:_run_block``: the ``[x, x0]``
projection, attention and MLP, every use), forward and backward together
(device trace)."""

from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "shared_attn")
