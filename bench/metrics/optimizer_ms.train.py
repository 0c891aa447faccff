"""Device ms a step in the optimizer's update (scope ``optimizer`` of
``train/trainer.py``, the AdamW update of every leaf; device trace)."""

from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "optimizer")
