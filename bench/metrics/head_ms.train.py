"""Device ms a step in the model's head (scope ``head`` of
``models/lm.py``: final norm, unembedding and cross-entropy), forward and
backward together (device trace)."""

from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "head")
