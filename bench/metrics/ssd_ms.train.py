"""Device ms a step in the Mamba2 SSD scan (``models/ssm.py:_ssd_chunked``,
scope ``ssd``), forward and backward together (device trace)."""

from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "ssd")
