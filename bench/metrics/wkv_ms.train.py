"""Device ms a step in the RWKV6 recurrence (``models/ssm.py:_wkv_scan``,
scope ``wkv``), forward and backward together (device trace)."""

from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "wkv")
