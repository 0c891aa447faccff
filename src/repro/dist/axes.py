"""The mesh-axis registry: which named axes exist and what each one means.

=======  ==================================================================
axis     role
=======  ==================================================================
data     FSDP/ZeRO-3 parameter sharding + batch data parallelism (ICI);
         also the sequence axis for seq-sharded long-context decode
model    tensor parallelism (Megatron col/row splits) and expert
         parallelism for MoE (ICI)
pod      pure data parallelism across pods (DCN) — params never shard here
=======  ==================================================================

``has_axis``/``axis_size_or_1`` are TRACE-time queries of the enclosing
binding (shard_map mesh axis, or ``vmap(axis_name=...)`` in semantic tests).
Outside any binding every ``dist.ops`` primitive degrades to its local
meaning, so the same model code runs unsharded under plain ``jit``.
"""
from __future__ import annotations

import dataclasses

from jax import lax

from repro.core._axis import axis_size


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Canonical axis names; import ``AXES`` rather than string literals."""
    data: str = "data"
    model: str = "model"
    pod: str = "pod"

    def __iter__(self):
        return iter((self.data, self.model, self.pod))


AXES = MeshAxes()


def has_axis(axis_name: str | None) -> bool:
    """True iff ``axis_name`` is bound in the current trace (static)."""
    if not axis_name:
        return False
    try:
        lax.axis_size(axis_name)
        return True
    except (NameError, KeyError, ValueError, TypeError):
        return False


def axis_size_or_1(axis_name: str | None) -> int:
    """Static size of ``axis_name``, or 1 when it is not bound."""
    return axis_size(axis_name) if has_axis(axis_name) else 1
