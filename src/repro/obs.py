"""The program's own tracing: device scopes, host spans, a compile counter.

Everything goes into JAX's profiler, the one system an operator's capture
(``launch/train.py --trace-dir``) and the benchmark already read; there is
no exporter of its own.

Device scopes (``jax.named_scope``) name the ``op_name`` metadata of every
instruction compiled inside them, so they cost nothing at run time:

* ``embed``, ``head`` (final norm, logits, loss), ``layers`` (a scanned
  layer group: the scan's own slicing and stacking of each layer's
  parameters, residuals and gradients) and one scope per block kind
  (``rwkv``, ``mamba``, ``attn``, ``attn_local``, ``shared_attn``) in
  ``models/lm.py``; ``wkv`` (the RWKV6 recurrence) with ``wkv/intra``
  (its intra-chunk part) and ``wkv/state`` (the chunk states, their scan
  and the inter-chunk output) nested inside, and ``ssd`` (Mamba2's
  chunked scan), in ``models/ssm.py``;
* ``grad_sync`` and ``optimizer`` in ``train/trainer.py``;
* ``pgtune.<op>.<impl>`` at each collective the dispatcher emits,
  ``core/api.py``.

Autodiff wraps a scope entered outside a loop body as ``jvp(head)`` and
``transpose(jvp(head))``; a scope inside a scanned body stays bare.

Host spans (``jax.profiler.TraceAnnotation``) land on the profiler's host
clock: ``train.step`` (with the step number) and ``train.put_batch`` in
``Trainer``; ``train.wait`` and ``ckpt.save`` in ``launch/train.py``.

Compiles: ``compiles()`` counts the executables this process has built
since the counter was installed, from JAX's
``/jax/core/compile/backend_compile_duration`` event, which wraps both a
compile and a load from the persistent cache.
"""
from __future__ import annotations

import threading

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _Compiles:
    """Executables built in this process, and their seconds.  Compiles are
    a property of the process, so one counter serves every caller; callers
    read differences."""

    def __init__(self):
        self.lock = threading.Lock()
        self.count = 0
        self.seconds = 0.0
        self.installed = False

    def on_duration(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            with self.lock:
                self.count += 1
                self.seconds += duration

    def install(self) -> None:
        with self.lock:
            if not self.installed:
                jax.monitoring.register_event_duration_secs_listener(
                    self.on_duration)
                self.installed = True


_COMPILES = _Compiles()


def compiles() -> tuple[int, float]:
    """``(executables built, their seconds)`` since the first call of this
    function in the process."""
    if not _COMPILES.installed:
        _COMPILES.install()
    return _COMPILES.count, _COMPILES.seconds
