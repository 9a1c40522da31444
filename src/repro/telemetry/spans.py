"""Host-side timing spans.

Two annotation layers label a round for the profiler:

* inside jitted code, ``jax.named_scope`` labels the round phases
  (``round.select/train/attack/compress/aggregate/account``, with
  ``ref_train`` and ``edge_codec`` nested in ``round.aggregate``) on
  both device engines; the names land in HLO ``op_name`` metadata and
  profiler traces, and cost nothing at runtime;
* on the host, :func:`span` wraps a block in
  ``jax.profiler.TraceAnnotation``. ``FLServer.run_round`` opens a
  ``round`` span on every call, and on the device engines its
  ``host.dispatch`` / ``host.fetch`` / ``host.account`` children.

Capture them with ``jax.profiler.trace(logdir)`` around any driver call
and load the dump in Perfetto (``ui.perfetto.dev``) or TensorBoard.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Any, Iterator, Optional

import jax


@contextmanager
def span(name: str, context: Optional[Any] = None, *,
         phase: Optional[str] = None,
         t: Optional[int] = None) -> Iterator[None]:
    """Run a host-side block under a profiler ``TraceAnnotation``.

    ``context`` — an optional ``schema.RunContext``: when given, a
    ``span`` event with the block's seconds is emitted on exit (even if
    the block raised, so a crashing round still records how far it
    got)."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        if context is not None:
            context.span(name, time.perf_counter() - t0, phase=phase, t=t)
