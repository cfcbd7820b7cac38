"""Host spans of one serving round.

``span(name, totals, **meta)`` times a stretch of host code.  Whenever a
``jax.profiler`` trace is running it also enters a ``TraceAnnotation``, so
the span lands in the profiler's trace on the same clock as the device
ops; when none runs it only reads the host clock.  There is no switch:
spans are in the trace exactly when a trace is taken.  The measured
seconds stay on the span (``.s``) and, given a ``totals`` dict (the
executor's window counters), are added to ``totals[name]``.

The spans of a round, nested as they run:

  serve.step            ServingEngine.step: one scheduler round
    serve.advance       chunked-prefill segments
    serve.join          admission: plan, bucket, page gate and eviction
    serve.decode_round  one decode step over the decoding slots
      (inside join and decode rounds)
      serve.prefill / serve.resume / serve.decode   one program, ``rids``
        serve.stage        host index building and staging, up to dispatch
        serve.device_wait  the program's block_until_ready
      serve.select      the select readback to the host
      serve.retire      token bookkeeping and retirement
      serve.store       prefix-store offers
      serve.free        free_slots / page release
"""

from __future__ import annotations

import time
from typing import Dict, Optional

from jax.profiler import TraceAnnotation


class span:
    """Context manager: one named host span (see the module docstring)."""

    __slots__ = ("name", "meta", "totals", "s", "_t0", "_ann")

    def __init__(self, name: str, totals: Optional[Dict[str, float]] = None,
                 **meta):
        self.name = name
        self.meta = meta
        self.totals = totals
        self.s = 0.0

    def __enter__(self) -> "span":
        self._ann = None
        if TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(self.name, **self.meta)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.s = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self.totals is not None:
            self.totals[self.name] = self.totals.get(self.name, 0.0) + self.s
        return False


def rids(requests) -> str:
    """The ``rids`` metadata of a program span: its group's request ids."""
    return " ".join(str(r.rid) for r in requests)
