"""Host-side span timers and `jax.profiler` trace hooks.

`SpanLog` times named host-side phases (pack/dispatch/apply/encode/
round/flush) and emits them as schema ``span`` records; a span opened
from the virtual-time scheduler carries the scheduler's clock in
``virtual_s``, correlating host wall-time with simulated time.  Every
span also enters a `jax.profiler.TraceAnnotation`, so when an opt-in
trace is active (``--profile-dir``) the same phases appear as
annotated regions in the profiler timeline — one instrumentation
point, two views.

`profile_trace` is the opt-in trace context: a no-op unless a
directory is given, and degrades to a warning (never a crash) when the
installed jax cannot start a trace on this backend.

`phase` names one phase of the federated round on the device: a
``jax.named_scope("fed.<phase>")`` that the compiled round carries in
each instruction's ``op_name`` metadata, so a device trace groups the
round's time by phase (`PHASES`).  Metadata only: the compiled
program is the same with or without it.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import List, Optional

import jax


#: the round's device phases, each the scope ``fed.<name>`` (`phase`):
#: local forward/backward, the GNB curvature estimate, the Sophia
#: update, the comm streams' encode/decode, the server's combine, and
#: the gather/scatter of client rows in the resident stacks
PHASES = ("grad", "gnb", "sophia", "wire", "combine", "rows")


def phase(name: str):
    """The named device scope of one phase of the round (context
    manager); one ``fed.`` phase never encloses another."""
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r} (want one of {PHASES})")
    return jax.named_scope("fed." + name)


def annotate(name: str):
    """Profiler annotation for a host-side region (context manager);
    active only while a trace is being captured, ~free otherwise."""
    return jax.profiler.TraceAnnotation(name)


class SpanLog:
    """Collects ``span`` records; wall-clock zero is construction."""

    def __init__(self):
        self._t0 = time.perf_counter()
        self._spans: List[dict] = []

    @contextmanager
    def span(self, name: str, virtual_s: Optional[float] = None,
             trace_id: Optional[int] = None):
        start = time.perf_counter()
        try:
            with annotate(name):
                yield
        finally:
            rec = {"record": "span", "name": name,
                   "t_wall_s": start - self._t0,
                   "wall_s": time.perf_counter() - start}
            if virtual_s is not None:
                rec["virtual_s"] = float(virtual_s)
            if trace_id is not None:
                rec["trace_id"] = int(trace_id)
            self._spans.append(rec)

    def records(self) -> List[dict]:
        return list(self._spans)


class profile_trace:
    """``with profile_trace(dir):`` captures a `jax.profiler` trace
    into ``dir`` (view with TensorBoard / Perfetto); a no-op when
    ``dir`` is empty."""

    def __init__(self, directory: str):
        self.directory = directory
        self._active = False

    def __enter__(self):
        if self.directory:
            try:
                jax.profiler.start_trace(self.directory)
                self._active = True
            except Exception as e:      # backend without profiler support
                print(f"profiler trace unavailable ({e}); "
                      f"continuing without", flush=True)
        return self

    def __exit__(self, *exc):
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            print(f"wrote profiler trace to {self.directory}",
                  flush=True)
        return False
