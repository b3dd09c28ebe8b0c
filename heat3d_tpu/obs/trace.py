"""Tracing on the profiler's clock: host spans and device-side named scopes.

A jax.profiler trace of the solver shows raw XLA op names (fusion.123,
dynamic-update-slice.7) — useless for answering "how much of the step is
halo exchange vs stencil compute vs shell patches", and its host lines
show nothing of what the program was doing between dispatches. Three
pieces answer that:

- **Host spans** (:func:`open_span` / :func:`close_span`, driven by the
  one span API ``obs.get().span(name, **fields)`` in ``obs/ledger.py``).
  While a span is open, a ``jax.profiler.TraceAnnotation("heat3d.<name>")``
  marks it on the host timeline of any trace being captured; at close,
  one :class:`Span` record joins :data:`RING`, a bounded in-process ring
  that is always on (:func:`spans` reads it). The ring's ``start_ns`` and
  ``end_ns`` come from ``time.time_ns()``: the wall clock the profiler
  places its events on (a trace event's start is its offset from the
  ``profile_start_time`` stat of the trace's ``Task Environment`` plane),
  so a ring record and its annotation agree to microseconds and a reader
  can put a device gap inside a program span. Nothing here syncs the
  device or reads a device value.
- **Compile attribution.** One ``jax.monitoring`` duration listener,
  registered once, adds every ``/jax/core/compile/*`` duration (tracing,
  lowering, compiling) to the innermost open span on the calling thread
  as that span's ``compile_s`` field: a compile inside a timed window
  shows up in the span of the program that compiled.
- :func:`named_phase` — ``jax.named_scope`` under the ``heat3d.`` prefix,
  used INSIDE traced code (parallel/step.py, parallel/halo.py): the scope
  name lands in every emitted op's metadata, so profiler tools (and
  ``scripts/summarize_trace.py``'s phase table) can group device time by
  phase instead of by op. Zero runtime cost — it only renames ops at trace
  time.

Phase names used by the step builders (the contract the
``obs/perf/timeline.py`` phase tables and the profile→roofline join
group by — canonical list: ``parallel.step.PHASES``; keep in sync with
docs/OBSERVABILITY.md):

- ``heat3d.step`` (the whole step/superstep program — dispatch glue
  attributes here instead of ``(unattributed)``)
- ``heat3d.halo_exchange`` (and ``heat3d.halo.<axis>`` per axis)
- ``heat3d.stencil``
- ``heat3d.shell`` (nested in ``heat3d.stencil``: the faces-direct
  routes' shard-boundary shell patches — slab assembly,
  tap applications, pins and the update slices; the bulk kernel stays
  ``heat3d.stencil.<family>``)
- ``heat3d.residual``

Everything imports jax lazily, and only once the process has imported it
(no span can meet a compile or a trace before then), so the obs package
stays importable anywhere.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import sys
import threading
import time
from typing import Any, Dict, List, Optional

PHASE_PREFIX = "heat3d."
RING_SIZE = 4096
COMPILE_EVENTS = "/jax/core/compile/"


class Span:
    """One span: its name, bounds on the profiler's clock (``start_ns``,
    ``end_ns`` from ``time.time_ns()``; ``end_ns`` None while open), the
    ``id`` of the span open around it on the same thread (``parent``), its
    ``fields`` and ``status`` (``open`` | ``ok`` | ``error``).
    ``add(**fields)`` attaches fields; ``dur_s`` (``time.monotonic``) is
    readable after the span exits (callers feed it to the metrics
    registry)."""

    __slots__ = ("id", "name", "start_ns", "end_ns", "parent", "fields",
                 "status", "dur_s", "_annotation")

    def __init__(self, id: int, name: str, parent: Optional[int],
                 fields: Dict[str, Any]):
        self.id = id
        self.name = name
        self.parent = parent
        self.fields = fields
        self.status = "open"
        self.dur_s: Optional[float] = None
        self.end_ns: Optional[int] = None
        self._annotation = None
        self.start_ns = time.time_ns()

    def add(self, **fields: Any) -> None:
        self.fields.update(fields)

    def record(self) -> Dict[str, Any]:
        return {"id": self.id, "name": self.name, "start_ns": self.start_ns,
                "end_ns": self.end_ns, "parent": self.parent,
                "status": self.status, "fields": dict(self.fields)}


class SpanRing:
    """The last ``size`` closed spans of this process, oldest first, and
    the spans each thread has open. Bounded: an old record drops out when
    a new one closes past the size."""

    def __init__(self, size: int = RING_SIZE):
        self._closed: "collections.deque[Span]" = collections.deque(
            maxlen=size
        )
        self._local = threading.local()
        self._ids = itertools.count()

    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def open(self, name: str, fields: Dict[str, Any]) -> Span:
        stack = self._stack()
        span = Span(next(self._ids), name,
                    stack[-1].id if stack else None, dict(fields))
        stack.append(span)
        return span

    def close(self, span: Span, ok: bool) -> None:
        span.end_ns = time.time_ns()
        span.status = "ok" if ok else "error"
        stack = self._stack()
        if span in stack:
            stack.remove(span)
        self._closed.append(span)

    def innermost(self) -> Optional[Span]:
        """The span most recently opened, and still open, on this thread."""
        stack = self._stack()
        return stack[-1] if stack else None

    def records(self) -> List[Dict[str, Any]]:
        return [s.record() for s in list(self._closed)]


RING = SpanRing()
_annotation_cls: Any = None  # TraceAnnotation once jax is imported
_listening = False


def _on_duration(event: str, duration: float, **_: Any) -> None:
    if event.startswith(COMPILE_EVENTS):
        span = RING.innermost()
        if span is not None:
            span.fields["compile_s"] = span.fields.get("compile_s", 0.0) \
                + duration


def _attach_jax() -> None:
    """Once the process has imported jax: register the compile listener
    and resolve ``TraceAnnotation``."""
    global _annotation_cls, _listening
    if _listening or "jax" not in sys.modules:
        return
    _listening = True
    try:
        import jax.monitoring
        from jax.profiler import TraceAnnotation
    except (ImportError, AttributeError):
        return
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _annotation_cls = TraceAnnotation


def open_span(name: str, fields: Dict[str, Any]) -> Span:
    """Open span ``name`` on this thread, with its profiler annotation."""
    _attach_jax()
    span = RING.open(name, fields)
    if _annotation_cls is not None:
        span._annotation = _annotation_cls(PHASE_PREFIX + name)
        span._annotation.__enter__()
    return span


def close_span(span: Span, ok: bool) -> None:
    """Close ``span`` (opened by :func:`open_span`) into the ring."""
    RING.close(span, ok)
    if span._annotation is not None:
        span._annotation.__exit__(None, None, None)
        span._annotation = None


def spans() -> List[Dict[str, Any]]:
    """The ring's records, oldest first: ``id``, ``name``, ``start_ns``,
    ``end_ns``, ``parent``, ``status``, ``fields``."""
    return RING.records()


def named_phase(name: str):
    """``jax.named_scope('heat3d.<name>')`` — wrap traced (inside-jit)
    code so emitted ops carry the phase in their metadata."""
    if not name.startswith(PHASE_PREFIX):
        name = PHASE_PREFIX + name
    try:
        import jax

        return jax.named_scope(name)
    except (ImportError, AttributeError):
        return contextlib.nullcontext()


def scoped(name: str, fn):
    """``fn`` wrapped in :func:`named_phase` — for decorating a built step
    callable without restructuring it."""

    def wrapper(*args, **kwargs):
        with named_phase(name):
            return fn(*args, **kwargs)

    return wrapper
