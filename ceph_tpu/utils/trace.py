"""Span tracing + historic-op ring — the ZTracer/OpTracker analog.

The reference threads ``ZTracer::Trace`` handles through the EC
pipeline signatures (osd/ECBackend.h:70-94) and keeps an in-memory
history of completed ops served as ``dump_historic_ops``
(common/TrackedOp). Here: a context-manager ``span`` records name,
parent, wall duration, and tags into a bounded ring; nesting is
tracked per-thread so pipeline code never passes handles explicitly.

On TPU the same spans also emit ``jax.profiler.TraceAnnotation``
blocks when profiling is active, so host-side pipeline stages line up
with device timelines in XLA profile captures.

A stage is timed ONCE: ``span(name, perf=, key=)`` also adds its
duration to a ``TIME`` perf counter (what the benchmark's per-layer
metrics read), and ``record`` does the same for an interval that
starts on one thread and ends on another (queue wait, sub-op wait).
At most once a second a span's exit leaves a zero-length
``clock_anchor`` annotation carrying ``perf_counter_ns``: any profiler
trace then holds the offset between its own clock and
``Span.start_mono``, so recorded intervals and spans already open when
the trace started can be laid on the device timeline afterwards
(``tools/trace_tool.py --xplane``).
"""

from __future__ import annotations

import itertools
import os
import secrets
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field

#: process-unique prefix so trace ids stay distinct across OS
#: processes (the blkin trace-id role)
_TRACE_PREFIX = f"{os.getpid():x}-{secrets.token_hex(2)}"

#: jax.profiler.TraceAnnotation, resolved ONCE on first span instead
#: of an import+try/except per span (the round-14 hot-path fix: the
#: per-span import dominated small-op span cost). Lazy rather than
#: import-time so ``import ceph_tpu`` stays jax-free for the
#: multichip dryrun (the admin-socket builtin-registration contract).
#: Sentinel False = unresolved; None = resolved-absent.
_ANNOTATION_CLS: "object" = False

#: name of the zero-length annotation that ties the profiler's clock
#: to ``time.perf_counter`` (its ``mono_ns`` stat), and the least
#: seconds between two of them
ANCHOR_NAME = "clock_anchor"
ANCHOR_INTERVAL_S = 1.0


def _annotation_cls():
    global _ANNOTATION_CLS
    if _ANNOTATION_CLS is False:
        try:
            import jax.profiler

            _ANNOTATION_CLS = jax.profiler.TraceAnnotation
        except Exception:
            _ANNOTATION_CLS = None
    return _ANNOTATION_CLS


@dataclass
class Span:
    #: globally unique (process-prefixed) — parent links survive
    #: merging dump_historic output across OS processes, where
    #: bare per-process counters would collide
    span_id: str
    parent_id: str | None
    name: str
    start: float
    duration: float | None = None
    tags: dict = field(default_factory=dict)
    #: one id per END-TO-END operation, carried across the wire
    #: (client op -> primary -> replica sub-ops all share it)
    trace_id: str | None = None
    #: monotonic clock at span open, taken at the SAME instant as the
    #: wall-clock ``start``: trace assembly orders spans and computes
    #: intervals on (start_mono, start_mono + duration) within a
    #: process — mixing wall starts with perf_counter durations made
    #: cross-thread ordering wobble by the wall clock's granularity
    start_mono: float | None = None

    def as_dict(self) -> dict:
        return {
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start": self.start,
            "start_mono": self.start_mono,
            "duration": self.duration,
            "tags": self.tags,
            "trace_id": self.trace_id,
        }


class Tracer:
    #: spans kept: a 4 MiB EC(8,4) write leaves about 40 (stages,
    #: codec steps, k+m sub-writes), so this holds the last ~100 ops
    def __init__(self, history: int = 4096, enabled: bool = True) -> None:
        self.enabled = enabled
        self._ids = itertools.count(1)
        self._history: deque[Span] = deque(maxlen=history)
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._last_anchor = float("-inf")

    def _stack(self) -> list[Span]:
        if not hasattr(self._tls, "stack"):
            self._tls.stack = []
        return self._tls.stack

    @contextmanager
    def span(self, name: str, perf=None, key: str | None = None, **tags):
        """Time a stage on this thread. With ``perf`` (a PerfCounters
        set) the same duration is added to its ``TIME`` counter
        ``key``, whether or not the tracer keeps spans."""
        if not self.enabled:
            if perf is None:
                yield None
                return
            t0 = time.perf_counter()
            try:
                yield None
            finally:
                perf.tinc(key, time.perf_counter() - t0)
            return
        stack = self._stack()
        parent = stack[-1].span_id if stack else None
        trace_id = (
            stack[-1].trace_id
            if stack
            else f"{_TRACE_PREFIX}-{next(self._ids)}"
        )
        t0 = time.perf_counter()
        sp = Span(
            f"{_TRACE_PREFIX}-{next(self._ids)}", parent, name,
            time.time(), tags=tags, trace_id=trace_id, start_mono=t0,
        )
        stack.append(sp)
        annotation = None
        cls = _annotation_cls()
        if cls is not None:
            try:
                annotation = cls(name)
                annotation.__enter__()
            except Exception:
                annotation = None
        try:
            yield sp
        finally:
            if annotation is not None:
                annotation.__exit__(None, None, None)
            end = time.perf_counter()
            sp.duration = end - t0
            stack.pop()
            if perf is not None:
                perf.tinc(key, sp.duration)
            with self._lock:
                self._history.append(sp)
                anchor = end - self._last_anchor >= ANCHOR_INTERVAL_S
                if anchor:
                    self._last_anchor = end
            if anchor and cls is not None:
                try:
                    with cls(ANCHOR_NAME, mono_ns=time.perf_counter_ns()):
                        pass
                except Exception:
                    pass

    def record(
        self, name: str, start_mono: float, end_mono: float, *,
        trace_id: str | None, parent_id: str | None,
        perf=None, key: str | None = None, **tags,
    ) -> Span | None:
        """An interval that starts on one thread and ends on another
        (queue wait, sub-op wait), given as two ``perf_counter``
        readings: a Span in the history and a ``tinc``; no annotation,
        which is bound to one thread."""
        if end_mono < start_mono:
            raise ValueError(
                f"{name}: interval ends {start_mono - end_mono:.6f} s "
                "before it starts"
            )
        duration = end_mono - start_mono
        if perf is not None:
            perf.tinc(key, duration)
        if not self.enabled:
            return None
        sp = Span(
            f"{_TRACE_PREFIX}-{next(self._ids)}", parent_id, name,
            time.time() - (time.perf_counter() - start_mono),
            duration=duration, tags=tags, trace_id=trace_id,
            start_mono=start_mono,
        )
        with self._lock:
            self._history.append(sp)
        return sp

    def current(self) -> tuple[str | None, str | None]:
        """(trace_id, span_id) of the innermost open span — what a
        sender stamps into an outgoing message."""
        stack = self._stack()
        if not stack:
            return None, None
        return stack[-1].trace_id, stack[-1].span_id

    @contextmanager
    def continue_trace(self, trace_id: str | None, parent_id: str | None):
        """Adopt a REMOTE trace context (the wire hop of
        ZTracer/blkin: the reference threads trace handles through the
        EC pipeline signatures and the sub-op messages,
        osd/ECBackend.h:70-94). Spans opened inside link to the
        sender's span and share its trace id, so one client op's
        spans correlate across the client, the primary, and every
        replica — dump_historic filtered by trace_id IS the
        distributed trace."""
        if not self.enabled or trace_id is None:
            yield
            return
        stack = self._stack()
        marker = Span(
            parent_id if parent_id is not None else "",
            None, "<remote>", time.time(), trace_id=trace_id,
        )
        stack.append(marker)
        try:
            yield
        finally:
            stack.pop()

    def dump_historic(self, limit: int | None = None) -> list[dict]:
        with self._lock:
            spans = list(self._history)
        if limit is not None:
            spans = spans[-limit:]
        return [s.as_dict() for s in spans]

    def clear(self) -> None:
        """Forget the history; the next span to close leaves a clock
        anchor (a caller that clears right after starting a profiler
        trace gets one at its start)."""
        with self._lock:
            self._history.clear()
            self._last_anchor = float("-inf")


# Process-global tracer.
tracer = Tracer()
