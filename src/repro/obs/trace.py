"""Structured span tracing for the QUIP serving stack (docs/observability.md).

One :class:`Tracer` per :class:`~repro.service.server.QuipService` records a
per-query span tree — submit → admission → scheduler checkout/checkin →
morsel step → operator → impute flush → kernel dispatch — and exports it as
Chrome trace-event JSON (loadable in ``chrome://tracing`` / Perfetto).

Design constraints, in order:

* **Zero-allocation no-op mode.**  A disabled tracer must be free on the
  morsel hot path.  ``Tracer.span(...)`` returns the shared
  :data:`NULL_SPAN` singleton when disabled, and every hot call site
  additionally guards with ``if tracer.enabled`` so the keyword-argument
  dict is never even built.  The overhead gate in ``benchmarks/exp13_obs.py``
  asserts this contract.
* **Deterministic structure.**  ``clock="unit"`` replaces ``perf_counter``
  with a lock-guarded monotone tick, so CI asserts on span *counts and
  nesting* (:meth:`span_counts`, :meth:`span_tree`), never on wall time.
* **Thread safety.**  Spans nest through a thread-local parent stack
  (worker threads each get their own); the record list and the unit tick
  are guarded by one lock.  Cross-thread spans (a query's submit→finalize
  lifetime) use the explicit :meth:`begin`/:meth:`end` pair, which does not
  touch any thread's stack.

Per-query attribution: a span created with ``ticket=`` stamps it; nested
spans without one inherit the nearest enclosing span's ticket on the same
thread.  ``chrome_trace(ticket=...)`` exports one query's tree.

Profiler mirror: an enabled tracer's thread-local spans also enter a
``jax.profiler.TraceAnnotation`` named ``quip:<span name>``, so that a JAX
profile holds the program's spans on the device events' clock.  Cross-thread
:meth:`Tracer.begin`/:meth:`Tracer.end` spans and instants are not mirrored:
a profiler annotation opens and closes on one thread.

Compiles: a wall-clock tracer records every XLA compile the process makes
while it is alive as a ``jax:compile`` span (``fun_name``, ``secs``, and
``cache_load`` when the executable came from the persistent compile cache),
nested under the span open on the compiling thread.  The unit clock leaves
them out: what compiles depends on the process's caches, not on the query.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import Counter
from typing import Dict, List, Optional

from jax import monitoring
from jax.profiler import TraceAnnotation

from repro.analysis.lockcheck import make_lock
from repro.core.env import env_choice, env_flag

__all__ = [
    "NULL_SPAN",
    "NULL_TRACER",
    "PROFILER_PREFIX",
    "Span",
    "Tracer",
    "TRACE_CLOCKS",
    "resolve_tracer",
]

TRACE_CLOCKS = ("wall", "unit")

#: prefix of the program's spans in a JAX profile
PROFILER_PREFIX = "quip:"


class _NullSpan:
    """The shared no-op span: context manager + ``set``/``add`` sink.

    A singleton (:data:`NULL_SPAN`) so the disabled path allocates
    nothing — every ``with tracer.span(...)`` site reuses this object."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> "_NullSpan":
        return self

    def add(self, **counts) -> "_NullSpan":
        return self


NULL_SPAN = _NullSpan()


class Span:
    """One recorded event: a completed span (``ph="X"``) or an instant
    (``ph="i"``).  ``t0``/``t1`` are seconds under the wall clock and bare
    ticks under the unit clock."""

    __slots__ = ("span_id", "parent_id", "name", "cat", "ticket",
                 "thread", "t0", "t1", "args", "ph")

    def __init__(self, span_id: int, parent_id: Optional[int], name: str,
                 cat: str, ticket: Optional[int], thread: str,
                 t0: float, args: Dict[str, object], ph: str = "X"):
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.cat = cat
        self.ticket = ticket
        self.thread = thread
        self.t0 = t0
        self.t1: Optional[float] = None
        self.args = args
        self.ph = ph


class _LiveSpan:
    """Context-manager handle for one open span on the current thread,
    mirrored into the JAX profiler while it is open."""

    __slots__ = ("_tracer", "_span", "_mirror")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self._span = span
        self._mirror = TraceAnnotation(PROFILER_PREFIX + span.name)

    def set(self, **attrs) -> "_LiveSpan":
        self._span.args.update(attrs)
        return self

    def add(self, **counts) -> "_LiveSpan":
        """Add to numeric attributes, from 0 where unset."""
        args = self._span.args
        for key, n in counts.items():
            args[key] = args.get(key, 0) + n
        return self

    def __enter__(self) -> "_LiveSpan":
        self._mirror.__enter__()
        self._tracer._push(self._span)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            self._span.args.setdefault("error", exc_type.__name__)
        self._tracer._pop(self._span)
        self._mirror.__exit__(exc_type, exc, tb)
        return False


# --------------------------------------------------------------------------- #
# compile events (jax.monitoring), fanned out to the live wall-clock tracers
# --------------------------------------------------------------------------- #
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_compile_lock = make_lock("trace._compile_lock")
_compile_watchers: "weakref.WeakSet[Tracer]" = weakref.WeakSet()  # guarded-by: _compile_lock
_compile_listening = False  # guarded-by: _compile_lock
_compile_tls = threading.local()


def _on_event(event: str, **_kw) -> None:
    # JAX reports a persistent-cache hit on the compiling thread just before
    # the compile event that wraps it
    if event == _CACHE_HIT_EVENT:
        _compile_tls.cache_load = True


def _on_duration(event: str, secs: float, **kw) -> None:
    if event != _COMPILE_EVENT:
        return
    cache_load = getattr(_compile_tls, "cache_load", False)
    _compile_tls.cache_load = False
    with _compile_lock:
        watchers = list(_compile_watchers)
    for tracer in watchers:
        tracer._record_compile(str(kw.get("fun_name", "")), secs, cache_load)


def _watch_compiles(tracer: "Tracer") -> None:
    global _compile_listening
    with _compile_lock:
        if not _compile_listening:
            monitoring.register_event_listener(_on_event)
            monitoring.register_event_duration_secs_listener(_on_duration)
            _compile_listening = True
        _compile_watchers.add(tracer)


class Tracer:
    """Thread-safe span recorder with a wall or deterministic unit clock.

    ``enabled=False`` (the default of :func:`resolve_tracer` without
    ``QUIP_TRACE``) makes every recording call a no-op returning
    :data:`NULL_SPAN`.  Under the wall clock it also records the
    process's XLA compiles as ``jax:compile`` spans."""

    def __init__(self, enabled: bool = True, clock: str = "wall"):
        if clock not in TRACE_CLOCKS:
            raise ValueError(f"unknown trace clock {clock!r}; "
                             f"expected one of {TRACE_CLOCKS}")
        self.enabled = bool(enabled)
        self.clock = clock
        self._lock = make_lock("Tracer._lock")
        self._records: List[Span] = []  # guarded-by: _lock
        self._open: Dict[int, Span] = {}  # guarded-by: _lock
        self._next_id = 0  # guarded-by: _lock
        self._tick = 0  # guarded-by: _lock
        self._origin = time.perf_counter()
        self._tls = threading.local()
        if self.enabled and clock == "wall":
            _watch_compiles(self)

    # -- clock / ids ------------------------------------------------------#
    def now(self) -> float:
        if self.clock == "unit":
            with self._lock:
                self._tick += 1
                return float(self._tick)
        return time.perf_counter() - self._origin

    def _new_id(self) -> int:
        with self._lock:
            self._next_id += 1
            return self._next_id

    # -- thread-local span stack ------------------------------------------#
    def _stack(self) -> List[Span]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def _push(self, span: Span) -> None:
        self._stack().append(span)

    def _pop(self, span: Span) -> None:
        span.t1 = self.now()
        stack = self._stack()
        if stack and stack[-1] is span:
            stack.pop()
        with self._lock:
            self._records.append(span)

    def _parent(self) -> Optional[Span]:
        stack = self._stack()
        return stack[-1] if stack else None

    # -- recording API ----------------------------------------------------#
    def span(self, name: str, cat: str = "exec",
             ticket: Optional[int] = None,
             parent: Optional[int] = None, **args):
        """Open a nested span on this thread; use as a context manager.
        Disabled tracers return :data:`NULL_SPAN` (shared, allocation-free).
        ``parent`` overrides the thread-local nesting (e.g. to hang morsel
        steps under a cross-thread :meth:`begin` query span)."""
        if not self.enabled:
            return NULL_SPAN
        top = self._parent()
        if parent is None and top is not None:
            parent = top.span_id
        if ticket is None and top is not None:
            ticket = top.ticket
        return _LiveSpan(self, Span(
            self._new_id(), parent, name, cat, ticket,
            threading.current_thread().name, self.now(), args,
        ))

    def instant(self, name: str, cat: str = "event",
                ticket: Optional[int] = None,
                parent: Optional[int] = None, **args) -> None:
        """Record a zero-duration event (scheduler checkout/checkin,
        admission...).  ``parent`` hangs the event under a cross-thread
        :meth:`begin` span — the scheduler passes the query span so its
        instants join the ticket's tree instead of floating as roots."""
        if not self.enabled:
            return
        top = self._parent()
        if parent is None and top is not None:
            parent = top.span_id
        if ticket is None and top is not None:
            ticket = top.ticket
        span = Span(self._new_id(), parent, name, cat, ticket,
                    threading.current_thread().name, self.now(), args,
                    ph="i")
        span.t1 = span.t0
        with self._lock:
            self._records.append(span)

    def _record_compile(self, fun_name: str, secs: float,
                        cache_load: bool) -> None:
        """One compile that just ended on this thread: a complete span of
        its ``secs``, under the span open here (if any)."""
        top = self._parent()
        t1 = self.now()
        span = Span(self._new_id(), top.span_id if top else None,
                    "jax:compile", "compile", top.ticket if top else None,
                    threading.current_thread().name, t1 - secs,
                    {"fun_name": fun_name, "secs": secs,
                     "cache_load": cache_load})
        span.t1 = t1
        with self._lock:
            self._records.append(span)

    def begin(self, name: str, cat: str = "query",
              ticket: Optional[int] = None, **args) -> Optional[int]:
        """Open a cross-thread span (no thread-local nesting); returns its
        span id for :meth:`end`.  None when disabled."""
        if not self.enabled:
            return None
        span = Span(self._new_id(), None, name, cat, ticket,
                    threading.current_thread().name, self.now(), args)
        with self._lock:
            self._open[span.span_id] = span
        return span.span_id

    def end(self, span_id: Optional[int], **args) -> None:
        """Close a :meth:`begin` span (id None — disabled begin — is a
        no-op)."""
        if not self.enabled or span_id is None:
            return
        with self._lock:
            span = self._open.pop(span_id, None)
        if span is None:
            return
        span.args.update(args)
        span.t1 = self.now()
        with self._lock:
            self._records.append(span)

    def clear(self) -> None:
        with self._lock:
            self._records = []
            self._open = {}
            self._tick = 0
            self._next_id = 0
        self._origin = time.perf_counter()

    # -- introspection ----------------------------------------------------#
    def spans(self, ticket: Optional[int] = None,
              name: Optional[str] = None) -> List[Span]:
        """Recorded spans, oldest first, optionally filtered by ticket
        and/or name."""
        with self._lock:
            records = list(self._records)
        records.sort(key=lambda s: (s.t0, s.span_id))
        if ticket is not None:
            records = [s for s in records if s.ticket == ticket]
        if name is not None:
            records = [s for s in records if s.name == name]
        return records

    def span_counts(self, ticket: Optional[int] = None) -> Dict[str, int]:
        """``{span name: count}`` — the structural fingerprint CI asserts
        on under the unit clock (no wall time anywhere)."""
        return dict(Counter(s.name for s in self.spans(ticket)))

    def span_tree(self, ticket: Optional[int] = None) -> List[Dict]:
        """Nested ``{"name", "children"}`` forest ordered by start time —
        deterministic under ``clock="unit"`` with a serial scheduler."""
        records = self.spans(ticket)
        ids = {s.span_id for s in records}
        nodes = {s.span_id: {"name": s.name, "children": []} for s in records}
        roots: List[Dict] = []
        for s in records:
            node = nodes[s.span_id]
            if s.parent_id in ids:
                nodes[s.parent_id]["children"].append(node)
            else:
                roots.append(node)
        return roots

    # -- Chrome trace-event export ----------------------------------------#
    def chrome_trace(self, ticket: Optional[int] = None) -> Dict:
        """The whole service's (or one ticket's) trace as a Chrome
        trace-event JSON document: ``ph="X"`` complete events with µs
        timestamps, pid = ticket (0 for service-level spans), tid = a
        stable per-thread integer, plus ``ph="M"`` metadata naming every
        process and thread.  Unit-clock ticks export as 1 µs each."""
        records = self.spans(ticket)
        threads = {name: i + 1 for i, name in enumerate(
            sorted({s.thread for s in records})
        )}
        scale = 1.0 if self.clock == "unit" else 1e6  # → microseconds
        events: List[Dict] = []
        pids = sorted({s.ticket or 0 for s in records})
        for pid in pids:
            events.append({
                "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                "args": {"name": f"ticket {pid}" if pid else "service"},
            })
        for name, tid in threads.items():
            for pid in pids:
                events.append({
                    "name": "thread_name", "ph": "M", "pid": pid,
                    "tid": tid, "args": {"name": name},
                })
        for s in records:
            ev = {
                "name": s.name,
                "cat": s.cat,
                "ph": s.ph,
                "ts": s.t0 * scale,
                "pid": s.ticket or 0,
                "tid": threads[s.thread],
                "args": dict(s.args),
            }
            if s.ph == "X":
                ev["dur"] = max(((s.t1 or s.t0) - s.t0) * scale, 0.0)
            else:
                ev["s"] = "t"  # instant scope: thread
            events.append(ev)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "metadata": {"clock": self.clock, "tracer": "quip-obs"},
        }


#: the shared disabled tracer — the default wiring when observability is
#: off, so layers can hold a tracer unconditionally (no None checks)
NULL_TRACER = Tracer(enabled=False)


def resolve_tracer(tracer=None) -> Tracer:
    """Explicit :class:`Tracer` > bool > ``QUIP_TRACE`` env (truthy/falsy
    via :func:`env_flag`, garbage raises) > off.  The clock comes from
    ``QUIP_TRACE_CLOCK`` (``wall`` | ``unit``, via :func:`env_choice`)
    unless an explicit Tracer is handed in."""
    if isinstance(tracer, Tracer):
        return tracer
    clock = env_choice("QUIP_TRACE_CLOCK", TRACE_CLOCKS, "wall")
    if tracer is None:
        enabled = env_flag("QUIP_TRACE", False)
    else:
        enabled = bool(tracer)
    if not enabled:
        return NULL_TRACER
    return Tracer(enabled=True, clock=clock)
