"""Span tracing: a thread-safe, ~zero-cost-when-disabled trace API.

A :class:`Span` is a named ``[t_start, t_end]`` interval with attributes
and children; a finished request carries one closed span *tree* (root
``request`` with ``queue``/``plan``/``exec``(→``unit``→``realize``/
``dispatch``(→ backend stages ``pack``/``h2d``/``device``/``d2h``/
``host_finalize``))/``finalize`` stages — see DESIGN.md §15).

Design points:

* **Disabled is the default and near-free.**  ``tracer.span(...)`` on a
  disabled tracer returns a shared no-op singleton — no allocation, no
  clock read, no lock — so instrumentation can stay inline on hot paths.
* **Parenting is thread-local.**  ``with tracer.span("unit"):`` pushes
  onto the calling thread's stack, so nested instrumentation (session
  inside service executor, cache inside session) composes without
  plumbing span handles through every signature.
* **Cross-thread trees are explicit.**  Request roots are created with
  :meth:`Tracer.start_span` (unparented, not auto-emitted), carried on
  the request object across the submit → admission → executor thread
  hops, and stitched via :meth:`Span.adopt` / :meth:`Span.child` with
  explicit timestamps so adjacent stages share boundary instants and the
  stage sum equals the root duration exactly.
* **Timestamps** come from :mod:`repro.obs.clock` (one monotonic clock
  for deadlines, waits, and spans alike).
* **Profiler timeline.** With :func:`repro.obs.enable_jax_annotations`
  on, an entered span also enters a ``jax.profiler.TraceAnnotation`` of
  its name and attributes (:mod:`repro.obs.profiling`).
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

from repro.obs import clock as _clock
from repro.obs import profiling as _profiling


class Span:
    __slots__ = ("name", "attrs", "t_start", "t_end", "children",
                 "_parent", "_tracer", "_emit", "_annotation")

    def __init__(self, name: str, attrs: Optional[Dict[str, Any]] = None,
                 t_start: Optional[float] = None, tracer=None,
                 parent: Optional["Span"] = None, emit: bool = True):
        self.name = name
        self.attrs: Dict[str, Any] = dict(attrs) if attrs else {}
        self.t_start = _clock.now() if t_start is None else t_start
        self.t_end: Optional[float] = None
        self.children: List[Span] = []
        self._parent = parent
        self._tracer = tracer
        self._emit = emit
        self._annotation = None

    # -- lifecycle -----------------------------------------------------
    def __enter__(self) -> "Span":
        if self._tracer is not None:
            self._tracer._stack().append(self)
        if _profiling.jax_annotations_enabled():
            self._annotation = _profiling.annotation(self.name, self.attrs)
            self._annotation.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        if self._tracer is not None:
            stack = self._tracer._stack()
            if stack and stack[-1] is self:
                stack.pop()
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        self.end()
        if self._parent is None and self._emit and self._tracer is not None:
            self._tracer.finish(self)
        return False

    def end(self, t: Optional[float] = None) -> None:
        if self.t_end is None:
            self.t_end = _clock.now() if t is None else t

    # -- tree building -------------------------------------------------
    def child(self, name: str, *, t: Optional[float] = None,
              **attrs) -> "Span":
        """Manually-ended child (not pushed on any thread stack)."""
        s = Span(name, attrs, t_start=t, tracer=self._tracer, parent=self)
        self.children.append(s)
        return s

    def adopt(self, span: "Span") -> None:
        """Attach an independently-built span (e.g. the shared per-unit
        ``exec`` subtree) as a child of this tree."""
        self.children.append(span)

    # -- inspection ----------------------------------------------------
    @property
    def duration_ms(self) -> float:
        end = self.t_end if self.t_end is not None else _clock.now()
        return (end - self.t_start) * 1e3

    @property
    def closed(self) -> bool:
        return (self.t_end is not None
                and all(c.closed for c in self.children))

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()

    def find(self, name: str) -> Optional["Span"]:
        for s in self.walk():
            if s.name == name:
                return s
        return None

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "t_start": self.t_start,
            "t_end": self.t_end,
            "attrs": dict(self.attrs),
            "children": [c.to_dict() for c in self.children],
        }

    def __repr__(self) -> str:
        state = f"{self.duration_ms:.3f}ms" if self.t_end is not None \
            else "open"
        return f"Span({self.name!r}, {state}, children={len(self.children)})"


class _NoopSpan:
    """Shared do-nothing span returned by a disabled tracer."""

    __slots__ = ()
    name = "noop"
    t_start = 0.0
    t_end = 0.0
    children: List[Span] = []

    @property
    def attrs(self) -> Dict[str, Any]:
        return {}  # fresh throwaway so attr writes never accumulate

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def end(self, t=None) -> None:
        pass

    def child(self, name, *, t=None, **attrs):
        return self

    def adopt(self, span) -> None:
        pass

    @property
    def duration_ms(self) -> float:
        return 0.0

    @property
    def closed(self) -> bool:
        return True


NOOP_SPAN = _NoopSpan()


class Tracer:
    """Global span factory + sink dispatcher (see module docstring)."""

    def __init__(self):
        self._enabled = False
        self._sink = None
        self._tls = threading.local()
        self._lock = threading.Lock()
        self.n_finished = 0
        self.n_dropped = 0

    @property
    def enabled(self) -> bool:
        return self._enabled

    @property
    def sink(self):
        return self._sink

    def enable(self, sink=None) -> None:
        self._sink = sink
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False
        self._sink = None

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def span(self, name: str, *, emit: bool = True, **attrs):
        """Context-managed span parented on the calling thread's stack.
        Roots (no parent) are emitted to the sink on exit unless
        ``emit=False`` (used for subtrees adopted into request roots)."""
        if not self._enabled:
            return NOOP_SPAN
        stack = self._stack()
        parent = stack[-1] if stack else None
        s = Span(name, attrs, tracer=self, parent=parent, emit=emit)
        if parent is not None:
            parent.children.append(s)
        return s

    def start_span(self, name: str, *, t: Optional[float] = None,
                   **attrs) -> Optional[Span]:
        """Unparented manual span (request roots, plan spans).  Caller
        ends it and calls :meth:`finish`; returns None when disabled."""
        if not self._enabled:
            return None
        return Span(name, attrs, t_start=t, tracer=self, parent=None,
                    emit=False)

    def attach(self, name: str, t_start: float, t_end: float,
               **attrs) -> None:
        """Add a closed ``[t_start, t_end]`` child to the calling thread's
        innermost open span (no-op when disabled or none is open): an
        interval measured by someone else, such as an XLA compile."""
        if not self._enabled:
            return
        stack = self._stack()
        if stack:
            stack[-1].child(name, t=t_start, **attrs).end(t_end)

    def event(self, name: str, **attrs) -> None:
        """Point event (autotune decision, router refit) → sink."""
        if not self._enabled or self._sink is None:
            return
        try:
            self._sink.write_event(
                {"type": "event", "name": name, "t": _clock.now(),
                 "attrs": attrs})
        except Exception:
            with self._lock:
                self.n_dropped += 1

    def finish(self, root: Span) -> None:
        """Emit a finished root tree to the sink.  Sink failures are
        counted and dropped — telemetry must never take down serving."""
        with self._lock:
            self.n_finished += 1
        if self._sink is None:
            return
        try:
            self._sink.write_span(root)
        except Exception:
            with self._lock:
                self.n_dropped += 1


tracer = Tracer()


def get_tracer() -> Tracer:
    return tracer


def span(name: str, **attrs):
    """Module-level convenience: ``with obs.span("realize", n_pad=...):``"""
    return tracer.span(name, **attrs)


def event(name: str, **attrs) -> None:
    tracer.event(name, **attrs)
