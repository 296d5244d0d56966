"""Spans inside the program, off unless a caller turns them on.

    from repro.utils import obs
    with obs.root("amp4ec.infer"):            # one request
        with obs.span("amp4ec.stage", stage=0) as sp:
            ...
            sp.set(node="edge-1")              # an attribute known only later

Off (the default), ``span`` and ``root`` return one shared, prebuilt null
context: no profiler annotation, no clock read, no record. A call site
whose attributes cost anything to compute computes them only where
``enabled()``. On (``enable()``), each span

- enters ``jax.profiler.TraceAnnotation(name, **attrs)``, so that while a
  profiler trace runs it lands on the trace's clock beside the device's
  operations;
- is kept in memory as a :class:`Span`, timed on ``time.perf_counter``.

A span's parent is the innermost span open on the same thread. Its
``root_id`` names the request it belongs to: the id of the nearest
enclosing ``root`` span, else of the outermost span. ``snapshot()``
returns the spans; nothing is written anywhere else.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import List, NamedTuple, Optional

from jax.profiler import TraceAnnotation


class Span(NamedTuple):
    name: str
    start: float            # time.perf_counter()
    end: float
    span_id: int
    parent_id: Optional[int]
    root_id: int
    attrs: dict


class _Null:
    """What ``span`` and ``root`` return while recording is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def set(self, **attrs) -> None:
        pass


NULL = _Null()


class _Open:
    """One span while it runs."""

    __slots__ = ("_rec", "_name", "_attrs", "_root", "_id", "_parent", "_root_id",
                 "_start", "_annotation")

    def __init__(self, rec: "Recorder", name: str, attrs: dict, root: bool):
        self._rec, self._name, self._attrs, self._root = rec, name, attrs, root

    def set(self, **attrs) -> None:
        """Add attributes to the span's record (the profiler's copy keeps
        those given when the span opened)."""
        self._attrs.update(attrs)

    def __enter__(self):
        stack = self._rec._stack()
        parent = stack[-1] if stack else None
        self._id = next(self._rec._ids)
        self._parent = parent._id if parent else None
        self._root_id = self._id if self._root or parent is None else parent._root_id
        stack.append(self)
        self._annotation = TraceAnnotation(self._name, **self._attrs)
        self._annotation.__enter__()
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        self._annotation.__exit__(*exc)
        self._rec._stack().pop()
        self._rec._spans.append(Span(self._name, self._start, end, self._id, self._parent,
                                     self._root_id, self._attrs))
        return None


class Recorder:
    """Spans of one process (``obs.span`` and the other module functions
    use the process's one ``Recorder``)."""

    def __init__(self):
        self._on = False
        self._spans: List[Span] = []
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count(1)

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, **attrs):
        """A context manager timing ``name``, a child of the span open
        around it on this thread."""
        if not self._on:
            return NULL
        return _Open(self, name, attrs, root=False)

    def root(self, name: str, **attrs):
        """A span that starts a request: the spans inside it take its id
        as their ``root_id``."""
        if not self._on:
            return NULL
        return _Open(self, name, attrs, root=True)

    def enabled(self) -> bool:
        return self._on

    def enable(self) -> None:
        """Start recording afresh: what was recorded before is dropped."""
        with self._lock:
            self._spans = []
            self._on = True

    def disable(self) -> None:
        """Stop recording; what was recorded stays for ``snapshot``."""
        self._on = False

    def snapshot(self) -> dict:
        """``{"spans": [Span, ...]}``, a copy."""
        with self._lock:
            return {"spans": list(self._spans)}


_RECORDER = Recorder()
span = _RECORDER.span
root = _RECORDER.root
enabled = _RECORDER.enabled
enable = _RECORDER.enable
disable = _RECORDER.disable
snapshot = _RECORDER.snapshot
