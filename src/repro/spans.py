"""Host spans and stage counters of the served path.

``span(name, **ids)`` times one stage.  It opens a
``jax.profiler.TraceAnnotation(name, **ids)``, so a profiler trace shows
the stage on its thread's line of the host plane, on the clock of the
device planes; on exit it adds one count and the stage's
``time.perf_counter`` seconds to a process-wide table keyed by ``name``.
``count(name, n, s)`` adds to the same table without timing anything.
``snapshot()`` copies the table as ``{name: {"n": ..., "s": ...}}``.
Counters are lifetime totals, always on; read them as the change between
two snapshots.  The ``stats`` RPC carries the snapshot as ``spans``.

Ids tie a span to its cause.  ``group`` and ``hop`` pass from a span to
every span opened inside it on the same thread, so a store span opened
by a fused group's sampling carries that group's id.
"""
from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation

from .concurrency import witness_lock

_INHERITED = ("group", "hop")


class _Inherited(threading.local):
    ids = None                  # ids the enclosing span passes down


_local = _Inherited()


class Recorder:
    """Counts and seconds per name, behind one leaf lock."""

    def __init__(self):
        self._lock = witness_lock("spans._lock", threading.Lock())
        self._table: dict[str, list] = {}      # guarded-by: _lock

    def add(self, name: str, n: int = 1, s: float = 0.0) -> None:
        """Add ``n`` to ``name``'s count and ``s`` to its seconds."""
        with self._lock:
            row = self._table.get(name)
            if row is None:
                self._table[name] = [n, s]
            else:
                row[0] += n
                row[1] += s

    def snapshot(self) -> dict:
        """A copy of the table: ``{name: {"n": count, "s": seconds}}``."""
        with self._lock:
            return {k: {"n": n, "s": s} for k, (n, s) in self._table.items()}


RECORDER = Recorder()


class span:
    """Context manager: one timed stage named ``name`` (see the module
    docstring); ``ids`` are the annotation's arguments."""

    __slots__ = ("name", "ids", "_outer", "_ann", "_t0")

    def __init__(self, name: str, **ids):
        self.name = name
        self.ids = ids

    def __enter__(self):
        outer = self._outer = _local.ids
        ids = self.ids
        if ids:
            if outer:
                ids = {**outer, **ids}
            _local.ids = {k: ids[k] for k in _INHERITED if k in ids}
        elif outer:
            ids = outer
        self._ann = TraceAnnotation(self.name, **ids)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        _local.ids = self._outer
        RECORDER.add(self.name, 1, dt)
        return False


def count(name: str, n: int = 1, s: float = 0.0) -> None:
    """Add ``n`` (and ``s`` seconds) to counter ``name``."""
    RECORDER.add(name, n, s)


def snapshot() -> dict:
    """The process-wide table (``Recorder.snapshot``)."""
    return RECORDER.snapshot()
