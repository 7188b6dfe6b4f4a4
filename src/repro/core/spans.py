"""Host spans of the IMM solve loop, on the profiler's own clock.

A span is a ``jax.profiler.TraceAnnotation``: while a profiler trace is
active it lands on the trace's host plane, on the clock the profiler puts
the device events on, and its keyword arguments become the event's stats;
otherwise it costs one native call.  Spans nest by the thread: a span's
parent is the span that encloses it.  Every span opened while a solve is
open on the thread carries that solve's id as ``solve=``.

Names (``chipbench/program_trace.py`` reads them): ``imm.prepare``,
``imm.solve``, ``imm.lb_iter``, ``imm.sample_until``, ``imm.round``,
``imm.sample``, ``imm.append``, ``imm.select`` and ``imm.fetch`` — the last
around each explicit device-to-host fetch (:func:`fetch`), which also
counts it in the open solve's ``IMMStats.host_fetches``.  ``imm.append``
ends with the round's ``elements`` (summed in ``IMMStats.pool_elements``).
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.profiler import TraceAnnotation

_open = threading.local()      # .solve: (id, on_fetch) of the solve open here


def _solve():
    return getattr(_open, "solve", None)


def span(name: str, **args) -> TraceAnnotation:
    """The span ``name`` with ``args``, tagged with the open solve's id."""
    s = _solve()
    if s is not None:
        args["solve"] = s[0]
    return TraceAnnotation(name, **args)


@contextlib.contextmanager
def solve(solve_id: int, on_fetch, **args):
    """``imm.solve``: opens a solve on this thread; the spans inside carry
    ``solve_id``, and each fetch inside calls ``on_fetch()``."""
    prev = _solve()
    _open.solve = (solve_id, on_fetch)
    try:
        with TraceAnnotation("imm.solve", solve=solve_id, **args):
            yield
    finally:
        _open.solve = prev


def fetch(what: str, tree):
    """``jax.device_get(tree)`` in an ``imm.fetch`` span: one explicit
    device-to-host fetch."""
    s = _solve()
    if s is not None:
        s[1]()
    with span("imm.fetch", what=what):
        return jax.device_get(tree)
