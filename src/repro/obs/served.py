"""Spans of the served request path on a real clock, for the profiler.

``trace.py`` records virtual-clock spans for the simulator pin; this
module records what the host does per request while a profiler session
runs (``jax.profiler.trace`` / ``start_trace``): each span opens a
``jax.profiler.TraceAnnotation``, so it sits in the trace's host plane
beside the device's ops, and is copied into a bounded in-memory buffer
stamped on the profiler's own host clock (``time.time_ns``; the trace
counts from its ``profile_start_time``).  Span names (the closed
vocabulary):

=======================  ===================================================
``coach.decide``         one request's decision; sets the current request
                         id, which every later span carries until the next
``coach.step``           the Eq. 10/11 decision on the host
``coach.plan``           the per-task plan (``plan_for``)
``coach.account``        accounting and center update
``coach.probe_centers``  the trained-center view a fused pass probes
``coach.segment``        dispatch of segment ``k`` (meta ``k``)
``coach.boundary``       dispatch of the fused boundary pass
``coach.dequantize``     dispatch of the wire dequantize
``coach.probe_result``   lifting the fused probe to label space
``coach.d2h``            one device-to-host pull: the wait plus the copy
                         (meta ``site``; ``probe`` for the four outputs
                         of a fused boundary pass, fetched together)
``coach.gc``             a collector pause (meta ``generation``)
``coach.experts``        one expert layer's routing in the request: tokens
                         per expert (meta ``loads``, a tuple), the
                         model's index of the layer (meta ``layer``), and
                         from ``grouped_matmul.fetch_schedule`` on the
                         loads as the dropless layer pads them, the rows
                         its kernel computes (meta ``rows``) and the
                         share of its weight-block copies started two or
                         more visits ahead (meta ``early``)
=======================  ===================================================

Counts are counts of spans.  Sites follow ``trace.py``'s contract of
zero cost when disabled: each is guarded by ``if on():`` (one call,
``TraceAnnotation.is_enabled``), so with no profiler session a site
allocates and records nothing.  The buffer, like the profiler session,
is one per process; spans are for the serving thread.

The segment programs return their expert layers' loads on the device;
``hold_loads`` keeps them, and the engine's ``record_experts``, once the
request's result is on the host, pulls them (the device has long
finished them) and records the ``coach.experts`` spans.  Both run only
under a session.
"""

from __future__ import annotations

import contextlib
import gc
import time
from typing import NamedTuple, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.kernels import grouped_matmul as GM

DECIDE = "coach.decide"
STEP = "coach.step"
PLAN = "coach.plan"
ACCOUNT = "coach.account"
PROBE_CENTERS = "coach.probe_centers"
SEGMENT = "coach.segment"
BOUNDARY = "coach.boundary"
DEQUANTIZE = "coach.dequantize"
PROBE_RESULT = "coach.probe_result"
D2H = "coach.d2h"
GC = "coach.gc"
EXPERTS = "coach.experts"

SPANS = (DECIDE, STEP, PLAN, ACCOUNT, PROBE_CENTERS, SEGMENT, BOUNDARY,
         DEQUANTIZE, PROBE_RESULT, D2H, GC, EXPERTS)

MAX_SPANS = 1 << 18

on = TraceAnnotation.is_enabled


class Span(NamedTuple):
    name: str
    req: Optional[int]       # request id current when the span opened
    parent: Optional[int]    # index of the enclosing span in ``spans``
    t0: int                  # ns, ``time.time_ns``
    t1: Optional[int]        # None while the span is open
    meta: dict


class Recording(NamedTuple):
    spans: tuple
    dropped: int             # spans not kept: the buffer was full


_buf: list = []
_stack: list = []            # (index or None, entry or None) of open spans
_dropped = 0
_req: Optional[int] = None


def _open(name: str, meta: dict) -> Optional[list]:
    global _dropped
    parent = _stack[-1][0] if _stack else None
    if len(_buf) >= MAX_SPANS:
        _dropped += 1
        _stack.append((None, None))
        return None
    entry = [name, _req, parent, 0, None, meta]
    _buf.append(entry)
    _stack.append((len(_buf) - 1, entry))
    return entry


class _Recording:
    """One span while the profiler runs.  The annotation stamps its start
    when it is made; the copy's start is stamped right after, with no
    allocation between, so that no collection falls between the two."""

    __slots__ = ("name", "meta", "ann")

    def __init__(self, name: str, meta: dict):
        self.name, self.meta = name, meta

    def __enter__(self):
        entry = _open(self.name, self.meta)
        ann = TraceAnnotation(self.name, **self.meta)
        if entry is not None:
            entry[3] = time.time_ns()
        self.ann = ann
        ann.__enter__()

    def __exit__(self, *exc):
        self.ann.__exit__(*exc)
        _, entry = _stack.pop()
        if entry is not None:
            entry[4] = time.time_ns()


_OFF = contextlib.nullcontext()


def span(name: str, req: Optional[int] = None, **meta):
    """Context manager for one span; ``req`` makes that request current.
    A no-op while no profiler session runs."""
    global _req
    if not on():
        return _OFF
    if req is not None:
        _req = req
    return _Recording(name, meta)


_gc_open: list = []


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        if on():
            pause = _Recording(GC, {"generation": info["generation"]})
            pause.__enter__()
            _gc_open.append(pause)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


gc.callbacks.append(_on_gc)


_held: list = []             # (layer indices, device loads) not yet recorded


def hold_loads(layers, loads) -> None:
    """Keep a segment's expert loads, (layers, E) on the device, for
    ``record_experts``."""
    _held.append((layers, loads))


def record_experts() -> None:
    """One ``coach.experts`` span per expert layer held, and forget them.
    Call it after the request's result is on the host."""
    if not _held:
        return
    got = jax.device_get([loads for _, loads in _held])
    for (layers, _), held in zip(_held, got):
        for layer, loads in zip(layers, held.tolist()):
            rows, early = GM.fetch_schedule(GM.padded(np.asarray(loads)))
            with span(EXPERTS, layer=layer, loads=tuple(loads), rows=rows,
                      early=early):
                pass
    _held.clear()


def recorded() -> Recording:
    return Recording(tuple(Span(*e) for e in _buf), _dropped)


def clear() -> None:
    """Empty the buffer; call it outside every span."""
    global _dropped, _req
    _buf.clear()
    _held.clear()
    _dropped = 0
    _req = None
