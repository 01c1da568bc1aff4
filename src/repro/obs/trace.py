"""repro.obs.trace — lock-light span recorder.

Two kinds of span share one ring.

Request spans follow a request through the scheduler lifecycle::

    admit -> queue_wait -> bucket|slot -> compiled_step -> exit
                                                         | escalate
                                                         | shed / reject

carrying difficulty class (lane), predicted vs realized exit depth,
cascade member, slot ids and deadline slack.  They are recorded after
the fact (:meth:`Tracer.record`).

Phase spans say what a thread was doing: :meth:`Tracer.span` is a
context manager that records ``name``, ``ts``, ``dur``, the ``thread``
name and the ``parent`` (the enclosing span's name on the same thread,
None at top level), plus the bucket id ``bid`` the thread is working on
(:meth:`Tracer.bucket`).  The async dispatcher's loop is tiled by the
exclusive phases in :data:`DISPATCH_PHASES`; every blocking
device→host read on the serving path is a ``sync`` child of the phase
(or of ``admit``) it blocks.  Each such span also enters
``jax.profiler.TraceAnnotation(name)``, so a profile taken with host
tracing on shows the phases on the profiler's own clock.

Spans are recorded HOST-SIDE only, never inside jitted step functions:
device telemetry keeps flowing through the ``EngineState`` fold, and
the tracer is *joined* against it after the ``stats()`` reduction (the
reconciliation test pins span exits == telemetry exit histogram).
``ts`` is ``time.monotonic()`` seconds (the scheduler's default clock);
``wall_offset_ns`` maps it onto the wall clock.

The ring is a ``collections.deque(maxlen=capacity)``: append is O(1),
overflow drops the OLDEST span, and CPython's deque append is atomic
under the GIL so the record path takes no lock (the ``dropped`` counter
is therefore approximate under contention — by design; it is a gauge of
pressure, not an audit log).

Export: JSONL (a ``wall_offset_ns`` header line, then one span per line)
and Chrome ``trace_event`` JSON via :func:`chrome_trace` —
``tools/trace_view.py`` converts a JSONL dump into a file Perfetto /
``chrome://tracing`` loads directly, on the wall clock.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque

__all__ = ["Tracer", "Span", "NULL_SPAN", "chrome_trace", "load_jsonl",
           "load_wall_offset_ns", "wall_offset_ns", "DISPATCH_PHASES"]

#: the async dispatcher's exclusive phases, in loop order; together they
#: tile the dispatcher thread (``sync`` spans nest inside them)
DISPATCH_PHASES = ("wait", "select", "gather", "put", "launch", "fetch",
                   "fold", "resolve")

#: canonical span names (informational; the tracer accepts any name)
SPAN_NAMES = ("admit", "queue_wait", "bucket", "slot", "compiled_step",
              "exit", "escalate", "shed", "reject", "sync") \
    + DISPATCH_PHASES


def wall_offset_ns() -> int:
    """Wall-clock ns minus ``time.monotonic_ns()``, read now."""
    return time.time_ns() - time.monotonic_ns()


def _jsonable(v):
    if hasattr(v, "item") and getattr(v, "ndim", None) == 0:
        return v.item()
    if hasattr(v, "tolist"):
        return v.tolist()
    if isinstance(v, tuple):
        return list(v)
    return str(v)


class Span:
    """One phase span, recorded when its ``with`` block exits.  Made by
    :meth:`Tracer.span`; ``set`` adds attributes learned inside the
    block."""

    __slots__ = ("_tracer", "name", "attrs", "_t0", "_parent", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        from jax.profiler import TraceAnnotation
        local = self._tracer._local
        stack = local.__dict__.setdefault("stack", [])
        self._parent = stack[-1].name if stack else None
        stack.append(self)
        if "bid" not in self.attrs:
            bid = getattr(local, "bid", None)
            if bid is not None:
                self.attrs["bid"] = bid
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.monotonic()
        if self._parent is None:
            self._tracer._end_pending(self._t0)
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.monotonic()
        self._ann.__exit__(*exc)
        local = self._tracer._local
        local.stack.pop()
        span = dict(ts=self._t0, dur=t1 - self._t0,
                    thread=threading.current_thread().name,
                    parent=self._parent, **self.attrs)
        if self._parent is None and getattr(local, "tiled", False):
            local.pending = (self.name, span)      # ends at the next one
        else:
            self._tracer.record(self.name, **span)
        return False


class _NullSpan:
    """What a span site enters while obs is off: no clock, no record."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass


#: the one inert span; sites write
#: ``with tracer.span(...) if OBS.enabled else NULL_SPAN:``
NULL_SPAN = _NullSpan()


class _Bucket:
    """Context that makes ``bid`` the current bucket of this thread."""

    __slots__ = ("_local", "_bid", "_prev")

    def __init__(self, local, bid):
        self._local, self._bid = local, bid

    def __enter__(self):
        self._prev = getattr(self._local, "bid", None)
        self._local.bid = self._bid

    def __exit__(self, *exc) -> bool:
        self._local.bid = self._prev
        return False


class Tracer:
    """Bounded span ring.  ``record`` appends one dict — no locks, no
    syncs, no I/O; ``span`` times a block and records it on exit."""

    def __init__(self, capacity: int = 16384):
        self.capacity = int(capacity)
        self._ring: deque = deque(maxlen=max(self.capacity, 1))
        self.dropped = 0
        self.wall_offset_ns = wall_offset_ns()
        self._local = threading.local()

    def record(self, name: str, *, ts: float, dur: float = 0.0,
               rid=None, lane=None, **attrs) -> None:
        """One span: ``ts``/``dur`` in scheduler-clock seconds."""
        if self.capacity <= 0:
            return
        if len(self._ring) == self._ring.maxlen:
            self.dropped += 1                      # approximate, lock-free
        span = {"name": name, "ts": ts, "dur": dur}
        if rid is not None:
            span["rid"] = rid
        if lane is not None:
            span["lane"] = lane
        if attrs:
            span.update(attrs)
        self._ring.append(span)

    def span(self, name: str, **attrs) -> Span:
        """``with tracer.span("fetch"):`` records the block as a span of
        this thread, child of the span it is nested in."""
        return Span(self, name, attrs)

    def bucket(self, bid) -> _Bucket:
        """``with tracer.bucket(bid):`` — spans opened on this thread
        inside the block, without a ``bid`` of their own, carry this
        one."""
        return _Bucket(self._local, bid)

    def tile(self) -> None:
        """Make the calling thread's top-level spans tile it: from now on
        each one is recorded when the next begins, ending there, and the
        time between the two (loop plumbing, or the thread waiting to run
        again) is its ``tail``.  The dispatcher's loop calls this, so its
        phases account for every instant of the thread."""
        self._local.tiled = True

    def untile(self) -> None:
        """End :meth:`tile` on the calling thread, recording its last
        span."""
        self._end_pending(time.monotonic())
        self._local.tiled = False

    def _end_pending(self, now: float) -> None:
        pending = getattr(self._local, "pending", None)
        if pending is not None:
            self._local.pending = None
            name, span = pending
            span["tail"] = now - span["ts"] - span["dur"]
            span["dur"] = now - span["ts"]
            self.record(name, **span)

    @property
    def bid(self):
        """The current bucket id of the calling thread (None outside
        :meth:`bucket`)."""
        return getattr(self._local, "bid", None)

    def __len__(self) -> int:
        return len(self._ring)

    def spans(self, name: str | None = None) -> list:
        """Snapshot (oldest first), optionally filtered by span name."""
        out = list(self._ring)
        if name is not None:
            out = [s for s in out if s["name"] == name]
        return out

    def clear(self) -> None:
        self._ring.clear()
        self.dropped = 0

    def export_jsonl(self, path: str) -> int:
        """Write a ``wall_offset_ns`` header line, then one span per
        line; returns the number of spans written."""
        spans = self.spans()
        with open(path, "w") as f:
            f.write(json.dumps({"wall_offset_ns": self.wall_offset_ns})
                    + "\n")
            for s in spans:
                f.write(json.dumps(s, default=_jsonable) + "\n")
        return len(spans)


def _lines(path: str) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def load_jsonl(path: str) -> list:
    """The spans of an :meth:`Tracer.export_jsonl` dump."""
    return [d for d in _lines(path) if "name" in d]


def load_wall_offset_ns(path: str) -> int:
    """The dump's ``wall_offset_ns`` header (0 for a dump without one)."""
    return next((int(d["wall_offset_ns"]) for d in _lines(path)
                 if "wall_offset_ns" in d), 0)


def chrome_trace(spans, wall_offset_ns: int = 0) -> dict:
    """Chrome ``trace_event`` JSON (the object format Perfetto and
    ``chrome://tracing`` load).  Phase spans land on one track per
    thread (``sync`` children nest inside their phase), request spans
    on one track per lane; span attrs ride along in ``args``.
    Timestamps are ``ts`` + ``wall_offset_ns`` in µs, so a dump lines
    up with a profiler trace of the same run."""
    tids: dict = {}
    events = []
    for s in spans:
        key = f"thread {s['thread']}" if "thread" in s \
            else f"lane {s.get('lane', '-')!r}"
        tid = tids.get(key)
        if tid is None:
            tid = tids[key] = len(tids) + 1
            events.append({"ph": "M", "pid": 0, "tid": tid,
                           "name": "thread_name", "args": {"name": key}})
        args = {k: _jsonable(v) if not isinstance(
                    v, (int, float, str, bool, type(None))) else v
                for k, v in s.items() if k not in ("name", "ts", "dur")}
        events.append({"name": s["name"], "ph": "X", "pid": 0, "tid": tid,
                       "ts": float(s["ts"]) * 1e6 + wall_offset_ns / 1e3,
                       "dur": max(float(s.get("dur", 0.0)), 0.0) * 1e6,
                       "args": args})
    return {"traceEvents": events, "displayTimeUnit": "ms"}
