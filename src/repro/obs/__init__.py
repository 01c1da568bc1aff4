"""repro.obs — the serving observability layer.

One switch, three surfaces:

* **tracing** (:mod:`repro.obs.trace`) — host-side spans in a bounded
  drop-oldest ring: per request (``admit -> queue_wait -> bucket/slot
  -> compiled_step -> exit | escalate | shed``), and per thread
  (:func:`span`: the dispatcher's phases, the ``sync`` of every
  blocking device→host read, :func:`to_host`); JSONL + Chrome
  ``trace_event`` export on the wall clock (``tools/trace_view.py``).
* **metrics registry** (:mod:`repro.obs.metrics`) — counters / gauges /
  histograms with label sets and a Prometheus text exposition (file
  and stdlib-``http.server`` endpoint); :mod:`repro.obs.adapters`
  mirrors every existing signal into it (EngineState telemetry,
  per-lane DAES, ``trace_counts``, kernel dispatch decisions, queue
  depths, slot/page occupancy).
* **structured logging** (:mod:`repro.obs.log`) — the dispatcher
  threads' failure paths log ``key=value`` lines and count
  ``dart_errors_total``.

Usage::

    from repro import obs
    obs.configure(enabled=True, textfile="artifacts/metrics.prom")
    server = AsyncDartServer(engine)        # auto-instrumented
    ...
    obs.flush_textfile()                    # or let the writer thread
    print(obs.OBS.registry.render())        # Prometheus text

Disabled (the default) is zero-cost on the hot path: every
instrumentation site is a single ``if OBS.enabled`` attribute check
(span sites enter the inert :data:`NULL_SPAN`: no clock read, no
allocation), spans are recorded only from host-side scheduler code
(never inside jitted step functions), and no extra host syncs are
introduced — the differential suites pin bit-identical outputs and
unchanged ``trace_counts`` with obs off.  What enabled costs is
measured on the chip (docs/observability.md).
"""
from __future__ import annotations

import threading

import numpy as np

from repro.obs import log  # noqa: F401  (re-export)
from repro.obs.metrics import (Registry, parse_prometheus,
                               render_prometheus, start_http_server,
                               write_textfile)
from repro.obs.trace import NULL_SPAN, Tracer, chrome_trace, wall_offset_ns

__all__ = ["OBS", "configure", "reset", "is_enabled", "get_registry",
           "get_tracer", "flush_textfile", "span", "to_host", "NULL_SPAN",
           "Registry", "Tracer", "chrome_trace", "render_prometheus",
           "parse_prometheus", "log"]

DEFAULT_TRACE_CAPACITY = 16384


class _ObsState:
    """The process-wide observability switchboard.  Hot-path code reads
    ONE attribute (``OBS.enabled``) and does nothing else when off."""

    def __init__(self):
        self.enabled = False
        self.registry = Registry()
        self.tracer = Tracer(DEFAULT_TRACE_CAPACITY)
        self.textfile: str | None = None
        self._writer: threading.Thread | None = None
        self._writer_stop: threading.Event | None = None
        self._http = None

    @property
    def http_port(self) -> int | None:
        return None if self._http is None else self._http.server_address[1]


OBS = _ObsState()


def is_enabled() -> bool:
    return OBS.enabled


def get_registry() -> Registry:
    return OBS.registry


def get_tracer() -> Tracer:
    return OBS.tracer


def span(name: str, **attrs):
    """``with obs.span("fetch"):`` — a span of the calling thread on the
    global tracer (see :meth:`repro.obs.trace.Tracer.span`).  Hot-path
    sites guard it: ``with obs.span(...) if OBS.enabled else NULL_SPAN``."""
    return OBS.tracer.span(name, **attrs)


def to_host(value, site: str) -> np.ndarray:
    """``np.asarray(value)``: a blocking device→host read.  With obs on
    it is a ``sync`` span (``site``, ``bytes``), child of the span it
    blocks, and counts ``dart_device_syncs_total{site}``."""
    if not OBS.enabled:
        return np.asarray(value)
    with OBS.tracer.span("sync", site=site) as sp:
        out = np.asarray(value)
        sp.set(bytes=int(out.nbytes))
    OBS.registry.counter("dart_device_syncs_total",
                         "blocking device-to-host reads by call site",
                         ("site",)).inc(1, site=site)
    return out


def configure(enabled: bool | None = None, *,
              trace_capacity: int | None = None,
              textfile: str | None = None,
              textfile_interval_s: float | None = None,
              http_port: int | None = None) -> _ObsState:
    """Configure the global observability state.

    enabled:             master switch for hot-path instrumentation
    trace_capacity:      span ring size (drop-oldest past it)
    textfile:            path to (re)write the Prometheus exposition to
    textfile_interval_s: start a daemon writer rewriting ``textfile``
                         every interval (atomic rename — safe to tail)
    http_port:           serve ``/metrics`` via stdlib http.server
                         (0 = OS-assigned; read it back from
                         ``OBS.http_port``)
    """
    if enabled is not None:
        OBS.enabled = bool(enabled)
    if trace_capacity is not None:
        OBS.tracer = Tracer(trace_capacity)
    if textfile is not None:
        OBS.textfile = textfile
        if textfile_interval_s:
            _stop_writer()
            stop = threading.Event()

            def loop():
                while not stop.wait(textfile_interval_s):
                    try:
                        write_textfile(OBS.registry, textfile)
                    except Exception:              # noqa: BLE001
                        pass

            t = threading.Thread(target=loop, daemon=True,
                                 name="obs-textfile-writer")
            OBS._writer, OBS._writer_stop = t, stop
            t.start()
    if http_port is not None and OBS._http is None:
        OBS._http = start_http_server(OBS.registry, port=http_port)
    if OBS.enabled:
        OBS.tracer.wall_offset_ns = wall_offset_ns()
        # kernel dispatch decisions are always counted (trace-time
        # bookkeeping, like trace_counts); export them once enabled
        from repro.obs import adapters
        adapters.bind_dispatch(OBS.registry)
    return OBS


def flush_textfile() -> str | None:
    """Write the exposition file now (regardless of the writer thread)."""
    if OBS.textfile is None:
        return None
    return write_textfile(OBS.registry, OBS.textfile)


def _stop_writer() -> None:
    if OBS._writer_stop is not None:
        OBS._writer_stop.set()
    OBS._writer = OBS._writer_stop = None


def reset() -> _ObsState:
    """Tear down exporters and return to the disabled default (tests)."""
    _stop_writer()
    if OBS._http is not None:
        try:
            OBS._http.shutdown()
        except Exception:                          # noqa: BLE001
            pass
        OBS._http = None
    OBS.enabled = False
    OBS.textfile = None
    OBS.registry = Registry()
    OBS.tracer = Tracer(DEFAULT_TRACE_CAPACITY)
    return OBS
