"""ServingLoop — the async, SLO-aware dispatcher over the DART engines.

``AsyncDartServer`` turns a ``DartEngine`` / ``ShardedDartEngine`` into
a real server: callers ``submit(x, deadline_ms, priority)`` and get a
future; a background dispatcher consolidates queued requests into
``BatchCompactor`` buckets and flushes each bucket through ONE engine
call.  The lifecycle of a request:

    submit ──admit──▶ lane queue ──flush──▶ in-flight ──resolve──▶ future
           (Eq. 8 α,    (per difficulty   (one infer call  (np outputs,
            cost         class; back-      per bucket;      latency fold,
            prediction)  pressure)         pipelined)       prior update)

Flush policy (size-or-deadline):

* **deadline** — a lane flushes when its earliest deadline minus the
  estimated service time (EMA of recent bucket latencies + margin)
  would otherwise expire while waiting.
* **size**     — a lane flushes at the consolidation target
  (``max_batch``), or early when it exactly fills a power-of-two bucket
  at ≥ half the target: waiting longer could only grow padding waste,
  never shrink it ("never pad past the next bucket when waiting would
  beat padding").
* **hold**     — no BEST-EFFORT (deadline-less) request waits longer
  than ``flush_ms`` even on an idle stream.  Deadline'd requests are
  deliberately excluded: their SLO already bounds the wait, and holding
  them until deadline pressure (or a full bucket) maximizes
  consolidation at exactly the loads where it pays.

Packing: the flushed lane's head goes first, then its other requests
first-fit in order (one that does not fit is skipped, not a stop), then
first-fit from the lanes ``_mixable_lanes`` names (``RequestQueue.take``).

Pipelining: with a sharded engine, dispatched outputs stay ON DEVICE
(PR 2 left them lazy precisely for this) — the loop keeps up to
``pipeline_depth`` buckets in flight and only materializes (resolving
futures, folding latency telemetry into the engine's host-side request
window) when the pipeline is full or there is nothing left to
dispatch.  Completing a bucket reads only that bucket's outputs, never
the engine's state, so the buckets launched after it stay queued on
the device while the host works.

Tracing (obs on): the dispatcher thread's time is tiled by exclusive
phase spans, each carrying the bucket id ``bid`` it works for —
``wait`` (``why``: empty / timer / inflight-poll), ``select`` (flush
decision + take, ``reason``), ``gather`` (``path``: ``device`` when the
bucket is stacked from admission's device rows, else ``host``;
``behind``: in-flight buckets whose outputs are not ready yet), ``put``
and ``launch`` (the engine's), ``fetch`` (output reads + per-request
split), ``fold`` (telemetry) and ``resolve`` (futures).  The time
between two phases counts to the one that ended, as its ``tail``
(``Tracer.tile``).  Blocking device→host reads are ``sync`` children
(docs/observability.md).
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from collections import deque
from concurrent.futures import Future

import jax
import numpy as np

from repro.core import daes as DAES
from repro.core import difficulty as DIFF
from repro.engine.sharded import ShardedDartEngine
from repro.obs import NULL_SPAN, OBS, span, to_host
from repro.obs import adapters as OBS_A
from repro.obs import log as OBS_LOG
from repro.serving.planner import AdmissionPlanner
from repro.serving.predict import ExitDepthPredictor
from repro.serving.queue import RequestQueue
from repro.serving.request import DispatchError, Request, RequestRejected

#: result keys sliced per request out of a consolidated engine call
_RESULT_KEYS = ("pred", "conf", "exit_idx", "alpha", "macs")


def _ready(out: dict) -> bool:
    """Whether a dispatched bucket's outputs are computed, without
    blocking: its first output leaf's ``is_ready()`` (host values are
    ready)."""
    leaf = jax.tree.leaves(out)[0]
    return not isinstance(leaf, jax.Array) or leaf.is_ready()


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Knobs of the async scheduler (see module docstring for the flush
    semantics).

    max_batch:      consolidation target, samples per flushed bucket
    flush_ms:       max hold time for a non-full lane
    margin_ms:      scheduling slack subtracted from every deadline
    max_queue:      per-lane backpressure limit, in requests
    policy:         "shed" | "reject" | "degrade-alpha"
    degrade_factor: alpha scale applied under degrade-alpha
    min_fill:       min fill fraction before growing into a larger bucket
    mode:           engine inference mode for dispatched buckets
    pipeline_depth: max in-flight (unmaterialized) buckets
    edges:          difficulty-class boundaries on Eq. 8 alpha
    sample_ndim:    rank of ONE sample (submit auto-batches bare samples)
    starve_ms:      continuous slot refill only — how long the most
                    urgent queued request may be passed over for lack
                    of capacity before freed slots are reserved for it
                    (see ``RequestQueue.pop_next``)
    predict:        admission-time exit-depth prediction — "off" |
                    "conservative" (head-skip only where Eq. 19
                    provably can't fire: bit-identical decisions) |
                    "aggressive" (additionally skip gates the learned
                    histogram says never fire — opt-in, measured).
                    On, requests get predicted-depth lanes, an
                    admission latency quote, and per-bucket head-skip
                    (see ``repro.serving.predict``)
    """
    max_batch: int = 64
    flush_ms: float = 5.0
    margin_ms: float = 1.0
    max_queue: int = 256
    policy: str = "shed"
    degrade_factor: float = 0.5
    min_fill: float = 0.5
    mode: str = "masked"
    pipeline_depth: int = 2
    edges: tuple = DIFF.DEFAULT_EDGES
    sample_ndim: int = 3
    starve_ms: float = 50.0
    predict: str = "off"


class _BucketScheduler:
    """Lane-queue + dispatcher-thread machinery shared by the classifier
    scheduler (:class:`AsyncDartServer`) and the LM decode session
    (:class:`~repro.serving.lm_session.LMDecodeSession`).

    Subclasses implement ``_admit`` (build a Request) and ``_dispatch``
    (serve a flushed run of requests); the base owns admission,
    flush timing, the worker thread, and shutdown."""

    def __init__(self, cfg: SchedulerConfig, *, clock=time.monotonic,
                 start: bool = True):
        self.cfg = cfg
        self._clock = clock
        # Effective consolidation target: cfg.max_batch clamped to what
        # ONE dispatch can serve as a single compiled shape — flushing
        # more than the engine's largest bucket would make bucket_key
        # raise mid-flush and wedge the dispatcher.
        self.max_batch = max(1, min(cfg.max_batch, self._max_batch_cap()))
        self.queue = RequestQueue(max_queue=cfg.max_queue,
                                  policy=cfg.policy)
        self._rid = itertools.count()
        self._cv = threading.Condition()
        self._stop = False
        self._closed = False
        self._service_s = 0.0        # EMA of bucket service time
        self._bid = 0                # next bucket id (advanced with obs on)
        self.last_error: Exception | None = None
        self.counters = {"submitted": 0, "completed": 0, "degraded": 0,
                         "flush_deadline": 0, "flush_size": 0,
                         "flush_hold": 0, "flush_forced": 0}
        self._thread = None
        if OBS.enabled:
            OBS_A.bind_scheduler(self)
        if start:
            self.start()

    # -- subclass hooks -------------------------------------------------
    def _admit(self, x, deadline_ms, priority, *, now, **kw) -> Request:
        """Build the Request.  ``now`` is stamped at the START of
        submit(), so admission work (the Eq. 8 estimate) counts toward
        the request's latency and deadline like any other service
        time."""
        raise NotImplementedError

    def _dispatch(self, reqs: list, reason: str) -> None:
        raise NotImplementedError

    def _engine_call(self, fn):
        """Run one engine call.  ``fn(engine) -> result``; the default
        binds the scheduler's single engine.  The resilience layer
        (:class:`~repro.serving.resilience.EnginePool`) overrides this
        to add engine selection, retry/backoff and hedging without the
        dispatch sites knowing."""
        return fn(self.engine)

    def _on_dispatch_error(self, reqs: list, exc: Exception) -> bool:
        """Dispatch-failure hook: return True when the requests were
        re-routed (e.g. requeued by the pool after an engine death) and
        must NOT have their futures failed.  Default: unhandled."""
        return False

    def _drain_one(self) -> bool:
        """Materialize one in-flight bucket if any; False when idle."""
        return False

    def _bucket_key(self, n: int) -> int:
        """Padded dispatch shape for n samples.  Must be TOTAL (never
        raise): oversized single requests pass through take() and are
        dispatched unpadded."""
        return n

    def _max_batch_cap(self) -> int:
        """Largest sample count one dispatch can serve as one shape."""
        return self.cfg.max_batch

    def _mixable_lanes(self, key) -> list:
        """Lanes whose requests may top up a bucket flushed from lane
        ``key`` (``RequestQueue.take``'s ``others``).  Default: none, so
        buckets stay lane-pure — right wherever a bucket's cost depends
        on which lane its rows came from."""
        return []

    # -- lifecycle ------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=type(self).__name__)
        self._thread.start()

    def submit(self, x, deadline_ms: float | None = None,
               priority: int = 0, **kw) -> Future:
        """Enqueue one request; resolves to its per-request result dict
        (or raises RequestShed/RequestRejected under backpressure)."""
        t0 = self._clock()
        with span("admit") if OBS.enabled else NULL_SPAN as sp:
            req = self._admit(x, deadline_ms, priority, now=t0, **kw)
            # The closed check and the push share the cv lock with
            # close(): a request either lands before _closed is set
            # (close's flush serves it) or is rejected — never silently
            # stranded in a lane no worker will ever flush.
            with self._cv:
                if self._closed:
                    req.fail(RequestRejected("scheduler is closed"))
                    return req.future
                action = self.queue.push(req)
                self.counters["submitted"] += 1
                self._cv.notify()
            if OBS.enabled:
                OBS_A.record_admit(self, req, action, sp)
        return req.future

    def close(self, wait: bool = True) -> None:
        """Stop admitting, serve everything already queued, join."""
        with self._cv:
            self._closed = True
            self._stop = True
            self._cv.notify()
        if self._thread is not None:
            self._thread.join(timeout=60)
            self._thread = None
        if wait:
            self.flush()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- scheduling -----------------------------------------------------
    def _select_flush(self, now: float):
        """(lane, reason, force) of the most urgent flush-ready lane,
        or None.  Urgency: deadline pressure ≻ size ≻ hold."""
        slack = (self.cfg.margin_ms / 1e3) + self._service_s
        best = None                       # (rank, tiebreak, lane, reason)
        for key in self.queue.keys():
            n_q = self.queue.samples(key)
            if not n_q:
                continue
            edl = self.queue.earliest_deadline(key)
            held = self.queue.oldest_undeadlined(key)
            if edl is not None and edl - now <= slack:
                cand = (0, edl, key, "deadline")
            elif n_q >= self.max_batch or (
                    2 * n_q >= self.max_batch
                    and self._bucket_key(n_q) == n_q):
                cand = (1, -n_q, key, "size")
            elif held is not None \
                    and now - held >= self.cfg.flush_ms / 1e3:
                cand = (2, held, key, "hold")
            else:
                continue
            if best is None or cand < best:
                best = cand
        if best is None:
            return None
        _, _, key, reason = best
        return key, reason, reason == "deadline"

    def _wait_timeout(self, now: float) -> float | None:
        """Seconds until the next deadline/hold event (None = wait for
        a submit notification)."""
        slack = (self.cfg.margin_ms / 1e3) + self._service_s
        nxt = None
        for key in self.queue.keys():
            edl = self.queue.earliest_deadline(key)
            held = self.queue.oldest_undeadlined(key)
            for t in ((edl - slack) if edl is not None else None,
                      (held + self.cfg.flush_ms / 1e3)
                      if held is not None else None):
                if t is not None and (nxt is None or t < nxt):
                    nxt = t
        if nxt is None:
            return None
        return max(nxt - now, 1e-4)

    def pump(self) -> bool:
        """One scheduling decision: flush the most urgent ready lane, or
        materialize one in-flight bucket.  Returns False when idle.
        (The worker thread loops this; tests drive it directly.)"""
        with span("select", bid=self._bid) if OBS.enabled \
                else NULL_SPAN as sp:
            sel = self._select_flush(self._clock())
            reqs = None
            if sel is not None:
                key, reason, force = sel
                reqs, rows = self.queue.take(
                    key, self.max_batch, self._bucket_key,
                    min_fill=self.cfg.min_fill, force=force,
                    others=self._mixable_lanes(key))
            if OBS.enabled:
                sp.set(reason=reason if reqs else None)
        if reqs:
            self.counters[f"flush_{reason}"] += 1
            self._dispatch_safe(reqs, reason, rows)
            return True
        return self._drain_one()

    def _dispatch_safe(self, reqs: list, reason: str, rows: dict) -> None:
        """A bad bucket must not kill the dispatcher: an exception from
        the engine fails THIS bucket's futures and the loop lives on
        (a shape-mismatched input would otherwise strand every pending
        future behind a dead daemon thread).  ``rows``: the samples
        ``take`` came by each way."""
        bucket = NULL_SPAN
        if OBS.enabled:
            bid, self._bid = self._bid, self._bid + 1
            OBS_A.record_bucket(self, reqs, reason, self._clock(), bid,
                                rows)
            bucket = OBS.tracer.bucket(bid)
        try:
            with bucket:
                self._dispatch(reqs, reason)
        except Exception as e:                     # noqa: BLE001
            if self._on_dispatch_error(reqs, e):
                return                             # re-routed, not failed
            self.counters["dispatch_errors"] = \
                self.counters.get("dispatch_errors", 0) + 1
            self.last_error = e
            OBS_LOG.error("dispatch", "bucket dispatch failed", exc=e,
                          reason=reason, lane=reqs[0].lane,
                          n_requests=len(reqs),
                          rids=[r.rid for r in reqs[:8]])
            err = e if isinstance(e, DispatchError) else DispatchError(
                "dispatch", reqs[0].lane, [r.rid for r in reqs], e)
            for r in reqs:
                r.fail(err)

    def flush(self) -> None:
        """Force-dispatch every queued request and materialize all
        in-flight work (shutdown / test barrier)."""
        while True:
            keys = self.queue.keys()
            if not keys:
                break
            for key in keys:
                while True:
                    reqs, rows = self.queue.take(
                        key, self.max_batch, self._bucket_key, force=True,
                        others=self._mixable_lanes(key))
                    if not reqs:
                        break
                    self.counters["flush_forced"] += 1
                    self._dispatch_safe(reqs, "forced", rows)
        while self._drain_one():
            pass

    def _run(self) -> None:
        try:
            self._loop()
        finally:
            OBS.tracer.untile()

    def _loop(self) -> None:
        busy = False
        while True:
            if OBS.enabled:
                # each turn: obs can be switched on while the loop runs
                OBS.tracer.tile()
            if not busy:
                with span("wait", bid=self._bid) if OBS.enabled \
                        else NULL_SPAN as sp, self._cv:
                    if not self._stop:
                        queued = not self.queue.empty
                        timeout = self._wait_timeout(self._clock()) \
                            if queued else \
                            (0.002 if self._has_inflight() else None)
                        if OBS.enabled:
                            sp.set(why="timer" if queued else
                                   "inflight-poll" if timeout else "empty")
                        self._cv.wait(timeout)
                    if self._stop:
                        return
            try:
                busy = self.pump()
                if busy and self._stop:
                    return
            except Exception as e:                 # noqa: BLE001
                # Dispatch errors are contained by _dispatch_safe; this
                # catches scheduler bugs so the thread survives (queued
                # work still fails fast through _dispatch_safe rather
                # than hanging behind a dead loop).
                busy = False
                self.last_error = e
                OBS_LOG.error("scheduler", "scheduler loop error",
                              exc=e, scheduler=type(self).__name__)
                time.sleep(0.01)

    def _has_inflight(self) -> bool:
        return False


class AsyncDartServer(_BucketScheduler):
    """The difficulty-aware async request scheduler over a DartEngine.

        engine = DartEngine.from_config(cfg, params, ...)
        server = AsyncDartServer(engine)
        fut = server.submit(x, deadline_ms=50)
        out = fut.result()          # same keys as engine.infer + latency
        server.stats()              # engine stats + p50/p95/p99 + misses
        server.close()

    Works with the eager engine and (better: pipelined, one compiled
    dispatch per bucket) the sharded engine.  Under a fixed policy,
    scheduler decisions never change routing decisions: completed
    outputs are identical to serving each request alone through
    ``engine.infer`` (with §II.C adaptation on, reordering shifts where
    the periodic updates fall — see docs/serving.md).

    Constructing with a :class:`~repro.cascade.engine.CascadeEngine`
    transparently builds the cascade scheduler
    (:class:`~repro.cascade.serving.CascadeAsyncServer`): lanes become
    (member, difficulty class), escalations re-enqueue into the next
    member's lanes."""

    def __new__(cls, engine=None, *args, **kw):
        if cls is AsyncDartServer and engine is not None:
            from repro.cascade.engine import CascadeEngine
            if isinstance(engine, CascadeEngine):
                from repro.cascade.serving import CascadeAsyncServer
                cls = CascadeAsyncServer
        return object.__new__(cls)

    def __init__(self, engine, cfg: SchedulerConfig = SchedulerConfig(),
                 *, clock=time.monotonic, start: bool = True):
        self.engine = engine
        self.planner = self._make_planner(cfg)
        self.predictor = None if cfg.predict == "off" else \
            ExitDepthPredictor(engine.n_exits, edges=cfg.edges,
                               mode=cfg.predict,
                               priors=self.planner.priors)
        # Per-lane Eq. 9 telemetry: static reference = the full network
        # (for a cascade engine, the biggest member's full network).
        self.daes = DAES.LaneDaesAccumulator(
            static_macs=float(np.asarray(engine.cum_costs)[-1]))
        self._inflight: deque = deque()
        super().__init__(cfg, clock=clock, start=start)

    def _make_planner(self, cfg: SchedulerConfig):
        # a sharded engine stacks masked buckets on the device from the
        # rows admission already copied there
        return AdmissionPlanner(
            self.engine, edges=cfg.edges,
            device_rows=cfg.mode == "masked"
            and isinstance(self.engine, ShardedDartEngine))

    # -- hooks ----------------------------------------------------------
    def _bucket_key(self, n: int) -> int:
        if n > self.engine.compactor.max_bucket:
            return n            # oversized single request: unpadded
        return self.engine.bucket_key(n)

    def _max_batch_cap(self) -> int:
        return self.engine.compactor.max_bucket

    def _mixable_lanes(self, key) -> list:
        """Every other lane where a bucket's cost does not depend on its
        rows' difficulty: masked mode without a predictor runs every row
        through every stage, so a lane-pure bucket costs what a mixed
        one does.  Compacted mode (stages shrink as rows exit) and
        ``predict`` (head-skip from the bucket's smallest α) keep lanes
        pure.  A masked step that skipped exited rows would end the
        exemption here."""
        if self.cfg.mode != "masked" or self.predictor is not None:
            return []
        return [k for k in self.queue.keys() if k != key]

    def _admit(self, x, deadline_ms, priority, *, now, **kw) -> Request:
        x = np.asarray(x)
        if x.ndim == self.cfg.sample_ndim:
            x = x[None]
        alpha, lane, cost, rows = self.planner.admit(x)
        if self.cfg.policy == "degrade-alpha" \
                and self.queue.depth(lane) >= self.cfg.max_queue:
            alpha = alpha * self.cfg.degrade_factor
            lane, cost = self.planner.classify(alpha)
            self.counters["degraded"] += 1
        payload = {}
        if self.predictor is not None:
            depth, band = self.predictor.admit_info(float(np.mean(alpha)))
            quote = self._quote_ms(depth)
            if (quote is not None and deadline_ms is not None
                    and self.cfg.policy == "degrade-alpha"
                    and quote > deadline_ms):
                # the quote says this request cannot make its SLO at
                # its predicted depth: degrade it at admission instead
                # of letting it miss
                alpha = alpha * self.cfg.degrade_factor
                lane, cost = self.planner.classify(alpha)
                self.counters["degraded"] += 1
                depth, band = self.predictor.admit_info(
                    float(np.mean(alpha)))
                quote = self._quote_ms(depth)
            # predicted-depth lane component: a flushed bucket's rows
            # are predicted to exit together
            lane = (lane, band)
            payload = {"quote_ms": quote, "depth": depth}
            if quote is not None:
                cost = quote    # predicted_cost becomes the SLO quote
        return Request(
            rid=next(self._rid), x=x, n=x.shape[0], alpha=alpha,
            lane=lane, predicted_cost=cost, priority=priority,
            t_submit=now,
            deadline_s=None if deadline_ms is None
            else now + deadline_ms / 1e3,
            future=Future(), payload=payload, rows=rows)

    def _quote_ms(self, depth: float):
        quote_fn = getattr(self.planner, "quote_ms", None)
        return None if quote_fn is None else quote_fn(depth)

    def _infer_batch(self, reqs: list, x, alpha) -> dict:
        """ONE engine call for a flushed run of requests.  Masked
        dispatches pad to the bucket so every consolidation size inside
        a bucket reuses ONE compiled forward; compacted mode buckets its
        stages internally.  A single request larger than the biggest
        bucket goes through unpadded (the sharded engine chunk-splits
        it; the eager forward just runs that shape) — bucket_key would
        raise BatchTooLarge on it."""
        pad_to = self.engine.bucket_key(len(x)) \
            if self.cfg.mode == "masked" \
            and len(x) <= self.engine.compactor.max_bucket else None
        min_exit = 0
        if self.predictor is not None:
            # the bucket's smallest difficulty bounds every row (Eq. 19
            # is monotone in alpha), so one min_exit covers the bucket
            min_exit = self.predictor.min_exit(self.engine,
                                               float(np.min(alpha)))
        return self._engine_call(
            lambda eng: eng.infer(x, mode=self.cfg.mode, record=True,
                                  alpha=alpha, pad_to=pad_to,
                                  min_exit=min_exit))

    def _dispatch(self, reqs: list, reason: str) -> None:
        with span("gather") if OBS.enabled else NULL_SPAN as sp:
            # admission's device rows where every request has them (the
            # engine stacks them on the device), else the host images
            if all(r.rows is not None for r in reqs):
                x, path = tuple(row for r in reqs for row in r.rows), \
                    "device"
            else:
                x, path = np.concatenate([r.x for r in reqs]), "host"
            alpha = np.concatenate([r.alpha for r in reqs])
            if OBS.enabled:
                behind = sum(not _ready(out) for _, out, _, _ in
                             self._inflight)
                sp.set(path=path, behind=behind)
                OBS_A.record_assembly(path, behind)
        t0 = self._clock()
        out = self._infer_batch(reqs, x, alpha)
        # Service EMA from the dispatch call itself: it feeds the
        # deadline slack, so it must not absorb pipeline idle time (a
        # deferred materialization would look like a slow engine).  For
        # a sharded engine the call returns before the device finishes —
        # an underestimate the margin_ms knob exists to cover.
        service = self._clock() - t0
        self._service_s = service if not self._service_s else \
            0.8 * self._service_s + 0.2 * service
        self._inflight.append((reqs, out, t0,
                               OBS.tracer.bid if OBS.enabled else None))
        while len(self._inflight) > self.cfg.pipeline_depth:
            self._complete_safe(*self._inflight.popleft())

    def _drain_one(self) -> bool:
        if not self._inflight:
            return False
        self._complete_safe(*self._inflight.popleft())
        return True

    def _complete_safe(self, reqs, out, t_dispatch, bid=None) -> None:
        try:
            with OBS.tracer.bucket(bid) if OBS.enabled else NULL_SPAN:
                self._complete(reqs, out, t_dispatch)
        except Exception as e:                     # noqa: BLE001
            self.last_error = e
            self.counters["complete_errors"] = \
                self.counters.get("complete_errors", 0) + 1
            OBS_LOG.error("complete", "bucket materialization failed",
                          exc=e, lane=reqs[0].lane,
                          rids=[r.rid for r in reqs[:8]])
            err = e if isinstance(e, DispatchError) else DispatchError(
                "complete", reqs[0].lane, [r.rid for r in reqs], e)
            for r in reqs:
                r.fail(err)

    def _has_inflight(self) -> bool:
        return bool(self._inflight)

    # -- completion -----------------------------------------------------
    def _complete(self, reqs, out, t_dispatch) -> None:
        with span("fetch") if OBS.enabled else NULL_SPAN:
            vals = {k: to_host(out[k], "output") for k in _RESULT_KEYS}
            now = self._clock()
            ends = np.cumsum([r.n for r in reqs])
            lats, missed, results = [], [], []
            for r, a, z in zip(reqs, np.concatenate([[0], ends[:-1]]),
                               ends):
                res = {k: v[a:z] for k, v in vals.items()}
                lat_ms = (now - r.t_submit) * 1e3
                miss = r.deadline_s is not None and now > r.deadline_s
                res.update(latency_ms=lat_ms, deadline_missed=miss,
                           predicted_cost=r.predicted_cost, lane=r.lane)
                lats.append(lat_ms)
                missed.append(miss)
                results.append(res)
        # Telemetry folds BEFORE any future resolves: a caller woken by
        # fut.result() must find its request already in
        # stats()["requests"] (the documented pattern).
        with span("fold") if OBS.enabled else NULL_SPAN:
            self.engine.record_requests(lats, missed)
            self.planner.observe(vals["exit_idx"], vals["alpha"])
            if self.predictor is not None:
                self.predictor.observe(vals["alpha"], vals["exit_idx"])
                self.engine.record_quotes(
                    [r.payload.get("quote_ms") for r in reqs], lats)
                svc = getattr(self.planner, "observe_service", None)
                if svc is not None:
                    svc((now - t_dispatch) * 1e3,
                        float(np.mean(vals["exit_idx"])))
            for r, res in zip(reqs, results):
                self.daes.observe(r.lane, res["conf"], res["macs"],
                                  res["alpha"])
        with span("resolve") if OBS.enabled else NULL_SPAN:
            self.counters["completed"] += len(reqs)
            if OBS.enabled:
                OBS_A.record_completed(self, reqs, results, t_dispatch,
                                       now)
            for r, res in zip(reqs, results):
                r.resolve(res)

    # -- metering -------------------------------------------------------
    def stats(self) -> dict:
        """Engine stats (incl. ``requests`` latency percentiles + miss
        rate, from the engine's request window) + scheduler-level
        counters."""
        s = self.engine.stats()
        s["scheduler"] = {
            **self.counters,
            "shed": self.queue.shed, "rejected": self.queue.rejected,
            "starved": self.queue.starved,
            "queued": {k: self.queue.depth(k) for k in self.queue.keys()},
            "inflight": len(self._inflight),
            "depth_prior": self.planner.priors(),
            "service_ms_ema": self._service_s * 1e3,
        }
        if self.predictor is not None:
            s["scheduler"]["predictor"] = self.predictor.stats()
            stage_fn = getattr(self.planner, "stage_ms", None)
            if stage_fn is not None:
                s["scheduler"]["stage_ms_ema"] = stage_fn()
        s["daes"] = self.daes.rows()
        return s
