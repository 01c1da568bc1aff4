"""repro.obs.adapters — wire every existing serving signal into the
registry and the tracer.

Two kinds of adapter, matching the two ways data flows:

* **push-side** ``record_*`` helpers, called from the scheduler /
  session hot path ONLY behind an ``if OBS.enabled`` check.  They see
  values the serving code already materialized (numpy outputs at
  completion, host counters) — no extra device syncs.
* **pull-side** ``bind_*`` collectors, registered once per object and
  run at SCRAPE time: ``EngineState`` telemetry after the
  ``reduce_telemetry`` fold, per-lane DAES from
  ``LaneDaesAccumulator``, ``trace_counts`` (a recompile in production
  becomes the alertable ``dart_recompiles_total``), kernel dispatch
  decisions from ``repro.kernels.dispatch``, queue depths / starvation
  reservations from ``RequestQueue``, and slot-pool / page-allocator
  occupancy from the continuous decoder.  Collectors hold weakrefs, so
  a garbage-collected server unregisters itself.

Metric catalog: see docs/observability.md.
"""
from __future__ import annotations

import time
import weakref

import numpy as np

from repro.obs import OBS
from repro.obs.metrics import LATENCY_BUCKETS_MS

__all__ = ["record_admit", "record_bucket", "record_completed",
           "record_escalations", "record_lm_bucket", "record_slot_admit",
           "record_slot_exit", "record_retry", "record_hedge",
           "record_requeue", "record_fault", "bind_scheduler",
           "bind_dispatch", "bind_pool"]


def _lane(lane) -> str:
    return str(lane)


def _latency_hist(reg):
    return reg.histogram("dart_request_latency_ms",
                         "end-to-end request latency by lane",
                         ("lane",), buckets=LATENCY_BUCKETS_MS)


# ---------------------------------------------------------------------------
# push side (hot path; callers guard with OBS.enabled)
# ---------------------------------------------------------------------------

def record_admit(sched, req, action: str, span) -> None:
    """One admitted (or dropped-at-admission) request, called inside its
    ``admit`` span: the span covers the admission work itself (the
    Eq. 8 estimate, with its ``put`` and ``sync`` children) and the
    queue push."""
    lane = _lane(req.lane)
    alpha = float(np.mean(req.alpha)) if req.n else 0.0
    span.set(rid=req.rid, lane=req.lane, n=req.n, alpha=alpha,
             predicted_cost=float(req.predicted_cost),
             priority=req.priority, action=action)
    t1 = sched._clock()
    reg = OBS.registry
    reg.counter("dart_requests_total", "requests submitted by lane",
                ("lane",)).inc(1, lane=lane)
    if action in ("shed", "rejected"):
        OBS.tracer.record("shed" if action == "shed" else "reject",
                          ts=t1, rid=req.rid, lane=req.lane, n=req.n)
        reg.counter("dart_requests_dropped_total",
                    "requests dropped at admission (backpressure)",
                    ("lane", "action")).inc(1, lane=lane, action=action)


def record_bucket(sched, reqs: list, reason: str, now: float,
                  bid: int, rows: dict) -> None:
    """One flushed bucket: its id, which lane, which requests and how
    many samples, WHY it flushed (deadline pressure / size / hold /
    forced), and how ``RequestQueue.take`` came by its samples
    (``rows``: ``fifo`` / ``skip_ahead`` / ``other_lane``)."""
    OBS.tracer.record("bucket", ts=now, lane=reqs[0].lane, bid=bid,
                      rids=[r.rid for r in reqs], n_requests=len(reqs),
                      n_samples=sum(r.n for r in reqs), reason=reason,
                      **rows)
    OBS.registry.counter("dart_flushes_total", "bucket flushes by reason",
                         ("reason",)).inc(1, reason=reason)
    by_order = OBS.registry.counter(
        "dart_bucket_rows_total",
        "bucket samples by how the flush took them", ("order",))
    for order, n in rows.items():
        by_order.inc(n, order=order)


def record_assembly(path: str, behind: int) -> None:
    """Where one dispatched bucket's images were assembled: ``device``
    (stacked from admission's device rows) or ``host`` (concatenated
    host images); and how many buckets were still running on the
    device when it launched (``behind``)."""
    OBS.registry.counter("dart_bucket_assembly_total",
                         "dispatched buckets by where their images were "
                         "assembled", ("path",)).inc(1, path=path)
    OBS.registry.counter("dart_buckets_launched_total",
                         "dispatched buckets by the number of earlier "
                         "buckets whose outputs were not ready yet",
                         ("behind",)).inc(1, behind=str(behind))


def record_completed(server, reqs: list, results: list, t_dispatch: float,
                     now: float) -> None:
    """Completed requests of one materialized bucket: spans
    ``queue_wait`` (submit -> dispatch) and ``compiled_step``
    (dispatch -> materialized), both with the bucket's ``bid``, plus
    the ``exit`` span joining the host-side view (predicted cost,
    deadline slack) with the realized exit depths the engine
    computed."""
    reg, tr = OBS.registry, OBS.tracer
    bid = tr.bid
    hist = _latency_hist(reg)
    comp = reg.counter("dart_requests_completed_total",
                       "requests completed by lane", ("lane",))
    miss_c = reg.counter("dart_deadline_miss_total",
                         "deadline misses by lane", ("lane",))
    exits = reg.counter("dart_exits_total",
                        "served samples by cascade member and exit stage",
                        ("member", "stage"))
    for r, res in zip(reqs, results):
        lane = _lane(r.lane)
        exit_idx = np.asarray(res["exit_idx"]).ravel()
        members = np.asarray(res["member"]).ravel() \
            if "member" in res else np.zeros(exit_idx.shape, np.int64)
        slack = None if r.deadline_s is None else r.deadline_s - now
        tr.record("queue_wait", ts=r.t_submit,
                  dur=max(t_dispatch - r.t_submit, 0.0),
                  rid=r.rid, lane=r.lane, bid=bid)
        tr.record("compiled_step", ts=t_dispatch,
                  dur=max(now - t_dispatch, 0.0), rid=r.rid, lane=r.lane,
                  n=r.n, bid=bid)
        tr.record("exit", ts=now, rid=r.rid, lane=r.lane,
                  exits=exit_idx.tolist(), members=members.tolist(),
                  predicted_cost=float(r.predicted_cost),
                  realized_cost=float(np.mean(np.asarray(res["macs"]))),
                  deadline_slack_s=slack,
                  deadline_missed=bool(res["deadline_missed"]))
        hist.observe(float(res["latency_ms"]), lane=lane)
        comp.inc(1, lane=lane)
        if res["deadline_missed"]:
            miss_c.inc(1, lane=lane)
        for m in np.unique(members):
            sel = members == m
            for s in np.unique(exit_idx[sel]):
                exits.inc(int(np.sum(exit_idx[sel] == s)),
                          member=str(int(m)), stage=str(int(s)))


def record_escalations(member: int, continuations: list,
                       now: float) -> None:
    """Cascade escalations re-enqueued into the next member's lanes.
    ``continuations``: (root, idx, x, alpha, next_member) tuples, as
    assembled by ``CascadeAsyncServer._complete``."""
    esc = OBS.registry.counter(
        "dart_escalations_total",
        "samples escalated past a cascade boundary", ("member",))
    for root, idx, x, a_esc, nxt in continuations:
        n = int(x.shape[0])
        OBS.tracer.record("escalate", ts=now, rid=root.rid,
                          lane=root.lane, n=n, member=member,
                          to_member=int(nxt),
                          alpha=float(np.mean(a_esc)) if n else 0.0)
        esc.inc(n, member=str(member))


def record_lm_bucket(session, reqs: list, stage_slices: list, t0: float,
                     now: float) -> None:
    """One flushed LM decode bucket: per-request spans with realized
    per-token exit stages."""
    reg, tr = OBS.registry, OBS.tracer
    hist = _latency_hist(reg)
    comp = reg.counter("dart_requests_completed_total",
                       "requests completed by lane", ("lane",))
    toks = reg.counter("dart_lm_tokens_total", "decoded tokens", ())
    for r, stages in zip(reqs, stage_slices):
        lane = _lane(r.lane)
        stages = np.asarray(stages)
        tr.record("queue_wait", ts=r.t_submit,
                  dur=max(t0 - r.t_submit, 0.0), rid=r.rid, lane=r.lane)
        tr.record("compiled_step", ts=t0, dur=max(now - t0, 0.0),
                  rid=r.rid, lane=r.lane, n=r.n)
        tr.record("exit", ts=now, rid=r.rid, lane=r.lane,
                  exits=stages.ravel().tolist(),
                  n_tokens=int(stages.size),
                  predicted_cost=float(r.predicted_cost),
                  deadline_slack_s=None if r.deadline_s is None
                  else r.deadline_s - now)
        hist.observe((now - r.t_submit) * 1e3, lane=lane)
        comp.inc(1, lane=lane)
        toks.inc(int(stages.size))


def record_slot_admit(session, req, now: float) -> None:
    """Continuous batching: a request entered the slot pool — the
    ``slot`` span carries its slot ids and the pool pressure."""
    slots = None
    slots_of = getattr(session.decoder, "slots_of", None)
    if slots_of is not None:
        slots = slots_of(req.rid)
    OBS.tracer.record("slot", ts=now, dur=0.0, rid=req.rid,
                      lane=req.lane, slots=slots,
                      pages_in_use=session.decoder.allocator.in_use,
                      queue_wait_s=max(now - req.t_submit, 0.0))


def record_slot_exit(session, req, stages, lat_ms: float, miss: bool,
                     now: float) -> None:
    reg, tr = OBS.registry, OBS.tracer
    lane = _lane(req.lane)
    stages = np.asarray(stages)
    tr.record("exit", ts=now, rid=req.rid, lane=req.lane,
              exits=stages.ravel().tolist(), n_tokens=int(stages.size),
              deadline_missed=bool(miss),
              deadline_slack_s=None if req.deadline_s is None
              else req.deadline_s - now)
    _latency_hist(reg).observe(lat_ms, lane=lane)
    reg.counter("dart_requests_completed_total",
                "requests completed by lane", ("lane",)).inc(1, lane=lane)
    if miss:
        reg.counter("dart_deadline_miss_total",
                    "deadline misses by lane", ("lane",)).inc(1, lane=lane)
    reg.counter("dart_lm_tokens_total", "decoded tokens",
                ()).inc(int(stages.size))


def record_retry(engine: str, attempt: int) -> None:
    """One retried dispatch (the engine pool re-running a bucket on
    another engine after a failure)."""
    OBS.tracer.record("retry", ts=time.monotonic(), engine=engine,
                      attempt=attempt)
    OBS.registry.counter("dart_retries_total",
                         "bucket dispatch retries by engine",
                         ("engine",)).inc(1, engine=engine)


def record_hedge(slow: str, to: str) -> None:
    """One hedged re-dispatch: the straggler-policy deadline expired on
    ``slow`` and the bucket was duplicated onto ``to``."""
    OBS.tracer.record("hedge", ts=time.monotonic(), slow=slow, to=to)
    OBS.registry.counter("dart_hedges_total",
                         "hedged straggler re-dispatches by slow engine",
                         ("engine",)).inc(1, engine=slow)


def record_requeue(n_requests: int) -> None:
    """One dead-engine bucket requeue (backpressure-bypassing)."""
    OBS.tracer.record("requeue", ts=time.monotonic(),
                      n_requests=n_requests)
    OBS.registry.counter("dart_requeues_total",
                         "requests requeued after losing their engine",
                         ()).inc(n_requests)


def record_fault(point: str, kind: str, engine) -> None:
    """One injected fault firing (chaos runs only)."""
    OBS.tracer.record("fault", ts=time.monotonic(), point=point,
                      kind=kind, engine=engine)
    OBS.registry.counter("dart_faults_injected_total",
                         "chaos faults injected by cut point and kind",
                         ("point", "kind")).inc(1, point=point, kind=kind)


# ---------------------------------------------------------------------------
# pull side (scrape-time collectors)
# ---------------------------------------------------------------------------

def bind_scheduler(sched, name: str | None = None) -> None:
    """Register a scrape-time collector exporting everything the
    scheduler (and the engine behind it) already knows.  Weakly bound:
    the collector unregisters itself once the scheduler is collected."""
    if name is None:
        name = type(sched).__name__
    ref = weakref.ref(sched)

    def collect(reg):
        obj = ref()
        if obj is None:
            return "dead"
        _collect_scheduler(reg, obj, name)
        return None

    OBS.registry.register_collector(collect)


def _collect_scheduler(reg, sched, name: str) -> None:
    # scheduler counters (submitted/completed/flush_*/degraded/...)
    ev = reg.counter("dart_scheduler_events_total",
                     "scheduler counters by event", ("event",))
    for k, v in sched.counters.items():
        ev.set_total(v, event=k)
    q = sched.queue
    ev.set_total(q.shed, event="shed")
    ev.set_total(q.rejected, event="rejected")
    ev.set_total(getattr(q, "starved", 0), event="starved")
    depth = reg.gauge("dart_queue_depth", "queued requests by lane",
                      ("lane",))
    for k in q.keys():
        depth.set(q.depth(k), lane=_lane(k))
    if hasattr(sched, "_inflight"):
        reg.gauge("dart_inflight",
                  "dispatched, unmaterialized buckets").set(
            len(sched._inflight))
    if getattr(sched, "_service_s", None) is not None:
        reg.gauge("dart_service_ms_ema",
                  "EMA of bucket service time").set(
            sched._service_s * 1e3)

    # per-lane DAES (Eq. 9) from the streaming accumulator
    daes = getattr(sched, "daes", None)
    if daes is not None:
        for lane, row in daes.rows().items():
            for col in ("daes", "speedup", "power_eff", "acc_pct", "n"):
                reg.gauge(f"dart_lane_{col}",
                          f"per-lane {col} (Eq. 9 telemetry)",
                          ("lane",)).set(float(row[col]),
                                         lane=_lane(lane))

    # admission-planner depth priors
    planner = getattr(sched, "planner", None)
    if planner is not None:
        pri = planner.priors()
        gd = reg.gauge("dart_depth_prior",
                       "admission planner expected exit depth",
                       ("member", "dclass"))
        if isinstance(pri, dict):                  # cascade planner
            for m, per in enumerate(pri["depth"]):
                for c, d in enumerate(per):
                    if d is not None:
                        gd.set(d, member=str(m), dclass=str(c))
            ge = reg.gauge("dart_escalation_ema",
                           "per-(boundary, class) escalation-rate EMA",
                           ("member", "dclass"))
            for m, per in enumerate(pri["escalation"]):
                for c, r in enumerate(per):
                    if r is not None:
                        ge.set(r, member=str(m), dclass=str(c))
        else:
            for c, d in enumerate(pri):
                if d is not None:
                    gd.set(d, member="0", dclass=str(c))

    # exit-depth predictor (ISSUE 9): hit/miss + head-skip counters
    predictor = getattr(sched, "predictor", None)
    if predictor is not None:
        ps = predictor.stats()
        pe = reg.counter("dart_predictor_events_total",
                         "exit-depth predictor counters by event",
                         ("event",))
        for k in ("hits", "misses", "skip_calls", "skip_stages",
                  "observed"):
            pe.set_total(ps[k], event=k)
        if ps["hit_rate"] is not None:
            reg.gauge("dart_predictor_hit_rate",
                      "fraction of requests whose predicted depth band "
                      "matched the realized exit").set(ps["hit_rate"])
        # admission-quote error (quote vs realized latency), from the
        # engine's request window
        win = getattr(getattr(sched, "engine", None), "request_window",
                      None)
        quote = win.stats().get("quote") if win is not None else None
        if quote is not None:
            reg.gauge("dart_quote_mean_abs_err_ms",
                      "mean |admission quote - realized latency|"
                      ).set(quote["mean_abs_err_ms"])
            reg.gauge("dart_quote_mean_ms",
                      "mean admission-time latency quote").set(
                quote["mean_quote_ms"])

    # engine telemetry (after the reduce_telemetry fold inside stats())
    engine = getattr(sched, "engine", None)
    if engine is None:
        return
    members = getattr(engine, "members", None)
    if members is not None:
        for i, m in enumerate(members):
            _collect_engine(reg, m, f"{name}.m{i}")
    else:
        _collect_engine(reg, engine, name)

    # continuous decoder slot/page occupancy
    decoder = getattr(sched, "decoder", None)
    if decoder is not None:
        for k, v in decoder.occupancy().items():
            reg.gauge(f"dart_{k}",
                      "continuous-batching pool occupancy").set(v)


def _collect_engine(reg, engine, name: str) -> None:
    st = engine.stats()
    reg.counter("dart_engine_served_total", "samples served by engine",
                ("engine",)).set_total(st["served"], engine=name)
    reg.gauge("dart_engine_mean_macs", "mean normalized MACs per sample",
              ("engine",)).set(st["mean_macs"], engine=name)
    exits = reg.counter("dart_engine_exits_total",
                        "EngineState exit histogram by stage",
                        ("engine", "stage"))
    for s, c in enumerate(np.asarray(st["exit_counts"]).ravel()):
        exits.set_total(int(c), engine=name, stage=str(s))
    req = st.get("requests")
    if req:
        lm = req["latency_ms"]
        g = reg.gauge("dart_engine_latency_ms",
                      "EngineState latency-ring percentiles",
                      ("engine", "quantile"))
        for qk in ("p50", "p95", "p99", "mean"):
            g.set(lm[qk], engine=name, quantile=qk)
        reg.gauge("dart_engine_miss_rate", "deadline miss rate",
                  ("engine",)).set(req["miss_rate"], engine=name)
    tc = getattr(engine, "trace_counts", None) or {}
    fam = reg.counter("dart_trace_total",
                      "compiled-step traces by cache key",
                      ("engine", "key"))
    for key, c in tc.items():
        fam.set_total(c, engine=name, key=repr(key))
    reg.counter("dart_recompiles_total",
                "re-traces of an already-compiled step key "
                "(alertable: should stay 0)",
                ("engine",)).set_total(
        sum(max(0, c - 1) for c in tc.values()), engine=name)


def bind_pool(pool) -> None:
    """Register a scrape-time collector for an
    :class:`~repro.serving.resilience.EnginePool`: per-engine health
    gauges (2 healthy / 1 degraded / 0 dead-or-drained), the chaos /
    retry / hedge / requeue / quarantine totals, the degradation-ladder
    rung, and the straggler-policy hedge deadline.  Weakly bound, like
    ``bind_scheduler``."""
    ref = weakref.ref(pool)

    def collect(reg):
        obj = ref()
        if obj is None:
            return "dead"
        from repro.serving.resilience import HEALTH_LEVEL
        st = obj.stats()
        health = reg.gauge("dart_engine_health",
                           "pool engine health (2 healthy / 1 degraded "
                           "/ 0 dead or drained)", ("engine",))
        for name, state in st["engines"].items():
            health.set(HEALTH_LEVEL[state], engine=name)
        reg.gauge("dart_degradation_rung",
                  "graceful-degradation ladder rung (0 = full service)"
                  ).set(st["rung"])
        ev = reg.counter("dart_pool_events_total",
                         "engine-pool counters by event", ("event",))
        for k in ("calls", "retries", "hedges", "requeues",
                  "quarantined", "deaths", "stragglers", "joins",
                  "drains"):
            ev.set_total(st[k], event=k)
        reg.counter("dart_retries_total",
                    "bucket dispatch retries by engine",
                    ("engine",)).set_total(st["retries"], engine="_pool")
        reg.counter("dart_hedges_total",
                    "hedged straggler re-dispatches by slow engine",
                    ("engine",)).set_total(st["hedges"], engine="_pool")
        reg.counter("dart_faults_injected_total",
                    "chaos faults injected by cut point and kind",
                    ("point", "kind")).set_total(
            st["faults_injected"], point="_all", kind="_all")
        if st["straggler_deadline_ms"] is not None:
            reg.gauge("dart_hedge_deadline_ms",
                      "straggler-policy rolling-median hedge deadline"
                      ).set(st["straggler_deadline_ms"])
        return None

    OBS.registry.register_collector(collect)


def bind_dispatch(reg) -> None:
    """Export ``repro.kernels.dispatch`` backend decisions (pallas /
    pallas-interpret / xla selection counts — the xla ones are the
    fallback counter)."""

    def collect(reg):
        from repro.kernels import dispatch as KD
        fam = reg.counter("dart_kernel_dispatch_total",
                          "kernel backend dispatch decisions",
                          ("kernel", "backend"))
        for (kernel, backend), c in KD.dispatch_counts().items():
            fam.set_total(c, kernel=kernel, backend=backend)
        return None

    reg.register_collector(collect)
