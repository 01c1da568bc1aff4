"""Cascade serving — the async scheduler over a CascadeEngine.

``AsyncDartServer(cascade)`` transparently constructs
:class:`CascadeAsyncServer` (the façade's ``__new__`` dispatches here).
The request lifecycle grows one loop over the plain scheduler's:

    submit ──admit──▶ (member, class) lane ──flush──▶ member bucket
          (Eq. 8 α +      │                               │
           member choice) │   ┌──── escalate? ────────────┘
                          ◀───┘ re-enqueue @ (member+1, class(α'))
                                α' = escalation prior
                          └──▶ all samples terminal → resolve future

* **Admission** — :class:`CascadePlanner` routes each request to the
  CHEAPEST member whose per-(member, class) escalation prior predicts
  termination (cold start: the smallest member), and predicts cascade
  cost as the escalation-rate-weighted sum of member costs.
* **Dispatch** — one engine call per flushed (member, bucket) lane via
  ``CascadeEngine.infer_member`` (the member pads with its OWN
  bucket_key, so the per-member trace-count guarantees hold).
* **Escalation** — completed buckets apply the cascade's elementwise
  escalation gate; escalated samples re-enqueue as CONTINUATION
  requests into the next member's lane (``RequestQueue.requeue``:
  already-admitted work bypasses backpressure), carrying the
  escalation-prior alpha.  A request's future resolves only when every
  sample is terminal; outputs are assembled per sample into the ROOT
  request's buffer, so partial escalation inside one request works.
* **Telemetry** — per-member depth priors + escalation EMAs fold per
  bucket; request latency/SLO and per-(terminal member, class) DAES
  fold when a ROOT resolves.
"""
from __future__ import annotations

import threading
from concurrent.futures import Future

import jax.numpy as jnp
import numpy as np

from repro.core import difficulty as DIFF
from repro.obs import OBS
from repro.obs import adapters as OBS_A
from repro.obs import log as OBS_LOG
from repro.serving.loop import _RESULT_KEYS, AsyncDartServer
from repro.serving.planner import AdmissionPlanner
from repro.serving.request import Request


class CascadePlanner:
    """Admission planning for a cascade: difficulty class + member
    choice + cascade-cost prediction.

    Wraps one :class:`AdmissionPlanner` per member (the per-class exit-
    depth EMAs stay per member) and adds the cross-member state: a
    per-(boundary, class) escalation-rate EMA.  ``admit``/``classify``
    return the same ``(alpha, lane, cost)``/``(lane, cost)`` shapes the
    base scheduler consumes — the lane is ``(member, class)``."""

    def __init__(self, cascade, edges=DIFF.DEFAULT_EDGES,
                 ema_decay: float = 0.9, escalation_cut: float = 0.5):
        self.cascade = cascade
        self.edges = np.asarray(edges, np.float32)
        self.n_classes = len(self.edges) + 1
        self.ema_decay = float(ema_decay)
        self.escalation_cut = float(escalation_cut)
        self.members = [AdmissionPlanner(m, edges=edges,
                                         ema_decay=ema_decay)
                        for m in cascade.members]
        self._esc_ema = [[None] * self.n_classes
                         for _ in cascade.members[:-1]]
        self._lock = threading.Lock()

    # -- admission ------------------------------------------------------
    def admit(self, x):
        """(alpha (n,), lane=(member, class), predicted cascade cost,
        None): members are served from the host images (escalations
        slice them), so no device rows are kept."""
        alpha = np.asarray(self.cascade._alpha(jnp.asarray(x)),
                           np.float32)
        return (alpha,) + self.classify(alpha) + (None,)

    def classify(self, alpha):
        """(lane, cost) for a known alpha (degrade-alpha re-admission)."""
        a = float(np.mean(alpha))
        dclass = int(DIFF.difficulty_class(a, self.edges))
        member = self.choose_member(dclass)
        return (member, dclass), self.predicted_cost(member, a, dclass)

    def classify_escalated(self, member: int, alpha):
        """Lane + cost for an escalation into ``member`` — the class is
        re-derived from the escalation-prior alpha (a sample that looked
        easy but stumped the small member IS hard traffic now)."""
        a = float(np.mean(alpha))
        dclass = int(DIFF.difficulty_class(a, self.edges))
        return (member, dclass), self.predicted_cost(member, a, dclass)

    def choose_member(self, dclass: int) -> int:
        """Cheapest member whose per-class prior predicts termination:
        walk small → large, skipping members whose observed escalation
        rate for this class exceeds the cut (admitting there would just
        pay the small model AND escalate).  Cold start is optimistic —
        the smallest member."""
        with self._lock:
            for m in range(len(self.cascade.members) - 1):
                r = self._esc_ema[m][dclass]
                if r is None or r < self.escalation_cut:
                    return m
        return len(self.cascade.members) - 1

    def predicted_cost(self, member: int, alpha_mean: float,
                       dclass: int) -> float:
        """Expected cascade MACs/sample from ``member`` on: each visited
        member's within-member predicted cost (its planner's depth
        prior) scaled to cascade units, weighted by the probability of
        reaching it (product of escalation-rate EMAs; unseen = 0)."""
        mc = self.cascade.member_costs
        cost, p_reach = 0.0, 1.0
        for m in range(member, len(mc)):
            cost += p_reach * float(mc[m]) \
                * self.members[m].predicted_cost(alpha_mean, dclass)
            if m == len(mc) - 1:
                break
            with self._lock:
                r = self._esc_ema[m][dclass]
            p_reach *= 0.0 if r is None else r
            if p_reach <= 0.0:
                break
        return float(cost)

    # -- telemetry fold -------------------------------------------------
    def observe(self, member: int, exit_idx, alpha) -> None:
        """Fold one served member-bucket into that member's depth
        priors."""
        self.members[member].observe(exit_idx, alpha)

    def observe_escalation(self, member: int, dclass: int,
                           esc_mask) -> None:
        """Fold a bucket's escalation fraction into the (member, class)
        EMA that drives ``choose_member``/``predicted_cost``."""
        r = float(np.mean(esc_mask))
        with self._lock:
            prev = self._esc_ema[member][dclass]
            self._esc_ema[member][dclass] = r if prev is None else \
                self.ema_decay * prev + (1.0 - self.ema_decay) * r

    def priors(self) -> dict:
        """Depth priors per member + escalation-rate EMAs per boundary."""
        with self._lock:
            esc = [list(row) for row in self._esc_ema]
        return {"depth": [p.priors() for p in self.members],
                "escalation": esc}

    # -- snapshot (serving-state checkpoint) ----------------------------
    def state_dict(self) -> dict:
        with self._lock:
            esc = [list(row) for row in self._esc_ema]
        return {"members": [p.state_dict() for p in self.members],
                "escalation": esc}

    def load_state_dict(self, state: dict) -> None:
        for p, s in zip(self.members, state["members"]):
            p.load_state_dict(s)
        with self._lock:
            for row, saved in zip(self._esc_ema, state["escalation"]):
                row[:] = list(saved)


class CascadeAsyncServer(AsyncDartServer):
    """The async scheduler over a :class:`CascadeEngine` — construct it
    as ``AsyncDartServer(cascade_engine, cfg)``; the façade dispatches
    here.  Same submit/close/stats surface; results additionally carry
    ``member`` (per-sample terminal member) and ``macs`` in cascade
    units (biggest member full network = 1.0)."""

    def _make_planner(self, cfg):
        return CascadePlanner(self.engine, edges=cfg.edges)

    def _mixable_lanes(self, key) -> list:
        """None: a lane names the member its bucket runs on."""
        return []

    # -- dispatch -------------------------------------------------------
    def _infer_batch(self, reqs: list, x, alpha) -> dict:
        member = reqs[0].lane[0]
        eng = self.engine.members[member]
        pad_to = eng.bucket_key(x.shape[0]) \
            if self.cfg.mode == "masked" \
            and x.shape[0] <= eng.compactor.max_bucket else None
        return self._engine_call(
            lambda cas: cas.infer_member(member, x, alpha=alpha,
                                         mode=self.cfg.mode, record=True,
                                         pad_to=pad_to))

    # -- completion -----------------------------------------------------
    def _root_buffer(self, root: Request) -> dict:
        buf = root.payload.get("buf")
        if buf is None:
            n = root.n
            buf = {"pred": np.zeros(n, np.int64),
                   "conf": np.zeros(n, np.float32),
                   "exit_idx": np.zeros(n, np.int64),
                   "member": np.zeros(n, np.int64),
                   "macs": np.zeros(n, np.float64),
                   "alpha": np.asarray(root.alpha, np.float32).copy(),
                   "remaining": n}
            root.payload["buf"] = buf
        return buf

    def _complete(self, reqs, out, t_dispatch) -> None:
        vals = {k: np.asarray(out[k]) for k in _RESULT_KEYS}
        member = reqs[0].lane[0]
        dclass = reqs[0].lane[1]
        last = len(self.engine.members) - 1
        now = self._clock()

        # elementwise escalation gate on the member's terminal decisions
        # (vals["alpha"] is what THIS member admitted under: the raw
        # Eq. 8 alpha at member 0, the escalation prior after)
        esc_all = self.engine.should_escalate(member, vals["conf"],
                                              vals["alpha"])
        macs_all = self.engine.member_macs(member, vals["exit_idx"])

        # telemetry folds BEFORE any future resolves (the documented
        # pattern: a caller woken by fut.result() finds its request
        # already in stats())
        self.planner.observe(member, vals["exit_idx"], vals["alpha"])
        if member < last:
            self.planner.observe_escalation(member, dclass, esc_all)
        self.engine.fold(member, int(esc_all.sum()),
                         float(macs_all.sum()),
                         n_admitted=sum(r.n for r in reqs
                                        if "root" not in r.payload))

        continuations, finished = [], []
        ends = np.cumsum([r.n for r in reqs])
        for r, a, z in zip(reqs, np.concatenate([[0], ends[:-1]]), ends):
            sl = {k: v[a:z] for k, v in vals.items()}
            esc = esc_all[a:z] if member < last \
                else np.zeros(r.n, bool)
            root = r.payload.get("root", r)
            idx = r.payload.get("idx")
            if idx is None:
                idx = np.arange(r.n)
            buf = self._root_buffer(root)
            buf["macs"][idx] += macs_all[a:z]
            term = ~esc
            for k in ("pred", "conf", "exit_idx"):
                buf[k][idx[term]] = sl[k][term]
            buf["member"][idx[term]] = member
            buf["remaining"] -= int(term.sum())
            if esc.any():
                new_alpha = self.engine.escalation_alpha(
                    sl["alpha"][esc], sl["conf"][esc])
                continuations.append((root, idx[esc], r.x[esc],
                                      new_alpha, member + 1))
            if buf["remaining"] == 0:
                finished.append((root, buf))

        # escalations re-enqueue into the larger member's lanes,
        # bypassing backpressure (already-admitted work)
        for root, idx_esc, x_esc, a_esc, nxt in continuations:
            lane, cost = self.planner.classify_escalated(nxt, a_esc)
            cont = Request(
                rid=next(self._rid), x=x_esc, n=x_esc.shape[0],
                alpha=a_esc, lane=lane, predicted_cost=cost,
                priority=root.priority, t_submit=root.t_submit,
                deadline_s=root.deadline_s, future=Future(),
                payload={"root": root, "idx": idx_esc})
            # nobody awaits a continuation's own future — a dispatch
            # failure must surface on the ROOT future instead
            cont.future.add_done_callback(
                self._make_root_failer(root, cont))
            self.queue.requeue(cont)
            self.counters["escalated"] = \
                self.counters.get("escalated", 0) + cont.n
        if OBS.enabled and continuations:
            OBS_A.record_escalations(member, continuations, now)

        lats, missed, resolutions = [], [], []
        for root, buf in finished:
            lat_ms = (now - root.t_submit) * 1e3
            miss = root.deadline_s is not None and now > root.deadline_s
            res = {k: buf[k] for k in ("pred", "conf", "exit_idx",
                                       "member", "alpha", "macs")}
            res.update(latency_ms=lat_ms, deadline_missed=miss,
                       predicted_cost=root.predicted_cost,
                       lane=root.lane)
            lats.append(lat_ms)
            missed.append(miss)
            # DAES keyed by (TERMINAL member, admission class): cascade-
            # total macs are attributed to the member that resolved the
            # sample (it carries the smaller members' spend with it)
            for m in np.unique(buf["member"]):
                sel = buf["member"] == m
                self.daes.observe((int(m), int(root.lane[1])),
                                  buf["conf"][sel], buf["macs"][sel],
                                  buf["alpha"][sel])
            resolutions.append((root, res))
        if lats:
            self.engine.record_requests(lats, missed)
        self.counters["completed"] += len(finished)
        if OBS.enabled and resolutions:
            OBS_A.record_completed(self, [r for r, _ in resolutions],
                                   [res for _, res in resolutions],
                                   t_dispatch, now)
        for root, res in resolutions:
            root.resolve(res)

    @staticmethod
    def _make_root_failer(root: Request, cont: Request):
        """Done-callback propagating a continuation's failure to its
        ROOT future — logged, because the root caller only sees the
        exception, not WHICH member's continuation died."""
        def fail_root(f):
            exc = f.exception()
            if exc is None:
                return
            OBS_LOG.error("cascade", "escalation continuation failed",
                          exc=exc, rid=root.rid, cont_rid=cont.rid,
                          lane=cont.lane)
            root.fail(exc)
        return fail_root

    # -- shutdown -------------------------------------------------------
    def flush(self) -> None:
        """The base flush drains the queue then materializes in-flight
        buckets — but materializing can RE-ENQUEUE escalations, so loop
        until no member has pending work (terminates: the member index
        strictly increases per escalation)."""
        while True:
            super().flush()
            if self.queue.empty and not self._inflight:
                break
