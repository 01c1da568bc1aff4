"""EngineState — the complete DART serving state as ONE pytree.

Consolidates what used to live in three places (`DartParams` on the
server object, the raw `core.adaptive.init_state` dict, and ad-hoc
`ServerStats` counters) into a single registered pytree so the full
serving state can be jitted over, checkpointed through
``repro.checkpoint`` (flatten → leaf files → unflatten), and sharded as
one object.

Every field is a leaf (jnp array); scalar knobs like ``beta_diff`` are
stored as 0-d arrays so the state round-trips through
``checkpoint.save``/``restore`` without special-casing.
"""
from __future__ import annotations

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import adaptive as AD
from repro.core.routing import DartParams

_FIELDS = ("tau", "coef", "beta_diff", "beta_opt", "adaptive",
           "served", "exit_counts", "total_macs", "since_update",
           "lat_ms", "lat_ptr", "lat_count", "deadline_miss",
           "slot_steps", "decode_steps", "pages_peak",
           "quote_ms_sum", "quote_err_ms_sum", "quote_count")

#: The pre-latency-telemetry field set.  New telemetry leaves are only
#: ever APPENDED to ``_FIELDS``, so every older checkpoint is a strict
#: prefix of the current flatten order — ``restore_with_migration``
#: walks ``_LAYOUT_PREFIXES`` newest-first (restored prefix fields +
#: fresh values for the rest).
LEGACY_FIELDS = _FIELDS[:-10]

#: Known historical flatten orders, newest first: the continuous-
#: batching era (PRs 7-8, before the admission-quote counters), the
#: latency-telemetry era (PRs 4-6, before the slot/page counters) and
#: the pre-latency era.  Trying the longer prefix first is what keeps a
#: latency-era checkpoint from silently dropping its latency window.
_LAYOUT_PREFIXES = (_FIELDS[:-3], _FIELDS[:-6], LEGACY_FIELDS)

#: Default size of the per-request latency ring buffer (requests, not
#: samples — sized for percentile stability, not history).
LAT_WINDOW = 2048


@dataclasses.dataclass
class EngineState:
    """Threshold parameters + §II.C sliding-window state + serving counters.

    tau / coef:   (E-1,) Eq. 19 base thresholds and coefficients
    beta_diff:    () difficulty sensitivity (Eq. 19)
    beta_opt:     () accuracy/cost trade-off (Eq. 10)
    adaptive:     the raw ``core.adaptive.init_state`` dict (ring buffers,
                  per-class coefficients, UCB1 counters)
    served:       () int32 — total samples served
    exit_counts:  (E,) int32 — per-exit routed counts
    total_macs:   () float32 — cumulative MACs actually spent
    since_update: () int32 — samples since the last periodic update
    lat_ms:       (W,) float32 — per-REQUEST latency ring buffer
    lat_ptr:      () int32 — latency ring write cursor
    lat_count:    () int32 — requests completed (lifetime)
    deadline_miss: () int32 — requests completed past their deadline
                  (these four and the three ``quote_*`` leaves are the
                  checkpoint image of the engine's host-side
                  :class:`RequestWindow`; empty in a live engine's state)
    slot_steps:   () int32 — continuous batching: occupied slot-steps
                  (sum over decode steps of active slots; folded on
                  device inside the compiled step)
    decode_steps: () int32 — continuous batching: compiled decode-step
                  launches
    pages_peak:   () int32 — continuous batching: peak KV pages in use
                  (host-written at admission)
    quote_ms_sum: () float32 — sum of admission-time latency quotes for
                  completed quoted requests
    quote_err_ms_sum: () float32 — sum of |quote - realized latency|
                  over the same requests (the SLO quote error)
    quote_count:  () int32 — completed requests that carried a quote
    """
    tau: jnp.ndarray
    coef: jnp.ndarray
    beta_diff: jnp.ndarray
    beta_opt: jnp.ndarray
    adaptive: dict
    served: jnp.ndarray
    exit_counts: jnp.ndarray
    total_macs: jnp.ndarray
    since_update: jnp.ndarray
    lat_ms: jnp.ndarray
    lat_ptr: jnp.ndarray
    lat_count: jnp.ndarray
    deadline_miss: jnp.ndarray
    slot_steps: jnp.ndarray
    decode_steps: jnp.ndarray
    pages_peak: jnp.ndarray
    quote_ms_sum: jnp.ndarray
    quote_err_ms_sum: jnp.ndarray
    quote_count: jnp.ndarray

    # -- pytree protocol ------------------------------------------------
    def tree_flatten(self):
        return tuple(getattr(self, f) for f in _FIELDS), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(**dict(zip(_FIELDS, children)))

    # -- construction ---------------------------------------------------
    @classmethod
    def create(cls, n_exits: int, acfg: AD.AdaptiveConfig,
               dart: DartParams | None = None,
               lat_window: int = LAT_WINDOW) -> "EngineState":
        dart = dart or DartParams.default(n_exits)
        return cls(
            tau=jnp.asarray(dart.tau, jnp.float32),
            coef=jnp.asarray(dart.coef, jnp.float32),
            beta_diff=jnp.asarray(dart.beta_diff, jnp.float32),
            beta_opt=jnp.asarray(dart.beta_opt, jnp.float32),
            adaptive=AD.init_state(acfg),
            served=jnp.zeros((), jnp.int32),
            exit_counts=jnp.zeros((n_exits,), jnp.int32),
            total_macs=jnp.zeros((), jnp.float32),
            since_update=jnp.zeros((), jnp.int32),
            lat_ms=jnp.zeros((lat_window,), jnp.float32),
            lat_ptr=jnp.zeros((), jnp.int32),
            lat_count=jnp.zeros((), jnp.int32),
            deadline_miss=jnp.zeros((), jnp.int32),
            slot_steps=jnp.zeros((), jnp.int32),
            decode_steps=jnp.zeros((), jnp.int32),
            pages_peak=jnp.zeros((), jnp.int32),
            quote_ms_sum=jnp.zeros((), jnp.float32),
            quote_err_ms_sum=jnp.zeros((), jnp.float32),
            quote_count=jnp.zeros((), jnp.int32),
        )

    # -- views ----------------------------------------------------------
    @property
    def dart(self) -> DartParams:
        """The routing-parameter view (what `core.routing` consumes)."""
        return DartParams(tau=self.tau, coef=self.coef,
                          beta_diff=float(self.beta_diff),
                          beta_opt=float(self.beta_opt))

    def with_policy(self, tau=None, coef=None, beta_diff=None,
                    beta_opt=None) -> "EngineState":
        """Functional update of the threshold parameters."""
        rep = {}
        if tau is not None:
            rep["tau"] = jnp.asarray(tau, jnp.float32)
        if coef is not None:
            rep["coef"] = jnp.asarray(coef, jnp.float32)
        if beta_diff is not None:
            rep["beta_diff"] = jnp.asarray(beta_diff, jnp.float32)
        if beta_opt is not None:
            rep["beta_opt"] = jnp.asarray(beta_opt, jnp.float32)
        return dataclasses.replace(self, **rep)


jax.tree_util.register_pytree_node(
    EngineState,
    lambda s: s.tree_flatten(),
    EngineState.tree_unflatten)


# ---------------------------------------------------------------------------
# Per-request serving telemetry (latency / deadline SLO)
# ---------------------------------------------------------------------------
class RequestWindow:
    """Per-request latency/deadline telemetry, kept on the host.

    Unlike the per-SAMPLE counters above (folded on device inside the
    compiled step), request latency is a host-side quantity: the clock
    starts at submit() and stops when the scheduler materializes the
    result.  So the window lives in numpy: a ring of the last W request
    latencies, lifetime request and deadline-miss counts, and the sums
    of admission-time latency quotes.  The scheduler writes it once per
    completed bucket without touching the device, so completing a bucket
    never waits for the step launched after it.

    Every engine owns one (``engine.request_window``).  The EngineState
    leaves ``lat_*``, ``deadline_miss`` and ``quote_*`` hold it only in
    checkpoints:
    :meth:`fill` writes it into the state an engine saves, :meth:`take`
    reads it back out of a restored one.  One dispatcher thread writes,
    any thread may read (``stats``), under the window's lock."""

    def __init__(self, size: int = LAT_WINDOW):
        self.lat_ms = np.zeros(size, np.float32)
        self.ptr = 0
        self.count = 0
        self.deadline_miss = 0
        self.quote_ms_sum = np.float32(0.0)
        self.quote_err_ms_sum = np.float32(0.0)
        self.quote_count = 0
        self._lock = threading.Lock()

    def record_requests(self, latencies_ms, missed=None) -> None:
        """Fold a batch of completed requests into the ring.

        latencies_ms: (k,) per-request wall latency; ``missed``: optional
        (k,) bools — completed after the request's deadline."""
        lat = np.atleast_1d(np.asarray(latencies_ms, np.float32))
        k, w = lat.shape[0], self.lat_ms.shape[0]
        if k == 0:
            return
        n_miss = int(np.sum(missed)) if missed is not None else 0
        with self._lock:
            self.lat_ms[(self.ptr + np.arange(k)) % w] = lat
            self.ptr = (self.ptr + k) % w
            self.count += k
            self.deadline_miss += n_miss

    def record_quotes(self, quotes_ms, realized_ms) -> None:
        """Fold admission-time latency quotes vs realized latency for a
        batch of completed requests.  Entries with a None/NaN quote
        (admitted before the service EMA seeded) are skipped.  The sums
        accumulate in float32, as the checkpointed leaves do."""
        q = np.asarray([np.nan if v is None else v for v in quotes_ms],
                       np.float32)
        r = np.asarray(realized_ms, np.float32)
        ok = ~np.isnan(q)
        k = int(ok.sum())
        if k == 0:
            return
        with self._lock:
            self.quote_ms_sum += q[ok].sum()
            self.quote_err_ms_sum += np.abs(q[ok] - r[ok]).sum()
            self.quote_count += k

    def stats(self) -> dict:
        """Windowed latency percentiles + lifetime deadline-miss rate
        (and the mean quote and quote error once a quote completed)."""
        with self._lock:
            n, miss, qn = self.count, self.deadline_miss, self.quote_count
            lat = self.lat_ms[:min(n, self.lat_ms.shape[0])].copy()
            q_sum = float(self.quote_ms_sum)
            q_err = float(self.quote_err_ms_sum)
        out = {"requests": n, "deadline_miss": miss,
               "miss_rate": miss / max(n, 1)}
        if n:
            out["latency_ms"] = latency_percentiles(lat)
        if qn:
            out["quote"] = {"quoted": qn, "mean_quote_ms": q_sum / qn,
                            "mean_abs_err_ms": q_err / qn}
        return out

    def fill(self, state: EngineState) -> EngineState:
        """``state`` with this window in its ``lat_*``,
        ``deadline_miss`` and ``quote_*`` leaves (what a checkpoint
        saves)."""
        with self._lock:
            return dataclasses.replace(
                state, lat_ms=jnp.asarray(self.lat_ms.copy()),
                lat_ptr=jnp.asarray(self.ptr, jnp.int32),
                lat_count=jnp.asarray(self.count, jnp.int32),
                deadline_miss=jnp.asarray(self.deadline_miss, jnp.int32),
                quote_ms_sum=jnp.asarray(self.quote_ms_sum, jnp.float32),
                quote_err_ms_sum=jnp.asarray(self.quote_err_ms_sum,
                                             jnp.float32),
                quote_count=jnp.asarray(self.quote_count, jnp.int32))

    @classmethod
    def take(cls, state: EngineState):
        """``(window, state)``: the window ``state``'s leaves hold, and
        ``state`` with those leaves emptied — the live state never
        carries the window, so it cannot go stale there."""
        win = cls(state.lat_ms.shape[0])
        win.lat_ms[:] = np.asarray(state.lat_ms, np.float32)
        win.ptr = int(state.lat_ptr)
        win.count = int(state.lat_count)
        win.deadline_miss = int(state.deadline_miss)
        win.quote_ms_sum = np.float32(state.quote_ms_sum)
        win.quote_err_ms_sum = np.float32(state.quote_err_ms_sum)
        win.quote_count = int(state.quote_count)
        return win, cls(win.lat_ms.shape[0]).fill(state)


def latency_percentiles(lat_ms) -> dict:
    """p50/p95/p99/mean summary of a latency sample (ms).  The one
    implementation behind every ``stats()["requests"]["latency_ms"]``
    report (``RequestWindow.stats``)."""
    lat = np.asarray(lat_ms, np.float32)
    p50, p95, p99 = np.percentile(lat, [50.0, 95.0, 99.0])
    return {"p50": float(p50), "p95": float(p95), "p99": float(p99),
            "mean": float(lat.mean())}


# ---------------------------------------------------------------------------
# Per-replica (sharded) telemetry layout
# ---------------------------------------------------------------------------
# The sharded serving engine (repro.engine.sharded) keeps ONE EngineState
# whose *policy* leaves (tau/coef/beta_*, §II.C coefficients, UCB arms) are
# replicated across the mesh while the *telemetry* leaves (counters + the
# §II.C ring buffers) gain a leading replica dimension sharded over the
# data axis.  Each replica folds in only its local batch shard; readers
# reduce over the leading axis (`reduce_telemetry` / `merged_adaptive`).

#: EngineState fields that carry serving telemetry (everything else is
#: policy and stays replicated).
TELEMETRY_FIELDS = ("served", "exit_counts", "total_macs", "since_update",
                    "slot_steps", "decode_steps")

#: Keys of the `adaptive` dict that are per-replica ring-buffer state; the
#: remaining keys (coefficients, UCB counters, active_strategy, t) are
#: shared policy updated only by the periodic §II.C refinement.
ADAPTIVE_BUFFER_KEYS = ("buf_exit", "buf_class", "buf_conf", "buf_correct",
                        "buf_cost", "buf_valid", "ptr", "seen")


def split_adaptive(adaptive: dict) -> tuple[dict, dict]:
    """(per-replica ring buffers, shared coefficient/bandit state)."""
    bufs = {k: adaptive[k] for k in ADAPTIVE_BUFFER_KEYS}
    shared = {k: v for k, v in adaptive.items()
              if k not in ADAPTIVE_BUFFER_KEYS}
    return bufs, shared


def shard_telemetry(state: EngineState, n_replicas: int) -> EngineState:
    """Give telemetry leaves a leading (n_replicas,) axis.

    Existing counts land in replica 0 (zeros elsewhere) so totals are
    preserved under the cross-replica reduction."""
    def lead(v):
        v = jnp.asarray(v)
        return jnp.concatenate(
            [v[None], jnp.zeros((n_replicas - 1,) + v.shape, v.dtype)])
    bufs, shared = split_adaptive(state.adaptive)
    return dataclasses.replace(
        state,
        adaptive={**shared, **{k: lead(v) for k, v in bufs.items()}},
        **{f: lead(getattr(state, f)) for f in TELEMETRY_FIELDS})


def state_shardings(state: EngineState, repl, row) -> EngineState:
    """EngineState-of-NamedShardings for a telemetry-sharded state:
    policy leaves get ``repl`` (replicated), telemetry leaves (counters +
    the §II.C ring buffers, already carrying their leading replica axis
    from :func:`shard_telemetry`) get ``row`` (sharded over the data
    axis).  The one layout shared by every sharded engine
    (``ShardedDartEngine``, the sharded LM decode path)."""
    bufs, shared = split_adaptive(state.adaptive)
    return EngineState(
        tau=repl, coef=repl, beta_diff=repl, beta_opt=repl,
        adaptive={**{k: repl for k in shared}, **{k: row for k in bufs}},
        served=row, exit_counts=row, total_macs=row, since_update=row,
        slot_steps=row, decode_steps=row,
        # host-written telemetry: one global value per engine (no
        # replica axis) — the request window's checkpoint image and the
        # page high-watermark
        lat_ms=repl, lat_ptr=repl, lat_count=repl, deadline_miss=repl,
        pages_peak=repl,
        quote_ms_sum=repl, quote_err_ms_sum=repl, quote_count=repl)


def restore_with_migration(path: str, template: EngineState,
                           step: int | None = None):
    """``checkpoint.restore`` with legacy-layout migration: a checkpoint
    whose leaves are a strict prefix of the current flatten order (an
    older ``_LAYOUT_PREFIXES`` era) restores those fields and keeps the
    template's fresh values for the rest.  Prefixes are tried
    newest-first so a checkpoint restores the LONGEST layout it
    matches.  Returns ``(state, step)``.  Shared by every engine's
    ``restore_state``."""
    from repro import checkpoint as CK
    try:
        restored, step, _ = CK.restore(path, template, step)
        return restored, step
    except ValueError as e:
        if "leaf count" not in str(e):
            raise
    for i, fields in enumerate(_LAYOUT_PREFIXES):
        legacy = [getattr(template, f) for f in fields]
        try:
            leaves, step, _ = CK.restore(path, legacy, step)
        except ValueError as e:
            if "leaf count" not in str(e) or i == len(_LAYOUT_PREFIXES) - 1:
                raise
            continue
        return dataclasses.replace(
            template, **dict(zip(fields, leaves))), step
    raise AssertionError("unreachable")


def reduce_telemetry(state: EngineState) -> dict:
    """Cross-replica all-reduce of the counter fields -> global totals."""
    return {f: jnp.sum(getattr(state, f), axis=0) for f in TELEMETRY_FIELDS}


def telemetry_totals(state: EngineState, *, sharded: bool) -> dict:
    """Host-side numpy totals of the telemetry leaves — the single
    reduction behind every engine's ``stats()`` (and the join point the
    obs tracer reconciles its host-side spans against).  ``sharded``
    states reduce over the leading replica axis; eager states read the
    scalar leaves directly."""
    if sharded:
        return {k: np.asarray(v)
                for k, v in reduce_telemetry(state).items()}
    return {f: np.asarray(getattr(state, f)) for f in TELEMETRY_FIELDS}


def merged_adaptive(state: EngineState) -> dict:
    """One window view over all replicas: ring buffers (R, w) concatenate
    to (R*w,) — `buf_valid` already masks unwritten slots — while shared
    coefficient state passes through.  The result feeds every
    `core.adaptive` read (window_stats / periodic_update) unchanged."""
    bufs, shared = split_adaptive(state.adaptive)
    merged = {k: bufs[k].reshape((-1,) + bufs[k].shape[2:])
              for k in ADAPTIVE_BUFFER_KEYS if k.startswith("buf_")}
    # ptr/seen are per-replica write cursors; a merged window has no single
    # cursor — expose the total seen and a dead ptr.
    merged["ptr"] = jnp.zeros((), jnp.int32)
    merged["seen"] = jnp.sum(bufs["seen"]).astype(jnp.int32)
    return {**shared, **merged}
