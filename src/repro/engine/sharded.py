"""ShardedDartEngine — jit-compiled, data-parallel DART serving.

The eager :class:`~repro.engine.engine.DartEngine` dispatches the model,
the difficulty estimator and Alg. 1 routing as separate ops from Python;
this engine lowers the WHOLE serving step — forward, confidence
functional, difficulty estimation, Eq. 19 threshold adaptation, Alg. 1
exit selection and the §II.C telemetry fold — into one donated-state
jitted program replicated over a 1-D device mesh:

    mesh = make_serving_mesh()                  # ("data",) over devices
    engine = DartEngine.from_config(cfg, params, mesh=mesh)
    out = engine.infer(x, mode="masked")        # one compiled dispatch

Design (ISSUE 2 tentpole):

* **One compiled program per bucket.**  Request batches are padded to
  the `BatchCompactor` bucket (rounded up to a replica multiple) so the
  number of traced programs is bounded by #buckets (masked) or
  #stages × #buckets (compacted).  `trace_counts` records every trace,
  so tests can assert one trace per bucket.
* **Donated state.**  The step takes and returns the full
  :class:`EngineState`; the argument is donated, so serving is
  allocation-stable on accelerators (CPU ignores donation).
* **Sharded telemetry, replicated policy.**  Policy leaves (tau / coef /
  beta_* and the §II.C coefficient + UCB state) carry
  ``NamedSharding(mesh, P())``; telemetry leaves (counters and the ring
  buffers) gain a leading replica axis sharded over ``data`` (see
  ``state.shard_telemetry``).  Each replica folds in only its local
  batch shard — zero cross-replica traffic on the hot path — and
  ``update()`` / ``stats()`` reduce across replicas (merged §II.C
  window, summed counters).
* **The eager path stays the oracle.**  ``infer(x, mode="eager")`` runs
  the parent's eager masked pass (never records), and the equivalence
  suite asserts compiled == eager for preds, exit indices and telemetry
  after the all-reduce.

Confidence + gate (and in-step Eq. 8 difficulty) route through
``repro.kernels.dispatch`` (ISSUE 5 tentpole): the historical GSPMD
blocker — ``pallas_call`` does not partition — is solved by dispatch
wrapping pallas backends in ``shard_map`` over the ``("data",)`` axis,
so each replica gates its local rows in one fused launch per exit; on
this CPU container dispatch auto-selects the ``"xla"`` reference chain,
which is bit-identical to the eager oracle (see docs/kernels.md).
"""
from __future__ import annotations

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import adaptive as AD
from repro.core import thresholds as TH
from repro.engine import state as ST
from repro.engine.engine import DartEngine
from repro.engine.state import EngineState
from repro.obs import NULL_SPAN, OBS, span

def _silence_donation_warning():
    """CPU backends ignore donation and warn per step; donation still
    pays off on TPU/GPU, so keep declaring it and silence the noise —
    but only once someone actually constructs a sharded engine (a plain
    `import repro.engine` must not mutate global warning filters)."""
    warnings.filterwarnings(
        "ignore", message="Some donated buffers were not usable")


class ShardedDartEngine(DartEngine):
    """Data-parallel DART serving over a 1-D ("data",) mesh.

    Construct via ``DartEngine.from_config(cfg, params, mesh=mesh)`` (or
    directly).  ``infer`` modes:

    * ``masked``    — ONE jitted program: full forward + Alg. 1 + telemetry
      fold, batch sharded over the mesh.  The serving hot path.
    * ``compacted`` — stage-segmented: one fused (stage+exit+gate) program
      per (stage, bucket), survivors compacted between stages, telemetry
      folded by a compiled step.  Same decisions, real FLOP savings.
    * ``eager``     — the parent's eager masked pass (reference oracle;
      never records).
    """

    def __init__(self, model_cfg, params, *, mesh, state: EngineState,
                 acfg, data_axis: str = "data", **kw):
        super().__init__(model_cfg, params, state=state, acfg=acfg, **kw)
        _silence_donation_warning()
        self.mesh = mesh
        self.data_axis = data_axis
        # kernels.dispatch shard_maps pallas backends over the data axis
        # inside the compiled steps (xla backends partition under GSPMD)
        self.kernel_kw = {"mesh": mesh, "axis": data_axis}
        self.n_replicas = int(mesh.shape[data_axis])
        self.replica_multiple = self.n_replicas    # bucket_key granularity
        self._repl = NamedSharding(mesh, P())
        self._row = NamedSharding(mesh, P(data_axis))
        self._state_sh = self._state_shardings()
        self.params = jax.device_put(self.params, self._repl)
        # The compiled step DONATES the state, and device_put zero-copies
        # already-placed shards — so take ownership with a deep copy, or
        # donation would invalidate buffers the caller still holds (the
        # DartParams it passed in, a sibling engine built from the same
        # DartParams).
        owned = jax.tree.map(lambda a: jnp.array(a, copy=True),
                             ST.shard_telemetry(self.state, self.n_replicas))
        self.state = jax.device_put(owned, self._state_sh)
        self._steps: dict = {}        # cache key -> compiled callable
        self.trace_counts: dict = {}  # cache key -> number of traces
        # Device rows: admission splits a request's images into one f32
        # array per image (one program per request size), and a masked
        # bucket is stacked from them on the device, zero images in its
        # pad slots (one program per bucket size).
        self.split_rows = jax.jit(self._split_traced)
        self._stack = jax.jit(self._stack_traced, out_shardings=self._row)
        self._zero_rows: dict = {}    # image shape -> device zero image
        # Host mirror of sum(state.since_update): checking the periodic-
        # update schedule must not force a device sync per request, or
        # back-to-back compiled steps could never pipeline.
        self._pending = 0

    # ------------------------------------------------------------------
    # sharding layout
    # ------------------------------------------------------------------
    def _state_shardings(self) -> EngineState:
        """EngineState-of-NamedShardings: policy replicated, telemetry
        row-sharded on its leading replica axis."""
        return ST.state_shardings(self.state, self._repl, self._row)

    def _commit(self):
        """Re-pin the state to its sharding layout after any eager
        mutation (calibrate / update / restore)."""
        self.state = jax.device_put(self.state, self._state_sh)

    def _count_trace(self, key):
        # Runs in the Python body of a step function, i.e. once per trace.
        self.trace_counts[key] = self.trace_counts.get(key, 0) + 1

    # ------------------------------------------------------------------
    # traced pieces
    # ------------------------------------------------------------------
    def _split_traced(self, x):
        """``split_rows``: the (n, ...) device batch ``x`` as n f32
        device arrays, one per image, the form in which ``infer`` takes
        a batch whose images are already on the device."""
        self._count_trace(("split", x.shape[0]))
        return tuple(x.astype(jnp.float32))

    def _stack_traced(self, rows):
        self._count_trace(("stack", len(rows)))
        return jnp.stack(rows)

    def _coef_traced(self, state: EngineState):
        if self.adapt:
            # effective_coef touches only the shared (replicated) keys.
            return AD.effective_coef(state.adaptive, self.acfg)
        return state.coef

    def _fold_traced(self, state: EngineState, exit_idx, pred, conf, macs,
                     valid) -> EngineState:
        """Per-replica telemetry fold: each replica's segment of the
        (padded) batch lands in its own counters / ring buffer."""
        r, e = self.n_replicas, self.n_exits
        per = exit_idx.shape[0] // r
        validf = valid.astype(jnp.float32)
        oh = jax.nn.one_hot(exit_idx, e) * validf[:, None]
        n_new = validf.reshape(r, per).sum(1).astype(jnp.int32)
        exit_counts = state.exit_counts \
            + oh.reshape(r, per, e).sum(1).astype(jnp.int32)
        total_macs = state.total_macs \
            + (macs * validf).reshape(r, per).sum(1)
        adaptive = state.adaptive
        if self.adapt:
            bufs, shared = ST.split_adaptive(adaptive)
            cost = macs / float(self.cum_costs[-1])
            rec = jax.vmap(
                lambda b, ei, pc, cf, cs, v: AD.record_batch(
                    b, self.acfg, ei, pc, cf, cf, cs, valid=v))
            new_bufs = rec(
                bufs, exit_idx.reshape(r, per),
                (pred % self.acfg.n_classes).reshape(r, per),
                conf.reshape(r, per), cost.reshape(r, per),
                validf.reshape(r, per))
            adaptive = {**shared, **new_bufs}
        return dataclasses.replace(
            state, adaptive=adaptive, served=state.served + n_new,
            exit_counts=exit_counts, total_macs=total_macs,
            since_update=state.since_update + n_new)

    # ------------------------------------------------------------------
    # compiled step factories (cached per bucket)
    # ------------------------------------------------------------------
    def _masked_step(self, bp: int, record: bool, with_alpha: bool = False,
                     min_exit: int = 0):
        """Full DART serving step for a (bp,)-padded batch.

        ``with_alpha``: the variant that takes admission-time difficulty
        as an operand instead of fusing the Eq. 8 estimator into the
        step (used by the async scheduler, which estimated difficulty
        once at enqueue).

        ``min_exit`` is a STATIC head-skip depth: gates s < min_exit
        never launch inside the compiled step (the predictor ruled them
        out — under the conservative bound they provably never fire, so
        the program is decision-identical to the min_exit=0 one)."""
        key = ("masked-alpha" if with_alpha else "masked", bp, record) \
            if not min_exit else \
            ("masked-alpha-skip" if with_alpha else "masked-skip",
             bp, record, min_exit)
        if key in self._steps:
            return self._steps[key]
        cum = jnp.asarray(self.cum_costs, jnp.float32)

        def step(params, state, x, valid, *aux):
            self._count_trace(key)
            logits = self._forward_traced(params, x)     # (E, bp, C)
            if with_alpha:
                alpha = aux[0]
            else:
                with jax.named_scope("difficulty"):
                    alpha = self._diff_fn(x, self.dcfg, **self.kernel_kw)
            eff = TH.adapt_thresholds(state.tau, self._coef_traced(state),
                                      alpha, state.beta_diff)
            exit_idx, conf, pred = self._route_traced(logits, eff,
                                                      min_exit=min_exit)
            macs = cum[exit_idx]
            if record:
                state = self._fold_traced(state, exit_idx, pred, conf,
                                          macs, valid)
            return state, {"exit_idx": exit_idx, "conf": conf,
                           "pred": pred, "alpha": alpha, "macs": macs}

        self._steps[key] = jax.jit(
            step, donate_argnums=(1,),
            out_shardings=(self._state_sh, self._row))
        return self._steps[key]

    def _forward_traced(self, params, x):
        return self.family.forward(params, x, self.cfg)["exit_logits"]

    def _route_traced(self, logits, eff, min_exit: int = 0):
        """Alg. 1 over stacked exit logits (E, bp, C) with (bp, E-1)
        effective thresholds -> (exit_idx, conf, pred).

        For the paper's ``softmax-max`` functional every exit runs ONE
        fused gate launch through ``kernels.dispatch`` (confidence +
        argmax + Eq. 19 compare in a single VMEM pass per row on pallas
        backends; the bit-identical jnp chain on xla).  Other
        functionals keep the generic conf-stack path.

        Gates i < ``min_exit`` are skipped (no gate launch; they can
        never win the argmax)."""
        e, bp = logits.shape[0], logits.shape[1]
        if self.confidence != "softmax-max":
            if min_exit:        # unreachable threshold, fires stay False
                eff = eff.at[:, :min_exit].set(jnp.inf)
            conf_stack = self._conf_fn(logits)
            exit_idx, conf = TH.select_exit(conf_stack, eff)
            preds_all = jnp.argmax(logits, axis=-1)
            pred = jnp.take_along_axis(preds_all, exit_idx[None],
                                       axis=0)[0]
            return exit_idx, conf, pred
        from repro.kernels import dispatch as KD
        confs, preds, fires = [], [], []
        for i in range(e):
            if i < min_exit and i < e - 1:
                # ruled-out gate: no fused launch, placeholder lanes
                # (argmax can never select an all-False column)
                confs.append(jnp.zeros((bp,), jnp.float32))
                preds.append(jnp.zeros((bp,), jnp.int32))
                fires.append(jnp.zeros((bp,), bool))
                continue
            th_i = eff[:, i] if i < e - 1 \
                else jnp.full((bp,), -1.0, jnp.float32)
            with jax.named_scope(f"gate{i}"):
                c, _, p, f = KD.exit_gate(logits[i], th_i,
                                          **self.kernel_kw)
            confs.append(c)
            preds.append(p)
            # Alg. 1 line 12: the final exit accepts unconditionally,
            # whatever the confidence functional's range
            fires.append(f if i < e - 1 else jnp.ones_like(f))
        fires = jnp.stack(fires, axis=1) > 0            # (bp, E)
        exit_idx = jnp.argmax(fires, axis=1)            # first firing exit
        conf = jnp.take_along_axis(jnp.stack(confs, 1), exit_idx[:, None],
                                   axis=1)[:, 0]
        pred = jnp.take_along_axis(jnp.stack(preds, 1), exit_idx[:, None],
                                   axis=1)[:, 0]
        return exit_idx, conf, pred

    def _stage_step(self, s: int, bp: int):
        """Fused stage + exit head + gate for bucket ``bp``.  The gate
        (confidence + argmax + Eq. 19 compare) is one dispatch-routed
        launch — shard_map-wrapped pallas on TPU, the bit-identical jnp
        chain on xla."""
        key = ("stage", s, bp)
        if key in self._steps:
            return self._steps[key]

        def step(params, h, eff):
            self._count_trace(key)
            h2 = self.family.apply_stage(params, h, s, self.cfg)
            logits = self.family.apply_exit(params, h2, s, self.cfg)
            if self.confidence == "softmax-max":
                from repro.kernels import dispatch as KD
                conf, _, pred, fire = KD.exit_gate(logits, eff,
                                                   **self.kernel_kw)
                return h2, conf, pred, fire > 0
            conf = self._conf_fn(logits)
            pred = jnp.argmax(logits, axis=-1)
            return h2, conf, pred, conf > eff

        self._steps[key] = jax.jit(step, out_shardings=self._row)
        return self._steps[key]

    def _stage_fwd_step(self, s: int, bp: int):
        """Forward-only stage for bucket ``bp`` — the head-skip variant
        of ``_stage_step`` for gates the predictor ruled out: no exit
        head, no gate launch, and (host-side) no fire/conf sync, since
        by the conservative bound every row survives."""
        key = ("stage-fwd", s, bp)
        if key in self._steps:
            return self._steps[key]

        def step(params, h):
            self._count_trace(key)
            return self.family.apply_stage(params, h, s, self.cfg)

        self._steps[key] = jax.jit(step, out_shardings=self._row)
        return self._steps[key]

    def _fold_step(self, bp: int):
        """Compiled telemetry fold for the compacted path."""
        key = ("fold", bp)
        if key in self._steps:
            return self._steps[key]

        def step(state, exit_idx, pred, conf, macs, valid):
            self._count_trace(key)
            return self._fold_traced(state, exit_idx, pred, conf, macs,
                                     valid)

        self._steps[key] = jax.jit(step, donate_argnums=(0,),
                                   out_shardings=self._state_sh)
        return self._steps[key]

    # ------------------------------------------------------------------
    # inference
    # ------------------------------------------------------------------
    def infer(self, x, mode: str = "masked", record: bool | None = None,
              alpha=None, pad_to: int | None = None,
              min_exit: int = 0) -> dict:
        """Serve one request batch through the compiled path.

        x — (B, ...) host images, or a tuple of B device images
            (``split_rows``), which the compiled modes stack into the
            padded batch on the device instead of padding, casting and
            copying host images.
        mode="masked"    — one jitted step (serving hot path).
        mode="compacted" — compiled stage-segmented path (FLOP savings).
        mode="eager"     — the parent's eager masked pass (oracle;
                           never records).
        record — fold serving counters + the §II.C window into the
                 sharded state (default ON for the compiled modes —
                 they ARE the serving path — and OFF for the oracle).
        alpha  — optional (B,) admission-time difficulty (see
                 ``DartEngine.infer``).
        pad_to — accepted for API parity and ignored: every compiled
                 path already pads to ``bucket_key(B)`` internally.
        min_exit — STATIC head-skip depth (see ``DartEngine.infer``):
                 compiled steps for gates s < min_exit skip the exit
                 head + fused gate launches; with the conservative
                 bound decisions are unchanged.  The eager oracle
                 ignores it."""
        if not 0 <= int(min_exit) < self.n_exits:
            raise ValueError(f"min_exit {min_exit} out of range for "
                             f"{self.n_exits} exits")
        min_exit = int(min_exit)
        if mode == "eager":
            return super()._infer_masked(np.asarray(x), record=False,
                                         alpha=alpha)
        if mode not in ("masked", "compacted"):
            raise ValueError(
                f"unknown mode {mode!r}; known: masked, compacted, eager")
        record = True if record is None else record
        if not isinstance(x, tuple):
            x = np.asarray(x)
        b = len(x)
        if b > self.compactor.max_bucket:
            parts = [self._infer_chunk(
                x[a:z], mode, record,
                alpha=None if alpha is None else alpha[a:z],
                min_exit=min_exit)
                for a, z in self.compactor.chunks(b)]
            out = {k: np.concatenate([p[k] for p in parts])
                   for k in ("pred", "conf", "exit_idx", "alpha", "macs")}
        else:
            out = self._infer_chunk(x, mode, record, alpha=alpha,
                                    min_exit=min_exit)
        if record:
            self._maybe_update()
        return out

    def _pad_batch(self, x, bp):
        """(x, valid) padded to ``bp`` rows on the row sharding: device
        images stacked on the device with zero images in the pad slots,
        host images padded and cast on the host and copied."""
        valid = np.zeros(bp, np.float32)
        valid[:len(x)] = 1.0
        if isinstance(x, tuple):
            shape = x[0].shape
            if shape not in self._zero_rows:
                self._zero_rows[shape] = jnp.zeros(shape, jnp.float32)
            xp = self._stack(x + (self._zero_rows[shape],) * (bp - len(x)))
        else:
            xp = jax.device_put(
                self.compactor.pad(x.astype(np.float32, copy=False), bp),
                self._row)
        return xp, jax.device_put(valid, self._row)

    def _infer_chunk(self, x, mode, record, alpha=None,
                     min_exit: int = 0) -> dict:
        b = len(x)
        bp = self.bucket_key(b)
        if mode == "masked":
            # obs phases: ``put`` (the padded operands on the device;
            # ``bytes``: what is copied from the host), ``launch`` (the
            # async step dispatch + output slicing)
            with span("put") if OBS.enabled else NULL_SPAN as sp:
                xp, valid = self._pad_batch(x, bp)
                ap = None if alpha is None else jax.device_put(
                    self.compactor.pad(np.asarray(alpha, np.float32), bp),
                    self._row)
                if OBS.enabled:
                    sp.set(bytes=int(valid.nbytes) + (
                        0 if isinstance(x, tuple) else int(xp.nbytes)) + (
                        0 if ap is None else int(ap.nbytes)))
            with span("launch") if OBS.enabled else NULL_SPAN:
                step = self._masked_step(bp, record, alpha is not None,
                                         min_exit=min_exit)
                if ap is None:
                    self.state, out = step(self.params, self.state, xp,
                                           valid)
                else:
                    self.state, out = step(self.params, self.state, xp,
                                           valid, ap)
                # Outputs stay ON DEVICE (lazy): a serving loop that
                # doesn't read them immediately pipelines compiled steps
                # back to back through the donated state chain.
                # np.asarray() on any value materializes it.
                res = {k: v[:b] for k, v in out.items()}
        else:
            res = self._compacted_chunk(x, bp, record, alpha=alpha,
                                        min_exit=min_exit)
        if record:
            self._pending += b
        return res

    def _compacted_chunk(self, x, bp, record, alpha=None,
                         min_exit: int = 0) -> dict:
        if not self.family.staged:
            raise ValueError(
                f"compacted mode needs a staged family; "
                f"{type(self.cfg).__name__} is not staged — use "
                f"mode='masked'")
        b = len(x)
        xp, valid = self._pad_batch(x, bp)
        alpha = np.asarray(self._alpha(xp))[:b] if alpha is None \
            else np.asarray(alpha, np.float32)

        out_pred = np.zeros(b, np.int64)
        out_conf = np.zeros(b, np.float32)
        out_exit = np.zeros(b, np.int64)

        coef = np.asarray(self._coef_traced(self.state), np.float32)
        tau = np.asarray(self.state.tau, np.float32)
        beta_diff = float(self.state.beta_diff)

        h_active = self._stem(self.params, xp)[:b]
        active = np.arange(b)
        alpha_active = alpha
        for s in range(self.n_exits):
            n = len(active)
            sp = self.bucket_key(n)
            if s < min_exit and s < self.n_exits - 1:
                # ruled-out gate: forward-only compiled stage — no exit
                # head, no gate launch, no fire/conf host sync, no
                # compaction (every row provably survives)
                h_pad = jax.device_put(
                    self.compactor.pad(jnp.asarray(h_active), sp),
                    self._row)
                h_active = self._stage_fwd_step(s, sp)(
                    self.params, h_pad)[:n]
                continue
            if s < self.n_exits - 1:
                eff = np.asarray(TH.stage_threshold(
                    tau[s], coef[s], alpha_active, beta_diff))
                # padded lanes get an unreachable threshold -> never fire
                eff_pad = self.compactor.pad(
                    eff.astype(np.float32), sp, fill=2.0)
            else:
                # final gate always accepts (Alg. 1 line 12)
                eff_pad = np.full(sp, -1.0, np.float32)
            h_pad = jax.device_put(
                self.compactor.pad(jnp.asarray(h_active), sp), self._row)
            eff_pad = jax.device_put(jnp.asarray(eff_pad), self._row)
            h2, conf, pred, fire = self._stage_step(s, sp)(
                self.params, h_pad, eff_pad)
            fire = np.asarray(fire[:n])
            conf = np.asarray(conf[:n])
            pred = np.asarray(pred[:n])

            done = active[fire]
            out_pred[done] = pred[fire]
            out_conf[done] = conf[fire]
            out_exit[done] = s
            keep = ~fire
            if not keep.any():
                break
            h_active = self.compactor.gather(h2[:n], np.nonzero(keep)[0])
            alpha_active = alpha_active[keep]
            active = active[keep]

        macs = self.cum_costs[out_exit].astype(np.float32)
        if record:
            ei = self.compactor.pad(out_exit.astype(np.int32), bp)
            pr = self.compactor.pad(out_pred.astype(np.int32), bp)
            cf = self.compactor.pad(out_conf, bp)
            mc = self.compactor.pad(macs, bp)
            self.state = self._fold_step(bp)(
                self.state, jnp.asarray(ei), jnp.asarray(pr),
                jnp.asarray(cf), jnp.asarray(mc), valid)
        return {"pred": out_pred, "conf": out_conf, "exit_idx": out_exit,
                "alpha": alpha, "macs": macs}

    # ------------------------------------------------------------------
    # §II.C adaptation + metering (cross-replica reductions)
    # ------------------------------------------------------------------
    def _maybe_update(self):
        # self._pending mirrors sum(state.since_update) host-side so the
        # schedule check never blocks on the in-flight state.
        if self.adapt and self._pending >= self.update_every:
            self.update()

    def update(self) -> None:
        """One §II.C periodic refinement over the MERGED window: all
        replicas' ring buffers are reduced into one view, both
        adaptation laws + UCB1 run once, and the new (shared) policy
        coefficients are re-replicated."""
        s = self.state
        merged = AD.periodic_update(ST.merged_adaptive(s), self.acfg,
                                    beta_opt=float(s.beta_opt))
        _, new_shared = ST.split_adaptive(merged)
        bufs, _ = ST.split_adaptive(s.adaptive)
        self.state = dataclasses.replace(
            s, adaptive={**new_shared, **bufs},
            since_update=jnp.zeros_like(s.since_update))
        self._pending = 0
        self._policy_mirror = None
        self._commit()

    def calibrate(self, data, **kw):
        pol = super().calibrate(data, **kw)
        self._commit()
        return pol

    def restore_state(self, path: str, step: int | None = None):
        step = super().restore_state(path, step)
        self._pending = int(np.sum(np.asarray(self.state.since_update)))
        self._commit()
        return step

    def stats(self) -> dict:
        """Global serving statistics: counters summed over replicas,
        §II.C window statistics over the merged window."""
        from repro.obs import stats as OBS_STATS
        out = OBS_STATS.engine_summary(
            ST.telemetry_totals(self.state, sharded=True))
        out.update(
            active_strategy=AD.STRATEGIES[
                int(self.state.adaptive["active_strategy"])],
            replicas=self.n_replicas,
            served_per_replica=np.asarray(self.state.served))
        if out["served"]:
            w = AD.window_stats(ST.merged_adaptive(self.state), self.acfg)
            out["window"] = {k: np.asarray(v) for k, v in w.items()}
        return OBS_STATS.attach_requests(out, self.request_window)
