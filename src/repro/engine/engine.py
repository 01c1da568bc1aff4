"""DartEngine — the unified façade over the whole DART lifecycle.

One object owns the paper's three contributions end to end:

    engine = DartEngine.from_config(cfg, params)        # wire up
    engine.calibrate(cal_data)                          # §II.B  (policy)
    out = engine.infer(x, mode="compacted")             # Alg. 1 (serving)
    engine.update()                                     # §II.C  (adapt)
    engine.stats()                                      # metering

Every strategy is a string looked up in ``repro.engine.registry``
(confidence functional, difficulty estimator, policy optimizer), so the
same engine serves classifiers, LMs and diffusion models and new exit
criteria plug in without touching call sites.

All mutable serving state lives in ONE pytree (``EngineState``):
checkpoint it with ``repro.checkpoint.save(path, step, engine.state)``
and restore with ``engine.restore_state(...)`` — counters, ring buffers,
UCB arms and thresholds all round-trip together.

Execution modes (DESIGN.md §4.1):

* ``masked``    — single jitted full forward, Alg. 1 on the stacked exit
  confidences.  Worst-case compute; bit-identical decisions.
* ``compacted`` — stage-segmented execution with ``BatchCompactor``:
  survivors of each gate are compacted into power-of-two buckets, so
  early exits buy back real FLOPs.  Oversized request batches are split
  into max-bucket chunks (no silent clamping).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import adaptive as AD
from repro.core import difficulty as DIFF
from repro.core import routing as R
from repro.core import thresholds as TH
from repro.core.policy import CalibrationData, PolicyResult
from repro.core.routing import DartParams
from repro.engine import registry as REG
from repro.engine import state as ST
from repro.engine.compactor import BatchCompactor
from repro.engine.state import EngineState
from repro.models import get_family


def _n_exits(model_cfg, family) -> int:
    if family.staged:
        return family.num_stages(model_cfg)
    if hasattr(model_cfg, "exit_layers"):
        return len(model_cfg.exit_layers) + 1
    raise ValueError(f"cannot infer exit count for {type(model_cfg)}")


class DartEngine:
    """Session object for DART inference (train → calibrate → serve → adapt).

    Construct via :meth:`from_config`; mutable state is ``self.state``
    (an :class:`EngineState` pytree), everything else is static wiring.
    """

    def __init__(self, model_cfg, params, *, state: EngineState,
                 acfg: AD.AdaptiveConfig,
                 dcfg: DIFF.DifficultyConfig = DIFF.DEFAULT,
                 confidence: str = "softmax-max",
                 difficulty: str = "image",
                 optimizer: str = "joint_dp",
                 cum_costs=None, buckets=None,
                 adapt: bool = True, update_every: int = 100):
        self.cfg = model_cfg
        self.params = params
        self.state = state
        self.acfg = acfg
        self.dcfg = dcfg
        self.family = get_family(model_cfg)
        self.n_exits = _n_exits(model_cfg, self.family)
        self.confidence = confidence
        self.difficulty = difficulty
        self.optimizer = optimizer
        self._conf_fn = REG.get_confidence(confidence)
        self._diff_fn = REG.get_difficulty(difficulty)
        self._opt_fn = REG.get_optimizer(optimizer)
        self.compactor = BatchCompactor(buckets)
        # Compile-cache key granularity: padded batch shapes are rounded
        # up to a multiple of this (1 eagerly; the sharded engine sets it
        # to the replica count so the mesh divides every bucket evenly).
        self.replica_multiple = 1
        # Difficulty/gate calls go through repro.kernels.dispatch; the
        # sharded engines extend this with their mesh so pallas backends
        # shard_map over the data axis (dispatch ignores it on xla).
        self.kernel_kw: dict = {}
        self.adapt = adapt
        self.update_every = update_every
        self.total_latency_s = 0.0
        if cum_costs is None:
            cum_costs = np.arange(1, self.n_exits + 1) / self.n_exits
        self.cum_costs = np.asarray(cum_costs, float)

        cfgc = model_cfg
        if self.family.staged:
            self._stem = jax.jit(
                lambda p, x: self.family.apply_stem(p, x, cfgc))
            self._stage = [
                jax.jit(lambda p, h, s=s: self.family.apply_stage(
                    p, h, s, cfgc)) for s in range(self.n_exits)]
            self._exit = [
                jax.jit(lambda p, h, s=s: self.family.apply_exit(
                    p, h, s, cfgc)) for s in range(self.n_exits)]
        self._alpha = jax.jit(
            lambda x: self._diff_fn(x, self.dcfg, **self.kernel_kw))
        self._forward = jax.jit(
            lambda p, x: self.family.forward(p, x, cfgc))

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------
    @classmethod
    def from_config(cls, model_cfg, params, *, dart: DartParams | None = None,
                    adaptive_cfg: AD.AdaptiveConfig | None = None,
                    n_classes: int | None = None,
                    beta_opt: float | None = None,
                    mesh=None, **kw) -> "DartEngine":
        """Build an engine from a model config + trained params.

        ``model_cfg`` may be a config object or an arch id resolved via
        ``configs.registry`` (e.g. ``"vit-s16"``).

        ``mesh``: a 1-D ("data",) device mesh (``launch.mesh.
        make_serving_mesh``) — serving then goes through the
        jit-end-to-end data-parallel :class:`~repro.engine.sharded.
        ShardedDartEngine` instead of the eager engine."""
        if isinstance(model_cfg, str):
            from repro.configs import registry as cfg_registry
            model_cfg = cfg_registry.get(model_cfg)
        family = get_family(model_cfg)
        e = _n_exits(model_cfg, family)
        acfg = adaptive_cfg or AD.AdaptiveConfig(
            n_exits=e,
            n_classes=n_classes or getattr(model_cfg, "n_classes", 10))
        state = EngineState.create(e, acfg, dart)
        if beta_opt is not None:
            state = state.with_policy(beta_opt=beta_opt)
        if mesh is not None:
            from repro.engine.sharded import ShardedDartEngine
            if cls is DartEngine:
                cls = ShardedDartEngine
            kw["mesh"] = mesh
        return cls(model_cfg, params, state=state, acfg=acfg, **kw)

    # ------------------------------------------------------------------
    # §II.B — calibration / policy fitting
    # ------------------------------------------------------------------
    def collect_calibration(self, data_cfg, *, n=512, split="eval",
                            offset=0, batch=64) -> CalibrationData:
        """Run the model over ``n`` samples and build per-exit calibration
        measurements (confidence, correctness, difficulty, entropy)."""
        from repro.data.datasets import make_batch
        confs, ents, corrects, alphas, labels = [], [], [], [], []
        for start in range(offset, offset + n, batch):
            x, y = make_batch(data_cfg, range(start, start + batch),
                              split=split)
            out = self._forward(self.params, jnp.asarray(x))
            logits = out["exit_logits"]                     # (E, B, C)
            conf = np.asarray(self._conf_fn(logits))
            ent = np.asarray(R.entropy_from_logits(logits))
            pred = np.asarray(jnp.argmax(logits, axis=-1))
            alpha = np.asarray(self._alpha(jnp.asarray(x)))
            confs.append(conf.T)
            ents.append(ent.T)
            corrects.append((pred == y[None]).T.astype(float))
            alphas.append(alpha)
            labels.append(y)
        return CalibrationData(
            conf=np.concatenate(confs),
            correct=np.concatenate(corrects),
            alpha=np.concatenate(alphas),
            cum_costs=self.cum_costs / self.cum_costs[-1],
            labels=np.concatenate(labels),
            entropy=np.concatenate(ents))

    def calibrate(self, data, **kw) -> PolicyResult:
        """Fit the exit policy with the registered optimizer and install
        it into the engine state.

        ``data``: a :class:`CalibrationData`, or a ``DatasetConfig`` (the
        engine collects measurements itself).  Returns the fitted
        :class:`PolicyResult`."""
        if not isinstance(data, CalibrationData):
            data = self.collect_calibration(data, **{
                k: kw.pop(k) for k in ("n", "split", "offset", "batch")
                if k in kw})
        kw.setdefault("beta_opt", float(self.state.beta_opt))
        pol = self._opt_fn(data, **kw)
        self.state = self.state.with_policy(
            tau=pol.tau, coef=pol.coef, beta_diff=pol.beta_diff)
        self._policy_mirror = None
        return pol

    # ------------------------------------------------------------------
    # serving helpers
    # ------------------------------------------------------------------
    def dart_params(self, coef=None) -> DartParams:
        """Current routing parameters (adaptive coefficients folded in)."""
        s = self.state
        if coef is None:
            coef = self._coef()
        return DartParams(tau=s.tau, coef=coef,
                          beta_diff=float(s.beta_diff),
                          beta_opt=float(s.beta_opt))

    def _coef(self):
        if self.adapt:
            return AD.effective_coef(self.state.adaptive, self.acfg)
        return self.state.coef

    #: confidence functionals bounded above by 1.0 — the precondition
    #: for the sound head-skip bound (core.thresholds.min_exit_bound)
    _BOUNDED_CONF = ("softmax-max", "lm-token")

    def min_exit_bound(self, alpha_lo: float = 0.0) -> int:
        """Sound per-bucket head-skip depth under the CURRENT policy:
        the number of leading gates Eq. 19 provably rules out for every
        input with difficulty ≥ ``alpha_lo`` (see ``core.thresholds.
        min_exit_bound``).  Returns 0 (skip nothing) for confidence
        functionals without a known upper bound."""
        if self.confidence not in self._BOUNDED_CONF or self.n_exits < 2:
            return 0
        tau, coef, beta_diff = self._policy_host()
        return TH.min_exit_bound(tau, coef, beta_diff, alpha_lo)

    def _policy_host(self):
        """Host mirror of (tau, effective coef, beta_diff), cached so
        admission-time bound checks never force a device sync of the
        serving state per dispatch.  Invalidated explicitly by
        calibrate()/update()/restore_state() (the §II.C coefficient
        path) and implicitly by ``with_policy`` installs (the cache is
        keyed on the tau/coef leaf identities, which those replace)."""
        key = (id(self.state.tau), id(self.state.coef))
        cached = getattr(self, "_policy_mirror", None)
        if cached is None or cached[0] != key:
            self._policy_mirror = (key, (
                np.asarray(self.state.tau, np.float32),
                np.asarray(self._coef(), np.float32),
                float(self.state.beta_diff)))
        return self._policy_mirror[1]

    def bucket_key(self, n: int) -> int:
        """THE compile-cache key for an ``n``-sample batch: the
        ``BatchCompactor`` bucket rounded up to ``replica_multiple``.
        Every serving path (eager compacted, sharded masked/compacted,
        the async scheduler's flush planner) must key compiled shapes
        through here so they agree on what shares a compilation."""
        return self.compactor.padded_size(n, self.replica_multiple)

    def _gate(self, logits, eff_thresh):
        if self.confidence == "softmax-max":
            from repro.kernels import dispatch as KD
            conf, _, pred, fire = KD.exit_gate(
                logits, jnp.asarray(eff_thresh, jnp.float32),
                **self.kernel_kw)
            return conf, pred, fire.astype(bool)
        conf = self._conf_fn(logits)
        pred = jnp.argmax(logits, axis=-1)
        return conf, pred, conf > eff_thresh

    def route(self, stack, inputs=None, alpha=None, **difficulty_kw):
        """Generic Alg. 1 routing over a stacked-exit output.

        ``stack``: raw per-exit outputs, shape (E, B, ...) — converted to
        confidences by the registered functional.  ``alpha`` may be given
        directly, or ``inputs`` is fed to the difficulty estimator.
        jit-safe; state is read, never written."""
        conf_stack = self._conf_fn(stack)
        if alpha is None:
            alpha = self._diff_fn(inputs, self.dcfg, **difficulty_kw)
        return R.route(conf_stack, alpha, self.dart_params())

    # ------------------------------------------------------------------
    # inference
    # ------------------------------------------------------------------
    def infer(self, x, mode: str = "compacted", record: bool | None = None,
              alpha=None, pad_to: int | None = None,
              min_exit: int = 0) -> dict:
        """Serve one request batch.

        mode="masked"    — full forward, Alg. 1 on stacked confidences.
        mode="compacted" — stage-segmented with batch compaction (same
                           decisions, real FLOP savings).
        record — update serving counters + the §II.C sliding window
                 (defaults on for compacted serving, off for masked so a
                 reference pass never perturbs the engine state).
        alpha  — optional (B,) precomputed Eq. 8 difficulty.  The async
                 scheduler (repro.serving) estimates difficulty once at
                 admission and hands it through here, so routing never
                 re-runs the estimator on the consolidated batch.
        pad_to — masked mode only: zero-pad the batch to this fixed
                 shape (normally ``engine.bucket_key(B)``) so arbitrary
                 request-consolidation sizes reuse one compiled forward
                 per bucket.  Padding never reaches outputs or telemetry.
                 The sharded engine ignores it (it pads internally).
        min_exit — head-skip depth: gates s < min_exit are skipped (no
                 exit head, no Alg. 1 gate).  With the CONSERVATIVE
                 bound (``engine.min_exit_bound(min(alpha))``) those
                 gates provably never fire, so decisions are unchanged
                 — compacted mode then skips their launches and host
                 syncs; masked mode computes every exit anyway and
                 ignores it."""
        if not 0 <= int(min_exit) < self.n_exits:
            raise ValueError(f"min_exit {min_exit} out of range for "
                             f"{self.n_exits} exits")
        if mode == "masked":
            return self._infer_masked(x, record=bool(record), alpha=alpha,
                                      pad_to=pad_to)
        if mode == "compacted":
            record = True if record is None else record
            return self._infer_compacted(x, record=record, alpha=alpha,
                                         min_exit=int(min_exit))
        raise ValueError(f"unknown mode {mode!r}; known: masked, compacted")

    # -- masked ---------------------------------------------------------
    def _infer_masked(self, x, record: bool = False, alpha=None,
                      pad_to: int | None = None) -> dict:
        t0 = time.time()
        x = jnp.asarray(x)
        b = x.shape[0]
        if pad_to is not None and pad_to > b:
            x = self.compactor.pad(x, pad_to)
            if alpha is not None:
                alpha = self.compactor.pad(
                    np.asarray(alpha, np.float32), pad_to)
        out = self._forward(self.params, x)
        logits = out["exit_logits"]                         # (E, bp, C)
        conf_stack = self._conf_fn(logits)
        alpha = self._alpha(x) if alpha is None else jnp.asarray(alpha)
        r = R.route(conf_stack, alpha, self.dart_params())
        preds_all = jnp.argmax(logits, axis=-1)
        pred = jnp.take_along_axis(preds_all, r["exit_idx"][None], axis=0)[0]
        if x.shape[0] > b:                  # strip padded lanes
            r = {k: v[:b] for k, v in r.items()}
            pred = pred[:b]
            preds_all = preds_all[:, :b]
            conf_stack = conf_stack[:, :b]
        macs = self.cum_costs[np.asarray(r["exit_idx"])]
        res = {**r, "pred": pred, "preds_all": preds_all,
               "conf_stack": conf_stack, "macs": macs,
               "latency_s": time.time() - t0}
        if record:
            idx = np.asarray(r["exit_idx"])
            self._record(idx, np.asarray(pred), np.asarray(r["conf"]), macs,
                         latency_s=res["latency_s"],
                         exit_counts=np.bincount(idx,
                                                 minlength=self.n_exits))
            self._maybe_update()
        return res

    # -- compacted ------------------------------------------------------
    def _infer_compacted(self, x, record: bool = True, alpha=None,
                         min_exit: int = 0) -> dict:
        b = x.shape[0]
        if b > self.compactor.max_bucket:
            # One request = one policy: chunks are recorded but the §II.C
            # periodic update is deferred past the last chunk, so every
            # sample of the request is gated under the same coefficients
            # (and compacted stays bit-identical to masked).
            parts = [self._infer_compacted_chunk(
                x[a:z], record=record,
                alpha=None if alpha is None else alpha[a:z],
                min_exit=min_exit)
                for a, z in self.compactor.chunks(b)]
            out = {k: np.concatenate([p[k] for p in parts])
                   for k in ("pred", "conf", "exit_idx", "alpha", "macs")}
            out["latency_s"] = sum(p["latency_s"] for p in parts)
        else:
            out = self._infer_compacted_chunk(x, record=record, alpha=alpha,
                                              min_exit=min_exit)
        if record:
            self._maybe_update()
        return out

    def _infer_compacted_chunk(self, x, record: bool, alpha=None,
                               min_exit: int = 0) -> dict:
        if not self.family.staged:
            raise ValueError(
                f"compacted mode needs a staged family; "
                f"{type(self.cfg).__name__} is not staged — use "
                f"mode='masked' or the LM decode engine")
        t0 = time.time()
        b = x.shape[0]
        x = jnp.asarray(x)
        alpha = np.asarray(self._alpha(x)) if alpha is None \
            else np.asarray(alpha, np.float32)

        out_pred = np.zeros(b, np.int64)
        out_conf = np.zeros(b, np.float32)
        out_exit = np.zeros(b, np.int64)

        coef = np.asarray(self._coef(), np.float32)
        tau = np.asarray(self.state.tau, np.float32)
        beta_diff = float(self.state.beta_diff)

        h_active = self._stem(self.params, x)
        active = np.arange(b)
        alpha_active = alpha
        exit_counts = np.zeros(self.n_exits, np.int32)
        for s in range(self.n_exits):
            n = len(active)
            bucket = self.bucket_key(n)
            h_pad = self.compactor.pad(h_active, bucket)
            h_pad = self._stage[s](self.params, h_pad)
            if s < min_exit and s < self.n_exits - 1:
                # gate ruled out for every row (predictor head-skip):
                # no exit head, no Alg. 1 gate, no fire/conf host sync
                h_active = h_pad[:n]
                continue
            logits = self._exit[s](self.params, h_pad)
            if s < self.n_exits - 1:
                eff = np.asarray(TH.stage_threshold(
                    tau[s], coef[s], alpha_active, beta_diff))
                # padded lanes get an unreachable threshold -> never fire
                eff_pad = self.compactor.pad(
                    np.asarray(eff, np.float32), bucket, fill=2.0)
                conf, pred, fire = self._gate(logits, jnp.asarray(eff_pad))
                fire = np.asarray(fire[:n])
            else:
                conf, pred, _ = self._gate(
                    logits, jnp.zeros(bucket, jnp.float32))
                fire = np.ones(n, bool)
            conf = np.asarray(conf[:n])
            pred = np.asarray(pred[:n])

            done = active[fire]
            out_pred[done] = pred[fire]
            out_conf[done] = conf[fire]
            out_exit[done] = s
            exit_counts[s] += int(fire.sum())
            keep = ~fire
            if not keep.any():
                break
            h_active = self.compactor.gather(h_pad[:n], np.nonzero(keep)[0])
            alpha_active = alpha_active[keep]
            active = active[keep]

        macs = self.cum_costs[out_exit]
        latency = time.time() - t0
        if record:
            self._record(out_exit, out_pred, out_conf, macs,
                         latency_s=latency, exit_counts=exit_counts)
        return {"pred": out_pred, "conf": out_conf, "exit_idx": out_exit,
                "alpha": alpha, "macs": macs, "latency_s": latency}

    # ------------------------------------------------------------------
    # §II.C — adaptation + metering
    # ------------------------------------------------------------------
    def _record(self, exit_idx, pred, conf, macs, *, latency_s=0.0,
                exit_counts=None):
        """Fold one served batch into the state: counters always, the
        §II.C sliding window only when adaptation is on."""
        b = len(exit_idx)
        s = self.state
        if exit_counts is None:
            exit_counts = np.bincount(exit_idx, minlength=self.n_exits)
        counts = s.exit_counts + jnp.asarray(exit_counts, jnp.int32)
        adaptive = s.adaptive
        if self.adapt:
            # confidence-calibrated pseudo-correctness (paper §II.C.1)
            adaptive = AD.record_batch(
                adaptive, self.acfg, jnp.asarray(exit_idx),
                jnp.asarray(pred % self.acfg.n_classes),
                jnp.asarray(conf), jnp.asarray(conf),
                jnp.asarray(macs / self.cum_costs[-1]))
        self.state = dataclasses.replace(
            s, adaptive=adaptive, served=s.served + b, exit_counts=counts,
            total_macs=s.total_macs + float(np.sum(macs)),
            since_update=s.since_update + b)
        self.total_latency_s += latency_s

    def _maybe_update(self):
        if self.adapt and int(self.state.since_update) >= self.update_every:
            self.update()

    def update(self) -> None:
        """One §II.C periodic refinement: run both adaptation laws on the
        sliding window, score with the Eq. 10 reward, update UCB1."""
        s = self.state
        adaptive = AD.periodic_update(s.adaptive, self.acfg,
                                      beta_opt=float(s.beta_opt))
        self.state = dataclasses.replace(
            s, adaptive=adaptive, since_update=jnp.zeros((), jnp.int32))
        self._policy_mirror = None

    def record_requests(self, latencies_ms, missed=None) -> None:
        """Fold completed-request latency/deadline telemetry into the
        engine state (host-side write; the async scheduler calls this
        once per flushed bucket)."""
        self.state = ST.record_requests(self.state, latencies_ms, missed)

    def record_quotes(self, quotes_ms, realized_ms) -> None:
        """Fold admission-time SLO quote error telemetry (quote vs
        realized latency; host-side write, like record_requests)."""
        self.state = ST.record_quotes(self.state, quotes_ms, realized_ms)

    def stats(self) -> dict:
        """Serving counters + windowed §II.C statistics."""
        from repro.obs import stats as OBS_STATS
        s = self.state
        out = OBS_STATS.engine_summary(
            ST.telemetry_totals(s, sharded=False))
        out["total_latency_s"] = self.total_latency_s
        out["active_strategy"] = AD.STRATEGIES[
            int(s.adaptive["active_strategy"])]
        if out["served"]:
            w = AD.window_stats(s.adaptive, self.acfg)
            out["window"] = {k: np.asarray(v) for k, v in w.items()}
        return OBS_STATS.attach_requests(out, s)

    # ------------------------------------------------------------------
    # state round-trip
    # ------------------------------------------------------------------
    def save_state(self, path: str, step: int = 0):
        """Checkpoint the FULL serving state (one pytree) atomically."""
        from repro import checkpoint as CK
        return CK.save(path, step, self.state)

    def restore_state(self, path: str, step: int | None = None):
        # Pre-latency-telemetry checkpoints restore through the shared
        # prefix migration (state.LEGACY_FIELDS).
        self.state, step = ST.restore_with_migration(path, self.state, step)
        self._policy_mirror = None
        return step

    # ------------------------------------------------------------------
    # cost measurement (XLA cost analysis — exact, not hand counted)
    # ------------------------------------------------------------------
    def measure_costs(self, img_shape) -> np.ndarray:
        """Cumulative MACs per exit from XLA cost analysis of each
        stage+exit; also installs the result as ``self.cum_costs``."""
        if not self.family.staged:
            raise ValueError("measure_costs needs a staged family")
        fam, cfg = self.family, self.cfg
        x = jnp.zeros((1,) + tuple(img_shape))
        h = fam.apply_stem(self.params, x, cfg)
        cum, total = [], 0.0

        def flops_of(fn, *args):
            c = jax.jit(fn).lower(*args).compile().cost_analysis()
            return float(c.get("flops", 0.0))

        for s in range(self.n_exits):
            total += flops_of(
                lambda p, h, s=s: fam.apply_stage(p, h, s, cfg),
                self.params, h)
            h = fam.apply_stage(self.params, h, s, cfg)
            head = flops_of(
                lambda p, h, s=s: fam.apply_exit(p, h, s, cfg),
                self.params, h)
            cum.append((total + head) / 2.0)          # flops -> MACs
        self.cum_costs = np.asarray(cum)
        return self.cum_costs
