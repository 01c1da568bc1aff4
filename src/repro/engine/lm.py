"""LM decode engine — early-exit autoregressive serving on the DART gate.

The LM analogue of :class:`repro.engine.DartEngine` (re-homed from the
long-deleted ``repro.runtime.lm_server``, built on the shared
:class:`BatchCompactor` and :class:`EngineState`): per decode step the
layer stack runs stage-by-stage; exited samples *skip* the remaining
stages — their KV entries are filled by CALM-style state propagation —
and survivors (plus their cache rows) are compacted into power-of-two
buckets.

Two execution paths serve bit-identical decisions (ISSUE 4 tentpole):

* ``mode="eager"`` — the reference oracle: each stage dispatches its
  pieces (stage layers, exit head, gate, KV propagation, cache
  scatter) as separate ops from Python.
* ``mode="sharded"`` — constructed with ``mesh=make_serving_mesh()``:
  ONE donated-cache jitted program per ``(stage, bucket)`` fusing the
  stage forward, per-token confidence, the Eq. 8 decode-time difficulty
  EMA (embed step), Eq. 19 / Alg. 1 stage-threshold routing, CALM KV
  propagation for the exited rows AND the telemetry fold.  The KV
  cache, the hidden-state buffer and the :class:`EngineState` live as
  ``NamedSharding``-annotated donated pytrees (batch rows sharded over
  the ``("data",)`` mesh, policy replicated, telemetry per replica), so
  a decode step never reallocates the cache and never round-trips state
  through the host.  Compile caches are keyed by ``engine.bucket_key``
  — the same ``BatchCompactor`` bucket ∘ replica-multiple key the image
  engines and the async scheduler share.

The exit gate uses the ``lm-token`` confidence functional and the
``token_difficulty_ema`` decode-time difficulty estimator from the
engine registries.  Inside the fused step the whole exit head —
rmsnorm → unembed matmul → softmax confidence → Eq. 19 threshold gate —
is ONE ``repro.kernels.dispatch`` call (ISSUE 5 tentpole): dispatch
shard_maps the fused Pallas exit-head kernel over the ``("data",)``
axis on TPU (solving the "pallas_call does not partition under GSPMD"
blocker) and lowers to the bit-identical jnp chain on xla backends,
so the eager-oracle guarantee is unchanged on this CPU container.

MoE caveat: capacity-based expert dispatch makes a token's output
depend on which other tokens share its batch, so for MoE configs the
bucket-padded sharded path is not bit-identical to eager survivor
compaction; the oracle guarantee covers dense configs.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import adaptive as AD
from repro.core import difficulty as DIFF
from repro.core import thresholds as TH
from repro.core.routing import DartParams
from repro.engine import registry as REG
from repro.engine import state as ST
from repro.engine.compactor import (BatchCompactor, OutOfCapacity,
                                    PageAllocator, SlotPool)
from repro.engine.state import EngineState
from repro.models import layers as L
from repro.models import transformer_lm as TLM


def _stages(cfg):
    """[(start, end)) layer ranges; stage k ends at exit_layers[k]."""
    bounds = [0] + [e + 1 for e in sorted(cfg.exit_layers)] + [cfg.n_layers]
    return [(a, b) for a, b in zip(bounds[:-1], bounds[1:])]


def _stage_apply(params, x, cache_sl, cache_index, *, cfg, a, b):
    """Run layers [a, b) of the stack for one decode position.

    x: (B', 1, D); cache_sl: per-layer cache rows for exactly these
    layers.  Shared verbatim by the eager per-stage path and the fused
    sharded step, so both compute identical values row for row."""
    cos, sin = L.rope_freqs(
        cfg.qk_rope_dim if cfg.attn_kind == "mla" else cfg.hd,
        cache_sl[0]["c_kv"].shape[1] if cfg.attn_kind == "mla"
        else cache_sl[0]["k"].shape[1], cfg.rope_theta)
    new_sl = []
    for j, i in enumerate(range(a, b)):
        p = params["layers"][i]
        h = L.rmsnorm(p["attn_norm"], x)
        if cfg.attn_kind == "mla":
            att, c = L.mla_decode(p["attn"], h, cos, sin,
                                  cache_sl[j], cache_index)
        else:
            att, c = L.gqa_decode(p["attn"], h, cos, sin,
                                  cache_sl[j], cache_index)
        new_sl.append(c)
        x = x + att
        h2 = L.rmsnorm(p["ffn_norm"], x)
        if cfg.layer_is_moe(i):
            from repro.models.moe import moe_apply
            f, _ = moe_apply(p["moe"], h2, cfg.moe, ep_mode=cfg.moe_ep_mode)
        else:
            f = L.swiglu(p["ffn"], h2)
        x = x + f
    return x, new_sl


def _stage_apply_paged(params, x, pages_sl, page_table, page_idx, offset,
                       positions, *, cfg, a, b, gather_kw=None):
    """Run layers [a, b) for one decode position against the PAGED KV
    store — the continuous-batching mirror of :func:`_stage_apply`.

    x: (S, 1, D) — the full slot pool; ``positions`` is per-slot, so
    rows at different depths coexist in one launch.  ``page_idx`` is the
    write page per slot (out-of-range for rows that must not write) and
    ``page_table`` the read indirection; the per-layer math is the same
    functions the contiguous path uses, so values are bit-identical at
    equal padded view length."""
    psz = (pages_sl[0]["c_kv"] if cfg.attn_kind == "mla"
           else pages_sl[0]["k"]).shape[1]
    view_len = page_table.shape[1] * psz
    cos, sin = L.rope_freqs(
        cfg.qk_rope_dim if cfg.attn_kind == "mla" else cfg.hd,
        view_len, cfg.rope_theta)
    new_sl = []
    for j, i in enumerate(range(a, b)):
        p = params["layers"][i]
        h = L.rmsnorm(p["attn_norm"], x)
        if cfg.attn_kind == "mla":
            att, c = L.mla_decode_paged(p["attn"], h, cos, sin,
                                        pages_sl[j], page_table, page_idx,
                                        offset, positions,
                                        gather_kw=gather_kw)
        else:
            att, c = L.gqa_decode_paged(p["attn"], h, cos, sin,
                                        pages_sl[j], page_table, page_idx,
                                        offset, positions,
                                        gather_kw=gather_kw)
        new_sl.append(c)
        x = x + att
        h2 = L.rmsnorm(p["ffn_norm"], x)
        if cfg.layer_is_moe(i):
            from repro.models.moe import moe_apply
            f, _ = moe_apply(p["moe"], h2, cfg.moe, ep_mode=cfg.moe_ep_mode)
        else:
            f = L.swiglu(p["ffn"], h2)
        x = x + f
    return x, new_sl


class LMDecodeEngine:
    """Early-exit LM decoding behind the engine/session API.

        engine = LMDecodeEngine(cfg, params, dart)            # eager
        engine = LMDecodeEngine(cfg, params, dart,
                                mesh=make_serving_mesh())      # sharded
        tokens, stages = engine.generate(prompts, n_new=16)
        session = engine.session()      # queue-backed concurrent callers

    ``generate`` defaults to the sharded jitted path when a mesh was
    given and to the eager path otherwise; ``mode="eager"`` always runs
    the oracle.  All policy + telemetry lives in ``engine.state`` (an
    :class:`EngineState`), checkpointable via ``save_state`` /
    ``restore_state`` exactly like the classifier engines.
    """

    def __init__(self, cfg, params, dart: DartParams, *,
                 buckets=(1, 2, 4, 8, 16, 32, 64, 128),
                 confidence: str = "lm-token", mesh=None,
                 data_axis: str = "data"):
        assert not cfg.layer_scan
        self.cfg = cfg
        self.params = params
        self.compactor = BatchCompactor(buckets)
        self.mesh = mesh
        self.confidence = confidence
        self._conf_fn = REG.get_confidence(confidence)
        self.stages = _stages(cfg)
        self.n_exits = len(self.stages)
        self.exit_names = [str(i) for i in sorted(cfg.exit_layers)] \
            + ["final"]
        # cumulative layer fraction spent by a token exiting at stage s
        self.cum_costs = np.asarray(
            [b / cfg.n_layers for _, b in self.stages], np.float32)
        self.stats_exit = np.zeros(len(self.stages), np.int64)
        self.layers_run = 0
        self.layers_skipped = 0
        self._steps: dict = {}        # cache key -> compiled callable
        self.trace_counts: dict = {}  # cache key -> number of traces

        acfg = AD.AdaptiveConfig(n_exits=self.n_exits,
                                 n_classes=min(cfg.vocab, 64))
        self.acfg = acfg
        # request latency/deadline telemetry lives on the host
        self.request_window, self.state = ST.RequestWindow.take(
            EngineState.create(self.n_exits, acfg, dart))

        if mesh is not None:
            from repro.engine.sharded import _silence_donation_warning
            _silence_donation_warning()
            self.data_axis = data_axis
            self.n_replicas = int(mesh.shape[data_axis])
            self.replica_multiple = self.n_replicas
            self._repl = NamedSharding(mesh, P())
            self._row = NamedSharding(mesh, P(data_axis))
            self.params = jax.device_put(self.params, self._repl)
            # Donated steps would invalidate buffers the caller still
            # holds (its DartParams, a sibling engine) — take ownership
            # with a deep copy before placing the state.
            owned = jax.tree.map(
                lambda a: jnp.array(a, copy=True),
                ST.shard_telemetry(self.state, self.n_replicas))
            self._state_sh = ST.state_shardings(owned, self._repl,
                                                self._row)
            self.state = jax.device_put(owned, self._state_sh)
        else:
            self.n_replicas = 1
            self.replica_multiple = 1
        # kernels.dispatch shard_maps pallas backends over the data axis
        # inside the fused decode steps (xla partitions under GSPMD)
        self.kernel_kw = {} if mesh is None \
            else {"mesh": mesh, "axis": data_axis}

        cfgc = cfg
        self._stage_fns = [
            jax.jit(partial(_stage_apply, cfg=cfgc, a=a, b=b))
            for a, b in self.stages]
        self._exit_logits = [
            jax.jit(partial(lambda params, h, name: TLM.exit_logits(
                params, cfgc, h, name), name=n)) for n in self.exit_names]
        self._propagate = [
            jax.jit(partial(lambda params, h, cache, idx, fl:
                            TLM.lm_kv_propagate(params, h, cfgc, cache, idx,
                                                from_layer=fl), fl=b))
            for _, b in self.stages]
        self._embed = jax.jit(lambda params, t: L.embed(
            params["embed"], t).astype(cfgc.compute_dtype))
        self._cont_default = None  # lazy decoder for generate("continuous")

    # ------------------------------------------------------------------
    @property
    def dart(self) -> DartParams:
        """The routing-parameter view (reads the live EngineState)."""
        return self.state.dart

    #: confidence functionals provably bounded above by 1.0, for which
    #: the Eq. 19 rule-out bound is sound (see thresholds.min_exit_bound)
    _BOUNDED_CONF = ("softmax-max", "lm-token")

    def min_exit_bound(self, alpha_lo: float = 0.0) -> int:
        """Sound per-batch ``min_exit`` under the CURRENT policy: gates
        0..m-1 can never fire for any row with decode-time difficulty
        ≥ ``alpha_lo``.  The routing alpha is the Eq. 8 decode EMA
        (infimum 0.0), so callers without a tighter bound pass 0.0."""
        if self.confidence not in self._BOUNDED_CONF or self.n_exits < 2:
            return 0
        tau, coef, beta_diff = self._policy_host()
        return TH.min_exit_bound(tau, coef, beta_diff, alpha_lo)

    def _policy_host(self):
        """Host mirror of (tau, coef, beta_diff), cached on the array
        identities so the serving hot path never re-syncs policy."""
        key = (id(self.state.tau), id(self.state.coef))
        cached = getattr(self, "_policy_mirror", None)
        if cached is None or cached[0] != key:
            self._policy_mirror = (key, (
                np.asarray(self.state.tau, np.float32),
                np.asarray(self.state.coef, np.float32),
                float(self.state.beta_diff)))
        return self._policy_mirror[1]

    def prompt_alpha(self, prompt_tokens) -> np.ndarray:
        """Admission-time Eq. 8 difficulty of a prompt batch (B, S):
        the token-domain estimator over the input embeddings — what the
        exit-depth predictor conditions on before any backbone layer
        runs.  Host numpy out; one jitted launch per prompt length."""
        toks = jnp.asarray(np.asarray(prompt_tokens))
        key = ("lm-prompt-alpha", toks.shape[1])
        if key not in self._steps:
            cfg = self.cfg

            def step(params, t):
                self._count_trace(key)
                x = L.embed(params["embed"], t).astype(cfg.compute_dtype)
                return DIFF.token_difficulty(x)

            self._steps[key] = jax.jit(step)
        return np.asarray(self._steps[key](self.params, toks))

    def bucket_key(self, n: int) -> int:
        """THE compile-cache key for an ``n``-row decode bucket: the
        ``BatchCompactor`` bucket rounded up to a replica multiple —
        the same keying the image engines and the async scheduler
        use, so every serving path agrees on what shares a compiled
        shape."""
        return self.compactor.padded_size(n, self.replica_multiple)

    def session(self, cfg=None, *, continuous: bool = False, **kw):
        """Queue-backed session handle: drive this decode engine through
        the async scheduler (deadlines, priorities, consolidation of
        concurrent ``generate`` callers into shared bucketed decode
        loops).  ``continuous=True`` returns the slot-refill session
        over a :class:`ContinuousLMDecoder` instead (requests stream
        through the slot pool; no bucket flushes).  See
        :class:`repro.serving.LMDecodeSession` /
        :class:`repro.serving.lm_session.LMContinuousSession`."""
        from repro.serving.lm_session import (LMContinuousSession,
                                              LMDecodeSession)
        if continuous:
            return LMContinuousSession(self, cfg=cfg, **kw)
        return LMDecodeSession(self, cfg=cfg, **kw)

    def continuous(self, n_slots=None, page_size=8, max_len=None):
        """A slot-based continuous-batching decoder over a paged KV
        cache (ISSUE 7 tentpole).  Each call returns a fresh
        :class:`ContinuousLMDecoder` (its slot pool and page store are
        private mutable serving state); compiled steps are cached on
        the ENGINE keyed by pool geometry, so decoders of the same
        shape share traces."""
        return ContinuousLMDecoder(self, n_slots=n_slots,
                                   page_size=page_size, max_len=max_len)

    # ------------------------------------------------------------------
    # state round-trip (same machinery as DartEngine)
    # ------------------------------------------------------------------
    def save_state(self, path: str, step: int = 0):
        from repro import checkpoint as CK
        return CK.save(path, step, self.request_window.fill(self.state))

    def restore_state(self, path: str, step: int | None = None):
        state, step = ST.restore_with_migration(path, self.state, step)
        self.request_window, self.state = ST.RequestWindow.take(state)
        self._policy_mirror = None
        if self.mesh is not None:
            self._commit()
        return step

    def _commit(self):
        self.state = jax.device_put(self.state, self._state_sh)

    def stats(self) -> dict:
        """Decode telemetry: per-stage exit counts, tokens served, mean
        layer fraction spent (counters reduced over replicas when
        sharded)."""
        from repro.obs import stats as OBS_STATS
        tel = ST.telemetry_totals(self.state,
                                  sharded=self.mesh is not None)
        out = OBS_STATS.engine_summary(tel)
        out.update(
            layers_run=self.layers_run,
            layers_skipped=self.layers_skipped,
            replicas=self.n_replicas,
            continuous={
                "slot_steps": int(tel["slot_steps"]),
                "decode_steps": int(tel["decode_steps"]),
                "pages_peak": int(np.asarray(self.state.pages_peak))})
        return OBS_STATS.attach_requests(out, self.request_window)

    def record_requests(self, latencies_ms, missed=None) -> None:
        """Fold completed-request latency/deadline telemetry into the
        engine's host-side request window (the LM session calls this
        once per flushed decode bucket)."""
        self.request_window.record_requests(latencies_ms, missed)

    def record_quotes(self, quotes_ms, realized_ms) -> None:
        """Fold admission-time SLO quote error telemetry (quote vs
        realized latency) into the request window."""
        self.request_window.record_quotes(quotes_ms, realized_ms)

    def _count_trace(self, key):
        # Runs in the Python body of a step function, i.e. once per trace.
        self.trace_counts[key] = self.trace_counts.get(key, 0) + 1

    # ------------------------------------------------------------------
    # eager path (the oracle)
    # ------------------------------------------------------------------
    def init_cache(self, batch, max_len):
        return TLM.lm_init_cache(self.cfg, batch, max_len)

    def prefill(self, tokens, cache):
        cache, _ = TLM.lm_prefill(self.params, jnp.asarray(tokens),
                                  self.cfg, cache)
        return cache

    def decode_step(self, tokens, cache, cache_index, alpha, *,
                    record: bool | None = None):
        """tokens: (B,) int; cache: full-depth list; alpha: (B,) difficulty.
        Returns (next_token (B,), exit_stage (B,), new_cache, new_alpha).

        ``record``: fold the step into ``state`` telemetry AND the host
        diagnostics (stats_exit / layers_run / layers_skipped).
        Defaults on for a pure-eager engine and OFF on a sharded one —
        there the eager path is the oracle, and a host-side fold would
        both pollute serving telemetry and broadcast scalar adds over
        the state's leading replica axis."""
        if record is None:
            record = self.mesh is None
        b = tokens.shape[0]
        x_full = self._embed(self.params, jnp.asarray(tokens)[:, None])
        alpha = np.asarray(DIFF.token_difficulty_ema(jnp.asarray(alpha),
                                                     x_full))
        tau = np.asarray(self.state.tau, np.float32)
        coef = np.asarray(self.state.coef, np.float32)
        beta_diff = float(self.state.beta_diff)

        out_tok = np.zeros(b, np.int64)
        out_stage = np.zeros(b, np.int64)
        active = np.arange(b)
        x = x_full
        n_stages = len(self.stages)
        cache = list(cache)

        for s, (a, bnd) in enumerate(self.stages):
            n = len(active)
            bucket = self.compactor.bucket_for(n)
            act = jnp.asarray(active)
            # gather cache rows for the active set (+pad with row 0)
            gather_idx = self.compactor.pad(np.asarray(active), bucket,
                                            fill=0).astype(np.int64)
            cache_sl = [jax.tree.map(
                lambda c: jnp.take(c, jnp.asarray(gather_idx), axis=0),
                cache[i]) for i in range(a, bnd)]
            x_pad = self.compactor.pad(x, bucket)
            x_new, new_sl = self._stage_fns[s](self.params, x_pad, cache_sl,
                                               cache_index)
            # scatter updated cache rows back
            for j, i in enumerate(range(a, bnd)):
                cache[i] = jax.tree.map(
                    lambda full, sl: full.at[act].set(sl[:n]),
                    cache[i], new_sl[j])
            if record:
                self.layers_run += (bnd - a) * n

            logits = self._exit_logits[s](self.params, x_new[:n, 0])
            conf = self._conf_fn(logits)
            pred = jnp.argmax(logits, -1)
            conf, pred = np.asarray(conf), np.asarray(pred)

            if s < n_stages - 1:
                eff = np.asarray(TH.stage_threshold(
                    tau[s], coef[s], alpha[active], beta_diff))
                fire = conf > eff
            else:
                fire = np.ones(n, bool)
            done = active[fire]
            out_tok[done] = pred[fire]
            out_stage[done] = s
            if record:
                self.stats_exit[s] += int(fire.sum())

            if s < n_stages - 1 and fire.any():
                # CALM state propagation for the exited rows
                h_exit = x_new[:n][jnp.asarray(np.nonzero(fire)[0])]
                sub = [jax.tree.map(lambda c: jnp.take(
                    c, jnp.asarray(done), axis=0), cache[i])
                    for i in range(len(cache))]
                sub = self._propagate[s](self.params, h_exit[:, 0], sub,
                                         cache_index)
                for i in range(self.stages[s][1], self.cfg.n_layers):
                    cache[i] = jax.tree.map(
                        lambda full, sl: full.at[jnp.asarray(done)].set(sl),
                        cache[i], sub[i])
                if record:
                    self.layers_skipped += \
                        (self.cfg.n_layers - bnd) * int(fire.sum())
            keep = ~fire
            if not keep.any():
                break
            x = x_new[:n][jnp.asarray(np.nonzero(keep)[0])]
            active = active[keep]
        if record:
            self._record_host(out_stage)
        return out_tok, out_stage, cache, alpha

    def _record_host(self, out_stage) -> None:
        """Eager-path telemetry fold (numpy, one decode step)."""
        s = self.state
        b = len(out_stage)
        counts = np.bincount(out_stage, minlength=self.n_exits)
        self.state = dataclasses.replace(
            s, served=s.served + jnp.asarray(b, jnp.int32),
            exit_counts=s.exit_counts + jnp.asarray(counts, jnp.int32),
            total_macs=s.total_macs + float(np.sum(
                self.cum_costs[out_stage])),
            since_update=s.since_update + jnp.asarray(b, jnp.int32))

    # ------------------------------------------------------------------
    # sharded path: fused per-(stage, bucket) donated-cache steps
    # ------------------------------------------------------------------
    def _embed_step(self, bp: int):
        """Fused embed + Eq. 8 decode-time difficulty EMA for a
        ``bp``-row bucket (the per-decode-step prologue)."""
        key = ("lm-embed", bp)
        if key in self._steps:
            return self._steps[key]
        cfg = self.cfg

        def step(params, toks, alpha):
            self._count_trace(key)
            x_full = L.embed(params["embed"],
                             toks[:, None]).astype(cfg.compute_dtype)
            alpha = DIFF.token_difficulty_ema(alpha, x_full)
            return x_full, alpha

        self._steps[key] = jax.jit(step, donate_argnums=(2,),
                                   out_shardings=self._row)
        return self._steps[key]

    def _prefill_step(self, bp: int, plen: int, max_len: int):
        key = ("lm-prefill", bp, plen, max_len)
        if key in self._steps:
            return self._steps[key]
        cfg = self.cfg

        def step(params, tokens, cache):
            self._count_trace(key)
            cache, _ = TLM.lm_prefill(params, tokens, cfg, cache)
            return cache

        self._steps[key] = jax.jit(step, donate_argnums=(2,),
                                   out_shardings=self._row)
        return self._steps[key]

    def _stage_step(self, s: int, sp: int, bp: int, max_len: int):
        """ONE compiled decode step for (stage ``s``, survivor bucket
        ``sp``) over a ``bp``-row generate bucket: cache-row gather,
        stage forward, exit head + confidence, Eq. 19 threshold + Alg. 1
        gate, token/stage scatter, CALM KV propagation for the fired
        rows, telemetry fold.  The cache, hidden buffer, token buffers
        and EngineState are donated, so repeated steps re-use their
        buffers (no realloc)."""
        key = ("lm-stage", s, sp, bp, max_len)
        if key in self._steps:
            return self._steps[key]
        a, bnd = self.stages[s]
        cfg = self.cfg
        final = s == len(self.stages) - 1
        exit_name = self.exit_names[s]

        def step(params, state, cache, x_full, toks, stg, idx, valid,
                 alpha, cache_index):
            self._count_trace(key)
            # gather the survivors' rows; padded lanes (idx == bp) clip
            # to the last (padding) row and are masked by ``valid``
            x = jnp.take(x_full, idx, axis=0, mode="clip")
            cache_sl = [jax.tree.map(
                lambda c: jnp.take(c, idx, axis=0, mode="clip"), cache[i])
                for i in range(a, bnd)]
            x_new, new_sl = _stage_apply(params, x, cache_sl, cache_index,
                                         cfg=cfg, a=a, b=bnd)
            cache = list(cache)
            for j, i in enumerate(range(a, bnd)):
                cache[i] = jax.tree.map(
                    lambda full, sl: full.at[idx].set(sl, mode="drop"),
                    cache[i], new_sl[j])
            x_full = x_full.at[idx].set(x_new, mode="drop")

            vb = valid > 0
            if final:
                # Alg. 1 line 12: the final head always accepts
                eff = jnp.full(idx.shape, -1.0, jnp.float32)
            else:
                al = jnp.take(alpha, idx, mode="clip")
                eff = TH.stage_threshold(state.tau[s], state.coef[s], al,
                                         state.beta_diff)
            conf, pred, fire = self._head_traced(params, x_new[:, 0],
                                                 exit_name, eff)
            # the unconditional final accept must not depend on the
            # confidence functional's range (the -1.0 eff is only a
            # belt-and-braces sentinel for bounded functionals)
            fire = vb if final else (fire & vb)
            idx_fire = jnp.where(fire, idx, bp)  # non-fired -> dropped
            toks = toks.at[idx_fire].set(pred.astype(toks.dtype),
                                         mode="drop")
            stg = stg.at[idx_fire].set(s, mode="drop")
            if not final:
                cache = self._propagate_traced(params, cache, x_new[:, 0],
                                               idx_fire, cache_index, bnd)
            state = self._fold_decode(state, s, fire)
            return state, (cache, x_full, toks, stg, fire)

        self._steps[key] = jax.jit(
            step, donate_argnums=(1, 2, 3, 4, 5),
            out_shardings=(self._state_sh, self._row))
        return self._steps[key]

    def _stage_fwd_step(self, s: int, sp: int, bp: int, max_len: int):
        """Forward-only twin of :meth:`_stage_step` for gates the
        predictor ruled out (``min_exit`` head-skip): cache-row gather,
        stage forward, cache + hidden scatter — NO exit head, NO Alg. 1
        gate, NO propagation, NO telemetry fold and NO host fire sync.
        Sound only when the gate provably can't fire (every row
        survives), so decisions stay bit-identical to the oracle."""
        key = ("lm-stage-fwd", s, sp, bp, max_len)
        if key in self._steps:
            return self._steps[key]
        a, bnd = self.stages[s]
        cfg = self.cfg

        def step(params, cache, x_full, idx, cache_index):
            self._count_trace(key)
            x = jnp.take(x_full, idx, axis=0, mode="clip")
            cache_sl = [jax.tree.map(
                lambda c: jnp.take(c, idx, axis=0, mode="clip"), cache[i])
                for i in range(a, bnd)]
            x_new, new_sl = _stage_apply(params, x, cache_sl, cache_index,
                                         cfg=cfg, a=a, b=bnd)
            cache = list(cache)
            for j, i in enumerate(range(a, bnd)):
                cache[i] = jax.tree.map(
                    lambda full, sl: full.at[idx].set(sl, mode="drop"),
                    cache[i], new_sl[j])
            x_full = x_full.at[idx].set(x_new, mode="drop")
            return cache, x_full

        self._steps[key] = jax.jit(step, donate_argnums=(1, 2),
                                   out_shardings=self._row)
        return self._steps[key]

    def _head_traced(self, params, h, exit_name: str, eff):
        """The decode-time exit decision for one stage: rmsnorm → unembed
        matmul → softmax confidence → Eq. 19 gate, as ONE
        ``kernels.dispatch`` call for the ``lm-token`` functional (the
        fused Pallas exit-head kernel on TPU, shard_map-wrapped over the
        data axis; the bit-identical jnp chain on xla).  Returns
        (conf, pred, fire bool)."""
        cfg = self.cfg
        if self.confidence == "lm-token":
            from repro.kernels import dispatch as KD
            norm = params["final_norm"] if exit_name == "final" \
                else params["exit_heads"][exit_name]["norm"]
            conf, pred, fire = KD.exit_head_gate(
                h, norm["scale"], TLM._unembed_table(params, cfg), eff,
                **self.kernel_kw)
            return conf, pred, fire > 0
        logits = TLM.exit_logits(params, cfg, h, exit_name)
        conf = self._conf_fn(logits)
        return conf, jnp.argmax(logits, -1), conf > eff

    def _propagate_traced(self, params, cache, h_exit, idx_fire,
                          cache_index, from_layer):
        """CALM propagation inside the fused step: the SAME projection
        math as the eager path (``transformer_lm.lm_kv_project`` is the
        one implementation both share), scattered straight into rows
        ``[idx_fire, cache_index]`` of the full donated cache
        (non-fired rows carry the out-of-bounds index and are
        dropped)."""
        cfg = self.cfg
        rows = TLM.lm_kv_project(params, h_exit, cfg, cache, cache_index,
                                 from_layer)
        cache = list(cache)
        for i, r in zip(range(from_layer, cfg.n_layers), rows):
            c = dict(cache[i])
            for name, val in r.items():
                c[name] = c[name].at[idx_fire, cache_index].set(
                    val[:, 0].astype(c[name].dtype), mode="drop")
            cache[i] = c
        return cache

    def _fold_decode(self, state: EngineState, s: int, fire) -> EngineState:
        """Per-replica telemetry fold for one (stage, bucket) step: each
        replica's segment of the padded bucket lands in its own
        counters (``stats()`` reduces across replicas)."""
        r = self.n_replicas
        per = fire.shape[0] // r
        f = fire.astype(jnp.float32).reshape(r, per)
        n_new = f.sum(1).astype(jnp.int32)
        return dataclasses.replace(
            state,
            served=state.served + n_new,
            exit_counts=state.exit_counts.at[:, s].add(n_new),
            total_macs=state.total_macs
            + n_new.astype(jnp.float32) * float(self.cum_costs[s]),
            since_update=state.since_update + n_new)

    def _fold_decode_dense(self, state: EngineState, s: int,
                           fire) -> EngineState:
        """Telemetry fold against an UNSHARDED state (scalar counters,
        (E,) exit_counts) — the continuous decoder's mesh-less twin of
        :meth:`_fold_decode`."""
        n_new = jnp.sum(fire.astype(jnp.int32))
        return dataclasses.replace(
            state,
            served=state.served + n_new,
            exit_counts=state.exit_counts.at[s].add(n_new),
            total_macs=state.total_macs
            + n_new.astype(jnp.float32) * float(self.cum_costs[s]),
            since_update=state.since_update + n_new)

    # ------------------------------------------------------------------
    # generation
    # ------------------------------------------------------------------
    def generate(self, prompt_tokens: np.ndarray, n_new: int,
                 max_len: int | None = None, mode: str | None = None,
                 min_exit: int = 0):
        """prompt_tokens: (B, S0).  Greedy generation with early exits.
        Returns (tokens (B, n_new), exit stages (B, n_new)).

        mode — "sharded" (default when built with ``mesh=``): the fused
        donated-cache compiled decode loop; "eager": the per-stage
        oracle path (never records telemetry on a sharded engine);
        "continuous": the slot-pool continuous-batching decoder over
        the paged KV cache (rows admitted as slots free up — no bucket
        flushes, ONE compiled decode step for every admission
        pattern).  Batches larger than the biggest bucket are split
        into chunks (each chunk gets its own KV cache); the continuous
        path instead streams rows through the slot pool.

        min_exit — gates below this stage are skipped on the sharded
        path (forward-only stage steps: no exit head, no gate launch,
        no fire host sync).  Sound when it comes from
        :meth:`min_exit_bound`, where the gate provably never fires —
        tokens and stages stay bit-identical to the oracle.  The eager
        and continuous paths always run the full oracle."""
        if not 0 <= int(min_exit) < self.n_exits:
            raise ValueError(f"min_exit {min_exit} out of range for "
                             f"{self.n_exits} exits")
        min_exit = int(min_exit)
        if mode is None:
            mode = "sharded" if self.mesh is not None else "eager"
        if mode not in ("sharded", "eager", "continuous"):
            raise ValueError(
                f"unknown mode {mode!r}; known: sharded, eager, "
                "continuous")
        if mode == "sharded" and self.mesh is None:
            raise ValueError(
                "mode='sharded' needs a mesh — construct with "
                "LMDecodeEngine(..., mesh=make_serving_mesh())")
        if mode == "continuous":
            return self._generate_continuous(np.asarray(prompt_tokens),
                                             n_new)
        b, s0 = prompt_tokens.shape
        if b > self.compactor.max_bucket:
            outs, stgs = [], []
            for a, z in self.compactor.chunks(b):
                o, st = self.generate(prompt_tokens[a:z], n_new, max_len,
                                      mode=mode, min_exit=min_exit)
                outs.append(o)
                stgs.append(st)
            return np.concatenate(outs), np.concatenate(stgs)
        if mode == "sharded":
            return self._generate_sharded(prompt_tokens, n_new, max_len,
                                          min_exit=min_exit)
        return self._generate_eager(prompt_tokens, n_new, max_len)

    def _generate_eager(self, prompt_tokens, n_new, max_len=None):
        b, s0 = prompt_tokens.shape
        max_len = max_len or (s0 + n_new + 1)
        cache = self.init_cache(b, max_len)
        cache = self.prefill(prompt_tokens[:, :-1], cache)
        alpha = np.full((b,), 0.5, np.float32)
        toks = prompt_tokens[:, -1]
        out = []
        stages = []
        for t in range(n_new):
            # decode_step's default record already disables the fold on
            # a sharded engine (the eager path is the oracle there)
            toks, stage, cache, alpha = self.decode_step(
                toks, cache, s0 - 1 + t, alpha)
            out.append(toks.copy())
            stages.append(stage.copy())
        return np.stack(out, 1), np.stack(stages, 1)

    def _generate_sharded(self, prompt_tokens, n_new, max_len=None,
                          min_exit=0):
        cfg = self.cfg
        prompts = np.asarray(prompt_tokens)
        b, s0 = prompts.shape
        bp = self.bucket_key(b)
        max_len = max_len or (s0 + n_new + 1)
        cache = jax.device_put(self.init_cache(bp, max_len), self._row)
        pad = self.compactor.pad(prompts.astype(np.int64), bp)
        if s0 > 1:
            cache = self._prefill_step(bp, s0 - 1, max_len)(
                self.params, jnp.asarray(pad[:, :-1]), cache)
        alpha = jax.device_put(jnp.full((bp,), 0.5, jnp.float32),
                               self._row)
        toks = jax.device_put(jnp.asarray(pad[:, -1], jnp.int32),
                              self._row)
        stg = jax.device_put(jnp.zeros((bp,), jnp.int32), self._row)
        n_layers = cfg.n_layers
        out, stages_out = [], []
        for t in range(n_new):
            ci = s0 - 1 + t
            x_full, alpha = self._embed_step(bp)(self.params, toks, alpha)
            active = np.arange(b)
            for s, (a, bnd) in enumerate(self.stages):
                n = active.size
                sp = self.bucket_key(n)
                idx = np.full(sp, bp, np.int32)
                idx[:n] = active
                if s < min_exit and s < len(self.stages) - 1:
                    # gate ruled out for every row: forward-only step
                    # (no exit head, no gate, no fire host sync)
                    cache, x_full = self._stage_fwd_step(
                        s, sp, bp, max_len)(self.params, cache, x_full,
                                            jnp.asarray(idx), ci)
                    self.layers_run += (bnd - a) * n
                    continue
                valid = np.zeros(sp, np.float32)
                valid[:n] = 1.0
                self.state, (cache, x_full, toks, stg, fire) = \
                    self._stage_step(s, sp, bp, max_len)(
                        self.params, self.state, cache, x_full, toks, stg,
                        jnp.asarray(idx), jnp.asarray(valid), alpha, ci)
                # the ONE host sync per stage: survivors are
                # data-dependent shapes
                fire_np = np.asarray(fire)[:n]
                nf = int(fire_np.sum())
                self.layers_run += (bnd - a) * n
                self.stats_exit[s] += nf
                if s < len(self.stages) - 1:
                    self.layers_skipped += (n_layers - bnd) * nf
                active = active[~fire_np]
                if active.size == 0:
                    break
            out.append(np.asarray(toks)[:b].astype(np.int64))
            stages_out.append(np.asarray(stg)[:b].astype(np.int64))
        return np.stack(out, 1), np.stack(stages_out, 1)

    def _generate_continuous(self, prompts, n_new):
        """Drive the (engine-owned) default continuous decoder: admit
        each prompt row as its own request whenever the slot pool has
        room, step until every row finished.  Rows at different depths
        coexist in one launch, so a large batch streams through
        ``n_slots`` slots without bucket flushes."""
        b, s0 = prompts.shape
        if self._cont_default is None:
            self._cont_default = self.continuous()
        dec = self._cont_default
        if not dec.fits_ever(1, s0, n_new):
            raise ValueError(
                f"prompt_len={s0} + n_new={n_new} exceeds the default "
                f"continuous decoder's max_len={dec.max_len}; build one "
                "via engine.continuous(max_len=...) and admit directly")
        out_t: list = [None] * b
        out_s: list = [None] * b
        pending = list(range(b))
        done = 0
        while done < b:
            while pending and dec.can_admit(1, s0, n_new):
                i = pending.pop(0)
                dec.admit(prompts[i:i + 1], n_new, tag=("gen", i))
            if not dec.active_rows:
                raise RuntimeError("continuous generate stalled with "
                                   "pending rows and an empty pool")
            for tag, toks, stgs in dec.step():
                if isinstance(tag, tuple) and tag[0] == "gen":
                    out_t[tag[1]] = toks[0]
                    out_s[tag[1]] = stgs[0]
                    done += 1
        return np.stack(out_t), np.stack(out_s)


class ContinuousLMDecoder:
    """Slot-based continuous batching over a paged KV cache.

        dec = engine.continuous(n_slots=8, page_size=8, max_len=64)
        dec.admit(prompts, n_new=12, tag="req-0")   # any step
        events = dec.step()   # [(tag, tokens (B, n), stages (B, n))]

    ONE fixed-shape compiled decode step serves the whole pool: an
    active-mask plus per-slot position counter lets rows at different
    depths (and different requests) coexist in a single launch, so
    admission never retraces — ``trace_counts`` stays at one
    ``("lm-cont-decode", ...)`` entry for every admission pattern.

    KV lives in a page store (n_pages, page_size, ...) per layer with a
    free-list :class:`PageAllocator`; each slot reads through its row of
    the page table (a ``kernels.dispatch``-routed gather) and writes
    through a per-slot (page, offset) scatter.  A row that fires its
    exit gate stops writing KV *within the same launch* (its write page
    index goes out of range → dropped), and a finished request frees its
    slot and pages to the admission queue THAT step — Alg. 1 early
    termination is what creates serving capacity.

    Bit-identity: the per-layer math is the same functions the eager
    oracle uses, and masked-out view positions contribute exact zeros,
    so tokens AND exit stages match ``generate(mode="eager",
    max_len=dec.view_len)`` row for row (dense configs — the MoE caveat
    from the module docstring applies).

    Under a mesh, slots and pages are sharded over the data axis and the
    allocator keeps slot s's pages inside slot s's replica range, so the
    Pallas gather's shard_map sees local page ids.
    """

    def __init__(self, engine: LMDecodeEngine, *, n_slots=None,
                 page_size=8, max_len=None):
        from repro.engine.sharded import _silence_donation_warning
        _silence_donation_warning()
        self.eng = engine
        cfg = engine.cfg
        if engine.mesh is None and not getattr(engine, "_state_owned",
                                               False):
            # the continuous step DONATES the engine state — on a
            # mesh-less engine its leaves may still alias the caller's
            # DartParams (or a sibling engine built from them); take
            # ownership before the first donation, like the sharded
            # constructor does
            engine.state = jax.tree.map(
                lambda a: jnp.array(a, copy=True), engine.state)
            engine._state_owned = True
        if max_len is None:
            max_len = cfg.max_seq
        if n_slots is None:
            n_slots = max(min(16, engine.compactor.max_bucket),
                          engine.replica_multiple)
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        if n_slots % engine.replica_multiple:
            raise ValueError(
                f"n_slots={n_slots} not a multiple of the replica "
                f"multiple {engine.replica_multiple}")
        self.n_slots = int(n_slots)
        self.page_size = int(page_size)
        self.max_len = int(max_len)
        self.pages_per_slot = -(-self.max_len // self.page_size)
        #: dense attention view length (page-table width × page size);
        #: the eager oracle must be run at THIS max_len for bit-identity
        self.view_len = self.pages_per_slot * self.page_size
        self.n_pages = self.n_slots * self.pages_per_slot
        self.pool = SlotPool(self.n_slots, engine.n_replicas)
        self.allocator = PageAllocator(self.n_pages, engine.n_replicas)

        # device state: per-layer page stores + the Eq. 8 difficulty EMA
        self.pages = TLM.lm_init_cache(cfg, self.n_pages, self.page_size)
        self.alpha = jnp.full((self.n_slots,), 0.5, jnp.float32)
        if engine.mesh is not None:
            self.pages = jax.device_put(self.pages, engine._row)
            self.alpha = jax.device_put(self.alpha, engine._row)

        # host bookkeeping (numpy; shipped into each step as operands)
        s = self.n_slots
        self.pos = np.zeros(s, np.int32)        # next KV write position
        self.active = np.zeros(s, np.int32)
        self.fresh = np.zeros(s, np.int32)      # reset EMA to 0.5
        self.tokens = np.zeros(s, np.int32)     # last emitted token
        self.page_table = np.zeros((s, self.pages_per_slot), np.int32)
        self._requests: dict = {}               # rid -> record
        self._slot_req: dict = {}               # slot -> (rid, row)
        self._slot_pages: dict = {}             # slot -> [page ids]
        self._next_rid = 0
        self._pages_hwm = 0

    # -- admission ------------------------------------------------------
    @property
    def active_rows(self) -> int:
        return int(self.active.sum())

    def pages_needed(self, s0: int, n_new: int) -> int:
        """Pages reserved up-front at admission: the last KV position a
        request writes is ``s0 + n_new - 2`` (the final generated
        token's step reads the cache but its own KV write is the one
        that would serve step n_new+1)."""
        return max(1, -(-(s0 + n_new - 1) // self.page_size))

    def fits_ever(self, n_rows: int, s0: int, n_new: int) -> bool:
        """Could this request EVER be admitted (even into an empty
        pool)?  Sessions reject impossible requests instead of queueing
        them forever."""
        return (n_rows <= self.n_slots
                and self.pages_needed(s0, n_new) <= self.pages_per_slot)

    def _placement(self, n_rows: int, npg: int):
        """First-fit of ``n_rows`` (slot + npg pages each) into replica
        ranges — a slot's pages always come from its own range, so
        sharded gathers stay local.  None if it doesn't fit now."""
        r = self.eng.n_replicas
        slots = [self.pool.available(i) for i in range(r)]
        pages = [self.allocator.available(i) for i in range(r)]
        plan = []
        for _ in range(n_rows):
            for i in range(r):
                if slots[i] and pages[i] >= npg:
                    plan.append(i)
                    slots[i] -= 1
                    pages[i] -= npg
                    break
            else:
                return None
        return plan

    def can_admit(self, n_rows: int, s0: int, n_new: int) -> bool:
        if not self.fits_ever(n_rows, s0, n_new):
            return False
        return self._placement(n_rows,
                               self.pages_needed(s0, n_new)) is not None

    def admit(self, prompt_tokens, n_new: int, tag=None):
        """Admit one request (B rows, shared prompt length / n_new).
        All-or-nothing: raises :class:`OutOfCapacity` when the pool
        can't place every row right now.  Prompts prefill straight into
        the request's own pages; decode joins the pool next step."""
        prompts = np.asarray(prompt_tokens)
        b, s0 = prompts.shape
        if n_new < 1:
            raise ValueError("n_new must be >= 1")
        if not self.fits_ever(b, s0, n_new):
            raise ValueError(
                f"request (rows={b}, s0={s0}, n_new={n_new}) can never "
                f"fit this decoder (n_slots={self.n_slots}, "
                f"max_len={self.max_len})")
        npg = self.pages_needed(s0, n_new)
        plan = self._placement(b, npg)
        if plan is None:
            raise OutOfCapacity(
                f"pool full: rows={b} x pages={npg} don't fit "
                f"({self.pool.in_use}/{self.n_slots} slots, "
                f"{self.allocator.in_use}/{self.n_pages} pages in use)")
        rid = self._next_rid
        self._next_rid += 1
        rec = {"rid": rid, "tag": rid if tag is None else tag,
               "slots": [], "remaining": int(n_new),
               "toks": [[] for _ in range(b)],
               "stgs": [[] for _ in range(b)]}
        for row in range(b):
            slot = self.pool.acquire(plan[row])
            pg = self.allocator.alloc(npg, plan[row])
            self._slot_pages[slot] = pg
            self._slot_req[slot] = (rid, row)
            rec["slots"].append(slot)
            self.page_table[slot, :] = 0
            self.page_table[slot, :npg] = pg
            self.pos[slot] = s0 - 1
            self.tokens[slot] = int(prompts[row, -1])
            self.active[slot] = 1
            self.fresh[slot] = 1
            if s0 > 1:
                self._prefill_row(prompts[row, :-1], pg)
        self._requests[rid] = rec
        self._pages_hwm = max(self._pages_hwm, self.allocator.in_use)
        st = self.eng.state
        if self._pages_hwm > int(np.asarray(st.pages_peak)):
            peak = jnp.asarray(self._pages_hwm, jnp.int32)
            if self.eng.mesh is not None:
                peak = jax.device_put(peak, self.eng._repl)
            self.eng.state = dataclasses.replace(st, pages_peak=peak)
        return rec["tag"]

    def release(self, tag) -> bool:
        """Cancel an in-flight request mid-cascade: frees its slots and
        KV pages immediately (no completion event is emitted)."""
        for rid, rec in list(self._requests.items()):
            if rec["tag"] == tag or rid == tag:
                self._release_slots(rec["slots"])
                del self._requests[rid]
                return True
        return False

    def _release_slots(self, slots) -> None:
        for slot in slots:
            self.allocator.free(self._slot_pages.pop(slot))
            self.pool.release(slot)
            del self._slot_req[slot]
            self.active[slot] = 0
            self.fresh[slot] = 0
            self.pos[slot] = 0
            self.tokens[slot] = 0
            self.page_table[slot, :] = 0

    # -- compiled steps (cached on the engine, keyed by geometry) -------
    def _prefill_row(self, prompt, pg) -> None:
        plen = int(prompt.shape[0])
        npre = -(-plen // self.page_size)
        step = self._prefill_step(plen, npre)
        self.pages = step(self.eng.params,
                          jnp.asarray(prompt[None, :], jnp.int32),
                          self.pages,
                          jnp.asarray(np.asarray(pg[:npre], np.int32)))

    def _prefill_step(self, plen: int, npre: int):
        """Prefill one row into its reserved pages: the SAME
        ``lm_prefill`` as the oracle into a temporary dense cache,
        reshaped to (npre, psz, ...) page rows and scattered at the
        row's page ids (donated page store)."""
        eng = self.eng
        key = ("lm-cont-prefill", plen, npre, self.page_size)
        if key in eng._steps:
            return eng._steps[key]
        cfg = eng.cfg
        psz = self.page_size

        def step(params, tokens, pages, page_ids):
            eng._count_trace(key)
            tmp = TLM.lm_init_cache(cfg, 1, npre * psz)
            tmp, _ = TLM.lm_prefill(params, tokens, cfg, tmp)
            pages = list(pages)
            for i in range(cfg.n_layers):
                pg = dict(pages[i])
                for name, leaf in tmp[i].items():
                    rows = leaf[0].reshape((npre, psz) + leaf.shape[2:])
                    pg[name] = pg[name].at[page_ids].set(
                        rows.astype(pg[name].dtype))
                pages[i] = pg
            return pages

        kw = {} if eng.mesh is None else {"out_shardings": eng._row}
        eng._steps[key] = jax.jit(step, donate_argnums=(2,), **kw)
        return eng._steps[key]

    def _embed_step(self):
        """Embed + fresh-slot EMA reset + Eq. 8 decode-time difficulty
        EMA for the whole pool (donates the EMA buffer)."""
        eng = self.eng
        key = ("lm-cont-embed", self.n_slots)
        if key in eng._steps:
            return eng._steps[key]
        cfg = eng.cfg

        def step(params, toks, alpha, fresh):
            eng._count_trace(key)
            x = L.embed(params["embed"],
                        toks[:, None]).astype(cfg.compute_dtype)
            alpha = jnp.where(fresh > 0, jnp.float32(0.5), alpha)
            alpha = DIFF.token_difficulty_ema(alpha, x)
            return x, alpha

        kw = {} if eng.mesh is None else {"out_shardings": eng._row}
        eng._steps[key] = jax.jit(step, donate_argnums=(2,), **kw)
        return eng._steps[key]

    def _decode_step(self):
        """THE continuous decode step: every stage for every slot in one
        fixed-shape launch.  ``run`` masks inactive slots and rows that
        fired at an earlier stage this step (their KV write page goes
        out of range → scatter-dropped; their recorded token/stage stop
        updating), so one trace serves every admission pattern, every
        depth mix, every survivor count."""
        eng = self.eng
        key = ("lm-cont-decode", self.n_slots, self.page_size,
               self.pages_per_slot)
        if key in eng._steps:
            return eng._steps[key]
        cfg = eng.cfg
        psz = self.page_size
        n_pages = self.n_pages
        view_len = self.view_len
        n_layers = cfg.n_layers
        stages = eng.stages
        final_s = len(stages) - 1
        gather_kw = eng.kernel_kw
        fold = eng._fold_decode if eng.mesh is not None \
            else eng._fold_decode_dense

        def step(params, state, pages, x, alpha, pos, active, page_table):
            eng._count_trace(key)
            s_pool = pos.shape[0]
            run = active > 0
            page_w = jnp.take_along_axis(
                page_table, (pos // psz)[:, None], axis=1)[:, 0]
            off = pos % psz
            toks_out = jnp.zeros((s_pool,), jnp.int32)
            stg_out = jnp.zeros((s_pool,), jnp.int32)
            pages = list(pages)
            for s, (a, bnd) in enumerate(stages):
                final = s == final_s
                pidx = jnp.where(run, page_w, n_pages)  # OOB -> no write
                x, new_sl = _stage_apply_paged(
                    params, x, [pages[i] for i in range(a, bnd)],
                    page_table, pidx, off, pos,
                    cfg=cfg, a=a, b=bnd, gather_kw=gather_kw)
                for j, i in enumerate(range(a, bnd)):
                    pages[i] = new_sl[j]
                if final:
                    # Alg. 1 line 12: the final head always accepts
                    eff = jnp.full((s_pool,), -1.0, jnp.float32)
                else:
                    eff = TH.stage_threshold(state.tau[s], state.coef[s],
                                             alpha, state.beta_diff)
                conf, pred, fire = eng._head_traced(
                    params, x[:, 0], eng.exit_names[s], eff)
                fire = run if final else (fire & run)
                toks_out = jnp.where(fire, pred.astype(jnp.int32),
                                     toks_out)
                stg_out = jnp.where(fire, jnp.int32(s), stg_out)
                if not final:
                    # CALM propagation for the fired rows, scattered at
                    # their (page, offset) for layers [bnd, n_layers)
                    rows = TLM.lm_kv_project(params, x[:, 0], cfg, None,
                                             None, bnd, positions=pos,
                                             max_len=view_len)
                    pidx_f = jnp.where(fire, page_w, n_pages)
                    for i, rr in zip(range(bnd, n_layers), rows):
                        pg = dict(pages[i])
                        for name, val in rr.items():
                            pg[name] = pg[name].at[pidx_f, off].set(
                                val[:, 0].astype(pg[name].dtype),
                                mode="drop")
                        pages[i] = pg
                state = fold(state, s, fire)
                run = run & ~fire
            state = self._fold_slots(state, active)
            return state, (pages, toks_out, stg_out)

        kw = {} if eng.mesh is None \
            else {"out_shardings": (eng._state_sh, eng._row)}
        eng._steps[key] = jax.jit(step, donate_argnums=(1, 2, 3), **kw)
        return eng._steps[key]

    def _fold_slots(self, state: EngineState, active) -> EngineState:
        """Continuous-batching occupancy telemetry, folded on device
        inside the step (per replica when sharded; decode_steps counts
        launches once, on replica 0)."""
        eng = self.eng
        occ_all = (active > 0).astype(jnp.int32)
        if eng.mesh is None:
            return dataclasses.replace(
                state,
                slot_steps=state.slot_steps + occ_all.sum(),
                decode_steps=state.decode_steps + 1)
        r = eng.n_replicas
        occ = occ_all.reshape(r, occ_all.shape[0] // r).sum(1)
        one = jnp.zeros((r,), jnp.int32).at[0].add(1)
        return dataclasses.replace(
            state,
            slot_steps=state.slot_steps + occ,
            decode_steps=state.decode_steps + one)

    # -- the step -------------------------------------------------------
    def step(self):
        """Advance every active slot one token.  Returns completion
        events ``[(tag, tokens (B, n_new), stages (B, n_new)), ...]``;
        finished requests free their slots and KV pages before this
        returns, so the capacity is admittable immediately."""
        eng = self.eng
        if not self.active.any():
            return []
        # pass a copy: on CPU the device buffer may alias the host array,
        # and the reset below would then land before the async step reads
        x, self.alpha = self._embed_step()(
            eng.params, jnp.asarray(self.tokens), self.alpha,
            jnp.asarray(self.fresh.copy()))
        self.fresh[:] = 0
        eng.state, (self.pages, toks_out, stg_out) = self._decode_step()(
            eng.params, eng.state, self.pages, x, self.alpha,
            jnp.asarray(self.pos), jnp.asarray(self.active),
            jnp.asarray(self.page_table))
        tok_np = np.asarray(toks_out)   # the ONE host sync per step
        stg_np = np.asarray(stg_out)
        events = []
        finished = []
        stepped: set = set()
        for slot in np.nonzero(self.active)[0]:
            slot = int(slot)
            rid, row = self._slot_req[slot]
            rec = self._requests[rid]
            rec["toks"][row].append(int(tok_np[slot]))
            rec["stgs"][row].append(int(stg_np[slot]))
            self.pos[slot] += 1
            self.tokens[slot] = int(tok_np[slot])
            # host diagnostics use the same semantic accounting as the
            # eager engine: layers a token needed vs skipped
            st = int(stg_np[slot])
            bnd = eng.stages[st][1]
            eng.stats_exit[st] += 1
            eng.layers_run += bnd
            eng.layers_skipped += eng.cfg.n_layers - bnd
            if rid not in stepped:
                stepped.add(rid)
                rec["remaining"] -= 1
                if rec["remaining"] == 0:
                    finished.append(rid)
        for rid in finished:
            rec = self._requests.pop(rid)
            self._release_slots(rec["slots"])
            events.append((rec["tag"],
                           np.asarray(rec["toks"], np.int64),
                           np.asarray(rec["stgs"], np.int64)))
        return events

    # -- introspection --------------------------------------------------
    def slots_of(self, tag) -> list:
        """Slot ids currently held by the request admitted under ``tag``
        (empty once the request has retired)."""
        for rec in self._requests.values():
            if rec["tag"] == tag:
                return [int(s) for s in rec["slots"]]
        return []

    def occupancy(self) -> dict:
        """Slot-pool / page-allocator occupancy gauges for the obs
        registry (host ints only — never touches device state)."""
        return {"slots_total": self.n_slots,
                "slots_in_use": self.active_rows,
                "pages_total": self.n_pages,
                "pages_in_use": self.allocator.in_use,
                "pages_peak": self._pages_hwm}

    def stats(self) -> dict:
        return {"n_slots": self.n_slots,
                "active": self.active_rows,
                "page_size": self.page_size,
                "pages_total": self.n_pages,
                "pages_in_use": self.allocator.in_use,
                "pages_peak": self._pages_hwm}

    def check_invariants(self) -> None:
        """Assert the slot-pool/page-table/free-list consistency the
        property harness leans on: active-mask ↔ ownership agreement,
        no page shared between slots, every non-held page on a free
        list, per-replica placement."""
        active_slots = {int(s) for s in np.nonzero(self.active)[0]}
        assert active_slots == set(self._slot_req), \
            (active_slots, set(self._slot_req))
        assert active_slots == self.pool._held
        used = []
        for slot in active_slots:
            pg = self._slot_pages[slot]
            used.extend(pg)
            assert list(self.page_table[slot, :len(pg)]) == list(pg)
            rng = self.pool.range_of(slot)
            assert all(p // self.allocator.per_range == rng for p in pg)
        assert len(used) == len(set(used)), "page double-booked"
        assert set(used) == self.allocator._held
        n_free = sum(self.allocator.available(i)
                     for i in range(self.allocator.n_ranges))
        assert n_free + len(used) == self.n_pages
        s_free = sum(self.pool.available(i)
                     for i in range(self.pool.n_ranges))
        assert s_free + len(active_slots) == self.n_slots
