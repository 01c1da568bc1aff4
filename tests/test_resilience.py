"""Fault-tolerant serving (ISSUE 10): the chaos-injected engine pool.

Covers the tentpole surface end to end:

* deterministic fault plans — same seed => same plan, same plan over
  the same call sequence => identical injection traces (the CI
  determinism contract);
* structured dispatch/complete/step failure across all three
  schedulers (classifier, cascade, LM-continuous): the bucket's
  futures fail with :class:`DispatchError`, the daemon survives, and
  the NEXT bucket succeeds;
* EnginePool mechanics: retry-on-death, output-validation quarantine,
  straggler hedging, bounded requeue when nothing is live, the
  degradation ladder engaging AND reversing (drain/join), and the
  atomic serving-state snapshot round-trip;
* the chaos property: random request streams x random fault schedules
  => every future resolves exactly once (a result or a structured
  error, never a hang), and every request no fault touched is
  bit-identical to the eager single-engine oracle.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, strategies as st

from _prop import examples

from repro.cascade import CascadeEngine
from repro.core.routing import DartParams
from repro.engine import DartEngine, LMDecodeEngine
from repro.models.transformer_lm import LMConfig, lm_init
from repro.models.vit import ViTConfig, vit_init
from repro.parallel.sharding import unzip
from repro.runtime.chaos import (FaultInjector, FaultPlan, FaultSpec,
                                 InjectedEngineDeath, NullInjector)
from repro.serving import (AsyncDartServer, DispatchError, EnginePool,
                           InvalidEngineOutput, NoHealthyEngines,
                           PooledDartServer, RequestShed, ResilienceConfig,
                           SchedulerConfig, pooled_cascade_server,
                           pooled_lm_session)
from repro.serving.resilience import (_TAU_ALWAYS_FIRE, validate_output)

CFG = ViTConfig(name="res-vt", img_res=32, patch=8, n_layers=3, d_model=32,
                n_heads=2, d_ff=64, n_classes=10, exit_layers=(0, 1))
COSTS = [0.4, 0.7, 1.0]
ORIG_TAU = 0.2

LMCFG = LMConfig(name="res-lm", n_layers=2, d_model=16, n_heads=2,
                 n_kv_heads=1, d_ff=32, vocab=16, exit_layers=(0,),
                 max_seq=32, remat=False)

_CACHE: dict = {}


def _vit_params():
    if "vit" not in _CACHE:
        _CACHE["vit"] = unzip(vit_init(jax.random.key(0), CFG))[0]
    return _CACHE["vit"]


def _mk_engine():
    return DartEngine.from_config(
        CFG, _vit_params(), cum_costs=COSTS, adapt=False,
        dart=DartParams(tau=jnp.full((2,), ORIG_TAU), coef=jnp.ones(2),
                        beta_diff=0.3))


def _pool_engines():
    """Three cached same-params engines (two poolable + one oracle),
    usable from hypothesis tests (which cannot take fixtures).  Pool
    engines get their policy reset so ladder residue from a previous
    example cannot leak across examples."""
    if "engines" not in _CACHE:
        _CACHE["engines"] = (_mk_engine(), _mk_engine(), _mk_engine())
    e0, e1, oracle = _CACHE["engines"]
    for eng in (e0, e1):
        eng.state = eng.state.with_policy(tau=jnp.full((2,), ORIG_TAU))
    return e0, e1, oracle


def _images(seed, n):
    return np.random.RandomState(seed).rand(
        n, 32, 32, 3).astype(np.float32)


def _rcfg(**kw):
    kw.setdefault("backoff_s", 0.001)
    kw.setdefault("requeue_backoff_s", 0.001)
    kw.setdefault("call_timeout_s", 30.0)
    return ResilienceConfig(**kw)


def _drive(srv, futs, rounds=400):
    for _ in range(rounds):
        if all(f.done() for f in futs):
            return
        srv.flush()
        time.sleep(0.002)
    raise AssertionError("futures did not resolve while driving")


# ---------------------------------------------------------------------------
# fault plans: determinism + replay
# ---------------------------------------------------------------------------
def test_fault_plan_generate_deterministic_and_json_roundtrip():
    a = FaultPlan.generate(seed=11, n_faults=6)
    b = FaultPlan.generate(seed=11, n_faults=6)
    assert a.to_json() == b.to_json()
    assert FaultPlan.generate(seed=12, n_faults=6).to_json() != a.to_json()
    back = FaultPlan.from_json(a.to_json())
    assert back.specs == a.specs
    with pytest.raises(ValueError, match="unknown kind"):
        FaultSpec("melted", "step", 0)
    with pytest.raises(ValueError, match="unknown cut point"):
        FaultSpec("straggler", "nowhere", 0)


def _scripted_fire(inj):
    """A fixed fire() sequence (what a scheduler run would produce);
    returns the injection trace."""
    for i in range(12):
        for eng in ("e0", "e1"):
            for point in ("dispatch", "step", "complete"):
                try:
                    inj.fire(point, engine=eng)
                except InjectedEngineDeath:
                    pass
    return inj.trace


def test_same_plan_replayed_twice_yields_identical_traces():
    plan = FaultPlan.generate(seed=23, n_faults=5, horizon=12,
                              max_delay_s=0.0)
    t1 = _scripted_fire(FaultInjector(plan))
    t2 = _scripted_fire(FaultInjector(plan))
    assert t1 == t2 and len(t1) > 0


def test_targeted_spec_counts_per_engine_and_fires_once():
    inj = FaultInjector(FaultPlan([
        FaultSpec("nan_output", "step", 1, engine="e1")]))
    assert inj.fire("step", engine="e0") is None    # e1 count untouched
    assert inj.fire("step", engine="e1") is None    # e1 call #0
    assert inj.fire("step", engine="e1") == "nan_output"  # e1 call #1
    assert inj.fire("step", engine="e1") is None    # fires at most once
    assert inj.counts()[("step", "e1")] == 3


def test_null_injector_still_validates_cut_points():
    inj = NullInjector()
    assert inj.fire("dispatch") is None
    with pytest.raises(ValueError, match="unknown cut point"):
        inj.fire("dispach")


def test_validate_output_quarantines_poisoned_results():
    ok = {"conf": np.array([0.5, 0.9]), "exit_idx": np.array([0, 1])}
    validate_output(ok, n_exits=3)
    with pytest.raises(InvalidEngineOutput, match="non-finite"):
        validate_output({"conf": np.array([0.5, np.nan])}, n_exits=3)
    with pytest.raises(InvalidEngineOutput, match="out of range"):
        validate_output({"conf": np.array([0.5]),
                         "exit_idx": np.array([7])}, n_exits=3)
    with pytest.raises(InvalidEngineOutput, match="decode exit stage"):
        validate_output((np.zeros((1, 2), np.int32),
                         np.array([[9]], np.int32)), n_exits=3)


# ---------------------------------------------------------------------------
# structured failure paths: the three schedulers survive a bad bucket
# ---------------------------------------------------------------------------
class _Boom(RuntimeError):
    pass


def _boom_once(srv):
    """Replace the dispatch seam so the FIRST bucket raises."""
    state = {"n": 0}
    orig = srv._engine_call

    def call(fn):
        state["n"] += 1
        if state["n"] == 1:
            raise _Boom("injected dispatch failure")
        return orig(fn)
    srv._engine_call = call
    return state


def test_classifier_dispatch_failure_daemon_survives():
    eng = _mk_engine()
    x = _images(0, 4)
    with AsyncDartServer(eng, SchedulerConfig(max_batch=4,
                                              flush_ms=1.0)) as srv:
        _boom_once(srv)
        with pytest.raises(DispatchError) as ei:
            srv.submit(x[:2]).result(timeout=60)
        assert ei.value.stage == "dispatch"
        assert isinstance(ei.value.cause, _Boom)
        assert srv._thread.is_alive()
        out = srv.submit(x[2:]).result(timeout=60)
        assert out["pred"].shape == (2,)
    assert srv.counters["dispatch_errors"] == 1


def test_classifier_complete_failure_is_structured():
    eng = _mk_engine()
    srv = AsyncDartServer(eng, SchedulerConfig(max_batch=4), start=False)
    orig = srv._complete
    state = {"n": 0}

    def complete(reqs, out, t0):
        state["n"] += 1
        if state["n"] == 1:
            raise _Boom("injected materialization failure")
        return orig(reqs, out, t0)
    srv._complete = complete
    f1 = srv.submit(_images(1, 2))
    _drive(srv, [f1])
    with pytest.raises(DispatchError) as ei:
        f1.result(timeout=5)
    assert ei.value.stage == "complete"
    f2 = srv.submit(_images(2, 2))
    _drive(srv, [f2])
    assert f2.result(timeout=5)["pred"].shape == (2,)
    assert srv.counters["complete_errors"] == 1
    srv.close()


@pytest.fixture(scope="module")
def cascade_members():
    vc = ViTConfig(name="res-casc", img_res=32, patch=8, n_layers=3,
                   d_model=16, n_heads=2, d_ff=32, n_classes=10,
                   exit_layers=(0, 1))
    params, _ = unzip(vit_init(jax.random.key(1), vc))
    small = DartEngine.from_config(
        vc, params, cum_costs=COSTS, adapt=False,
        dart=DartParams(tau=jnp.full((2,), ORIG_TAU), coef=jnp.ones(2),
                        beta_diff=0.3))
    return (small, _mk_engine())


def _mk_cascade(members):
    # theta=-1.0 never escalates: the failure/chaos behaviour under
    # test is scheduler-level, independent of escalation volume
    return CascadeEngine(list(members), member_costs=[0.25, 1.0],
                         theta=np.array([-1.0]), beta_esc=0.1)


def test_cascade_dispatch_failure_daemon_survives(cascade_members):
    cas = _mk_cascade(cascade_members)
    x = _images(3, 4)
    with AsyncDartServer(cas, SchedulerConfig(max_batch=4,
                                              flush_ms=1.0)) as srv:
        _boom_once(srv)
        with pytest.raises(DispatchError) as ei:
            srv.submit(x[:2]).result(timeout=60)
        assert ei.value.stage == "dispatch"
        assert isinstance(ei.value.cause, _Boom)
        assert srv._thread.is_alive()
        out = srv.submit(x[2:]).result(timeout=60)
        assert out["pred"].shape == (2,)


def test_lm_continuous_step_failure_fails_pool_not_daemon():
    if "lm" not in _CACHE:
        _CACHE["lm"] = unzip(lm_init(jax.random.key(0), LMCFG))[0]
    eng = LMDecodeEngine(LMCFG, _CACHE["lm"],
                         DartParams(tau=jnp.full((1,), 1.0),
                                    coef=jnp.ones(1), beta_diff=0.1))
    sess = eng.session(continuous=True,
                       cfg=SchedulerConfig(policy="reject", flush_ms=0.0),
                       start=False, n_slots=4, page_size=4, max_len=16)
    rs = np.random.RandomState(5)
    f1 = sess.submit(rs.randint(0, LMCFG.vocab, (1, 4)), n_new=2)
    sess.pump()                       # admit into the slot pool
    orig = sess.decoder.step
    state = {"n": 0}

    def step():
        state["n"] += 1
        if state["n"] == 1:
            raise _Boom("injected decode-step failure")
        return orig()
    sess.decoder.step = step
    sess.pump()                       # the poisoned step
    with pytest.raises(DispatchError) as ei:
        f1.result(timeout=5)
    assert ei.value.stage == "step"
    assert isinstance(ei.value.cause, _Boom)
    assert sess.counters["step_errors"] == 1
    # the session keeps serving
    f2 = sess.submit(rs.randint(0, LMCFG.vocab, (1, 4)), n_new=2)
    for _ in range(200):
        if f2.done():
            break
        sess.pump()
    out2 = f2.result(timeout=5)
    assert out2["tokens"].shape == (1, 2)
    sess.close()


# ---------------------------------------------------------------------------
# EnginePool mechanics
# ---------------------------------------------------------------------------
def test_pool_retries_past_injected_death_and_ladder_engages():
    e0, e1, _ = _pool_engines()
    inj = FaultInjector(FaultPlan([
        FaultSpec("engine_death", "step", 0, engine="e0")]))
    pool = EnginePool({"e0": e0, "e1": e1}, _rcfg(), injector=inj,
                      heartbeat=False)
    srv = PooledDartServer(pool, SchedulerConfig(edges=(), max_batch=4),
                           start=False)
    futs = [srv.submit(_images(7, 2)) for _ in range(4)]
    _drive(srv, futs)
    for f in futs:                    # one engine dies, the other serves
        assert f.result(timeout=5)["pred"].shape == (2,)
    p = srv.stats()["pool"]
    assert p["deaths"] >= 1 and p["retries"] >= 1
    assert p["faults_injected"] >= 1
    assert p["rung"] >= 2             # <=1 of 2 live
    # at least the faulted bucket is marked (round-robin may serve the
    # first bucket cleanly from e1 before e0's death spec fires)
    assert p["touched_rids"] >= 2
    assert DEAD_STATES & set(p["engines"].values())
    srv.close()
    pool.close()


DEAD_STATES = {"dead"}


def test_pool_quarantines_nan_output_and_serves_from_peer():
    e0, e1, _ = _pool_engines()
    inj = FaultInjector(FaultPlan([
        FaultSpec("nan_output", "step", 0)]))   # whichever engine is first
    pool = EnginePool({"e0": e0, "e1": e1}, _rcfg(), injector=inj,
                      heartbeat=False)
    srv = PooledDartServer(pool, SchedulerConfig(edges=(), max_batch=4),
                           start=False)
    f = srv.submit(_images(8, 2))
    _drive(srv, [f])
    out = f.result(timeout=5)
    assert np.all(np.isfinite(out["conf"]))     # the NaN never leaked
    p = srv.stats()["pool"]
    assert p["quarantined"] == 1 and p["retries"] >= 1
    assert p["touched_rids"] == 1
    srv.close()
    pool.close()


def test_pool_hedges_straggler_first_result_wins():
    e0, e1, _ = _pool_engines()
    x = _images(9, 2)
    for eng in (e0, e1):              # warm so call times are stable
        eng.infer(x, mode="masked", record=False)
    inj = FaultInjector(FaultPlan([
        FaultSpec("straggler", "step", 0, delay_s=1.0)]))
    pool = EnginePool({"e0": e0, "e1": e1},
                      _rcfg(hedge_factor=3.0, straggler_window=10),
                      injector=inj, heartbeat=False)
    for _ in range(6):                # seed the rolling median: ~60ms cap
        pool.straggler.record(0.02)
    t0 = time.monotonic()
    out = pool.call(lambda eng: eng.infer(x, mode="masked", record=False))
    assert np.asarray(out["pred"]).shape == (2,)
    assert time.monotonic() - t0 < 1.0          # did not wait out the hold
    st_ = pool.stats()
    assert st_["hedges"] == 1 and st_["stragglers"] == 1
    assert st_["straggler_deadline_ms"] is not None
    pool.close()


def test_requeue_is_bounded_when_nothing_is_live():
    e0, e1, _ = _pool_engines()
    pool = EnginePool({"e0": e0, "e1": e1}, _rcfg(requeue_limit=3),
                      heartbeat=False)
    pool._mark_dead("e0", reason="test")
    pool._mark_dead("e1", reason="test")
    srv = PooledDartServer(pool, SchedulerConfig(edges=(), max_batch=4),
                           start=False)
    # priority above the rung-4 shed floor: reaches the requeue path
    f = srv.submit(_images(10, 2), priority=5)
    srv.flush()                       # requeues resolve within one flush
    with pytest.raises(DispatchError) as ei:
        f.result(timeout=5)
    assert isinstance(ei.value.cause, NoHealthyEngines)
    assert srv.counters["requeued"] == 3        # bounded, then failed
    assert srv.stats()["pool"]["requeues"] == 3
    srv.close()
    pool.close()


def test_ladder_rungs_engage_and_reverse():
    """4 pool slots over one shared engine: kill 3 -> rung 3 installs
    the scaled-tau + max-depth-cap policy; kill the 4th -> rung 4
    sheds below the priority floor; joins reverse everything."""
    e0, _, _ = _pool_engines()
    pool = EnginePool({n: e0 for n in ("a", "b", "c", "d")}, _rcfg(),
                      heartbeat=False)
    srv = PooledDartServer(pool, SchedulerConfig(edges=(), max_batch=4),
                           start=False)
    for name in ("a", "b", "c"):
        pool._mark_dead(name, reason="test")
    assert pool.rung == 3
    tau = np.asarray(e0.state.tau)
    assert tau[0] == pytest.approx(ORIG_TAU * pool.cfg.degraded_tau_scale)
    assert tau[1] == _TAU_ALWAYS_FIRE           # capped stage always fires
    assert pool.alpha_scale == pool.cfg.degraded_alpha_scale
    pool._mark_dead("d", reason="test")
    assert pool.rung == 4 and pool.shed_floor is not None
    with pytest.raises(RequestShed):
        srv.submit(_images(11, 2), priority=0).result(timeout=5)
    assert srv.counters["shed_degraded"] == 1
    for name in ("a", "b", "c", "d"):
        pool.join(name, warm=False)
    assert pool.rung == 0 and pool.shed_floor is None
    assert pool.alpha_scale == 1.0
    np.testing.assert_allclose(np.asarray(e0.state.tau),
                               np.full((2,), ORIG_TAU))
    hist = [h["to"] for h in pool.rung_history]
    assert hist[-1] == 0 and max(hist) == 4     # engaged AND reversed
    srv.close()
    pool.close()


def test_drain_is_not_a_failure_and_join_restores_capacity():
    e0, e1, _ = _pool_engines()
    pool = EnginePool({"e0": e0, "e1": e1}, _rcfg(), heartbeat=False)
    pool.drain("e1")
    st_ = pool.stats()
    assert st_["engines"]["e1"] == "drained"
    assert st_["deaths"] == 0 and st_["drains"] == 1
    assert pool.rung == 2
    pool.join("e1", warm=False)
    assert pool.stats()["engines"]["e1"] == "healthy"
    assert pool.rung == 0 and pool.stats()["joins"] == 1
    pool.close()


def test_snapshot_roundtrip_restores_learned_priors(tmp_path):
    e0, e1, _ = _pool_engines()
    pool = EnginePool({"e0": e0, "e1": e1}, _rcfg(), heartbeat=False)
    srv = PooledDartServer(pool, SchedulerConfig(edges=(), max_batch=4),
                           start=False)
    futs = [srv.submit(_images(12, 2)) for _ in range(4)]
    _drive(srv, futs)
    [f.result(timeout=5) for f in futs]
    snap = str(tmp_path / "snap")
    srv.snapshot(snap, step=7)
    learned = srv.planner.state_dict()
    srv.close()
    pool.close()

    e0b, e1b, _ = _pool_engines()
    pool2 = EnginePool({"e0": e0b, "e1": e1b}, _rcfg(), heartbeat=False)
    srv2 = PooledDartServer(pool2, SchedulerConfig(edges=(), max_batch=4),
                            start=False)
    assert srv2.planner.state_dict() != learned  # cold start differs
    assert srv2.restore_snapshot(snap) == 7
    assert srv2.planner.state_dict() == learned
    srv2.close()
    pool2.close()


def test_snapshot_roundtrip_keeps_request_window(tmp_path):
    """``snapshot`` writes the primary engine's request window into the
    checkpoint; ``restore_snapshot`` into fresh engines gives the same
    ``stats()["requests"]``."""
    e0, e1, _ = _pool_engines()
    pool = EnginePool({"e0": e0, "e1": e1}, _rcfg(), heartbeat=False)
    srv = PooledDartServer(pool, SchedulerConfig(edges=(), max_batch=4),
                           start=False)
    futs = [srv.submit(_images(13, 2), deadline_ms=d)
            for d in (0.0, 1e4, 0.0, 1e4)]
    _drive(srv, futs)
    [f.result(timeout=5) for f in futs]
    before = srv.stats()["requests"]
    assert before["deadline_miss"] >= 2
    snap = str(tmp_path / "snap")
    srv.snapshot(snap, step=3)
    srv.close()
    pool.close()

    pool2 = EnginePool({"e0": _mk_engine(), "e1": _mk_engine()}, _rcfg(),
                       heartbeat=False)
    srv2 = PooledDartServer(pool2, SchedulerConfig(edges=(), max_batch=4),
                            start=False)
    assert "requests" not in srv2.stats()
    assert srv2.restore_snapshot(snap) == 3
    assert srv2.stats()["requests"] == before
    srv2.close()
    pool2.close()


def test_pooled_lm_session_survives_engine_death():
    if "lm" not in _CACHE:
        _CACHE["lm"] = unzip(lm_init(jax.random.key(0), LMCFG))[0]

    def mk_lm():
        return LMDecodeEngine(LMCFG, _CACHE["lm"],
                              DartParams(tau=jnp.full((1,), 1.0),
                                         coef=jnp.ones(1), beta_diff=0.1))
    l0, l1, oracle = mk_lm(), mk_lm(), mk_lm()
    inj = FaultInjector(FaultPlan([
        FaultSpec("engine_death", "step", 0)]))
    pool = EnginePool({"l0": l0, "l1": l1}, _rcfg(), injector=inj,
                      heartbeat=False)
    sess = pooled_lm_session(pool, SchedulerConfig(max_batch=2),
                             start=False)
    prompts = np.random.RandomState(6).randint(0, LMCFG.vocab, (2, 4))
    f = sess.submit(prompts, n_new=3)
    _drive(sess, [f])
    out = f.result(timeout=5)
    ref_toks, ref_stages = oracle.generate(prompts, 3)
    np.testing.assert_array_equal(out["tokens"], ref_toks)
    np.testing.assert_array_equal(out["stages"], ref_stages)
    assert sess.stats()["pool"]["deaths"] == 1
    sess.close()
    pool.close()


def test_pooled_cascade_server_survives_engine_death(cascade_members):
    cas0 = _mk_cascade(cascade_members)
    cas1 = _mk_cascade(cascade_members)   # same members: same pure fn
    inj = FaultInjector(FaultPlan([
        FaultSpec("engine_death", "step", 0)]))
    pool = EnginePool({"c0": cas0, "c1": cas1}, _rcfg(), injector=inj,
                      heartbeat=False)
    srv = pooled_cascade_server(pool, SchedulerConfig(edges=(),
                                                      max_batch=4),
                                start=False)
    x = _images(13, 2)
    f = srv.submit(x)
    _drive(srv, [f])
    out = f.result(timeout=5)
    assert out["pred"].shape == (2,)
    assert (out["member"] == 0).all()     # theta sentinel: no escalation
    assert srv.stats()["pool"]["deaths"] == 1
    srv.close()
    pool.close()


def test_wedged_engine_is_declared_dead_and_call_rerouted():
    e0, e1, _ = _pool_engines()
    pool = EnginePool({"e0": e0, "e1": e1},
                      _rcfg(call_timeout_s=0.2, hedge=False, retries=2),
                      heartbeat=False)
    release = threading.Event()
    x = _images(14, 2)

    def wedge_or_serve(eng):
        if eng is e0:
            release.wait(5.0)          # a stuck compiled step
            raise RuntimeError("was wedged")
        return eng.infer(x, mode="masked", record=False)
    # pin round-robin so the first pick is e0
    pool._rr = len(pool.engines) - 1
    out = pool.call(wedge_or_serve)
    release.set()
    assert np.asarray(out["pred"]).shape == (2,)
    assert pool.stats()["engines"]["e0"] == "dead"
    pool.close()


# ---------------------------------------------------------------------------
# the chaos property (ISSUE 10 acceptance)
# ---------------------------------------------------------------------------
@settings(max_examples=examples(4), deadline=None)
@given(seed=st.integers(0, 10_000))
def test_chaos_streams_resolve_exactly_once_and_match_oracle(seed):
    """Random request streams x random fault schedules: every future
    resolves exactly once — a result or a structured error, never a
    hang or a double resolution; telemetry invariants hold; untouched
    requests are bit-identical to the eager oracle."""
    rs = np.random.RandomState(seed)
    plan = FaultPlan.generate(seed, n_faults=int(rs.randint(1, 6)),
                              engines=("e0", "e1"), horizon=16,
                              max_delay_s=0.02)
    e0, e1, oracle = _pool_engines()
    pool = EnginePool({"e0": e0, "e1": e1}, _rcfg(call_timeout_s=10.0),
                      injector=FaultInjector(plan), heartbeat=False)
    srv = PooledDartServer(
        pool, SchedulerConfig(edges=(),
                              max_batch=int(rs.choice([4, 8]))),
        start=False)
    n_req = int(rs.randint(4, 10))
    xs, futs, resolutions = [], [], []
    for _ in range(n_req):
        x = rs.rand(int(rs.randint(1, 4)), 32, 32, 3).astype(np.float32)
        xs.append(x)
        f = srv.submit(x)
        f.add_done_callback(lambda _f: resolutions.append(1))
        futs.append(f)
    _drive(srv, futs, rounds=600)
    assert len(resolutions) == n_req             # exactly once each
    n_ok = n_err = 0
    for f in futs:
        exc = f.exception(timeout=1)
        if exc is None:
            out = f.result()
            assert np.all(np.isfinite(np.asarray(out["conf"])))
            n_ok += 1
        else:
            assert isinstance(exc, (DispatchError, RequestShed))
            n_err += 1
    assert n_ok + n_err == n_req
    p = srv.stats()["pool"]
    assert p["faults_injected"] <= len(plan)
    assert p["deaths"] <= 2                      # an engine dies once
    assert p["quarantined"] <= p["retries"] + 1
    # rids any fault/rung touched are excluded; the rest must be
    # bit-identical to serving alone through the oracle engine
    for rid, (x, f) in enumerate(zip(xs, futs)):
        if rid in srv.touched_rids or f.exception() is not None:
            continue
        out = f.result()
        ref = oracle.infer(x, mode="masked", record=False)
        np.testing.assert_array_equal(out["pred"], np.asarray(ref["pred"]))
        np.testing.assert_array_equal(out["exit_idx"],
                                      np.asarray(ref["exit_idx"]))
        np.testing.assert_array_equal(out["conf"], np.asarray(ref["conf"]))
    srv.close()
    pool.close()
