"""repro.serving: the async difficulty-aware request scheduler.

Covers: deadline-flush vs size-flush ordering,
future results identical to the eager oracle, backpressure shedding
lowest-priority first, latency-percentile telemetry against a
recomputed reference, and a seeded burst test that is deterministic on
the CPU backend (run the suite with ``JAX_PLATFORMS=cpu``; the conftest
already pins tests to the host platform's single device).

Scheduler-logic tests drive the loop manually (``start=False`` + a fake
clock + ``pump()``) so nothing depends on wall-clock timing; one test
exercises the real background dispatcher thread end to end.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.routing import DartParams
from repro.data.datasets import DatasetConfig, make_batch
from repro.engine import DartEngine
from repro.engine import state as ST
from repro.models.vit import ViTConfig, vit_init
from repro.parallel.sharding import unzip
from repro.serving import (AsyncDartServer, RequestShed, RequestRejected,
                           SchedulerConfig)

DATA = DatasetConfig(name="synth-cifar", n_train=128, n_eval=128)
COSTS = [0.4, 0.7, 1.0]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture(scope="module")
def vit_engine_factory():
    vc = ViTConfig(name="vt", img_res=32, patch=8, n_layers=3, d_model=32,
                   n_heads=2, d_ff=64, n_classes=10, exit_layers=(0, 1))
    params, _ = unzip(vit_init(jax.random.key(0), vc))

    def make(**kw):
        kw.setdefault("cum_costs", COSTS)
        kw.setdefault("adapt", True)
        kw.setdefault("update_every", 10 ** 9)
        return DartEngine.from_config(
            vc, params,
            dart=DartParams(tau=jnp.full((2,), 0.2), coef=jnp.ones(2),
                            beta_diff=0.3), **kw)
    return make


@pytest.fixture(scope="module")
def eval_images():
    x, _ = make_batch(DATA, range(96), split="eval")
    return np.asarray(x)


# ---------------------------------------------------------------------------
# flush ordering
# ---------------------------------------------------------------------------
def test_deadline_flush_preempts_size_flush(vit_engine_factory, eval_images):
    """A deadline-pressed lane must dispatch before a size-ready lane,
    and a size-ready lane before a merely-held one."""
    eng = vit_engine_factory()
    alpha = np.asarray(eng._alpha(jnp.asarray(eval_images)))
    med = float(np.median(alpha))
    easy = eval_images[alpha <= med]
    hard = eval_images[alpha > med]
    clock = FakeClock()
    srv = AsyncDartServer(
        eng, SchedulerConfig(max_batch=8, flush_ms=50.0, margin_ms=5.0,
                             pipeline_depth=0, edges=(med,)),
        clock=clock, start=False)
    # lane 0 (easy): size-ready; lane 1 (hard): one small request whose
    # deadline falls inside the scheduling slack
    size_futs = [srv.submit(easy[i:i + 3]) for i in range(0, 9, 3)]
    ddl_fut = srv.submit(hard[:2], deadline_ms=4.0)
    assert srv.pump()                       # 1st decision: deadline lane
    assert ddl_fut.done() and not any(f.done() for f in size_futs)
    assert srv.counters["flush_deadline"] == 1
    assert srv.pump()                       # 2nd: the size-ready lane
    assert sum(f.done() for f in size_futs) >= 2
    assert srv.counters["flush_size"] == 1
    # a lone small request only flushes once its hold expires
    hold_fut = srv.submit(easy[10:12])
    assert not srv.pump()
    clock.advance(0.051)                    # > flush_ms
    assert srv.pump()
    assert hold_fut.done()
    assert srv.counters["flush_hold"] == 1
    srv.close()


def test_size_flush_stops_at_bucket_boundary(vit_engine_factory,
                                             eval_images):
    """The flush never grows into the next power-of-two bucket when the
    larger bucket would be mostly padding (min_fill)."""
    eng = vit_engine_factory()
    clock = FakeClock()
    # 8 + 3 queued samples: taking the 3-sample request would pad the
    # flushed bucket to 16 at 11/16 fill >= 0.5 -> taken; but at
    # min_fill=0.75 the flush must stop at the exactly-full 8-bucket.
    srv = AsyncDartServer(
        eng, SchedulerConfig(max_batch=16, flush_ms=10.0,
                             pipeline_depth=0, edges=()),
        clock=clock, start=False)
    srv_hi = AsyncDartServer(
        eng, SchedulerConfig(max_batch=16, flush_ms=10.0, min_fill=0.75,
                             pipeline_depth=0, edges=()),
        clock=clock, start=False)
    futs = {}
    for s in (srv, srv_hi):
        futs[s] = (s.submit(eval_images[:8]), s.submit(eval_images[8:11]))
    clock.advance(0.011)                    # hold expires for both
    for s in (srv, srv_hi):
        f8, f3 = futs[s]
        assert s.pump()                     # hold flush (non-forced take)
        assert f8.done()
        assert f3.done() is (s is srv)      # 0.5 takes it, 0.75 doesn't
        s.close()
        assert f3.done()
    # a size flush triggers WITHOUT any clock advance once the lane
    # exactly fills a bucket at >= half the target
    srv3 = AsyncDartServer(
        eng, SchedulerConfig(max_batch=16, flush_ms=10.0,
                             pipeline_depth=0, edges=()),
        clock=FakeClock(), start=False)
    f8 = srv3.submit(eval_images[:8])
    assert srv3.pump()
    assert f8.done() and srv3.counters["flush_size"] == 1
    srv3.close()


# ---------------------------------------------------------------------------
# oracle equivalence
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["masked", "compacted"])
def test_futures_match_eager_oracle(vit_engine_factory, eval_images, mode):
    """Every completed request's outputs must be identical to serving
    that request alone through the eager engine."""
    eng = vit_engine_factory()
    oracle = vit_engine_factory()
    with AsyncDartServer(eng, SchedulerConfig(max_batch=16, flush_ms=2.0,
                                              mode=mode)) as srv:
        sizes = [1, 3, 4, 2, 7, 1, 5, 4, 6, 3]
        reqs, start = [], 0
        for n in sizes:
            reqs.append((start, n, srv.submit(eval_images[start:start + n],
                                              deadline_ms=500.0)))
            start += n
        outs = [(a, n, f.result(timeout=120)) for a, n, f in reqs]
    for a, n, out in outs:
        ref = oracle.infer(eval_images[a:a + n], mode="masked",
                           record=False)
        np.testing.assert_array_equal(out["exit_idx"],
                                      np.asarray(ref["exit_idx"]))
        np.testing.assert_array_equal(out["pred"], np.asarray(ref["pred"]))
        np.testing.assert_allclose(out["conf"], np.asarray(ref["conf"]),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(out["alpha"],
                                      np.asarray(ref["alpha"]))
    # per-sample serving telemetry folded for every dispatched sample
    assert int(np.sum(np.asarray(eng.state.served))) == sum(
        n for _, n, _ in outs)


# ---------------------------------------------------------------------------
# backpressure
# ---------------------------------------------------------------------------
def test_backpressure_sheds_lowest_priority_first(vit_engine_factory,
                                                  eval_images):
    eng = vit_engine_factory()
    srv = AsyncDartServer(
        eng, SchedulerConfig(max_queue=2, policy="shed", edges=()),
        clock=FakeClock(), start=False)
    f_p1 = srv.submit(eval_images[:1], priority=1)
    f_p2 = srv.submit(eval_images[1:2], priority=2)
    # newcomer with the lowest priority is itself shed
    f_p0 = srv.submit(eval_images[2:3], priority=0)
    with pytest.raises(RequestShed):
        f_p0.result(timeout=5)
    # higher-priority newcomer evicts the lowest-priority queued request
    f_p9 = srv.submit(eval_images[3:4], priority=9)
    with pytest.raises(RequestShed):
        f_p1.result(timeout=5)
    assert srv.queue.shed == 2
    srv.close()          # drains the survivors
    assert f_p2.result(timeout=5)["pred"].shape == (1,)
    assert f_p9.result(timeout=5)["pred"].shape == (1,)


def test_backpressure_reject_and_degrade(vit_engine_factory, eval_images):
    eng = vit_engine_factory()
    srv = AsyncDartServer(
        eng, SchedulerConfig(max_queue=1, policy="reject", edges=()),
        clock=FakeClock(), start=False)
    ok = srv.submit(eval_images[:1])
    bad = srv.submit(eval_images[1:2])
    with pytest.raises(RequestRejected):
        bad.result(timeout=5)
    assert srv.queue.rejected == 1
    srv.close()
    assert ok.result(timeout=5)["deadline_missed"] is False

    # degrade-alpha: the over-limit request is admitted with scaled-down
    # difficulty (earlier exits = cheaper), re-laned as easy traffic
    eng2 = vit_engine_factory()
    alpha = np.asarray(eng2._alpha(jnp.asarray(eval_images[:2])))
    # put the class edge between the degraded and original difficulty
    edge = 0.5 * float(alpha.min())
    srv2 = AsyncDartServer(
        eng2, SchedulerConfig(max_queue=1, policy="degrade-alpha",
                              degrade_factor=0.25, edges=(edge,)),
        clock=FakeClock(), start=False)
    a = srv2.submit(eval_images[:1])        # lane 1 (hard), fills it
    b = srv2.submit(eval_images[1:2])       # lane 1 full -> degraded
    assert srv2.counters["degraded"] == 1
    srv2.close()
    a_out, b_out = a.result(timeout=5), b.result(timeout=5)
    assert a_out["lane"] == 1 and b_out["lane"] == 0
    np.testing.assert_allclose(b_out["alpha"], 0.25 * alpha[1:2],
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------
def test_latency_percentiles_match_recomputed_reference(vit_engine_factory,
                                                        eval_images):
    # Driven on a FakeClock with manual pumps: latencies are exact
    # scheduler-clock values, so the percentile comparison (and the
    # zero-miss assertion at a 10s SLO) cannot depend on host speed.
    eng = vit_engine_factory()
    clk = FakeClock()
    srv = AsyncDartServer(eng, SchedulerConfig(max_batch=8, flush_ms=1.0),
                          clock=clk, start=False)
    futs = []
    for i in range(0, 48, 2):
        futs.append(srv.submit(eval_images[i:i + 2], deadline_ms=1e4))
        clk.advance(0.003)         # staggered submits → distinct latencies
    for _ in range(1000):
        if all(f.done() for f in futs):
            break
        clk.advance(0.005)
        if not srv.pump():
            srv.flush()
    srv.close()
    lats = [f.result(timeout=5)["latency_ms"] for f in futs]
    st = srv.stats()
    assert st["requests"]["requests"] == len(lats)
    assert st["requests"]["deadline_miss"] == 0
    ref = np.percentile(np.asarray(lats, np.float32), [50.0, 95.0, 99.0])
    got = st["requests"]["latency_ms"]
    np.testing.assert_allclose([got["p50"], got["p95"], got["p99"]], ref,
                               rtol=1e-5)


def test_latency_ring_buffer_wraps(vit_engine_factory):
    """Host request window: ring overwrite keeps the LAST w records and
    the lifetime counters keep counting."""
    eng = vit_engine_factory()
    win, _ = ST.RequestWindow.take(
        ST.EngineState.create(3, eng.acfg, lat_window=4))
    lats = [10.0, 20.0, 30.0, 40.0, 50.0, 60.0]
    win.record_requests(lats[:3], missed=[True, False, False])
    win.record_requests(lats[3:], missed=[False, True, False])
    st = win.stats()
    assert st["requests"] == 6 and st["deadline_miss"] == 2
    assert st["miss_rate"] == pytest.approx(2 / 6)
    window = {50.0, 60.0, 30.0, 40.0}       # last 4, ring order
    assert set(win.lat_ms.tolist()) == window
    np.testing.assert_allclose(
        st["latency_ms"]["p95"],
        np.percentile(np.asarray(sorted(window), np.float32), 95.0))


def test_request_window_checkpoint_roundtrip(vit_engine_factory,
                                            eval_images, tmp_path):
    """A served sharded engine's request window (latencies, deadline
    misses, quote sums) survives save_state -> fresh engine ->
    restore_state; the live state never carries it."""
    from repro.launch.mesh import make_serving_mesh
    eng = vit_engine_factory(mesh=make_serving_mesh())
    clk = FakeClock()
    srv = AsyncDartServer(eng, SchedulerConfig(max_batch=8, flush_ms=1.0),
                          clock=clk, start=False)
    futs = []
    for i in range(0, 24, 2):
        futs.append(srv.submit(eval_images[i:i + 2],
                               deadline_ms=1.0 if i % 4 else 1e4))
        clk.advance(0.003)
    clk.advance(0.01)
    srv.close()
    assert all(f.done() for f in futs)
    eng.record_quotes([4.0, None, 9.5], [5.0, 6.0, 7.0])
    before = eng.stats()["requests"]
    assert before["requests"] == 12 and before["deadline_miss"] == 6
    assert before["quote"]["quoted"] == 2
    assert int(eng.state.lat_count) == 0
    eng.save_state(str(tmp_path), step=4)
    fresh = vit_engine_factory(mesh=make_serving_mesh())
    assert "requests" not in fresh.stats()
    assert fresh.restore_state(str(tmp_path)) == 4
    assert fresh.stats()["requests"] == before
    assert int(fresh.state.lat_count) == 0


def test_bad_request_fails_its_future_not_the_loop(vit_engine_factory,
                                                   eval_images):
    """An input the engine rejects (here: wrong channel count) must fail
    THAT bucket's futures and leave the scheduler serving."""
    eng = vit_engine_factory()
    clock = FakeClock()
    srv = AsyncDartServer(eng, SchedulerConfig(edges=()), clock=clock,
                          start=False)
    bad = srv.submit(np.zeros((2, 32, 32, 5), np.float32))
    clock.advance(1.0)                      # hold expires
    assert srv.pump()                       # dispatch fails, loop lives
    with pytest.raises(Exception):
        bad.result(timeout=5)
    assert srv.counters["dispatch_errors"] == 1
    ok = srv.submit(eval_images[:2])
    clock.advance(1.0)
    srv.close()
    assert ok.result(timeout=5)["pred"].shape == (2,)


def test_oversized_masked_request_dispatches_unpadded(vit_engine_factory,
                                                      eval_images):
    """A single request larger than the biggest bucket must not trip
    bucket_key overflow — it dispatches unpadded."""
    eng = vit_engine_factory(buckets=(1, 2, 4, 8))
    clock = FakeClock()
    srv = AsyncDartServer(eng, SchedulerConfig(max_batch=8, edges=()),
                          clock=clock, start=False)
    fut = srv.submit(eval_images[:12])      # 12 > max_bucket 8
    clock.advance(1.0)
    assert srv.pump()
    srv.close()
    out = fut.result(timeout=5)
    assert out["pred"].shape == (12,)
    oracle = vit_engine_factory(buckets=(1, 2, 4, 8))
    ref = oracle.infer(eval_images[:12], mode="masked", record=False)
    np.testing.assert_array_equal(out["exit_idx"],
                                  np.asarray(ref["exit_idx"]))


def test_max_batch_clamps_to_engine_buckets(vit_engine_factory,
                                            eval_images):
    """A consolidation target beyond the engine's largest bucket must
    clamp, not wedge the dispatcher with BatchTooLarge mid-flush."""
    eng = vit_engine_factory(buckets=(1, 2, 4, 8))
    clock = FakeClock()
    srv = AsyncDartServer(eng, SchedulerConfig(edges=()),  # max_batch=64
                          clock=clock, start=False)
    assert srv.max_batch == 8
    futs = [srv.submit(eval_images[i:i + 3]) for i in (0, 3, 6)]
    clock.advance(1.0)                      # hold expires: 9 queued
    while srv.pump():
        pass
    srv.close()
    for i, f in enumerate(futs):
        assert f.result(timeout=5)["pred"].shape == (3,)
    assert srv.last_error is None


def test_restore_pre_latency_checkpoint(vit_engine_factory, eval_images,
                                        tmp_path):
    """Checkpoints written before EngineState grew the latency leaves
    (a strict prefix of the new flatten order) must still restore."""
    from repro import checkpoint as CK
    eng = vit_engine_factory()
    eng.infer(eval_images[:16], mode="masked", record=True)
    legacy = [getattr(eng.state, f) for f in ST.LEGACY_FIELDS]
    CK.save(str(tmp_path), 3, legacy)       # legacy-shaped manifest
    eng2 = vit_engine_factory()
    assert eng2.restore_state(str(tmp_path)) == 3
    assert int(eng2.state.served) == 16     # legacy telemetry restored
    assert int(eng2.state.lat_count) == 0   # fresh latency counters
    assert "requests" not in eng2.stats()   # and an empty window
    np.testing.assert_array_equal(np.asarray(eng2.state.exit_counts),
                                  np.asarray(eng.state.exit_counts))


def test_planner_seeds_prior_from_engine_window(vit_engine_factory,
                                                eval_images):
    """An engine that already served (e.g. restored from a checkpoint)
    seeds the planner's cold-start depth prediction from its §II.C
    window — the exit-count prior from telemetry."""
    from repro.core import adaptive as AD
    from repro.serving import AdmissionPlanner
    eng = vit_engine_factory()
    fresh = AdmissionPlanner(eng)
    assert fresh._global_depth is None          # nothing served yet
    eng.infer(eval_images[:32], mode="masked", record=True)
    seeded = AdmissionPlanner(eng)
    np.testing.assert_allclose(
        seeded._global_depth,
        float(AD.window_exit_depth(eng.state.adaptive, eng.acfg)),
        rtol=1e-6)
    # and the prediction runs through the cumulative cost curve
    cost = seeded.predicted_cost(0.5, dclass=0)
    assert COSTS[0] <= cost <= COSTS[-1]


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------
def test_seeded_burst_is_deterministic(vit_engine_factory, eval_images):
    """A seeded bursty arrival pattern driven through a fake clock must
    reproduce decisions, flush reasons and telemetry exactly."""
    def run_once():
        eng = vit_engine_factory()
        clock = FakeClock()
        srv = AsyncDartServer(
            eng, SchedulerConfig(max_batch=8, flush_ms=10.0, margin_ms=2.0,
                                 pipeline_depth=1, edges=(0.35, 0.65)),
            clock=clock, start=False)
        rng = np.random.RandomState(7)
        futs = []
        for _ in range(6):                          # 6 bursts
            for _ in range(int(rng.randint(1, 5))):
                n = int(rng.randint(1, 5))
                a = int(rng.randint(0, len(eval_images) - n))
                futs.append(srv.submit(
                    eval_images[a:a + n],
                    deadline_ms=float(rng.randint(20, 80)),
                    priority=int(rng.randint(0, 3))))
            clock.advance(0.004)
            while srv.pump():
                pass
        clock.advance(1.0)
        srv.close()
        outs = [f.result(timeout=5) for f in futs]
        sig = {
            "exit_idx": np.concatenate([o["exit_idx"] for o in outs]),
            "pred": np.concatenate([o["pred"] for o in outs]),
            "lanes": [o["lane"] for o in outs],
            "flushes": {k: v for k, v in srv.counters.items()
                        if k.startswith("flush_")},
            "served": int(np.sum(np.asarray(srv.engine.state.served))),
        }
        return sig

    a, b = run_once(), run_once()
    np.testing.assert_array_equal(a["exit_idx"], b["exit_idx"])
    np.testing.assert_array_equal(a["pred"], b["pred"])
    assert a["lanes"] == b["lanes"]
    assert a["flushes"] == b["flushes"]
    assert a["served"] == b["served"]


# ---------------------------------------------------------------------------
# LM decode session
# ---------------------------------------------------------------------------
def test_lm_session_matches_direct_generate():
    from repro.engine import LMDecodeEngine
    from repro.models.transformer_lm import LMConfig
    from repro.runtime.trainer import Trainer, TrainConfig

    lc = LMConfig(name="lm-sess", n_layers=4, d_model=32, n_heads=2,
                  n_kv_heads=1, d_ff=64, vocab=32, exit_layers=(1,),
                  max_seq=32, remat=False)
    tr = Trainer(lc, TrainConfig(batch_size=8, steps=5, lr=3e-3),
                 DatasetConfig(name="tokens", n_train=128),
                 data_kind="tokens")
    tr.run()
    dart = DartParams(tau=jnp.asarray([0.3]), coef=jnp.ones(1),
                      beta_diff=0.15)
    prompts, _ = make_batch(DatasetConfig(name="tokens", n_train=128),
                            range(4), kind="tokens", seq_len=9,
                            vocab=lc.vocab)
    ref_eng = LMDecodeEngine(lc, tr.params, dart)
    ref_tok, ref_stg = ref_eng.generate(prompts, n_new=6)

    eng = LMDecodeEngine(lc, tr.params, dart)
    sess = eng.session(start=False, clock=FakeClock())
    futs = [sess.submit(prompts[i], n_new=6) for i in range(4)]
    sess.close()                            # flushes one consolidated call
    outs = [f.result(timeout=5) for f in futs]
    np.testing.assert_array_equal(
        np.concatenate([o["tokens"] for o in outs]), ref_tok)
    np.testing.assert_array_equal(
        np.concatenate([o["stages"] for o in outs]), ref_stg)
    # all four callers shared one bucketed decode loop
    assert sess.counters["flush_forced"] == 1
    assert sess.stats()["requests"]["requests"] == 4
