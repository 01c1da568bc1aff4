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

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    # deterministic fallback (raises under REPRO_REQUIRE_HYPOTHESIS=1)
    from _hypothesis_compat import given, settings, strategies as st

from _prop import examples

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
    # lane 0 (easy): size-ready; lane 1 (hard): one request whose
    # deadline falls inside the scheduling slack, filling its bucket so
    # no request of lane 0 tops it up
    size_futs = [srv.submit(easy[i:i + 3]) for i in range(0, 9, 3)]
    ddl_fut = srv.submit(hard[:8], deadline_ms=4.0)
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


# ---------------------------------------------------------------------------
# bucket packing: first-fit within the lane, top-up across lanes
# ---------------------------------------------------------------------------
def _req(rid, n, lane=0):
    from concurrent.futures import Future

    from repro.serving.request import Request
    return Request(rid=rid, x=np.zeros((n, 1), np.float32), n=n,
                   alpha=np.zeros(n, np.float32), lane=lane,
                   predicted_cost=1.0, priority=0, t_submit=float(rid),
                   deadline_s=None, future=Future())


def _pow2_key(cap):
    """Padded shape of n samples: the next power of two, up to ``cap``
    (n itself beyond it)."""
    return lambda n: n if n > cap else 1 << max(n - 1, 0).bit_length()


def _queue(sizes, lanes=None):
    from repro.serving.queue import RequestQueue
    q = RequestQueue()
    for rid, n in enumerate(sizes):
        q.push(_req(rid, n, 0 if lanes is None else lanes[rid]))
    return q


def _fifo_run(lane, max_samples, bucket_key, min_fill, force):
    """The run that stopping at the first request that does not fit
    would take (the rule before first-fit): the reference prefix."""
    out, total = [], 0
    for r in lane:
        new_total = total + r.n
        if new_total > max_samples:
            if not out:
                out.append(r)
            break
        if out and not force:
            b_old, b_new = bucket_key(total), bucket_key(new_total)
            if b_new > b_old and new_total / b_new < min_fill:
                break
        out.append(r)
        total = new_total
    return out


def test_take_fills_past_a_request_that_does_not_fit():
    """A 21 then a 13 no longer flushes a bucket of 21: the 13 is
    skipped and the 8 and 2 behind it fill the bucket to 31."""
    key = lambda n: 8 if n <= 8 else 32               # noqa: E731
    q = _queue([21, 13, 8, 2])
    reqs, rows = q.take(0, 32, key)
    assert [r.rid for r in reqs] == [0, 2, 3]         # head first
    assert rows == {"fifo": 21, "skip_ahead": 10, "other_lane": 0}
    assert [r.rid for r in q.take(0, 32, key)[0]] == [1]
    assert q.take(0, 32, key) == ([], {"fifo": 0, "skip_ahead": 0,
                                       "other_lane": 0})


def test_take_keeps_the_fifo_run_as_prefix():
    """Per take, the FIFO run is a prefix of the first-fit run."""
    key = _pow2_key(32)
    sizes = [5, 13, 21, 1, 8, 2, 32, 5, 1, 13, 2]
    q, ref = _queue(sizes), [_req(i, n) for i, n in enumerate(sizes)]
    while ref:
        fifo = [r.rid for r in _fifo_run(ref, 32, key, 0.5, False)]
        got = [r.rid for r in q.take(0, 32, key)[0]]
        assert got[:len(fifo)] == fifo and len(got) >= len(fifo)
        ref = [r for r in ref if r.rid not in got]


def test_take_honours_min_fill_without_force():
    """min_fill still refuses a request that would grow the bucket
    mostly empty, and the walk goes on past it; force drops min_fill
    but keeps the walk."""
    key = _pow2_key(16)
    # 8+3 = 11/16 < 0.75: skipped; 8+5 = 13/16: taken; 13+2 stays in
    # the 16-bucket: taken
    reqs, rows = _queue([8, 3, 5, 2]).take(0, 16, key, min_fill=0.75)
    assert [r.rid for r in reqs] == [0, 2, 3]
    assert rows == {"fifo": 8, "skip_ahead": 7, "other_lane": 0}
    reqs, _ = _queue([8, 3]).take(0, 16, key, min_fill=0.75)
    assert [r.rid for r in reqs] == [0]
    reqs, rows = _queue([8, 3, 5, 2]).take(0, 16, key, min_fill=0.75,
                                           force=True)
    assert [r.rid for r in reqs] == [0, 1, 2]       # 16: full
    assert rows["fifo"] == 16


def test_skipped_request_is_served_within_bounded_flushes():
    """A skipped request is overtaken only by requests using room it
    could not use; with p requests ahead of it in its lane it leaves
    within p + 1 takes of that lane, however many small requests keep
    arriving behind it."""
    key = lambda n: 8 if n <= 8 else 32               # noqa: E731
    q = _queue([21, 30, 13])                          # the 13: p = 2
    rid, served_at = 3, None
    for take in range(1, 4):
        for _ in range(4):                            # small arrivals
            q.push(_req(rid, 2))
            rid += 1
        if 2 in [r.rid for r in q.take(0, 32, key)[0]]:
            served_at = take
            break
    assert served_at == 3                             # the bound, reached


def _serve(srv, reqs):
    """Submit ``reqs`` at once, expire the hold, pump once, close.  Spies
    on ``take``: returns the lanes and rows of each flushed bucket, and
    each request's result."""
    taken = []
    real = srv.queue.take

    def spy(*a, **kw):
        got, rows = real(*a, **kw)
        if got:
            taken.append(({r.lane for r in got}, rows))
        return got, rows
    srv.queue.take = spy
    futs = [srv.submit(x, **kw) for x, kw in reqs]
    srv._clock.advance(1.0)
    srv.pump()
    srv.close()
    return taken, [f.result(timeout=60) for f in futs]


def _split_by_alpha(eng, images):
    alpha = np.asarray(eng._alpha(jnp.asarray(images)))
    med = float(np.median(alpha))
    return images[alpha <= med], images[alpha > med], med


def test_masked_server_tops_up_across_lanes(vit_engine_factory,
                                            eval_images):
    """Masked without a predictor, a bucket's cost does not depend on
    its rows' difficulty: one flush serves both lanes, and every
    request still gets the eager oracle's decisions."""
    eng, oracle = vit_engine_factory(), vit_engine_factory()
    easy, hard, med = _split_by_alpha(eng, eval_images)
    srv = AsyncDartServer(
        eng, SchedulerConfig(max_batch=16, pipeline_depth=0,
                             edges=(med,)), clock=FakeClock(), start=False)
    xs = [easy[:2], hard[:2], easy[2:4], hard[2:5]]
    taken, outs = _serve(srv, [(x, {}) for x in xs])
    # lane 0's 2+2 in the 4-bucket, topped up with lane 1's 2 and 3
    assert taken == [({0, 1}, {"fifo": 4, "skip_ahead": 0,
                               "other_lane": 5})]
    assert srv.counters["flush_hold"] == 1
    for x, out in zip(xs, outs):
        ref = oracle.infer(x, mode="masked", record=False)
        np.testing.assert_array_equal(out["exit_idx"],
                                      np.asarray(ref["exit_idx"]))
        np.testing.assert_array_equal(out["pred"], np.asarray(ref["pred"]))


@pytest.mark.parametrize("kind", ["compacted", "predict", "cascade", "lm"])
def test_topup_never_crosses_lanes_where_lanes_carry_cost(
        vit_engine_factory, eval_images, kind):
    """Compacted stages, predicted head-skip, cascade members and LM
    shapes make a bucket's cost depend on its lane: buckets stay
    lane-pure, each lane's head served first."""
    if kind == "lm":
        from repro.engine import LMDecodeEngine
        from repro.models.transformer_lm import LMConfig, lm_init
        lc = LMConfig(name="lm-lanes", n_layers=2, d_model=16, n_heads=2,
                      n_kv_heads=1, d_ff=32, vocab=16, exit_layers=(0,),
                      max_seq=16, remat=False)
        lm = LMDecodeEngine(lc, unzip(lm_init(jax.random.key(0), lc))[0],
                            DartParams(tau=jnp.asarray([0.3]),
                                       coef=jnp.ones(1), beta_diff=0.15))
        srv = lm.session(SchedulerConfig(max_batch=8, policy="reject"),
                         start=False, clock=FakeClock())
        prompts = np.random.RandomState(0).randint(0, 16, (4, 4))
        reqs = [(p, {"n_new": n}) for p, n in zip(prompts, (2, 3, 2, 3))]
    else:
        eng = vit_engine_factory()
        easy, hard, med = _split_by_alpha(eng, eval_images)
        if kind == "cascade":
            from repro.cascade import CascadeEngine
            eng = CascadeEngine([eng, vit_engine_factory()],
                                member_costs=[0.5, 1.0], beta_esc=0.1)
        srv = AsyncDartServer(eng, SchedulerConfig(
            max_batch=16, pipeline_depth=0, edges=(med,),
            mode="compacted" if kind == "compacted" else "masked",
            predict="conservative" if kind == "predict" else "off"),
            clock=FakeClock(), start=False)
        reqs = [(x, {}) for x in (easy[:2], hard[:2], easy[2:4],
                                  hard[2:5])]
    assert srv._mixable_lanes(0) == []
    taken, _ = _serve(srv, reqs)
    assert len({lane for lanes, _ in taken for lane in lanes}) >= 2
    for lanes, rows in taken:
        assert len(lanes) == 1 and rows["other_lane"] == 0


def test_bucket_rows_counter_and_span_add_up(vit_engine_factory,
                                             eval_images):
    """``dart_bucket_rows_total{order}`` and the ``bucket`` span's
    ``fifo`` / ``skip_ahead`` / ``other_lane`` add up to its
    ``n_samples``, and each order shows up on this stream."""
    from repro import obs
    eng = vit_engine_factory()
    easy, hard, med = _split_by_alpha(eng, eval_images)
    obs.reset()
    obs.configure(enabled=True)
    try:
        srv = AsyncDartServer(
            eng, SchedulerConfig(max_batch=8, pipeline_depth=0,
                                 edges=(med,)),
            clock=FakeClock(), start=False)
        # lane 0: 5, 4 (skipped), 2; lane 1: 1 -> one bucket of 8
        futs = [srv.submit(easy[:5]), srv.submit(easy[5:9]),
                srv.submit(easy[9:11]), srv.submit(hard[:1])]
        srv._clock.advance(1.0)
        srv.close()
        for f in futs:
            f.result(timeout=60)
        buckets = [s for s in obs.get_tracer().spans()
                   if s["name"] == "bucket"]
        fam = obs.get_registry().get("dart_bucket_rows_total")
        by_order = {o: fam.value(order=o)
                    for o in ("fifo", "skip_ahead", "other_lane")}
    finally:
        obs.reset()
    assert len(buckets) == 2
    for s in buckets:
        assert s["fifo"] + s["skip_ahead"] + s["other_lane"] \
            == s["n_samples"]
    assert (buckets[0]["fifo"], buckets[0]["skip_ahead"],
            buckets[0]["other_lane"]) == (5, 2, 1)
    assert sum(by_order.values()) == sum(s["n_samples"] for s in buckets)
    assert by_order == {"fifo": 9.0, "skip_ahead": 2.0, "other_lane": 1.0}


@settings(max_examples=examples(60), deadline=None)
@given(seed=st.integers(0, 2 ** 31 - 1), n_lanes=st.integers(1, 3),
       mix=st.sampled_from([False, True]))
def test_take_property_random_streams(seed, n_lanes, mix):
    """Random size streams over random lanes, interleaved with takes of
    random lanes: every request is taken exactly once, no bucket
    exceeds max_batch, every take holds at least the FIFO run's samples
    with that run as its prefix, and a request leaves within p + 1
    takes of its lane (p: requests ahead of it at admission)."""
    from repro.serving.queue import RequestQueue
    rs = np.random.RandomState(seed)
    max_batch = int(rs.choice([8, 16, 32]))
    key = _pow2_key(max_batch)
    min_fill = float(rs.choice([0.0, 0.5, 0.75]))
    q = RequestQueue()
    lanes = {k: [] for k in range(n_lanes)}      # mirror, lane order
    budget, taken, rid = {}, [], 0
    for _ in range(int(rs.randint(5, 40))):
        for _ in range(int(rs.randint(0, 4))):
            lane = int(rs.randint(n_lanes))
            r = _req(rid, int(rs.randint(1, max_batch + 1)), lane)
            budget[rid] = len(lanes[lane]) + 1
            lanes[lane].append(r)
            q.push(r)
            rid += 1
        live = [k for k, v in lanes.items() if v]
        if not live:
            continue
        k = live[int(rs.randint(len(live)))]
        force = bool(rs.randint(2))
        others = [o for o in live if o != k] if mix else ()
        fifo = _fifo_run(lanes[k], max_batch, key, min_fill, force)
        reqs, rows = q.take(k, max_batch, key, min_fill=min_fill,
                            force=force, others=others)
        got = [r.rid for r in reqs]
        assert got[:len(fifo)] == [r.rid for r in fifo]
        n = sum(r.n for r in reqs)
        assert n >= sum(r.n for r in fifo) and n <= max_batch
        assert sum(rows.values()) == n
        assert mix or rows["other_lane"] == 0
        for r in lanes[k]:
            budget[r.rid] -= 1
        taken += got
        for v in lanes.values():
            v[:] = [r for r in v if r.rid not in got]
        assert all(budget[r.rid] > 0 for v in lanes.values() for r in v)
    for k in range(n_lanes):
        while True:
            reqs, _ = q.take(k, max_batch, key, force=True)
            if not reqs:
                break
            taken += [r.rid for r in reqs]
    assert sorted(taken) == list(range(rid))
    assert q.empty
