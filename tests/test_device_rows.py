"""Device-resident request rows: admission keeps each request's images
on the device (one f32 array per image) and the dispatcher stacks a
sharded engine's masked bucket from them there, instead of
concatenating, padding, casting and copying host images.

Covers:

* served outputs are bit-identical to ``engine.infer`` on the bucket's
  concatenated host images with the same alpha, for request sizes that
  cross both buckets — on a 1-device mesh and (subprocess) on an
  8-fake-device mesh;
* after one request of each size and the host-path warm-up, random
  mixes of those sizes trace and compile nothing: the split is traced
  once per request size, the stack once per bucket size;
* ``dart_bucket_assembly_total{path}`` and the ``gather`` span's
  ``path`` say ``device`` for sharded masked buckets and ``host`` for
  compacted, eager and oversized ones; ``put`` copies under 1 KB on the
  device path;
* a bucket of device rows that a dying engine fails is requeued and
  resolves exactly once, with the same answer.
"""
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs as obs
from repro.core.routing import DartParams
from repro.engine import DartEngine, ShardedDartEngine
from repro.launch.mesh import make_serving_mesh
from repro.models.vit import ViTConfig, vit_init
from repro.obs import metrics as M
from repro.parallel.sharding import unzip
from repro.runtime.chaos import FaultInjector, FaultPlan, FaultSpec
from repro.serving import (AsyncDartServer, EnginePool, PooledDartServer,
                           ResilienceConfig, SchedulerConfig)

CFG = ViTConfig(name="rows-vt", img_res=32, patch=8, n_layers=3,
                d_model=32, n_heads=2, d_ff=64, n_classes=10,
                exit_layers=(0, 1))
BUCKETS = (8, 32)
#: request sizes of the benchmark's traffic
SIZES = (1, 2, 5, 8, 13, 21, 32)
_PARAMS: list = []


def _engine(mesh=True, **kw):
    if not _PARAMS:
        _PARAMS.append(unzip(vit_init(jax.random.key(0), CFG))[0])
    return DartEngine.from_config(
        CFG, _PARAMS[0], mesh=make_serving_mesh() if mesh else None,
        cum_costs=[0.4, 0.7, 1.0], adapt=False, buckets=BUCKETS,
        dart=DartParams(tau=jnp.full((2,), 0.2), coef=jnp.ones(2),
                        beta_diff=0.3), **kw)


def _images(seed, n):
    return np.random.RandomState(seed).rand(n, 32, 32, 3).astype(
        np.float32)


def _spy(srv):
    """Record each dispatched bucket's requests and operand kind."""
    seen, infer = [], srv._infer_batch

    def spy(reqs, x, alpha):
        seen.append((list(reqs), isinstance(x, tuple)))
        return infer(reqs, x, alpha)
    srv._infer_batch = spy
    return seen


def check_matches_host_path():
    """Serve mixed request sizes through a server over a sharded engine;
    each bucket's answers must equal ``engine.infer`` (masked, same
    alpha) on its requests' host images concatenated."""
    eng, oracle = _engine(), _engine()
    srv = AsyncDartServer(eng, SchedulerConfig(max_batch=32, edges=()),
                          start=False)
    seen = _spy(srv)
    groups = [[1, 2, 5], [13, 8, 3, 7], [32], [21, 2, 1], [4], [16, 9]]
    futs = []
    for g, sizes in enumerate(groups):     # one bucket each
        futs += [srv.submit(_images(10 * g + i, n))
                 for i, n in enumerate(sizes)]
        srv.flush()
    results = {id(f): f.result(timeout=60) for f in futs}
    assert len(seen) == len(groups)
    assert {len(reqs) > 1 for reqs, _ in seen} == {True, False}
    bps = set()
    for reqs, on_device in seen:
        assert on_device
        x = np.concatenate([r.x for r in reqs])
        alpha = np.concatenate([r.alpha for r in reqs])
        bps.add(oracle.bucket_key(len(x)))
        ref = oracle.infer(x, mode="masked", alpha=alpha)
        a = 0
        for r in reqs:
            got = results[id(r.future)]
            for k in ("pred", "conf", "exit_idx", "alpha", "macs"):
                np.testing.assert_array_equal(
                    got[k], np.asarray(ref[k])[a:a + r.n], err_msg=k)
            a += r.n
    assert bps == set(BUCKETS)
    srv.close()
    return len(seen)


def test_served_buckets_match_host_path_one_device():
    assert check_matches_host_path() > 1


MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path[:0] = [%r, %r]
    import jax
    assert len(jax.devices()) == 8
    import test_device_rows as T
    from repro.launch.mesh import make_serving_mesh
    assert make_serving_mesh().shape["data"] == 8
    print("BUCKETS", T.check_matches_host_path())
""" % (os.path.join(os.path.dirname(__file__), "..", "src"),
       os.path.dirname(__file__)))


def test_served_buckets_match_host_path_eight_devices():
    r = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "BUCKETS" in r.stdout


def test_random_mixes_trace_and_compile_nothing_new():
    """The benchmark's warm-up (one request of each size, then
    ``engine.infer`` on host images for every batch size) covers every
    program random mixes of those sizes use."""
    eng = _engine()
    srv = AsyncDartServer(eng, SchedulerConfig(max_batch=32, edges=()),
                          start=False)
    for i, n in enumerate(SIZES):
        fut = srv.submit(_images(i, n))
        srv.flush()
        fut.result(timeout=60)
    alpha = np.full(32, 0.5, np.float32)
    for b in range(1, 33):
        np.asarray(eng.infer(_images(0, b), mode="masked",
                             alpha=alpha[:b])["pred"])
    traced = dict(eng.trace_counts)
    assert {k: v for k, v in traced.items() if k[0] == "stack"} == {
        ("stack", 8): 1, ("stack", 32): 1}
    assert {k for k in traced if k[0] == "split"} == {
        ("split", n) for n in SIZES}
    compiles = []
    listener = _compile_listener(compiles)
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        rs = np.random.RandomState(3)
        seen = _spy(srv)
        for _ in range(6):
            futs = [srv.submit(_images(int(s), int(n)))
                    for s, n in enumerate(rs.choice(SIZES, 6))]
            srv.flush()
            for f in futs:
                f.result(timeout=60)
    finally:
        listener.active = False
    assert all(on_device for _, on_device in seen)
    assert len({sum(r.n for r in reqs) for reqs, _ in seen}) > 2
    assert compiles == []
    assert eng.trace_counts == traced
    srv.close()


def _compile_listener(sink):
    def on(event, secs, **_):
        if on.active and event == "/jax/core/compile/backend_compile_duration":
            sink.append(secs)
    on.active = True
    return on


@pytest.fixture
def traced():
    obs.reset()
    obs.configure(enabled=True)
    yield obs.get_tracer()
    obs.reset()


def _assembly(tracer, srv_kw, engine, sizes):
    """Serve ``sizes`` in one lane; (counter by path, gather paths,
    put bytes) of the buckets."""
    srv = AsyncDartServer(engine, SchedulerConfig(edges=(), **srv_kw),
                          start=False)
    futs = [srv.submit(_images(i, n)) for i, n in enumerate(sizes)]
    srv.flush()
    for f in futs:
        f.result(timeout=60)
    srv.close()
    fam = M.parse_prometheus(obs.get_registry().render()).get(
        "dart_bucket_assembly_total", {"samples": []})
    counts = {lab["path"]: v for _, lab, v in fam["samples"]}
    gathers = [s["path"] for s in tracer.spans("gather")]
    puts = [s["bytes"] for s in tracer.spans("put") if s["parent"] is None]
    return counts, gathers, puts


def test_assembly_counter_device_for_sharded_masked(traced):
    counts, gathers, puts = _assembly(traced, {"max_batch": 32}, _engine(),
                                      [1, 5, 13, 2])
    n = len(traced.spans("bucket"))
    assert counts == {"device": n} and gathers == ["device"] * n
    assert len(puts) == n and all(0 < b < 1024 for b in puts), puts


@pytest.mark.parametrize("case", ["compacted", "eager", "oversized"])
def test_assembly_counter_host_otherwise(traced, case):
    srv_kw, eng, sizes = {
        "compacted": ({"mode": "compacted"}, _engine(), [1, 5, 13]),
        "eager": ({}, _engine(mesh=False), [1, 5, 13]),
        "oversized": ({}, _engine(), [40]),
    }[case]
    counts, gathers, puts = _assembly(traced, srv_kw, eng, sizes)
    n = len(traced.spans("bucket"))
    assert counts == {"host": n} and gathers == ["host"] * n
    if case == "oversized":           # two chunks, images copied
        assert len(puts) == 2 and min(puts) > 8 * 32 * 32 * 3 * 4


def test_requeued_device_rows_resolve_exactly_once():
    eng, oracle = _engine(), _engine()
    inj = FaultInjector(FaultPlan([
        FaultSpec("engine_death", "step", 0, engine="e0")]))
    pool = EnginePool({"e0": eng},
                      ResilienceConfig(backoff_s=0.001,
                                       requeue_backoff_s=0.001),
                      injector=inj, heartbeat=False)
    assert isinstance(pool.primary, ShardedDartEngine)
    srv = PooledDartServer(pool, SchedulerConfig(edges=(), max_batch=32),
                           start=False)
    seen = _spy(srv)
    x = [_images(20 + i, n) for i, n in enumerate((3, 5))]
    futs = [srv.submit(xi, priority=5) for xi in x]
    resolved = []
    for f in futs:
        f.add_done_callback(lambda f: resolved.append(f))
    time.sleep(0.01)                  # past the hold
    assert srv.pump()                 # the engine dies: bucket requeued
    assert srv.counters["requeued"] == 2 and not resolved
    assert all(r.rows is not None for r in seen[0][0])
    pool.join("e0")                   # warms the noted bucket shape
    srv.flush()
    assert len(resolved) == 2 and set(resolved) == set(futs)
    assert [on_device for _, on_device in seen] == [True, True]
    assert [r.rid for r in seen[0][0]] == [r.rid for r in seen[1][0]]
    assert all(r.rows is None for r in seen[1][0])   # dropped on resolve
    ref = oracle.infer(np.concatenate(x), mode="masked",
                       alpha=np.concatenate([r.alpha for r in seen[1][0]]))
    got = [f.result(timeout=5) for f in futs]
    for k in ("pred", "conf", "exit_idx", "alpha", "macs"):
        np.testing.assert_array_equal(
            np.concatenate([g[k] for g in got]), np.asarray(ref[k]),
            err_msg=k)
    srv.close()
    pool.close()
