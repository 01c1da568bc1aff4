"""repro.obs: the serving observability layer.

Covers, per the PR 8 acceptance list:

* metrics registry round-trip — what ``render()`` writes,
  ``parse_prometheus`` reads back verbatim (incl. escaped labels and
  histogram series), and the percentile estimator agrees between the
  registry and the dashboard;
* the tracer ring — bounded, drop-oldest, corruption-free on overflow,
  Chrome ``trace_event`` export loads as one track per lane;
* disabled mode is INERT: serving a seeded stream with obs off records
  nothing, registers nothing, and produces bit-identical outputs to the
  same stream served with obs ON (tracing must never perturb results);
* enabled mode RECONCILES: the sum of per-span exits equals the
  EngineState telemetry exit histogram after the ``stats()`` reduction,
  and every cataloged metric family shows up in the exposition;
* exporters — textfile + stdlib http endpoint serve parseable text, and
  ``tools/dartop.py --once --json`` consumes it end to end;
* structured logging — a dispatcher failure logs a ``repro.obs.*``
  record and counts ``dart_errors_total``, instead of only failing the
  future silently;
* continuous batching — slot spans carry slot ids, occupancy gauges
  export, and obs-on does not add compiled-step retraces
  (``trace_counts`` stays 1 per key);
* the dispatcher's phase spans, over a sharded engine — exclusive,
  one ``bid`` per bucket, covering the dispatcher thread; every
  blocking device→host read is a ``sync`` child of a phase (or of
  ``admit``), exactly five per masked bucket, counted by site;
* the span API — parent, thread, inherited ``bid``, the profiler
  annotation it enters — and the wall-clock Chrome export.
"""
import json
import logging
import os
import subprocess
import sys
import textwrap
import urllib.request
from concurrent.futures import Future
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.obs as obs
from repro.core.routing import DartParams
from repro.data.datasets import DatasetConfig, make_batch
from repro.engine import DartEngine, LMDecodeEngine
from repro.launch.mesh import make_serving_mesh
from repro.models.transformer_lm import LMConfig, lm_init
from repro.models.vit import ViTConfig, vit_init
from repro.obs import metrics as M
from repro.obs import trace as T
from repro.obs.stats import SUMMARY_KEYS
from repro.parallel.sharding import unzip
from repro.serving import AsyncDartServer, SchedulerConfig
from repro.serving.loop import _BucketScheduler
from repro.serving.request import DispatchError, Request

ROOT = Path(__file__).resolve().parent.parent
DATA = DatasetConfig(name="synth-cifar", n_train=128, n_eval=128)

LM_CFG = LMConfig(name="lm-obs-t", n_layers=4, d_model=32, n_heads=2,
                  n_kv_heads=1, d_ff=64, vocab=32, exit_layers=(0, 2),
                  max_seq=64, remat=False)


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs.reset()
    yield
    obs.reset()


def _vit_engine_factory():
    vc = ViTConfig(name="vt-obs", img_res=32, patch=8, n_layers=3,
                   d_model=32, n_heads=2, d_ff=64, n_classes=10,
                   exit_layers=(0, 1))
    params, _ = unzip(vit_init(jax.random.key(0), vc))

    def make(**kw):
        kw.setdefault("cum_costs", [0.4, 0.7, 1.0])
        kw.setdefault("adapt", True)
        kw.setdefault("update_every", 10 ** 9)
        return DartEngine.from_config(
            vc, params,
            dart=DartParams(tau=jnp.full((2,), 0.2), coef=jnp.ones(2),
                            beta_diff=0.3), **kw)
    return make


@pytest.fixture(scope="module")
def vit_engine_factory():
    return _vit_engine_factory()


@pytest.fixture(scope="module")
def eval_images():
    x, _ = make_batch(DATA, range(64), split="eval")
    return np.asarray(x)


def _serve_stream(engine, images):
    """Serve the images 4-at-a-time through a threaded server; returns
    (per-request results, server stats, the closed server).  Callers
    that scrape afterwards must keep the server referenced — the pull
    collector is weakref-bound to it."""
    srv = AsyncDartServer(engine, SchedulerConfig(max_batch=8,
                                                  flush_ms=1.0))
    futs = [srv.submit(images[i:i + 4], deadline_ms=10_000)
            for i in range(0, len(images), 4)]
    outs = [f.result(timeout=120) for f in futs]
    srv.close()
    return outs, srv.stats(), srv


# ---------------------------------------------------------------------------
# metrics: exposition round-trip
# ---------------------------------------------------------------------------
def test_counter_roundtrip_with_escaped_labels():
    r = M.Registry()
    nasty = 'quo"te\\back\nnewline'
    r.counter("dart_x_total", "help with\nnewline", ("lane",)).inc(
        3, lane=nasty)
    fams = M.parse_prometheus(r.render())
    assert fams["dart_x_total"]["type"] == "counter"
    assert fams["dart_x_total"]["help"] == "help with\nnewline"
    [(name, labels, value)] = fams["dart_x_total"]["samples"]
    assert (name, labels["lane"], value) == ("dart_x_total", nasty, 3.0)


def test_histogram_exposition_and_percentile():
    r = M.Registry()
    h = r.histogram("lat_ms", "x", ("lane",), buckets=(1, 10, 100))
    for v in (0.5, 5, 5, 50):
        h.observe(v, lane="a")
    fams = M.parse_prometheus(r.render())
    fam = fams["lat_ms"]
    assert fam["type"] == "histogram"
    by_le = {lab["le"]: v for n, lab, v in fam["samples"]
             if n == "lat_ms_bucket"}
    assert by_le == {"1": 1.0, "10": 3.0, "100": 4.0, "+Inf": 4.0}
    [(_, _, total)] = [s for s in fam["samples"] if s[0] == "lat_ms_sum"]
    assert total == pytest.approx(60.5)
    # registry estimator == dashboard estimator, cumulative -> counts
    assert h.percentile(50, lane="a") == pytest.approx(
        M.estimate_percentile((1, 10, 100), [1, 2, 1, 0], 50))


def test_registry_redeclaration_must_agree():
    r = M.Registry()
    c = r.counter("n_total", "x", ("lane",))
    assert r.counter("n_total", "x", ("lane",)) is c
    with pytest.raises(ValueError):
        r.counter("n_total", "x", ("member",))
    with pytest.raises(ValueError):
        r.gauge("n_total", "x", ("lane",))
    with pytest.raises(ValueError):
        c.inc(1, wrong="label")


def test_collectors_raising_or_dead_are_dropped():
    r = M.Registry()
    calls = []
    r.register_collector(lambda reg: calls.append("ok"))
    r.register_collector(lambda reg: "dead")
    r.register_collector(lambda reg: 1 / 0)
    r.collect()
    r.collect()
    assert calls == ["ok", "ok"]       # survivor ran twice
    with r._lock:
        assert len(r._collectors) == 1  # dead + raising removed


# ---------------------------------------------------------------------------
# tracer ring
# ---------------------------------------------------------------------------
def test_ring_overflow_drops_oldest_without_corruption():
    tr = T.Tracer(capacity=8)
    for i in range(100):
        tr.record("admit", ts=float(i), rid=i, lane=i % 3)
    spans = tr.spans()
    assert [s["rid"] for s in spans] == list(range(92, 100))
    assert len(tr) == 8 and tr.dropped == 92
    assert all(s["ts"] == float(s["rid"]) for s in spans)
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_chrome_trace_tracks_per_lane(tmp_path):
    tr = T.Tracer()
    tr.record("queue_wait", ts=1.0, dur=0.5, rid=0, lane=(0, 1))
    tr.record("compiled_step", ts=1.5, dur=0.25, rid=0, lane=(0, 1),
              n=np.int64(4))
    tr.record("exit", ts=2.0, rid=1, lane=(1, 0),
              exits=np.asarray([2, 2]))
    path = tmp_path / "spans.jsonl"
    assert tr.export_jsonl(str(path)) == 3
    doc = T.chrome_trace(T.load_jsonl(str(path)))
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    xs = [e for e in events if e["ph"] == "X"]
    assert len(meta) == 2 and len(xs) == 3      # one track per lane
    assert {e["tid"] for e in xs} == {m["tid"] for m in meta}
    assert xs[0]["ts"] == pytest.approx(1.0e6)  # seconds -> micros
    assert xs[0]["dur"] == pytest.approx(0.5e6)
    json.dumps(doc)                              # fully serializable


# ---------------------------------------------------------------------------
# disabled mode is inert; enabled mode reconciles
# ---------------------------------------------------------------------------
def test_disabled_inert_and_bit_identical(vit_engine_factory, eval_images):
    # the eager engine, then the sharded one (phase and sync spans)
    for kw in ({}, {"mesh": make_serving_mesh()}):
        obs.reset()
        assert not obs.is_enabled()
        off, _, _ = _serve_stream(vit_engine_factory(**kw), eval_images)
        assert len(obs.get_tracer()) == 0
        assert "dart_" not in obs.get_registry().render()

        obs.configure(enabled=True)
        on, _, _srv = _serve_stream(vit_engine_factory(**kw), eval_images)
        assert len(obs.get_tracer()) > 0
        if kw:
            assert obs.get_tracer().spans("sync")
        for a, b in zip(off, on):
            for k in ("pred", "conf", "exit_idx", "alpha", "macs"):
                assert np.array_equal(a[k], b[k]), k


def test_spans_reconcile_with_engine_telemetry(vit_engine_factory,
                                               eval_images):
    obs.configure(enabled=True)
    eng = vit_engine_factory()
    _, stats, srv = _serve_stream(eng, eval_images)
    for k in SUMMARY_KEYS:
        assert k in stats
    span_exits = np.zeros(eng.n_exits, np.int64)
    for s in obs.get_tracer().spans("exit"):
        for e in s["exits"]:
            span_exits[int(e)] += 1
    assert np.array_equal(span_exits, np.asarray(stats["exit_counts"]))
    assert stats["scheduler"]["starved"] == 0
    # one admit + queue_wait + compiled_step per request
    n_req = len(eval_images) // 4
    assert len(obs.get_tracer().spans("admit")) == n_req
    assert len(obs.get_tracer().spans("queue_wait")) == n_req

    fams = M.parse_prometheus(obs.get_registry().render())
    for fam in ("dart_requests_total", "dart_requests_completed_total",
                "dart_request_latency_ms", "dart_exits_total",
                "dart_flushes_total", "dart_lane_daes",
                "dart_lane_speedup", "dart_lane_power_eff",
                "dart_depth_prior", "dart_queue_depth",
                "dart_scheduler_events_total", "dart_engine_latency_ms",
                "dart_engine_exits_total", "dart_trace_total",
                "dart_recompiles_total", "dart_kernel_dispatch_total"):
        assert fam in fams, fam
    # counters mirror the scheduler's own view
    comp = sum(v for n, lab, v in
               fams["dart_requests_completed_total"]["samples"])
    assert comp == stats["scheduler"]["completed"] == n_req


# ---------------------------------------------------------------------------
# the dispatcher's phases and device->host syncs (sharded engine)
# ---------------------------------------------------------------------------
DISPATCHER = "AsyncDartServer"          # the dispatcher thread's name
#: the masked path's blocking reads per bucket: five outputs in
#: ``fetch`` (the latency window lives on the host: ``fold`` reads none)
SYNCS_PER_BUCKET = {("fetch", "output"): 5}


@pytest.fixture(scope="module")
def served_traced(vit_engine_factory, eval_images):
    """One traced serve over a sharded engine: its spans and exposition
    (obs is reset again before each test)."""
    obs.reset()
    obs.configure(enabled=True)
    _, _, srv = _serve_stream(vit_engine_factory(mesh=make_serving_mesh()),
                              eval_images)
    out = {"spans": obs.get_tracer().spans(),
           "fams": M.parse_prometheus(obs.get_registry().render()),
           "n_req": len(eval_images) // 4}
    del srv
    obs.reset()
    return out


def _top_phases(spans, thread=None):
    return sorted((s for s in spans if s["name"] in T.DISPATCH_PHASES
                   and s["parent"] is None
                   and thread in (None, s["thread"])),
                  key=lambda s: s["ts"])


def test_dispatcher_phases_share_a_bid_and_do_not_overlap(served_traced):
    spans = served_traced["spans"]
    phases = _top_phases(spans, DISPATCHER)
    assert {"wait", "select", "gather", "put", "launch", "fetch", "fold",
            "resolve"} <= {s["name"] for s in phases}
    for a, b in zip(phases, phases[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1e-9, (a, b)
    assert all(isinstance(s["bid"], int) for s in phases)
    bids = [s["bid"] for s in spans if s["name"] == "bucket"]
    assert bids == sorted(set(bids))
    # every dispatched bucket went through each of its phases once,
    # under its own bid (a bucket flushed at close runs on the closer)
    for bid in bids:
        names = sorted(s["name"] for s in _top_phases(spans)
                       if s["bid"] == bid and s["name"] not in
                       ("wait", "select"))
        assert names == sorted(["gather", "put", "launch", "fetch",
                                "fold", "resolve"]), (bid, names)


def test_dispatcher_phases_cover_the_thread(served_traced):
    phases = _top_phases(served_traced["spans"], DISPATCHER)
    first = phases[0]["ts"]
    last = max(s["ts"] + s["dur"] for s in phases)
    covered = sum(s["dur"] for s in phases)
    assert covered >= 0.95 * (last - first), covered / (last - first)
    # each phase ends where the next begins; the time after its body
    # (loop plumbing, the thread waiting to run) is its tail
    for a, b in zip(phases, phases[1:]):
        assert a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=1e-9)
    assert all(0.0 <= s["tail"] <= s["dur"] for s in phases)


def test_every_sync_has_a_parent_phase(served_traced):
    spans = served_traced["spans"]
    syncs = [s for s in spans if s["name"] == "sync"]
    assert syncs
    for s in syncs:
        assert s["parent"] in T.DISPATCH_PHASES + ("admit",), s
        assert s["bytes"] > 0 and s["site"]
    # admission: one copy to the device and one read of alpha, under
    # its admit span, on the submitting thread
    n_req = served_traced["n_req"]
    for name, site in (("put", None), ("sync", "admit_alpha")):
        kids = [s for s in spans if s["name"] == name
                and s["parent"] == "admit"]
        assert len(kids) == n_req
        assert all(s.get("site") == site and s["thread"] != DISPATCHER
                   for s in kids)


def test_masked_path_syncs_per_bucket(served_traced):
    spans = served_traced["spans"]
    bids = [s["bid"] for s in spans if s["name"] == "bucket"]
    for bid in bids:
        got = {}
        for s in spans:
            if s["name"] == "sync" and s.get("bid") == bid:
                key = (s["parent"], s["site"])
                got[key] = got.get(key, 0) + 1
        assert got == SYNCS_PER_BUCKET, (bid, got)
    # the registry counts the same reads by site
    fam = served_traced["fams"]["dart_device_syncs_total"]
    by_site = {lab["site"]: v for _, lab, v in fam["samples"]}
    assert by_site == {"output": 5.0 * len(bids),
                       "admit_alpha": float(served_traced["n_req"])}


def test_launched_buckets_counted_by_behind(served_traced):
    """Every ``gather`` carries ``behind`` (in-flight buckets not yet
    computed when the next one launches), and
    ``dart_buckets_launched_total{behind}`` counts each bucket once."""
    spans = served_traced["spans"]
    n_buckets = len([s for s in spans if s["name"] == "bucket"])
    gathers = [s for s in spans if s["name"] == "gather"]
    assert len(gathers) == n_buckets
    for s in gathers:
        assert isinstance(s["behind"], int) and 0 <= s["behind"] <= 2, s
    fam = served_traced["fams"]["dart_buckets_launched_total"]
    by_behind = {lab["behind"]: v for _, lab, v in fam["samples"]}
    assert sum(by_behind.values()) == n_buckets
    for b, v in by_behind.items():
        assert v == sum(s["behind"] == int(b) for s in gathers)


# ---------------------------------------------------------------------------
# the request window lives on the host
# ---------------------------------------------------------------------------
def _check_request_window(images) -> None:
    """Serve a traced stream (half the requests past a zero deadline)
    through a sharded engine over every device: ``fold`` makes no
    blocking read, and ``stats()["requests"]`` is exactly what the
    futures report."""
    obs.reset()
    obs.configure(enabled=True)
    eng = _vit_engine_factory()(mesh=make_serving_mesh())
    assert eng.n_replicas == jax.device_count()
    srv = AsyncDartServer(eng, SchedulerConfig(max_batch=8, flush_ms=1.0))
    futs = [srv.submit(images[i:i + 4],
                       deadline_ms=0.0 if i % 8 else 10_000)
            for i in range(0, len(images), 4)]
    outs = [f.result(timeout=120) for f in futs]
    srv.close()
    spans = obs.get_tracer().spans()
    assert any(s["name"] == "fold" for s in spans)
    assert not [s for s in spans
                if s["name"] == "sync" and s["parent"] == "fold"]
    req = srv.stats()["requests"]
    lats = np.asarray([o["latency_ms"] for o in outs], np.float32)
    assert req["requests"] == len(outs)
    n_miss = sum(o["deadline_missed"] for o in outs)
    assert req["deadline_miss"] == n_miss >= len(outs) // 2
    got = req["latency_ms"]
    np.testing.assert_array_equal(
        [got["p50"], got["p95"], got["p99"]],
        np.percentile(lats, [50.0, 95.0, 99.0]).astype(np.float64))
    obs.reset()


WINDOW_SCRIPT = textwrap.dedent("""
    import sys
    sys.path[:0] = [%r, %r]
    import numpy as np
    from repro.data.datasets import make_batch
    import test_obs
    x, _ = make_batch(test_obs.DATA, range(64), split="eval")
    test_obs._check_request_window(np.asarray(x))
    print("WINDOW_OK")
""" % (str(ROOT / "src"), str(ROOT / "tests")))


@pytest.mark.parametrize("n_devices", [1, 8])
def test_fold_reads_nothing_and_window_matches_futures(n_devices,
                                                       eval_images):
    if n_devices == 1:
        _check_request_window(eval_images)
        return
    env = {**os.environ, "XLA_FLAGS":
           f"--xla_force_host_platform_device_count={n_devices}"}
    r = subprocess.run([sys.executable, "-c", WINDOW_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "WINDOW_OK" in r.stdout


def test_completion_reads_no_leaf_of_the_engine_state(vit_engine_factory,
                                                      eval_images,
                                                      monkeypatch):
    """Completing a bucket hands ``to_host`` that bucket's outputs and
    never a leaf of the engine's current state — the state is the
    output of the newest step, so reading it would wait for that step
    and drain the device."""
    eng = vit_engine_factory(mesh=make_serving_mesh())
    seen = []

    def recording(value, site):
        leaves = jax.tree.leaves(eng.state)
        seen.append((site, any(value is leaf for leaf in leaves)))
        return np.asarray(value)

    original = obs.to_host
    for name, mod in list(sys.modules.items()):
        if name.startswith("repro.") \
                and getattr(mod, "to_host", None) is original:
            monkeypatch.setattr(mod, "to_host", recording)
    outs, st, _ = _serve_stream(eng, eval_images)
    assert st["requests"]["requests"] == len(outs)
    sites = [site for site, _ in seen]
    assert sites.count("output") >= 5
    assert not [site for site, is_leaf in seen if is_leaf], seen


def test_request_spans_carry_their_bucket(served_traced):
    spans = served_traced["spans"]
    rids_of = {s["bid"]: s["rids"] for s in spans if s["name"] == "bucket"}
    assert sum(len(r) for r in rids_of.values()) == served_traced["n_req"]
    for name in ("queue_wait", "compiled_step"):
        got = [s for s in spans if s["name"] == name]
        assert len(got) == served_traced["n_req"]
        for s in got:
            assert s["rid"] in rids_of[s["bid"]], s


# ---------------------------------------------------------------------------
# the span API and the wall-clock export
# ---------------------------------------------------------------------------
def test_span_records_parent_thread_and_bucket(monkeypatch):
    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    tr = T.Tracer()
    with tr.bucket(7):
        with tr.span("fetch", n=2) as sp:
            with tr.span("sync", site="output"):
                pass
            sp.set(extra=1)
        with tr.span("wait", bid=8):
            pass
    with tr.span("admit"):
        pass
    sync, fetch, wait, admit = tr.spans()
    assert entered == ["fetch", "sync", "wait", "admit"]
    assert (sync["parent"], sync["bid"], sync["site"]) == \
        ("fetch", 7, "output")
    assert fetch["parent"] is None and fetch["bid"] == 7
    assert (fetch["n"], fetch["extra"]) == (2, 1)
    assert fetch["ts"] <= sync["ts"] and \
        sync["ts"] + sync["dur"] <= fetch["ts"] + fetch["dur"]
    assert wait["bid"] == 8                  # an explicit bid wins
    assert "bid" not in admit and tr.bid is None
    assert {s["thread"] for s in tr.spans()} == {"MainThread"}


def test_null_span_records_nothing():
    with obs.NULL_SPAN as sp:
        sp.set(anything=1)
    assert obs.to_host(jnp.arange(3), "x").tolist() == [0, 1, 2]
    assert len(obs.get_tracer()) == 0
    assert "dart_device_syncs_total" not in obs.get_registry().render()


def test_chrome_export_is_on_the_wall_clock(tmp_path):
    tr = T.Tracer()
    tr.wall_offset_ns = 5_000_000_000
    with tr.span("gather", bid=0):
        pass
    tr.record("queue_wait", ts=1.0, dur=0.5, rid=0, lane=1)
    path = tmp_path / "spans.jsonl"
    assert tr.export_jsonl(str(path)) == 2
    assert T.load_wall_offset_ns(str(path)) == 5_000_000_000
    spans = T.load_jsonl(str(path))
    assert [s["name"] for s in spans] == ["gather", "queue_wait"]
    out = tmp_path / "trace.json"
    res = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "trace_view.py"), str(path),
         "-o", str(out)], capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    events = json.loads(out.read_text())["traceEvents"]
    tracks = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert tracks == {"thread MainThread", "lane 1"}
    qw = next(e for e in events if e["name"] == "queue_wait")
    assert qw["ts"] == pytest.approx(1.0e6 + 5.0e6)
    g = next(e for e in events if e["name"] == "gather")
    assert g["ts"] == pytest.approx(spans[0]["ts"] * 1e6 + 5.0e6)


# ---------------------------------------------------------------------------
# exporters + dashboard
# ---------------------------------------------------------------------------
def test_textfile_http_and_dartop_roundtrip(vit_engine_factory,
                                            eval_images, tmp_path):
    prom = tmp_path / "metrics.prom"
    obs.configure(enabled=True, textfile=str(prom), http_port=0)
    _, _, srv = _serve_stream(vit_engine_factory(), eval_images)
    obs.flush_textfile()

    # the http endpoint serves the same (parseable) exposition
    port = obs.OBS.http_port
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
        assert r.headers["Content-Type"].startswith("text/plain")
        fams = M.parse_prometheus(r.read().decode())
    assert "dart_requests_total" in fams
    assert "dart_request_latency_ms" in fams

    out = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "dartop.py"),
         "--once", "--json", "--file", str(prom)],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    view = json.loads(out.stdout)
    assert view["scheduler"]["completed"] == len(eval_images) // 4
    assert view["latency_ms"]                       # per-lane p50/p95
    for d in view["latency_ms"].values():
        assert set(d) == {"p50", "p95", "count"}
    assert sum(sum(h.values()) for h in view["exits"].values()) \
        == len(eval_images)
    assert view["recompiles"] == 0


# ---------------------------------------------------------------------------
# structured logging on dispatcher failure (satellite 2)
# ---------------------------------------------------------------------------
class _Boom(RuntimeError):
    pass


class _FailingScheduler(_BucketScheduler):
    def _admit(self, x, deadline_ms, priority, *, now, **kw):
        return Request(rid=next(self._rid), x=np.asarray(x), n=1,
                       alpha=np.zeros(1, np.float32), lane=0,
                       predicted_cost=1.0, priority=priority,
                       t_submit=now, deadline_s=None, future=Future())

    def _dispatch(self, reqs, reason):
        raise _Boom("engine exploded")


def test_dispatch_failure_is_logged_and_counted(caplog):
    sched = _FailingScheduler(SchedulerConfig(), start=False)
    fut = sched.submit(np.zeros(3))
    with caplog.at_level(logging.ERROR, logger="repro.obs"):
        sched.flush()
    with pytest.raises(DispatchError) as ei:
        fut.result(timeout=5)
    assert isinstance(ei.value.cause, _Boom)
    assert ei.value.stage == "dispatch"
    assert sched.counters["dispatch_errors"] == 1
    errs = obs.get_registry().counter(
        "dart_errors_total", "scheduler/dispatcher errors by component",
        ("component",))
    assert errs.value(component="dispatch") == 1
    rec = [r for r in caplog.records
           if r.name == "repro.obs.dispatch"]
    assert rec and "bucket dispatch failed" in rec[0].getMessage()
    assert "rids=" in rec[0].getMessage()


# ---------------------------------------------------------------------------
# continuous batching: slot spans, occupancy gauges, no retraces
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def lm_engine():
    params = unzip(lm_init(jax.random.key(0), LM_CFG))[0]
    return LMDecodeEngine(LM_CFG, params, DartParams(
        tau=jnp.full((2,), 0.0), coef=jnp.ones(2), beta_diff=0.1))


def test_continuous_slot_spans_and_occupancy(lm_engine):
    obs.configure(enabled=True)
    sess = lm_engine.session(continuous=True, n_slots=4, page_size=4,
                             max_len=16, start=False)
    rs = np.random.RandomState(3)
    futs = [sess.submit(rs.randint(0, LM_CFG.vocab, (1, 4)), n_new=3)
            for _ in range(5)]
    sess.flush()
    for f in futs:
        f.result(timeout=120)

    slot_spans = obs.get_tracer().spans("slot")
    assert len(slot_spans) == 5
    assert all(s["slots"] for s in slot_spans)       # real slot ids
    exits = obs.get_tracer().spans("exit")
    assert sum(s["n_tokens"] for s in exits) == 5 * 3

    fams = M.parse_prometheus(obs.get_registry().render())
    occ = {n: fams[n]["samples"][0][2]
           for n in ("dart_slots_total", "dart_pages_total",
                     "dart_pages_peak", "dart_slots_in_use",
                     "dart_pages_in_use")}
    assert occ["dart_slots_total"] == 4
    assert occ["dart_pages_peak"] >= 1
    assert occ["dart_slots_in_use"] == 0             # all retired
    assert "dart_lm_tokens_total" in fams
    assert "starved" in sess.stats()["scheduler"]
    # obs-on added no compiled-step retraces
    assert all(c == 1 for c in lm_engine.trace_counts.values())
    assert lm_engine.trace_counts
