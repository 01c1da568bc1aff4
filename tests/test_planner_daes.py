"""Satellite coverage: AdmissionPlanner telemetry priors and the DAES
metric stack (Eqs. 9, 20-22) against hand-computed values."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import daes as DAES
from repro.core import difficulty as DIFF
from repro.core.routing import DartParams
from repro.engine import DartEngine
from repro.models.vit import ViTConfig, vit_init
from repro.parallel.sharding import unzip
from repro.serving import AdmissionPlanner

CUM = [0.4, 0.7, 1.0]


@pytest.fixture(scope="module")
def engine():
    vc = ViTConfig(name="pl-vt", img_res=32, patch=8, n_layers=3,
                   d_model=32, n_heads=2, d_ff=64, n_classes=10,
                   exit_layers=(0, 1))
    params, _ = unzip(vit_init(jax.random.key(0), vc))
    return DartEngine.from_config(
        vc, params, cum_costs=CUM, adapt=False,
        dart=DartParams(tau=jnp.full((2,), 0.2), coef=jnp.ones(2),
                        beta_diff=0.3))


# ---------------------------------------------------------------------------
# AdmissionPlanner priors
# ---------------------------------------------------------------------------
def test_observe_folds_per_class_ema(engine):
    pl = AdmissionPlanner(engine, edges=(0.35, 0.65), ema_decay=0.9)
    assert pl.priors() == [None, None, None]
    # first observation SETS the class EMA (no decay on cold start):
    # class 0 (alpha .1, .2) depths (0, 2) -> 1.0; class 2 (alpha .9)
    # depth 1 -> 1.0
    pl.observe(np.array([0, 2, 1]), np.array([0.1, 0.2, 0.9]))
    pr = pl.priors()
    np.testing.assert_allclose(pr[0], 1.0)
    assert pr[1] is None
    np.testing.assert_allclose(pr[2], 1.0)
    # second observation folds: 0.9*1.0 + 0.1*2.0 = 1.1 for class 0
    pl.observe(np.array([2]), np.array([0.1]))
    np.testing.assert_allclose(pl.priors()[0], 1.1)
    np.testing.assert_allclose(pl.priors()[2], 1.0)
    assert pl.priors()[1] is None


def test_predicted_cost_fallback_chain(engine):
    pl = AdmissionPlanner(engine, edges=(0.35, 0.65), ema_decay=0.9)
    # 1. never-seen class, never-served engine: linear-in-alpha depth
    #    alpha=0.5 -> depth 1.0 -> interp on cum/cum[-1] = 0.7
    np.testing.assert_allclose(pl.predicted_cost(0.5, 1), 0.7)
    #    fractional depth interpolates the curve: 0.25*(n_exits-1)=0.5
    #    -> (0.4 + 0.7)/2 = 0.55
    np.testing.assert_allclose(pl.predicted_cost(0.25, 0), 0.55)
    # 2. any observation seeds the GLOBAL depth fallback, which then
    #    covers classes never seen themselves
    pl.observe(np.array([2, 2]), np.array([0.9, 0.9]))      # class 2
    np.testing.assert_allclose(pl.predicted_cost(0.1, 0), 1.0)
    # 3. the per-class EMA wins over the global fallback where it exists
    pl.observe(np.array([0, 0]), np.array([0.1, 0.1]))      # class 0
    np.testing.assert_allclose(pl.predicted_cost(0.1, 0), 0.4)


def test_classify_uses_mean_alpha(engine):
    pl = AdmissionPlanner(engine, edges=(0.35, 0.65))
    dclass, cost = pl.classify(np.array([0.8, 1.0]))
    assert dclass == 2
    np.testing.assert_allclose(cost, pl.predicted_cost(0.9, 2))
    assert pl.classify(np.array([0.1]))[0] == 0


def test_admit_alpha_matches_engine(engine):
    """Admission's alpha is the engine's own Eq. 8 estimator — computed
    once, handed to dispatch."""
    pl = AdmissionPlanner(engine)
    x = np.asarray(jax.random.normal(jax.random.key(1), (4, 32, 32, 3)))
    alpha, dclass, cost, rows = pl.admit(x)
    assert rows is None                 # no device rows unless asked for
    np.testing.assert_allclose(
        alpha, np.asarray(engine._alpha(jnp.asarray(x))), atol=1e-6)
    assert dclass == int(DIFF.difficulty_class(float(alpha.mean()),
                                               pl.edges))
    assert cost > 0


# ---------------------------------------------------------------------------
# DAES metric stack (Eqs. 9, 20-22), hand-computed
# ---------------------------------------------------------------------------
def _meas():
    static = DAES.MethodMeasurement("static", accuracy=0.92, time_s=0.10,
                                    macs=4e8, energy_j=2.0)
    m = DAES.MethodMeasurement("dart", accuracy=0.90, time_s=0.04,
                               macs=1e8, energy_j=0.6)
    return static, m


def test_speedup_power_daes_hand_computed():
    static, m = _meas()
    np.testing.assert_allclose(DAES.speedup(static, m), 2.5)       # Eq.20
    np.testing.assert_allclose(
        DAES.power_efficiency(static, m, "macs"), 4.0)             # Eq.22
    np.testing.assert_allclose(
        DAES.power_efficiency(static, m, "measured"), 2.0 / 0.6)
    # Eq. 9: 0.90 * 2.5 * 4.0 / (1 + 0.85)
    np.testing.assert_allclose(
        DAES.daes(static, m, 0.85, "macs"), 0.9 * 2.5 * 4.0 / 1.85)
    np.testing.assert_allclose(DAES.avg_power(m), 0.6 / 0.04)      # Eq.21
    assert DAES.avg_power(DAES.MethodMeasurement("x", 1, 1, 1)) is None


def test_summary_row_fields():
    static, m = _meas()
    row = DAES.summary_row(static, m, 0.85)
    np.testing.assert_allclose(row["acc_pct"], 90.0)
    np.testing.assert_allclose(row["time_ms"], 40.0)
    np.testing.assert_allclose(row["macs_m"], 100.0)
    np.testing.assert_allclose(row["speedup"], 2.5)
    np.testing.assert_allclose(row["daes"],
                               DAES.daes(static, m, 0.85))


def test_lane_accumulator_rows_hand_computed():
    acc = DAES.LaneDaesAccumulator(static_macs=1.0)
    assert acc.rows() == {}
    # two observations in one lane: mean conf 0.8, mean macs 0.25,
    # mean alpha 0.5
    acc.observe((0, 1), conf=[0.7, 0.9], macs=[0.2, 0.3],
                alpha=[0.4, 0.6])
    acc.observe((1, 2), conf=[0.6], macs=[1.0], alpha=[0.9])
    rows = acc.rows()
    assert set(rows) == {(0, 1), (1, 2)}
    r = rows[(0, 1)]
    assert r["n"] == 2
    np.testing.assert_allclose(r["acc_pct"], 80.0)
    np.testing.assert_allclose(r["speedup"], 1.0 / 0.25)   # time ∝ macs
    np.testing.assert_allclose(r["power_eff"], 1.0 / 0.25)
    # Eq. 9 with pseudo-accuracy: 0.8 * 4 * 4 / 1.5
    np.testing.assert_allclose(r["daes"], 0.8 * 4 * 4 / 1.5)
    # a lane that pays the full static cost has speedup exactly 1
    np.testing.assert_allclose(rows[(1, 2)]["speedup"], 1.0)
    np.testing.assert_allclose(rows[(1, 2)]["daes"],
                               0.6 * 1.0 * 1.0 / 1.9)


def test_server_stats_exports_per_lane_daes(engine):
    """Satellite: stats()["daes"] reports Eq. 9 per difficulty class."""
    from repro.serving import AsyncDartServer, SchedulerConfig
    from repro.data.datasets import DatasetConfig, make_batch
    x, _ = make_batch(DatasetConfig(name="synth-cifar", n_train=128,
                                    n_eval=128), range(24), split="eval")
    x = np.asarray(x)
    with AsyncDartServer(engine, SchedulerConfig(
            max_batch=8, flush_ms=2.0, pipeline_depth=0)) as srv:
        for i in range(0, 24, 6):
            srv.submit(x[i:i + 6]).result(timeout=60)
        daes_rows = srv.stats()["daes"]
    assert daes_rows, "serving must export at least one DAES lane"
    assert sum(r["n"] for r in daes_rows.values()) == 24
    for r in daes_rows.values():
        assert r["speedup"] >= 1.0 - 1e-9      # early exits only save
        assert r["daes"] > 0
