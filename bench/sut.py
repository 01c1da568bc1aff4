"""The system under test, built through the program's own entry points:
``AsyncDartServer.submit`` over ``DartEngine.from_config(..., mesh=
make_serving_mesh(), adapt=False, buckets=...)`` in masked mode.

The benchmark hands the program weights it drew itself (the family's
``bench/reference`` ``init``) and the policy it calibrated; it reads
back only the served answers and the program's spans.
"""
from __future__ import annotations

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np

OPTIMIZER = "bench-exit-shares"


def program_config(cfg: dict):
    """The program's configuration object for a configuration file:
    ``program_config`` names its class, the file's keys fill its fields
    and ``dtype`` its parameter and compute types."""
    module, cls = cfg["program_config"].split(":")
    klass = getattr(importlib.import_module(module), cls)
    kw = {f.name: cfg[f.name] for f in dataclasses.fields(klass)
          if f.name in cfg and f.name != "name"}
    kw = {k: tuple(v) if isinstance(v, list) else v for k, v in kw.items()}
    dtype = jnp.dtype(cfg["dtype"])
    return klass(name=cfg["name"], param_dtype=dtype, compute_dtype=dtype,
                 **kw)


def make_weights(cfg: dict, seed: int, family):
    """The family's random weights from ``seed``, made on the device in
    one jitted call, in the served dtype."""
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)
    dtype = jnp.dtype(cfg["dtype"])
    return jax.jit(lambda k: family.init(k, cfg, dtype))(key)


def shares_tau(conf, alpha, shares, beta_diff):
    """Base thresholds under which the exits take ``shares`` of the rows.

    ``conf`` (E, N) confidences at every exit, ``alpha`` (N,).  With
    coef 1, Eq. 19 fires gate s iff conf_s - beta * alpha > tau_s (where
    the threshold is not clipped), so tau_s is taken halfway between two
    adjacent values of conf_s - beta * alpha among the rows still alive
    at s, leaving the wanted number above it."""
    n = conf.shape[1]
    alive = np.ones(n, bool)
    want = np.round(np.cumsum(shares) * n).astype(int)
    tau, taken = [], 0
    for s in range(conf.shape[0] - 1):
        score = conf[s] - beta_diff * alpha
        vals = np.sort(score[alive])
        k = min(max(want[s] - taken, 0), len(vals))
        if k == 0:
            t = 1.0
        elif k == len(vals):
            t = float(vals[0]) - 1e-3
        else:
            t = 0.5 * float(vals[-k - 1] + vals[-k])
        tau.append(t)
        fire = alive & (score > t)
        taken += int(fire.sum())
        alive &= ~fire
    return np.asarray(tau, np.float32)


def _register_optimizer():
    """A policy optimizer that installs the thresholds it is given
    (``tau=``), through the engine's public ``calibrate``."""
    from repro.core.policy import PolicyResult
    from repro.engine import registry

    if OPTIMIZER in registry.OPTIMIZERS:
        return

    @registry.register_optimizer(OPTIMIZER)
    def _fixed(data, *, tau, beta_diff, **_):
        tau = np.asarray(tau, np.float32)
        return PolicyResult(tau=tau, coef=np.ones_like(tau),
                            beta_diff=float(beta_diff), objective=0.0,
                            method=OPTIMIZER)


class System:
    """Engine and server of one cell, with the calibration and warm-up
    the window needs."""

    def __init__(self, cfg: dict, params):
        from repro.core.routing import DartParams
        from repro.engine import DartEngine
        from repro.launch.mesh import make_serving_mesh
        from repro.serving import AsyncDartServer, SchedulerConfig
        _register_optimizer()
        self.cfg = cfg
        self.n_exits = cfg["n_exits"]
        hold = np.ones(self.n_exits - 1, np.float32)
        self.engine = DartEngine.from_config(
            program_config(cfg), params,
            dart=DartParams(tau=jnp.asarray(hold), coef=jnp.asarray(hold),
                            beta_diff=cfg["beta_diff"]),
            mesh=make_serving_mesh(), adapt=cfg["adapt"],
            buckets=tuple(cfg["buckets"]), optimizer=OPTIMIZER)
        self.server = AsyncDartServer(
            self.engine, SchedulerConfig(max_batch=cfg["max_batch"],
                                         mode="masked"))
        self.misrouted = 0

    def reset(self, params):
        """Serve other weights of the same shapes on the same compiled
        programs, through a fresh server."""
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.serving import AsyncDartServer
        self.server.close()
        self.engine.params = jax.device_put(
            params, NamedSharding(self.engine.mesh, PartitionSpec()))
        self.server = AsyncDartServer(self.engine, self.server.cfg)

    def submit(self, x):
        return self.server.submit(x)

    def set_tau(self, tau):
        """Install base thresholds ``tau`` (coefficients 1) through the
        engine's public ``calibrate``."""
        from repro.core.policy import CalibrationData
        e = self.n_exits
        none = CalibrationData(conf=np.zeros((1, e)), correct=np.zeros((1, e)),
                               alpha=np.zeros(1), cum_costs=np.ones(e))
        self.engine.calibrate(none, tau=tau, beta_diff=self.cfg["beta_diff"])

    def _serve_all(self, images):
        """Serve ``images`` in max-batch requests; the answers in order."""
        b = self.cfg["max_batch"]
        futs = [self.submit(images[a:a + b]) for a in range(0, len(images), b)]
        res = [f.result(timeout=600) for f in futs]
        return {k: np.concatenate([r[k] for r in res])
                for k in ("exit_idx", "conf", "alpha", "pred")}

    def calibrate(self, images, shares):
        """Thresholds that give the exits ``shares`` of ``images``, from
        the masked step's own confidences: pass k installs a policy under
        which every row leaves at exit k, so the confidence it serves is
        exit k's.  ``misrouted`` counts the rows a pass served elsewhere
        (0 from a sound program)."""
        e = self.n_exits
        conf, misrouted = [], 0
        for k in range(e):
            probe = np.ones(e - 1, np.float32)
            if k < e - 1:
                probe[k] = -1.0
            self.set_tau(probe)
            out = self._serve_all(images)
            misrouted += int(np.sum(out["exit_idx"] != k))
            conf.append(out["conf"])
        tau = shares_tau(np.stack(conf), out["alpha"], shares,
                         self.cfg["beta_diff"])
        self.set_tau(tau)
        self.misrouted = misrouted
        return tau

    def warm_up(self, pool, sizes):
        """Compile every shape the window uses: admission at each request
        size, and the masked step with every consolidated batch size."""
        for n in sizes:
            self.submit(pool[:n]).result(timeout=600)
        alpha = np.full(self.cfg["max_batch"], 0.5, np.float32)
        for b in range(1, self.cfg["max_batch"] + 1):
            out = self.engine.infer(pool[:b], mode="masked", record=True,
                                    alpha=alpha[:b])
            np.asarray(out["pred"])

    def close(self):
        self.server.close()
