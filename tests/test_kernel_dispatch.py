"""kernels.dispatch — backend policy, VMEM fallback boundary, and
kernel-vs-XLA-ref parity INSIDE the compiled sharded serving steps.

The parity tests force ``pallas-interpret`` so the actual kernel bodies
run inside the jit-end-to-end engines (shard_map-wrapped over the
("data",) mesh) and compare against the eager oracle; the 8-replica run
executes in a subprocess with ``--xla_force_host_platform_device_count=8``
(same pattern as test_sharded_engine / test_lm_sharded).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.routing import DartParams
from repro.data.datasets import DatasetConfig, make_batch
from repro.engine import DartEngine, LMDecodeEngine
from repro.kernels import dispatch
from repro.kernels.exit_gate.ref import ref_exit_gate
from repro.launch.mesh import make_serving_mesh
from repro.models.cnn_zoo import AlexNetConfig
from repro.models.transformer_lm import LMConfig, lm_init
from repro.parallel.sharding import unzip
from repro.runtime.trainer import Trainer, TrainConfig


# ---------------------------------------------------------------------------
# backend selection policy
# ---------------------------------------------------------------------------

def test_auto_policy_never_interprets():
    """Interpret mode must be opt-in: auto selection is pallas on TPU
    and the XLA ref everywhere else — never the interpreter."""
    for kernel in ("exit_gate", "difficulty", "exit_head"):
        chosen = dispatch.select_backend(kernel, vmem_bytes=1024)
        expect = "pallas" if jax.default_backend() == "tpu" else "xla"
        assert chosen == expect


def test_forced_backend_scope_and_validation():
    assert dispatch.forced_backend() is None
    with dispatch.force_backend("pallas-interpret"):
        assert dispatch.forced_backend() == "pallas-interpret"
        assert dispatch.select_backend("exit_gate", vmem_bytes=0) == \
            "pallas-interpret"
        with dispatch.force_backend("xla"):
            assert dispatch.select_backend("exit_gate", vmem_bytes=0) == \
                "xla"
        assert dispatch.forced_backend() == "pallas-interpret"
    assert dispatch.forced_backend() is None
    with pytest.raises(ValueError, match="unknown backend"):
        with dispatch.force_backend("cuda"):
            pass


def test_env_backend_validation(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "xla")
    assert dispatch.forced_backend() == "xla"
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "auto")
    assert dispatch.forced_backend() is None
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "warp")
    with pytest.raises(ValueError, match="REPRO_KERNEL_BACKEND"):
        dispatch.forced_backend()


# ---------------------------------------------------------------------------
# VMEM fallback boundary
# ---------------------------------------------------------------------------

def test_vmem_boundary_select():
    """Just-under stays on the (forced) pallas backend; just-over
    degrades to the XLA ref — even under force."""
    budget = dispatch.VMEM_BUDGET_BYTES
    with dispatch.force_backend("pallas-interpret"):
        assert dispatch.select_backend("exit_gate",
                                       vmem_bytes=budget) == \
            "pallas-interpret"
        assert dispatch.select_backend("exit_gate",
                                       vmem_bytes=budget + 1) == "xla"


def test_vmem_boundary_end_to_end(monkeypatch):
    """The fused gate crosses the budget on real shapes: the kernel runs
    for a just-under row and the ref runs (bitwise) for a just-over
    row."""
    from repro.kernels.exit_gate import exit_gate_kernel as KMOD
    calls = []
    orig = KMOD.exit_gate_pallas

    def spy(*a, **kw):
        calls.append(kw.get("block_b"))
        return orig(*a, **kw)

    monkeypatch.setattr(KMOD, "exit_gate_pallas", spy)
    budget = dispatch.VMEM_BUDGET_BYTES
    # the widest f32 row whose block_b=1 footprint still fits the budget
    lo, hi = 1, budget
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if dispatch._gate_step_bytes(1, mid, 4) <= budget:
            lo = mid
        else:
            hi = mid - 1
    v_under = lo
    v_over = v_under + 1
    rng = np.random.RandomState(0)
    with dispatch.force_backend("pallas-interpret"):
        lg = jnp.asarray(rng.randn(1, v_under), jnp.float32)
        got = dispatch.exit_gate(lg, jnp.zeros(1))
        assert len(calls) == 1      # kernel traced
        want = ref_exit_gate(lg, jnp.zeros(1))
        np.testing.assert_allclose(got[0], want[0], rtol=3e-5, atol=3e-6)
        np.testing.assert_array_equal(got[2], want[2])

        lg = jnp.asarray(rng.randn(1, v_over), jnp.float32)
        got = dispatch.exit_gate(lg, jnp.zeros(1))
        assert len(calls) == 1      # fell back: no new kernel trace
        want = ref_exit_gate(lg, jnp.zeros(1))
        np.testing.assert_array_equal(got[0], want[0])   # ref bitwise
        np.testing.assert_array_equal(got[2], want[2])


# ---------------------------------------------------------------------------
# parity inside the compiled sharded steps (1-device mesh in-process)
# ---------------------------------------------------------------------------

DATA = DatasetConfig(name="synth-cifar", n_train=256, n_eval=128)
COSTS = [0.3, 0.7, 1.0]


@pytest.fixture(scope="module")
def trained_cnn():
    mc = AlexNetConfig(img_res=32, n_classes=10,
                       channels=(16, 24, 32, 24, 24), fc_dims=(96, 48))
    tr = Trainer(mc, TrainConfig(batch_size=32, steps=10, lr=3e-3), DATA)
    tr.run()
    return mc, tr.params


def _dart(tau):
    return DartParams(tau=jnp.full((2,), tau), coef=jnp.ones(2),
                      beta_diff=0.3)


def test_kernels_inside_sharded_steps_match_oracle(trained_cnn):
    """With pallas-interpret forced, the masked AND compacted compiled
    steps run the actual kernel bodies (shard_map-wrapped) — decisions
    must match the eager oracle and confidences must be allclose."""
    mc, params = trained_cnn
    x, _ = make_batch(DATA, range(24), split="eval")
    with dispatch.force_backend("pallas-interpret"):
        eng = DartEngine.from_config(mc, params, mesh=make_serving_mesh(),
                                     dart=_dart(0.2), cum_costs=COSTS)
        ref = eng.infer(x, mode="eager")
        out = eng.infer(x, mode="masked")
        np.testing.assert_array_equal(out["exit_idx"],
                                      np.asarray(ref["exit_idx"]))
        np.testing.assert_array_equal(out["pred"], np.asarray(ref["pred"]))
        np.testing.assert_allclose(out["conf"], np.asarray(ref["conf"]),
                                   rtol=3e-5, atol=3e-5)
        np.testing.assert_allclose(out["alpha"], np.asarray(ref["alpha"]),
                                   rtol=3e-5, atol=3e-5)
        com = eng.infer(x, mode="compacted")
        np.testing.assert_array_equal(com["exit_idx"], out["exit_idx"])
        np.testing.assert_array_equal(com["pred"], out["pred"])


def test_no_retrace_after_kernel_wiring(trained_cnn):
    """One trace per (step, bucket) must survive the kernel wiring —
    repeated serving in one bucket never retraces, on either backend."""
    mc, params = trained_cnn
    x, _ = make_batch(DATA, range(16), split="eval")
    for backend in (None, "pallas-interpret"):
        with dispatch.force_backend(backend):
            eng = DartEngine.from_config(mc, params,
                                         mesh=make_serving_mesh(),
                                         dart=_dart(0.2), cum_costs=COSTS)
            for n in (3, 4, 3, 4):              # one bucket
                eng.infer(x[:n], mode="masked")
            assert eng.trace_counts == {("masked", 4, True): 1}, \
                (backend, eng.trace_counts)
            eng.infer(x[:13], mode="compacted")
            eng.infer(x[:16], mode="compacted")
            for key, count in eng.trace_counts.items():
                assert count == 1, (backend, key, count)


LM_CFG = LMConfig(name="lm-dispatch-t", n_layers=4, d_model=32, n_heads=2,
                  n_kv_heads=1, d_ff=64, vocab=32, exit_layers=(0, 2),
                  max_seq=64, remat=False)


def test_fused_exit_head_inside_decode_step_matches_oracle():
    """The fused exit-head kernel inside the compiled (stage, bucket)
    decode step must reproduce the eager oracle's tokens and exit
    depths."""
    params = unzip(lm_init(jax.random.key(0), LM_CFG))[0]
    prompts = np.random.RandomState(0).randint(0, LM_CFG.vocab, (5, 7))
    dart = DartParams(tau=jnp.full((2,), 0.05), coef=jnp.ones(2),
                      beta_diff=0.1)
    eager = LMDecodeEngine(LM_CFG, params, dart)
    tok_e, stg_e = eager.generate(prompts, n_new=8)
    with dispatch.force_backend("pallas-interpret"):
        sh = LMDecodeEngine(LM_CFG, params, dart,
                            mesh=make_serving_mesh())
        tok_s, stg_s = sh.generate(prompts, n_new=8)
    np.testing.assert_array_equal(tok_s, tok_e)
    np.testing.assert_array_equal(stg_s, stg_e)
    # one trace per (stage, bucket) with the kernel in the step
    for key, count in sh.trace_counts.items():
        assert count == 1, (key, count)


# ---------------------------------------------------------------------------
# 8-replica parity (subprocess, fake devices)
# ---------------------------------------------------------------------------

MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, %r)
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.routing import DartParams
    from repro.data.datasets import DatasetConfig, make_batch
    from repro.engine import DartEngine, LMDecodeEngine
    from repro.kernels import dispatch
    from repro.launch.mesh import make_serving_mesh
    from repro.models.cnn_zoo import AlexNetConfig
    from repro.models.transformer_lm import LMConfig, lm_init
    from repro.parallel.sharding import unzip
    from repro.runtime.trainer import Trainer, TrainConfig

    DATA = DatasetConfig(name="synth-cifar", n_train=256, n_eval=128)
    mc = AlexNetConfig(img_res=32, n_classes=10,
                       channels=(16, 24, 32, 24, 24), fc_dims=(96, 48))
    tr = Trainer(mc, TrainConfig(batch_size=32, steps=8, lr=3e-3), DATA)
    tr.run()
    dart = DartParams(tau=jnp.full((2,), 0.2), coef=jnp.ones(2),
                      beta_diff=0.3)
    x, _ = make_batch(DATA, range(24), split="eval")
    with dispatch.force_backend("pallas-interpret"):
        eng = DartEngine.from_config(mc, tr.params,
                                     mesh=make_serving_mesh(), dart=dart,
                                     cum_costs=[0.3, 0.7, 1.0])
        assert eng.n_replicas == 8, eng.n_replicas
        ref = eng.infer(x, mode="eager")
        out = eng.infer(x, mode="masked")
        np.testing.assert_array_equal(out["exit_idx"],
                                      np.asarray(ref["exit_idx"]))
        np.testing.assert_array_equal(out["pred"],
                                      np.asarray(ref["pred"]))
        np.testing.assert_allclose(out["conf"], np.asarray(ref["conf"]),
                                   rtol=3e-5, atol=3e-5)
        com = eng.infer(x, mode="compacted")
        np.testing.assert_array_equal(com["exit_idx"], out["exit_idx"])
        np.testing.assert_array_equal(com["pred"], out["pred"])
        for key, count in eng.trace_counts.items():
            assert count == 1, (key, count)

        # a non-replica-divisible admission batch degrades to the xla
        # ref instead of a broken shard_map
        a3 = np.asarray(eng._alpha(jnp.asarray(x[:3])))
        np.testing.assert_allclose(
            a3, np.asarray(ref["alpha"])[:3], rtol=3e-5, atol=3e-5)

    cfg = LMConfig(name="lm-dispatch-8", n_layers=4, d_model=32,
                   n_heads=2, n_kv_heads=1, d_ff=64, vocab=32,
                   exit_layers=(0, 2), max_seq=64, remat=False)
    params = unzip(lm_init(jax.random.key(0), cfg))[0]
    prompts = np.random.RandomState(0).randint(0, cfg.vocab, (5, 7))
    ldart = DartParams(tau=jnp.full((2,), 0.05), coef=jnp.ones(2),
                       beta_diff=0.1)
    tok_e, stg_e = LMDecodeEngine(cfg, params, ldart).generate(
        prompts, n_new=6)
    with dispatch.force_backend("pallas-interpret"):
        sh = LMDecodeEngine(cfg, params, ldart, mesh=make_serving_mesh())
        tok_s, stg_s = sh.generate(prompts, n_new=6)
    np.testing.assert_array_equal(tok_s, tok_e)
    np.testing.assert_array_equal(stg_s, stg_e)
    print("DISPATCH_8DEV_OK")
""" % os.path.join(os.path.dirname(__file__), "..", "src"))


def test_kernel_parity_on_8_devices():
    """Forced-kernel parity inside the compiled sharded steps on an
    8-fake-device ("data",) mesh (subprocess)."""
    r = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DISPATCH_8DEV_OK" in r.stdout
