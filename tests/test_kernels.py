"""Per-kernel allclose sweeps (shapes × dtypes) against the ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch
from repro.kernels.difficulty.difficulty_kernel import difficulty_pallas
from repro.kernels.difficulty.ref import ref_components
from repro.kernels.difficulty import ops as dops
from repro.kernels.exit_gate.exit_gate_kernel import exit_gate_pallas
from repro.kernels.exit_gate.ref import ref_exit_gate
from repro.kernels.exit_gate import ops as gops
from repro.kernels.exit_head.exit_head_kernel import exit_head_gate_pallas
from repro.kernels.exit_head.ref import ref_exit_head_gate
from repro.core.difficulty import DifficultyConfig


DIFF_SHAPES = [(1, 28, 28, 1), (4, 32, 32, 3), (2, 64, 64, 3),
               (3, 48, 80, 3), (2, 224, 224, 3), (1, 128, 128, 4)]


@pytest.mark.parametrize("shape", DIFF_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_difficulty_kernel_matches_ref(shape, dtype):
    img = jax.random.uniform(jax.random.key(hash(shape) % 1000),
                             shape).astype(dtype)
    got = difficulty_pallas(img)
    want = ref_components(img)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("params", [
    dict(tau_edge=0.05, var_scale=0.1, grad_scale=0.1, w1=0.5, w2=0.25,
         w3=0.25),
    dict(tau_edge=0.3, var_scale=0.02, grad_scale=0.5, w1=0.2, w2=0.4,
         w3=0.4),
])
def test_difficulty_kernel_param_sweep(params):
    img = jax.random.uniform(jax.random.key(7), (3, 40, 40, 3))
    got = difficulty_pallas(img, **params)
    want = ref_components(img, **params)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_difficulty_ops_dispatch_and_fallback():
    cfg = DifficultyConfig()
    small = jax.random.uniform(jax.random.key(0), (2, 32, 32, 3))
    # auto on CPU: the xla ref chain
    np.testing.assert_allclose(dops.components(small, cfg),
                               ref_components(small), rtol=2e-5, atol=2e-6)
    with dispatch.force_backend("pallas-interpret"):
        # forced kernel path matches the ref
        np.testing.assert_allclose(dops.components(small, cfg),
                                   ref_components(small), rtol=2e-5,
                                   atol=2e-6)
        # oversized image falls back to the jnp ref
        big = jax.random.uniform(jax.random.key(1), (1, 2048, 1024, 3))
        np.testing.assert_allclose(dops.components(big, cfg),
                                   ref_components(big), rtol=2e-5,
                                   atol=2e-6)


GATE_SHAPES = [(1, 2), (8, 10), (4, 1000), (2, 32000), (1, 129280),
               (16, 49155)]


@pytest.mark.parametrize("shape", GATE_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_exit_gate_matches_ref(shape, dtype):
    b, v = shape
    lg = (jax.random.normal(jax.random.key(v), (b, v)) * 4).astype(dtype)
    th = jax.random.uniform(jax.random.key(v + 1), (b,))
    got = exit_gate_pallas(lg, th)
    want = ref_exit_gate(lg, th)
    np.testing.assert_allclose(got[0], want[0], rtol=3e-5, atol=3e-6)
    np.testing.assert_allclose(got[1], want[1], rtol=3e-4, atol=3e-5)
    np.testing.assert_array_equal(got[2], want[2])
    np.testing.assert_array_equal(got[3], want[3])


def test_exit_gate_tie_breaking():
    """argmax must pick the FIRST maximal index, like jnp.argmax."""
    lg = jnp.zeros((2, 64)).at[0, 5].set(3.0).at[0, 9].set(3.0) \
        .at[1, 0].set(1.0)
    got = exit_gate_pallas(lg, jnp.zeros(2))
    want = ref_exit_gate(lg, jnp.zeros(2))
    np.testing.assert_array_equal(got[2], want[2])
    assert int(got[2][0]) == 5


def test_exit_gate_threshold_edge():
    """fire must be a STRICT > comparison (Alg. 1 line 8)."""
    lg = jnp.log(jnp.array([[0.7, 0.2, 0.1]]))
    conf = ref_exit_gate(lg, jnp.zeros(1))[0]
    got_eq = exit_gate_pallas(lg, conf)         # τ == conf -> no fire
    assert int(got_eq[3][0]) == 0
    got_lt = exit_gate_pallas(lg, conf - 1e-3)
    assert int(got_lt[3][0]) == 1


def test_softmax_confidence_nd():
    lg = jax.random.normal(jax.random.key(3), (5, 7, 33))
    ref_conf = jnp.max(jax.nn.softmax(lg, axis=-1), axis=-1)
    for backend in (None, "pallas-interpret"):
        conf, pred = gops.softmax_confidence(lg, backend=backend)
        np.testing.assert_allclose(conf, ref_conf, rtol=2e-5, atol=2e-6)
        np.testing.assert_array_equal(pred, jnp.argmax(lg, axis=-1))


@pytest.mark.parametrize("block_b", [1, 2, 4])
def test_exit_gate_blocked_rows_match(block_b):
    """The autotuned rows-per-grid-step variant must match block_b=1."""
    lg = jax.random.normal(jax.random.key(9), (8, 50)) * 3
    th = jax.random.uniform(jax.random.key(10), (8,))
    got = exit_gate_pallas(lg, th, block_b=block_b)
    want = ref_exit_gate(lg, th)
    np.testing.assert_allclose(got[0], want[0], rtol=3e-5, atol=3e-6)
    np.testing.assert_array_equal(got[2], want[2])
    np.testing.assert_array_equal(got[3], want[3])


def test_exit_gate_blocked_requires_divisor():
    lg = jnp.zeros((6, 8))
    with pytest.raises(ValueError, match="does not divide"):
        exit_gate_pallas(lg, jnp.zeros(6), block_b=4)


# ---------------------------------------------------------------------------
# fused LM exit head (rmsnorm -> unembed -> conf -> Eq. 19 gate)
# ---------------------------------------------------------------------------

HEAD_SHAPES = [(1, 8, 16, None), (4, 32, 64, 16), (2, 16, 100, 25),
               (3, 24, 96, 96), (5, 64, 1000, 250)]


@pytest.mark.parametrize("shape", HEAD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_exit_head_matches_ref(shape, dtype):
    b, d, v, block_v = shape
    key = jax.random.key(b * 1000 + v)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    h = (jax.random.normal(k1, (b, d)) * 2).astype(dtype)
    scale = (1.0 + 0.1 * jax.random.normal(k2, (d,))).astype(dtype)
    tab = jax.random.normal(k3, (v, d)).astype(dtype)
    th = jax.random.uniform(k4, (b,))
    got = exit_head_gate_pallas(h, scale, tab, th, block_v=block_v)
    want = ref_exit_head_gate(h, scale, tab, th)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got[0], want[0], rtol=tol, atol=tol)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])


def test_exit_head_tie_and_threshold_edge():
    """Cross-block argmax ties resolve to the FIRST index; the gate is
    a strict > compare of the kernel's own confidence.  (The online
    softmax adds the two blocks' sums in a different order than the
    one-pass ref, so the two confidences agree to a few ulp, not
    bitwise: the boundary is the kernel's value.)"""
    d, v = 8, 32
    h = jnp.ones((1, d))
    scale = jnp.ones((d,))
    # two identical unembed rows (5 and 21) in different vocab blocks
    tab = jnp.zeros((v, d)).at[5].set(0.3).at[21].set(0.3)
    got = exit_head_gate_pallas(h, scale, tab, jnp.zeros(1), block_v=16)
    want = ref_exit_head_gate(h, scale, tab, jnp.zeros(1))
    assert int(got[1][0]) == int(want[1][0]) == 5
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6, atol=0)
    conf = got[0]
    eq = exit_head_gate_pallas(h, scale, tab, conf, block_v=16)
    assert int(eq[2][0]) == 0                # tau == conf -> no fire
    lt = exit_head_gate_pallas(h, scale, tab, conf - 1e-3, block_v=16)
    assert int(lt[2][0]) == 1


def test_exit_head_block_v_divides_and_fits():
    budget = dispatch.VMEM_BUDGET_BYTES
    for v, d in [(32, 16), (32000, 4096), (129280, 7168), (997, 64)]:
        bv = dispatch.exit_head_block_v(v, d)
        assert v % bv == 0
        assert dispatch._head_step_bytes(bv, d) <= budget
