"""The main-path Pallas kernels compiled for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: each test
lowers and compiles a kernel for a ``v5e:2x2`` topology described in a
fixture (nothing runs).  This catches what interpret mode cannot —
block shapes off the (8, 128) tiling, scalar stores, and more scoped
VMEM than a kernel may use — at serving widths, without chip time.

The topology is described only inside the module fixture: loading the
TPU library at import time would make parallel test workers fight over
its lock.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.kernels import dispatch
from repro.kernels.difficulty.difficulty_kernel import difficulty_pallas
from repro.kernels.exit_gate.exit_gate_kernel import exit_gate_pallas
from repro.kernels.exit_head.exit_head_kernel import exit_head_gate_pallas
from repro.kernels.paged_gather.paged_gather_kernel import \
    paged_gather_pallas

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
RESNET = registry.get("resnet-152")
LLAMA = registry.get("tinyllama-1.1b")
SLOTS = 16                     # the continuous decoder's default pool


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                         # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache; keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes):
    """AOT-compile ``fn`` for the described chip; returns the HLO text
    (raises what the TPU compiler raises)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _compiles(fn, sharding, *shapes) -> bool:
    try:
        _compile(fn, sharding, *shapes)
    except Exception as e:                         # noqa: BLE001
        if "vmem" not in str(e).lower():
            raise
        return False
    return True


# ---------------------------------------------------------------------------
# serving-width compiles through the dispatch layer (backend forced to
# the compiled kernel, blocks chosen as on the chip)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,v", [(1, RESNET.n_classes),
                                 (128, RESNET.n_classes),
                                 (SLOTS, LLAMA.vocab)])
def test_exit_gate_compiles(one_chip, b, v):
    hlo = _compile(lambda lg, th: dispatch.exit_gate(lg, th,
                                                     backend="pallas"),
                   one_chip, ((b, v), BF16), ((b,), F32))
    assert "tpu_custom_call" in hlo


def test_difficulty_compiles(one_chip):
    r = RESNET.img_res
    hlo = _compile(lambda x: dispatch.difficulty_components(
        x, backend="pallas"), one_chip, ((128, r, r, 3), F32))
    assert "tpu_custom_call" in hlo


def test_exit_head_compiles(one_chip):
    d, v = LLAMA.d_model, LLAMA.vocab
    hlo = _compile(lambda h, sc, tab, th: dispatch.exit_head_gate(
        h, sc, tab, th, backend="pallas"), one_chip,
        ((SLOTS, d), BF16), ((d,), BF16), ((v, d), BF16), ((SLOTS,), F32))
    assert "tpu_custom_call" in hlo


def test_paged_gather_compiles(one_chip):
    # the continuous decoder's GQA page store at max_seq, page_size 8
    psz = 8
    per_slot = LLAMA.max_seq // psz
    pages = ((SLOTS * per_slot, psz, LLAMA.n_kv_heads, LLAMA.hd), BF16)
    hlo = _compile(lambda pg, tab: dispatch.paged_gather(
        pg, tab, backend="pallas"), one_chip, pages,
        ((SLOTS, per_slot), I32))
    assert "tpu_custom_call" in hlo


# ---------------------------------------------------------------------------
# the dispatch VMEM estimate never accepts a shape the compiler refuses
# ---------------------------------------------------------------------------

def _gate_case(b, v, block_b, dt):
    return (dispatch._gate_step_bytes(block_b, v, jnp.dtype(dt).itemsize),
            functools.partial(exit_gate_pallas, block_b=block_b,
                              interpret=False),
            [((b, v), dt), ((b,), F32)])


def _difficulty_case(b, h, w, c, dt):
    return (dispatch._difficulty_step_bytes(h, w, c, jnp.dtype(dt).itemsize),
            functools.partial(difficulty_pallas, interpret=False),
            [((b, h, w, c), dt)])


def _head_case(b, d, v, block_v, dt):
    return (dispatch._head_step_bytes(block_v, d, b, jnp.dtype(dt).itemsize),
            functools.partial(exit_head_gate_pallas, block_v=block_v,
                              interpret=False),
            [((b, d), dt), ((d,), dt), ((v, d), dt), ((b,), F32)])


def _paged_case(page, dt):
    return (dispatch._paged_step_bytes(page, jnp.dtype(dt).itemsize),
            functools.partial(paged_gather_pallas, interpret=False),
            [((64,) + page, dt), ((8, 8), I32)])


#: (case, whether the v5e compiler refuses it); every kernel's sweep
#: holds at least one refusal so the agreement is tested from both sides
VMEM_SWEEPS = {
    "exit_gate": [
        (_gate_case(8, 129280, 8, F32), False),
        (_gate_case(24, 100000, 8, F32), False),
        (_gate_case(16, 32000, 8, BF16), False),
        (_gate_case(16, 129280, 16, BF16), True),
    ],
    "difficulty": [
        (_difficulty_case(2, 224, 224, 3, BF16), False),
        (_difficulty_case(2, 384, 384, 3, F32), False),
        (_difficulty_case(2, 512, 512, 3, F32), False),
        (_difficulty_case(2, 640, 640, 3, F32), True),
    ],
    "exit_head": [
        (_head_case(1, 2048, 32000, 1280, BF16), False),
        (_head_case(128, 2048, 32000, 640, BF16), False),
        (_head_case(8, 7168, 129280, 128, BF16), False),
        (_head_case(8, 7168, 129280, 1280, BF16), True),
    ],
    "paged_gather": [
        (_paged_case((16, 4, 64), BF16), False),
        (_paged_case((16, 1, 576), BF16), False),
        (_paged_case((2048, 8, 128), F32), True),
    ],
}


@pytest.mark.parametrize("kernel", sorted(VMEM_SWEEPS))
def test_vmem_estimate_agrees_with_compiler(one_chip, kernel):
    for (est, fn, shapes), refused in VMEM_SWEEPS[kernel]:
        ok = _compiles(fn, one_chip, *shapes)
        assert ok != refused, (kernel, shapes, "compiler verdict moved")
        if est <= dispatch.VMEM_BUDGET_BYTES:
            assert ok, (kernel, shapes, est, "estimate accepts a shape "
                        "the compiler refuses")
        if refused:
            assert est > dispatch.VMEM_BUDGET_BYTES, (kernel, shapes, est)
