"""ONE backend-selection layer for every fused kernel on the serving path.

Before this module each call site carried its own ``use_kernel`` flag, a
hardcoded ``interpret=True`` default and a private ``VMEM_BUDGET_BYTES``
check; the compiled serving engines simply refused to call the kernels
("pallas_call does not partition under GSPMD").  Dispatch centralizes
all of that:

* **Backend selection at trace time.**  ``select_backend(kernel,
  vmem_bytes=...)`` picks one of

  - ``"pallas"``            — the compiled Mosaic kernel (TPU),
  - ``"pallas-interpret"``  — the kernel body on the host interpreter
    (tests / debugging; NEVER auto-selected for production traffic),
  - ``"xla"``               — the pure-jnp reference chain (CPU/GPU, and
    the fallback whenever a block would not fit the VMEM budget).

  Auto policy: ``pallas`` on TPU, ``xla`` everywhere else.  Force a
  backend globally with ``REPRO_KERNEL_BACKEND=<name>``, per scope with
  ``with dispatch.force_backend("pallas-interpret"): ...``, or per call
  with ``backend=``.  Shapes are static under ``jax.jit``, so selection
  happens exactly once per compiled program — the hot path never
  branches at run time.

* **VMEM-budget fallback.**  Each kernel declares its per-grid-step VMEM
  footprint; a pallas backend whose footprint exceeds
  ``VMEM_BUDGET_BYTES`` silently degrades to ``"xla"`` (XLA tiles those
  shapes itself).  This is the one place that check lives.

* **shard_map wrapping.**  ``pallas_call`` does not partition under
  GSPMD, which is why the jit-end-to-end engines historically computed
  the gate as unfused XLA ops.  Every public op here takes ``mesh=`` /
  ``axis=``: when a pallas backend is selected inside a sharded step,
  the call is wrapped in ``shard_map`` over the (batch-sharded) axis so
  each replica runs the kernel on its local rows — zero cross-replica
  traffic, one launch per (stage, replica).  The ``xla`` backend needs
  no wrapping (GSPMD partitions the reference chain).

* **Autotuned block sizes.**  ``_AUTOTUNE`` is a small shape-keyed
  table: rows-per-grid-step for the exit gate (amortizes grid overhead
  on small vocabularies) and the vocab block for the fused exit head
  (bounded by the VMEM budget, always a divisor of V).

Public fused ops (each returns exactly what its ``ref`` computes, and
the ``xla`` backend IS the ref — bit-identical to the eager oracle):

    exit_gate(logits, thresholds)           (conf, entropy, pred, fire)
    softmax_confidence(logits)              (conf, pred) over (..., V)
    difficulty_components(images, cfg)      (B, 4) Eq. 1-8 statistics
    image_difficulty(images, cfg)           (B,) fused Eq. 8 alpha
    exit_head_gate(h, scale, table, thr)    (conf, pred, fire) --
        rmsnorm -> unembed matmul -> softmax conf -> Eq. 19 gate,
        without materializing the (B, V) logits in HBM.
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

BACKENDS = ("pallas", "pallas-interpret", "xla")

#: one VMEM budget for every kernel (per grid step, bytes)
VMEM_BUDGET_BYTES = 12 * 1024 * 1024

_FORCED = threading.local()


def _env_backend() -> str | None:
    b = os.environ.get("REPRO_KERNEL_BACKEND", "").strip().lower()
    if b in ("", "auto"):
        return None
    if b not in BACKENDS:
        raise ValueError(
            f"REPRO_KERNEL_BACKEND={b!r} not in {BACKENDS + ('auto',)}")
    return b


def forced_backend() -> str | None:
    """The currently forced backend (scope override > env var), or None
    for auto selection."""
    return getattr(_FORCED, "backend", None) or _env_backend()


@contextlib.contextmanager
def force_backend(backend: str | None):
    """Force every dispatch in this scope onto ``backend`` (one of
    ``BACKENDS``, or None to restore auto).  The VMEM-budget fallback
    still applies — an over-budget shape degrades to ``"xla"`` even
    under force, which is what makes the fallback boundary testable."""
    if backend is not None and backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; known: {BACKENDS}")
    prev = getattr(_FORCED, "backend", None)
    _FORCED.backend = backend
    try:
        yield
    finally:
        _FORCED.backend = prev


def select_backend(kernel: str, *, vmem_bytes: int = 0,
                   backend: str | None = None,
                   platform: str | None = None) -> str:
    """Resolve the backend for one ``kernel`` call at trace time.

    ``vmem_bytes``: the kernel's per-grid-step VMEM footprint for the
    shapes about to run.  ``backend``: per-call override (else the
    forced scope/env backend, else auto by platform)."""
    b = backend or forced_backend()
    if b is None:
        platform = platform or jax.default_backend()
        b = "pallas" if platform == "tpu" else "xla"
    if b != "xla" and vmem_bytes > VMEM_BUDGET_BYTES:
        return "xla"                      # XLA tiles over-budget shapes
    return b


def _interpret(backend: str) -> bool:
    return backend == "pallas-interpret"


def resolve_interpret(interpret) -> bool:
    """Raw-kernel default: ``None`` means interpret mode everywhere but
    TPU, so a directly-called kernel stays runnable in tests on CPU.
    Dispatch itself always passes an explicit value."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


# ---------------------------------------------------------------------------
# shape-keyed autotune table (block sizes per kernel)
# ---------------------------------------------------------------------------

#: (kernel, shape class) -> block parameters.  Extend with measured
#: entries when tuning on real hardware; unlisted shapes take the
#: class defaults below.
_AUTOTUNE: dict[tuple[str, str], dict] = {
    # small vocabularies: a whole serving bucket per grid step
    ("exit_gate", "v<=2048"): {"block_b": 128},
    # LM vocabularies: one 8-row sublane tile per step
    ("exit_gate", "v>2048"): {"block_b": 8},
    # one image per grid step (the image IS the block)
    ("difficulty", "default"): {},
    # fused exit head: vocab block target (shrunk to the VMEM budget
    # and to a lane-aligned divisor of V by exit_head_block_v)
    ("exit_head", "default"): {"block_v": 2048},
    # paged KV gather: one page per grid step (the page IS the block)
    ("paged_gather", "default"): {},
}

#: TPU tiling: a block's last dim is a multiple of 128 lanes (or the
#: whole array dim) and its second-to-last a multiple of 8 sublanes.
_LANES = 128
_SUBLANES = 8


def gate_block_b(b: int, v: int) -> int:
    """Rows per grid step for the exit gate.  The whole batch when it
    is at most the autotune target or not a multiple of 8 (a block
    equal to the array is always legal); otherwise the largest multiple
    of 8 that is <= the target and divides the batch."""
    key = ("exit_gate", "v<=2048" if v <= 2048 else "v>2048")
    target = _AUTOTUNE[key]["block_b"]
    if b <= target or b % _SUBLANES:
        return b
    block = target - target % _SUBLANES
    while b % block:
        block -= _SUBLANES
    return block


def exit_head_block_v(v: int, d: int, b: int = 8, itemsize: int = 4,
                      budget: int = VMEM_BUDGET_BYTES) -> int:
    """Vocab block for the fused exit head: the largest lane-aligned
    divisor of ``v`` that is <= the autotune target AND fits the VMEM
    budget for ``b`` rows and an ``itemsize``-byte table.  A vocabulary
    with no lane-aligned divisor takes one whole-vocab block.  When no
    candidate fits, the smallest is returned and the budget check in
    ``exit_head_gate`` routes the call to the XLA reference."""
    target = _AUTOTUNE[("exit_head", "default")]["block_v"]
    aligned = [k for k in range(_LANES, min(v, target) + 1, _LANES)
               if v % k == 0]
    if not aligned:
        return v
    fits = [k for k in aligned
            if _head_step_bytes(k, d, b, itemsize) <= budget]
    return max(fits) if fits else aligned[0]


# ---------------------------------------------------------------------------
# per-grid-step VMEM footprints: what the TPU compiler allocates, tile
# padding, double-buffered operands and fp32 temporaries included (fitted
# to v5e compiles; tests/test_tpu_compile.py holds them to the compiler)
# ---------------------------------------------------------------------------

#: fixed per-kernel allowance (semaphores, small scratch, masks)
_VMEM_SLACK = 256 * 1024


def _vmem_bytes(shape, itemsize: int) -> int:
    """Bytes one VMEM buffer of ``shape`` occupies once tiled: the last
    dim padded to 128 lanes, the second-to-last to the dtype's sublane
    tile (8 rows of 32-bit words, so 16 for bf16)."""
    shape = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    sub = _SUBLANES * max(1, 4 // itemsize)
    lead = 1
    for n in shape[:-2]:
        lead *= n
    return (lead * -(-shape[-2] // sub) * sub
            * -(-shape[-1] // _LANES) * _LANES * itemsize)


def _gate_step_bytes(block_b: int, v: int, itemsize: int = 4) -> int:
    # double-buffered logits block + its fp32 working copy
    return (2 * _vmem_bytes((block_b, v), itemsize)
            + _vmem_bytes((block_b, v), 4) + _VMEM_SLACK)


def _difficulty_step_bytes(h: int, w: int, c: int,
                           itemsize: int = 4) -> int:
    # double-buffered (C, H, W) image block + fp32 planes: the image,
    # gray, and the stencil/Sobel temporaries
    return (2 * _vmem_bytes((c, h, w), itemsize)
            + (c + 6) * _vmem_bytes((h, w), 4) + _VMEM_SLACK)


def _head_step_bytes(block_v: int, d: int, b: int = 8,
                     itemsize: int = 4) -> int:
    # double-buffered unembed block and hidden rows, fp32 normalized
    # rows, and fp32 (B, block_v) logits / exp / iota temporaries
    return (2 * _vmem_bytes((block_v, d), itemsize)
            + 2 * _vmem_bytes((b, d), itemsize)
            + 2 * _vmem_bytes((b, d), 4)
            + 3 * _vmem_bytes((b, block_v), 4) + _VMEM_SLACK)


def _paged_step_bytes(page_shape, itemsize: int = 4) -> int:
    # one page in + one page out, each double-buffered
    return 4 * _vmem_bytes(page_shape, itemsize) + _VMEM_SLACK


# ---------------------------------------------------------------------------
# shard_map wrapping (pallas backends only; xla partitions under GSPMD)
# ---------------------------------------------------------------------------

def _maybe_shard_map(fn, mesh, axis, in_specs, out_specs):
    if mesh is None:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _axis_size(mesh, axis) -> int:
    return 1 if mesh is None else int(mesh.shape[axis])


# ---------------------------------------------------------------------------
# fused exit gate (Alg. 1 lines 5-9): conf / entropy / pred / fire
# ---------------------------------------------------------------------------
#
# Each public op resolves its backend OUTSIDE the jitted implementation
# and passes it as a static argument: `force_backend(...)` is trace-time
# Python state, so baking it into the jit cache key is what keeps a
# forced re-dispatch from silently reusing a compilation made under a
# different backend.

#: (kernel, chosen-backend) -> times that decision was taken.  Trace-time
#: bookkeeping like ``trace_counts`` — `_resolve` runs at the Python call
#: level (outside jit), so counting here adds nothing to compiled steps.
#: An unexpected "xla" fallback count for a pallas-preferred kernel is
#: the alertable signal the obs registry exports.
DISPATCH_COUNTS: dict = {}


def dispatch_counts() -> dict:
    """Snapshot of backend-resolution decisions since the last reset."""
    return dict(DISPATCH_COUNTS)


def reset_dispatch_counts() -> None:
    DISPATCH_COUNTS.clear()


def _resolve(kernel: str, vmem_bytes: int, backend, mesh, axis,
             sharded_rows: int) -> str:
    chosen = select_backend(kernel, backend=backend,
                            vmem_bytes=vmem_bytes)
    # shard_map needs the sharded dim divisible by the axis; odd row
    # counts (e.g. per-request admission batches) take the xla ref,
    # which partitions under plain GSPMD.
    if chosen != "xla" and mesh is not None \
            and sharded_rows % _axis_size(mesh, axis):
        chosen = "xla"
    key = (kernel, chosen)
    DISPATCH_COUNTS[key] = DISPATCH_COUNTS.get(key, 0) + 1
    return chosen


@functools.partial(jax.jit,
                   static_argnames=("backend", "block_b", "mesh", "axis"))
def _exit_gate_impl(logits, thresholds, *, backend, block_b, mesh, axis):
    from repro.kernels.exit_gate import ref
    if backend == "xla":
        return ref.ref_exit_gate(logits, thresholds)
    from repro.kernels.exit_gate.exit_gate_kernel import exit_gate_pallas

    def local(lg, th):
        return exit_gate_pallas(lg, th, block_b=block_b,
                                interpret=_interpret(backend))

    wrapped = _maybe_shard_map(local, mesh, axis,
                               in_specs=(P(axis), P(axis)),
                               out_specs=(P(axis),) * 4)
    return wrapped(logits, thresholds)


def exit_gate(logits, thresholds, *, mesh=None, axis: str = "data",
              backend: str | None = None):
    """Fused (conf, entropy, pred, fire).  logits (B, V), thresholds
    (B,) — the Eq. 19 difficulty-adapted per-sample thresholds.

    Inside a sharded step pass ``mesh=``/``axis=``: a pallas backend is
    then shard_map-wrapped so each replica gates its local rows."""
    b, v = logits.shape
    block_b = gate_block_b(max(b // _axis_size(mesh, axis), 1), v)
    chosen = _resolve("exit_gate",
                      _gate_step_bytes(block_b, v, logits.dtype.itemsize),
                      backend, mesh, axis, b)
    return _exit_gate_impl(logits, thresholds, backend=chosen,
                           block_b=block_b, mesh=mesh, axis=axis)


def softmax_confidence(logits, *, mesh=None, axis: str = "data",
                       backend: str | None = None):
    """(conf, pred) over (..., V) — the gate without a threshold.
    Leading dims are flattened into the kernel grid; dim 0 is the
    sharded one when ``mesh`` is given."""
    shape = logits.shape
    flat_b = 1
    for s in shape[:-1]:
        flat_b *= s
    block_b = gate_block_b(max(flat_b // _axis_size(mesh, axis), 1),
                           shape[-1])
    chosen = _resolve("exit_gate",
                      _gate_step_bytes(block_b, shape[-1],
                                       logits.dtype.itemsize),
                      backend, mesh, axis, shape[0])
    if mesh is not None and logits.ndim != 2:
        chosen = "xla"          # only 2-D rows shard_map cleanly here
    if chosen == "xla":
        lf = logits.astype(jnp.float32)
        conf = jnp.max(jax.nn.softmax(lf, axis=-1), axis=-1)
        return conf, jnp.argmax(logits, axis=-1).astype(jnp.int32)
    flat = logits.reshape(-1, shape[-1])
    conf, _, pred, _ = _exit_gate_impl(
        flat, jnp.ones((flat.shape[0],), jnp.float32), backend=chosen,
        block_b=block_b, mesh=mesh, axis=axis)
    return conf.reshape(shape[:-1]), pred.reshape(shape[:-1])


# ---------------------------------------------------------------------------
# fused difficulty estimator (Eqs. 1-8)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("cfg", "backend", "mesh", "axis"))
def _difficulty_impl(images, *, cfg, backend, mesh, axis):
    from repro.kernels.difficulty import ref
    kw = dict(tau_edge=cfg.tau_edge, var_scale=cfg.var_scale,
              grad_scale=cfg.grad_scale, w1=cfg.w_edge, w2=cfg.w_variance,
              w3=cfg.w_gradient)
    if backend == "xla":
        return ref.ref_components(images, **kw)
    from repro.kernels.difficulty.difficulty_kernel import difficulty_pallas

    def local(img):
        return difficulty_pallas(img, interpret=_interpret(backend), **kw)

    wrapped = _maybe_shard_map(local, mesh, axis, in_specs=(P(axis),),
                               out_specs=P(axis))
    return wrapped(images)


def difficulty_components(images, cfg=None, *, mesh=None,
                          axis: str = "data",
                          backend: str | None = None):
    """(B, H, W, C) -> (B, 4): alpha_edge, alpha_var, alpha_grad, alpha
    (Eq. 8), one HBM read of the image per sample on pallas backends."""
    from repro.core.difficulty import DEFAULT
    cfg = DEFAULT if cfg is None else cfg
    b, h, w, c = images.shape
    chosen = _resolve("difficulty",
                      _difficulty_step_bytes(h, w, c,
                                             images.dtype.itemsize),
                      backend, mesh, axis, b)
    return _difficulty_impl(images, cfg=cfg, backend=chosen, mesh=mesh,
                            axis=axis)


def image_difficulty(images, cfg=None, *, mesh=None, axis: str = "data",
                     backend: str | None = None):
    """Fused Eq. 8 alpha — drop-in for ``core.difficulty.
    image_difficulty`` (the admission planner and every serving engine
    route through here)."""
    return difficulty_components(images, cfg, mesh=mesh, axis=axis,
                                 backend=backend)[:, 3]


# ---------------------------------------------------------------------------
# fused LM exit head (decode-time): rmsnorm -> unembed -> conf -> gate
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("eps", "backend", "block_v",
                                             "mesh", "axis"))
def _exit_head_impl(h, scale, table, thresholds, *, eps, backend, block_v,
                    mesh, axis):
    from repro.kernels.exit_head import ref
    if backend == "xla":
        return ref.ref_exit_head_gate(h, scale, table, thresholds, eps=eps)
    from repro.kernels.exit_head.exit_head_kernel import exit_head_gate_pallas

    def local(hh, sc, tab, th):
        return exit_head_gate_pallas(hh, sc, tab, th, eps=eps,
                                     block_v=block_v,
                                     interpret=_interpret(backend))

    wrapped = _maybe_shard_map(
        local, mesh, axis,
        in_specs=(P(axis), P(), P(), P(axis)),
        out_specs=(P(axis),) * 3)
    return wrapped(h, scale, table, thresholds)


# ---------------------------------------------------------------------------
# paged KV gather (continuous-batching decode): page store -> dense view
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("backend", "mesh", "axis"))
def _paged_gather_impl(pages, page_table, *, backend, mesh, axis):
    from repro.kernels.paged_gather import ref
    if backend == "xla":
        return ref.ref_paged_gather(pages, page_table)
    from repro.kernels.paged_gather.paged_gather_kernel import \
        paged_gather_pallas

    def local(pg, tab):
        # the continuous decoder allocates slot s's pages inside slot
        # s's replica range, so global ids map to local shard rows by a
        # plain modulo
        return paged_gather_pallas(pg, tab % pg.shape[0],
                                   interpret=_interpret(backend))

    wrapped = _maybe_shard_map(local, mesh, axis,
                               in_specs=(P(axis), P(axis)),
                               out_specs=P(axis))
    return wrapped(pages, page_table)


def paged_gather(pages, page_table, *, mesh=None, axis: str = "data",
                 backend: str | None = None):
    """Dense per-slot view of a paged KV store.

    pages (N, psz, ...) — the shared page store; page_table (S, P) int32
    — slot i's pages in order.  Returns (S, P*psz, ...), bit-identical
    to a contiguous (S, P*psz, ...) cache holding the same rows.  Inside
    a sharded step pass ``mesh=``/``axis=``: pages shard over the page
    axis, slots over the table axis (the decoder's range-partitioned
    allocator keeps each slot's pages on its own replica)."""
    chosen = _resolve("paged_gather",
                      _paged_step_bytes(pages.shape[1:],
                                        pages.dtype.itemsize),
                      backend, mesh, axis, page_table.shape[0])
    if chosen != "xla" and mesh is not None \
            and pages.shape[0] % _axis_size(mesh, axis):
        chosen = "xla"            # page store must divide over replicas
    return _paged_gather_impl(pages, page_table, backend=chosen,
                              mesh=mesh, axis=axis)


def exit_head_gate(h, scale, table, thresholds, *, eps: float = 1e-6,
                   mesh=None, axis: str = "data",
                   backend: str | None = None):
    """Fused decode-time exit head for the ``lm-token`` functional.

    h (B, D) hidden rows, scale (D,) rmsnorm weight, table (V, D)
    unembed, thresholds (B,) Eq. 19 per-token thresholds.  Returns
    (conf (B,) f32, pred (B,) i32, fire (B,) i32) WITHOUT materializing
    the (B, V) logits in HBM (online softmax over vocab blocks)."""
    b, d = h.shape
    v = table.shape[0]
    rows = max(b // _axis_size(mesh, axis), 1)
    isz = table.dtype.itemsize
    block_v = exit_head_block_v(v, d, rows, isz)
    chosen = _resolve("exit_head", _head_step_bytes(block_v, d, rows, isz),
                      backend, mesh, axis, b)
    return _exit_head_impl(h, scale, table, thresholds, eps=eps,
                           backend=chosen, block_v=block_v, mesh=mesh,
                           axis=axis)
