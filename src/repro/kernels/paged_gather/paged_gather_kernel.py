"""Pallas paged KV gather (continuous-batching decode path).

The page table is a *scalar-prefetch* operand
(``pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=1)``): grid step
``(i, j)`` copies page ``table[i, j]`` of the store into row block
``(i, j)`` of the dense per-slot view, so the data movement IS the
BlockSpec index_map — the kernel body is a straight VMEM copy and no
(S*P,)-sized gather indices ever materialize in HBM.

The store may be sharded over pages under shard_map; callers then pass
a table of *local* page ids (the continuous decoder's allocator keeps
slot s's pages inside slot s's replica range, so ``table % local_N``
is exact).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(tab_ref, pages_ref, out_ref):
    out_ref[...] = pages_ref[...]


def paged_gather_pallas(pages, page_table, *, interpret=None):
    """pages (N, psz, ...), page_table (S, P) int32 -> (S, P*psz, ...)."""
    from repro.kernels.dispatch import resolve_interpret
    s, p = page_table.shape
    psz = pages.shape[1]
    rest = pages.shape[2:]
    zeros = (0,) * len(rest)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s, p),
        in_specs=[pl.BlockSpec(
            (1, psz) + rest,
            lambda i, j, tab: (tab[i, j], 0) + zeros)],
        out_specs=pl.BlockSpec(
            (1, psz) + rest,
            lambda i, j, tab: (i, j) + zeros),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, p * psz) + rest, pages.dtype),
        interpret=resolve_interpret(interpret),
    )(page_table.astype(jnp.int32), pages)
