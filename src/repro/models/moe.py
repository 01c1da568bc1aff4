"""Mixture-of-Experts FFN with expert-parallel (EP) / tensor-parallel (TP)
execution under ``shard_map``.

Two physical layouts, chosen by divisibility of the expert count by the
``model`` mesh axis:

* ``ep``  — experts stacked over the model axis (DeepSeek-V3: 256 experts /
  16 shards = 16 local experts).  Each shard computes only its local
  experts; outputs are combined with a ``psum`` over the model axis (the
  all-reduce realization of the EP combine — an all-to-all variant is a
  recorded §Perf candidate).
* ``tp``  — expert count not divisible (Granite: 40 experts on 16 shards);
  every shard holds all experts but only ``d_ff/model`` of each hidden dim
  (Megatron-style TP inside the expert).  Same ``psum`` combine.

Dispatch is capacity-based sort+scatter (Switch/GShard "dropping"
semantics): exact static shapes, exact matmul FLOPs in ``cost_analysis``
(no one-hot dispatch einsum, no ragged_dot FLOPs inflation — both were
measured and rejected; see DESIGN.md).

NOTE on sorts: this environment's jaxlib cannot differentiate through
``sort``/``gather-with-batching-dims``; all integer routing tensors are
wrapped in ``stop_gradient`` (they carry no useful gradient anyway — the
router gradient flows through the top-k *probabilities*, which multiply
the combined expert outputs, exactly as in Switch/DeepSeek).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import Param
from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int                      # per-expert hidden dim
    n_shared: int = 0              # shared (always-on) experts
    capacity_factor: float = 1.25
    router_norm_topk: bool = True  # renormalize top-k probs (DeepSeek style)
    aux_loss_weight: float = 0.01


def moe_init(key, d_model, cfg: MoEConfig, dtype, *, ep_mode: str = "ep"):
    """ep_mode: "ep" stacks experts on the model axis; "tp" shards the
    per-expert hidden dim instead (for E not divisible by the mesh)."""
    e, f = cfg.n_experts, cfg.d_ff
    if ep_mode == "ep":
        gate_axes = ("experts", "embed", None)
        down_axes = ("experts", None, "embed")
    else:
        gate_axes = (None, "embed", "moe_mlp")
        down_axes = (None, "moe_mlp", "embed")
    p = {
        "router": Param(L.trunc_normal(L.rng(key, "router"),
                                       (d_model, e), jnp.float32, std=0.02),
                        ("embed", None)),
        "w_gate": Param(L.trunc_normal(L.rng(key, "w_gate"),
                                       (e, d_model, f), dtype), gate_axes),
        "w_up": Param(L.trunc_normal(L.rng(key, "w_up"),
                                     (e, d_model, f), dtype), gate_axes),
        "w_down": Param(L.trunc_normal(L.rng(key, "w_down"),
                                       (e, f, d_model), dtype), down_axes),
    }
    if cfg.n_shared:
        p["shared"] = L.swiglu_init(L.rng(key, "shared"), d_model,
                                    cfg.n_shared * f, dtype)
    return p


def _route(x, router_w, cfg: MoEConfig):
    """Router in fp32.  Returns (probs_topk, ids_topk, aux_loss)."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # (T, E)
    p_top, ids = lax.top_k(probs, cfg.top_k)                 # (T, k)
    if cfg.router_norm_topk:
        p_top = p_top / jnp.sum(p_top, axis=-1, keepdims=True)
    # Switch load-balance loss: E * sum_e f_e * P_e
    e = cfg.n_experts
    f_e = jnp.mean(
        jnp.sum(jax.nn.one_hot(lax.stop_gradient(ids), e, dtype=jnp.float32),
                axis=1), axis=0)                             # (E,)
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e) * cfg.aux_loss_weight
    return p_top, lax.stop_gradient(ids), aux


def _expert_compute_local(x, p_top, ids, w_gate, w_up, w_down,
                          cfg: MoEConfig, first_expert: int):
    """Capacity-based dispatch to the local expert slice, differentiable.

    x: (T, D) local tokens; ids/p_top: (T, k); w_*: (E_loc, D, F_loc)...
    Returns partial output (T, D) — sum of local experts' contributions.
    """
    t, d = x.shape
    k = cfg.top_k
    e_loc = w_gate.shape[0]
    capacity = max(8, int(math.ceil(t * k / cfg.n_experts
                                    * cfg.capacity_factor / 8.0)) * 8)
    capacity = min(capacity, t)

    flat_ids = ids.reshape(-1)                               # (T*k,)
    flat_probs = p_top.reshape(-1)
    tok_ids = lax.stop_gradient(
        jnp.repeat(jnp.arange(t, dtype=jnp.int32), k))
    local_eid = flat_ids - first_expert
    is_local = (local_eid >= 0) & (local_eid < e_loc)
    sort_key = jnp.where(is_local, local_eid, e_loc)         # non-local last
    order = lax.stop_gradient(jnp.argsort(sort_key, stable=True))

    s_eid = sort_key[order]
    s_tok = tok_ids[order]
    s_prob = flat_probs[order]
    # position of each routed token within its expert queue
    counts = jax.ops.segment_sum(jnp.ones_like(s_eid), s_eid,
                                 num_segments=e_loc + 1)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(s_eid.shape[0], dtype=jnp.int32) - starts[s_eid].astype(jnp.int32)
    keep = (pos < capacity) & (s_eid < e_loc)
    slot = jnp.where(keep, s_eid * capacity + pos, e_loc * capacity)
    slot = lax.stop_gradient(slot)

    # scatter tokens into (E_loc*C (+1 overflow), D) buffer
    xbuf = jnp.zeros((e_loc * capacity + 1, d), x.dtype)
    xbuf = xbuf.at[slot].add(jnp.take(x, s_tok, axis=0)
                             * keep[:, None].astype(x.dtype))
    xe = xbuf[:-1].reshape(e_loc, capacity, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate)) \
        * jnp.einsum("ecd,edf->ecf", xe, w_up)
    ye = jnp.einsum("ecf,efd->ecd", h, w_down)               # (E_loc, C, D)

    # gather back, weight by router prob, combine per token
    y_slots = ye.reshape(e_loc * capacity, d)
    y_routed = jnp.take(y_slots, jnp.minimum(slot, e_loc * capacity - 1),
                        axis=0)
    y_routed = y_routed * (s_prob * keep.astype(s_prob.dtype)
                           )[:, None].astype(y_routed.dtype)
    out = jax.ops.segment_sum(y_routed, s_tok, num_segments=t)
    return out.astype(x.dtype)


def _moe_body(x, router_w, w_gate, w_up, w_down, cfg: MoEConfig,
              ep_mode: str, axis_name: str | None,
              all_axes: tuple = ()):
    """Per-shard MoE computation (also the single-device path when
    axis_name is None).  ``all_axes``: every mesh axis — the aux loss must
    be reduced over ALL of them (it varies across data shards; reducing
    over the model axis alone leaves an inconsistent 'replicated' value
    and a wrong router gradient — caught by tests/test_moe_dispatch)."""
    p_top, ids, aux = _route(x, router_w, cfg)
    if ep_mode == "ep" and axis_name is not None:
        shard = lax.axis_index(axis_name)
        first = shard * w_gate.shape[0]
    else:
        first = 0
    out = _expert_compute_local(x, p_top, ids, w_gate, w_up, w_down, cfg,
                                first_expert=first)
    if axis_name is not None:
        out = lax.psum(out, axis_name)
        aux = lax.pmean(aux, all_axes or axis_name)
    return out, aux


def _moe_body_a2a(x, router_w, w_gate, w_up, w_down, cfg: MoEConfig,
                  axis_name: str, ep: int, all_axes: tuple = ()):
    """Token-sharded EP with all-to-all dispatch (DeepSeek-style).

    Tokens are sharded over BOTH the data axes and the model axis (the
    sequence-parallel layout); each shard routes its local tokens, sends
    each (token, expert-choice) to the expert-owning shard with one
    all-to-all, computes locally, and returns results with a second
    all-to-all.  Wire bytes per device ~ 2 * T_loc * k * D * cap / ep per
    direction — ~4x less than the AR-combine realization at DeepSeek
    shapes (EXPERIMENTS.md §Perf napkin math)."""
    t_l, d = x.shape
    k = cfg.top_k
    e_loc = w_gate.shape[0]
    p_top, ids, aux = _route(x, router_w, cfg)

    dest = lax.stop_gradient(ids // e_loc)                    # (T_l, k)
    flat_dest = dest.reshape(-1)
    flat_eloc = lax.stop_gradient((ids % e_loc).reshape(-1))
    flat_prob = p_top.reshape(-1)
    tok_ids = jnp.repeat(jnp.arange(t_l, dtype=jnp.int32), k)

    c_send = max(8, int(math.ceil(t_l * k / ep
                                  * cfg.capacity_factor / 8.0)) * 8)
    c_send = min(c_send, t_l * k)
    order = lax.stop_gradient(jnp.argsort(flat_dest, stable=True))
    s_dest = flat_dest[order]
    counts = jax.ops.segment_sum(jnp.ones_like(s_dest), s_dest,
                                 num_segments=ep)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(s_dest.shape[0], dtype=jnp.int32) \
        - starts[s_dest].astype(jnp.int32)
    keep = pos < c_send
    slot = lax.stop_gradient(jnp.where(keep, s_dest * c_send + pos,
                                       ep * c_send))

    s_tok = jnp.take(tok_ids, order)

    def scatter_to_slots(vals, fill):
        buf = jnp.full((ep * c_send + 1,) + vals.shape[1:], fill,
                       vals.dtype)
        masked = jnp.where(
            keep.reshape((-1,) + (1,) * (vals.ndim - 1)), vals,
            jnp.asarray(fill, vals.dtype))
        return buf.at[slot].set(masked)[:-1]

    x_send = scatter_to_slots(jnp.take(x, s_tok, axis=0), 0.0)
    e_send = scatter_to_slots(jnp.take(flat_eloc, order).astype(jnp.int32),
                              e_loc)
    p_send = scatter_to_slots(jnp.take(flat_prob, order), 0.0)

    # dispatch all-to-all, tiled over the model axis
    x_recv = lax.all_to_all(x_send.reshape(ep, c_send, d), axis_name,
                            split_axis=0, concat_axis=0).reshape(-1, d)
    e_recv = lax.all_to_all(e_send.reshape(ep, c_send), axis_name,
                            split_axis=0, concat_axis=0).reshape(-1)
    p_recv = lax.all_to_all(p_send.reshape(ep, c_send), axis_name,
                            split_axis=0, concat_axis=0).reshape(-1)

    # local expert compute; each received slot carries exactly one choice
    local_cfg = dataclasses.replace(cfg, n_experts=e_loc, top_k=1,
                                    router_norm_topk=False)
    y_slots = _expert_compute_local(
        x_recv, p_recv[:, None], e_recv[:, None], w_gate, w_up, w_down,
        local_cfg, first_expert=0)

    # return all-to-all + combine at the source shard
    y_back = lax.all_to_all(y_slots.reshape(ep, c_send, d), axis_name,
                            split_axis=0, concat_axis=0).reshape(-1, d)
    y_sorted = jnp.take(y_back, jnp.minimum(slot, ep * c_send - 1), axis=0)
    contrib = y_sorted * keep[:, None].astype(y_sorted.dtype)
    out = jax.ops.segment_sum(contrib, s_tok, num_segments=t_l)
    return out.astype(x.dtype), lax.pmean(aux, all_axes or axis_name)


def moe_apply(p, x, cfg: MoEConfig, *, mesh=None, dp_axes=("data",),
              model_axis="model", ep_mode: str = "ep",
              dispatch: str = "ar"):
    """Apply the MoE FFN.  x: (B, S, D) or (T, D).

    With a mesh, runs under shard_map: tokens sharded over ``dp_axes``,
    experts (or expert hidden dims) over ``model_axis``.
    dispatch: "ar"  — psum combine, tokens replicated over the model axis;
              "a2a" — token-sharded all-to-all EP (needs ep_mode="ep" and
                      token count divisible by dp*ep).
    """
    orig_shape = x.shape
    if x.ndim == 3:
        x = x.reshape(-1, x.shape[-1])

    if mesh is None or mesh.shape.get(model_axis, 1) == 1:
        out, aux = _moe_body(x, p["router"], p["w_gate"], p["w_up"],
                             p["w_down"], cfg, ep_mode, None)
    else:
        if ep_mode == "ep":
            wspec_g = P(model_axis, None, None)
            wspec_d = P(model_axis, None, None)
        else:
            wspec_g = P(None, None, model_axis)
            wspec_d = P(None, model_axis, None)
        dp = tuple(a for a in dp_axes if a in mesh.shape)
        tokens = x.shape[0]
        dp_size = math.prod(mesh.shape[a] for a in dp)
        ep = mesh.shape[model_axis]
        if dispatch == "a2a" and ep_mode == "ep" \
                and tokens % max(dp_size * ep, 1) == 0:
            xspec = P(dp + (model_axis,), None)
            body = partial(_moe_body_a2a, cfg=cfg, axis_name=model_axis,
                           ep=ep, all_axes=tuple(mesh.axis_names))
            out, aux = jax.shard_map(
                body, mesh=mesh,
                in_specs=(xspec, P(None, None), wspec_g, wspec_g, wspec_d),
                out_specs=(xspec, P()),
                check_vma=False,
            )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
            if "shared" in p:
                out = out + L.swiglu(p["shared"], x)
            return out.reshape(orig_shape), aux
        xspec = P(dp if tokens % max(dp_size, 1) == 0 and dp_size > 1 and tokens >= dp_size else None, None)
        body = partial(_moe_body, cfg=cfg, ep_mode=ep_mode,
                       axis_name=model_axis,
                       all_axes=tuple(mesh.axis_names))
        out, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=(xspec, P(None, None), wspec_g, wspec_g, wspec_d),
            out_specs=(xspec, P()),
            check_vma=False,
        )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if "shared" in p:
        out = out + L.swiglu(p["shared"], x)
    return out.reshape(orig_shape), aux


def moe_flops(tokens: int, d_model: int, cfg: MoEConfig) -> int:
    """Analytic forward FLOPs for the routed + shared experts."""
    routed = tokens * cfg.top_k * (3 * 2 * d_model * cfg.d_ff)
    shared = tokens * cfg.n_shared * (3 * 2 * d_model * cfg.d_ff)
    router = tokens * 2 * d_model * cfg.n_experts
    return routed + shared + router
