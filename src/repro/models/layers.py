"""Common neural-net building blocks (pure JAX, init/apply style).

Every ``*_init`` returns a pytree whose leaves are
:class:`repro.parallel.sharding.Param` (value + logical axis names); the
matching ``*_apply`` consumes the plain value tree (same structure with
Param leaves replaced by arrays — see ``sharding.unzip``).
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
from jax import lax

from repro.parallel.sharding import Param

Array = jax.Array


def rng(key: Array, name: str) -> Array:
    """Deterministic named RNG stream.  crc32, not hash(): str hashes
    are salted per process, and one seed must give the same weights in
    every process."""
    folded = key
    for token in name.split("/"):
        folded = jax.random.fold_in(folded, zlib.crc32(token.encode()))
    return folded


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def trunc_normal(key, shape, dtype, std=0.02):
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                              jnp.float32)).astype(dtype)


def lecun_normal(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[-2] if len(shape) > 1 else shape[0]
    std = 1.0 / math.sqrt(fan_in)
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                              jnp.float32)).astype(dtype)


def he_normal(key, shape, dtype, fan_in):
    std = math.sqrt(2.0 / fan_in)
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Linear / norms
# ---------------------------------------------------------------------------

def linear_init(key, in_dim, out_dim, dtype, *, axes=("embed", "mlp"),
                bias=True, std=0.02):
    p = {"w": Param(trunc_normal(rng(key, "w"), (in_dim, out_dim), dtype, std),
                    axes)}
    if bias:
        p["b"] = Param(jnp.zeros((out_dim,), dtype), (axes[1],))
    return p


def linear(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def rmsnorm_init(dim, dtype):
    return {"scale": Param(jnp.ones((dim,), dtype), (None,))}


def rmsnorm(p, x, eps=1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * p["scale"].astype(jnp.float32)).astype(dtype)


def layernorm_init(dim, dtype):
    return {"scale": Param(jnp.ones((dim,), dtype), (None,)),
            "bias": Param(jnp.zeros((dim,), dtype), (None,))}


def layernorm(p, x, eps=1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(dtype)


def groupnorm(p, x, groups, eps=1e-6):
    """GroupNorm over channel-last input (..., C)."""
    dtype = x.dtype
    *lead, c = x.shape
    x = x.astype(jnp.float32).reshape(*lead, groups, c // groups)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = ((x - mu) * lax.rsqrt(var + eps)).reshape(*lead, c)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(key, dim, hidden, dtype, *, act="gelu", bias=True):
    return {
        "up": linear_init(rng(key, "up"), dim, hidden, dtype,
                          axes=("embed", "mlp"), bias=bias),
        "down": linear_init(rng(key, "down"), hidden, dim, dtype,
                            axes=("mlp", "embed"), bias=bias),
    }


_ACTS = {"gelu": jax.nn.gelu, "relu": jax.nn.relu, "silu": jax.nn.silu,
         "tanh": jnp.tanh}


def mlp(p, x, act="gelu"):
    h = _ACTS[act](linear(p["up"], x))
    return linear(p["down"], h)


def swiglu_init(key, dim, hidden, dtype):
    return {
        "gate": linear_init(rng(key, "gate"), dim, hidden, dtype,
                            axes=("embed", "mlp"), bias=False),
        "up": linear_init(rng(key, "up"), dim, hidden, dtype,
                          axes=("embed", "mlp"), bias=False),
        "down": linear_init(rng(key, "down"), hidden, dim, dtype,
                            axes=("mlp", "embed"), bias=False),
    }


def swiglu(p, x):
    return linear(p["down"], jax.nn.silu(linear(p["gate"], x)) * linear(p["up"], x))


# ---------------------------------------------------------------------------
# Rotary position embedding (llama convention)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim, max_seq, theta=10000.0, dtype=jnp.float32):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rope(x, cos, sin, positions=None):
    """x: (B, S, H, Dh). cos/sin: (S_max, Dh/2). positions: (B, S) or None."""
    if positions is None:
        cos_p = cos[: x.shape[1]][None, :, None, :]
        sin_p = sin[: x.shape[1]][None, :, None, :]
    else:
        cos_p = cos[positions][:, :, None, :]
        sin_p = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos_p - x2 * sin_p,
                           x2 * cos_p + x1 * sin_p], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention — dense and chunked (online-softmax) paths
# ---------------------------------------------------------------------------

def _repeat_kv(k, n_rep):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def dense_attention(q, k, v, *, causal=False, kv_len=None, scale=None,
                    bias=None):
    """Materialized-scores attention.

    q: (B, Sq, H, Dh); k/v: (B, Skv, Hkv, Dh).  ``kv_len``: (B,) valid KV
    lengths (decode against a padded cache).  Returns (B, Sq, H, Dh).
    """
    b, sq, h, dh = q.shape
    hkv = k.shape[2]
    k = _repeat_kv(k, h // hkv)
    v = _repeat_kv(v, h // hkv)
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if bias is not None:
        scores = scores + bias
    skv = k.shape[1]
    if causal:
        qi = lax.broadcasted_iota(jnp.int32, (sq, skv), 0) + (skv - sq)
        ki = lax.broadcasted_iota(jnp.int32, (sq, skv), 1)
        scores = jnp.where(ki <= qi, scores, -jnp.inf)
    if kv_len is not None:
        ki = lax.broadcasted_iota(jnp.int32, (1, 1, 1, skv), 3)
        scores = jnp.where(ki < kv_len[:, None, None, None], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def chunked_attention(q, k, v, *, causal=True, q_chunk=1024, kv_chunk=1024,
                      scale=None):
    """Flash-style attention: scan over KV chunks with an online softmax,
    vmapped over Q chunks.  Never materializes the (Sq, Skv) score matrix —
    peak temp is O(q_chunk * kv_chunk) per (batch, head).

    Equivalent to dense_attention within fp32 softmax accumulation.
    """
    b, sq, h, dh = q.shape
    hkv = k.shape[2]
    n_rep = h // hkv
    skv = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    nq, nkv = sq // q_chunk, skv // kv_chunk
    assert sq % q_chunk == 0 and skv % kv_chunk == 0, (sq, skv, q_chunk, kv_chunk)

    # (B, nq, qc, H, Dh) / (B, nkv, kc, Hkv, Dh)
    qr = q.reshape(b, nq, q_chunk, h, dh)
    kr = k.reshape(b, nkv, kv_chunk, hkv, dh)
    vr = v.reshape(b, nkv, kv_chunk, hkv, dh)

    def q_block(qi, qc):  # qc: (B, qc, H, Dh)
        def kv_step(carry, inp):
            m, l, acc = carry
            ki, kc, vc = inp
            kcr = _repeat_kv(kc, n_rep)
            vcr = _repeat_kv(vc, n_rep)
            s = jnp.einsum("bqhd,bkhd->bhqk", qc, kcr).astype(jnp.float32) * scale
            if causal:
                qpos = qi * q_chunk + lax.broadcasted_iota(
                    jnp.int32, (q_chunk, kv_chunk), 0)
                kpos = ki * kv_chunk + lax.broadcasted_iota(
                    jnp.int32, (q_chunk, kv_chunk), 1)
                s = jnp.where(kpos <= qpos, s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # guard -inf rows (fully masked chunk)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
            corr = jnp.where(jnp.isfinite(m), corr, 0.0)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vcr.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, h, q_chunk, dh), jnp.float32)
        ks = jnp.arange(nkv)
        (m, l, acc), _ = lax.scan(
            kv_step, (m0, l0, a0),
            (ks, jnp.moveaxis(kr, 1, 0), jnp.moveaxis(vr, 1, 0)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 1, 2).astype(q.dtype)  # (B, qc, H, Dh)

    outs = lax.map(lambda args: q_block(*args),
                   (jnp.arange(nq), jnp.moveaxis(qr, 1, 0)))
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, dh)


def decode_attention(q, k_cache, v_cache, kv_len, *, scale=None):
    """Single-token decode attention against a padded KV cache.

    q: (B, 1, H, Dh); caches: (B, S_max, Hkv, Dh); kv_len: (B,).
    """
    return dense_attention(q, k_cache, v_cache, causal=False, kv_len=kv_len,
                           scale=scale)


# ---------------------------------------------------------------------------
# GQA attention block (llama-family)
# ---------------------------------------------------------------------------

def gqa_init(key, d_model, n_heads, n_kv, head_dim, dtype):
    return {
        "wq": Param(trunc_normal(rng(key, "wq"),
                                 (d_model, n_heads, head_dim), dtype),
                    ("embed", "heads", "head_dim")),
        "wk": Param(trunc_normal(rng(key, "wk"),
                                 (d_model, n_kv, head_dim), dtype),
                    ("embed", "kv_heads", "head_dim")),
        "wv": Param(trunc_normal(rng(key, "wv"),
                                 (d_model, n_kv, head_dim), dtype),
                    ("embed", "kv_heads", "head_dim")),
        "wo": Param(trunc_normal(rng(key, "wo"),
                                 (n_heads, head_dim, d_model), dtype),
                    ("heads", "head_dim", "embed")),
    }


def gqa_qkv(p, x, cos, sin, positions=None):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = apply_rope(q, cos, sin, positions)
    k = apply_rope(k, cos, sin, positions)
    return q, k, v


def gqa_out(p, attn):
    return jnp.einsum("bshk,hkd->bsd", attn, p["wo"])


def gqa_apply(p, x, cos, sin, *, causal=True, chunked=False,
              q_chunk=1024, kv_chunk=1024):
    q, k, v = gqa_qkv(p, x, cos, sin)
    if chunked:
        o = chunked_attention(q, k, v, causal=causal,
                              q_chunk=q_chunk, kv_chunk=kv_chunk)
    else:
        o = dense_attention(q, k, v, causal=causal)
    return gqa_out(p, o)


def gqa_decode(p, x, cos, sin, cache, cache_index):
    """One-token decode. x: (B, 1, D). cache: {"k","v"}: (B,Smax,Hkv,Dh),
    cache_index: scalar int32 — current length (same for whole batch)."""
    positions = jnp.full((x.shape[0], 1), cache_index, jnp.int32)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = apply_rope(q, cos, sin, positions)
    k = apply_rope(k, cos, sin, positions)
    k_cache = lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), cache_index, axis=1)
    v_cache = lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), cache_index, axis=1)
    kv_len = jnp.full((x.shape[0],), cache_index + 1, jnp.int32)
    o = decode_attention(q, k_cache, v_cache, kv_len)
    return gqa_out(p, o), {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2/V3)
# ---------------------------------------------------------------------------

def mla_init(key, d_model, n_heads, dtype, *, q_lora_rank=1536,
             kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
             v_head_dim=128):
    return {
        "wq_a": Param(trunc_normal(rng(key, "wq_a"), (d_model, q_lora_rank),
                                   dtype), ("embed", "latent")),
        "q_norm": rmsnorm_init(q_lora_rank, dtype),
        "wq_b": Param(trunc_normal(rng(key, "wq_b"),
                                   (q_lora_rank, n_heads,
                                    qk_nope_dim + qk_rope_dim), dtype),
                      ("latent", "heads", "head_dim")),
        "wkv_a": Param(trunc_normal(rng(key, "wkv_a"),
                                    (d_model, kv_lora_rank + qk_rope_dim),
                                    dtype), ("embed", "latent")),
        "kv_norm": rmsnorm_init(kv_lora_rank, dtype),
        "wk_b": Param(trunc_normal(rng(key, "wk_b"),
                                   (kv_lora_rank, n_heads, qk_nope_dim),
                                   dtype), ("latent", "heads", "head_dim")),
        "wv_b": Param(trunc_normal(rng(key, "wv_b"),
                                   (kv_lora_rank, n_heads, v_head_dim),
                                   dtype), ("latent", "heads", "head_dim")),
        "wo": Param(trunc_normal(rng(key, "wo"),
                                 (n_heads, v_head_dim, d_model), dtype),
                    ("heads", "head_dim", "embed")),
    }


def _mla_dims(p):
    kv_lora = p["wk_b"].shape[0]
    nope = p["wk_b"].shape[2]
    rope = p["wq_b"].shape[2] - nope
    vdim = p["wv_b"].shape[2]
    return kv_lora, nope, rope, vdim


def mla_apply(p, x, cos, sin, *, causal=True, chunked=False,
              q_chunk=1024, kv_chunk=1024, positions=None):
    """Non-absorbed MLA (training / prefill): decompress K,V per position."""
    kv_lora, nope, rope, vdim = _mla_dims(p)
    b, s, _ = x.shape
    q_lat = rmsnorm(p["q_norm"], x @ p["wq_a"])
    q = jnp.einsum("bsl,lhk->bshk", q_lat, p["wq_b"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, cos, sin, positions)

    kv = x @ p["wkv_a"]
    c_kv = rmsnorm(p["kv_norm"], kv[..., :kv_lora])
    k_rope = kv[..., kv_lora:][:, :, None, :]                      # shared head
    k_rope = apply_rope(k_rope, cos, sin, positions)
    k_nope = jnp.einsum("bsl,lhk->bshk", c_kv, p["wk_b"])
    v = jnp.einsum("bsl,lhk->bshk", c_kv, p["wv_b"])

    h = q.shape[2]
    k_rope_b = jnp.broadcast_to(k_rope, (b, s, h, rope))
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope_b], axis=-1)
    scale = 1.0 / math.sqrt(nope + rope)
    if chunked:
        o = chunked_attention(q_full, k_full, v, causal=causal, scale=scale,
                              q_chunk=q_chunk, kv_chunk=kv_chunk)
    else:
        o = dense_attention(q_full, k_full, v, causal=causal, scale=scale)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def mla_decode(p, x, cos, sin, cache, cache_index):
    """Absorbed MLA decode: attention runs in the compressed latent space,
    cache stores only (c_kv, k_rope) — the MLA memory win.

    cache: {"c_kv": (B, Smax, kv_lora), "k_rope": (B, Smax, rope)}.
    """
    kv_lora, nope, rope, vdim = _mla_dims(p)
    b = x.shape[0]
    positions = jnp.full((b, 1), cache_index, jnp.int32)

    q_lat = rmsnorm(p["q_norm"], x @ p["wq_a"])
    q = jnp.einsum("bsl,lhk->bshk", q_lat, p["wq_b"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, cos, sin, positions)
    # absorb wk_b into q: q_lat_abs (B,1,H,kv_lora)
    q_abs = jnp.einsum("bshk,lhk->bshl", q_nope, p["wk_b"])

    kv = x @ p["wkv_a"]
    c_new = rmsnorm(p["kv_norm"], kv[..., :kv_lora])
    kr_new = apply_rope(kv[..., kv_lora:][:, :, None, :], cos, sin,
                        positions)[:, :, 0, :]
    c_cache = lax.dynamic_update_slice_in_dim(
        cache["c_kv"], c_new.astype(cache["c_kv"].dtype), cache_index, axis=1)
    r_cache = lax.dynamic_update_slice_in_dim(
        cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), cache_index, axis=1)

    smax = c_cache.shape[1]
    scale = 1.0 / math.sqrt(nope + rope)
    scores = (jnp.einsum("bshl,btl->bhst", q_abs, c_cache)
              + jnp.einsum("bshr,btr->bhst", q_rope, r_cache)
              ).astype(jnp.float32) * scale
    ti = lax.broadcasted_iota(jnp.int32, (1, 1, 1, smax), 3)
    scores = jnp.where(ti <= cache_index, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("bhst,btl->bshl", w, c_cache)      # (B,1,H,kv_lora)
    o = jnp.einsum("bshl,lhk->bshk", o_lat, p["wv_b"])    # absorb wv_b
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, {"c_kv": c_cache, "k_rope": r_cache}


# ---------------------------------------------------------------------------
# Paged decode — slot-pool continuous batching over a block KV cache
# ---------------------------------------------------------------------------
#
# The paged variants mirror gqa_decode / mla_decode but replace the
# (B, Smax, ...) per-row cache with a shared page store (N_pages, psz, ...)
# indexed through a per-slot ``page_table`` (S, P).  Each slot carries its
# OWN position (``positions``: (S,)), so rows at different depths/lengths
# coexist in one fixed-shape launch.  Writes go through a precomputed
# (page_idx, offset) pair — callers pass an out-of-range page index for
# rows that must not write (inactive slots, rows that already fired an
# exit this step) and the ``mode="drop"`` scatter discards them.  Reads
# gather the slot's pages back into a dense (S, P*psz, ...) view via
# ``kernels.dispatch.paged_gather`` and reuse the exact dense attention
# math, so values are bit-identical to the contiguous-cache oracle at
# equal padded length.


def paged_write(pages, rows, page_idx, offset):
    """Scatter one row per slot into ``pages[page_idx[i], offset[i]]``.

    pages: (N, psz, ...); rows: (S, ...); page_idx/offset: (S,) int32.
    Out-of-range page_idx entries are dropped (masked write).
    """
    return pages.at[page_idx, offset].set(rows.astype(pages.dtype),
                                          mode="drop")


def gqa_decode_paged(p, x, cos, sin, pages, page_table, page_idx, offset,
                     positions, *, gather_kw=None):
    """One-token GQA decode against a paged KV cache.

    x: (S, 1, D); pages: {"k","v"}: (N, psz, Hkv, Dh); page_table: (S, P);
    page_idx/offset/positions: (S,) int32 (per-slot write target and
    current position).  Returns (out (S, 1, D), new pages).
    """
    from repro.kernels import dispatch as KD
    gather_kw = gather_kw or {}
    pos2 = positions[:, None]                               # (S, 1)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = apply_rope(q, cos, sin, pos2)
    k = apply_rope(k, cos, sin, pos2)
    k_pages = paged_write(pages["k"], k[:, 0], page_idx, offset)
    v_pages = paged_write(pages["v"], v[:, 0], page_idx, offset)
    k_view = KD.paged_gather(k_pages, page_table, **gather_kw)
    v_view = KD.paged_gather(v_pages, page_table, **gather_kw)
    o = decode_attention(q, k_view, v_view, positions + 1)
    return gqa_out(p, o), {"k": k_pages, "v": v_pages}


def mla_decode_paged(p, x, cos, sin, pages, page_table, page_idx, offset,
                     positions, *, gather_kw=None):
    """Absorbed MLA decode against a paged latent cache.

    pages: {"c_kv": (N, psz, kv_lora), "k_rope": (N, psz, rope)}.
    Same per-slot indexing contract as :func:`gqa_decode_paged`.
    """
    from repro.kernels import dispatch as KD
    gather_kw = gather_kw or {}
    kv_lora, nope, rope, vdim = _mla_dims(p)
    pos2 = positions[:, None]                               # (S, 1)

    q_lat = rmsnorm(p["q_norm"], x @ p["wq_a"])
    q = jnp.einsum("bsl,lhk->bshk", q_lat, p["wq_b"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, cos, sin, pos2)
    q_abs = jnp.einsum("bshk,lhk->bshl", q_nope, p["wk_b"])

    kv = x @ p["wkv_a"]
    c_new = rmsnorm(p["kv_norm"], kv[..., :kv_lora])
    kr_new = apply_rope(kv[..., kv_lora:][:, :, None, :], cos, sin,
                        pos2)[:, :, 0, :]
    c_pages = paged_write(pages["c_kv"], c_new[:, 0], page_idx, offset)
    r_pages = paged_write(pages["k_rope"], kr_new[:, 0], page_idx, offset)
    c_view = KD.paged_gather(c_pages, page_table, **gather_kw)
    r_view = KD.paged_gather(r_pages, page_table, **gather_kw)

    lp = c_view.shape[1]
    scale = 1.0 / math.sqrt(nope + rope)
    scores = (jnp.einsum("bshl,btl->bhst", q_abs, c_view)
              + jnp.einsum("bshr,btr->bhst", q_rope, r_view)
              ).astype(jnp.float32) * scale
    ti = lax.broadcasted_iota(jnp.int32, (1, 1, 1, lp), 3)
    scores = jnp.where(ti <= positions[:, None, None, None], scores,
                       -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("bhst,btl->bshl", w, c_view)
    o = jnp.einsum("bshl,lhk->bshk", o_lat, p["wv_b"])
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, {"c_kv": c_pages, "k_rope": r_pages}


# ---------------------------------------------------------------------------
# Convolutions (NHWC)
# ---------------------------------------------------------------------------

def conv_init(key, kh, kw, cin, cout, dtype, *, bias=True, groups=1,
              std=None):
    fan_in = kh * kw * cin // groups
    w = he_normal(rng(key, "w"), (kh, kw, cin // groups, cout), dtype, fan_in)
    p = {"w": Param(w, ("spatial", "spatial", "in_channels", "channels"))}
    if bias:
        p["b"] = Param(jnp.zeros((cout,), dtype), ("channels",))
    return p


def conv2d(p, x, *, stride=1, padding="SAME", groups=1):
    s = (stride, stride) if isinstance(stride, int) else stride
    y = lax.conv_general_dilated(
        x, p["w"], window_strides=s, padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)
    if "b" in p:
        y = y + p["b"]
    return y


def max_pool(x, window, stride, padding="SAME"):
    return lax.reduce_window(x, -jnp.inf, lax.max,
                             (1, window, window, 1), (1, stride, stride, 1),
                             padding)


def avg_pool(x, window, stride, padding="SAME"):
    s = lax.reduce_window(x, 0.0, lax.add, (1, window, window, 1),
                          (1, stride, stride, 1), padding)
    ones = jnp.ones_like(x)
    n = lax.reduce_window(ones, 0.0, lax.add, (1, window, window, 1),
                          (1, stride, stride, 1), padding)
    return s / n


def global_avg_pool(x):
    """(B, H, W, C) -> (B, C) or (B, N, D) -> (B, D)."""
    axes = tuple(range(1, x.ndim - 1))
    return jnp.mean(x, axis=axes)


# ---------------------------------------------------------------------------
# Patch embedding (ViT / DiT)
# ---------------------------------------------------------------------------

def patch_embed_init(key, patch, cin, dim, dtype):
    return {"proj": conv_init(rng(key, "proj"), patch, patch, cin, dim, dtype),
            }


def patch_embed(p, x, patch):
    y = conv2d(p["proj"], x, stride=patch, padding="VALID")
    b, h, w, c = y.shape
    return y.reshape(b, h * w, c)


def sincos_pos_embed(n_pos, dim, dtype=jnp.float32, temperature=10000.0):
    """1D sin-cos table, (n_pos, dim)."""
    omega = jnp.arange(dim // 2, dtype=jnp.float32) / (dim / 2.0)
    omega = 1.0 / (temperature ** omega)
    pos = jnp.arange(n_pos, dtype=jnp.float32)
    out = jnp.einsum("p,d->pd", pos, omega)
    return jnp.concatenate([jnp.sin(out), jnp.cos(out)], axis=1).astype(dtype)


def sincos_pos_embed_2d(h, w, dim, dtype=jnp.float32):
    eh = sincos_pos_embed(h, dim // 2, dtype)
    ew = sincos_pos_embed(w, dim // 2, dtype)
    grid = jnp.concatenate(
        [jnp.repeat(eh, w, axis=0), jnp.tile(ew, (h, 1))], axis=1)
    return grid  # (h*w, dim)


# ---------------------------------------------------------------------------
# Plain MHA block for encoder-style transformers (ViT / DiT / LeViT)
# ---------------------------------------------------------------------------

def mha_init(key, d_model, n_heads, dtype, *, head_dim=None, bias=True):
    hd = head_dim or d_model // n_heads
    return {
        "wq": Param(trunc_normal(rng(key, "wq"), (d_model, n_heads, hd),
                                 dtype), ("embed", "heads", "head_dim")),
        "wk": Param(trunc_normal(rng(key, "wk"), (d_model, n_heads, hd),
                                 dtype), ("embed", "heads", "head_dim")),
        "wv": Param(trunc_normal(rng(key, "wv"), (d_model, n_heads, hd),
                                 dtype), ("embed", "heads", "head_dim")),
        "wo": Param(trunc_normal(rng(key, "wo"), (n_heads, hd, d_model),
                                 dtype), ("heads", "head_dim", "embed")),
        "bq": Param(jnp.zeros((n_heads, hd), dtype), ("heads", "head_dim")),
        "bo": Param(jnp.zeros((d_model,), dtype), (None,)),
    }


def mha_apply(p, x, *, bias=None, chunked=False, q_chunk=1024, kv_chunk=1024):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"]) + p["bq"]
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if chunked:
        o = chunked_attention(q, k, v, causal=False, q_chunk=q_chunk,
                              kv_chunk=kv_chunk)
    else:
        o = dense_attention(q, k, v, causal=False, bias=bias)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]) + p["bo"]


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

def embed_init(key, vocab, dim, dtype):
    return {"table": Param(trunc_normal(rng(key, "table"), (vocab, dim),
                                        dtype, std=0.01), ("vocab", "embed"))}


def embed(p, ids):
    return jnp.take(p["table"], ids, axis=0)


def unembed(p, x):
    """Tied unembedding: (B, S, D) @ (V, D)^T."""
    return jnp.einsum("bsd,vd->bsv", x, p["table"])
