"""Attention ops: chunked online-softmax (flash-style) in pure jnp.

These are (a) the CPU execution path, (b) the oracles for the Pallas kernels
in ``repro.kernels``, and (c) the building block of the *distributed*
flash-decode (KV-sequence-sharded) attention used for long-context cells.

All functions take **absolute positions** for q and kv plus a kv validity
mask, which uniformly covers training (arange), prefill, dense decode caches,
ring-buffer (sliding-window) caches and paged pools.

Shapes:
  q:  [B, Sq, Hq, D]       (Hq = n_kv_heads * group)
  k:  [B, Sk, Hkv, D]
  v:  [B, Sk, Hkv, D]
  q_pos: [B, Sq] int32     absolute position of each query
  kv_pos: [B, Sk] int32    absolute position of each kv slot
  kv_valid: [B, Sk] bool   slot holds real data
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _group_q(q: jax.Array, n_kv: int) -> jax.Array:
    """[B, Sq, Hq, D] -> [B, Sq, Hkv, G, D]."""
    b, sq, hq, d = q.shape
    assert hq % n_kv == 0, (hq, n_kv)
    return q.reshape(b, sq, n_kv, hq // n_kv, d)


def _flash_core(
    q: jax.Array,              # [B, Sq, Hkv, G, D] (pre-scaled)
    k: jax.Array,              # [B, Sk, Hkv, D]
    v: jax.Array,
    q_pos: jax.Array,          # [B, Sq]
    kv_pos: jax.Array,         # [B, Sk]
    kv_valid: jax.Array,       # [B, Sk] bool
    *,
    causal: bool,
    window: int,
    chunk: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Chunked online softmax. Returns UNNORMALIZED (o, m, l):
       o: [B, Sq, Hkv, G, D] f32 = sum_j exp(s_j - m) v_j
       m: [B, Sq, Hkv, G]    f32 running max
       l: [B, Sq, Hkv, G]    f32 running sum of exp
    The caller normalizes (o / l) or combines partials across shards.
    """
    b, sq, hkv, g, d = q.shape
    sk = k.shape[1]
    chunk = min(chunk, sk)
    while sk % chunk:          # largest divisor of sk not above the request
        chunk -= 1
    n_chunks = sk // chunk

    kc = k.reshape(b, n_chunks, chunk, hkv, d)
    vc = v.reshape(b, n_chunks, chunk, hkv, d)
    pc = kv_pos.reshape(b, n_chunks, chunk)
    mc = kv_valid.reshape(b, n_chunks, chunk)

    o0 = jnp.zeros((b, sq, hkv, g, d), jnp.float32)
    m0 = jnp.full((b, sq, hkv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, hkv, g), jnp.float32)

    def body(carry, xs):
        o, m, l = carry
        k_j, v_j, p_j, valid_j = xs  # [B, chunk, Hkv, D], .., [B, chunk], [B, chunk]
        s = jnp.einsum(
            "bqhgd,bchd->bqhgc", q, k_j.astype(q.dtype),
            preferred_element_type=jnp.float32)
        mask = valid_j[:, None, :]                       # [B, 1, chunk]
        if causal:
            mask = mask & (p_j[:, None, :] <= q_pos[:, :, None])
        if window > 0:
            mask = mask & (q_pos[:, :, None] - p_j[:, None, :] < window)
        s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bqhgc,bchd->bqhgd", p, v_j.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return (o_new, m_new, l_new), None

    xs = (
        jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0),
        jnp.moveaxis(pc, 1, 0), jnp.moveaxis(mc, 1, 0),
    )
    (o, m, l), _ = jax.lax.scan(body, (o0, m0, l0), xs)
    return o, m, l


def _normalize(o, m, l, dtype):
    l = jnp.maximum(l, 1e-30)
    return (o / l[..., None]).astype(dtype)


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    q_pos: Optional[jax.Array] = None,
    kv_pos: Optional[jax.Array] = None,
    kv_valid: Optional[jax.Array] = None,
    *,
    causal: bool = True,
    window: int = 0,
    chunk: int = 512,
) -> jax.Array:
    """Full (train/prefill) attention. q [B,Sq,Hq,D] -> [B,Sq,Hq,D].

    Differentiable with O(S) memory: a custom VJP recomputes score chunks
    in the backward pass (flash-attention backward) instead of letting AD
    save every chunk's probabilities (which would be O(S^2))."""
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    if q_pos is None:
        q_pos = jnp.broadcast_to(jnp.arange(sq, dtype=jnp.int32)[None], (b, sq))
    if kv_pos is None:
        kv_pos = jnp.broadcast_to(jnp.arange(sk, dtype=jnp.int32)[None], (b, sk))
    if kv_valid is None:
        kv_valid = jnp.ones((b, sk), bool)
    return _flash_attention_vjp(q, k, v, q_pos, kv_pos, kv_valid,
                                causal, window, chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _flash_attention_vjp(q, k, v, q_pos, kv_pos, kv_valid,
                         causal, window, chunk):
    out, _ = _flash_fwd(q, k, v, q_pos, kv_pos, kv_valid,
                        causal, window, chunk)
    return out


def _flash_fwd(q, k, v, q_pos, kv_pos, kv_valid, causal, window, chunk):
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    qg = _group_q(q, hkv) * (d ** -0.5)
    o, m, l = _flash_core(qg, k, v, q_pos, kv_pos, kv_valid,
                          causal=causal, window=window, chunk=chunk)
    out = _normalize(o, m, l, q.dtype).reshape(b, sq, hq, d)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))           # [B,Sq,Hkv,G]
    return out, (q, k, v, q_pos, kv_pos, kv_valid, out, lse)


def _flash_bwd(causal, window, chunk, res, do):
    q, k, v, q_pos, kv_pos, kv_valid, out, lse = res
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = d ** -0.5
    qg = (_group_q(q, hkv) * scale).astype(jnp.float32)    # [B,Sq,Hkv,G,D]
    og = _group_q(out, hkv).astype(jnp.float32)
    dog = _group_q(do, hkv).astype(jnp.float32)
    delta = (og * dog).sum(-1)                             # [B,Sq,Hkv,G]
    ck = min(chunk, sk)
    while sk % ck:
        ck -= 1
    n = sk // ck
    kc = jnp.moveaxis(k.reshape(b, n, ck, hkv, d), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, n, ck, hkv, d), 1, 0)
    pc = jnp.moveaxis(kv_pos.reshape(b, n, ck), 1, 0)
    mc = jnp.moveaxis(kv_valid.reshape(b, n, ck), 1, 0)

    def body(dq, xs):
        k_j, v_j, p_j, valid_j = xs
        s = jnp.einsum("bqhgd,bchd->bqhgc", qg, k_j.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        mask = valid_j[:, None, :]
        if causal:
            mask = mask & (p_j[:, None, :] <= q_pos[:, :, None])
        if window > 0:
            mask = mask & (q_pos[:, :, None] - p_j[:, None, :] < window)
        s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                    # [B,Sq,Hkv,G,C]
        dv_j = jnp.einsum("bqhgc,bqhgd->bchd", p, dog,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqhgd,bchd->bqhgc", dog, v_j.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bqhgc,bchd->bqhgd", ds, k_j.astype(jnp.float32),
                             preferred_element_type=jnp.float32)
        dk_j = jnp.einsum("bqhgc,bqhgd->bchd", ds, qg,
                          preferred_element_type=jnp.float32)
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros((b, sq, hkv, g, d), jnp.float32)
    dq, (dk, dv) = jax.lax.scan(body, dq0, (kc, vc, pc, mc))
    dq = (dq * scale).reshape(b, sq, hq, d).astype(q.dtype)
    dk = jnp.moveaxis(dk, 0, 1).reshape(b, sk, hkv, d).astype(k.dtype)
    dv = jnp.moveaxis(dv, 0, 1).reshape(b, sk, hkv, d).astype(v.dtype)
    return dq, dk, dv, None, None, None


_flash_attention_vjp.defvjp(
    lambda q, k, v, qp, kp, kv, causal, window, chunk: _flash_fwd(
        q, k, v, qp, kp, kv, causal, window, chunk),
    _flash_bwd)


def decode_attention(
    q: jax.Array,              # [B, Hq, D] one new token per sequence
    k_cache: jax.Array,        # [B, Sk, Hkv, D]
    v_cache: jax.Array,
    q_pos: jax.Array,          # [B] absolute position of the new token
    kv_pos: jax.Array,         # [B, Sk]
    kv_valid: jax.Array,       # [B, Sk]
    *,
    causal: bool = True,
    window: int = 0,
    chunk: int = 1024,
) -> jax.Array:
    """Single-token decode attention -> [B, Hq, D]."""
    b, hq, d = q.shape
    hkv = k_cache.shape[2]
    qg = _group_q(q[:, None], hkv) * (d ** -0.5)
    o, m, l = _flash_core(qg, k_cache, v_cache, q_pos[:, None], kv_pos, kv_valid,
                          causal=causal, window=window, chunk=chunk)
    return _normalize(o, m, l, q.dtype).reshape(b, 1, hq, d)[:, 0]


def decode_attention_partial(
    q, k_cache, v_cache, q_pos, kv_pos, kv_valid, *, window: int = 0,
    chunk: int = 1024,
):
    """Decode attention returning unnormalized (o, m, l) for LSE-combining."""
    b, hq, d = q.shape
    hkv = k_cache.shape[2]
    qg = _group_q(q[:, None], hkv) * (d ** -0.5)
    return _flash_core(qg, k_cache, v_cache, q_pos[:, None], kv_pos, kv_valid,
                       causal=True, window=window, chunk=chunk)


def lse_combine(o, m, l, axis_names) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Combine per-shard (o, m, l) partials across ``axis_names`` (inside
    shard_map): the cross-device step of distributed flash-decode."""
    m_g = jax.lax.pmax(m, axis_names)
    scale = jnp.exp(m - m_g)
    l_g = jax.lax.psum(l * scale, axis_names)
    o_g = jax.lax.psum(o * scale[..., None], axis_names)
    return o_g, m_g, l_g


def distributed_decode_attention(
    mesh,
    kv_axes: Tuple[str, ...],
    q: jax.Array,              # [B, Hq, D] replicated over kv_axes
    k_cache: jax.Array,        # [B, Sk, Hkv, D] sharded over kv_axes on Sk
    v_cache: jax.Array,
    q_pos: jax.Array,          # [B]
    kv_pos: jax.Array,         # [B, Sk] sharded like k_cache
    kv_valid: jax.Array,
    *,
    window: int = 0,
    chunk: int = 1024,
    batch_axes: Tuple[str, ...] = (),
) -> jax.Array:
    """Flash-decode with the KV sequence sharded across ``kv_axes``:
    each shard attends over its local KV slice; partials are LSE-combined.
    This is what makes global_batch=1 x 500k-context decode shardable.
    """
    dtype = q.dtype
    kv_seq_spec = P(batch_axes or None, kv_axes)

    def local(qi, ki, vi, qpi, kpi, kvi):
        o, m, l = decode_attention_partial(
            qi, ki, vi, qpi, kpi, kvi, window=window, chunk=chunk)
        o, m, l = lse_combine(o, m, l, kv_axes)
        return _normalize(o, m, l, dtype)

    b_spec = P(batch_axes or None)
    in_specs = (
        b_spec, kv_seq_spec, kv_seq_spec, b_spec, kv_seq_spec, kv_seq_spec)
    mapped = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                           out_specs=b_spec, check_vma=False)
    out = mapped(q, k_cache, v_cache, q_pos, kv_pos, kv_valid)
    b, _, hkv, g, d = out.shape
    return out.reshape(b, hkv * g, d)


def paged_gather(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """pool [P, page, H, D], page_table [B, N] -> [B, N*page, H, D]."""
    b, n = page_table.shape
    _, page, h, d = pool.shape
    out = pool[page_table]                    # [B, N, page, H, D]
    return out.reshape(b, n * page, h, d)


def paged_decode_attention(
    q: jax.Array,              # [B, Hq, D]
    k_pool: jax.Array,         # [P, page, Hkv, D]
    v_pool: jax.Array,
    page_table: jax.Array,     # [B, N] int32 (entries < P; pad -> page 0)
    context_lens: jax.Array,   # [B] tokens currently in cache
    *,
    window: int = 0,
    chunk: int = 1024,
) -> jax.Array:
    """Reference paged decode attention (oracle for the Pallas kernel)."""
    b = q.shape[0]
    page = k_pool.shape[1]
    n = page_table.shape[1]
    k = paged_gather(k_pool, page_table)
    v = paged_gather(v_pool, page_table)
    kv_pos = jnp.broadcast_to(
        jnp.arange(n * page, dtype=jnp.int32)[None], (b, n * page))
    kv_valid = kv_pos < context_lens[:, None]
    q_pos = jnp.maximum(context_lens - 1, 0)
    return decode_attention(q, k, v, q_pos, kv_pos, kv_valid,
                            window=window, chunk=min(chunk, n * page))


def paged_prefill_attention(
    q: jax.Array,              # [B, Sq, Hq, D] one prompt chunk per sequence
    k_pool: jax.Array,         # [P, page, Hkv, D]
    v_pool: jax.Array,
    page_table: jax.Array,     # [B, N] int32
    q_start: jax.Array,        # [B] absolute position of q[:, 0]
    context_lens: jax.Array,   # [B] tokens in cache INCLUDING this chunk
    *,
    window: int = 0,
    chunk: int = 1024,
) -> jax.Array:
    """Chunked-prefill attention over a paged pool (oracle for the Pallas
    kernel): the chunk's own K/V have already been scattered into the pool,
    so each query at absolute position ``q_start + i`` attends causally over
    everything the pool holds for its sequence — the previously prefilled
    context (and any CoW-shared prefix pages) plus the in-chunk causal
    block. This is what makes token-budget chunked prefill possible: a
    prompt's KV accumulates in its allocator pages across engine steps
    while decode of other slots proceeds in between."""
    b, sq = q.shape[0], q.shape[1]
    page = k_pool.shape[1]
    n = page_table.shape[1]
    k = paged_gather(k_pool, page_table)
    v = paged_gather(v_pool, page_table)
    q_pos = q_start[:, None] + jnp.arange(sq, dtype=jnp.int32)[None]
    kv_pos = jnp.broadcast_to(
        jnp.arange(n * page, dtype=jnp.int32)[None], (b, n * page))
    kv_valid = kv_pos < context_lens[:, None]
    return flash_attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                           kv_valid=kv_valid, causal=True, window=window,
                           chunk=min(chunk, n * page))
