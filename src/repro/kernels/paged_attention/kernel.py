"""Pallas TPU paged decode attention (GQA) over a block-paged KV pool.

This is the serving hot-spot MIRAGE's elastic KV pool feeds into: the pool
is a flat array of pages (possibly spanning multiple *segments* donated by
remapped parameters — the allocator hands the kernel one logical pool), and
each sequence owns a list of page indices (its page table).

Grid: (batch, kv_heads, num_pages_per_seq). The page table and per-sequence
context lengths ride in scalar-prefetch memory (SMEM) so the k/v BlockSpec
index maps can look up the *physical* page for (sequence, logical page) while
the DMA for page j+1 overlaps the compute on page j (standard TPU pipeline).

Per-program VMEM: q tile [group, d] + one K page + one V page
[page_size, d] + f32 accumulators — e.g. page=64, d=128, group=8 in bf16
is ~70 KB, leaving headroom to raise page_size or multi-page blocks.

All query heads of one KV head (the GQA group) are processed together so
K/V pages are read once per group rather than once per query head — the
kernel is KV-bandwidth-bound and this keeps bytes moved at the GQA minimum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    # scalar prefetch
    page_table_ref,                # [B, N] int32 (SMEM)
    context_lens_ref,              # [B] int32 (SMEM)
    # blocks
    q_ref,                         # [1, 1, G, D]
    k_ref,                         # [1, 1, page, D]
    v_ref,                         # [1, 1, page, D]
    o_ref,                         # [1, 1, G, D]
    # scratch
    m_ref, l_ref, acc_ref,         # [G], [G], [G, D] f32
    *,
    page_size: int,
    sm_scale: float,
    window: int,
):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    ctx = context_lens_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = j * page_size
    q_pos = ctx - 1
    live = start < ctx
    if window > 0:
        live = jnp.logical_and(live, q_pos - (start + page_size - 1) < window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale      # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)                 # [page, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [G, page]
        kpos = start + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
        mask = kpos < ctx
        if window > 0:
            mask = jnp.logical_and(mask, q_pos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nj - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _prefill_kernel(
    # scalar prefetch
    page_table_ref,                # [B, N] int32 (SMEM)
    context_lens_ref,              # [B] int32 (SMEM): tokens incl. chunk
    q_start_ref,                   # [B] int32 (SMEM): abs pos of query 0
    # blocks
    q_ref,                         # [1, 1, Sq, G, D]
    k_ref,                         # [1, 1, page, D]
    v_ref,                         # [1, 1, page, D]
    o_ref,                         # [1, 1, Sq, G, D]
    # scratch
    m_ref, l_ref, acc_ref,         # [Sq*G], [Sq*G], [Sq*G, D] f32
    *,
    page_size: int,
    group: int,
    sm_scale: float,
    window: int,
):
    """Chunked-prefill attention: Sq chunk queries of one (sequence, KV
    head) pair sweep the sequence's pages; the chunk's own K/V were
    scattered into the pool before the call, so page j covers both the
    prior context and the in-chunk causal block. Same online-softmax
    pipeline as the decode kernel, with a per-query-row causal mask."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    ctx = context_lens_ref[b]
    q0 = q_start_ref[b]
    sq = q_ref.shape[2]
    rows = sq * group

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = j * page_size
    live = start < ctx
    if window > 0:
        # the page is visible to at least the OLDEST query (largest window
        # reach is the smallest q position: q0)
        live = jnp.logical_and(live, q0 - (start + page_size - 1) < window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32).reshape(rows, -1) * sm_scale
        k = k_ref[0, 0].astype(jnp.float32)                 # [page, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [rows, page]
        qpos = q0 + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 0) // group
        kpos = start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        mask = jnp.logical_and(kpos <= qpos, kpos < ctx)
        if window > 0:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nj - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).reshape(
            sq, group, -1).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "interpret"))
def paged_prefill_attention(
    q: jax.Array,             # [B, Sq, Hq, D] prompt chunk
    k_pool: jax.Array,        # [P, page, Hkv, D]
    v_pool: jax.Array,
    page_table: jax.Array,    # [B, N] int32
    q_start: jax.Array,       # [B] int32 absolute position of q[:, 0]
    context_lens: jax.Array,  # [B] int32 tokens in cache incl. the chunk
    *,
    window: int = 0,
    interpret: bool = False,
) -> jax.Array:
    b, sq, hq, d = q.shape
    _, page, hkv, _ = k_pool.shape
    n = page_table.shape[1]
    group = hq // hkv

    qg = q.reshape(b, sq, hkv, group, d)
    qg = jnp.moveaxis(qg, 2, 1)                   # [B, Hkv, Sq, G, D]
    kp = jnp.moveaxis(k_pool, 2, 1)               # [P, Hkv, page, D]
    vp = jnp.moveaxis(v_pool, 2, 1)

    grid = (b, hkv, n)

    def q_map(bi, h, j, *refs):
        return (bi, h, 0, 0, 0)

    def kv_map(bi, h, j, page_table_ref, context_lens_ref, q_start_ref):
        return (page_table_ref[bi, j], h, 0, 0)

    kernel = functools.partial(
        _prefill_kernel, page_size=page, group=group, sm_scale=d ** -0.5,
        window=window)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, sq, group, d), q_map),
                pl.BlockSpec((1, 1, page, d), kv_map),
                pl.BlockSpec((1, 1, page, d), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, sq, group, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((sq * group,), jnp.float32),
                pltpu.VMEM((sq * group,), jnp.float32),
                pltpu.VMEM((sq * group, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, sq, group, d), q.dtype),
        interpret=interpret,
        name="paged_prefill_attention",
    )(page_table.astype(jnp.int32), context_lens.astype(jnp.int32),
      q_start.astype(jnp.int32), qg, kp, vp)
    return jnp.moveaxis(out, 1, 2).reshape(b, sq, hq, d)


@functools.partial(
    jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(
    q: jax.Array,             # [B, Hq, D]
    k_pool: jax.Array,        # [P, page, Hkv, D]
    v_pool: jax.Array,
    page_table: jax.Array,    # [B, N] int32
    context_lens: jax.Array,  # [B] int32
    *,
    window: int = 0,
    interpret: bool = False,
) -> jax.Array:
    b, hq, d = q.shape
    p_total, page, hkv, _ = k_pool.shape
    n = page_table.shape[1]
    group = hq // hkv

    # [B, Hkv, G, D] so one program handles a whole GQA group.
    qg = q.reshape(b, hkv, group, d)
    # pools as [P, Hkv, page, D] so a block is one (page x head) tile.
    kp = jnp.moveaxis(k_pool, 2, 1)
    vp = jnp.moveaxis(v_pool, 2, 1)

    grid = (b, hkv, n)

    def q_map(bi, h, j, *refs):
        return (bi, h, 0, 0)

    def kv_map(bi, h, j, page_table_ref, context_lens_ref):
        return (page_table_ref[bi, j], h, 0, 0)

    kernel = functools.partial(
        _kernel, page_size=page, sm_scale=d ** -0.5, window=window)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, group, d), q_map),
                pl.BlockSpec((1, 1, page, d), kv_map),
                pl.BlockSpec((1, 1, page, d), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, group, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((group,), jnp.float32),
                pltpu.VMEM((group,), jnp.float32),
                pltpu.VMEM((group, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(page_table.astype(jnp.int32), context_lens.astype(jnp.int32), qg, kp, vp)
    return out.reshape(b, hq, d)
