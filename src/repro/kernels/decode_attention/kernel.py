"""Flash-decoding for TPU: single-token attention over a long KV cache.

The CUDA flash-decoding trick (split-K across SMs + cross-SM reduction) maps to
TPU as a sequential KV-block grid dimension with fp32 VMEM scratch carrying the
running (max, sum, acc) -- the sequential grid is free on TPU since blocks
stream through VMEM anyway; the win is never materializing (Hq, S) logits in
HBM and reading K/V exactly once.

The valid cache length ``pos`` and the window are scalar-prefetch operands, so
the same compiled kernel serves every decode step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dec_kernel(s_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                *, block_k: int, group: int, sm_scale: float):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    pos = s_ref[0]        # number of valid cache entries (incl. current token)
    window = s_ref[1]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = ki * block_k
    live = k_start < pos
    live &= jnp.where(window > 0, k_start + block_k - 1 >= pos - window, True)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * sm_scale           # (Hq, d)
        k = k_ref[0].astype(jnp.float32)                      # (bk, Hkv, d)
        v = v_ref[0].astype(jnp.float32)
        # GQA: logits[h, t] = q[h] . k[t, h // group]
        kr = jnp.repeat(k, group, axis=1)                     # (bk, Hq, d)
        s = jnp.einsum("hd,thd->ht", q, kr)                   # (Hq, bk)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = k_pos < pos
        valid &= jnp.where(window > 0, k_pos >= pos - window, True)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        vr = jnp.repeat(v, group, axis=1)                     # (bk, Hq, d)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jnp.einsum("ht,thd->hd", p, vr)
        m_scr[...] = m_cur

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def decode_attention_fwd(q, k_cache, v_cache, scalars, *, block_k: int = 1024,
                         interpret: bool = False):
    """q: (B, Hq, D); caches: (B, S, Hkv, D); scalars: (2,) int32 [pos, window].

    Returns (B, Hq, D).
    """
    B, Hq, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    group = Hq // Hkv
    block_k = min(block_k, S)
    nk = pl.cdiv(S, block_k)

    kernel = functools.partial(_dec_kernel, block_k=block_k, group=group,
                               sm_scale=D ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec((1, Hq, D), lambda b, ki, s: (b, 0, 0)),
            pl.BlockSpec((1, block_k, Hkv, D), lambda b, ki, s: (b, ki, 0, 0)),
            pl.BlockSpec((1, block_k, Hkv, D), lambda b, ki, s: (b, ki, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, ki, s: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq,), jnp.float32),
            pltpu.VMEM((Hq,), jnp.float32),
            pltpu.VMEM((Hq, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        interpret=interpret,
    )(scalars, q, k_cache, v_cache)


def _paged_mixed_kernel(tbl_ref, start_ref, win_ref, qrow_ref, kcol_ref,
                        q_ref, k_ref, v_ref, *rest, page_size: int,
                        sm_scale: float, q_len: int, int8: bool = False):
    """Mixed-span block-table flash attention, one grid step per (row, page).

    Each row carries ``q_len`` queries at consecutive logical positions
    ``start[b] + t`` -- prefill chunks, speculative verify blocks and plain
    decode (q_len == 1) are the same kernel.  Query ``t`` attends keys
    ``k <= start[b] + t`` (per-query causal), minus the sliding window.

    Layout: every tile is 2-D so the TPU compiler takes it as is.  The
    row's queries arrive as one ``(q_len * Hq, D)`` tile (row ``t * Hq +
    h``) and a page as one ``(page_size * Hkv, D)`` tile (row ``p * Hkv +
    j``), so ONE matmul scores every head against every key of the page.
    Pairs from different KV groups are masked out; the two small int32
    tables ``qrow`` (query position offset, KV group per tile row) and
    ``kcol`` (key offset, KV group per tile column) carry the pairing, so
    the kernel needs no integer division.  With ``page_size * Hkv <= 128``
    the extra cross-group columns fit lanes a per-head tile would leave
    idle, so they cost no vector work."""
    if int8:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    pi = pl.program_id(1)
    npg = pl.num_programs(1)
    start = start_ref[b]    # logical position of this row's first query
    window = win_ref[0]
    # window <= 0 means unlimited: an effective width no position reaches
    win_eff = jnp.where(window > 0, window, jnp.int32(1 << 30))

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = pi * page_size
    # the page is live if ANY query can see ANY of its keys; per-query
    # masking below handles the rest
    live = (k_start < start + q_len) & (k_start + page_size > start + 1 - win_eff)

    @pl.when(live)
    def _compute():
        q = q_ref[0]                                          # (T*Hq, D)
        k = k_ref[0]                                          # (ps*Hkv, D)
        v = v_ref[0]
        if int8:
            k = k.astype(jnp.float32) * ks_ref[0]             # (ps*Hkv, 1)
            v = v.astype(jnp.float32) * vs_ref[0]
            q = q.astype(jnp.float32)
        s = jax.lax.dot_general(q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        q_pos = start + qrow_ref[:, 0:1]                      # (T*Hq, 1)
        k_pos = k_start + kcol_ref[0:1, :]                    # (1, ps*Hkv)
        valid = ((qrow_ref[:, 1:2] == kcol_ref[1:2, :])
                 & (k_pos <= q_pos) & (k_pos > q_pos - win_eff))
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]                                   # (T*Hq, 1)
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        # explicit zero where invalid: a query that sees none of this
        # (block-live) page still has m == NEG_INF, and exp(s - m) would be
        # exp(0) garbage for its masked lanes
        p = jnp.where(valid, jnp.exp(s - m_cur), 0.0)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        pv = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_cur

    @pl.when(pi == npg - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _pair_tables(q_len: int, n_q_heads: int, page_size: int, n_kv_heads: int):
    """Int32 tables the mixed kernel masks with: ``qrow`` (T*Hq, 2) holds
    each query tile row's position offset ``t`` and KV group ``h // group``;
    ``kcol`` (2, ps*Hkv) holds each key tile column's in-page offset and KV
    group."""
    group = n_q_heads // n_kv_heads
    r = jnp.arange(q_len * n_q_heads, dtype=jnp.int32)
    qrow = jnp.stack([r // n_q_heads, (r % n_q_heads) // group], axis=1)
    c = jnp.arange(page_size * n_kv_heads, dtype=jnp.int32)
    kcol = jnp.stack([c // n_kv_heads, c % n_kv_heads], axis=0)
    return qrow, kcol


def paged_mixed_attention_fwd(q, k_pages, v_pages, block_table, starts,
                              window, *, k_scale=None, v_scale=None,
                              interpret: bool = False):
    """q: (B, T, Hq, D) -- T queries per row at logical positions
    ``starts[b] + t``; pages: (P, page_size, Hkv, D); block_table: (B, n)
    int32; starts: (B,) int32; window: (1,) int32, -1 = unlimited.
    ``k_scale``/``v_scale``: optional (P, page_size, Hkv, 1) f32 pools for
    int8 pages -- dequantized in-register after the page DMA, so the int8
    pool is what streams from HBM.

    Per-query causal attention over each row's own pages; the KV for the
    span itself must already be written (query t attends its own key).
    Table entries past a row's last live page are never fetched: the page
    index map repeats the last live page, whose block the pipeline then
    skips re-copying.  Returns (B, T, Hq, D).
    """
    B, T, Hq, D = q.shape
    page_size, Hkv = k_pages.shape[1], k_pages.shape[2]
    n_pages = block_table.shape[1]
    int8 = k_scale is not None
    R, C = T * Hq, page_size * Hkv
    qrow, kcol = _pair_tables(T, Hq, page_size, Hkv)

    kernel = functools.partial(_paged_mixed_kernel, page_size=page_size,
                               sm_scale=D ** -0.5, q_len=T, int8=int8)

    def page_map(b, pi, tbl, st, win):
        last = (st[b] + T - 1) // page_size
        return tbl[b, jnp.minimum(pi, last)], 0, 0

    def row_map(b, pi, tbl, st, win):
        return b, 0, 0

    def fixed_map(b, pi, tbl, st, win):
        return 0, 0

    in_specs = [pl.BlockSpec((R, 2), fixed_map),
                pl.BlockSpec((2, C), fixed_map),
                pl.BlockSpec((1, R, D), row_map),
                pl.BlockSpec((1, C, D), page_map),
                pl.BlockSpec((1, C, D), page_map)]
    inputs = [qrow, kcol, q.reshape(B, R, D),
              k_pages.reshape(-1, C, D), v_pages.reshape(-1, C, D)]
    if int8:
        in_specs += [pl.BlockSpec((1, C, 1), page_map)] * 2
        inputs += [k_scale.reshape(-1, C, 1), v_scale.reshape(-1, C, 1)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, R, D), row_map),
        scratch_shapes=[
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, R, D), q.dtype),
        interpret=interpret,
    )(block_table, starts, window, *inputs)
    return out.reshape(B, T, Hq, D)


def paged_decode_attention_fwd(q, k_pages, v_pages, block_table, lengths,
                               window, *, k_scale=None, v_scale=None,
                               interpret: bool = False):
    """q: (B, Hq, D); pages: (P, page_size, Hkv, D); block_table: (B, n) int32;
    lengths: (B,) int32 valid logical entries per row (incl. the current
    token); window: (1,) int32, -1 = unlimited.

    The T = 1 case of :func:`paged_mixed_attention_fwd` (the single query
    sits at position ``length - 1``).  Returns (B, Hq, D).  Rows attend only
    to their own pages; table entries past a row's live pages may point
    anywhere (trash page) -- they are never read.
    """
    out = paged_mixed_attention_fwd(
        q[:, None], k_pages, v_pages, block_table, lengths - 1, window,
        k_scale=k_scale, v_scale=v_scale, interpret=interpret)
    return out[:, 0]
