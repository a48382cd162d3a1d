"""Flash-decoding for TPU: single-token attention over a long KV cache.

The CUDA flash-decoding trick (split-K across SMs + cross-SM reduction) maps to
TPU as a sequential KV-block grid dimension with fp32 VMEM scratch carrying the
running (max, sum, acc) -- the sequential grid is free on TPU since blocks
stream through VMEM anyway; the win is never materializing (Hq, S) logits in
HBM and reading K/V exactly once.

The valid cache length ``pos`` and the window are scalar-prefetch operands, so
the same compiled kernel serves every decode step.

The paged kernels (a mixed span of T queries per row, and its T = 1 decode
case) loop over each row's live pages only, in multi-page blocks copied
through the block table, one KV head per score tile.
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


# Block-size rule of the paged mixed kernel (see paged_mixed_attention_fwd).
_MAX_BLOCK_KEYS = 512
_SCORE_TILE_BYTES = 256 * 1024
_KV_BUFFER_BYTES = 4 * 1024 * 1024
_LANES = 128


def _lanes(width: int) -> int:
    """``width`` rounded up to whole 128-lane tiles."""
    return pl.cdiv(width, _LANES) * _LANES


def _packing(dtype) -> int:
    """Elements of ``dtype`` in one 32-bit word."""
    return 4 // jnp.dtype(dtype).itemsize


def _pages_per_block(page_size: int, n_pages: int, rows: int,
                     key_bytes: int, packing: int) -> int:
    """Pages per key block: the most whose keys stay within
    ``_MAX_BLOCK_KEYS``, whose ``(rows, keys)`` f32 score tile stays within
    ``_SCORE_TILE_BYTES``, and whose double buffers (``key_bytes`` per key)
    stay within ``_KV_BUFFER_BYTES``; at least one page, at most the
    table's width, and a whole number of 32-bit words per head."""
    keys = min(_MAX_BLOCK_KEYS, _SCORE_TILE_BYTES // (4 * rows),
               _KV_BUFFER_BYTES // key_bytes)
    nb = max(1, min(n_pages, keys // page_size))
    while nb * page_size % packing:
        nb += 1
    return nb


def _key_order(n_keys: int, packing: int):
    """(1, n_keys) int32: the block offset of the key in each score column.
    :func:`_head_rows` reads a packed pool one element position of its
    32-bit words at a time, so column ``r * n_keys / packing + s`` holds
    key ``packing * s + r``."""
    c = jnp.arange(n_keys, dtype=jnp.int32)
    per = n_keys // packing
    return (packing * (c % per) + c // per).reshape(1, n_keys)


def _head_rows(buf, j: int, n_kv: int, n_keys: int, packing: int):
    """Rows of KV head ``j`` from a block buffer whose row ``t * n_kv + h``
    holds key ``t`` of head ``h``, as float32 in :func:`_key_order`'s
    column order for a pool of ``packing`` elements per 32-bit word.
    ``buf`` is an f32 buffer, or a packed one bitcast to uint32.

    Mosaic loads strided rows of 32-bit types only, so a packed pool is
    read as words: word ``w`` holds rows ``packing * w`` (lowest bits) to
    ``packing * w + packing - 1``, and key ``packing * s + r`` of head
    ``j`` sits in word ``s * n_kv + (r * n_kv + j) // packing`` at element
    ``(r * n_kv + j) % packing``.  One strided load per ``r`` picks the
    words and a shift the element: bf16 moves into a float32's top half
    (the same value), int8 is sign-extended.  An f32 buffer -- the int8
    pool's scales -- is read in the same order."""
    n = n_keys // packing
    pieces = []
    for r in range(packing):
        row = r * n_kv + j
        if buf.dtype == jnp.float32:
            pieces.append(buf[pl.ds(row, n, stride=packing * n_kv), :])
            continue
        w = buf[pl.ds(row // packing, n, stride=n_kv), :]
        e = row % packing
        if packing == 2:
            bits = w << 16 if e == 0 else w & jnp.uint32(0xFFFF0000)
            pieces.append(pltpu.bitcast(bits, jnp.float32))
        else:
            x = pltpu.bitcast(w, jnp.int32)
            pieces.append(((x << (24 - 8 * e)) >> 24).astype(jnp.float32))
    return pieces[0] if packing == 1 else jnp.concatenate(pieces, axis=0)


def _paged_mixed_kernel(tbl_ref, start_ref, win_ref, qoff_ref, kord_ref,
                        q_ref, *refs, page_size: int, pages_per_block: int,
                        sm_scale: float, q_len: int, int8: bool):
    """Mixed-span block-table flash attention, one grid step per row.

    Each row carries ``q_len`` queries at consecutive logical positions
    ``start[b] + t`` -- prefill chunks, speculative verify blocks and plain
    decode (q_len == 1) are the same kernel.  Query ``t`` attends keys
    ``k <= start[b] + t`` (per-query causal), minus the sliding window.

    Iteration space: a ``fori_loop`` over the row's live key blocks only,
    from the first page the earliest query's window reaches to page
    ``(start + q_len - 1) // page_size``.  A block is ``pages_per_block``
    pages gathered through the block table by one async copy per page and
    pool into a VMEM buffer (the pools stay in HBM); the next block's
    copies -- or the next row's first block's -- run while this block
    computes, into the other half of a double buffer whose slot is carried
    across rows in SMEM.  Table entries past the row's last live page are
    clamped to it, so a short last block re-reads a live page whose keys
    the causal mask drops.

    Inside a block a static loop over KV heads scores only that head's
    ``q_len * group`` queries (rows ``t * group + g`` of ``q_ref[0, j]``)
    against that head's keys (:func:`_head_rows`), so no cross-group pair
    is computed; int8 keys and values are dequantized there.  ``qoff``
    (rows, 1) holds each query row's position offset ``t``, ``kord`` (1,
    keys) each score column's key offset in the block, so the kernel needs
    no integer division.  Running max, sum and output live per KV head in
    f32 scratch."""
    n_pools = 4 if int8 else 2
    pools = refs[:n_pools]                  # HBM: k, v[, k_scale, v_scale]
    o_ref = refs[n_pools]
    bufs = refs[n_pools + 1:2 * n_pools + 1]
    sems, m_scr, l_scr, acc_scr, slot_ref = refs[2 * n_pools + 1:]
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    n_kv = q_ref.shape[1]
    n_table = tbl_ref.shape[1]
    nb = pages_per_block
    rows_per_page = bufs[0].shape[1] // nb
    n_keys = kord_ref.shape[1]
    packing = _packing(bufs[0].dtype)
    window = win_ref[0]
    # window <= 0 means unlimited: an effective width no position reaches
    win_eff = jnp.where(window > 0, window, jnp.int32(1 << 30))

    def live_pages(row):
        start = start_ref[row]
        first = jax.lax.div(jnp.maximum(start + 1 - win_eff, 0), page_size)
        last = jnp.minimum(jax.lax.div(start + q_len - 1, page_size),
                           n_table - 1)
        return first, last

    def page_copies(page, i, slot):
        dst = pl.ds(pl.multiple_of(i * rows_per_page, rows_per_page),
                    rows_per_page)
        return [pltpu.make_async_copy(pool.at[page], buf.at[slot, dst],
                                      sems.at[n, slot])
                for n, (pool, buf) in enumerate(zip(pools, bufs))]

    def start_block(row, blk, slot):
        first, last = live_pages(row)

        def start(i, carry):
            logical = jnp.minimum(first + blk * nb + i, last)
            for c in page_copies(tbl_ref[row, logical], i, slot):
                c.start()
            return carry
        jax.lax.fori_loop(0, nb, start, 0)

    def wait_block(slot):
        def wait(i, carry):
            # a wait needs only the copy's size and semaphore
            for c in page_copies(0, i, slot):
                c.wait()
            return carry
        jax.lax.fori_loop(0, nb, wait, 0)

    @pl.when(b == 0)
    def _first_block():
        slot_ref[0] = 0
        start_block(0, 0, 0)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    start = start_ref[b]
    first, last = live_pages(b)
    n_blocks = jax.lax.div(last - first, nb) + 1
    q_pos = start + qoff_ref[...]                             # (rows, 1)

    def block(blk, slot):
        nxt = 1 - slot

        @pl.when(blk + 1 < n_blocks)
        def _next_block():
            start_block(b, blk + 1, nxt)

        @pl.when((blk + 1 == n_blocks) & (b + 1 < n_rows))
        def _next_row():
            start_block(b + 1, 0, nxt)

        wait_block(slot)
        k_pos = (first + blk * nb) * page_size + kord_ref[...]   # (1, keys)
        valid = (k_pos <= q_pos) & (k_pos > q_pos - win_eff)
        kb, vb = bufs[0].at[slot], bufs[1].at[slot]
        if packing > 1:
            kb, vb = kb.bitcast(jnp.uint32), vb.bitcast(jnp.uint32)
        for j in range(n_kv):
            q = q_ref[0, j]                                   # (rows, D)
            k = _head_rows(kb, j, n_kv, n_keys, packing)      # (keys, D)
            v = _head_rows(vb, j, n_kv, n_keys, packing)
            if int8:
                ks = _head_rows(bufs[2].at[slot], j, n_kv, n_keys, packing)
                vs = _head_rows(bufs[3].at[slot], j, n_kv, n_keys, packing)
                k, v = k * ks[:, :1], v * vs[:, :1]
                q = q.astype(jnp.float32)
            else:
                k, v = k.astype(bufs[0].dtype), v.astype(bufs[1].dtype)
            s = jax.lax.dot_general(
                q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_scr[j]                                 # (rows, 1)
            m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            # explicit zero where invalid: a query that sees none of this
            # (block-live) block still has m == NEG_INF, and exp(s - m)
            # would be exp(0) garbage for its masked lanes
            p = jnp.where(valid, jnp.exp(s - m_cur), 0.0)
            l_scr[j] = l_scr[j] * alpha + p.sum(axis=1, keepdims=True)
            pv = jnp.dot(p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
            acc_scr[j] = acc_scr[j] * alpha + pv
            m_scr[j] = m_cur
        return nxt

    slot_ref[0] = jax.lax.fori_loop(0, n_blocks, block, slot_ref[0])
    denom = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def paged_mixed_attention_fwd(q, k_pages, v_pages, block_table, starts,
                              window, *, k_scale=None, v_scale=None,
                              interpret: bool = False):
    """q: (B, T, Hq, D) -- T queries per row at logical positions
    ``starts[b] + t``; pages: (P, page_size, Hkv, D); block_table: (B, n)
    int32; starts: (B,) int32; window: (1,) int32, -1 = unlimited.
    ``k_scale``/``v_scale``: optional (P, page_size, Hkv, 1) f32 pools for
    int8 pages -- dequantized in VMEM after the page copy, so the int8
    pool is what streams from HBM.

    Per-query causal attention over each row's own pages; the KV for the
    span itself must already be written (query t attends its own key).
    One grid step per row loops over the row's live key blocks only
    (:func:`_paged_mixed_kernel`); table entries past a row's last live
    page are never read, and a dead row (``starts`` 0) costs one block.

    Block size, from the shapes alone: the most pages whose keys number at
    most 512, whose f32 score tile of one KV head -- ``(T * Hq / Hkv,
    keys)`` -- stays within 256 KiB, and whose double-buffered pages (and
    scales) stay within 4 MiB of VMEM (:func:`_pages_per_block`).  At page
    32 and 32 queries: 8 pages for GQA group 8, 16 for group 4; 16 for
    decode.

    The kernel copies each page as ``(page_size * Hkv, width)`` rows, and
    Mosaic copies HBM rows only in whole 128-lane tiles.  So a head dim
    that is not a multiple of 128, and the int8 pools' ``(..., 1)``
    scales, are zero-padded to whole tiles here: one more pass over each
    of those pools on every call, beside the relayout into rows that XLA
    makes of those pools' layouts anyway.  That pass is a cost of those
    paths; pools laid out in 128-lane rows at allocation would remove it.
    Returns (B, T, Hq, D).
    """
    B, T, Hq, D = q.shape
    page_size, Hkv = k_pages.shape[1], k_pages.shape[2]
    n_pages = block_table.shape[1]
    group = Hq // Hkv
    int8 = k_scale is not None
    R, C = T * group, page_size * Hkv
    Dp = _lanes(D)
    if Dp != D:
        q = jnp.pad(q, [(0, 0)] * 3 + [(0, Dp - D)])

    def rows(pool):
        """(P, page, Hkv, w) -> (P, page * Hkv, w padded to whole lanes)"""
        x = pool.reshape(-1, C, pool.shape[-1])
        pad = _lanes(x.shape[-1]) - x.shape[-1]
        return jnp.pad(x, [(0, 0), (0, 0), (0, pad)]) if pad else x

    packing = _packing(k_pages.dtype)
    # VMEM bytes one key of every head takes in the double buffers
    key_bytes = Hkv * 2 * (2 * Dp * k_pages.dtype.itemsize
                           + (2 * _LANES * 4 if int8 else 0))
    nb = _pages_per_block(page_size, n_pages, R, key_bytes, packing)

    kernel = functools.partial(_paged_mixed_kernel, page_size=page_size,
                               pages_per_block=nb, sm_scale=D ** -0.5,
                               q_len=T, int8=int8)

    def row_map(b, tbl, st, win):
        return b, 0, 0, 0

    def fixed_map(b, tbl, st, win):
        return 0, 0

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    qoff = (jnp.arange(R, dtype=jnp.int32) // group).reshape(R, 1)
    kord = _key_order(nb * page_size, packing)
    # rows t * group + g of head j: that KV head's queries, contiguous
    qh = q.reshape(B, T, Hkv, group, Dp).transpose(0, 2, 1, 3, 4)
    in_specs = [pl.BlockSpec((R, 1), fixed_map),
                pl.BlockSpec(kord.shape, fixed_map),
                pl.BlockSpec((1, Hkv, R, Dp), row_map), hbm, hbm]
    inputs = [qoff, kord, qh.reshape(B, Hkv, R, Dp),
              rows(k_pages), rows(v_pages)]
    scratch = [pltpu.VMEM((2, nb * C, Dp), k_pages.dtype)] * 2
    if int8:
        in_specs += [hbm, hbm]
        inputs += [rows(k_scale), rows(v_scale)]
        scratch += [pltpu.VMEM((2, nb * C, _LANES), jnp.float32)] * 2
    scratch += [pltpu.SemaphoreType.DMA((len(scratch), 2)),
                pltpu.VMEM((Hkv, R, 1), jnp.float32),
                pltpu.VMEM((Hkv, R, 1), jnp.float32),
                pltpu.VMEM((Hkv, R, Dp), jnp.float32),
                pltpu.SMEM((1,), jnp.int32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hkv, R, Dp), row_map),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, Dp), q.dtype),
        # a row's last block prefetches the next row's first: rows in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_table, starts, window, *inputs)
    out = out.reshape(B, Hkv, T, group, Dp).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, Hq, Dp)[..., :D]


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
