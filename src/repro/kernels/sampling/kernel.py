"""Fused greedy sampling/logprob epilogue for TPU decode.

The decode hot loop needs two scalars per batch row from the (B, V) logits:
the argmax token and that token's log-probability.  Doing this with
``log_softmax`` materializes a second (B, V) tensor in HBM just to gather one
element of it; on a 128k-vocab model that is the largest intermediate of the
whole decode step.  This kernel streams the vocab once through VMEM carrying a
running (max, logsumexp accumulator, argmax) per row and emits the two
scalars directly -- the flash-attention trick applied to the sampler.

Tie-breaking matches ``jnp.argmax`` exactly (first maximal index wins): blocks
are visited in vocab order and a later block only takes over on a strictly
greater maximum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


#: rows folded per grid step: the lm-head weight streams from HBM once per
#: row block, so one block covers a whole mixed step (max_batch * span rows)
ROW_BLOCK = 256

#: scoped VMEM the epilogues may use: a (256, 2048) f32 logits tile plus
#: double-buffered bf16 weight blocks of 2048 columns need more than the
#: compiler's default scope, and stay well inside a v5e core's 128 MiB
VMEM_LIMIT = 64 * 1024 * 1024


def _fold(x, vi, m_scr, l_scr, bi_scr, *, block_v: int, total_v: int):
    """Fold one (rows, block_v) f32 logits tile into the running per-row
    (max, logsumexp accumulator, argmax) stats.  The running max doubles as
    the best value: a later block takes the argmax only on a strictly
    greater maximum, and inside a block the smallest maximal index wins, so
    ties break like ``jnp.argmax`` (first maximal index)."""
    # the last block may overhang the vocab: mask the padding lanes dead
    idx = vi * block_v + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    x = jnp.where(idx < total_v, x, NEG_INF)
    bmax = x.max(axis=1, keepdims=True)                       # (rows, 1)
    barg = jnp.where(x == bmax, idx, jnp.int32(total_v)).min(axis=1,
                                                             keepdims=True)
    m_prev = m_scr[...]
    bi_scr[...] = jnp.where(bmax > m_prev, barg, bi_scr[...])
    m_cur = jnp.maximum(m_prev, bmax)
    l_scr[...] = (l_scr[...] * jnp.exp(m_prev - m_cur)
                  + jnp.exp(x - m_cur).sum(axis=1, keepdims=True))
    m_scr[...] = m_cur


def _init_stats(m_scr, l_scr, bi_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    bi_scr[...] = jnp.zeros_like(bi_scr)


def _emit(tok_ref, lp_ref, l_scr, bi_scr):
    tok_ref[...] = bi_scr[...]
    lp_ref[...] = -jnp.log(jnp.maximum(l_scr[...], 1e-30))   # max - lse


def _epilogue_kernel(x_ref, tok_ref, lp_ref, m_scr, l_scr, bi_scr,
                     *, block_v: int, total_v: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _():
        _init_stats(m_scr, l_scr, bi_scr)

    _fold(x_ref[...].astype(jnp.float32), vi, m_scr, l_scr, bi_scr,
          block_v=block_v, total_v=total_v)

    @pl.when(vi == pl.num_programs(1) - 1)
    def _():
        _emit(tok_ref, lp_ref, l_scr, bi_scr)


def _lmhead_epilogue_kernel(h_ref, w_ref, tok_ref, lp_ref, m_scr, l_scr,
                            bi_scr, *, block_v: int, total_v: int):
    """Fused lm-head + greedy epilogue: the (rows, block_v) logits tile is
    computed on the MXU from a block of hidden rows and one vocab block of
    the weight matrix, then folded like :func:`_epilogue_kernel` -- the
    (N, V) logits tensor never exists, not even as a kernel input."""
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _():
        _init_stats(m_scr, l_scr, bi_scr)

    dt = jnp.promote_types(h_ref.dtype, w_ref.dtype)
    x = jnp.dot(h_ref[...].astype(dt), w_ref[...].astype(dt),
                preferred_element_type=jnp.float32)          # (rows, block_v)
    _fold(x, vi, m_scr, l_scr, bi_scr, block_v=block_v, total_v=total_v)

    @pl.when(vi == pl.num_programs(1) - 1)
    def _():
        _emit(tok_ref, lp_ref, l_scr, bi_scr)


def _row_blocked_call(kernel, rows, operands, in_specs, n, block_v, nv,
                      interpret):
    """Run a vocab-streaming epilogue over ``n`` rows in blocks of ``rows``
    (the row operand is padded to whole blocks; padding rows are dropped)
    and return ``(token (n,) int32, logprob (n,) f32)``."""
    n_pad = -(-n // rows) * rows
    operands = [jnp.pad(operands[0], ((0, n_pad - n), (0, 0)))] + operands[1:]
    row_spec = pl.BlockSpec((rows, 1), lambda r, vi: (r, 0))
    tok, lp = pl.pallas_call(
        kernel,
        grid=(n_pad // rows, nv),
        in_specs=in_specs,
        out_specs=[row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
                   jax.ShapeDtypeStruct((n_pad, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*operands)
    return tok[:n, 0], lp[:n, 0]


def _rows(n: int) -> int:
    """Row block: all rows when they fit one block, else ROW_BLOCK."""
    return n if n <= ROW_BLOCK else ROW_BLOCK


def greedy_epilogue_fwd(logits, *, block_v: int = 2048,
                        interpret: bool = False):
    """logits: (B, V) -> (token (B,) int32, logprob (B,) f32).

    One vocab pass; never materializes the normalized (B, V) log-probs.
    """
    B, V = logits.shape
    block_v = min(block_v, V)
    nv = pl.cdiv(V, block_v)              # last block masks its overhang
    rows = _rows(B)
    kernel = functools.partial(_epilogue_kernel, block_v=block_v, total_v=V)
    in_specs = [pl.BlockSpec((rows, block_v), lambda r, vi: (r, vi))]
    return _row_blocked_call(kernel, rows, [logits], in_specs, B, block_v, nv,
                             interpret)


def lmhead_epilogue_fwd(h, w, *, block_v: int = 2048,
                        interpret: bool = False):
    """h: (N, d) hidden rows; w: (d, V) lm-head weight.

    Returns (token (N,) int32, logprob (N,) f32) -- argmax of ``h @ w`` and
    its log-probability, streaming vocab blocks of ``w`` through VMEM so no
    (N, V) logits tensor is materialized.  Rows are blocked together, so the
    weight streams from HBM once per block of ``ROW_BLOCK`` rows, not once
    per row.  ``N`` is whatever the caller flattened: B decode rows or B*T
    verify positions.
    """
    N, d = h.shape
    V = w.shape[1]
    block_v = min(block_v, V)
    nv = pl.cdiv(V, block_v)              # last block masks its overhang
    rows = _rows(N)
    kernel = functools.partial(_lmhead_epilogue_kernel,
                               block_v=block_v, total_v=V)
    in_specs = [pl.BlockSpec((rows, d), lambda r, vi: (r, 0)),
                pl.BlockSpec((d, block_v), lambda r, vi: (0, vi))]
    return _row_blocked_call(kernel, rows, [h, w], in_specs, N, block_v, nv,
                             interpret)
