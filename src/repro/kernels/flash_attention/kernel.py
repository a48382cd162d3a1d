"""Blockwise online-softmax attention (FlashAttention), Pallas TPU.

TPU adaptation notes (vs the CUDA original):
* the KV loop is a *grid dimension* (innermost, sequential on TPU) with fp32
  VMEM scratch carrying the running max / sum / accumulator between KV steps --
  the TPU analogue of warp-persistent register tiles;
* block shapes are MXU-aligned (multiples of 128 on the contracting dims);
* causal + sliding-window masking uses an in-block iota mask; the window is a
  *scalar-prefetch* operand so one compiled kernel serves every layer of a
  local/global interleaved stack (gemma3) under ``lax.scan``;
* GQA is expressed in the BlockSpec index maps (query head h reads KV head
  ``h // group``), so KV duplication never materializes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(w_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
               *, block_q: int, block_k: int, causal: bool, sm_scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    window = w_ref[0]

    # block-level skip: blocks entirely above the causal diagonal or entirely
    # outside the sliding window contribute nothing
    # window <= 0 means unlimited: an effective width no position reaches
    win_eff = jnp.where(window > 0, window, jnp.int32(1 << 30))
    live = k_start + block_k - 1 > q_start - win_eff
    if causal:
        live &= k_start <= q_start + block_q - 1

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale        # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                   # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = q @ k.T                                           # (bq, bk)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # boolean masks combine with & only: the TPU compiler cannot
        # legalize a select between two boolean vectors
        mask = k_pos > q_pos - win_eff
        if causal:
            mask &= k_pos <= q_pos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v
        m_scr[...] = m_cur

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, window, *, causal: bool = True,
                        block_q: int = 512, block_k: int = 512,
                        interpret: bool = False):
    """q: (B, Hq, S, D); k/v: (B, Hkv, S, D); window: (1,) int32 (<=0 = none).

    Returns (B, Hq, S, D).
    """
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(S, block_k)

    kernel = functools.partial(
        _fa_kernel, block_q=block_q, block_k=block_k, causal=causal,
        sm_scale=D ** -0.5)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki, w: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, w: (b, h // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, w: (b, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki, w: (b, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, D), q.dtype),
        interpret=interpret,
    )(window, q, k, v)
