"""Jit-ready wrapper for the decode-attention kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import (
    decode_attention_fwd,
    paged_decode_attention_fwd,
    paged_mixed_attention_fwd,
)


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


# replint: traced -- jitted from the serving engine
def decode_attention(q1, k_cache, v_cache, pos, *, window: int | None = None,
                     block_k: int | None = None):
    """q1: (B, 1, Hq, D); caches: (B, S, Hkv, D); pos: scalar int32 valid length.

    ``block_k=None`` resolves the autotuned per-backend default
    (`repro.kernels.decode_attention.autotune`).  Returns (B, 1, Hq, D).
    """
    if block_k is None:
        from repro.kernels.decode_attention.autotune import default_block_k
        block_k = default_block_k()
    scalars = jnp.stack([jnp.asarray(pos, jnp.int32),
                         jnp.asarray(window if window else -1, jnp.int32)])
    out = decode_attention_fwd(q1[:, 0], k_cache, v_cache, scalars,
                               block_k=block_k, interpret=_interpret())
    return out[:, None]


# replint: traced -- jitted from the serving engine
def decode_attention_paged(q1, k_pages, v_pages, block_table, lengths, *,
                           window=None, k_scale=None, v_scale=None):
    """Block-table decode attention over a paged KV pool.

    q1: (B, 1, Hq, D); pages: (P, page_size, Hkv, D); block_table: (B, n)
    int32 (logical page i of row b lives in physical page block_table[b, i]);
    lengths: (B,) valid logical entries per row, including the current token.
    ``window`` may be a python int/None or a traced int32 scalar (-1 / None =
    unlimited), so the call sites inside a scanned layer stack can pass the
    per-layer window.  ``k_scale``/``v_scale``: (P, page_size, Hkv, 1) f32
    pools for int8 pages (dequantized in-kernel).  Returns (B, 1, Hq, D).
    """
    win = jnp.reshape(jnp.asarray(-1 if window is None else window, jnp.int32),
                      (1,))
    out = paged_decode_attention_fwd(
        q1[:, 0], k_pages, v_pages, jnp.asarray(block_table, jnp.int32),
        jnp.asarray(lengths, jnp.int32), win, k_scale=k_scale, v_scale=v_scale,
        interpret=_interpret())
    return out[:, None]


# replint: traced -- jitted from the serving engine mixed step
def decode_attention_mixed(q, k_pages, v_pages, block_table, starts, *,
                           window=None, k_scale=None, v_scale=None):
    """Mixed-span block-table attention over a paged KV pool.

    q: (B, T, Hq, D) -- T consecutive queries per row, the first at logical
    position ``starts[b]`` (so a decode row has T == 1 and
    ``starts == pos``, a prefill chunk has T == chunk_size, a speculative
    verify block T == 1 + draft_len); pages / block_table / scales as in
    :func:`decode_attention_paged`.  The span's own KV must be written
    before the call.

    The kernel visits only each row's live pages, in blocks of several
    pages copied through the block table, and scores each KV head's
    ``T * Hq / Hkv`` queries against that head's keys alone; pages per
    block follow from the shapes (see
    :func:`repro.kernels.decode_attention.kernel.paged_mixed_attention_fwd`).
    Returns (B, T, Hq, D).
    """
    win = jnp.reshape(jnp.asarray(-1 if window is None else window, jnp.int32),
                      (1,))
    return paged_mixed_attention_fwd(
        q, k_pages, v_pages, jnp.asarray(block_table, jnp.int32),
        jnp.asarray(starts, jnp.int32), win, k_scale=k_scale, v_scale=v_scale,
        interpret=_interpret())


__all__ = ["decode_attention", "decode_attention_paged",
           "decode_attention_mixed"]
