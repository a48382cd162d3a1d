"""Decoder-only LM supporting the dense / GQA / SWA / local-global / MoE variants
of the zoo, built as pure functions over a scanned, stacked-parameter block stack.

Key properties:
* ``lax.scan`` over layers keeps HLO size O(1) in depth (fast 512-device compiles);
* prefill attention streams over query chunks (blockwise softmax) above
  ``STREAM_THRESHOLD`` so 32k-token prefill never materializes an (S, S) tensor;
* decode uses a preallocated KV cache with position-masked single-token attention;
* per-layer heterogeneity (local vs global attention) is expressed as a scanned
  boolean so the stack stays homogeneous.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models.attention import sdpa
from repro.models.common import (
    ModelConfig, apply_rope, gated_mlp, init_dense, rms_norm, rope_tables,
)
from repro.models.moe import moe_ffn
from repro.serving import kvcache

STREAM_THRESHOLD = 4096
STREAM_CHUNK = 512


# ---------------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------------

def init_block_params(rng, cfg: ModelConfig):
    """One transformer block; leaves later get a leading L dim via vmap."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    Hq, Hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(rng, 10)
    p = {
        "ln1": jnp.ones((d,), cfg.dtype),
        "ln2": jnp.ones((d,), cfg.dtype),
        "wq": init_dense(ks[0], (d, Hq * hd), cfg.dtype),
        "wk": init_dense(ks[1], (d, Hkv * hd), cfg.dtype),
        "wv": init_dense(ks[2], (d, Hkv * hd), cfg.dtype),
        "wo": init_dense(ks[3], (Hq * hd, d), cfg.dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((Hq * hd,), cfg.dtype)
        p["bk"] = jnp.zeros((Hkv * hd,), cfg.dtype)
        p["bv"] = jnp.zeros((Hkv * hd,), cfg.dtype)
    if cfg.moe:
        m = cfg.moe
        p["moe"] = {
            "router": init_dense(ks[4], (d, m.n_experts), jnp.float32),
            "w_gate": init_dense(ks[5], (m.n_experts, d, m.d_expert), cfg.dtype),
            "w_up": init_dense(ks[6], (m.n_experts, d, m.d_expert), cfg.dtype),
            "w_down": init_dense(ks[7], (m.n_experts, m.d_expert, d), cfg.dtype,
                                 scale=m.d_expert ** -0.5),
        }
    else:
        p["mlp"] = {
            "w_gate": init_dense(ks[4], (d, cfg.d_ff), cfg.dtype),
            "w_up": init_dense(ks[5], (d, cfg.d_ff), cfg.dtype),
            "w_down": init_dense(ks[6], (cfg.d_ff, d), cfg.dtype,
                                 scale=cfg.d_ff ** -0.5),
        }
    return p


def init_params(rng, cfg: ModelConfig):
    k_embed, k_blocks, k_head = jax.random.split(rng, 3)
    blocks = jax.vmap(lambda k: init_block_params(k, cfg))(
        jax.random.split(k_blocks, cfg.n_layers))
    params = {
        "embed": init_dense(k_embed, (cfg.vocab, cfg.d_model), cfg.dtype, scale=0.02),
        "blocks": blocks,
        "ln_f": jnp.ones((cfg.d_model,), cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_dense(k_head, (cfg.d_model, cfg.vocab), cfg.dtype)
    return params


def layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """(L,) int32: -1 = full/global attention, else SWA width for that layer."""
    L = cfg.n_layers
    if cfg.global_every:
        w = cfg.window or 1024
        return jnp.array(
            [-1 if (i % cfg.global_every == cfg.global_every - 1) else w
             for i in range(L)], dtype=jnp.int32)
    if cfg.window:
        return jnp.full((L,), cfg.window, dtype=jnp.int32)
    return jnp.full((L,), -1, dtype=jnp.int32)


# ---------------------------------------------------------------------------------
# attention with streaming prefill
# ---------------------------------------------------------------------------------

def _stream_attention(q, k, v, window: jax.Array, q_offset: int = 0):
    """Blockwise-softmax causal attention, O(S * chunk) memory.

    q: (B, S, Hq, D); window: scalar int32 (-1 = unlimited).
    """
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    nq = S // STREAM_CHUNK
    qc = q.reshape(B, nq, STREAM_CHUNK, Hq, D).transpose(1, 0, 2, 3, 4)

    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    k_pos = jnp.arange(k.shape[1])

    def chunk_fn(_, qi_i):
        qi, i = qi_i
        qf = qi.astype(jnp.float32) * (D ** -0.5)
        qf = qf.reshape(B, STREAM_CHUNK, Hkv, group, D)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf)
        q_pos = i * STREAM_CHUNK + jnp.arange(STREAM_CHUNK) + q_offset
        m = k_pos[None, :] <= q_pos[:, None]
        m &= jnp.where(window > 0, k_pos[None, :] > q_pos[:, None] - window, True)
        logits = jnp.where(m[None, None, None], logits, attn_mod.NEG_INF)
        w = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", w, vf)
        return None, out.reshape(B, STREAM_CHUNK, Hq, D).astype(qi.dtype)

    _, outs = jax.lax.scan(chunk_fn, None, (qc, jnp.arange(nq)))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, S, Hq, D)


def _prefill_attention(q, k, v, window: jax.Array, use_kernel: bool):
    S = q.shape[1]
    if use_kernel:
        from repro.kernels.flash_attention.ops import flash_attention_dyn
        return flash_attention_dyn(q, k, v, window)
    if S > STREAM_THRESHOLD and S % STREAM_CHUNK == 0:
        return _stream_attention(q, k, v, window)
    mask = attn_mod.attention_mask(S, S, causal=True, window=None)
    k_pos = jnp.arange(S)
    wmask = jnp.where(window > 0,
                      k_pos[None, :] > k_pos[:, None] - window, True)
    return sdpa(q, k, v, mask & wmask)


# ---------------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------------

def _project_qkv(x, bp, cfg: ModelConfig):
    B, S, d = x.shape
    hd = cfg.resolved_head_dim
    q = x @ bp["wq"]
    k = x @ bp["wk"]
    v = x @ bp["wv"]
    if cfg.qkv_bias:
        q = q + bp["bq"]
        k = k + bp["bk"]
        v = v + bp["bv"]
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    return q, k, v


def _ffn(h, bp, cfg: ModelConfig):
    if cfg.moe:
        import os
        from repro.distributed import moe_ep
        mesh = moe_ep.get_ep_mesh()
        if mesh is not None and "model" in mesh.axis_names \
                and os.environ.get("REPRO_MOE_EP", "1") == "1":
            return moe_ep.moe_ffn_ep(h, bp["moe"], cfg.moe, mesh)
        B, S, d = h.shape
        out, aux = moe_ffn(h.reshape(B * S, d), bp["moe"], cfg.moe)
        return out.reshape(B, S, d), aux
    return gated_mlp(h, bp["mlp"]["w_gate"], bp["mlp"]["w_up"], bp["mlp"]["w_down"]), 0.0


def block_forward(x, bp, window, cos, sin, cfg: ModelConfig, use_kernel: bool):
    """Training / prefill block: x (B, S, d)."""
    h = rms_norm(x, bp["ln1"], cfg.norm_eps)
    q, k, v = _project_qkv(h, bp, cfg)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    o = _prefill_attention(q, k, v, window, use_kernel)
    x = x + o.reshape(*x.shape[:2], -1) @ bp["wo"]
    h = rms_norm(x, bp["ln2"], cfg.norm_eps)
    f, aux = _ffn(h, bp, cfg)
    return x + f, (k, v), aux


def block_decode(x, bp, window, cache_k, cache_v, pos, cos, sin, cfg: ModelConfig,
                 cache_ks=None, cache_vs=None, block_table=None,
                 use_kernel: bool = False):
    """One-token decode.  x: (B, 1, d).

    KV storage sits behind the cache-ops interface (`repro.serving.kvcache`):
    dense caches are (B, S_max, Hkv, hd) with ``pos`` a scalar (uniform batch)
    or (B,) vector (continuous batching); with ``block_table`` (B, n_pages)
    the caches are paged pools (P, page_size, Hkv, hd) shared by all rows, and
    the new token scatters into the row's current page.
    ``cache_ks/vs``: per-token/head int8 scales when kv_cache_dtype == int8."""
    int8_kv = cache_ks is not None
    h = rms_norm(x, bp["ln1"], cfg.norm_eps)
    q, k, v = _project_qkv(h, bp, cfg)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    if int8_kv:
        k_store, k_sc = _kv_quantize(k)
        v_store, v_sc = _kv_quantize(v)
    else:
        k_store, v_store = k, v
    if block_table is not None:
        ops = kvcache.PagedOps(block_table)
    elif jnp.ndim(pos) == 1:
        ops = kvcache.DenseVectorOps()
    else:
        ops = kvcache.DenseScalarOps()
    cache_k = ops.write(cache_k, k_store, pos)
    cache_v = ops.write(cache_v, v_store, pos)
    if int8_kv:
        cache_ks = ops.write(cache_ks, k_sc, pos)
        cache_vs = ops.write(cache_vs, v_sc, pos)
    if block_table is not None and use_kernel:
        # Pallas path: attend over the page pool directly, no gather; int8
        # pools carry their scales into the kernel and dequantize in-register
        from repro.kernels.decode_attention.ops import decode_attention_paged
        o = decode_attention_paged(q, cache_k, cache_v, block_table, pos + 1,
                                   window=window,
                                   k_scale=cache_ks if int8_kv else None,
                                   v_scale=cache_vs if int8_kv else None)
    else:
        k_eff = ops.view(cache_k)
        v_eff = ops.view(cache_v)
        if int8_kv:
            k_eff = _kv_dequantize(k_eff, ops.view(cache_ks), cfg.dtype)
            v_eff = _kv_dequantize(v_eff, ops.view(cache_vs), cfg.dtype)
        mask = ops.mask(k_eff.shape[1], pos, window)
        o = sdpa(q, k_eff, v_eff, mask)
    x = x + o.reshape(*x.shape[:2], -1) @ bp["wo"]
    h = rms_norm(x, bp["ln2"], cfg.norm_eps)
    f, _ = _ffn(h, bp, cfg)
    return x + f, (cache_k, cache_v, cache_ks, cache_vs)


def block_verify(x, bp, window, layer, pools, pos, cos, sin, cfg: ModelConfig,
                 block_table, use_kernel: bool = False):
    """Span decode: x (B, T, d), each row's T tokens at consecutive logical
    positions starting at ``pos[b]``.

    This is the mixed chunked-prefill / speculative-verify block: the span's
    KV is scattered into the paged pool first (so query t attends its own
    key), then per-query causal attention runs over the row's pages -- via
    the mixed Pallas kernel or the gather + span-mask route.  T = 1 is
    exactly :func:`block_decode`.

    ``pools``: every layer's pools, {"k", "v"[, "k_scale", "v_scale"]} of
    (L, P, page_size, ...); the block writes and reads layer ``layer``
    through the (L * P, ...) page view, where layer l's page p is page
    l * P + p, so the pools are updated in place and no layer is copied
    out.  Returns (x, pools)."""
    h = rms_norm(x, bp["ln1"], cfg.norm_eps)
    q, k, v = _project_qkv(h, bp, cfg)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    int8_kv = cfg.kv_cache_dtype == "int8"
    if int8_kv:
        (kq, ks), (vq, vs) = _kv_quantize(k), _kv_quantize(v)
        new = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    else:
        new = {"k": k, "v": v}
    L, P = pools["k"].shape[:2]
    ops = kvcache.PagedOps(block_table + layer * P)

    def pages(pool):
        return pool.reshape((L * P,) + pool.shape[2:])

    def write(pool, span):
        return ops.write_span(pages(pool), span, pos).reshape(pool.shape)

    pools = jax.tree.map(write, pools, new)
    if use_kernel:
        from repro.kernels.decode_attention.ops import decode_attention_mixed
        o = decode_attention_mixed(q, pools["k"], pools["v"], block_table, pos,
                                   layer, window=window,
                                   k_scale=pools.get("k_scale"),
                                   v_scale=pools.get("v_scale"))
    else:
        k_eff = ops.view(pages(pools["k"]))
        v_eff = ops.view(pages(pools["v"]))
        if int8_kv:
            k_eff = _kv_dequantize(k_eff, ops.view(pages(pools["k_scale"])),
                                   cfg.dtype)
            v_eff = _kv_dequantize(v_eff, ops.view(pages(pools["v_scale"])),
                                   cfg.dtype)
        mask = ops.span_mask(k_eff.shape[1], pos, q.shape[1], window)
        o = sdpa(q, k_eff, v_eff, mask)
    x = x + o.reshape(*x.shape[:2], -1) @ bp["wo"]
    h = rms_norm(x, bp["ln2"], cfg.norm_eps)
    f, _ = _ffn(h, bp, cfg)
    return x + f, pools


# ---------------------------------------------------------------------------------
# model-level functions
# ---------------------------------------------------------------------------------

def _embed_in(params, batch, cfg: ModelConfig):
    if cfg.input_mode == "embeddings":
        return batch["embeds"].astype(cfg.dtype)
    return params["embed"][batch["tokens"]]


def _lm_head(params, h, cfg: ModelConfig):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (h @ w).astype(jnp.float32)


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


# replint: traced -- jitted from the serving engine
def forward(params, batch, cfg: ModelConfig):
    """Full-sequence forward -> (logits (B,S,V) f32, aux).  Always the jnp
    path: training differentiates it."""
    x = _embed_in(params, batch, cfg)
    B, S, _ = x.shape
    cos, sin = rope_tables(jnp.arange(S), cfg.resolved_head_dim, cfg.rope_theta)
    windows = layer_windows(cfg)

    def body(x, layer):
        bp, w = layer
        x, _, aux = block_forward(x, bp, w, cos, sin, cfg, use_kernel=False)
        return x, aux

    body = _remat(body, cfg)
    x, auxs = jax.lax.scan(body, x, (params["blocks"], windows))
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    return _lm_head(params, x, cfg), jnp.sum(auxs)


# replint: traced -- jitted from the serving engine
def loss_fn(params, batch, cfg: ModelConfig):
    logits, aux = forward(params, batch, cfg)
    tgt = batch["targets"]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    ll = jnp.take_along_axis(logp, tgt[:, 1:, None], axis=-1)[..., 0]
    mask = (tgt[:, 1:] >= 0).astype(jnp.float32)
    loss = -(ll * mask).sum() / jnp.clip(mask.sum(), 1.0)
    return loss + 0.01 * aux, {"ce": loss, "aux": aux}


def _kv_quantize(x):
    """x: (..., hd) -> (int8 values, f32 scale over the last dim)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _kv_dequantize(q, scale, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    hd = cfg.resolved_head_dim
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, hd)
    if cfg.kv_cache_dtype == "int8":
        sshape = shape[:-1] + (1,)
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(sshape, jnp.float32),
            "v_scale": jnp.zeros(sshape, jnp.float32),
        }
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
    }


# replint: traced -- jitted from the serving engine
def prefill(params, batch, cfg: ModelConfig, max_len: int | None = None,
            *, use_kernel: bool = False, last_idx=None):
    """Run the prompt, return (last-position logits, cache dict).

    ``last_idx``: traced position of the true last prompt token -- a scalar,
    or a (B,) vector for batched bucketed prefill (each row selects its own
    last position).  Bucketed prefill pads prompts to a fixed power-of-two
    length so one compiled shape serves the whole bucket; the causal mask
    keeps positions <= last_idx independent of the padding, and ``last_idx``
    selects the real logits."""
    x = _embed_in(params, batch, cfg)
    B, S, _ = x.shape
    max_len = max_len or S
    cos, sin = rope_tables(jnp.arange(S), cfg.resolved_head_dim, cfg.rope_theta)
    windows = layer_windows(cfg)

    def body(x, layer):
        bp, w = layer
        x, (k, v), _ = block_forward(x, bp, w, cos, sin, cfg, use_kernel)
        return x, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], windows))
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    if last_idx is None:
        x_last = x[:, -1:]
    elif jnp.ndim(last_idx) == 0:
        x_last = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
    else:
        x_last = x[jnp.arange(B), last_idx][:, None]          # per-row select
    logits = _lm_head(params, x_last, cfg)
    if max_len > S:
        pad = ((0, 0), (0, 0), (0, max_len - S), (0, 0), (0, 0))
        ks = jnp.pad(ks, pad)
        vs = jnp.pad(vs, pad)
    if cfg.kv_cache_dtype == "int8":
        kq, ksc = _kv_quantize(ks)
        vq, vsc = _kv_quantize(vs)
        return logits, {"k": kq, "v": vq, "k_scale": ksc, "v_scale": vsc}
    return logits, {"k": ks.astype(cfg.dtype), "v": vs.astype(cfg.dtype)}


# replint: traced -- jitted from the serving engine
def decode_step(params, cache, token, pos, cfg: ModelConfig, *,
                block_table=None, use_kernel: bool = False):
    """token: (B, 1) int32 (or (B,1,d) embeds); pos: scalar int32 count of
    cached tokens, or (B,) per-row counts (continuous batching).  With
    ``block_table`` (B, n_pages) the cache leaves are paged pools
    (L, P, page_size, ...) -- see `repro.serving.kvcache`.  Returns
    (logits (B,1,V), new cache)."""
    if cfg.input_mode == "embeddings" and token.ndim == 3:
        x = token.astype(cfg.dtype)
    else:
        x = params["embed"][token]
    if jnp.ndim(pos) == 1:
        cos, sin = rope_tables(pos[:, None], cfg.resolved_head_dim, cfg.rope_theta)
    else:
        cos, sin = rope_tables(jnp.array([pos]), cfg.resolved_head_dim, cfg.rope_theta)
    windows = layer_windows(cfg)

    int8_kv = cfg.kv_cache_dtype == "int8"

    def body(x, layer):
        if int8_kv:
            bp, w, ck, cv, cks, cvs = layer
        else:
            bp, w, ck, cv = layer
            cks = cvs = None
        x, (ck, cv, cks, cvs) = block_decode(x, bp, w, ck, cv, pos, cos, sin, cfg,
                                             cache_ks=cks, cache_vs=cvs,
                                             block_table=block_table,
                                             use_kernel=use_kernel)
        return x, ((ck, cv, cks, cvs) if int8_kv else (ck, cv))

    if int8_kv:
        x, (ks, vs, kss, vss) = jax.lax.scan(
            body, x, (params["blocks"], windows, cache["k"], cache["v"],
                      cache["k_scale"], cache["v_scale"]))
        new_cache = {"k": ks, "v": vs, "k_scale": kss, "v_scale": vss}
    else:
        x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], windows,
                                             cache["k"], cache["v"]))
        new_cache = {"k": ks, "v": vs}
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    return _lm_head(params, x, cfg), new_cache


# replint: traced -- jitted from the serving engine mixed step
def verify_step(params, cache, tokens, pos, cfg: ModelConfig, *,
                block_table, use_kernel: bool = False,
                lmhead_kernel: bool = False, lmhead_block_v: int = 0):
    """Score a T-token span per row in one forward: tokens (B, T) int32 at
    logical positions ``pos[b] + t`` over a paged cache.

    Returns ``(tok (B, T) int32, lp (B, T) f32, new_cache)``: the greedy
    next token and its logprob *after each span position*, computed through
    the fused lm-head epilogue so the (B, T, V) logits tensor is never
    materialized.  One function serves every mixed-step role:

    * decode row (T == 1): ``tok[:, 0]`` is the next token -- identical to
      ``decode_step`` + ``greedy_epilogue``;
    * speculative verify (T == 1 + d): ``tok[:, j]`` is the model's true
      output after draft j, giving the acceptance rule its oracle;
    * prefill chunk: the span's KV is committed, ``tok[:, -1]`` seeds
      decode when the chunk is the prompt's last.
    """
    from repro.kernels.sampling.ops import fused_lmhead_greedy
    x = params["embed"][tokens]
    T = tokens.shape[1]
    cos, sin = rope_tables(pos[:, None] + jnp.arange(T)[None, :],
                           cfg.resolved_head_dim, cfg.rope_theta)
    windows = layer_windows(cfg)

    # the pools ride in the scan's carry, so each layer writes its span
    # into them in place; as xs and ys, every layer's pool would be copied
    # out and stacked back in every iteration
    def body(carry, layer):
        x, pools = carry
        bp, w, idx = layer
        return block_verify(x, bp, w, idx, pools, pos, cos, sin, cfg,
                            block_table=block_table,
                            use_kernel=use_kernel), None

    (x, new_cache), _ = jax.lax.scan(
        body, (x, cache),
        (params["blocks"], windows, jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    w_head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    tok, lp = fused_lmhead_greedy(x, w_head, use_kernel=lmhead_kernel,
                                  block_v=lmhead_block_v)
    return tok, lp, new_cache


__all__ = [
    "init_params", "forward", "loss_fn", "prefill", "decode_step", "init_cache",
    "verify_step", "layer_windows", "block_forward", "block_decode",
    "block_verify",
]
