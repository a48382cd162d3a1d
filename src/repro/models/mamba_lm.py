"""Pure Mamba-2 LM (mamba2-1.3b) and the Zamba2-style hybrid (SSM stack with a
single shared attention(+MLP) block applied every N layers)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import sdpa
from repro.models.common import (
    ModelConfig, apply_rope, gated_mlp, init_dense, rms_norm, rope_tables,
)
from repro.models.lm import (
    _lm_head, _prefill_attention, _project_qkv, _remat, init_block_params,
)
from repro.models.ssm import mamba2_block


# ---------------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------------

def init_mamba_layer(rng, cfg: ModelConfig):
    d = cfg.d_model
    s = cfg.ssm
    d_in = s.expand * d
    h = d_in // s.head_dim
    g, n, w = s.n_groups, s.d_state, s.conv_width
    ks = jax.random.split(rng, 6)
    # in_proj is split into semantically separate matrices so tensor parallelism
    # can shard z/x/dt by SSM head while replicating the (group-shared) B/C
    # projections -- the standard Mamba TP layout.
    return {
        "ln": jnp.ones((d,), cfg.dtype),
        "w_z": init_dense(ks[0], (d, d_in), cfg.dtype),
        "w_x": init_dense(ks[1], (d, d_in), cfg.dtype),
        "w_bc": init_dense(ks[2], (d, 2 * g * n), cfg.dtype),
        "w_dt": init_dense(ks[3], (d, h), cfg.dtype),
        "conv_x": init_dense(ks[4], (w, d_in), cfg.dtype, scale=w ** -0.5),
        "conv_bc": init_dense(ks[5], (w, 2 * g * n), cfg.dtype, scale=w ** -0.5),
        "A_log": jnp.zeros((h,), jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "norm": jnp.ones((d_in,), cfg.dtype),
        "out_proj": init_dense(ks[2], (d_in, d), cfg.dtype),
    }


def init_params(rng, cfg: ModelConfig):
    k_embed, k_blocks, k_head, k_attn = jax.random.split(rng, 4)
    blocks = jax.vmap(lambda k: init_mamba_layer(k, cfg))(
        jax.random.split(k_blocks, cfg.n_layers))
    params = {
        "embed": init_dense(k_embed, (cfg.vocab, cfg.d_model), cfg.dtype, scale=0.02),
        "blocks": blocks,
        "ln_f": jnp.ones((cfg.d_model,), cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_dense(k_head, (cfg.d_model, cfg.vocab), cfg.dtype)
    if cfg.shared_attn_every:
        params["shared_attn"] = init_block_params(k_attn, cfg)  # attn + mlp block
    return params


def _n_attn_calls(cfg: ModelConfig) -> int:
    if not cfg.shared_attn_every:
        return 0
    return sum(1 for i in range(cfg.n_layers)
               if i % cfg.shared_attn_every == cfg.shared_attn_every - 1)


# ---------------------------------------------------------------------------------
# forward (training / prefill)
# ---------------------------------------------------------------------------------

def _shared_attn_forward(x, params, cos, sin, cfg: ModelConfig, use_kernel: bool):
    bp = params["shared_attn"]
    h = rms_norm(x, bp["ln1"], cfg.norm_eps)
    q, k, v = _project_qkv(h, bp, cfg)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    o = _prefill_attention(q, k, v, jnp.int32(-1), use_kernel)
    x = x + o.reshape(*x.shape[:2], -1) @ bp["wo"]
    h = rms_norm(x, bp["ln2"], cfg.norm_eps)
    f = gated_mlp(h, bp["mlp"]["w_gate"], bp["mlp"]["w_up"], bp["mlp"]["w_down"])
    return x + f, (k, v)


def forward(params, batch, cfg: ModelConfig, *, use_kernel: bool = False,
            collect_cache: bool = False):
    x = params["embed"][batch["tokens"]] if cfg.input_mode == "tokens" \
        else batch["embeds"].astype(cfg.dtype)
    B, S, _ = x.shape
    every = cfg.shared_attn_every
    cos = sin = None
    if every:
        cos, sin = rope_tables(jnp.arange(S), cfg.resolved_head_dim, cfg.rope_theta)

    ssm_states, conv_states, attn_kv = [], [], []

    def mamba_body(x, bp):
        h = rms_norm(x, bp["ln"], cfg.norm_eps)
        y, st, cv = mamba2_block(h, bp, cfg.ssm, use_kernel=use_kernel)
        return x + y, (st, cv)

    mamba_body = _remat(mamba_body, cfg)

    if not every:
        x, (sts, cvs) = jax.lax.scan(mamba_body, x, params["blocks"])
    else:
        # super-block structure: scan chunks of `every` ssm layers, then the shared
        # attention block (same weights each call, per-call KV cache).
        L = cfg.n_layers
        n_super = L // every
        rest = L - n_super * every
        blocks = params["blocks"]
        head = jax.tree.map(lambda a: a[: n_super * every].reshape(
            (n_super, every) + a.shape[1:]), blocks)
        sts_all, cvs_all = [], []
        for j in range(n_super):    # n_super ~ 9: unrolled outer, scanned inner
            sub = jax.tree.map(lambda a: a[j], head)
            x, (st, cv) = jax.lax.scan(mamba_body, x, sub)
            sts_all.append(st)
            cvs_all.append(cv)
            x, kv = _shared_attn_forward(x, params, cos, sin, cfg, use_kernel)
            attn_kv.append(kv)
        if rest:
            tail = jax.tree.map(lambda a: a[n_super * every:], blocks)
            x, (st, cv) = jax.lax.scan(mamba_body, x, tail)
            sts_all.append(st)
            cvs_all.append(cv)
        sts = jnp.concatenate(sts_all, 0)
        cvs = jnp.concatenate(cvs_all, 0)

    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = _lm_head(params, x, cfg)
    if collect_cache:
        cache = {"ssm": sts, "conv": cvs}
        if every:
            cache["attn_k"] = jnp.stack([k for k, _ in attn_kv])
            cache["attn_v"] = jnp.stack([v for _, v in attn_kv])
        return logits, cache
    return logits, jnp.float32(0.0)


def loss_fn(params, batch, cfg: ModelConfig):
    logits, _ = forward(params, batch, cfg)
    tgt = batch["targets"]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    ll = jnp.take_along_axis(logp, tgt[:, 1:, None], axis=-1)[..., 0]
    mask = (tgt[:, 1:] >= 0).astype(jnp.float32)
    loss = -(ll * mask).sum() / jnp.clip(mask.sum(), 1.0)
    return loss, {"ce": loss}


# ---------------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    h = d_in // s.head_dim
    conv_ch = d_in + 2 * s.n_groups * s.d_state
    cache = {
        "ssm": jnp.zeros((cfg.n_layers, batch, h, s.head_dim, s.d_state), jnp.float32),
        "conv": jnp.zeros((cfg.n_layers, batch, s.conv_width, conv_ch), cfg.dtype),
    }
    if cfg.shared_attn_every:
        calls = _n_attn_calls(cfg)
        hd = cfg.resolved_head_dim
        cache["attn_k"] = jnp.zeros((calls, batch, max_len, cfg.n_kv_heads, hd), cfg.dtype)
        cache["attn_v"] = jnp.zeros((calls, batch, max_len, cfg.n_kv_heads, hd), cfg.dtype)
    return cache


def prefill(params, batch, cfg: ModelConfig, max_len: int | None = None,
            *, use_kernel: bool = False):
    logits, cache = forward(params, batch, cfg, use_kernel=use_kernel,
                            collect_cache=True)
    S = (batch["tokens"].shape[1] if cfg.input_mode == "tokens"
         else batch["embeds"].shape[1])
    max_len = max_len or S
    if cfg.shared_attn_every and max_len > S:
        pad = ((0, 0), (0, 0), (0, max_len - S), (0, 0), (0, 0))
        cache["attn_k"] = jnp.pad(cache["attn_k"], pad)
        cache["attn_v"] = jnp.pad(cache["attn_v"], pad)
    return logits[:, -1:], cache


def decode_step(params, cache, token, pos, cfg: ModelConfig):
    x = params["embed"][token]
    every = cfg.shared_attn_every
    cos = sin = None
    if every:
        cos, sin = rope_tables(jnp.array([pos]), cfg.resolved_head_dim, cfg.rope_theta)

    def mamba_body(x, layer):
        bp, st, cv = layer
        h = rms_norm(x, bp["ln"], cfg.norm_eps)
        y, st, cv = mamba2_block(h, bp, cfg.ssm, state=st, conv_state=cv, decode=True)
        return x + y, (st, cv)

    if not every:
        x, (sts, cvs) = jax.lax.scan(
            mamba_body, x, (params["blocks"], cache["ssm"], cache["conv"]))
        new_cache = {"ssm": sts, "conv": cvs}
    else:
        L = cfg.n_layers
        n_super = L // every
        rest = L - n_super * every
        blocks = params["blocks"]
        split = lambda a, lo, hi: jax.tree.map(lambda t: t[lo:hi], a)
        sts_all, cvs_all, ks_all, vs_all = [], [], [], []
        bp_attn = params["shared_attn"]
        for j in range(n_super):
            lo, hi = j * every, (j + 1) * every
            x, (st, cv) = jax.lax.scan(
                mamba_body, x,
                (split(blocks, lo, hi), cache["ssm"][lo:hi], cache["conv"][lo:hi]))
            sts_all.append(st); cvs_all.append(cv)
            # shared attention decode, call-j cache
            h = rms_norm(x, bp_attn["ln1"], cfg.norm_eps)
            q, k, v = _project_qkv(h, bp_attn, cfg)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
            ck = jax.lax.dynamic_update_slice(cache["attn_k"][j], k.astype(cfg.dtype),
                                              (0, pos, 0, 0))
            cv_ = jax.lax.dynamic_update_slice(cache["attn_v"][j], v.astype(cfg.dtype),
                                               (0, pos, 0, 0))
            valid = jnp.arange(ck.shape[1]) < pos + 1
            o = sdpa(q, ck, cv_, valid[None, :])
            x = x + o.reshape(*x.shape[:2], -1) @ bp_attn["wo"]
            h2 = rms_norm(x, bp_attn["ln2"], cfg.norm_eps)
            x = x + gated_mlp(h2, bp_attn["mlp"]["w_gate"], bp_attn["mlp"]["w_up"],
                              bp_attn["mlp"]["w_down"])
            ks_all.append(ck); vs_all.append(cv_)
        if rest:
            lo = n_super * every
            x, (st, cv) = jax.lax.scan(
                mamba_body, x,
                (split(blocks, lo, L), cache["ssm"][lo:], cache["conv"][lo:]))
            sts_all.append(st); cvs_all.append(cv)
        new_cache = {
            "ssm": jnp.concatenate(sts_all, 0),
            "conv": jnp.concatenate(cvs_all, 0),
            "attn_k": jnp.stack(ks_all),
            "attn_v": jnp.stack(vs_all),
        }

    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    return _lm_head(params, x, cfg), new_cache


__all__ = ["init_params", "forward", "loss_fn", "prefill", "decode_step", "init_cache"]
