"""Plain float32 reference of a dense decoder, from the published equations.

RMSNorm before attention, before the MLP and before the head; rotary
position embedding on the two halves of each head (theta from the config);
grouped-query attention with a causal mask; optional biases on Q, K and V;
a SwiGLU MLP; a tied or untied head.  Every matmul runs at
``Precision.HIGHEST`` in float32.  Nothing here imports the program.

The weights are made here too, from the seed, with no help from the
program: the seed's key splits into (embedding, blocks, head), the blocks'
key into one key per layer and each layer's key into ten; every matrix is a
standard normal draw scaled by its fan-in to the power -1/2 (the embedding
by 0.02, the MLP's down projection by ``d_ff ** -0.5``), rounded to
bfloat16, the type the model is served in.  Norm gains are one and biases
zero.  The reference then computes in float32 on those bfloat16 values.

The check runs layer by layer, one sequence at a time, with queries in
blocks, so it fits beside nothing else on one chip.  ``control=True`` runs
a second stream beside it in float8 (e4m3): the next precision below
bfloat16, which the check has to fail.  The control's matmuls take float8 operands: weights
rounded once, activations rounded as they enter each matmul (one scale per
row), with float32 accumulation.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512            # queries per attention block
LEN_BUCKET = 512         # sequences are zero-padded to a multiple of this


@dataclass(frozen=True)
class Dims:
    d: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int
    n_layers: int
    theta: float
    eps: float
    tied: bool
    qkv_bias: bool

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        d, hq = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
        return cls(d=d, n_heads=hq, n_kv=int(cfg["num_key_value_heads"]),
                   head_dim=int(cfg.get("head_dim") or d // hq),
                   d_ff=int(cfg["intermediate_size"]),
                   vocab=int(cfg["vocab_size"]),
                   n_layers=int(cfg["num_hidden_layers"]),
                   theta=float(cfg["rope_theta"]),
                   eps=float(cfg["rms_norm_eps"]),
                   tied=bool(cfg["tie_word_embeddings"]),
                   qkv_bias=bool(cfg["architecture"]["qkv_bias"]))


def params_key(seed: int):
    """The key the served weights are made from, for any whole seed."""
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0xFFFFFFFF)


def _draw(key, shape, std):
    return ((jax.random.normal(key, shape, dtype=jnp.float32) * std)
            .astype(jnp.bfloat16).astype(jnp.float32))


def _fp8(w, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@partial(jax.jit, static_argnames=("dm", "fp8"))
def _layer_weights(key, dm: Dims, fp8: bool):
    d, hd = dm.d, dm.head_dim
    q, kv = dm.n_heads * hd, dm.n_kv * hd
    ks = jax.random.split(key, 10)
    w = {"wq": _draw(ks[0], (d, q), d ** -0.5),
         "wk": _draw(ks[1], (d, kv), d ** -0.5),
         "wv": _draw(ks[2], (d, kv), d ** -0.5),
         "wo": _draw(ks[3], (q, d), q ** -0.5),
         "wg": _draw(ks[4], (d, dm.d_ff), d ** -0.5),
         "wu": _draw(ks[5], (d, dm.d_ff), d ** -0.5),
         "wd": _draw(ks[6], (dm.d_ff, d), dm.d_ff ** -0.5)}
    if fp8:
        w = {k: _fp8(v, 0) for k, v in w.items()}
    if dm.qkv_bias:
        w.update(bq=jnp.zeros((q,)), bk=jnp.zeros((kv,)), bv=jnp.zeros((kv,)))
    return w


@partial(jax.jit, static_argnames=("dm", "fp8"))
def _embedding(key, dm: Dims, fp8: bool):
    """The (V, d) token embedding in float32."""
    emb = _draw(jax.random.split(key, 3)[0], (dm.vocab, dm.d), 0.02)
    return _fp8(emb, 1) if fp8 else emb


@partial(jax.jit, static_argnames=("dm", "fp8"))
def _head_weight(key, dm: Dims, fp8: bool):
    """The (d, V) head in float32: the embedding's transpose when tied."""
    if dm.tied:
        return _embedding(key, dm, fp8).T
    head = _draw(jax.random.split(key, 3)[2], (dm.d, dm.vocab), dm.d ** -0.5)
    return _fp8(head, 0) if fp8 else head


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, dm: Dims):
    half = dm.head_dim // 2
    inv = 1.0 / (dm.theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _mm(a, w, fp8: bool):
    """a @ w in float32; with ``fp8`` the activations are rounded to float8
    first, one scale per row, as a float8 matmul would take them."""
    return jnp.matmul(_fp8(a, -1) if fp8 else a, w, precision=HI)


@partial(jax.jit, static_argnames=("dm", "fp8"))
def _layer(x, w, dm: Dims, fp8: bool):
    """One decoder layer over one zero-padded sequence x (S, d)."""
    S = x.shape[0]
    hd, g = dm.head_dim, dm.n_heads // dm.n_kv
    pos = jnp.arange(S)
    h = _rms(x, dm.eps)
    q = _mm(h, w["wq"], fp8)
    k = _mm(h, w["wk"], fp8)
    v = _mm(h, w["wv"], fp8)
    if dm.qkv_bias:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = _rope(q.reshape(S, dm.n_heads, hd), pos, dm)
    k = _rope(k.reshape(S, dm.n_kv, hd), pos, dm)
    v = v.reshape(S, dm.n_kv, hd)
    qb = q.reshape(S // Q_BLOCK, Q_BLOCK, dm.n_kv, g, hd)

    def block(args):
        i, qi = args
        s = jnp.einsum("qkgd,skd->kgqs", qi, k, precision=HI) * hd ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)

    o = jax.lax.map(block, (jnp.arange(S // Q_BLOCK), qb))
    o = o.reshape(S, dm.n_heads * hd)
    x = x + _mm(o, w["wo"], fp8)
    h = _rms(x, dm.eps)
    f = jax.nn.silu(_mm(h, w["wg"], fp8)) * _mm(h, w["wu"], fp8)
    return x + _mm(f, w["wd"], fp8)


@partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, head, rows, eps: float, fp8: bool):
    """Float32 logits (n, V) at the given rows of x."""
    return _mm(_rms(x[rows], eps), head, fp8)


def _logits(x, head, first: int, n: int, eps: float,
            fp8: bool) -> np.ndarray:
    """Logits at rows ``first .. first + n - 1``; the rows are padded to a
    multiple of ``LEN_BUCKET`` so that few shapes compile."""
    m = -(-n // LEN_BUCKET) * LEN_BUCKET
    rows = np.minimum(first + np.arange(m), x.shape[0] - 1)
    return np.asarray(_head(x, head, jnp.asarray(rows), eps, fp8))[:n]


def _stats(ref, served, other=None):
    """Per served position: the gap of the served token below the
    reference's best, its reference log-probability, and with ``other``
    (the control's logits) the reference gap of the control's first token
    and the control's log-probability of the served token."""
    ref = np.asarray(ref, np.float64)
    idx = np.arange(len(served))
    best = ref.max(axis=1)
    lse = best + np.log(np.exp(ref - best[:, None]).sum(axis=1))
    out = {"gap": best - ref[idx, served], "lp": ref[idx, served] - lse}
    if other is not None:
        other = np.asarray(other, np.float64)
        top = other.argmax(axis=1)
        ob = other.max(axis=1)
        olse = ob + np.log(np.exp(other - ob[:, None]).sum(axis=1))
        out["ctrl_gap"] = best - ref[idx, top]
        out["ctrl_lp"] = other[idx, served] - olse
    return out


def check_sequences(cfg: dict, seed: int, seqs, *, control: bool = False):
    """Reference readings for served requests.

    ``seqs``: [(prompt (P,) int, served tokens (n,) int)].  Returns one dict
    per request: ``max_gap`` (the widest gap by which a served token's
    reference logit lies below the reference's best at its position) and
    ``mean_lp`` (the reference's mean log-probability of the served
    tokens); with ``control``, ``ctrl_max_gap`` and ``ctrl_mean_lp``, the
    same readings of the float8 control."""
    dm = Dims.from_config(cfg)
    key = params_key(seed)
    streams = (False, True) if control else (False,)
    xs = {}
    for f in streams:
        emb = _embedding(key, dm, f)
        xs[f] = []
        for prompt, served in seqs:
            toks = np.concatenate([np.asarray(prompt), np.asarray(served)[:-1]])
            S = -(-len(toks) // LEN_BUCKET) * LEN_BUCKET
            padded = np.zeros(S, np.int32)
            padded[:len(toks)] = toks
            xs[f].append(emb[jnp.asarray(padded)])
        del emb
    layer_keys = jax.random.split(jax.random.split(key, 3)[1], dm.n_layers)
    for li in range(dm.n_layers):
        for f in streams:
            w = _layer_weights(layer_keys[li], dm, f)
            xs[f] = [_layer(x, w, dm, f) for x in xs[f]]
            del w
    logits = {}
    for f in streams:
        head = _head_weight(key, dm, f)
        logits[f] = [_logits(x, head, len(p) - 1, len(s), dm.eps, f)
                     for x, (p, s) in zip(xs[f], seqs)]
        del head
    results = []
    for i, (prompt, served) in enumerate(seqs):
        served = np.asarray(served, np.int64)
        st = _stats(logits[False][i], served,
                    logits[True][i] if control else None)
        r = {"max_gap": float(st["gap"].max()), "mean_lp": float(st["lp"].mean())}
        if control:
            r["ctrl_max_gap"] = float(st["ctrl_gap"].max())
            r["ctrl_mean_lp"] = float(st["ctrl_lp"].mean())
        results.append(r)
    return results


__all__ = ["Dims", "check_sequences", "params_key"]
