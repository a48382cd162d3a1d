"""Expert-parallel MoE via shard_map: the hillclimbed replacement for the
pjit-scatter dispatch (EXPERIMENTS.md SSPerf).

Why: under plain SPMD, the sort-based dispatch's cross-sharding gathers
(x[token_idx] with tokens data-sharded feeding an expert-sharded buffer)
degenerate into full (T*k, D) f32 REPLICATED arrays all-reduced over the model
axis -- measured 7 x 68.7 GB all-reduces per olmoe train step.

Scheme (zero-communication dispatch, one psum combine):
* tokens stay on their (pod, data) shard; every model rank sees the same local
  tokens (activations are replicated over 'model' between TP blocks anyway);
* each model rank owns E/mp experts; routing is computed redundantly (cheap,
  deterministic) on every rank;
* each rank scatters ONLY the tokens routed to its own experts into its local
  (E_loc, C_loc, D) buffer -- no inter-device traffic at all;
* after the expert FFN, each rank holds partial outputs for the local tokens
  that visited its experts; one psum over 'model' completes the combine:
  per layer traffic = |activations| instead of k x |token copies| x E-spread.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import MoEConfig
from repro.models.moe import load_balance_loss, router_topk

#: set by launchers (dryrun / train) when a mesh is active; models pick it up.
_EP_MESH = None


def set_ep_mesh(mesh) -> None:
    global _EP_MESH
    _EP_MESH = mesh


def get_ep_mesh():
    return _EP_MESH


def _local_moe(x, params, cfg: MoEConfig, model_axis: str, mp: int):
    """Per-device body: x (T_loc, D) local tokens; params expert-sharded
    (E_loc, D, F) on ``model_axis``; ``mp`` = static model-axis size."""
    T, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    rank = jax.lax.axis_index(model_axis)
    E_loc = E // mp
    C = max(int(T * k * cfg.capacity_factor / E), min(4, T * k))

    weights, experts, logits = router_topk(x, params["router"], cfg)

    flat_e = experts.reshape(-1)
    flat_w = weights.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), k)
    order = jnp.argsort(flat_e, stable=True)
    se, sw, st = flat_e[order], flat_w[order], flat_t[order]
    starts = jnp.searchsorted(se, jnp.arange(E), side="left")
    rank_in_e = jnp.arange(T * k) - starts[se]
    mine = (se // E_loc) == rank          # routed to an expert owned by this rank
    keep = (rank_in_e < C) & mine

    e_loc = jnp.where(keep, se - rank * E_loc, 0)
    c_idx = jnp.where(keep, rank_in_e, 0)
    src = jnp.where(keep[:, None], x[st], 0.0).astype(x.dtype)
    buf = jnp.zeros((E_loc, C, D), dtype=x.dtype)
    buf = buf.at[e_loc, c_idx].add(src, mode="drop")

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    y = jnp.einsum("ecf,efd->ecd", h, params["w_down"])

    gathered = y[e_loc, c_idx]
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    out = jnp.zeros((T, D), dtype=jnp.float32)
    out = out.at[st].add(gathered.astype(jnp.float32) * sw[:, None])
    # ONE combine all-reduce per layer: tokens visited experts on other ranks
    out = jax.lax.psum(out.astype(x.dtype), model_axis)
    aux = load_balance_loss(logits, experts, E)
    return out, aux


def _local_moe_tp(x, params, cfg: MoEConfig, model_axis: str):
    """TP mode (E < model ranks): every rank routes + dispatches ALL experts
    locally, expert FFNs are sharded on the hidden dim F; the down-projection
    produces partial sums completed by the same single psum."""
    T, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    C = max(int(T * k * cfg.capacity_factor / E), min(4, T * k))

    weights, experts, logits = router_topk(x, params["router"], cfg)
    flat_e = experts.reshape(-1)
    flat_w = weights.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), k)
    order = jnp.argsort(flat_e, stable=True)
    se, sw, st = flat_e[order], flat_w[order], flat_t[order]
    starts = jnp.searchsorted(se, jnp.arange(E), side="left")
    rank_in_e = jnp.arange(T * k) - starts[se]
    keep = rank_in_e < C
    e_idx = jnp.where(keep, se, 0)
    c_idx = jnp.where(keep, rank_in_e, 0)
    src = jnp.where(keep[:, None], x[st], 0.0).astype(x.dtype)
    buf = jnp.zeros((E, C, D), dtype=x.dtype)
    buf = buf.at[e_idx, c_idx].add(src, mode="drop")

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    y = jnp.einsum("ecf,efd->ecd", h, params["w_down"])   # partial over F shard

    gathered = y[e_idx, c_idx]
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    out = jnp.zeros((T, D), dtype=jnp.float32)
    out = out.at[st].add(gathered.astype(jnp.float32) * sw[:, None])
    out = jax.lax.psum(out.astype(x.dtype), model_axis)   # completes F partials
    aux = load_balance_loss(logits, experts, E)
    return out, aux


def moe_ffn_ep(x3d, params, cfg: MoEConfig, mesh):
    """x3d: (B, S, D) batch-sharded on (pod, data).  Returns (out, aux).

    EP mode when n_experts divides the model axis; per-expert TP mode otherwise
    (experts replicated in E, sharded on the FFN hidden dim).
    """
    daxes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    model_axis = "model"
    mp = mesh.shape["model"]
    ep_mode = cfg.n_experts % mp == 0
    # tiny batches (long-context decode feeds batch=1) cannot shard over the
    # data axes: compute them redundantly on every data rank instead
    dsize = 1
    for a in daxes:
        dsize *= mesh.shape[a]
    if x3d.shape[0] % dsize != 0:
        daxes = ()

    def body(x_loc, p_loc):
        B, S, D = x_loc.shape
        xf = x_loc.reshape(B * S, D)
        if ep_mode:
            out, aux = _local_moe(xf, p_loc, cfg, model_axis, mp)
        else:
            out, aux = _local_moe_tp(xf, p_loc, cfg, model_axis)
        # aux is identical across model ranks (redundant routing) but differs per
        # data shard: mean over every axis so the P() out_spec is truthful
        aux = jax.lax.pmean(aux, model_axis)
        for ax in daxes:
            aux = jax.lax.pmean(aux, ax)
        return out.reshape(B, S, D), aux

    if ep_mode:
        w_specs = {"router": P(None, None), "w_gate": P("model", None, None),
                   "w_up": P("model", None, None), "w_down": P("model", None, None)}
    else:
        w_specs = {"router": P(None, None), "w_gate": P(None, None, "model"),
                   "w_up": P(None, None, "model"), "w_down": P(None, "model", None)}

    x_spec = P(daxes, None, None) if daxes else P(None, None, None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, w_specs),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    out, aux = fn(x3d, params)
    return out, jnp.mean(aux)


__all__ = ["moe_ffn_ep", "set_ep_mesh", "get_ep_mesh"]
