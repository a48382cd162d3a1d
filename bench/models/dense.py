"""A dense decoder: GQA attention with optional QKV bias, a SwiGLU MLP, and
a tied or untied head.  The configuration files that run it say
``"bench_model": "dense"``.

An architecture the benchmark serves is one file, ``bench/models/<name>.py``,
named by the ``bench_model`` key of each configuration file that uses it
(``bench/configs/<config>.json``).  The harness (``bench/run.py``) loads it
by path and asks it for nothing but the names below, so a configuration of
a new architecture adds that file and edits none:

``model_config(cfg) -> repro.models.ModelConfig``
    The served model, built from the configuration file's numbers (``cfg``
    is that file, parsed).  The harness builds it with
    ``repro.models.build_model`` and serves it through the engine.

``params_key(seed) -> jax key``
    The key the served weights are made from (``model.init_params``), for
    any whole seed, however large.

``check_sequences(cfg, seed, seqs, *, control=False) -> list[dict]``
    The plain float32 reference, with weights of its own drawn from
    ``params_key(seed)`` and nothing imported from the program.  ``seqs``
    is ``[(prompt, served tokens)]``; each result holds ``max_gap`` (the
    widest gap by which a served token's reference logit lies below the
    reference's best) and ``mean_lp`` (the reference's mean
    log-probability of the served tokens).  With ``control`` also
    ``ctrl_max_gap`` and ``ctrl_mean_lp``: the same readings of the
    reference run in the next precision below the served one, which the
    cell's limits have to fail.

``costs(cfg)``
    The operations and bytes the algorithm needs, from shapes alone, in an
    object with:

    * ``n_layers``: layers that each call the attention kernel once per
      loop iteration;
    * ``positions_flops(start, end) -> float``: operations of the positions
      ``start[i] <= p < end[i]`` of each row ``i`` (position ``p`` attends
      ``p + 1`` keys), summed over rows (``step_mfu``);
    * ``attention_call(starts, q_len) -> (operations, bytes)``: one layer's
      call of the paged mixed attention kernel, row ``b`` holding ``q_len``
      queries from position ``starts[b]`` (``paged_mixed_attention_roofline``);
    * ``lmhead_call(rows) -> (operations, bytes)``: one call of the fused
      lm-head epilogue over ``rows`` hidden rows (``lmhead_epilogue_roofline``).

    A multiply-add counts as two operations.  Count what the algorithm
    needs, never what a kernel happens to do, so that a roofline share
    cannot pass 100% unless the time is wrong.

``KERNELS``
    ``{kernel name: name of its Pallas kernel function}`` for kernels this
    architecture adds to the two every model shares (``KERNELS`` in
    ``bench/run.py``).  A kernel's roofline reader,
    ``bench/metrics/<kernel name>_roofline.py``, finds its device time
    under the kernel name.

Here the reference is ``bench/reference.py`` and the costs
``bench/costs.py``.
"""
from __future__ import annotations

from pathlib import Path

from bench import costs as _costs
from bench.reference import check_sequences, params_key

KERNELS: dict[str, str] = {}


def model_config(cfg: dict):
    """The served model's configuration, from the config file's numbers."""
    import jax.numpy as jnp
    from repro.models.common import ModelConfig

    d, hq = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return ModelConfig(
        name=Path(cfg.get("source", "model")).name, family="dense",
        n_layers=int(cfg["num_hidden_layers"]), d_model=d, n_heads=hq,
        n_kv_heads=int(cfg["num_key_value_heads"]),
        d_ff=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
        head_dim=int(cfg.get("head_dim") or d // hq),
        rope_theta=float(cfg["rope_theta"]),
        qkv_bias=bool(cfg["architecture"]["qkv_bias"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=jnp.bfloat16, remat="none")


class Costs:
    """``bench/costs.py``'s counts of a dense layer, at one config's shapes."""

    def __init__(self, cfg: dict):
        self.shapes = _costs.Shapes.from_config(cfg)
        self.n_layers = self.shapes.n_layers

    def positions_flops(self, start, end) -> float:
        return _costs.positions_flops(self.shapes, start, end)

    def attention_call(self, starts, q_len: int) -> tuple[float, float]:
        return _costs.attention_call(self.shapes, starts, q_len)

    def lmhead_call(self, rows: int) -> tuple[float, float]:
        return _costs.lmhead_call(self.shapes, rows)


def costs(cfg: dict) -> Costs:
    return Costs(cfg)


__all__ = ["KERNELS", "Costs", "check_sequences", "costs", "model_config",
           "params_key"]
