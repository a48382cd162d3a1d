"""Model bundle: uniform functional interface over the zoo's families."""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable

import jax

from repro.models.common import ModelConfig


@dataclass(frozen=True)
class Model:
    """Pure-function bundle; everything is jit/pjit-able with explicit shardings."""

    cfg: ModelConfig
    init_params: Callable          # rng -> params
    forward: Callable              # (params, batch) -> (logits, aux)
    loss_fn: Callable              # (params, batch) -> (loss, metrics)
    prefill: Callable              # (params, batch, max_len) -> (logits, cache)
    decode_step: Callable          # (params, cache, token, pos) -> (logits, cache)
    init_cache: Callable           # (batch, max_len) -> cache
    supports_paged: bool = False   # decode_step accepts block_table= (paged KV)
    use_kernel: bool = False       # serving functions run the Pallas kernels
    # (params, cache, tokens (B,T), pos (B,), block_table=) ->
    # (tok (B,T), lp (B,T), cache): span scoring through the fused lm-head;
    # None for families without the paged mixed path
    verify_step: Callable | None = None

    def abstract_params(self):
        return jax.eval_shape(self.init_params, jax.random.key(0))


def build_model(cfg: ModelConfig, *, use_kernel: bool | None = None) -> Model:
    """Bundle ``cfg``'s family functions.

    The serving functions (prefill, decode_step, verify_step) run the Pallas
    kernels when ``use_kernel`` is true.  ``None`` decides it from the
    platform: on for TPU, where the kernels compile natively, off elsewhere.
    Tests pass True to run the kernels in interpret mode on the CPU.
    ``forward`` and ``loss_fn`` always take the jnp path: training
    differentiates them, and the kernels are forward-only.
    """
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    paged = cfg.family in ("dense", "moe", "vlm")
    if paged:
        from repro.models import lm as mod
    elif cfg.family in ("ssm", "hybrid"):
        from repro.models import mamba_lm as mod
    elif cfg.family in ("audio", "encdec"):
        from repro.models import whisper as mod
    else:
        raise ValueError(f"unknown family {cfg.family}")

    decode_kwargs = {"use_kernel": use_kernel} if paged else {}
    return Model(
        cfg=cfg,
        init_params=partial(mod.init_params, cfg=cfg),
        forward=partial(mod.forward, cfg=cfg),
        loss_fn=partial(mod.loss_fn, cfg=cfg),
        prefill=partial(mod.prefill, cfg=cfg, use_kernel=use_kernel),
        decode_step=partial(mod.decode_step, cfg=cfg, **decode_kwargs),
        init_cache=partial(mod.init_cache, cfg),
        supports_paged=paged,
        use_kernel=use_kernel,
        verify_step=(partial(mod.verify_step, cfg=cfg, use_kernel=use_kernel,
                             lmhead_kernel=use_kernel)
                     if paged else None),
    )


__all__ = ["Model", "build_model"]
