"""Float32 reference for served greedy output.

A server runs in bfloat16; the reference runs the same weights in float32 at
full matmul precision.  Random weights tie: two tokens' logits can agree to
the last bit the server resolves, and which of them a bf16 server emits is
then a rounding accident.  So served tokens are not compared one for one
with the reference's argmax.  Instead, over the served sequence itself
(prompt plus output, so one near-tie cannot derail the rest of the check):

* every emitted token's reference logit must lie within a tolerance of that
  position's maximum (``max_gap``);
* the request's mean logprob must agree with the reference's mean logprob of
  the same tokens (``mean_logprob``).

Both within ``TOL_STD`` standard deviations of the reference logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: agreement tolerance, in standard deviations of a position's reference
#: logits.  bf16 keeps 8 significant bits, and the roundings of a few dozen
#: layers leave a bf16 server's logits a few hundredths of a standard
#: deviation from the float32 ones: the worst gap measured is 0.025 (smoke
#: models, CPU) and 0.015 (qwen2.5-3b, 36 layers, TPU v5e).  A server
#: computing in a format of 4 significant bits would stray about 16 times
#: further, and a token the model did not choose sits several standard
#: deviations below the maximum; a tenth of a standard deviation keeps a 4x
#: margin over bf16 and fails both.
TOL_STD = 0.1


def reference_logits(model, params, tokens) -> np.ndarray:
    """(S, V) float32 logits of ``model.forward`` over one token sequence.

    Only the embedding is upcast: the activations then start in float32 and
    every bf16 weight promotes inside its own matmul, so the whole forward
    runs in float32 without a float32 copy of the block weights.  The
    sequence is zero-padded to a power of two (causal attention keeps the
    real positions exact), so a few lengths share one compiled forward."""
    toks = np.asarray(tokens, np.int32)
    S = len(toks)
    padded = np.zeros((1, 1 << max(S - 1, 1).bit_length()), np.int32)
    padded[0, :S] = toks
    p32 = {**params, "embed": params["embed"].astype(jnp.float32)}
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(model.forward)(p32, {"tokens": padded})
    return np.asarray(logits[0, :S], np.float32)


def greedy_agreement(logits: np.ndarray, prompt_len: int,
                     output) -> tuple[float, float, float]:
    """Score a served greedy ``output`` against reference ``logits`` over
    prompt + output: token j was chosen after position ``prompt_len - 1 + j``.

    Returns ``(max_gap, mean_logprob, scale)``: the largest amount by which
    an emitted token's logit falls short of its position's maximum (0.0 when
    every token is a reference argmax, ties included), the reference's mean
    log-probability of the emitted tokens, and the mean standard deviation of
    the logits at those positions -- the unit ``TOL_STD`` is counted in."""
    out = np.asarray(output, np.int64)
    rows = logits[prompt_len - 1:prompt_len - 1 + len(out)].astype(np.float64)
    chosen = rows[np.arange(len(out)), out]
    gap = float((rows.max(axis=1) - chosen).max())
    m = rows.max(axis=1, keepdims=True)
    lse = m[:, 0] + np.log(np.exp(rows - m).sum(axis=1))
    return gap, float((chosen - lse).mean()), float(rows.std(axis=1).mean())


__all__ = ["TOL_STD", "reference_logits", "greedy_agreement"]
