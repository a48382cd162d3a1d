"""The mixed step keeps the KV pool in place: the layer scan carries the
pools and each layer writes its span into them through the (L * P, ...)
page view, and the jitted step takes the pool donated.

Parity: against the layer scan that slices each layer's pools out as the
scan's inputs and stacks them back as its outputs, the step gives the same
tokens and logprobs bit for bit, and the same pool outside the trash page,
on the gather path and on the kernel (interpret mode), with native and
int8 KV, at head dims that the kernel pads and that it reads as a view.
"""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model, lm
from repro.models.common import rms_norm, rope_tables
from repro.serving import Request, ServeConfig, ServingEngine
from repro.serving.kvcache import TRASH_PAGE

SERVE = ServeConfig(max_batch=3, max_len=64, page_size=8, chunk_size=8,
                    draft_len=3, decode_steps=3)


def _verify_layer_by_layer(params, cache, tokens, pos, *, cfg, block_table,
                           use_kernel, lmhead_kernel, lmhead_block_v=0):
    """``lm.verify_step`` with each layer's pools as the scan's xs and ys:
    every layer writes its span with ``paged_update_span`` into its own
    (P, ...) pools, which the scan stacks back."""
    from repro.kernels.sampling.ops import fused_lmhead_greedy
    x = params["embed"][tokens]
    cos, sin = rope_tables(pos[:, None] + jnp.arange(tokens.shape[1])[None, :],
                           cfg.resolved_head_dim, cfg.rope_theta)

    def body(x, layer):
        bp, w, pools = layer
        x, pools = lm.block_verify(x, bp, w, 0,
                                   {n: p[None] for n, p in pools.items()},
                                   pos, cos, sin, cfg, block_table=block_table,
                                   use_kernel=use_kernel)
        return x, {n: p[0] for n, p in pools.items()}

    x, cache = jax.lax.scan(body, x, (params["blocks"], lm.layer_windows(cfg),
                                      cache))
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    w_head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    tok, lp = fused_lmhead_greedy(x, w_head, use_kernel=lmhead_kernel,
                                  block_v=lmhead_block_v)
    return tok, lp, cache


def _requests(vocab, n=5):
    rng = np.random.default_rng(19)
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab,
                                        int(rng.integers(3, 22))).astype(np.int32),
                    max_new_tokens=int(rng.integers(2, 12)))
            for i in range(n)]


# id: (use_kernel, kv_cache_dtype, head_dim).  Head dim 16 and int8 scales
# are padded by the kernel, which then reads one layer sliced out; head dim
# 128 without scales is read through the stacked view.
PARITY_CASES = {
    "gather_native": (False, "native", 0),
    "gather_int8": (False, "int8", 0),
    "kernel_native_padded": (True, "native", 0),
    "kernel_native_view": (True, "native", 128),
    "kernel_int8_d128": (True, "int8", 128),
}


@pytest.mark.parametrize("case", list(PARITY_CASES))
def test_mixed_step_matches_layer_by_layer_writes(case):
    use_kernel, kv_dtype, head_dim = PARITY_CASES[case]
    cfg = dataclasses.replace(get_smoke_config("smollm-135m"), n_layers=2,
                              kv_cache_dtype=kv_dtype, head_dim=head_dim)
    model = build_model(cfg, use_kernel=use_kernel)
    oracle = dataclasses.replace(model, verify_step=partial(
        _verify_layer_by_layer, cfg=cfg, use_kernel=use_kernel,
        lmhead_kernel=use_kernel))
    params = model.init_params(jax.random.key(3))
    engines = [ServingEngine(m, params, SERVE) for m in (model, oracle)]
    reqs = [_requests(cfg.vocab) for _ in engines]
    for eng, rs in zip(engines, reqs):
        for r in rs:
            eng.submit(r)
    steps = 0
    while engines[0].queue or engines[0].active:
        for eng in engines:
            eng.step(decode_steps=SERVE.decode_steps)
        steps += 1
        new, old = (eng.kv.pages for eng in engines)
        for name in new:
            np.testing.assert_array_equal(
                np.asarray(new[name][:, TRASH_PAGE + 1:]),
                np.asarray(old[name][:, TRASH_PAGE + 1:]), err_msg=name)
        assert [r.output for r in reqs[0]] == [r.output for r in reqs[1]]
        assert [r.score for r in reqs[0]] == [r.score for r in reqs[1]]
    assert steps > 2 and not engines[1].queue and not engines[1].active
    assert all(r.output for r in reqs[0])


@pytest.fixture(scope="module")
def smol():
    cfg = get_smoke_config("smollm-135m")
    model = build_model(cfg)
    return cfg, model, model.init_params(jax.random.key(0))


def test_step_donates_the_pool(smol):
    """The pool passed to a step is consumed by it: every leaf is deleted,
    and the engine holds the pool the step returned."""
    cfg, model, params = smol
    eng = ServingEngine(model, params, SERVE)
    for r in _requests(cfg.vocab, 3):
        eng.submit(r)
    eng.step()
    before = eng.kv.pages
    eng.step()
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(before))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(eng.kv.pages))


def test_migration_between_donating_steps_is_bit_identical(smol):
    """export_slot and import_slot between steps read and write only the
    pool the last step returned: a request moved mid-flight to a second
    engine finishes with the tokens and score of one that never moved."""
    cfg, model, params = smol
    ref, src, dst = (ServingEngine(model, params, SERVE) for _ in range(3))
    stay, move = _requests(cfg.vocab, 2), _requests(cfg.vocab, 2)
    for r in stay:
        ref.submit(r)
    for r in move:
        src.submit(r)
    for _ in range(2):
        ref.step()
        src.step()
    slot = next(s for s, r in src.active.items() if r.rid == 1)
    m = src.export_request(slot)
    assert m.pos > 0 and m.kv_chunks is not None
    dst.import_request(m)
    for eng in (ref, src, dst):
        eng.run_until_drained()
    assert [r.output for r in move] == [r.output for r in stay]
    assert [r.score for r in move] == [r.score for r in stay]
    for eng in (src, dst):
        eng.kv.check_invariants()
        assert eng.kv.n_free == eng.kv.num_pages - 1
