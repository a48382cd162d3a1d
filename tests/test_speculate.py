"""Overlapped chunked prefill + speculative decode: acceptance-rule units,
fused lm-head epilogue exactness, mixed-span attention kernel oracle, KV
rollback page accounting, eos-mid-chunk, and the pinned token-exactness of
greedy speculative decode against the single-step oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serving import Request, ServeConfig, ServingEngine
from repro.serving.kvcache import TRASH_PAGE, PagedKVCache, _span_mask
from repro.serving.speculate import NGramProposer, RepeatProposer, prefix_len


@pytest.fixture(scope="module")
def smol():
    cfg = get_smoke_config("smollm-135m")
    m = build_model(cfg)
    params = m.init_params(jax.random.key(0))
    return cfg, m, params


# ---------------------------------------------------------------------------------
# acceptance rule + proposers
# ---------------------------------------------------------------------------------

def test_prefix_len_is_leading_run():
    m = jnp.array([[True, True, False, True],
                   [False, True, True, True],
                   [True, True, True, True]])
    assert prefix_len(m).tolist() == [2, 0, 4]


def test_ngram_proposer_prompt_lookup():
    hist = jnp.array([[1, 2, 3, 4, 1, 2, 0, 0],
                      [7, 7, 7, 7, 7, 0, 0, 0],
                      [5, 9, 9, 9, 9, 9, 9, 0]], jnp.int32)
    ell = jnp.array([6, 5, 7], jnp.int32)
    p = NGramProposer(draft_len=3, ngram=2)(hist, ell)
    # row 0: trailing bigram (1,2) matched at [1,2] -> copy hist[2:5]
    assert p[0].tolist() == [3, 4, 1]
    # row 1: all-same history -> latest match, continuation then repeat-last
    assert p[1].tolist() == [7, 7, 7]
    assert p[2].tolist() == [9, 9, 9]


def test_ngram_proposer_no_match_falls_back_to_repeat():
    hist = jnp.array([[3, 1, 4, 1, 5, 0]], jnp.int32)   # trailing (1,5) unique
    ell = jnp.array([5], jnp.int32)
    p = NGramProposer(draft_len=2, ngram=2)(hist, ell)
    assert p[0].tolist() == [5, 5]                       # repeat last token
    r = RepeatProposer(draft_len=2)(hist, ell)
    assert r[0].tolist() == [5, 5]


def test_ngram_proposer_short_history():
    hist = jnp.zeros((2, 8), jnp.int32).at[0, 0].set(4).at[1, 0].set(6)
    ell = jnp.array([1, 1], jnp.int32)                  # one token: no bigram
    p = NGramProposer(draft_len=2, ngram=2)(hist, ell)
    assert p.tolist() == [[4, 4], [6, 6]]


# ---------------------------------------------------------------------------------
# fused lm-head epilogue
# ---------------------------------------------------------------------------------

def test_fused_lmhead_matches_materialized_oracle():
    """All three routes (single fused matmul, streaming jnp blocks, Pallas
    kernel) are token-exact and logprob-close vs computing the (N, V)
    logits and log_softmax -- including non-dividing vocab blocks."""
    from repro.kernels.sampling.ops import fused_lmhead_greedy
    from repro.kernels.sampling.ref import lmhead_greedy_ref
    h = jax.random.normal(jax.random.key(5), (6, 32)) * 2.0
    w = jax.random.normal(jax.random.key(6), (32, 999))
    tok_ref, lp_ref = lmhead_greedy_ref(h, w)
    for kw in ({}, {"block_v": 250}, {"block_v": 64},
               {"use_kernel": True, "block_v": 256},
               {"use_kernel": True, "block_v": 4096}):
        tok, lp = fused_lmhead_greedy(h, w, **kw)
        np.testing.assert_array_equal(np.asarray(tok), np.asarray(tok_ref)), kw
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lp_ref),
                                   atol=1e-5)


def test_fused_lmhead_verify_shape():
    """The d-token verify case (B, T, d) flattens through the same path."""
    from repro.kernels.sampling.ops import fused_lmhead_greedy
    from repro.kernels.sampling.ref import lmhead_greedy_ref
    h = jax.random.normal(jax.random.key(7), (3, 4, 16))
    w = jax.random.normal(jax.random.key(8), (16, 101))
    tok_ref, lp_ref = lmhead_greedy_ref(h, w)
    tok, lp = fused_lmhead_greedy(h, w, block_v=33)
    assert tok.shape == (3, 4)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(tok_ref))
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lp_ref), atol=1e-5)


# ---------------------------------------------------------------------------------
# mixed-span paged attention
# ---------------------------------------------------------------------------------

# id: (T, Hq, Hkv, D, page, table pages, starts, window, pool dtype).  A
# start of -1 is a dead row: start 0 on the all-trash table.  At T 32 and
# page 32 a block is 16 pages (512 keys) at group 4 and 8 pages (256 keys)
# at group 8, so the 20- and 10-page tables hold two blocks.
MIXED_CASES = {
    "g2_t4": (4, 4, 2, 8, 4, 6, (0, 5, 13), None, "float32"),
    "g2_t4_window": (4, 4, 2, 8, 4, 6, (0, 5, 13), 3, "float32"),
    "g1_t1": (1, 4, 4, 8, 4, 6, (3, 17, -1), None, "float32"),
    "g3_t4": (4, 9, 3, 16, 8, 6, (0, 9, 30), None, "float32"),
    # 100: live keys end mid-block; 540: two blocks; a dead row
    "g4_t32_blocks": (32, 8, 2, 8, 32, 20, (100, 540, -1), None, "float32"),
    # 250: ends mid-way into the second block
    "g8_t32_blocks": (32, 16, 2, 8, 32, 10, (200, 250, -1), None, "float32"),
    # the window's first key (561 + 1 - 100) sits mid-page, mid-block
    "g4_window_mid_block": (4, 8, 2, 8, 32, 20, (561, 30, 300), 100,
                            "float32"),
    "int8_window": (4, 4, 2, 8, 4, 6, (0, 5, 13), 6, "int8"),
    # 128-lane bf16 heads: the kernel reads packed words
    "bf16_g4_t32_blocks": (32, 8, 2, 128, 32, 20, (100, 540, -1), None,
                           "bfloat16"),
    "bf16_g3_odd_kv_heads": (4, 9, 3, 128, 16, 8, (20, 100, -1), None,
                             "bfloat16"),
}


@pytest.mark.parametrize("case", list(MIXED_CASES))
def test_mixed_kernel_matches_gather_sdpa(case):
    """The T>1 block-table kernel == gather + span-masked SDPA, over group
    sizes 1-8, T of 1, 4 and 32, rows that end mid-block or span two
    blocks, dead rows, windows, int8 pages and packed bf16 pages.  The
    pools are stacked two layers deep and the kernel reads layer 1, so a
    read of the wrong layer (layer 0 holds other values) shows."""
    from repro.kernels.decode_attention.ops import decode_attention_mixed
    from repro.models.attention import sdpa
    from repro.serving.kvcache import paged_gather
    T, Hq, Hkv, D, ps, n, starts, win, dtype = MIXED_CASES[case]
    B = len(starts)
    ks = jax.random.split(jax.random.key(9), 5)
    shape = (B * n + 1, ps, Hkv, D)
    scales = {}
    if dtype == "int8":
        kp = jax.random.randint(ks[0], shape, -127, 128, jnp.int8)
        vp = jax.random.randint(ks[1], shape, -127, 128, jnp.int8)
        scales = {name: jax.random.uniform(k, shape[:3] + (1,), minval=5e-3,
                                           maxval=3e-2)
                  for name, k in (("k_scale", ks[3]), ("v_scale", ks[4]))}
        q = jax.random.normal(ks[2], (B, T, Hq, D))
    else:
        kp = jax.random.normal(ks[0], shape, dtype)
        vp = jax.random.normal(ks[1], shape, dtype)
        q = jax.random.normal(ks[2], (B, T, Hq, D), dtype)
    tbl = jnp.arange(1, B * n + 1, dtype=jnp.int32).reshape(B, n)
    dead = jnp.array([s < 0 for s in starts])[:, None]
    tbl = jnp.where(dead, TRASH_PAGE, tbl)
    starts = jnp.array([max(s, 0) for s in starts], jnp.int32)
    kf, vf = kp.astype(jnp.float32), vp.astype(jnp.float32)
    if scales:
        kf, vf = kf * scales["k_scale"], vf * scales["v_scale"]
    def stacked(x):
        return jnp.stack([x[::-1], x])             # layer 0: other values
    out_k = decode_attention_mixed(
        q, stacked(kp), stacked(vp), tbl, starts, 1, window=win,
        **{name: stacked(x) for name, x in scales.items()})
    mask = _span_mask(n * ps, starts, T, jnp.int32(-1 if win is None else win))
    out_r = sdpa(q.astype(jnp.float32), paged_gather(kf, tbl),
                 paged_gather(vf, tbl), mask)
    # bf16: the reference scores the same bf16 values in f32 throughout
    atol = 2e-2 if dtype == "bfloat16" else 1e-5
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r), atol=atol)


def test_mixed_kernel_t1_equals_decode_kernel():
    """The T=1 slice of the mixed kernel is the plain paged decode kernel."""
    from repro.kernels.decode_attention.ops import (decode_attention_mixed,
                                                    decode_attention_paged)
    B, Hq, Hkv, D, ps, n = 2, 4, 2, 8, 4, 4
    ks = jax.random.split(jax.random.key(10), 3)
    kp = jax.random.normal(ks[0], (B * n + 1, ps, Hkv, D))
    vp = jax.random.normal(ks[1], (B * n + 1, ps, Hkv, D))
    q = jax.random.normal(ks[2], (B, 1, Hq, D))
    tbl = jnp.arange(1, B * n + 1, dtype=jnp.int32).reshape(B, n)
    pos = jnp.array([3, 11], jnp.int32)
    out_m = decode_attention_mixed(q, kp[None], vp[None], tbl, pos, 0)
    out_d = decode_attention_paged(q[:, 0][:, None], kp, vp, tbl, pos + 1)
    np.testing.assert_allclose(np.asarray(out_m), np.asarray(out_d), atol=1e-5)


# ---------------------------------------------------------------------------------
# KV rollback / page accounting
# ---------------------------------------------------------------------------------

def _pool(max_batch=2, max_len=64, page_size=16):
    def init_cache(batch, seq):
        return {"k": jnp.zeros((1, batch, seq, 1, 4))}
    return PagedKVCache(init_cache, max_batch=max_batch, max_len=max_len,
                        page_size=page_size)


def test_shrink_to_returns_speculative_pages():
    """Worst-case span pre-allocation followed by rejection: shrink_to hands
    the over-held pages back, resets their table entries to TRASH, and the
    free-list conservation invariant holds throughout."""
    kv = _pool()
    kv.reserve(0, 40)                       # chunked admission: no pages yet
    assert kv.held[0] == 0 and kv.worst[0] == 3
    kv.ensure_writable_span(0, 0, 34)       # worst-case span: 3 pages
    assert kv.held[0] == 3
    kv.check_invariants()
    freed = kv.shrink_to(0, 17)             # only 17 tokens committed
    assert freed == 1
    assert kv.held[0] == 2
    assert kv.block_table[0, 2] == TRASH_PAGE
    kv.check_invariants()
    # rejected-within-page tokens shrink nothing: page still holds pos < 17
    assert kv.shrink_to(0, 20) == 0
    kv.release(0)
    assert kv.n_free == kv.num_pages - 1
    kv.check_invariants()


def test_shrink_then_regrow_across_page_boundary():
    """A page appended for a draft crossing a page boundary, rejected, then
    re-accepted: shrink returns it, ensure_writable_span re-appends (possibly
    a different physical page), conservation holds."""
    kv = _pool()
    kv.reserve(0, 33)
    kv.ensure_writable_span(0, 0, 17)       # crosses into page 2
    p2 = int(kv.block_table[0, 1])
    assert kv.shrink_to(0, 16) == 1         # page-boundary rejection
    assert p2 in kv._free
    kv.ensure_writable_span(0, 16, 4)       # accept-heavy retry re-appends
    assert kv.held[0] == 2
    kv.check_invariants()
    kv.release(0)
    assert kv.n_free == kv.num_pages - 1


def test_reserve_rebooks_outstanding():
    kv = _pool()
    kv.reserve(0, 16)
    assert kv._outstanding == 1
    kv.reserve(0, 48)                       # re-book a bigger worst case
    assert kv._outstanding == 3
    kv.check_invariants()
    kv.release(0)
    assert kv._outstanding == 0
    kv.check_invariants()


def test_engine_page_conservation_through_speculation(smol):
    """A speculative drain (drafts accepted AND rejected along the way)
    ends with every page back on the free list and invariants intact.
    Single-step ``step`` calls leave rows mid-flight between syncs, so the
    invariants are checked on live state, not only after each row drains."""
    cfg, m, params = smol
    eng = ServingEngine(m, params,
                        ServeConfig(max_batch=4, max_len=64, page_size=8,
                                    chunk_size=8, draft_len=4))
    rng = np.random.default_rng(12)
    for i in range(6):
        eng.submit(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab,
                                int(rng.integers(4, 20))).astype(np.int32),
            max_new_tokens=int(rng.integers(4, 14))))
    seen_mid = False
    while eng.queue or eng.active:
        eng.step()
        eng.kv.check_invariants()           # conservation holds mid-flight
        seen_mid = seen_mid or bool(eng.active)
    assert seen_mid and len(eng.completed) == 6
    assert eng.kv.n_free == eng.kv.num_pages - 1
    eng.kv.check_invariants()


# ---------------------------------------------------------------------------------
# mixed-step semantics
# ---------------------------------------------------------------------------------

def _oracle(m, params, prompt, n, eos=None):
    toks = list(prompt)
    out = []
    for _ in range(n):
        logits, _ = m.forward(params, {"tokens": jnp.asarray(toks, jnp.int32)[None]})
        t = int(jnp.argmax(logits[0, -1]))
        out.append(t)
        toks.append(t)
        if eos is not None and t == eos:
            break
    return out


def test_speculative_greedy_token_exact_vs_oracle(smol):
    """PINNED acceptance gate: greedy speculative decode (chunked prefill +
    n-gram drafts + fused verify) emits bit-identical tokens to sequential
    single-step greedy decoding, for every request in a mixed batch."""
    cfg, m, params = smol
    eng = ServingEngine(m, params,
                        ServeConfig(max_batch=4, max_len=64,
                                    chunk_size=8, draft_len=3))
    rng = np.random.default_rng(13)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        int(rng.integers(3, 24))).astype(np.int32),
                    max_new_tokens=int(rng.integers(1, 10)))
            for i in range(8)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    assert len(eng.completed) == 8
    for r in reqs:
        assert r.output == _oracle(m, params, r.prompt, r.max_new_tokens), r.rid


def test_eos_in_prompt_does_not_truncate(smol):
    """eos tokens inside the prompt are known positions, not candidates:
    chunked prefill must stream them through without finishing the row."""
    cfg, m, params = smol
    rng = np.random.default_rng(14)
    prompt = rng.integers(0, cfg.vocab, 20).astype(np.int32)
    eos = int(prompt[9])                    # an eos token mid-prompt
    eng = ServingEngine(m, params,
                        ServeConfig(max_batch=2, max_len=64, eos_token=eos,
                                    chunk_size=4, draft_len=2))
    req = Request(rid=0, prompt=prompt, max_new_tokens=6)
    eng.submit(req)
    eng.run_until_drained()
    assert req.output == _oracle(m, params, prompt, 6, eos=eos)
    assert eng.kv.n_free == eng.kv.num_pages - 1


def test_emitted_eos_mid_chunk_stops_row(smol):
    """A row whose eos fires in the same mixed invocation that commits its
    final prefill chunk stops exactly at the eos, pages released."""
    cfg, m, params = smol
    rng = np.random.default_rng(15)
    prompt = rng.integers(0, cfg.vocab, 11).astype(np.int32)
    first = _oracle(m, params, prompt, 1)[0]
    eng = ServingEngine(m, params,
                        ServeConfig(max_batch=2, max_len=64, eos_token=first,
                                    chunk_size=16, draft_len=3))
    req = Request(rid=0, prompt=prompt, max_new_tokens=8)
    eng.submit(req)
    eng.run_until_drained()
    assert req.output == [first]            # eos was the very first emission
    assert eng.kv.n_free == eng.kv.num_pages - 1
    eng.kv.check_invariants()


def test_chunked_matches_bucketed_path(smol):
    """The chunked mixed loop and the legacy bucketed-prefill path both serve
    the float32 reference's greedy output and score.  Tokens are checked
    tie-aware (repro.models.reference): two bf16 paths may resolve an exact
    reference tie differently, and neither is then wrong."""
    from repro.models.reference import (TOL_STD, greedy_agreement,
                                        reference_logits)
    cfg, m, params = smol
    for chunked in (False, True):
        eng = ServingEngine(m, params,
                            ServeConfig(max_batch=4, max_len=64,
                                        chunked_prefill=chunked,
                                        chunk_size=8, draft_len=3))
        rng = np.random.default_rng(16)
        for i in range(6):
            eng.submit(Request(
                rid=i,
                prompt=rng.integers(0, cfg.vocab,
                                    int(rng.integers(4, 28))).astype(np.int32),
                max_new_tokens=int(rng.integers(2, 9))))
        eng.run_until_drained()
        assert len(eng.completed) == 6
        for r in eng.completed:
            logits = reference_logits(m, params,
                                      np.concatenate([r.prompt, r.output]))
            gap, ref_lp, scale = greedy_agreement(logits, len(r.prompt),
                                                  r.output)
            assert gap <= TOL_STD * scale, (chunked, r.rid, gap, scale)
            assert abs(r.score - ref_lp) <= TOL_STD * scale, \
                (chunked, r.rid, r.score, ref_lp, scale)


def test_mixed_loop_single_trace(smol):
    """The mixed loop runs at fixed max_batch width: every slot-population
    mix and every sync cadence shares ONE compiled variant, and no prefill
    graph is ever traced."""
    cfg, m, params = smol
    eng = ServingEngine(m, params, ServeConfig(max_batch=4, max_len=64,
                                               chunk_size=8, draft_len=3))
    rng = np.random.default_rng(17)
    for i in range(7):
        eng.submit(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab,
                                int(rng.integers(3, 30))).astype(np.int32),
            max_new_tokens=int(rng.integers(2, 8))))
    eng.step(now=0.0)                       # population 4
    eng.step(now=0.0, decode_steps=eng.decode_steps)
    eng.run_until_drained()                 # tail populations 3..1
    assert len(eng.completed) == 7
    assert eng.mixed_trace_count == 1
    assert eng.prefill_trace_count == 0


# ---------------------------------------------------------------------------------
# bucketed-path starvation control
# ---------------------------------------------------------------------------------

def test_bucket_max_wait_flushes_partial_group(smol):
    """A lone cold-bucket request behind a busy decode batch waits for
    bucket-mates at most ``bucket_max_wait`` steps, then flushes."""
    cfg, m, params = smol
    eng = ServingEngine(m, params,
                        ServeConfig(max_batch=4, max_len=64,
                                    chunked_prefill=False, bucket_max_wait=3))
    rng = np.random.default_rng(18)
    # a long-running batch keeps the engine busy
    for i in range(2):
        eng.submit(Request(rid=i,
                           prompt=rng.integers(0, cfg.vocab, 10).astype(np.int32),
                           max_new_tokens=30))
    eng.step(now=0.0)
    assert len(eng.active) == 2
    # a lone request in a different (cold) bucket: deferred, not prefilled
    lone = Request(rid=9, prompt=rng.integers(0, cfg.vocab, 20).astype(np.int32),
                   max_new_tokens=2)
    eng.submit(lone)
    eng.step(now=0.0)
    assert 9 not in {r.rid for r in eng.active.values()}   # waiting for mates
    eng.step(now=0.0)
    eng.step(now=0.0)
    eng.step(now=0.0)                       # max-wait reached: flushed
    assert (9 in {r.rid for r in eng.active.values()}
            or any(r.rid == 9 for r in eng.completed))
    eng.run_until_drained()
    assert len(eng.completed) == 3


def test_bucket_wait_coalesces_late_mate(smol):
    """A bucket-mate arriving during the wait window joins the deferred
    group: one prefill dispatch, occupancy 0.5 instead of 0.25 twice."""
    cfg, m, params = smol
    eng = ServingEngine(m, params,
                        ServeConfig(max_batch=4, max_len=64,
                                    chunked_prefill=False, bucket_max_wait=4))
    rng = np.random.default_rng(19)
    eng.submit(Request(rid=0, prompt=rng.integers(0, cfg.vocab, 9).astype(np.int32),
                       max_new_tokens=20))
    eng.step(now=0.0)                       # idle engine: flushes immediately
    assert len(eng.active) == 1
    eng.submit(Request(rid=1, prompt=rng.integers(0, cfg.vocab, 20).astype(np.int32),
                       max_new_tokens=4))
    eng.step(now=0.0)                       # deferred (busy, partial, cold)
    eng.submit(Request(rid=2, prompt=rng.integers(0, cfg.vocab, 24).astype(np.int32),
                       max_new_tokens=4))
    width_before = eng._prefill_width
    eng.step(now=0.0)
    eng.step(now=0.0)
    eng.step(now=0.0)
    eng.step(now=0.0)
    rids = {r.rid for r in eng.active.values()} | {r.rid for r in eng.completed}
    assert {1, 2} <= rids
    # both rode one width-4 dispatch (bucket 32): occupancy 2/4 for it
    assert eng._prefill_width == width_before + 4
    assert eng.bucket_occupancy[32] == 0.5
    eng.run_until_drained()
    assert len(eng.completed) == 3


def test_bucket_max_wait_zero_restores_immediate_flush(smol):
    cfg, m, params = smol
    eng = ServingEngine(m, params,
                        ServeConfig(max_batch=4, max_len=64,
                                    chunked_prefill=False, bucket_max_wait=0))
    rng = np.random.default_rng(20)
    eng.submit(Request(rid=0, prompt=rng.integers(0, cfg.vocab, 9).astype(np.int32),
                       max_new_tokens=10))
    eng.step(now=0.0)
    eng.submit(Request(rid=1, prompt=rng.integers(0, cfg.vocab, 20).astype(np.int32),
                       max_new_tokens=2))
    eng.step(now=0.0)                       # no waiting: prefilled at once
    assert 1 in ({r.rid for r in eng.active.values()}
                 | {r.rid for r in eng.completed})
    eng.run_until_drained()
    assert len(eng.completed) == 2
