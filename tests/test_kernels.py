"""Per-kernel allclose vs the pure-jnp oracles, swept over shapes/dtypes
(interpret mode on CPU; same kernel code compiles for TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.kernels.decode_attention.ops import (
    decode_attention,
    decode_attention_paged,
)
from repro.kernels.decode_attention.ref import (
    decode_attention_ref,
    paged_decode_attention_ref,
)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd.ops import ssd_intra
from repro.kernels.ssd.ref import ssd_intra_ref


@pytest.mark.parametrize("B,S,Hq,Hkv,D,win,dtype", [
    (2, 256, 4, 2, 64, None, jnp.float32),
    (1, 512, 8, 8, 128, None, jnp.float32),
    (2, 256, 4, 1, 64, 64, jnp.float32),
    (1, 384, 6, 2, 32, 128, jnp.float32),
    (1, 256, 4, 2, 64, None, jnp.bfloat16),
])
def test_flash_attention_allclose(B, S, Hq, Hkv, D, win, dtype):
    ks = jax.random.split(jax.random.PRNGKey(S + Hq), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    out = flash_attention(q, k, v, window=win, block_q=128, block_k=128)
    ref = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), win).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@given(st.integers(1, 3), st.sampled_from([128, 192, 256]),
       st.sampled_from([(4, 2), (4, 4), (6, 3)]), st.sampled_from([32, 64]))
@settings(max_examples=8, deadline=None)
def test_flash_attention_hypothesis(B, S, heads, D):
    Hq, Hkv = heads
    ks = jax.random.split(jax.random.PRNGKey(B * S), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    ref = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), None).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("B,S,Hq,Hkv,D,pos,win", [
    (2, 512, 8, 2, 64, 300, None),
    (1, 1024, 4, 4, 128, 1000, None),
    (2, 512, 8, 2, 64, 400, 128),
    (1, 256, 8, 1, 64, 17, None),       # pos not block-aligned
    (2, 384, 8, 2, 64, 201, 96),        # GQA + window + partial, unaligned
    (1, 256, 6, 3, 32, 250, 300),       # window wider than the filled cache
])
def test_decode_attention_allclose(B, S, Hq, Hkv, D, pos, win):
    ks = jax.random.split(jax.random.PRNGKey(S + pos), 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    out = decode_attention(q, k, v, pos, window=win, block_k=256)
    ref = decode_attention_ref(q[:, 0], k, v, pos, win)[:, None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,Hq,Hkv,D,ps,n,lens,win", [
    (3, 8, 2, 64, 16, 4, (17, 43, 64), None),     # GQA, partial pages
    (3, 8, 2, 64, 16, 4, (17, 43, 64), 24),       # GQA + sliding window
    (2, 4, 4, 32, 16, 3, (1, 48), None),          # MHA, one-token row
    (2, 8, 1, 64, 32, 2, (33, 50), 40),           # MQA, big pages + window
])
def test_paged_decode_attention_matches_oracles(B, Hq, Hkv, D, ps, n, lens, win):
    """Block-table kernel == gather-over-pages oracle == dense kernel oracle,
    under GQA, sliding windows, and partially filled last pages."""
    P = B * n + 2
    ks = jax.random.split(jax.random.PRNGKey(B * Hq + ps), 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, D))
    k_pages = jax.random.normal(ks[1], (P, ps, Hkv, D))
    v_pages = jax.random.normal(ks[2], (P, ps, Hkv, D))
    rng = np.random.default_rng(0)
    # disjoint random physical pages per row; page 0 is the trash page
    perm = rng.permutation(np.arange(1, P))
    tbl = jnp.asarray(perm[:B * n].reshape(B, n).astype(np.int32))
    lengths = jnp.asarray(np.array(lens, np.int32))
    out = decode_attention_paged(q, k_pages, v_pages, tbl, lengths, window=win)
    ref = paged_decode_attention_ref(q[:, 0], k_pages, v_pages, tbl, lengths,
                                     win)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # cross-check each row against the DENSE kernel oracle on the gathered
    # cache -- the paged path must be exactly the dense computation
    flat_k = np.asarray(k_pages).reshape(P * ps, Hkv, D)
    flat_v = np.asarray(v_pages).reshape(P * ps, Hkv, D)
    for b in range(B):
        idx = (np.asarray(tbl)[b][:, None] * ps + np.arange(ps)[None]).reshape(-1)
        dense = decode_attention_ref(q[b:b + 1, 0],
                                     jnp.asarray(flat_k[idx])[None],
                                     jnp.asarray(flat_v[idx])[None],
                                     int(lens[b]), win)
        np.testing.assert_allclose(np.asarray(out[b, 0]), np.asarray(dense[0]),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,Hq,Hkv,D,ps,n,lens,win", [
    (3, 8, 2, 64, 16, 4, (17, 43, 64), None),     # GQA, partial pages
    (2, 4, 4, 32, 16, 3, (1, 48), 24),            # MHA, one-token row, window
])
def test_paged_decode_attention_int8_matches_gather(B, Hq, Hkv, D, ps, n,
                                                    lens, win):
    """Acceptance: int8 KV through the paged Pallas kernel (in-register
    dequantize) == the dequantize-then-gather route it used to fall back
    to, and == the fp kernel on the dequantized pool."""
    from repro.kernels.decode_attention.ref import (
        paged_decode_attention_int8_ref,
    )
    P = B * n + 2
    ks = jax.random.split(jax.random.PRNGKey(B * Hq + ps + 7), 5)
    q = jax.random.normal(ks[0], (B, 1, Hq, D))
    k_pages = jax.random.randint(ks[1], (P, ps, Hkv, D), -127, 128, jnp.int8)
    v_pages = jax.random.randint(ks[2], (P, ps, Hkv, D), -127, 128, jnp.int8)
    k_scale = jax.random.uniform(ks[3], (P, ps, Hkv, 1), minval=5e-3,
                                 maxval=3e-2)
    v_scale = jax.random.uniform(ks[4], (P, ps, Hkv, 1), minval=5e-3,
                                 maxval=3e-2)
    rng = np.random.default_rng(1)
    perm = rng.permutation(np.arange(1, P))
    tbl = jnp.asarray(perm[:B * n].reshape(B, n).astype(np.int32))
    lengths = jnp.asarray(np.array(lens, np.int32))
    out = decode_attention_paged(q, k_pages, v_pages, tbl, lengths,
                                 window=win, k_scale=k_scale, v_scale=v_scale)
    ref = paged_decode_attention_int8_ref(q[:, 0], k_pages, v_pages, k_scale,
                                          v_scale, tbl, lengths, win)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # and the fp kernel on the pre-dequantized pool agrees
    fp = decode_attention_paged(q, k_pages.astype(jnp.float32) * k_scale,
                                v_pages.astype(jnp.float32) * v_scale,
                                tbl, lengths, window=win)
    np.testing.assert_allclose(np.asarray(out), np.asarray(fp),
                               atol=2e-5, rtol=2e-5)


def test_int8_paged_block_decode_matches_gather_path():
    """models.lm.block_decode routes int8 + block_table through the kernel
    when use_kernel=True; the caches must match bit-for-bit (same quantize,
    same scatter) and the logits within bf16 noise -- the kernel dequantizes
    in f32 registers where the gather route rounds through cfg.dtype."""
    import dataclasses

    from repro.configs import get_smoke_config
    from repro.models import build_model

    cfg = dataclasses.replace(get_smoke_config("smollm-135m"),
                              kv_cache_dtype="int8")
    m_gather = build_model(cfg)                    # jnp gather + dequantize
    m_kernel = build_model(cfg, use_kernel=True)   # int8 paged Pallas path
    params = m_gather.init_params(jax.random.key(0))
    B, ps, n = 2, 16, 2
    pages = m_gather.init_cache(n * B + 1, ps)     # (L, P, ps, ...) pools
    toks = jax.random.randint(jax.random.key(1), (B, 1), 0, cfg.vocab)
    tbl = jnp.asarray(np.array([[1, 2], [3, 4]], np.int32))
    pos = jnp.asarray(np.array([5, 20], np.int32))
    lg_g, cache_g = m_gather.decode_step(params, pages, toks, pos,
                                         block_table=tbl)
    lg_k, cache_k = m_kernel.decode_step(params, pages, toks, pos,
                                         block_table=tbl)
    np.testing.assert_allclose(np.asarray(lg_g), np.asarray(lg_k), atol=0.1)
    # layer-0 writes see identical inputs, so they quantize identically;
    # deeper layers inherit the f32-vs-bf16 attention noise through the
    # residual stream, so their writes may move by a few quantization steps
    np.testing.assert_array_equal(np.asarray(cache_g["k"][0]),
                                  np.asarray(cache_k["k"][0]))
    for name in ("k", "v"):
        g = np.asarray(cache_g[name], np.float32)
        k = np.asarray(cache_k[name], np.float32)
        assert np.mean(g != k) < 0.02 and np.abs(g - k).max() <= 8


@given(st.sampled_from([32, 64, 128]), st.sampled_from([2, 4]),
       st.sampled_from([16, 32]), st.sampled_from([8, 16]))
@settings(max_examples=8, deadline=None)
def test_ssd_intra_hypothesis(q, h, p, n):
    b, nc = 1, 2
    ks = jax.random.split(jax.random.PRNGKey(q + h), 4)
    xb = jax.random.normal(ks[0], (b, nc, q, h, p))
    acs = -jnp.abs(jax.random.normal(ks[1], (b, nc, q, h))).cumsum(2) * 0.1
    Bh = jax.random.normal(ks[2], (b, nc, q, h, n))
    Ch = jax.random.normal(ks[3], (b, nc, q, h, n))
    out = ssd_intra(xb, acs, Bh, Ch)
    ref = jnp.stack([ssd_intra_ref(xb[:, i], acs[:, i], Bh[:, i], Ch[:, i])
                     for i in range(nc)], 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)


def test_ssd_full_scan_kernel_path_matches_ref():
    """ssd_chunked(use_kernel=True) == ssd_chunked(use_kernel=False)."""
    from repro.models.ssm import ssd_chunked
    b, s, h, p, g, n, chunk = 2, 64, 4, 16, 1, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, g, n))
    C = jax.random.normal(ks[4], (b, s, g, n))
    D = jnp.ones((h,))
    y0 = ssd_chunked(x, dt, A, B, C, D, chunk, use_kernel=False)
    y1 = ssd_chunked(x, dt, A, B, C, D, chunk, use_kernel=True)
    np.testing.assert_allclose(np.asarray(y0, np.float32), np.asarray(y1, np.float32),
                               atol=1e-3, rtol=1e-3)


def test_ssd_chunked_matches_naive_recurrence():
    """The chunked SSD equals the literal per-step recurrence."""
    from repro.models.ssm import ssd_chunked, ssd_decode_step
    b, s, h, p, g, n, chunk = 1, 32, 2, 8, 1, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, g, n))
    C = jax.random.normal(ks[4], (b, s, g, n))
    D = jnp.zeros((h,))
    y_chunked = ssd_chunked(x, dt, A, B, C, D, chunk, use_kernel=False)
    state = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(s):
        y_t, state = ssd_decode_step(x[:, t], dt[:, t], A, B[:, t], C[:, t], D, state)
        ys.append(y_t)
    y_naive = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunked, np.float32),
                               np.asarray(y_naive, np.float32), atol=2e-3, rtol=2e-3)


def test_autotune_defaults_refuse_an_unknown_backend():
    """A backend missing from the table gets an error, not another
    backend's tile sizes."""
    from repro.kernels.decode_attention import autotune
    assert autotune.default_page_size("tpu") == autotune.DEFAULTS["tpu"]["page_size"]
    for fn in (autotune.default_page_size, autotune.default_block_k,
               autotune.default_chunk_size, autotune.default_draft_len,
               autotune.default_lmhead_block_v):
        with pytest.raises(ValueError, match="no autotune defaults"):
            fn("metal")
