"""Page-size / block-k autotuning for the decode-attention tier.

The paged kernels' only tile knob here is the page size (pages per key
block follow from the shapes, in the kernel), and the dense flash-decoding
kernel's is ``block_k``.
Neither has a universally best value: bigger pages amortize DMA issue and
grid overhead but waste bandwidth on partially filled last pages and shrink
the scheduler's allocation granularity; bigger ``block_k`` does the same for
the dense cache.

``sweep_page_size`` / ``sweep_block_k`` time the decode path the *current
backend actually executes* (CPU: the jitted gather+SDPA route the serving
engine runs; TPU/GPU: the Pallas kernels) and ``pick_defaults`` reduces a
sweep to the fastest configuration.  ``benchmarks/kernels_bench.py`` runs the
sweep and persists it as a JSON artifact; the table below holds the defaults
seeded from those sweeps, and is what :class:`repro.serving.ServeConfig`
resolves when ``page_size`` is left unset.
"""
from __future__ import annotations

import time
from statistics import median

import jax
import jax.numpy as jnp
import numpy as np

#: sweep-seeded defaults per backend (see benchmarks/artifacts/
#: kernels_paged_sweep.json for the data source).  TPU favors 32-token pages:
#: (32, 128) is the f32 minimum tile, so 16-token pages waste half of every
#: sublane; the CPU gather path is page-size-insensitive above 16, where the
#: free-list granularity argument wins.
#: ``chunk_size`` (prefill tokens folded into one mixed step per row),
#: ``draft_len`` (speculative tokens proposed per row per step), and
#: ``lmhead_block_v`` (vocab tile of the fused lm-head epilogue; 0 = single
#: fused matmul, the right call off-TPU) were seeded from the mixed-step
#: sweep (``sweep_span_width``).  Bigger chunks finish prefill in fewer
#: steps but inflate every mixed step's span width (decode rows pay the
#: padding); more drafts amortize the per-step fixed cost but waste
#: verifier FLOPs once the acceptance rate tails off.
DEFAULTS = {
    "cpu": {"page_size": 16, "block_k": 256,
            "chunk_size": 16, "draft_len": 3, "lmhead_block_v": 0},
    "tpu": {"page_size": 32, "block_k": 512,
            "chunk_size": 32, "draft_len": 3, "lmhead_block_v": 2048},
    "gpu": {"page_size": 16, "block_k": 256,
            "chunk_size": 16, "draft_len": 3, "lmhead_block_v": 2048},
}


def backend() -> str:
    return jax.default_backend()


def _defaults(be: str | None) -> dict:
    """The table row of backend ``be`` (default: the running backend).  A
    backend missing from the table is an error: another backend's tiles are
    no default for it."""
    be = be or backend()
    if be not in DEFAULTS:
        raise ValueError(f"no autotune defaults for backend {be!r}; "
                         f"known: {sorted(DEFAULTS)}")
    return DEFAULTS[be]


def default_page_size(be: str | None = None) -> int:
    return _defaults(be)["page_size"]


def default_block_k(be: str | None = None) -> int:
    return _defaults(be)["block_k"]


def default_chunk_size(be: str | None = None) -> int:
    return _defaults(be)["chunk_size"]


def default_draft_len(be: str | None = None) -> int:
    return _defaults(be)["draft_len"]


def default_lmhead_block_v(be: str | None = None) -> int:
    return _defaults(be)["lmhead_block_v"]


def _time_jitted(fn, *args, reps: int = 10) -> float:
    """Median wall microseconds per call of an already-jitted fn."""
    out = fn(*args)
    jax.tree.map(lambda a: a.block_until_ready(), out)   # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.tree.map(lambda a: a.block_until_ready(), out)
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(median(ts))


def _paged_inputs(rng, page_size, *, total_tokens, B, Hq, Hkv, D):
    """Same logical workload re-laid-out for each page size."""
    n = max(total_tokens // page_size, 1)
    P = B * n + 2
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, D))
    k_pages = jax.random.normal(ks[1], (P, page_size, Hkv, D))
    v_pages = jax.random.normal(ks[2], (P, page_size, Hkv, D))
    perm = np.random.default_rng(0).permutation(np.arange(1, P))
    tbl = jnp.asarray(perm[:B * n].reshape(B, n).astype(np.int32))
    lengths = jnp.full((B,), n * page_size, jnp.int32)
    return q, k_pages, v_pages, tbl, lengths


def sweep_page_size(page_sizes=(8, 16, 32, 64), *, total_tokens: int = 256,
                    B: int = 4, Hq: int = 8, Hkv: int = 2, D: int = 64,
                    reps: int = 10) -> list[dict]:
    """Time one paged decode-attention step per page size (fixed logical
    cache length), on the path the current backend serves from."""
    from repro.models.attention import sdpa
    from repro.serving.kvcache import _vector_mask, paged_gather

    rng = jax.random.key(0)
    rows = []
    for ps in page_sizes:
        q, k_pages, v_pages, tbl, lengths = _paged_inputs(
            rng, ps, total_tokens=total_tokens, B=B, Hq=Hq, Hkv=Hkv, D=D)
        if backend() == "cpu":
            # the gather route the CPU engine runs (kernel would interpret)
            def step(q, kp, vp, tbl, lens):
                k = paged_gather(kp, tbl)
                v = paged_gather(vp, tbl)
                mask = _vector_mask(k.shape[1], lens - 1, jnp.int32(-1))
                return sdpa(q, k, v, mask)
        else:
            from repro.kernels.decode_attention.ops import decode_attention_paged

            def step(q, kp, vp, tbl, lens):
                return decode_attention_paged(q, kp, vp, tbl, lens)
        us = _time_jitted(jax.jit(step), q, k_pages, v_pages, tbl, lengths,
                          reps=reps)
        rows.append({"page_size": int(ps), "us_per_step": us,
                     "backend": backend()})
    return rows


def _chunked_decode_ref(q, k_cache, v_cache, pos: int, block_k: int):
    """Blockwise streaming decode attention (the kernel's loop structure in
    jnp): scan KV in ``block_k`` chunks carrying running (max, sum, acc).
    Unlike the one-shot oracle this genuinely depends on block_k, so the
    CPU sweep measures a real chunking tradeoff rather than timing noise."""
    B, Hq, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    group = Hq // Hkv
    nk = S // block_k
    qf = q.astype(jnp.float32) * (D ** -0.5)
    kc = k_cache.astype(jnp.float32).reshape(B, nk, block_k, Hkv, D)
    vc = v_cache.astype(jnp.float32).reshape(B, nk, block_k, Hkv, D)

    def chunk(carry, inp):
        m, l, acc = carry
        kb, vb, i = inp                                       # (B, bk, Hkv, D)
        kr = jnp.repeat(kb, group, axis=2)
        s = jnp.einsum("bhd,bthd->bht", qf, kr)               # (B, Hq, bk)
        k_pos = i * block_k + jnp.arange(block_k)
        s = jnp.where(k_pos[None, None, :] < pos, s, -1e30)
        m_cur = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_cur)
        p = jnp.exp(s - m_cur[..., None])
        vr = jnp.repeat(vb, group, axis=2)
        acc = acc * alpha[..., None] + jnp.einsum("bht,bthd->bhd", p, vr)
        return (m_cur, l * alpha + p.sum(axis=-1), acc), None

    init = (jnp.full((B, Hq), -1e30), jnp.zeros((B, Hq)),
            jnp.zeros((B, Hq, D)))
    (m, l, acc), _ = jax.lax.scan(
        chunk, init, (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4),
                      jnp.arange(nk)))
    return acc / jnp.maximum(l, 1e-30)[..., None]


def sweep_block_k(block_ks=(128, 256, 512, 1024), *, S: int = 1024,
                  B: int = 4, Hq: int = 8, Hkv: int = 2, D: int = 64,
                  reps: int = 10) -> list[dict]:
    """Time one dense flash-decoding step per block_k (CPU times a chunked
    streaming oracle with the kernel's loop structure; TPU/GPU time the
    kernel itself)."""
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, D))
    kc = jax.random.normal(ks[1], (B, S, Hkv, D))
    vc = jax.random.normal(ks[2], (B, S, Hkv, D))
    rows = []
    for bk in block_ks:
        if backend() == "cpu":
            fn = jax.jit(lambda q, k, v, bk=bk: _chunked_decode_ref(
                q[:, 0], k, v, S // 2, block_k=min(bk, S)))
        else:
            from repro.kernels.decode_attention.ops import decode_attention
            fn = jax.jit(lambda q, k, v, bk=bk: decode_attention(
                q, k, v, S // 2, block_k=bk))
        us = _time_jitted(fn, q, kc, vc, reps=reps)
        rows.append({"block_k": int(bk), "us_per_step": us,
                     "backend": backend()})
    return rows


def sweep_span_width(widths=(1, 2, 4, 8, 16, 32), *, total_tokens: int = 256,
                     B: int = 4, Hq: int = 8, Hkv: int = 2, D: int = 64,
                     page_size: int | None = None, reps: int = 10) -> list[dict]:
    """Time one mixed-span attention step per query width T.

    ``us_per_token = us_per_step / T`` is the quantity chunk-size and
    draft-length trade against: a chunk of C tokens costs one T = C mixed
    row-step instead of C decode steps, and a draft of d tokens costs one
    T = d + 1 verify instead of up to d + 1 steps -- but only pays off while
    per-token cost still falls with T.
    """
    from repro.models.attention import sdpa
    from repro.serving.kvcache import _span_mask, paged_gather

    ps = page_size or default_page_size()
    rng = jax.random.key(2)
    rows = []
    for T in widths:
        q1, k_pages, v_pages, tbl, lengths = _paged_inputs(
            rng, ps, total_tokens=total_tokens, B=B, Hq=Hq, Hkv=Hkv, D=D)
        q = jnp.broadcast_to(q1, (B, T, Hq, D))
        starts = lengths - T
        if backend() == "cpu":
            def step(q, kp, vp, tbl, st):
                k = paged_gather(kp, tbl)
                v = paged_gather(vp, tbl)
                mask = _span_mask(k.shape[1], st, q.shape[1], jnp.int32(-1))
                return sdpa(q, k, v, mask)
        else:
            from repro.kernels.decode_attention.ops import decode_attention_mixed

            def step(q, kp, vp, tbl, st):
                return decode_attention_mixed(q, kp, vp, tbl, st)
        us = _time_jitted(jax.jit(step), q, k_pages, v_pages, tbl, starts,
                          reps=reps)
        rows.append({"span_width": int(T), "us_per_step": us,
                     "us_per_token": us / T, "backend": backend()})
    return rows


def pick_defaults(page_rows: list[dict], block_rows: list[dict],
                  span_rows: list[dict] | None = None) -> dict:
    """Reduce sweeps to the fastest configuration (the autotuned default)."""
    best_ps = min(page_rows, key=lambda r: r["us_per_step"])
    best_bk = min(block_rows, key=lambda r: r["us_per_step"])
    out = {"backend": backend(), "page_size": best_ps["page_size"],
           "block_k": best_bk["block_k"]}
    if span_rows:
        # widest span still paying for itself in per-token cost is the chunk
        # size; drafts stop at the knee less one (the verify block is d + 1)
        best_span = min(span_rows, key=lambda r: r["us_per_token"])
        out["chunk_size"] = best_span["span_width"]
        out["draft_len"] = max(best_span["span_width"] - 1, 1)
    return out


__all__ = ["DEFAULTS", "backend", "default_page_size", "default_block_k",
           "default_chunk_size", "default_draft_len", "default_lmhead_block_v",
           "sweep_page_size", "sweep_block_k", "sweep_span_width",
           "pick_defaults"]
