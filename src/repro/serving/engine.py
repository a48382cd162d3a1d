"""Single-replica serving engine: paged-KV continuous batcher with a
device-resident decode loop, batched bucketed prefill, and straggler
mitigation hooks.

This is the per-replica substrate the elastic layer (repro.core.elastic)
scales in and out.  Requests are classed by (prefill_len, decode_len) --
the LLM analogue of the paper's tweet classes -- and the engine reports the
application-level signals (queue depth, in-flight count, output score stream)
that drive the paper's auto-scaling policies.  ``Request.score`` is the
*real* application-output signal: the running mean log-probability of the
tokens the model actually generated, fed to the control plane's
``output_score`` channel by the serve driver.

Serving path (attention families; see DESIGN.md "Overlapped prefill and
speculative decode"):

* **paged KV cache** (`repro.serving.kvcache`) -- worst-case pages reserved
  at admission, allocated as spans are written, freed on completion;
* **mixed chunked-prefill / speculative decode** (the default) -- queued
  prompts are admitted with NO prefill dispatch: every engine step runs ONE
  jitted ``lax.while_loop`` over the fixed ``max_batch``-wide slot array in
  which each row either streams its next span-sized prompt chunk or
  verifies a drafted token block (n-gram proposer + longest-agreeing-prefix
  acceptance), so a flash crowd of prompts never stalls in-flight decodes
  and accepted drafts emit multiple tokens per model forward.  The fused
  lm-head epilogue (`repro.kernels.sampling`) streams vocab blocks of the
  head weights so no (B, T, V) logits tensor is materialized; rejected
  draft KV positions are rolled back via the page pool (``shrink_to``).
  One compiled variant total: the width is fixed and the step count is a
  traced operand;
* **batched bucketed prefill** (``chunked_prefill=False``) -- queued
  prompts sharing a power-of-two ``request_class`` bucket are coalesced
  into ONE fixed-width prefill call (padding rows scatter into the trash
  page); a partial group waits at most ``bucket_max_wait`` engine steps for
  bucket-mates before flushing, so cold buckets cannot starve;
* **device-resident decode** -- one jitted ``lax.while_loop`` advances the
  compacted active-slot batch up to K steps entirely on device, carrying
  tokens, positions, remaining budgets, eos/finish masks, and running
  logprob-score sums; the host syncs (one ``np.asarray`` round trip, one
  block-table upload) only every K steps or when a slot finishes.

Families without a paged decode path (ssm/hybrid, audio/encdec) fall back
to the legacy dense tree cache, which batch-decodes every slot -- through
the same K-step device loop.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.kernels.decode_attention import autotune
from repro.kernels.sampling.ops import greedy_epilogue
from repro.models.registry import Model
from repro.serving.kvcache import TRASH_PAGE, PagedKVCache
from repro.serving.speculate import make_proposer, prefix_len


def _device_of(params):
    """The one device every array leaf of ``params`` lives on, else None."""
    devs = {d for leaf in jax.tree.leaves(params)
            if isinstance(leaf, jax.Array) for d in leaf.devices()}
    return devs.pop() if len(devs) == 1 else None


def _bucket(n: int) -> int:
    """Power-of-two length bucket, floor 16."""
    return 1 << max(int(np.ceil(np.log2(max(n, 1)))), 4)


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (prompt_len,) int32
    max_new_tokens: int
    arrival_s: float = 0.0
    # filled by the engine
    first_token_s: float | None = None
    done_s: float | None = None
    output: list = field(default_factory=list)
    score: float = 0.0                 # running mean logprob of emitted tokens

    @property
    def request_class(self) -> tuple[int, int]:
        """(prefill bucket, decode bucket) -- the service-demand class."""
        return _bucket(len(self.prompt)), _bucket(self.max_new_tokens)


@dataclass(frozen=True)
class MigratedRequest:
    """One in-flight request lifted off a draining replica: the request,
    its decode progress, and its committed KV pages as host arrays (None
    when nothing is committed yet -- the importer replays the prompt)."""

    req: Request
    pos: int                           # committed KV positions on the source
    remaining: int                     # decode budget left (NOT max_new_tokens)
    kv_chunks: object                  # pytree of (L, h, ps, *rest) or None


@dataclass(frozen=True)
class ServeConfig:
    max_batch: int = 8
    max_len: int = 1024
    eos_token: int = -1                # -1: run to max_new_tokens
    greedy: bool = True
    paged: bool = True                 # paged KV cache (attention families)
    page_size: int | None = None       # None: autotuned per-backend default
    num_pages: int | None = None       # default: max_batch*(max_len/ps) + trash
    decode_steps: int = 8              # device-resident steps per host sync
    prefill_batch: int | None = None   # coalesced prefill width (None: max_batch)
    # -- mixed chunked-prefill / speculative decode (paged families) --
    chunked_prefill: bool = True       # fold prefill chunks into the decode loop
    chunk_size: int | None = None      # prefill tokens per mixed step (None: autotune)
    draft_len: int | None = None       # speculative tokens per step (None: autotune;
                                       # 0 disables speculation)
    proposer: str = "ngram"            # draft proposer kind (speculate.make_proposer)
    ngram: int = 2                     # n-gram order for the lookup proposer
    lmhead_block_v: int | None = None  # fused lm-head vocab tile (None: autotune)
    # -- bucketed-prefill path (chunked_prefill=False) --
    bucket_max_wait: int = 4           # engine steps a partial bucket group may
                                       # wait for bucket-mates before flushing


class ServingEngine:
    """Synchronous continuous batcher (slot-based).

    ``step()`` advances every *active* slot by up to ``decode_steps`` tokens
    in one jitted device loop (default 1 -- the control-plane drivers step
    virtual time one token at a time); finished slots release their pages
    and are refilled from the queue with a batched bucketed prefill.
    ``run_until_drained`` runs at the full ``cfg.decode_steps`` sync cadence.
    This mirrors production continuous batching while staying simple enough
    to run under interpret-mode tests.

    The KV pool is placed on the device that holds ``params``, so an engine
    whose params sit on one chip runs entirely on that chip.
    """

    def __init__(self, model: Model, params, cfg: ServeConfig):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.device = _device_of(params)
        self.queue: list[Request] = []
        self.active: dict[int, Request] = {}       # slot -> request
        # dynamic cap on concurrently active slots (<= cfg.max_batch): the unit
        # of elasticity the scaling control plane actuates on this engine
        self.slot_limit: int = cfg.max_batch
        self.pos = np.zeros(cfg.max_batch, dtype=np.int32)
        self.remaining = np.zeros(cfg.max_batch, dtype=np.int32)
        self.completed: list[Request] = []
        self.step_count = 0
        self.decode_steps = max(int(cfg.decode_steps), 1)
        self.prefill_batch = int(cfg.prefill_batch or cfg.max_batch)
        self._prefill_rows = 0                     # real rows batched-prefilled
        self._prefill_width = 0                    # padded rows dispatched
        self._bucket_stats: dict[int, list] = {}   # bucket -> [rows, width]
        self._bucket_first_wait: dict[int, int] = {}   # bucket -> first defer step
        self._clock = 0                            # ticks every step() call
        self.paged = cfg.paged and model.supports_paged
        self.chunked = (self.paged and cfg.chunked_prefill
                        and model.verify_step is not None)
        if self.chunked:
            chunk = cfg.chunk_size or autotune.default_chunk_size()
            draft = (cfg.draft_len if cfg.draft_len is not None
                     else autotune.default_draft_len())
            self.spec_len = max(int(draft), 0)
            self.span = max(int(chunk), self.spec_len + 1, 1)
            self.lmhead_block_v = (cfg.lmhead_block_v
                                   if cfg.lmhead_block_v is not None
                                   else autotune.default_lmhead_block_v())
            self.proposer = (make_proposer(cfg.proposer, self.span - 1,
                                           ngram=cfg.ngram)
                             if self.span > 1 else None)
            # the pool is donated: the loop writes it in place, and every
            # caller replaces ``self.kv.pages`` with the pool returned
            self._mixed_jit = jax.jit(self._mixed_step_fn, donate_argnums=(1,))
        else:
            self.spec_len = 0
            self.span = 1
            self.proposer = None
            self._mixed_jit = None
        # mixed-loop counters (speculation_stats), summed at each sync
        self._mixed_emitted = 0                    # tokens emitted by mixed loop
        self._mixed_live_iters = 0                 # live-row loop iterations
        self._mixed_computed = 0                   # max_batch x span x iters
        self._mixed_committed = 0                  # KV positions committed
        self._kv_reserved_iters = 0                # reserved pages x iters
        self._kv_committed_iters = 0               # committed pages x iters
        if self.paged:
            page_size = cfg.page_size or autotune.default_page_size()
            self.kv = PagedKVCache(model.init_cache, max_batch=cfg.max_batch,
                                   max_len=cfg.max_len, page_size=page_size,
                                   num_pages=cfg.num_pages, device=self.device)
            self._prefill_jit = jax.jit(self._paged_prefill_fn)
            self._decode_jit = jax.jit(self._paged_decode_fn)
        else:
            self.kv = None
            self.cache = None                      # dense tree cache, lazy init
            self._prefill_jit = jax.jit(self._dense_prefill_fn)
            self._decode_jit = jax.jit(self._dense_decode_fn)

    # -- jitted step functions ----------------------------------------------------
    # (bound methods: `self` is closed over, only array args are traced)

    def _paged_prefill_fn(self, params, pages, toks, last_idx, page_ids):
        """Batched bucketed prefill: toks (nb, pb) zero-padded rows sharing
        one bucket pb; retraces once per distinct bucket (nb is the fixed
        ``prefill_batch`` width).  Scatters each prompt's KV into its pages
        (bucket overhang and padding rows land in the trash page) and
        returns each row's greedy first token with its logprob."""
        from repro.serving.kvcache import write_prefill_pages
        logits, cache = self.model.prefill(
            params, {"tokens": toks}, max_len=int(toks.shape[1]),
            last_idx=last_idx)
        tok, lp = greedy_epilogue(logits[:, 0],
                                  use_kernel=self.model.use_kernel)
        pages = write_prefill_pages(pages, cache, page_ids)
        return tok, lp, pages

    def _decode_loop(self, params, kv, toks, pos, rem, live, n_steps, step_fn):
        """Up to ``n_steps`` greedy decode steps entirely on device.

        Carried state: KV storage, last tokens (na, 1), per-row positions /
        remaining budgets, the live mask (rows park when their budget runs
        out or they emit eos -- their KV writes keep landing in pages they
        still own, harmlessly), the emitted-token buffer, and running
        logprob sums.  ``n_steps`` is a traced operand, so K=1 control-plane
        steps and K=decode_steps drain bursts share one compiled loop per
        power-of-two batch size; the loop exits early once every row parks.
        """
        K = self.decode_steps
        na = toks.shape[0]
        eos = int(self.cfg.eos_token)
        carry = dict(
            i=jnp.int32(0), kv=kv, toks=toks, pos=pos, rem=rem, live=live,
            out_toks=jnp.full((na, K), -1, jnp.int32),
            lp_sum=jnp.zeros((na,), jnp.float32),
            n_emit=jnp.zeros((na,), jnp.int32),
        )

        def cond(c):
            return (c["i"] < n_steps) & jnp.any(c["live"])

        def body(c):
            logits, kv = step_fn(params, c["kv"], c["toks"], c["pos"])
            tok, lp = greedy_epilogue(logits[:, 0],
                                      use_kernel=self.model.use_kernel)
            live = c["live"]
            emit = jnp.where(live, tok, -1)
            out_toks = jax.lax.dynamic_update_slice(
                c["out_toks"], emit[:, None], (jnp.int32(0), c["i"]))
            inc = live.astype(jnp.int32)
            rem = c["rem"] - inc
            nxt = jnp.where(live, tok, c["toks"][:, 0])[:, None]
            live = live & (rem > 0)
            if eos >= 0:
                live = live & (tok != eos)
            return dict(i=c["i"] + 1, kv=kv, toks=nxt, pos=c["pos"] + inc,
                        rem=rem, live=live, out_toks=out_toks,
                        lp_sum=c["lp_sum"] + jnp.where(c["live"], lp, 0.0),
                        n_emit=c["n_emit"] + inc)

        c = jax.lax.while_loop(cond, body, carry)
        return (c["kv"], c["out_toks"], c["lp_sum"], c["n_emit"], c["pos"],
                c["rem"], c["i"])

    def _paged_decode_fn(self, params, pages, toks, pos, rem, live, tbl,
                         n_steps):
        """K-step device loop for a compacted active-slot batch (padding
        rows carry the trash-page table and write/attend harmlessly)."""
        return self._decode_loop(
            params, pages, toks, pos, rem, live, n_steps,
            lambda p, kv, tk, ps: self.model.decode_step(p, kv, tk, ps,
                                                         block_table=tbl))

    def _mixed_step_fn(self, params, pages, hist, ell, pos, rem, live, tbl,
                       n_steps):
        """Up to ``n_steps`` mixed chunked-prefill / speculative-decode steps
        entirely on device: ONE kernel invocation per step serves every row,
        whatever phase it is in.

        Per-row state is the committed token history ``hist`` (prompt +
        emitted; garbage past ``ell``) and the committed-KV count ``pos``.
        Each iteration builds a T-token block per row: block position j
        carries ``hist[pos + j]`` where known (a *prefill chunk*) and a
        proposer draft where not (*speculation*); the invariant
        ``pos <= ell - 1`` makes position 0 always known.  One
        ``verify_step`` scores the whole batch; position j's context is
        correct iff every earlier block token was known or agreed with the
        verifier, so the longest such prefix (``raw_valid``) is committed KV
        and the verifier outputs at committed positions past ``ell - 1``
        are emitted -- capped by the draft budget, the remaining token
        budget, and eos.  A decode row (pos == ell-1) reduces to verify
        last-token + drafts (always >= 1 token out); a mid-prompt row
        commits a chunk and emits nothing; the final chunk emits its first
        tokens in the same invocation that commits it -- no mode flag, no
        separate prefill dispatch, so a flash crowd of prompts never stalls
        in-flight decodes.
        """
        K = self.decode_steps
        T = self.span
        na, H = hist.shape
        eos = int(self.cfg.eos_token)
        cap = min(T, 1 + self.spec_len)    # emitted tokens per row per step
        OUT = K * cap
        jr = jnp.arange(T)
        rows = jnp.arange(na)
        verify = self.model.verify_step

        carry = dict(
            i=jnp.int32(0), kv=pages, hist=hist, ell=ell, pos=pos, rem=rem,
            live=live,
            out_toks=jnp.full((na, OUT), -1, jnp.int32),
            lp_sum=jnp.zeros((na,), jnp.float32),
            n_emit=jnp.zeros((na,), jnp.int32),
            live_iters=jnp.int32(0),
        )

        def cond(c):
            return (c["i"] < n_steps) & jnp.any(c["live"])

        def body(c):
            hist, ell, pos, live = c["hist"], c["ell"], c["pos"], c["live"]
            idx = pos[:, None] + jr[None, :]                  # (na, T)
            known = idx < ell[:, None]
            u = jnp.take_along_axis(hist, jnp.clip(idx, 0, H - 1), axis=1)
            if T > 1:
                drafts = self.proposer(hist, ell)             # (na, T-1)
                didx = jnp.clip(idx - ell[:, None], 0, T - 2)
                u = jnp.where(known, u,
                              jnp.take_along_axis(drafts, didx, axis=1))
            tok, lp, kv = verify(params, c["kv"], u, pos, block_table=tbl,
                                 lmhead_block_v=self.lmhead_block_v)
            # acceptance: block position j is in-sequence iff known, or its
            # token equals the verifier's output after position j-1 (chained
            # through the prefix rule); position 0 is known by invariant
            if T > 1:
                prev_ok = jnp.concatenate(
                    [jnp.ones((na, 1), bool), u[:, 1:] == tok[:, :-1]], axis=1)
                raw_valid = prefix_len(known | prev_ok)       # (na,) >= 1
            else:
                raw_valid = jnp.ones((na,), jnp.int32)
            # emission: verifier outputs at committed positions >= ell-1,
            # capped by draft budget, token budget, and (emitted) eos
            krank = jr[None, :] - (ell - 1 - pos)[:, None]    # emission rank
            cand = ((krank >= 0) & (jr[None, :] < raw_valid[:, None])
                    & (krank < jnp.minimum(c["rem"], cap)[:, None])
                    & live[:, None])
            if eos >= 0:
                eos_hit = cand & (tok == eos)
                emit = cand & (jnp.cumsum(eos_hit, axis=1) - eos_hit == 0)
                ate_eos = (eos_hit & emit).any(axis=1)
            else:
                emit = cand
                ate_eos = jnp.zeros((na,), bool)
            n_new = emit.sum(axis=1).astype(jnp.int32)
            # extend hist with the emitted tokens (flat scatter, OOB drops)
            col = ell[:, None] + krank
            hidx = jnp.where(emit, rows[:, None] * H + jnp.clip(col, 0, H - 1),
                             na * H)
            hist = (hist.reshape(-1)
                    .at[hidx.reshape(-1)].set(tok.reshape(-1), mode="drop")
                    .reshape(na, H))
            ocol = c["n_emit"][:, None] + krank
            oidx = jnp.where(emit,
                             rows[:, None] * OUT + jnp.clip(ocol, 0, OUT - 1),
                             na * OUT)
            out_toks = (c["out_toks"].reshape(-1)
                        .at[oidx.reshape(-1)].set(tok.reshape(-1), mode="drop")
                        .reshape(na, OUT))
            ell_n = ell + n_new
            # committed KV advances by the accepted prefix but never past the
            # last committed token: accepted-but-unemitted drafts roll back
            # (their page-pool writes are re-verified -- rewritten at the
            # same logical positions -- before any mask lets them be read)
            pos_n = jnp.where(live,
                              jnp.minimum(pos + raw_valid, ell_n - 1), pos)
            rem_n = c["rem"] - n_new
            live_n = live & (rem_n > 0) & ~ate_eos
            return dict(
                i=c["i"] + 1, kv=kv, hist=hist, ell=ell_n, pos=pos_n,
                rem=rem_n, live=live_n, out_toks=out_toks,
                lp_sum=c["lp_sum"] + (lp * emit).sum(axis=1),
                n_emit=c["n_emit"] + n_new,
                live_iters=c["live_iters"] + live.sum().astype(jnp.int32),
            )

        c = jax.lax.while_loop(cond, body, carry)
        return (c["kv"], c["out_toks"], c["lp_sum"], c["n_emit"], c["pos"],
                c["rem"], c["i"], c["live_iters"])

    def _dense_prefill_fn(self, params, batch):
        logits, cache1 = self.model.prefill(params, batch,
                                            max_len=self.cfg.max_len)
        tok, lp = greedy_epilogue(logits[:, -1],
                                  use_kernel=self.model.use_kernel)
        return tok[0], lp[0], cache1

    def _dense_decode_fn(self, params, cache, toks, pos, rem, live, n_steps):
        """K-step device loop over the full dense tree cache -- idle slots
        compute garbage that the live mask discards."""
        return self._decode_loop(
            params, cache, toks, pos, rem, live, n_steps,
            lambda p, kv, tk, ps: self.model.decode_step(p, kv, tk, ps))

    # -- queue interface ----------------------------------------------------------
    def submit(self, req: Request) -> None:
        total = len(req.prompt) + max(req.max_new_tokens, 1) - 1
        if total > self.cfg.max_len:
            raise ValueError(
                f"request {req.rid}: prompt {len(req.prompt)} + "
                f"{req.max_new_tokens} new tokens needs {total} cache slots "
                f"> max_len {self.cfg.max_len}")
        if self.paged and self.kv.pages_needed(total) > self.kv.num_pages - 1:
            raise ValueError(
                f"request {req.rid} needs more pages than the pool holds")
        self.queue.append(req)

    @property
    def n_in_system(self) -> int:
        return len(self.queue) + len(self.active)

    @property
    def prefill_trace_count(self) -> int:
        """Compiled prefill variants -- bounded by the distinct buckets seen
        (the batch dim is the fixed ``prefill_batch`` width)."""
        return int(self._prefill_jit._cache_size())

    @property
    def decode_trace_count(self) -> int:
        """Compiled decode variants -- bounded by ceil(log2(max_batch))+1
        (paged: one per power-of-two active-batch size; the K-step loop
        takes its step count as a traced operand)."""
        return int(self._decode_jit._cache_size())

    @property
    def mixed_trace_count(self) -> int:
        """Compiled mixed-step variants -- exactly 1 after warmup (the loop
        runs at the fixed ``max_batch`` width with the step count traced)."""
        return int(self._mixed_jit._cache_size()) if self.chunked else 0

    @property
    def prefill_occupancy(self) -> float:
        """Real rows per dispatched prefill row (1.0 = no padding waste)."""
        return self._prefill_rows / max(self._prefill_width, 1)

    @property
    def bucket_occupancy(self) -> dict[int, float]:
        """Per-bucket prefill occupancy (bucketed path only; the chunked
        path has no padded prefill rows to waste)."""
        return {pb: rows / max(width, 1)
                for pb, (rows, width) in sorted(self._bucket_stats.items())}

    @property
    def speculation_stats(self) -> dict[str, float]:
        """Mixed-loop counters, summed over every sync since the engine
        was built:

        * ``emitted`` / ``live_iters``: tokens emitted and live-row loop
          iterations, and their ratio ``tokens_per_row_step`` (> 1 means
          speculation is beating one-token-per-step decode);
        * ``computed_positions``: positions the loop computed
          (max_batch x span per iteration) and ``committed_positions``:
          KV positions it committed (prompt chunks plus accepted tokens);
        * ``kv_reserved_page_iters`` / ``kv_committed_page_iters``: pages
          reserved for the served slots, and pages their committed KV
          fills at the sync, each times the loop iterations."""
        return {
            "emitted": float(self._mixed_emitted),
            "live_iters": float(self._mixed_live_iters),
            "tokens_per_row_step": (self._mixed_emitted
                                    / max(self._mixed_live_iters, 1)),
            "computed_positions": float(self._mixed_computed),
            "committed_positions": float(self._mixed_committed),
            "kv_reserved_page_iters": float(self._kv_reserved_iters),
            "kv_committed_page_iters": float(self._kv_committed_iters),
        }

    # -- slot lifecycle -----------------------------------------------------------
    def _reset_slot(self, slot: int) -> None:
        """Free a slot's cache state when it empties (completion, eviction,
        or reclaim of a force-popped slot): release its pages and drop its
        reservation (a chunked slot may hold a reservation before its first
        page), then zero the per-slot position/budget registers."""
        if self.paged and (self.kv.held[slot] or self.kv.worst[slot]):
            self.kv.release(slot)
        self.pos[slot] = 0
        self.remaining[slot] = 0

    def evict(self, slot: int) -> Request:
        """Straggler mitigation: pull the request off its slot, free the
        slot's pages, and re-enqueue from scratch (backup dispatch)."""
        req = self.active.pop(slot)
        self._reset_slot(slot)
        req.output.clear()
        req.score = 0.0
        req.first_token_s = None
        self.submit(req)
        return req

    # -- migration (fleet drain path; see repro.serving.fleet) --------------------
    def export_request(self, slot: int) -> MigratedRequest:
        """Lift the in-flight request off ``slot`` for migration: copy its
        committed KV pages to host arrays, free the slot, and return
        everything :meth:`import_request` needs to resume it elsewhere
        bit-identically.  Call only at a step boundary (host ``pos``/
        ``remaining`` are synced then).  Chunked paged engines only -- the
        mixed loop rebuilds history from prompt + output, so per-row state
        transfers without a dense cache copy."""
        if not self.chunked:
            raise RuntimeError("migration requires the chunked paged engine")
        req = self.active.pop(slot)
        pos = int(self.pos[slot])
        chunks = self.kv.export_slot(slot) if pos > 0 else None
        m = MigratedRequest(req=req, pos=pos,
                            remaining=int(self.remaining[slot]),
                            kv_chunks=chunks)
        self._reset_slot(slot)
        return m

    def can_import(self) -> bool:
        """True if a migrated request could be admitted right now (free slot
        under the cap; page admission is checked per request at import)."""
        return (len(self.active) < min(self.slot_limit, self.cfg.max_batch)
                and len(self.active) < self.cfg.max_batch)

    def import_request(self, m: MigratedRequest) -> int:
        """Re-admit a migrated request with its committed KV installed.

        The decode budget resumes at the exported ``remaining`` (a plain
        ``submit`` would restart it at ``max_new_tokens`` and over-emit);
        the mixed loop then continues from ``pos`` exactly as the source
        would have -- per-row state is independent of batch composition, so
        the emitted tokens are bit-identical.  Returns the slot."""
        if not self.chunked:
            raise RuntimeError("migration requires the chunked paged engine")
        if not self.can_import():
            raise RuntimeError("no free slot under the cap for import")
        total = len(m.req.prompt) + m.req.max_new_tokens - 1
        if not self.kv.can_admit(total):
            raise RuntimeError("page pool cannot admit the migrated request")
        slot = next(s for s in range(self.cfg.max_batch)
                    if s not in self.active)
        if self.kv.held[slot] or self.kv.worst[slot]:
            self._reset_slot(slot)       # reclaim a force-popped slot's pages
        if m.pos > 0 and m.kv_chunks is not None:
            self.kv.import_slot(slot, m.kv_chunks, total)
        else:
            self.kv.reserve(slot, total)
        self.pos[slot] = m.pos
        self.remaining[slot] = m.remaining
        self.active[slot] = m.req
        return slot

    # -- scheduling ---------------------------------------------------------------
    def _note_prefilled(self, slot: int, req: Request, install: bool,
                        tok: int, logp: float, now: float) -> int:
        """Shared post-prefill bookkeeping (paged and dense paths): record
        the first token and its score; either finish at fill time (the
        prefill token was the whole budget) or install the request into its
        slot.  Returns 1 for a fill-time completion, else 0."""
        req.output.append(tok)
        req.first_token_s = now
        req.score += (logp - req.score) / len(req.output)
        if not install:
            # the prefill token is the whole budget: finish at fill time
            # (a decode here would emit max_new_tokens + 1 tokens)
            req.done_s = now
            self.completed.append(req)
            return 1
        self.pos[slot] = len(req.prompt)
        self.remaining[slot] = req.max_new_tokens - 1
        self.active[slot] = req
        return 0

    def _prefill_group(self, group, pb: int, now: float) -> int:
        """One batched bucketed prefill over ``group`` [(slot, req, install)]
        rows sharing bucket ``pb``; returns the number of fill-time
        completions (single-token budgets spent by the prefill argmax)."""
        width = self.prefill_batch
        n_chunks = pb // self.kv.page_size
        toks = np.zeros((width, pb), np.int32)
        last_idx = np.zeros((width,), np.int32)
        page_ids = np.full((width, n_chunks), TRASH_PAGE, np.int32)
        for j, (slot, req, install) in enumerate(group):
            prompt = np.asarray(req.prompt, np.int32)
            plen = len(prompt)
            toks[j, :plen] = prompt
            last_idx[j] = plen - 1
            if install:
                total = plen + req.max_new_tokens - 1
                page_ids[j] = self.kv.alloc_prefill(slot, plen, total,
                                                    n_chunks)
        tokv, lpv, self.kv.pages = self._prefill_jit(
            self.params, self.kv.pages, jnp.asarray(toks),
            jnp.asarray(last_idx), jnp.asarray(page_ids))
        tokv = np.asarray(tokv)
        lpv = np.asarray(lpv)
        self._prefill_rows += len(group)
        self._prefill_width += width
        stats = self._bucket_stats.setdefault(pb, [0, 0])
        stats[0] += len(group)
        stats[1] += width
        fill_done = 0
        for j, (slot, req, install) in enumerate(group):
            fill_done += self._note_prefilled(slot, req, install,
                                              int(tokv[j]), float(lpv[j]), now)
        return fill_done

    def _dense_prefill_into(self, slot: int, req: Request, install: bool):
        """Legacy dense path: one prefill per request, cache installed into
        the slot's rows of the dense tree cache."""
        prompt = np.asarray(req.prompt, np.int32)
        tok, logp, cache1 = self._prefill_jit(
            self.params, {"tokens": jnp.asarray(prompt)[None]})
        if install:
            if self.cache is None:
                self.cache = jax.tree.map(
                    lambda c: jnp.repeat(jnp.zeros_like(c),
                                         self.cfg.max_batch, axis=1),
                    cache1)
            # install the prefilled cache into the slot (batch dim = axis 1)
            self.cache = jax.tree.map(
                lambda full, one: jax.lax.dynamic_update_slice_in_dim(
                    full, one.astype(full.dtype), slot, axis=1),
                self.cache, cache1)
        return int(tok), float(logp)

    def _prefill_bucket(self, req: Request) -> int:
        # bucket >= page_size so the padded prompt is a whole number of
        # page chunks (both are powers of two; max_len is page-aligned)
        return min(max(_bucket(len(req.prompt)), self.kv.page_size),
                   self.cfg.max_len)

    def _fill_slots(self, now: float) -> int:
        """Refill free slots from the queue -- paged: coalescing same-bucket
        head-of-queue prompts into batched prefill calls.  Returns the number
        of requests that finished at fill time (max_new_tokens budget spent
        by the prefill token).  Such a request still consumes its slot for
        this step -- the prefill ran there -- so the slot cap bounds prefill
        work exactly like decode work."""
        limit = min(self.slot_limit, self.cfg.max_batch)
        free = [s for s in range(self.cfg.max_batch) if s not in self.active]
        if self.paged:
            # reclaim pages of slots that were force-popped without release()
            for s in free:
                if self.kv.held[s] or self.kv.worst[s]:
                    self._reset_slot(s)
        fill_done = 0
        while free and self.queue and len(self.active) + fill_done < limit:
            req = self.queue[0]
            if req.max_new_tokens <= 0:
                # nothing to generate: complete without a prefill or a slot
                self.queue.pop(0)
                req.done_s = now
                self.completed.append(req)
                continue
            if not self.paged:
                install = req.max_new_tokens > 1
                self.queue.pop(0)
                slot = free.pop(0)
                tok, logp = self._dense_prefill_into(slot, req, install)
                self._prefill_rows += 1            # dense fills one at a time
                self._prefill_width += 1
                fill_done += self._note_prefilled(slot, req, install,
                                                  tok, logp, now)
                continue
            if self.chunked:
                # chunked admission: no prefill dispatch at all -- reserve
                # the worst-case pages and hand the prompt to the mixed
                # loop, which streams it in span-sized chunks interleaved
                # with every other row's decode
                total = len(req.prompt) + req.max_new_tokens - 1
                if not self.kv.can_admit(total):
                    break                # defer until completions free pages
                self.queue.pop(0)
                slot = free.pop(0)
                self.kv.reserve(slot, total)
                self.pos[slot] = 0
                self.remaining[slot] = req.max_new_tokens
                self.active[slot] = req
                continue
            # paged: collect a same-bucket FIFO group for one batched prefill
            pb = self._prefill_bucket(req)
            group: list[tuple[int, Request, bool]] = []
            planned = 0                  # worst-case pages promised to group
            blocked = False
            while (self.queue and free and len(group) < self.prefill_batch
                   and len(self.active) + fill_done + len(group) < limit):
                r = self.queue[0]
                if r.max_new_tokens <= 0:
                    self.queue.pop(0)
                    r.done_s = now
                    self.completed.append(r)
                    continue
                if self._prefill_bucket(r) != pb:
                    break                # next bucket fills in the next group
                install = r.max_new_tokens > 1
                total = len(r.prompt) + r.max_new_tokens - 1
                if install and not self.kv.can_admit(total, planned):
                    blocked = True       # defer until completions free pages
                    break
                if install:
                    planned += self.kv.pages_needed(total)
                self.queue.pop(0)
                group.append((free.pop(0), r, install))
            if not group:
                break                    # head of queue blocked on pages
            full = (len(group) >= self.prefill_batch or not free
                    or len(self.active) + fill_done + len(group) >= limit)
            if (not full and not blocked and self.cfg.bucket_max_wait > 0
                    and (self.active or fill_done)):
                # partial group while the engine has other work: wait for
                # bucket-mates to raise occupancy -- but never beyond
                # ``bucket_max_wait`` engine steps, so a lone request in a
                # cold bucket cannot starve behind a busy decode batch
                first = self._bucket_first_wait.setdefault(pb, self._clock)
                if self._clock - first < self.cfg.bucket_max_wait:
                    for slot, r, _ in reversed(group):
                        free.insert(0, slot)
                        self.queue.insert(0, r)
                    break
            self._bucket_first_wait.pop(pb, None)
            fill_done += self._prefill_group(group, pb, now)
            if blocked:
                break
        return fill_done

    def _finish(self, slot: int, now: float) -> None:
        req = self.active.pop(slot)
        req.done_s = now
        self.completed.append(req)
        self._reset_slot(slot)

    def _apply_decode_outputs(self, rows, out_toks, lp_sum, n_emit, pos_out,
                              rem_out, iters, now: float,
                              live_iters=None) -> int:
        """Fold one device-loop sync back into host bookkeeping; returns the
        loop iterations run.

        ``rows``: [(batch row, slot)] -- compacted index order for the paged
        path, identity (slot == row) for the dense path.  Every output comes
        to the host in one fetch (``serve.sync``: the wait for the loop and
        the copy), then the fold (``serve.fold``).  ``live_iters`` is the
        mixed loop's: given, the fold adds to its counters."""
        with TraceAnnotation("serve.sync"):
            (out_toks, lp_sum, n_emit, pos_out, rem_out, iters,
             live_iters) = jax.device_get((out_toks, lp_sum, n_emit, pos_out,
                                           rem_out, iters, live_iters))
        with TraceAnnotation("serve.fold"):
            iters = int(iters)
            committed = 0
            finished = []
            for i, s in rows:
                # position/budget always advance (a mixed-loop row can commit
                # prefill chunks without emitting a single token)
                committed += int(pos_out[i]) - int(self.pos[s])
                self.pos[s] = int(pos_out[i])
                self.remaining[s] = int(rem_out[i])
                ne = int(n_emit[i])
                if ne == 0:
                    continue
                req = self.active[s]
                prev = len(req.output)
                if prev == 0:
                    req.first_token_s = now
                req.output.extend(int(t) for t in out_toks[i, :ne])
                req.score = (req.score * prev + float(lp_sum[i])) / (prev + ne)
                if rem_out[i] <= 0 or req.output[-1] == self.cfg.eos_token:
                    finished.append(s)
            if live_iters is not None:
                # before _finish releases the finished rows' pages
                slots = [s for _, s in rows]
                ps = self.kv.page_size
                self._mixed_emitted += int(n_emit.sum())
                self._mixed_live_iters += int(live_iters)
                self._mixed_computed += self.cfg.max_batch * self.span * iters
                self._mixed_committed += committed
                self._kv_reserved_iters += self.kv.n_reserved * iters
                self._kv_committed_iters += iters * int(
                    ((self.pos[slots] + ps - 1) // ps).sum())
            for s in finished:
                self._finish(s, now)
        return iters

    def _decode_active_paged(self, now: float, k: int = 1) -> tuple[int, int]:
        """Up to ``k`` batched heterogeneous-position decode steps over the
        active slots only, compacted and padded to a power-of-two batch, in
        one device loop.  Returns (slots served, device steps executed)."""
        slots = sorted(self.active)
        n = len(slots)
        if n == 0:
            return 0, 0                  # guard: np.log2(0) and an empty jit
        with TraceAnnotation("serve.pack"):
            na = 1 << max(int(np.ceil(np.log2(n))), 0)
            toks = np.zeros((na, 1), np.int32)
            posv = np.zeros((na,), np.int32)
            remv = np.zeros((na,), np.int32)
            livev = np.zeros((na,), bool)
            tblv = np.zeros((na, self.kv.pages_per_slot), np.int32)
            for i, s in enumerate(slots):
                # pre-allocate every page the next k on-device writes may touch
                span = min(k, int(self.remaining[s]))
                self.kv.ensure_writable_span(s, int(self.pos[s]), max(span, 1))
                toks[i, 0] = self.active[s].output[-1]
                posv[i] = self.pos[s]
                remv[i] = self.remaining[s]
                livev[i] = True
                tblv[i] = self.kv.block_table[s]
        with TraceAnnotation("serve.launch"):
            self.kv.pages, *outs = self._decode_jit(
                self.params, self.kv.pages, jnp.asarray(toks),
                jnp.asarray(posv), jnp.asarray(remv), jnp.asarray(livev),
                jnp.asarray(tblv), jnp.int32(k))
        iters = self._apply_decode_outputs(list(enumerate(slots)), *outs, now)
        return n, iters

    def _decode_active_mixed(self, now: float, k: int = 1) -> tuple[int, int]:
        """Up to ``k`` mixed chunked-prefill / speculative steps over the
        active slots in one device loop.  The batch is the full fixed
        ``max_batch`` width (dead rows carry the trash table), so exactly
        ONE compiled variant serves every slot mix -- no per-population
        retraces on the hot path.  Returns (slots served, loop iterations).
        """
        slots = sorted(self.active)
        n = len(slots)
        if n == 0:
            return 0, 0
        with TraceAnnotation("serve.pack"):
            na = self.cfg.max_batch
            T = self.span
            H = self.cfg.max_len + 1       # prompt + every emitted token
            hist = np.zeros((na, H), np.int32)
            ellv = np.zeros((na,), np.int32)
            posv = np.zeros((na,), np.int32)
            remv = np.zeros((na,), np.int32)
            livev = np.zeros((na,), bool)
            tblv = np.zeros((na, self.kv.pages_per_slot), np.int32)
            for i, s in enumerate(slots):
                req = self.active[s]
                plen = len(req.prompt)
                hist[i, :plen] = req.prompt
                if req.output:
                    hist[i, plen:plen + len(req.output)] = req.output
                ellv[i] = plen + len(req.output)
                total = plen + req.max_new_tokens - 1
                # pre-allocate every page the next k on-device spans may
                # write; writes past ``total`` hit TRASH table entries
                # harmlessly, so the span never outgrows the reservation
                span = min(k * T, total - int(self.pos[s]))
                self.kv.ensure_writable_span(s, int(self.pos[s]), max(span, 1))
                posv[i] = self.pos[s]
                remv[i] = self.remaining[s]
                livev[i] = True
                tblv[i] = self.kv.block_table[s]
        with TraceAnnotation("serve.launch"):
            (self.kv.pages, out_toks, lp_sum, n_emit, pos_out, rem_out, iters,
             live_iters) = self._mixed_jit(
                self.params, self.kv.pages, jnp.asarray(hist),
                jnp.asarray(ellv), jnp.asarray(posv), jnp.asarray(remv),
                jnp.asarray(livev), jnp.asarray(tblv), jnp.int32(k))
        iters = self._apply_decode_outputs(
            list(enumerate(slots)), out_toks, lp_sum, n_emit, pos_out, rem_out,
            iters, now, live_iters=live_iters)
        # KV rollback: hand back pages that only ever held rejected
        # speculative writes (the next span re-appends them if accepted)
        with TraceAnnotation("serve.rollback"):
            for s in slots:
                if s in self.active:
                    self.kv.shrink_to(s, max(int(self.pos[s]), 1))
        return n, iters

    def _decode_all_dense(self, now: float, k: int = 1) -> tuple[int, int]:
        """Legacy fallback (no paged cache): batch-decode every slot of the
        dense tree cache -- idle slots compute garbage that is discarded.
        Returns (slots served, device steps executed)."""
        slots = sorted(self.active)
        if not slots:
            return 0, 0                  # guard: empty active set
        with TraceAnnotation("serve.pack"):
            toks = np.zeros((self.cfg.max_batch, 1), np.int32)
            livev = np.zeros((self.cfg.max_batch,), bool)
            for slot, req in self.active.items():
                toks[slot, 0] = req.output[-1]
                livev[slot] = True
        with TraceAnnotation("serve.launch"):
            self.cache, *outs = self._decode_jit(
                self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(self.pos), jnp.asarray(self.remaining),
                jnp.asarray(livev), jnp.int32(k))
        iters = self._apply_decode_outputs([(s, s) for s in slots], *outs, now)
        return len(slots), iters

    def step(self, now: float | None = None, *,
             decode_steps: int | None = None) -> int:
        """One engine step: refill + one batched device loop over the active
        slots (``decode_steps`` tokens per slot, default 1).  Returns the
        number of slots that served work this step (decodes plus fill-time
        completions).

        The step is the ``serve.step`` span of a profiler trace (arguments
        ``k`` and ``rows``, the slots the loop serves), holding
        ``serve.fill`` and the loop's ``serve.pack``, ``serve.launch``,
        ``serve.sync``, ``serve.fold`` and ``serve.rollback``; they are
        recorded only while a profiler session runs."""
        now = time.monotonic() if now is None else now
        k = max(int(decode_steps or 1), 1)
        if k > self.decode_steps:
            # the emitted-token carry buffer is cfg.decode_steps wide (a
            # trace-time constant); silently clamping would make a driver's
            # virtual clock drift from what the engine actually served
            raise ValueError(
                f"decode_steps={k} > ServeConfig.decode_steps="
                f"{self.decode_steps}; raise the config to burst this far")
        self._clock += 1
        with TraceAnnotation("serve.step", k=k) as span:
            with TraceAnnotation("serve.fill"):
                fill_done = self._fill_slots(now)
            span.set_metadata(rows=len(self.active))
            if not self.active:
                if fill_done:
                    self.step_count += 1
                return fill_done
            if self.chunked:
                served, iters = self._decode_active_mixed(now, k)
            elif self.paged:
                served, iters = self._decode_active_paged(now, k)
            else:
                served, iters = self._decode_all_dense(now, k)
            self.step_count += max(iters, 1)
            return served + fill_done

    def run_until_drained(self, max_steps: int = 10_000) -> None:
        """Drain queue + active set at the full device-resident sync cadence
        (``cfg.decode_steps`` tokens between host round trips)."""
        for _ in range(max_steps):
            if not self.queue and not self.active:
                return
            self.step(decode_steps=self.decode_steps)
        raise RuntimeError("engine failed to drain")


__all__ = ["MigratedRequest", "Request", "ServeConfig", "ServingEngine"]
