"""Paged KV cache: block-table storage + the cache-ops interface decode runs on.

The serving engine's KV memory is a pool of fixed-size *pages* (``page_size``
tokens each), shared by every decode slot.  A slot owns an ordered list of
pages recorded in its block-table row: ``block_table[s, i]`` is the physical
page holding logical positions ``[i*ps, (i+1)*ps)`` of slot ``s``.  Page 0 is
the reserved TRASH page -- it is never allocated, and absorbs the writes of
padding rows and prefill-bucket overhang so every jit shape stays fixed.

Free-list discipline (pinned by tests, documented in DESIGN.md):

* **ownership** -- a non-trash page id is held by at most one slot at a time;
  ``free + held == num_pages - 1`` always;
* **alloc at prefill** -- ``ceil(prompt_len / ps)`` pages; **append** one page
  when decode crosses a page boundary; **free** every page when the slot is
  released (completion, eviction, or reclaim of a force-popped slot);
* **reservation** -- admission reserves the slot's worst-case page count
  (``ceil((prompt_len + max_new - 1) / ps)``), so a mid-decode append can
  never deadlock on an empty pool.

The pure functions (`paged_update`, `paged_gather`, `write_prefill_pages`)
and the small cache-ops classes below are the jit-side interface
:func:`repro.models.lm.block_decode` consumes -- dense and paged storage
behind one ``write / view / mask`` contract.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

TRASH_PAGE = 0


# ---------------------------------------------------------------------------------
# pure jit-side page ops
# ---------------------------------------------------------------------------------

# replint: traced -- jitted from the serving engine
def paged_update(cache, new, block_table, pos):
    """Scatter one new token per batch row into the page pool.

    cache: (P, ps, *rest); new: (B, 1, *rest); block_table: (B, n) int32;
    pos: (B,) logical write positions.  Rows whose table entry is the trash
    page write harmlessly into page 0.
    """
    P, ps = cache.shape[0], cache.shape[1]
    rest = cache.shape[2:]
    B = new.shape[0]
    idx = block_table[jnp.arange(B), pos // ps] * ps + pos % ps      # (B,)
    flat = cache.reshape((P * ps,) + rest)
    flat = flat.at[idx].set(new[:, 0].astype(cache.dtype))
    return flat.reshape(cache.shape)


# replint: traced -- jitted from the serving engine
def paged_update_span(cache, new, block_table, pos):
    """Scatter a span of ``T`` new tokens per batch row into the page pool.

    cache: (P, ps, *rest); new: (B, T, *rest); block_table: (B, n) int32;
    pos: (B,) logical positions of each row's span start -- row b writes
    logical positions [pos[b], pos[b] + T).  This is the mixed chunked-
    prefill / speculative-verify write: positions past a row's allocated
    pages hit TRASH block-table entries and land in page 0; positions past
    the table itself are clamped to the row's last logical slot, whose
    entry is TRASH unless the row is full -- and a full row only overflows
    after it has parked, when its KV is never read again.
    """
    P, ps = cache.shape[0], cache.shape[1]
    rest = cache.shape[2:]
    B, T = new.shape[0], new.shape[1]
    n = block_table.shape[1]
    p = jnp.clip(pos[:, None] + jnp.arange(T)[None, :], 0, n * ps - 1)  # (B, T)
    pages = jnp.take_along_axis(block_table, p // ps, axis=1)           # (B, T)
    idx = pages * ps + p % ps
    flat = cache.reshape((P * ps,) + rest)
    flat = flat.at[idx.reshape(-1)].set(
        new.reshape((B * T,) + rest).astype(cache.dtype))
    return flat.reshape(cache.shape)


# replint: traced -- jitted from the serving engine
def paged_gather(cache, block_table):
    """Reconstruct the dense per-slot view from the page pool.

    cache: (P, ps, *rest); block_table: (B, n) -> (B, n*ps, *rest); entry j of
    row b is logical position j of slot b (table order == logical order).
    """
    P, ps = cache.shape[0], cache.shape[1]
    rest = cache.shape[2:]
    B, n = block_table.shape
    flat = cache.reshape((P * ps,) + rest)
    idx = (block_table[:, :, None] * ps
           + jnp.arange(ps, dtype=block_table.dtype)[None, None, :]).reshape(B, n * ps)
    return flat[idx]


# replint: traced -- jitted from the serving engine
def write_prefill_pages(pages, cache, page_ids):
    """Scatter a batched prefill cache into the pool, page-chunked.

    pages: pytree of (L, P, ps, *rest); cache: matching pytree of
    (L, B, pb, *rest) with pb a multiple of ps; page_ids: (B, pb // ps) int32
    (or (pb // ps,) for B == 1) -- real pages first, trash (0) for the bucket
    overhang past each prompt.  Real page ids are unique across rows (free-
    list ownership); several rows may scatter their overhang into the trash
    page, where any of the duplicate writes may win -- all are garbage.
    """
    ids = jnp.reshape(jnp.asarray(page_ids, jnp.int32), (-1,))

    def scatter(pg, c):
        L, _, ps = pg.shape[:3]
        rest = pg.shape[3:]
        B, nc = c.shape[1], c.shape[2] // ps
        chunks = c.reshape((L, B * nc, ps) + rest).astype(pg.dtype)
        return pg.at[:, ids].set(chunks)

    return jax.tree.map(scatter, pages, cache)


# ---------------------------------------------------------------------------------
# cache-ops: the write / view / mask contract block_decode consumes
# ---------------------------------------------------------------------------------

# replint: traced -- jitted from the serving engine
def _vector_mask(seq_len, pos, window):
    """(B, Sq=1, S) validity mask for per-row positions -- shared by the dense
    vector path and the paged path so their semantics can never diverge."""
    k_pos = jnp.arange(seq_len)
    valid = k_pos[None, :] < pos[:, None] + 1                 # (B, S)
    valid &= jnp.where(window > 0, k_pos[None, :] > pos[:, None] - window, True)
    return valid[:, None, :]


# replint: traced -- jitted from the serving engine
def _span_mask(seq_len, pos, q_len, window):
    """(B, T, S) causal mask for a T-token span starting at per-row ``pos``:
    query j of row b sits at logical position pos[b] + j and attends keys
    k <= pos[b] + j (minus the sliding window, when set).  The T=1 slice is
    exactly :func:`_vector_mask` -- the mixed chunked-prefill / speculative
    path and the single-token decode path can never diverge."""
    k_pos = jnp.arange(seq_len)                               # (S,)
    q_pos = pos[:, None] + jnp.arange(q_len)[None, :]         # (B, T)
    valid = k_pos[None, None, :] <= q_pos[:, :, None]         # (B, T, S)
    valid &= jnp.where(window > 0,
                       k_pos[None, None, :] > q_pos[:, :, None] - window, True)
    return valid


class DenseScalarOps:
    """Uniform-position dense cache: all rows write at the same scalar pos."""

    def write(self, cache, new, pos):
        return jax.lax.dynamic_update_slice(
            cache, new.astype(cache.dtype), (0, pos) + (0,) * (cache.ndim - 2))

    def view(self, cache):
        return cache

    def mask(self, seq_len, pos, window):
        k_pos = jnp.arange(seq_len)
        valid = k_pos < pos + 1
        valid &= jnp.where(window > 0, k_pos > pos - window, True)
        return valid[None, :]                                 # (Sq=1, S)


class DenseVectorOps:
    """Heterogeneous-position dense cache: per-row write positions (B,)."""

    def write(self, cache, new, pos):
        zeros = (0,) * (cache.ndim - 2)
        return jax.vmap(
            lambda cb, nb, pb: jax.lax.dynamic_update_slice(
                cb, nb.astype(cb.dtype), (pb,) + zeros))(cache, new, pos)

    def view(self, cache):
        return cache

    def mask(self, seq_len, pos, window):
        return _vector_mask(seq_len, pos, window)


@dataclass
class PagedOps:
    """Block-table paged cache: pool leaves are (P, ps, *rest), shared by all
    rows; logical order is recovered by gathering in table order."""

    block_table: jax.Array                                    # (B, n) int32

    def write(self, cache, new, pos):
        return paged_update(cache, new, self.block_table, pos)

    def write_span(self, cache, new, pos):
        return paged_update_span(cache, new, self.block_table, pos)

    def view(self, cache):
        return paged_gather(cache, self.block_table)

    def mask(self, seq_len, pos, window):
        return _vector_mask(seq_len, pos, window)

    def span_mask(self, seq_len, pos, q_len, window):
        return _span_mask(seq_len, pos, q_len, window)


# ---------------------------------------------------------------------------------
# the host-side pool
# ---------------------------------------------------------------------------------

class PagedKVCache:
    """Page pool + block tables + free list for one :class:`ServingEngine`.

    ``init_cache_fn(batch, max_len)`` is the model's cache constructor; its
    leaf layout (L, B, S, *rest) is reinterpreted as per-page (L, P, ps, *rest)
    pools, so the same class serves f32/bf16 and int8 (value + scale leaves)
    caches without knowing the schema.  ``device``: where the pools live
    (None: JAX's default placement).
    """

    def __init__(self, init_cache_fn, *, max_batch: int, max_len: int,
                 page_size: int = 16, num_pages: int | None = None,
                 device=None):
        if page_size < 1 or page_size & (page_size - 1):
            # power of two: every pow2 prefill bucket >= page_size is then a
            # whole number of page chunks
            raise ValueError(f"page_size={page_size} must be a power of two")
        if max_len % page_size:
            raise ValueError(f"max_len={max_len} not a multiple of "
                             f"page_size={page_size}")
        self.page_size = page_size
        self.pages_per_slot = max_len // page_size
        # worst case: every slot full, plus the trash page
        self.num_pages = (num_pages if num_pages is not None
                          else max_batch * self.pages_per_slot + 1)
        proto = jax.eval_shape(lambda: init_cache_fn(1, page_size))
        self.pages = jax.tree.map(
            lambda s: jnp.zeros((s.shape[0], self.num_pages) + s.shape[2:],
                                s.dtype, device=device), proto)
        self.block_table = np.zeros((max_batch, self.pages_per_slot), np.int32)
        self.held = np.zeros(max_batch, np.int32)         # pages owned per slot
        self.worst = np.zeros(max_batch, np.int32)        # reserved worst case
        self._free: list[int] = list(range(self.num_pages - 1, TRASH_PAGE, -1))
        self._outstanding = 0                             # sum(worst - held)

    # -- accounting -------------------------------------------------------------
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_reserved(self) -> int:
        """Pages reserved for the slots' worst cases, held or not yet."""
        return int(self.worst.sum())

    def pages_needed(self, n_tokens: int) -> int:
        return max(math.ceil(n_tokens / self.page_size), 1)

    def can_admit(self, total_tokens: int, planned: int = 0) -> bool:
        """True if the pool can guarantee a request writing ``total_tokens``
        logical positions (prompt + decode appends) will never starve.

        ``planned``: worst-case pages already promised to co-admitted
        requests whose allocation has not executed yet (batched prefill
        collects a group before allocating any of it)."""
        return (self.pages_needed(total_tokens)
                <= self.n_free - self._outstanding - planned)

    # -- lifecycle --------------------------------------------------------------
    def alloc_prefill(self, slot: int, prompt_len: int, total_tokens: int,
                      n_chunks: int) -> np.ndarray:
        """Allocate the prompt's pages for ``slot`` and reserve its worst case.

        Returns the (n_chunks,) int32 page-id vector for the bucketed prefill
        scatter -- real pages first, trash for the bucket overhang.
        """
        n = self.pages_needed(prompt_len)
        worst = max(self.pages_needed(total_tokens), n)
        if n > self.n_free:
            raise RuntimeError("page pool exhausted despite reservation")
        ids = [self._free.pop() for _ in range(n)]
        self.block_table[slot, :n] = ids
        self.held[slot] = n
        self.worst[slot] = worst
        self._outstanding += worst - n
        out = np.full(n_chunks, TRASH_PAGE, np.int32)
        out[:n] = ids
        return out

    def reserve(self, slot: int, total_tokens: int) -> None:
        """Register ``slot``'s worst-case page count without allocating yet.

        Chunked-prefill admission: the slot's pages are appended lazily by
        :meth:`ensure_writable_span` as chunks stream in, but the reservation
        must be on the books from admission so co-admitted requests cannot
        promise away the pages this one will need."""
        worst = self.pages_needed(total_tokens)
        if worst > self.pages_per_slot:
            raise RuntimeError(f"reservation past slot capacity at slot {slot}")
        self._outstanding += worst - int(self.worst[slot])
        self.worst[slot] = worst

    def ensure_writable(self, slot: int, pos: int) -> None:
        """Append a page if the next write at logical ``pos`` crosses into an
        unallocated page (decode-time growth)."""
        self.ensure_writable_span(slot, pos, 1)

    def ensure_writable_span(self, slot: int, pos: int, n: int) -> None:
        """Make logical positions [pos, pos + n) of ``slot`` writable,
        appending pages as needed.

        This is the device-resident decode loop's contract: the host
        pre-allocates every page the next K on-device steps may write, so the
        jitted multi-step loop never has to sync back for a page append.  The
        span is bounded by the slot's remaining token budget, which the
        admission reservation already covers -- pre-allocating it early can
        never starve another slot's reserved append.
        """
        if n <= 0:
            return
        last_page = (pos + n - 1) // self.page_size
        if last_page >= self.pages_per_slot:
            raise RuntimeError(f"span past slot capacity at slot {slot}")
        if self.held[slot] < pos // self.page_size:
            raise RuntimeError(f"non-contiguous page growth at slot {slot}")
        while self.held[slot] <= last_page:
            if not self._free:
                raise RuntimeError("page pool exhausted despite reservation")
            self.block_table[slot, self.held[slot]] = self._free.pop()
            self.held[slot] += 1
            self._outstanding -= 1

    def shrink_to(self, slot: int, n_tokens: int) -> int:
        """Return pages past ``ceil(n_tokens / ps)`` to the free list.

        Speculative-decode rollback: the host pre-allocates pages for the
        worst case (every draft token accepted); after the sync reveals how
        many were actually committed, pages holding only rejected positions
        are handed back and their table entries reset to TRASH.  The freed
        pages re-enter ``_outstanding`` -- the slot's reservation still
        covers them, so a later accept-heavy burst can re-append without
        starving anyone.  Rejected tokens *within* the kept pages are not
        scrubbed: the next verify writes the same logical positions before
        any mask lets them be read.

        Returns the number of pages freed."""
        keep = min(self.pages_needed(n_tokens), int(self.held[slot]))
        freed = int(self.held[slot]) - keep
        if freed <= 0:
            return 0
        for i in range(keep, int(self.held[slot])):
            self._free.append(int(self.block_table[slot, i]))
            self.block_table[slot, i] = TRASH_PAGE
        self.held[slot] = keep
        self._outstanding += freed
        return freed

    def release(self, slot: int) -> None:
        """Return every page ``slot`` holds and drop its reservation."""
        n = int(self.held[slot])
        if n:
            self._free.extend(int(p) for p in self.block_table[slot, :n])
        self._outstanding -= int(self.worst[slot]) - n
        self.block_table[slot] = TRASH_PAGE
        self.held[slot] = 0
        self.worst[slot] = 0

    # -- migration (drain path; see engine.export_request) ----------------------
    def export_slot(self, slot: int):
        """Copy ``slot``'s held pages out of the pool as HOST arrays, in
        logical order: a pytree of (L, h, ps, *rest) leaves with h = pages
        held.  Positions past the slot's committed count inside the last
        page are garbage, exactly as they are on the source after a
        ``shrink_to`` -- the importer rewrites them before any mask lets
        them be read.  Returns None for a slot with no pages yet."""
        h = int(self.held[slot])
        if h == 0:
            return None
        ids = np.asarray(self.block_table[slot, :h])
        return jax.tree.map(lambda pg: np.asarray(pg[:, ids]), self.pages)

    # replint: traced -- write_prefill_pages is jit-side; the eager call here
    # is the cold migration path
    def import_slot(self, slot: int, chunks, total_tokens: int) -> None:
        """Install chunks from :meth:`export_slot` as ``slot``'s committed
        KV: allocate exactly their page count, put the slot's worst-case
        reservation (``total_tokens``) on the books, and scatter the pages
        into the pool in logical order."""
        h = jax.tree.leaves(chunks)[0].shape[1]
        ids = self.alloc_prefill(slot, h * self.page_size, total_tokens, h)
        cache = jax.tree.map(
            lambda c: jnp.asarray(c).reshape(
                (c.shape[0], 1, h * self.page_size) + c.shape[3:]), chunks)
        self.pages = write_prefill_pages(self.pages, cache, ids)

    # -- invariants (tests) -----------------------------------------------------
    def check_invariants(self) -> None:
        owned = [int(p) for s in range(self.block_table.shape[0])
                 for p in self.block_table[s, :self.held[s]]]
        assert TRASH_PAGE not in owned, "trash page allocated to a slot"
        assert len(owned) == len(set(owned)), "page owned by two slots"
        assert len(owned) + self.n_free == self.num_pages - 1, "page leak"
        assert self._outstanding == int((self.worst - self.held).sum())
        assert TRASH_PAGE not in self._free


__all__ = [
    "TRASH_PAGE", "paged_update", "paged_update_span", "paged_gather",
    "write_prefill_pages",
    "DenseScalarOps", "DenseVectorOps", "PagedOps", "PagedKVCache",
]
