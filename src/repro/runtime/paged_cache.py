"""Paged KV-cache subsystem: block-pool allocator + block-table array ops.

The serving cache is a *pool* of fixed-size KV blocks (pages) shared by all
sequences, FlashMLA/vLLM-style, instead of a dense ``[B, max_len]`` slab:

    pool        [num_blocks, block_size, *feat]   (per layer; jnp, on device)
    block_table [B, max_blocks]  int32            (shared across layers)
    lengths     [B]              int32            (tokens written per slot)

Sequence ``b``'s token at logical position ``t`` lives at
``pool[block_table[b, t // block_size], t % block_size]``.  Block ids are
granted by a host-side free-list (:class:`BlockPool`); the block *table* is
what the paged Pallas kernels prefetch to gather KV through (see
``kernels/etap/etap.py``).

Allocator invariants (DESIGN.md §8):
  · Block 0 is the reserved NULL block: never allocated, every padded /
    released table entry points at it.  Inactive batch slots therefore
    write their (ignored) decode rows into block 0 and read back finite
    garbage that is masked by ``length`` — no branches anywhere on device.
  · Admission reserves blocks for the request's full budget
    (prompt + max new tokens) up front, so a decode step can never fail
    mid-flight; running out of blocks is an *admission refusal*, which the
    continuous-batching scheduler (launch/serve.py) handles by queueing.
  · ``release`` returns blocks to the free list and zeroes the table row,
    so ids are recycled across requests (tests/test_paged.py proves
    reuse-after-release and the refusal path).

Prefix sharing (DESIGN.md §10): every non-null block carries a REFCOUNT —
one reference per batch slot mapping it plus one for the prefix-cache trie
(runtime/prefix_cache.py) when the block is cached.  :meth:`BlockPool.admit_shared`
maps an already-computed prefix chain into a new slot's table with a
refcount bump instead of a free-list draw (its prefill is SKIPPED), and
copy-on-write is eager-at-admission: a cached prefix ending mid-block gets
its partial tail block copied into a fresh private block before any token
is written, so in-flight writes never need to allocate (the no-mid-flight-
OOM invariant survives sharing).  ``release`` drops one reference per
chain block; only blocks hitting refcount zero return to the free list —
trie-cached prompt blocks live on as the LRU-evictable cached set.
Conservation (checked by :meth:`BlockPool.check_conservation`): a non-null
block is on the free list iff its refcount is zero, and writes may only
touch refcount-1 (exclusively owned) blocks.

Rollback and the host swap tier (DESIGN.md §12): :meth:`BlockPool.truncate`
is the invariant-safe rollback primitive — it shrinks a slot's block chain
(and, because the sz scale pools page with the code pools, its quantized
twin) to a token boundary, freeing tail blocks through the same
``unref_block`` path release uses, so trie-cached blocks survive and
free ⟺ ref == 0 conservation holds at every intermediate state.  ``release``
is ``truncate(slot, 0)`` plus slot teardown.  On top of it the pool is a
TWO-TIER HBM/host hierarchy: ``swap_out`` moves a preempted slot's written
blocks into a host-RAM tier (a second free-list of ``host_blocks`` ids; the
actual bytes are read off-device by the caller BEFORE the call and restored
by it after ``swap_in``), releasing every device block.  Swap records hold
NO device references — a swapped request's trie-cached prompt blocks are
owned by the trie alone, and cancelling a swapped request frees host ids
only (the double-unref edge tests/test_scheduler.py pins).

Quantized layouts (DESIGN.md §11): the pool may store KV rows as int8 (or
fp8 e4m3) codes with a per-ROW affine (scale, zero-point) pair kept in a
parallel ``sz`` pool of shape ``[num_blocks, block_size, *lead, 2]``.  The
row is the quantization granule — one (scale, zp) per written token (per
kv head for GQA pools) — because every write path (append_rows,
append_chunk) touches whole rows and only rows: a PER-BLOCK scale would
have to re-quantize previously-written rows whenever a later append raised
the block's max, destroying the bitwise-stability the prefix cache depends
on.  Scales live at block granularity in STORAGE (the sz pool pages with
the code pool, so :func:`copy_block` and COW move codes and scales
together), while the numeric granule is the row.  Dequantization is fused
inside the ETAP Pallas kernels (kernels/etap/etap.py): codes and scales
stream per pool block and are expanded in registers before the dot;
softmax statistics and accumulation stay fp32 (§6).
"""
from __future__ import annotations

import dataclasses
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

NULL_BLOCK = 0

# Quantized KV layouts (DESIGN.md §11).  "fp" is the config dtype
# passthrough; "int8" is asymmetric per-row affine; "fp8" emulates the
# H20's e4m3 format via jnp.float8_e4m3fn (symmetric — fp8 has a sign
# bit, so the zero-point is pinned to 0 and only the scale is live).
KV_LAYOUTS = ("fp", "int8", "fp8")
HAS_FP8 = hasattr(jnp, "float8_e4m3fn")
F8_MAX = 448.0                    # e4m3fn finite max (no inf encoding)
INT8_MAX = 127.0
LANES = 128                       # a TPU vreg's lane count (DESIGN.md §8)


def lane_width(d: int) -> int:
    """Stored width of a d-wide paged latent row: d rounded up to whole
    128-lane tiles (576 -> 640).  A pool whose rows fill their lane tiles
    has a row-major default layout on the TPU at any block count, so no
    program relays it (DESIGN.md §8); a width already a multiple of 128
    is stored as it is."""
    return -(-int(d) // LANES) * LANES


def pad_lanes(x, width: int):
    """Zero-extend x's last axis to ``width`` (x as it is at that width)."""
    pad = width - x.shape[-1]
    if not pad:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Static geometry of a paged cache."""
    block_size: int            # tokens per KV block (page)
    num_blocks: int            # pool size, INCLUDING the reserved null block
    max_blocks: int            # block-table width (max logical blocks/seq)

    def __post_init__(self):
        assert self.block_size >= 1 and self.max_blocks >= 1
        assert self.num_blocks >= 2, "need at least null block + one real block"

    @property
    def max_len(self) -> int:
        return self.max_blocks * self.block_size

    def blocks_for(self, n_tokens: int) -> int:
        return max(1, -(-int(n_tokens) // self.block_size))


def layout_for(batch_slots: int, max_len: int, block_size: int = 64,
               spare_blocks: int = 0) -> PagedLayout:
    """A layout that can hold `batch_slots` full-length sequences (+spares)."""
    max_blocks = max(1, -(-int(max_len) // block_size))
    return PagedLayout(block_size=block_size,
                       num_blocks=1 + batch_slots * max_blocks + spare_blocks,
                       max_blocks=max_blocks)


@dataclasses.dataclass
class SwapRecord:
    """Accounting for one preempted sequence resident in the host tier.

    ``host_ids`` hold one host-tier block id per WRITTEN logical block (the
    tail of the reservation that held no rows is re-reserved at swap_in,
    not stored); ``n_tokens`` is how many rows the host copies carry and
    ``budget`` the original reserved token budget, so restoration re-admits
    with exactly the guarantees the first admission had.  A record holds NO
    device references: the victim's trie-cached prompt blocks belong to the
    trie alone after swap_out, and discarding a record (cancel) returns
    host ids only."""
    key: object
    host_ids: list
    n_tokens: int
    budget: int


class BlockPool:
    """Host-side free-list allocator over `layout.num_blocks` KV blocks,
    owning the block table and per-slot lengths for `batch_slots` slots.
    ``host_blocks`` > 0 adds the host swap tier (DESIGN.md §12)."""

    def __init__(self, layout: PagedLayout, batch_slots: int,
                 host_blocks: int = 0, *, metrics=None):
        self.layout = layout
        self.batch_slots = batch_slots
        # optional MetricsRegistry (runtime/telemetry.py): swap-tier
        # traffic counters at swap_out/swap_in; occupancy gauges go
        # through :meth:`observe` (accounting only — never control flow)
        self.metrics = metrics
        # pop order low→high keeps tables human-readable in tests/logs
        self._free = deque(range(1, layout.num_blocks))      # 0 = null block
        self.table = np.zeros((batch_slots, layout.max_blocks), np.int32)
        self.lengths = np.zeros((batch_slots,), np.int32)
        self.active = np.zeros((batch_slots,), bool)
        # per-block reference count: one per slot mapping the block + one
        # when the prefix-cache trie holds it.  free ⟺ ref == 0.
        self.ref = np.zeros((layout.num_blocks,), np.int32)
        # logical block chain per slot: shared prefix blocks first (mapped
        # by admit_shared, refcount-bumped), then freshly allocated blocks
        self._chain: list[list[int]] = [[] for _ in range(batch_slots)]
        self._nshared = np.zeros((batch_slots,), np.int32)
        self._budget = np.zeros((batch_slots,), np.int32)    # reserved tokens
        # host swap tier: a second free-list of host-RAM block ids.  The
        # pool accounts capacity; the KV bytes live with the caller (read
        # off-device before swap_out, written back after swap_in).
        self.host_blocks = int(host_blocks)
        self._host_free = deque(range(self.host_blocks))
        self.swapped: dict = {}                  # key -> SwapRecord

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def host_free(self) -> int:
        return len(self._host_free)

    def free_slots(self) -> list[int]:
        return [b for b in range(self.batch_slots) if not self.active[b]]

    def free_ids(self) -> set[int]:
        """Snapshot of the free-list block ids — the public inspection
        surface for invariant tests (free ⟺ refcount 0 conservation);
        mutation still goes through admit/extend/append/release."""
        return set(self._free)

    def budget(self, slot: int) -> int:
        """Reserved token budget of `slot` (0 when torn down): the cap
        ``extend``/``append`` enforce and ``truncate`` rewinds to."""
        return int(self._budget[slot])

    def can_admit(self, max_total_len: int, n_shared: int = 0) -> bool:
        """Admission predicate: a free batch slot AND enough free blocks to
        reserve the request's whole token budget.  ``n_shared`` counts FULL
        prefix blocks only — blocks mapped from the prefix cache by a
        refcount bump with no free-list draw.  A chain whose tail block is
        PARTIAL (the shared prefix ends mid-block) contributes
        ``matched_tokens // block_size``, NOT ``len(chain)``: the partial
        donor block is never mapped — its logical position is taken by a
        freshly drawn eager-COW copy target, which must be counted against
        the free list *before* admission succeeds (the one-block-short
        refusal boundary, tests/test_paged.py)."""
        if max_total_len > self.layout.max_len:
            return False
        need = self.layout.blocks_for(max_total_len) - int(n_shared)
        return bool(self.free_slots()) and need <= self.num_free

    def admit(self, prompt_len: int, max_total_len: int) -> int | None:
        """Reserve a slot + blocks for `max_total_len` tokens; returns the
        slot id, or None (admission refusal — the caller keeps the request
        queued).  `prompt_len` rows are accounted as already written (the
        test/bench path that packs a prefilled dense cache via
        :func:`dense_to_paged`).  prompt_len 0 is a COLD admission: blocks
        are reserved but nothing is written yet — the chunked-prefill
        scheduler grows the length via :func:`extend` as it appends prompt
        chunks (launch/serve.py, DESIGN.md §9)."""
        got = self.admit_shared(prompt_len, max_total_len, ())
        return None if got is None else got[0]

    def admit_shared(self, prompt_len: int, max_total_len: int,
                     shared_ids) -> tuple | None:
        """Admission with a cached prefix: map `shared_ids` — the physical
        chain holding the request's first `prompt_len` tokens, found by the
        prefix-cache trie — into the new slot's table with a refcount bump
        per block, and allocate fresh blocks only for the remaining budget.
        The mapped prefix is never prefilled again (its tokens are
        accounted as written); chunked prefill resumes at offset
        `prompt_len`.

        Copy-on-write on divergence: when `prompt_len` ends MID-block, the
        chain's partial tail block is still the donor's (its later rows
        belong to the donor's continuation), so it is NOT mapped — the
        first fresh block takes its logical position and the pair is
        returned for the caller to device-copy (models.model.copy_paged_block)
        BEFORE any chunk is appended.  The copy happens at admission, not at
        write time, so admission still reserves the whole budget up front
        and in-flight steps never allocate.  The donor block must be kept
        referenced by the caller (trie or donor slot) until the copy runs.

        Returns (slot, cow) with cow = [] or [(src_block, dst_block)], or
        None (refusal)."""
        assert 0 <= prompt_len <= max_total_len and max_total_len >= 1
        shared_ids = [int(b) for b in shared_ids]
        n_full = prompt_len // self.layout.block_size
        if shared_ids:
            assert prompt_len >= 1
            assert len(shared_ids) == self.layout.blocks_for(prompt_len), \
                "shared chain must cover exactly the prompt_len prefix"
        else:
            n_full = 0                       # nothing to map without a chain
        if not self.can_admit(max_total_len, n_shared=n_full):
            return None
        slot = self.free_slots()[0]
        need = self.layout.blocks_for(max_total_len)
        reused = shared_ids[:n_full]
        fresh = [self._free.popleft() for _ in range(need - n_full)]
        cow = []
        if len(shared_ids) > n_full:         # prefix ends mid-block: COW
            cow.append((shared_ids[n_full], fresh[0]))
        for bid in reused:
            assert self.ref[bid] > 0, "shared block must be live (trie/slot)"
            self.ref[bid] += 1
        for bid in fresh:
            assert self.ref[bid] == 0
            self.ref[bid] = 1
        chain = reused + fresh
        self._chain[slot] = chain
        self._nshared[slot] = len(reused)
        self.table[slot] = NULL_BLOCK
        self.table[slot, :len(chain)] = chain
        self.lengths[slot] = prompt_len
        self._budget[slot] = max_total_len
        self.active[slot] = True
        return slot, cow

    def block_ids(self, slot: int) -> np.ndarray:
        """Physical block chain of `slot` in logical order: shared prefix
        blocks (if any) first, then the freshly allocated blocks."""
        return np.asarray(self._chain[slot], np.int32)

    def ref_block(self, bid: int) -> None:
        """Take an external (prefix-trie) reference on a live block."""
        assert bid != NULL_BLOCK and self.ref[bid] > 0
        self.ref[bid] += 1

    def unref_block(self, bid: int) -> bool:
        """Drop one reference; the block returns to the free list when the
        count hits zero.  Returns True iff the block was freed."""
        assert bid != NULL_BLOCK and self.ref[bid] > 0
        self.ref[bid] -= 1
        if self.ref[bid] == 0:
            self._free.append(bid)
            return True
        return False

    def _assert_writable(self, slot: int, lo_tok: int, hi_tok: int) -> None:
        """Writes may only touch exclusively-owned blocks (refcount 1): a
        shared or trie-cached block is read-only for every mapper — the COW
        copy at admission guarantees the write region is always private."""
        bs = self.layout.block_size
        for bid in self._chain[slot][lo_tok // bs:hi_tok // bs + 1]:
            assert self.ref[bid] == 1, \
                f"COW violation: write into shared block {bid} " \
                f"(refcount {int(self.ref[bid])})"

    def append(self, slot: int) -> None:
        """Account one generated token for `slot` (the device-side write is
        :func:`append_rows`).  Never allocates: admission already reserved
        the full budget."""
        assert self.active[slot]
        assert self.lengths[slot] < self._budget[slot], \
            f"slot {slot} exceeded its reserved budget"
        self._assert_writable(slot, int(self.lengths[slot]),
                              int(self.lengths[slot]))
        self.lengths[slot] += 1

    def extend(self, slot: int, n: int) -> None:
        """Account `n` prompt tokens appended to `slot` in one prefill chunk
        (the device-side write is :func:`append_chunk`).  Never allocates:
        admission already reserved the full budget, so a chunk can never run
        out of blocks mid-prompt."""
        assert self.active[slot] and n >= 0
        assert self.lengths[slot] + n <= self._budget[slot], \
            f"slot {slot} chunk of {n} exceeds its reserved budget"
        if n:
            self._assert_writable(slot, int(self.lengths[slot]),
                                  int(self.lengths[slot]) + n - 1)
        self.lengths[slot] += n

    def truncate(self, slot: int, n_tokens: int, *,
                 free_blocks: bool = True) -> int:
        """Invariant-safe ROLLBACK (DESIGN.md §12): shrink `slot`'s chain
        to the `n_tokens` boundary.  Tail blocks beyond
        ``blocks_for(n_tokens)`` (all of them at 0) are dropped through
        :meth:`unref_block` — a trie-cached or slot-shared tail block
        survives at its remaining refcount, exactly like release — their
        table columns are nulled, and the slot's budget shrinks to the
        kept blocks' capacity (the slot may still fill the kept tail block
        without allocating, but growing past it needs a fresh admission).
        The sz scale pools shrink for free: they page with the code pools,
        and rows beyond the new length are masked by ``lengths`` on every
        read path.  A truncation landing MID-block keeps that boundary
        block; if it is shared (refcount > 1) it stays read-only and the
        device write guard still fires on any append into it.

        ``free_blocks=False`` is the pure LENGTH rollback (the speculative-
        decoding primitive, ROADMAP item 2): only ``lengths`` rewinds — the
        rejected tokens' rows become masked garbage — and the reservation
        is untouched, so decoding continues under the no-mid-flight-
        allocation guarantee.

        Returns the number of blocks freed to the free list."""
        assert self.active[slot]
        n_tokens = int(n_tokens)
        assert 0 <= n_tokens <= int(self.lengths[slot]), \
            f"truncate to {n_tokens} past written length " \
            f"{int(self.lengths[slot])}"
        if not free_blocks:
            self.lengths[slot] = n_tokens
            return 0
        keep = self.layout.blocks_for(n_tokens) if n_tokens else 0
        chain = self._chain[slot]
        assert keep <= len(chain)
        freed = 0
        for bid in reversed(chain[keep:]):
            freed += bool(self.unref_block(bid))
        self._chain[slot] = chain[:keep]
        self.table[slot, keep:] = NULL_BLOCK
        self.lengths[slot] = n_tokens
        self._nshared[slot] = min(int(self._nshared[slot]), keep)
        self._budget[slot] = keep * self.layout.block_size
        return freed

    def release(self, slot: int) -> None:
        """Drop one reference per chain block and null the slot's table row
        (``truncate(slot, 0)`` + slot teardown).  Blocks hitting refcount
        zero return to the free list; blocks the prefix-cache trie (or
        another slot) still references stay allocated — that is what turns
        a finished request's prompt blocks into the LRU-evictable cached
        set instead of freeing them."""
        assert self.active[slot]
        # audit (falsifiable): columns BEYOND the chain must already be
        # null — admission nulls the row before writing the chain and no
        # write path touches columns past it, so a stale physical id there
        # means some mutation scribbled the table out of band.  The
        # truncate below then guarantees a released row can never surface
        # a stale mapping through device_views() (tests/test_paged.py).
        assert (self.table[slot, len(self._chain[slot]):]
                == NULL_BLOCK).all(), "stale ids beyond the slot's chain"
        self.truncate(slot, 0)
        self._nshared[slot] = 0
        self._budget[slot] = 0
        self.active[slot] = False

    # ------------------------------------------------------ host swap tier
    def can_swap_out(self, slot: int) -> bool:
        """Whether the host tier can absorb `slot`'s written blocks."""
        n = int(self.lengths[slot])
        nb = self.layout.blocks_for(n) if n else 0
        return nb <= self.host_free

    def swap_out(self, slot: int, key) -> SwapRecord | None:
        """Evacuate `slot` to the host tier: reserve one host block per
        WRITTEN device block, record (key, host ids, written length,
        original budget), then fully release the slot — device blocks the
        trie still caches survive as the cached set, private tail blocks
        free.  Returns the record, or None when the host tier is full (the
        scheduler then falls back to drop-and-recompute preemption).

        The CALLER moves the bytes: it must copy the written blocks
        (``block_ids(slot)[:nb]``) off-device BEFORE calling — after this
        returns, freed device blocks may be re-allocated and overwritten
        at any time."""
        assert self.active[slot]
        assert key not in self.swapped, f"key {key!r} already swapped"
        n_tokens = int(self.lengths[slot])
        nb = self.layout.blocks_for(n_tokens) if n_tokens else 0
        if nb > self.host_free:
            return None
        host_ids = [self._host_free.popleft() for _ in range(nb)]
        rec = SwapRecord(key=key, host_ids=host_ids, n_tokens=n_tokens,
                         budget=int(self._budget[slot]))
        self.swapped[key] = rec
        self.release(slot)
        if self.metrics is not None and nb:
            self.metrics.inc("pool/swap_out_blocks", nb)
        return rec

    def swap_in(self, key, shared_ids=(), matched: int = 0):
        """Restore a swapped sequence into a fresh slot: re-admit with the
        record's ORIGINAL budget (``admit_shared`` — a trie match on the
        prompt maps `shared_ids` by refcount bump so only the unmatched
        blocks need host copies written back), account the restored rows,
        and return the host ids to the tier.  Returns
        ``(slot, cow, record)`` or None (admission refusal: the record is
        untouched and the scheduler retries later).

        The caller writes the bytes AFTER this returns: host copies of
        logical blocks ``[matched // block_size : blocks_for(n_tokens))``
        go into ``block_ids(slot)`` at those positions.  A trie match
        LONGER than the swapped length is fine (the trie grew while the
        request was out): the matched blocks already hold valid rows and
        the restored length is their maximum."""
        rec = self.swapped[key]
        matched = int(matched)
        got = self.admit_shared(matched, rec.budget, shared_ids)
        if got is None:
            return None
        slot, cow = got
        n_eff = max(matched, rec.n_tokens)
        if n_eff > matched:
            self.extend(slot, n_eff - matched)
        if self.metrics is not None and rec.host_ids:
            self.metrics.inc("pool/swap_in_blocks", len(rec.host_ids))
        self.swap_free(key)
        return slot, cow, rec

    def swap_free(self, key) -> SwapRecord:
        """Drop a swap record and return its host ids to the tier — the
        restore-complete path, and the WHOLE release path for a request
        cancelled while preempted: its device references were already
        dropped once at swap_out, so freeing host capacity must not touch
        device refcounts again (the double-unref edge,
        tests/test_scheduler.py)."""
        rec = self.swapped.pop(key)
        self._host_free.extend(rec.host_ids)
        return rec

    def observe(self, metrics=None) -> None:
        """Publish pool occupancy gauges into a MetricsRegistry (the one
        given, else the pool's own).  Pure read — safe at any point the
        pool is consistent (serve calls it once per tick)."""
        m = metrics if metrics is not None else self.metrics
        if m is None:
            return
        m.gauge("pool/free_blocks").set(self.num_free)
        m.gauge("pool/used_blocks").set(
            self.layout.num_blocks - 1 - self.num_free)
        m.gauge("pool/active_slots").set(int(self.active.sum()))
        m.gauge("pool/shared_blocks").set(int((self.ref > 1).sum()))
        m.gauge("pool/host_free_blocks").set(self.host_free)
        m.gauge("pool/swapped_seqs").set(len(self.swapped))

    def check_conservation(self) -> None:
        """Refcount conservation (DESIGN.md §10): refcounts never negative,
        the null block is never referenced or freed, a non-null block is on
        the free list iff its refcount is zero, free + referenced blocks
        partition the pool, and every active slot's chain is fully live.
        Raises AssertionError on any violation (the hypothesis property
        test drives random op interleavings through this)."""
        assert (self.ref >= 0).all()
        assert int(self.ref[NULL_BLOCK]) == 0
        free = list(self._free)
        assert len(set(free)) == len(free), "duplicate ids on the free list"
        assert NULL_BLOCK not in free
        for bid in free:
            assert self.ref[bid] == 0, f"freed block {bid} still referenced"
        n_live = int((self.ref[1:] > 0).sum())
        assert len(free) + n_live == self.layout.num_blocks - 1
        for b in range(self.batch_slots):
            if self.active[b]:
                assert all(self.ref[bid] >= 1 for bid in self._chain[b])
                assert self._nshared[b] <= len(self._chain[b])
            else:
                assert not self._chain[b]
                assert (self.table[b] == NULL_BLOCK).all()
        # host tier: free ids + swap-record ids partition [0, host_blocks),
        # and no record claims more rows than its host blocks can hold
        hf = list(self._host_free)
        assert len(set(hf)) == len(hf), "duplicate ids on the host free list"
        used = [h for r in self.swapped.values() for h in r.host_ids]
        assert len(set(used)) == len(used), "host block in two swap records"
        assert not set(hf) & set(used)
        assert len(hf) + len(used) == self.host_blocks
        for r in self.swapped.values():
            nb = self.layout.blocks_for(r.n_tokens) if r.n_tokens else 0
            assert len(r.host_ids) == nb and r.n_tokens <= r.budget

    def audit(self) -> None:
        """The paranoia sweep (DESIGN.md §12, ``--paranoia N``):
        :meth:`check_conservation` plus the FULL-ROW null audit over every
        slot — table columns beyond each chain must be null and the mapped
        columns must mirror the chain exactly, written lengths must fit
        budgets, and budgets must fit chains — so invariant corruption
        surfaces at the scheduler step that caused it, not at release
        time.  Raises AssertionError on any violation."""
        self.check_conservation()
        for b in range(self.batch_slots):
            chain = self._chain[b]
            assert (self.table[b, len(chain):] == NULL_BLOCK).all(), \
                f"slot {b}: stale ids beyond its chain"
            assert (self.table[b, :len(chain)]
                    == np.asarray(chain, np.int32)).all(), \
                f"slot {b}: table row disagrees with its chain"
            assert int(self.lengths[b]) <= int(self._budget[b])
            if self._budget[b]:
                assert self.layout.blocks_for(int(self._budget[b])) \
                    <= len(chain), f"slot {b}: budget outruns its chain"

    def device_views(self):
        """(block_table [B, max_blocks], lengths [B]) as device arrays.

        COPIES, not views: jnp.array, never jnp.asarray.  On CPU jaxlib
        zero-copies aligned numpy buffers into device arrays, and JAX
        dispatch is async — an in-flight decode step would read the
        allocator's live table/lengths AFTER a subsequent host-side
        append()/release() mutated them (shifting the token write slot),
        a race that corrupts cache rows nondeterministically."""
        return jnp.array(self.table), jnp.array(self.lengths)


# --------------------------------------------------------- device-side ops
def append_rows(pool, table, lengths, rows):
    """Write one new token row per sequence at its current length.

    pool: [N, bs, *F]; table: [B, max_blocks] int32; lengths: [B] int32
    (write position = lengths[b]); rows: [B, *F], the last axis at most
    the pool's and zero-extended to it (a lane-aligned latent pool keeps
    its padded lanes exactly 0).  Inactive slots (all-null table, length
    0) land in the null block — harmless, masked on read."""
    bs = pool.shape[1]
    blk = lengths // bs
    slot = lengths % bs
    pid = jnp.take_along_axis(table, blk[:, None], axis=1)[:, 0]   # [B]
    return pool.at[pid, slot].set(pad_lanes(rows, pool.shape[-1]))


def append_chunk(pool, table, lengths, rows):
    """Write a C-token chunk per sequence starting at its current length.

    pool: [N, bs, *F]; table: [B, max_blocks] int32; lengths: [B] int32
    (chunk token c of sequence b lands at logical position lengths[b] + c);
    rows: [B, C, *F], zero-extended to the pool's last axis as in
    :func:`append_rows`.  The chunked-prefill analogue of
    :func:`append_rows`: one scatter covers the whole chunk even when it
    straddles block boundaries.  Rows of a sequence whose table is all-null
    (inactive slot) land in the null block — harmless, masked on read."""
    bs = pool.shape[1]
    C = rows.shape[1]
    pos = lengths[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]  # [B,C]
    blk = pos // bs
    slot = pos % bs
    pid = jnp.take_along_axis(table, blk, axis=1)                     # [B,C]
    return pool.at[pid, slot].set(
        pad_lanes(rows.astype(pool.dtype), pool.shape[-1]))


def copy_block(pool, src: int, dst: int):
    """Copy-on-write device copy: duplicate physical block `src` into `dst`
    in one pool [N, bs, *F].  The scheduler calls this (via
    models.model.copy_paged_block over the whole cache pytree) on the pair
    returned by :meth:`BlockPool.admit_shared` when a cached prefix ends
    mid-block, before any chunk is appended to the new slot."""
    return pool.at[dst].set(pool[src])


# ------------------------------------------------------------ quantization
def quant_dtype(kv_dtype: str):
    """Pool storage dtype for a KV layout ("fp" -> None: caller keeps the
    config dtype).  Raises on "fp8" when the jax build has no e4m3 type."""
    if kv_dtype == "fp":
        return None
    if kv_dtype == "int8":
        return jnp.int8
    if kv_dtype == "fp8":
        if not HAS_FP8:
            raise ValueError(
                "kv_dtype='fp8' needs jnp.float8_e4m3fn (jax >= 0.4.x with "
                "ml_dtypes); use 'int8' or 'fp' on this build")
        return jnp.float8_e4m3fn
    raise ValueError(f"kv_dtype must be one of {KV_LAYOUTS}, got {kv_dtype!r}")


def kv_dtype_of(pool) -> str:
    """Inverse of :func:`quant_dtype`: classify a pool by its dtype."""
    if pool.dtype == jnp.int8:
        return "int8"
    if HAS_FP8 and pool.dtype == jnp.float8_e4m3fn:
        return "fp8"
    return "fp"


def quantize_rows(rows, kv_dtype: str):
    """Quantize fp rows to (codes, sz) with one affine pair per row.

    rows: [..., F] — the last axis is the feature vector quantized as one
    granule (per kv-head granularity falls out of the leading axes: a GQA
    row [B, K, hd] carries K independent pairs).  Returns
    (codes [..., F] in :func:`quant_dtype`, sz [..., 2] fp32) with
    ``sz[..., 0]`` the scale and ``sz[..., 1]`` the zero-point, such that
    ``dequantize_rows(codes, sz) ≈ rows``:

        int8:  zp = (max+min)/2, scale = (max-min)/254,
               codes = round((x - zp)/scale) ∈ [-127, 127]
        fp8:   zp = 0, scale = amax/448, codes = e4m3(x/scale)

    Degenerate rows (max == min, e.g. the all-zero rows of a fresh pool)
    take scale = 1 so the affine stays invertible and the row round-trips
    exactly (codes 0, zp = the constant).  Quantization is a pure function
    of the row values — re-quantizing identical rows is bitwise stable,
    which is what makes prefix-cached decode bitwise equal to uncached
    *within* a kv layout."""
    dt = quant_dtype(kv_dtype)
    if dt is None:
        raise ValueError("quantize_rows on a 'fp' layout — nothing to do")
    x = rows.astype(jnp.float32)
    hi = jnp.max(x, axis=-1, keepdims=True)
    lo = jnp.min(x, axis=-1, keepdims=True)
    if kv_dtype == "int8":
        zp = (hi + lo) * 0.5
        rng = hi - lo
        scale = jnp.where(rng > 0, rng / (2.0 * INT8_MAX), 1.0)
        codes = jnp.round((x - zp) / scale)
        codes = jnp.clip(codes, -INT8_MAX, INT8_MAX).astype(jnp.int8)
    else:                                     # fp8: symmetric, sign in-code
        amax = jnp.maximum(jnp.abs(hi), jnp.abs(lo))
        zp = jnp.zeros_like(hi)
        scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
        # clamp BEFORE the cast: e4m3fn has no inf, overflow would be nan
        codes = jnp.clip(x / scale, -F8_MAX, F8_MAX).astype(dt)
    sz = jnp.concatenate([scale, zp], axis=-1)          # [..., 2]
    return codes, sz


def dequantize_rows(codes, sz):
    """Inverse of :func:`quantize_rows`: ``codes*scale + zp`` in fp32.
    codes: [..., F]; sz: [..., 2].  This is the SAME expression the Pallas
    kernels apply in registers (kernels/etap/etap.py:_dequant) — the XLA
    reference twin (kernels/etap/ref.py) goes through here, so kernel and
    oracle share one definition of the dequant."""
    return (codes.astype(jnp.float32) * sz[..., 0:1] + sz[..., 1:2])


def quantize_pool(pool, kv_dtype: str):
    """Quantize a whole fp pool [N, bs, *F] into (codes, sz [N, bs, *lead, 2])
    — the test/bench path that packs a prefilled fp pool (dense_to_paged)
    into the quantized layout wholesale."""
    return quantize_rows(pool, kv_dtype)


def row_bytes(feat: int, kv_dtype: str, fp_dtype=jnp.bfloat16,
              granules: int = 1) -> int:
    """KV bytes per written token row: `feat` features stored in the
    layout's code dtype plus (for quantized layouts) `granules` fp32
    (scale, zp) pairs.  The capacity lever the serve loop admits by."""
    if kv_dtype == "fp":
        return feat * jnp.dtype(fp_dtype).itemsize
    return feat + granules * 8            # 1-byte codes + fp32 (scale, zp)


def layout_for_bytes(budget_bytes: int, bytes_per_row: int, max_len: int,
                     block_size: int = 64, spare_blocks: int = 0):
    """Size a (layout, batch_slots) pair to a pool BYTE budget: as many
    blocks as the budget buys at `bytes_per_row`, and as many full-length
    batch slots as those blocks can back.  With the fp row size this
    reproduces :func:`layout_for` exactly; with a quantized row size the
    same budget admits ~2x (int8) the sequences — the acceptance lever of
    DESIGN.md §11.  `spare_blocks` are held OUT of the slot computation
    (the operator's COW / mid-block-admission headroom survives the
    quantized re-sizing instead of being folded into extra slots)."""
    max_blocks = max(1, -(-int(max_len) // block_size))
    block_bytes = block_size * int(bytes_per_row)
    num_blocks = max(2, 1 + int(budget_bytes) // block_bytes)
    usable = max(1, num_blocks - 1 - max(0, int(spare_blocks)))
    batch_slots = max(1, usable // max_blocks)
    return (PagedLayout(block_size=block_size, num_blocks=num_blocks,
                        max_blocks=max_blocks), batch_slots)


def append_rows_quant(pool, sz_pool, table, lengths, rows):
    """Quantized :func:`append_rows`: quantize the new rows in the pool's
    layout and scatter codes + (scale, zp) through the same table/length
    coordinates.  rows arrive in fp; returns (pool, sz_pool).  Each
    row's (scale, zp) is taken over its own lanes only; a lane-aligned
    pool's padded lanes get code 0, finite once dequantized."""
    codes, sz = quantize_rows(rows, kv_dtype_of(pool))
    return (append_rows(pool, table, lengths, codes),
            append_rows(sz_pool, table, lengths, sz))


def append_chunk_quant(pool, sz_pool, table, lengths, rows):
    """Quantized :func:`append_chunk` (rows: [B, C, *F])."""
    codes, sz = quantize_rows(rows, kv_dtype_of(pool))
    return (append_chunk(pool, table, lengths, codes),
            append_chunk(sz_pool, table, lengths, sz))


def gather_blocks(pool, table):
    """Dense [B, max_blocks * bs, *F] view of the paged rows (the XLA
    fallback / oracle path — the Pallas kernels never materialize this;
    they index the pool through the table inside the grid)."""
    B, nb = table.shape
    bs = pool.shape[1]
    g = pool[table]                                   # [B, nb, bs, *F]
    return g.reshape(B, nb * bs, *pool.shape[2:])


def dense_to_paged(dense, lengths, layout: PagedLayout):
    """Pack a dense [B, S, *F] cache into (pool, BlockPool) — test/bench
    helper and the dense→paged migration path.  Allocation order follows
    slot order, so tables are NOT identity maps of logical order across
    sequences (which is exactly what the kernels must be robust to)."""
    B, S = dense.shape[:2]
    pool_host = np.zeros((layout.num_blocks, layout.block_size)
                         + dense.shape[2:], np.asarray(dense).dtype)
    bp = BlockPool(layout, B)
    dense_np = np.asarray(dense)
    for b in range(B):
        n = int(lengths[b])
        slot = bp.admit(n, n)
        assert slot == b, "fresh pool admits in slot order"
        ids = bp.block_ids(b)
        nb = len(ids)
        padded = np.zeros((nb * layout.block_size,) + dense.shape[2:],
                          dense_np.dtype)
        padded[:n] = dense_np[b, :n]
        pool_host[ids] = padded.reshape(nb, layout.block_size,
                                        *dense.shape[2:])
    return jnp.asarray(pool_host), bp


def tree_append_rows(cache, table, lengths, rows):
    """:func:`append_rows` over matching (pool, rows) pytrees whose leaves
    carry a leading stacked-layer axis [n, ...] (the model's grouped cache)."""
    return jax.tree.map(
        lambda p, r: jax.vmap(
            lambda pp, rr: append_rows(pp, table, lengths, rr))(p, r),
        cache, rows)
