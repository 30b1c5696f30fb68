"""Block-paged KV cache: one preallocated pool, a host-side allocator.

The generate() path gives every request a contiguous [L, B, KV, T, D]
cache sized for its worst case — at serving batch sizes that fragments
HBM (a 2-token health-check ping reserves as much cache as a 2k-token
completion). Here the cache is ONE pool of fixed-size pages, and
request r's logical column t lives at physical page
`tbl[r, t // block_T]`, offset `t % block_T` — the vLLM PagedAttention
scheme. Every page holds ALL layers' K/V for its span (one allocator
decision covers L writes).

THE POOL'S LAYOUT — stated here once; everything that touches a pool
goes through the functions of this module or names this contract:

    pool_k / pool_v : [num_blocks, L, block_T, KV * D]      (token-major)

A page of one layer is [block_T, KV * D]: one ROW per cached token,
holding that token's K (or V) of every KV head side by side, head j in
lanes [j * D, (j + 1) * D) — the row the qkv projection produces, as it
lies. It is the one layout on which the three parties agree, so no
compiled program converts the pool between layouts (PERF.md §6, PR 25:
with the head-major [NB, L, KV, bT, D] pool and D = 64 the decode step
copied both pools 52 times a token):

  (a) the runtime's buffer between steps: the minor dimension KV * D
      fills the TPU's 128 lanes for every served model (a D = 64 minor
      dimension leaves half of each lane tile empty, and the runtime
      then stores the buffer block-index-minor), so the donated buffer
      is plain row-major from parameter to result;
  (b) the token write: a fed token's K/V is one contiguous row per slot
      (`write_token_rows`), written in place; a prefill chunk's rows
      (`write_chunk_rows`) and a prompt's whole pages
      (`write_prompt_blocks`) likewise;
  (c) the paged kernel (ops/decode_attention.paged_decode_attention)
      copies P pages a grid step inside the live range, each
      [block_T, KV * D] as it lies, end to end into one VMEM chunk,
      and contracts the chunk against block-diagonal queries it lays
      out in VMEM: no per-head slicing of a row, no relayout. (A row
      that fills whole lane tiles is what lets the kernel cut a page
      out of the tiled HBM buffer itself; 25 heads of 64 do not, and
      go a page a step through the pipeline: pick_pages.)

Nothing here depends on the model's name or on a flag: KV and D come
from the K/V rows and queries the callers hold, block_T and KV * D from
the pool's own shape (`pool_layout` is what the engine stamps in its
run_start manifest). Under tensor parallelism the row shards on its
lanes (`pool_partition_spec`): KV/tp whole heads per shard, contiguous.

The allocator is deliberately host-side and trivial (a free list over
ints): allocation happens at most once per admitted request plus once
per block_T generated tokens, never inside the compiled step. Block 0
is reserved as the TRASH page: idle slots' writes and padded
block-table rows land there, so the device program needs no branches —
occupancy is expressed entirely through indices and masks.

Round 21 (shared-prefix KV reuse, DESIGN.md §26) makes pages
REFCOUNTED: requests whose prompts share a hashed full-block prefix
map the same physical pages, so a page is released only on its LAST
reference. A page whose refcount hits zero while its contents are
still registered in the engine's PrefixCache is PARKED instead of
freed: parked pages count as free (they are reclaimable at any
moment, LRU-first) but keep their contents until the allocator
actually needs them — that is what turns a finished request's prompt
pages into the next request's prefix hit. The leak observable is
unchanged: `in_use` counts only referenced pages, so "every request
terminal => in_use == 0" holds whether or not a cache is parked on
top.
"""

from __future__ import annotations

import collections
from typing import Callable, Dict, List, Optional

import jax.numpy as jnp

# physical page 0 is never allocated: idle slots write their garbage
# K/V there and padded block-table rows point at it (always masked)
TRASH_BLOCK = 0


class OutOfBlocks(RuntimeError):
    """The pool cannot hold another request (admission-time signal; the
    engine's reservation accounting makes mid-flight exhaustion a bug,
    not an operational state)."""


def blocks_for(tokens: int, block_T: int) -> int:
    """Pages needed to cache `tokens` columns."""
    return max(0, -(-int(tokens) // block_T))


def pool_shape(num_blocks: int, L: int, KV: int, block_T: int, D: int
               ) -> tuple:
    """The module's layout as a shape — the one place it is spelled."""
    return (num_blocks, L, block_T, KV * D)


def init_pools(num_blocks: int, L: int, KV: int, block_T: int, D: int,
               dtype=jnp.float32):
    """The two device pools in the module's layout, zero-filled (the
    trash page must hold finite values: idle slots attend their own
    zero column)."""
    shape = pool_shape(num_blocks, L, KV, block_T, D)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


_LANES = 128


def pool_layout(num_blocks: int, L: int, KV: int, block_T: int, D: int
                ) -> dict:
    """What the engine stamps into run_start next to attn_impl_resolved
    (scalars: the manifest keeps nothing else): the pool's axes and
    shape, and how heads fold into a page row. pool_lane_fill is the
    share of the TPU's lane tiles a row fills (1.0 unless KV * D is no
    multiple of 128: gpt2-xl's 25 x 64 = 1600 fills 12.5 of 13)."""
    shape = pool_shape(num_blocks, L, KV, block_T, D)
    row = shape[-1]
    return {"pool_layout": "block,layer,token,kv_head*head_dim",
            "pool_shape": "x".join(map(str, shape)),
            "pool_heads_per_row": KV, "pool_head_dim": D,
            "pool_lane_fill": round(row / (-(-row // _LANES) * _LANES), 4)}


def pool_partition_spec(kv_sharded: bool):
    """PartitionSpec for the pools (module docstring) under the serve
    ("dp", "tp") mesh (serve/sharding.py). The KV-head axis is the ONLY
    shardable one, and in a page row it is the lanes: page identity (NB)
    must stay whole so one host-side block table serves every shard, L
    is scanned over, and a page's tokens stay together for the kernel's
    DMA. kv_sharded gives each tp shard KV/tp whole heads of every row
    (a contiguous lane range); otherwise (GQA head counts indivisible
    by tp) the pools replicate and the query groups shard instead
    (ops/decode_attention.shard_heads)."""
    from jax.sharding import PartitionSpec as P
    return P(None, None, None, "tp") if kv_sharded else P()


def token_rows(x, dtype):
    """[..., KV, D] per-token K or V -> [..., KV * D] page rows."""
    return x.reshape(*x.shape[:-2], -1).astype(dtype)


def write_token_rows(pool, blk, layer, off, x):
    """The decode step's write: slot s's fed-token K or V x[s] [KV, D]
    lands in row off[s] of layer `layer` of page blk[s] — one
    contiguous row per slot, in place."""
    return pool.at[blk, layer, off, :].set(token_rows(x, pool.dtype))


def write_chunk_rows(pool, blk, off, x):
    """A prefill chunk's write, all layers at once: x [L, W, KV, D],
    row w of every layer lands at (blk[w], :, off[w])."""
    rows = token_rows(x, pool.dtype)                       # [L, W, KV*D]
    # every (layer, row) is its own index: the update window is one
    # contiguous page row, as in the decode step's write — a window
    # over the layer axis would ask for a pool with layers minor
    layers = jnp.arange(rows.shape[0], dtype=jnp.int32)[:, None]
    return pool.at[blk[None, :], layers, off[None, :], :].set(rows)


def gather_pages(pool, tbl, layer, KV: int):
    """Layer `layer` of the pages a block table names, heads split out
    for the XLA attention paths: tbl [..., M] -> [..., M, bT, KV, D]."""
    pages = pool[tbl, layer]                               # [..., M, bT, KV*D]
    return pages.reshape(*pages.shape[:-1], KV, -1)


def write_prompt_blocks(pool_k, pool_v, k, v, block_ids):
    """Write one prefilled request's K/V into its allocated pages.

    k/v: [L, KV, Ppad, D] from *_prefill (B squeezed), Ppad a block_T
    multiple; block_ids: [Ppad // block_T] physical pages, TRASH-padded
    past the prompt's real pages (their garbage columns are never
    attendable). Pure — the engine jits this with the pools donated.
    """
    bT = pool_k.shape[2]
    L, KV, Ppad, D = k.shape
    M = Ppad // bT
    # [L, KV, M, bT, D] -> [M, L, bT, KV * D]: one row per physical page
    pages = lambda t: t.reshape(L, KV, M, bT, D).transpose(
        2, 0, 3, 1, 4).reshape(M, L, bT, KV * D)
    pool_k = pool_k.at[block_ids].set(pages(k).astype(pool_k.dtype))
    pool_v = pool_v.at[block_ids].set(pages(v).astype(pool_v.dtype))
    return pool_k, pool_v


class BlockAllocator:
    """Refcounted free-list allocator over the pool's pages (block 0
    reserved).

    alloc/append/free are the request lifecycle: `alloc(n)` takes the
    prompt's pages at admission, `append()` one more page when decode
    crosses a page boundary, `free(ids)` drops one REFERENCE per page
    when the request finishes (or is cancelled). LIFO reuse keeps
    recently-hot pages recently-reused.

    Shared-prefix reuse (round 21) adds three verbs on top:

      retain(b)     +1 ref on an in-use page (a second request mapped
                    the same physical prefix page);
      adopt(b)      revive a PARKED page (ref 0, contents cached) back
                    to ref 1 — a prefix hit on a finished request's
                    pages;
      free(ids, park=fn)   at ref 0, `park(b)` decides the page's
                    fate: a cache key means "park it" (contents stay,
                    page counts as free, reclaimable LRU-first), None
                    means plain free. Reclaiming a parked page calls
                    `on_evict(b, key)` so the cache unregisters it.

    Pages are never referenced and parked at once: `in_use` counts
    exactly the referenced pages, so the terminal-accounting invariant
    (everything terminal => in_use == 0) is cache-agnostic.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 blocks (1 data + reserved trash block "
                f"{TRASH_BLOCK}), got {num_blocks}")
        self.num_blocks = int(num_blocks)
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._ref: Dict[int, int] = {}
        # parked pages: ref 0, contents registered in a PrefixCache —
        # insertion order is the LRU order (oldest first; a page parks
        # at the MRU end every time its last reference drops)
        self._parked: "collections.OrderedDict[int, object]" = \
            collections.OrderedDict()
        # called as on_evict(block, key) when alloc() reclaims a parked
        # page — the PrefixCache unregisters the mapping there
        self.on_evict: Optional[Callable[[int, object], None]] = None
        # lifetime count of pages handed out by alloc()/append() — the
        # bench's KV-cost denominator: prefix hits acquire() instead of
        # alloc(), so pages-per-request dropping below the cache-off
        # figure is the reuse actually paying
        self.pages_allocated = 0

    @property
    def free_blocks(self) -> int:
        """Allocatable pages: truly free + parked (parked pages are
        reclaimable at any moment, so admission math counts them)."""
        return len(self._free) + len(self._parked)

    @property
    def in_use(self) -> int:
        """Pages currently referenced (trash page excluded) — the
        leak-accounting observable: after every request has reached a
        terminal state this must be 0, whatever path (finish, cancel,
        timeout, contained step error) released the pages. Parked pages
        hold cache contents but NO references, so they count as free."""
        return len(self._ref)

    @property
    def parked_blocks(self) -> int:
        return len(self._parked)

    @property
    def refcounts(self) -> Dict[int, int]:
        """Snapshot {block: refcount} of every referenced page (the
        round-21 accounting observable: empty once everything is
        terminal — each shared page's count returned to zero)."""
        return dict(self._ref)

    def alloc(self, n: int) -> List[int]:
        if n > self.free_blocks:
            raise OutOfBlocks(
                f"asked for {n} pages, {self.free_blocks} free "
                f"(pool has {self.num_blocks - 1} allocatable)")
        out = []
        for _ in range(n):
            if self._free:
                b = self._free.pop()
            else:
                # reclaim the least-recently-parked cached page; the
                # cache forgets it before the new owner ever writes
                b, key = self._parked.popitem(last=False)
                if self.on_evict is not None:
                    self.on_evict(b, key)
            self._ref[b] = 1
            out.append(b)
        self.pages_allocated += n
        return out

    def append(self) -> int:
        return self.alloc(1)[0]

    def retain(self, b: int) -> None:
        """One more reference on an in-use page (prefix sharing)."""
        b = int(b)
        if b not in self._ref:
            raise ValueError(f"retain of un-referenced block {b}")
        self._ref[b] += 1

    def acquire(self, b: int) -> None:
        """Take one reference on a CACHED page whichever state it is in:
        retain() if some resident already references it, adopt() if it
        sits parked — the engine's one prefix-hit acquisition verb.
        Acquired pages are eviction-proof, so acquire every cached page
        BEFORE alloc()ing fresh ones."""
        b = int(b)
        if b in self._ref:
            self._ref[b] += 1
        else:
            self.adopt(b)

    def adopt(self, b: int) -> None:
        """Revive a parked page to ref 1 (a prefix hit on cached
        contents). The page must currently be parked."""
        b = int(b)
        if b not in self._parked:
            raise ValueError(f"adopt of un-parked block {b}")
        del self._parked[b]
        self._ref[b] = 1

    def free(self, ids, park: Optional[Callable[[int], object]] = None
             ) -> None:
        """Drop one reference per page; at zero the page parks (when
        `park(b)` returns its cache key) or returns to the free list."""
        for b in ids:
            b = int(b)
            if b == TRASH_BLOCK:
                raise ValueError("freeing the reserved trash block")
            if b not in self._ref or not 0 < b < self.num_blocks:
                raise ValueError(f"double/invalid free of block {b}")
            self._ref[b] -= 1
            if self._ref[b]:
                continue
            del self._ref[b]
            key = park(b) if park is not None else None
            if key is not None:
                self._parked[b] = key      # MRU end of the LRU order
            else:
                self._free.append(b)

    def flush_parked(self) -> int:
        """Forget every parked page (containment rebuilt the pools, so
        cached contents no longer exist). Returns how many were
        dropped; the PrefixCache flushes its own mappings alongside."""
        n = len(self._parked)
        while self._parked:
            b, _ = self._parked.popitem()
            self._free.append(b)
        return n
