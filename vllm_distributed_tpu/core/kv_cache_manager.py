"""Per-request KV page allocation on top of the BlockPool.

Reference: vllm/v1/core/kv_cache_manager.py (``KVCacheManager``:
get_computed_blocks:137 for prefix-cache hits, allocate_slots:195 — incl.
the fork's ``tknp_skip_allocation`` used when a token-parallel peer owns the
request's KV, which we express via ``skip_allocation``).
"""

from collections import defaultdict
from dataclasses import dataclass
from typing import Optional

from vllm_distributed_tpu.core.block_pool import BlockPool, KVCacheBlock
from vllm_distributed_tpu.core.kv_cache_utils import (
    BlockHash, hash_block_tokens, hash_request_tokens,
    num_window_dead_blocks)
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.request import Request
from vllm_distributed_tpu.utils import cdiv

logger = init_logger(__name__)


@dataclass
class KVCacheBlocks:
    """Opaque result of an allocation: the page ids newly visible to the
    worker for this request."""

    blocks: list[KVCacheBlock]
    # The window group's new pages, slot for slot beside ``blocks``
    # (GroupedKVCacheManager); None for a model with one group.
    window_blocks: Optional[list[KVCacheBlock]] = None

    def get_block_ids(self) -> list[int]:
        return [b.block_id for b in self.blocks]

    def get_window_block_ids(self) -> Optional[list[int]]:
        if self.window_blocks is None:
            return None
        return [b.block_id for b in self.window_blocks]

    def __add__(self, other: "KVCacheBlocks") -> "KVCacheBlocks":
        return KVCacheBlocks(self.blocks + other.blocks)


class KVCacheManager:

    def __init__(
        self,
        block_size: int,
        num_blocks: int,
        enable_caching: bool = True,
        id_offset: int = 0,
        free_window: Optional[int] = None,
        tier=None,
    ) -> None:
        self.block_size = block_size
        self.enable_caching = enable_caching
        self.block_pool = BlockPool(num_blocks, enable_caching, id_offset)
        # Hierarchical KV tiering (core/kv_tier.py): evicted prefix
        # pages demote to host RAM / disk instead of vanishing, and
        # get_computed_blocks extends a device-cached prefix with
        # tier-resident continuation pages. None = untiered
        # (byte-identical pre-tiering behavior).
        self.tier = tier
        if tier is not None:
            self.block_pool.on_evict = tier.note_evicted
        # Sliding-window page freeing (reference: the SlidingWindowManager
        # of v1/core/single_type_kv_cache_manager.py:444 replacing
        # out-of-window blocks with the null block): when EVERY layer
        # this manager's pages serve is windowed, pages whose last
        # position can never again fall inside any future query's window
        # are freed mid-request and their req_to_blocks slot nulled. The
        # attention mask already excludes those positions, so a stale
        # (possibly reused) page id in the block table is never read
        # into a live score. None = some layer needs full history; no
        # mid-request freeing. A model that mixes window and full layers
        # gets one manager of each kind (GroupedKVCacheManager).
        self.free_window = free_window
        # Pages handed back behind the window, over the manager's life
        # (vdt:kv_window_pages_freed_total).
        self.window_pages_freed = 0
        # req_id -> count of leading slots already window-freed (loop
        # resume point; dropped with the request's block list).
        self._num_window_freed: dict[str, int] = {}

        # req_id -> pages owned (ordered by position in sequence).
        self.req_to_blocks: dict[str, list[KVCacheBlock]] = defaultdict(list)
        # req_id -> chained hashes of its full pages (grows lazily).
        self.req_to_block_hashes: dict[str, list[BlockHash]] = \
            defaultdict(list)
        # req_id -> number of pages already registered in the prefix cache.
        self.num_cached_block: dict[str, int] = {}

        # Stats (reference: PrefixCacheStats). Lifetime counters plus a
        # sliding window of recent lookup outcomes: the lifetime ratio
        # of a week-old server can't show that the cache stopped
        # hitting an hour ago, the window can.
        self.prefix_cache_queries = 0
        self.prefix_cache_hits = 0
        from collections import deque
        self._recent_queries: "deque[int]" = deque(maxlen=256)

    @property
    def usage(self) -> float:
        return self.block_pool.usage

    def get_num_free_blocks(self) -> int:
        return self.block_pool.get_num_free_blocks()

    # ------------------------------------------------------------------
    def get_computed_blocks(
            self, request: Request) -> tuple[KVCacheBlocks, int]:
        """Longest cached-prefix lookup for a WAITING request.

        Returns (cached blocks, num_computed_tokens). Never returns the
        *entire* prompt as cached — the last token must be recomputed so a
        logit is produced for it (reference: kv_cache_manager.py:137).
        """
        if not self.enable_caching:
            return KVCacheBlocks([]), 0

        block_hashes = self.req_to_block_hashes[request.request_id]
        if not block_hashes:
            block_hashes = hash_request_tokens(self.block_size, request)
            self.req_to_block_hashes[request.request_id] = block_hashes

        self.prefix_cache_queries += 1
        computed: list[KVCacheBlock] = []
        # Cap so at least one prompt token remains to be computed.
        max_cache_hit_tokens = request.num_tokens - 1
        for i, bh in enumerate(block_hashes):
            if (i + 1) * self.block_size > max_cache_hit_tokens:
                break
            block = self.block_pool.get_cached_block(bh)
            if block is None:
                break
            computed.append(block)
        # Tier continuation (core/kv_tier.py): extend the device-
        # resident prefix with pages whose content lives in host RAM /
        # disk. The hit arrays are staged on the tier manager under the
        # request id; the scheduler allocates device pages for the span
        # and ships a promote directive the runner executes before the
        # forward. The span counts as computed tokens — it is, the
        # bytes just live one tier down.
        num_tier = 0
        if self.tier is not None:
            num_tier = self.tier.match_prefix(
                request.request_id, block_hashes, len(computed),
                max_cache_hit_tokens, self.block_size)
        if computed or num_tier:
            self.prefix_cache_hits += 1
        self._recent_queries.append(1 if (computed or num_tier) else 0)
        return (KVCacheBlocks(computed),
                (len(computed) + num_tier) * self.block_size)

    def allocate_slots(
        self,
        request: Request,
        num_new_tokens: int,
        new_computed_blocks: Optional[KVCacheBlocks] = None,
        num_lookahead_tokens: int = 0,
        skip_allocation: bool = False,
        delay_caching: bool = False,
    ) -> Optional[KVCacheBlocks]:
        """Ensure the request has pages for ``num_new_tokens`` more tokens.

        Returns the newly-allocated pages, or None if the pool cannot
        satisfy the allocation (caller preempts). ``skip_allocation``
        mirrors the fork's tknp_skip_allocation (scheduler.py:494-500):
        the tokens are scheduled but a token-parallel peer holds the KV.
        """
        assert num_new_tokens > 0
        if skip_allocation:
            return KVCacheBlocks([])

        # Free the dead window prefix FIRST so the released pages can
        # satisfy this very allocation.
        self._free_out_of_window(request)

        computed_blocks = (new_computed_blocks.blocks
                           if new_computed_blocks else [])
        req_blocks = self.req_to_blocks[request.request_id]

        num_computed_tokens = (request.num_computed_tokens +
                               len(computed_blocks) * self.block_size)
        total_tokens = (num_computed_tokens + num_new_tokens +
                        num_lookahead_tokens)
        num_required_blocks = cdiv(total_tokens, self.block_size)
        num_new_blocks = (num_required_blocks - len(req_blocks) -
                          len(computed_blocks))

        # Cache-hit blocks with ref 0 still sit in the free queue; taking a
        # ref on them consumes free capacity, so discount them (reference:
        # kv_cache_manager.py:195 num_evictable_computed_blocks).
        num_evictable_computed = sum(1 for b in computed_blocks
                                     if b.ref_cnt == 0)
        if (num_new_blocks >
                self.block_pool.get_num_free_blocks() -
                num_evictable_computed):
            return None  # cannot allocate; caller decides to preempt

        # Commit: take refs on the cache-hit blocks, then allocate new ones.
        if computed_blocks:
            self.block_pool.touch(computed_blocks)
            req_blocks.extend(computed_blocks)

        new_blocks: list[KVCacheBlock] = []
        if num_new_blocks > 0:
            new_blocks = self.block_pool.get_new_blocks(num_new_blocks)
            req_blocks.extend(new_blocks)

        # delay_caching: pages allocated for an ASYNC external load must
        # not enter the prefix-cache index yet — the data isn't on device,
        # and a failed pull would otherwise poison every future lookup of
        # these hashes (reference: kv_cache_manager.py delay_cache_blocks
        # for the nixl path). They register later, when the request's
        # post-load allocations cover them.
        if self.enable_caching and not delay_caching:
            self._cache_full_blocks(request, num_computed_tokens,
                                    num_new_tokens)

        return KVCacheBlocks(new_blocks)

    def _cache_full_blocks(self, request: Request,
                           num_computed_tokens: int,
                           num_new_tokens: int) -> None:
        """Register hashes for pages that become full once the scheduled
        tokens are computed. Hashes only cover tokens that *exist* now
        (prompt + already-sampled); a decode step filling a page registers
        it on the following step via the growing hash list."""
        req_blocks = self.req_to_blocks[request.request_id]
        block_hashes = self.req_to_block_hashes[request.request_id]
        # Extend hashes to cover any newly-complete full pages.
        num_full_after = min(num_computed_tokens + num_new_tokens,
                             request.num_tokens) // self.block_size
        from vllm_distributed_tpu.core.kv_cache_utils import \
            request_hash_seed
        parent = (block_hashes[-1].hash_value if block_hashes
                  else request_hash_seed(request))
        while len(block_hashes) < num_full_after:
            start = len(block_hashes) * self.block_size
            chunk = tuple(request.all_token_ids[start:start +
                                                self.block_size])
            bh = hash_block_tokens(parent, chunk)
            block_hashes.append(bh)
            parent = bh.hash_value
        num_cached = self.num_cached_block.get(request.request_id, 0)
        if num_full_after > num_cached:
            self.block_pool.cache_full_blocks(req_blocks, block_hashes,
                                              num_cached, num_full_after)
            self.num_cached_block[request.request_id] = num_full_after

    def _free_out_of_window(self, request: Request) -> None:
        """Null + free every block whose last position precedes
        num_computed_tokens - window + 1 (``num_window_dead_blocks``: no
        future query can attend it). The mask in ops/attention keeps
        such a position out of every score, the mega-kernel's trimmed
        page loop (ops/pallas_attention.py) does not walk that far
        back, and the runner nulls the same slots in the table it ships
        by the same rule, so the kernel never gets a freed page's id."""
        if self.free_window is None:
            return
        num_dead = int(num_window_dead_blocks(
            request.num_computed_tokens, self.free_window, self.block_size))
        if num_dead <= 0:
            return
        blocks = self.req_to_blocks.get(request.request_id)
        if not blocks:
            return
        # Start at the first live slot (persisted) so steady-state decode
        # frees at most one new block in O(1), not O(dead prefix).
        start = self._num_window_freed.get(request.request_id, 0)
        end = min(num_dead, len(blocks))
        dead = []
        for i in range(start, end):
            if blocks[i] is not None:
                dead.append(blocks[i])
                blocks[i] = None
        if end > start:
            self._num_window_freed[request.request_id] = end
        if dead:
            self.window_pages_freed += len(dead)
            self.block_pool.free_blocks(dead)

    def _release_tail(self, request_id: str,
                      blocks: list[KVCacheBlock]) -> None:
        """Take back the pages an ``allocate_slots`` just appended (the
        other group of a GroupedKVCacheManager could not follow)."""
        if blocks:
            del self.req_to_blocks[request_id][-len(blocks):]
            self.block_pool.free_blocks(list(reversed(blocks)))

    def num_held_blocks(self) -> int:
        """Pages that requests hold now (freed slots excepted). Runs on
        a scrape's thread too: containers are snapshotted first."""
        return sum(1 for blocks in list(self.req_to_blocks.values())
                   for b in list(blocks) if b is not None)

    # ------------------------------------------------------------------
    def free(self, request: Request) -> None:
        """Release all pages of a finished/preempted request. Pages are
        returned tail-first so prefixes are evicted last."""
        blocks = self.req_to_blocks.pop(request.request_id, [])
        self.num_cached_block.pop(request.request_id, None)
        self._num_window_freed.pop(request.request_id, None)
        self.block_pool.free_blocks(
            [b for b in reversed(blocks) if b is not None])

    def free_block_hashes(self, request: Request) -> None:
        """Forget the request's hash list (on finish — distinct from free()
        because preempted requests keep hashes for re-prefill)."""
        self.req_to_block_hashes.pop(request.request_id, None)
        if self.tier is not None:
            self.tier.drop_request(request.request_id)

    def transfer_ownership(self, old_id: str, new_id: str) -> None:
        """Re-key a request's page ownership (scheduler watchdog: pages
        of a timed-out-but-still-in-flight pull are parked under a
        tombstone id until the worker reports, so the request can be
        re-queued with fresh pages under its own id). Block hashes stay
        with the original id — they describe the request's content, not
        the parked pages."""
        if old_id in self.req_to_blocks:
            self.req_to_blocks[new_id] = self.req_to_blocks.pop(old_id)
        if old_id in self.num_cached_block:
            self.num_cached_block[new_id] = \
                self.num_cached_block.pop(old_id)
        if old_id in self._num_window_freed:
            self._num_window_freed[new_id] = \
                self._num_window_freed.pop(old_id)

    def get_block_ids(self, request_id: str) -> list[int]:
        # Window-freed slots keep a position-aligned placeholder id; the
        # attention window mask guarantees those positions are never
        # read (see _free_out_of_window).
        return [0 if b is None else b.block_id
                for b in self.req_to_blocks[request_id]]

    def reset_prefix_cache(self) -> bool:
        return self.block_pool.reset_prefix_cache()

    def make_prefix_cache_stats(self) -> dict[str, float]:
        return {
            "queries": self.prefix_cache_queries,
            "hits": self.prefix_cache_hits,
        }

    def kv_telemetry(self) -> dict:
        """Block-pool introspection for the telemetry plane: pool
        occupancy, the request-held block/token footprint the scheduler
        turns into a fragmentation figure, and the windowed hit rate.
        Runs on the stats-RPC caller's thread while the core thread
        allocates/frees — every container is list()-snapshotted
        (GIL-atomic) before Python-level iteration."""
        stats = dict(self.block_pool.get_stats())
        stats["held_blocks"] = self.num_held_blocks()
        recent = list(self._recent_queries)
        stats["window_queries"] = len(recent)
        stats["window_hits"] = sum(recent)
        return stats


def _summed_telemetry(managers) -> dict:
    """The managers' ``kv_telemetry`` counts, key by key."""
    merged: dict = {}
    for m in managers:
        for k, v in m.kv_telemetry().items():
            merged[k] = merged.get(k, 0) + v
    return merged


class GroupedKVCacheManager:
    """Two kinds of K/V in one manager, for a model whose layers mix a
    sliding window with full attention (reference: the hybrid manager of
    v1/core/kv_cache_coordinator.py over single_type_kv_cache_manager.py
    and the SlidingWindowSpec of v1/kv_cache_interface.py: a page table
    a group, out-of-window blocks handed back).

    The FULL group's pages serve the layers that keep every token: a
    request holds ``cdiv(context, block)`` of them. The WINDOW group's
    pages serve the window layers: a request holds the slots from the
    first page a query can still see to the last scheduled token, at
    most ``(window + scheduled) / block + 2``, and the rest is handed
    back as the request moves on (``KVCacheManager._free_out_of_window``).
    Both tables are position-aligned (slot ``i`` holds positions
    ``[i x block, (i + 1) x block)``), so the groups allocate in step
    and the worker's slot arithmetic is the same for both; a freed slot
    reads the placeholder id 0 under the mask.

    Two pools, not one: the window group's is sized to all it can ever
    hold (``window_group_pool_blocks``, a function of the configuration)
    and the full group's gets the rest of the chip (worker/worker.py), so
    a page is as large as its own group's layers make it and neither
    group's layout depends on the other's depth. Admission and
    preemption follow what BOTH have free: an allocation is granted
    whole or not at all. Prefix sharing is refused for such a model (a
    hit would need the full group's pages and the window group's last
    window beside them; the scheduler turns caching off and says so).
    """

    def __init__(self, block_size: int, num_blocks: int,
                 num_window_blocks: int, window: int) -> None:
        self.block_size = block_size
        self.enable_caching = False
        self.full_group = KVCacheManager(block_size, num_blocks,
                                         enable_caching=False)
        self.window_group = KVCacheManager(block_size, num_window_blocks,
                                           enable_caching=False,
                                           free_window=window)
        # What the scheduler's per-pool walks read (_block_pools).
        self.managers = [self.full_group, self.window_group]

    @property
    def req_to_blocks(self) -> dict:
        return self.full_group.req_to_blocks

    @property
    def usage(self) -> float:
        """Pages in use over pages there are, both groups counted."""
        total = sum(m.block_pool.num_blocks for m in self.managers)
        return 1.0 - self.get_num_free_blocks() / total

    def get_num_free_blocks(self) -> int:
        return sum(m.get_num_free_blocks() for m in self.managers)

    def get_computed_blocks(
            self, request: Request) -> tuple[KVCacheBlocks, int]:
        return KVCacheBlocks([]), 0

    def allocate_slots(self, request: Request, num_new_tokens: int,
                       new_computed_blocks: Optional[KVCacheBlocks] = None,
                       num_lookahead_tokens: int = 0,
                       skip_allocation: bool = False,
                       delay_caching: bool = False,
                       ) -> Optional[KVCacheBlocks]:
        """Pages for ``num_new_tokens`` more tokens in both groups, or
        None and nothing taken (the caller preempts)."""
        assert not skip_allocation and not (
            new_computed_blocks and new_computed_blocks.blocks), \
            "no prefix hit and no token-parallel peer under cache groups"
        full = self.full_group.allocate_slots(
            request, num_new_tokens,
            num_lookahead_tokens=num_lookahead_tokens)
        if full is None:
            return None
        win = self.window_group.allocate_slots(
            request, num_new_tokens,
            num_lookahead_tokens=num_lookahead_tokens)
        if win is None:
            self.full_group._release_tail(request.request_id, full.blocks)
            return None
        assert len(win.blocks) == len(full.blocks), \
            "the groups' tables are position-aligned"
        return KVCacheBlocks(full.blocks, window_blocks=win.blocks)

    def free(self, request: Request) -> None:
        for m in self.managers:
            m.free(request)

    def free_block_hashes(self, request: Request) -> None:
        for m in self.managers:
            m.free_block_hashes(request)

    def transfer_ownership(self, old_id: str, new_id: str) -> None:
        for m in self.managers:
            m.transfer_ownership(old_id, new_id)

    def get_block_ids(self, request_id: str) -> list[int]:
        return self.full_group.get_block_ids(request_id)

    def get_window_block_ids(self, request_id: str) -> list[int]:
        return self.window_group.get_block_ids(request_id)

    def reset_prefix_cache(self) -> bool:
        return all([m.reset_prefix_cache() for m in self.managers])

    def make_prefix_cache_stats(self) -> dict[str, float]:
        return {"queries": 0, "hits": 0}

    def group_stats(self) -> dict[str, int]:
        """What each group holds and what the window group handed back
        (vdt:kv_group_pages_held, vdt:kv_window_pages_freed_total)."""
        return {
            "kv_full_pages_held": self.full_group.num_held_blocks(),
            "kv_window_pages_held": self.window_group.num_held_blocks(),
            "kv_window_pages_freed": self.window_group.window_pages_freed,
        }

    def kv_telemetry(self) -> dict:
        """Both pools summed; ``held_blocks`` counts a page of either
        group as one."""
        return _summed_telemetry(self.managers)


class TokenParallelKVCacheManager:
    """Partitioned KV management for token parallelism: the global page
    array is split into ``num_ranks`` contiguous per-rank pools, and every
    request's pages come exclusively from its assigned rank's pool — so a
    request's KV physically lives on one ``token``-axis shard of the
    sharded cache.

    TPU-native analogue of the fork's TokenParallelScheduler KV
    bookkeeping (vllm/v1/core/sched/scheduler.py:55-255 assign_ranks +
    per-rank free-block accounting, kv_cache_manager.py
    tknp_skip_allocation): instead of peer processes owning separate
    caches, one SPMD cache is sharded on the page axis and ownership is a
    page-range invariant maintained here. Page ids are GLOBAL (rank r owns
    [r*N/K, (r+1)*N/K)), so the worker's block tables and slot mappings
    need no translation — the runner derives each request's rank from its
    first page id.

    Requests must be assigned a rank (``request.tknp_rank``) before any
    call; the scheduler assigns ranks free-page-aware at admission.
    Prefix-cache lookups are per-rank: a prefix cached on rank 0 cannot
    serve a rank-1 request (its KV lives in rank 0's HBM shard), matching
    the reference's per-rank cache separation.
    """

    def __init__(
        self,
        block_size: int,
        num_blocks: int,
        num_ranks: int,
        enable_caching: bool = True,
    ) -> None:
        assert num_ranks > 1
        assert num_blocks % num_ranks == 0, \
            "page count must divide evenly across token-parallel ranks"
        self.block_size = block_size
        self.num_ranks = num_ranks
        self.blocks_per_rank = num_blocks // num_ranks
        self.managers = [
            KVCacheManager(block_size, self.blocks_per_rank,
                           enable_caching,
                           id_offset=r * self.blocks_per_rank)
            for r in range(num_ranks)
        ]
        # req_id -> rank, recorded at first allocation-path call.
        self.req_rank: dict[str, int] = {}

    def _mgr(self, request: Request) -> KVCacheManager:
        rank = getattr(request, "tknp_rank", None)
        assert rank is not None, \
            f"request {request.request_id} has no token-parallel rank"
        self.req_rank[request.request_id] = rank
        return self.managers[rank]

    def _maybe_mgr(self, request: Request) -> Optional[KVCacheManager]:
        """Manager for the request's rank, or None when no rank was ever
        assigned (a request aborted/rejected while still WAITING holds no
        pages and no hashes, so teardown is a no-op)."""
        if getattr(request, "tknp_rank", None) is None:
            return None
        return self._mgr(request)

    @property
    def usage(self) -> float:
        return sum(m.usage for m in self.managers) / self.num_ranks

    def get_num_free_blocks(self) -> int:
        return sum(m.get_num_free_blocks() for m in self.managers)

    def free_blocks_on_rank(self, rank: int) -> int:
        return self.managers[rank].get_num_free_blocks()

    def get_computed_blocks(self, request: Request):
        return self._mgr(request).get_computed_blocks(request)

    def allocate_slots(self, request: Request, num_new_tokens: int,
                       new_computed_blocks=None,
                       num_lookahead_tokens: int = 0,
                       skip_allocation: bool = False,
                       delay_caching: bool = False):
        return self._mgr(request).allocate_slots(
            request, num_new_tokens, new_computed_blocks,
            num_lookahead_tokens, skip_allocation, delay_caching)

    def free(self, request: Request) -> None:
        mgr = self._maybe_mgr(request)
        if mgr is not None:
            mgr.free(request)

    def free_block_hashes(self, request: Request) -> None:
        """Terminal teardown: also drops the rank record (it is only
        needed while block tables can still be queried)."""
        mgr = self._maybe_mgr(request)
        if mgr is not None:
            mgr.free_block_hashes(request)
        self.req_rank.pop(request.request_id, None)

    def release_rank(self, request: Request) -> None:
        """Un-assign a request that holds no pages so the next admission
        attempt re-picks the least-loaded rank (prevents a stalled queue
        head from pinning itself to a full rank)."""
        mgr = self._maybe_mgr(request)
        if mgr is not None:
            assert not mgr.req_to_blocks.get(request.request_id), \
                "cannot release the rank of a request holding pages"
            # A failed allocate_slots touches the defaultdict; drop the
            # empty entry or the old rank's manager leaks it forever.
            mgr.req_to_blocks.pop(request.request_id, None)
            mgr.free_block_hashes(request)
        self.req_rank.pop(request.request_id, None)
        request.tknp_rank = None

    def get_block_ids(self, request_id: str) -> list[int]:
        return self.managers[self.req_rank[request_id]].get_block_ids(
            request_id)

    def transfer_ownership(self, old_id: str, new_id: str) -> None:
        """Re-key page ownership within the owning rank's pool (see
        KVCacheManager.transfer_ownership)."""
        rank = self.req_rank.pop(old_id, None)
        if rank is None:
            return
        self.managers[rank].transfer_ownership(old_id, new_id)
        self.req_rank[new_id] = rank

    def reset_prefix_cache(self) -> bool:
        return all([m.reset_prefix_cache() for m in self.managers])

    def make_prefix_cache_stats(self) -> dict[str, float]:
        return {
            "queries": sum(m.prefix_cache_queries for m in self.managers),
            "hits": sum(m.prefix_cache_hits for m in self.managers),
        }

    def kv_telemetry(self) -> dict:
        """Per-rank pools summed — one fleet view of the partitioned
        page array (per-rank free counts already ride get_stats as
        tknp_free_blocks_rank*)."""
        return _summed_telemetry(self.managers)
