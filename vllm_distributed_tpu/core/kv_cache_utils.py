"""KV-cache block hashing and page-geometry helpers.

Reference: vllm/v1/core/kv_cache_utils.py (block hashing incl. chained
parent hashes used by the prefix cache) — re-implemented with deterministic
sha256 hashes so prefix-cache behavior is reproducible across processes
(the reference uses Python hash() with PYTHONHASHSEED pinning; sha256 avoids
the pinning requirement entirely).
"""

import hashlib
import struct
from dataclasses import dataclass
from typing import NamedTuple, Optional

import numpy as np

from vllm_distributed_tpu.request import Request
from vllm_distributed_tpu.utils import cdiv


class BlockHash(NamedTuple):
    """Hash of one full KV page: chained over the parent page so equal
    hashes imply equal full prefixes."""

    hash_value: bytes
    # Kept for collision resistance checks / debugging.
    token_ids: tuple[int, ...]


NONE_HASH = b"\x00" * 16


def hash_block_tokens(
    parent_hash: Optional[bytes],
    token_ids: tuple[int, ...],
    extra_keys: Optional[tuple] = None,
) -> BlockHash:
    """Chained hash of a full block of tokens.

    ``extra_keys`` carries things that change KV content beyond token ids
    (LoRA id, multimodal content hashes) — reference:
    v1/core/kv_cache_utils.py generate_block_hash_extra_keys.
    """
    h = hashlib.sha256()
    h.update(parent_hash or NONE_HASH)
    h.update(struct.pack(f"<{len(token_ids)}q", *token_ids))
    if extra_keys:
        h.update(repr(extra_keys).encode())
    return BlockHash(h.digest()[:16], token_ids)


def request_hash_seed(request: Request) -> Optional[bytes]:
    """Chain seed for a request's block hashes: multimodal requests
    salt with the image content hash — same token ids + different
    images must never collide (reference: the mm hash keys folded into
    block hashing, v1/core/kv_cache_utils). EVERY place that (re)starts
    a hash chain must seed from here, or an unsalted chain could hand
    one user's image-conditioned KV to another."""
    return getattr(request, "mm_hash", None)


def hash_request_tokens(block_size: int,
                        request: Request) -> list[BlockHash]:
    """Hash all *full* blocks of the request's current tokens."""
    token_ids = request.all_token_ids
    hashes: list[BlockHash] = []
    parent: Optional[bytes] = request_hash_seed(request)
    for start in range(0, len(token_ids) - block_size + 1, block_size):
        chunk = tuple(token_ids[start:start + block_size])
        bh = hash_block_tokens(parent, chunk)
        hashes.append(bh)
        parent = bh.hash_value
    return hashes


def num_window_dead_blocks(num_computed_tokens: int, window: int,
                           block_size: int) -> int:
    """Leading pages of a request that no query can see any more under a
    sliding window: the next query sits at ``num_computed_tokens`` and
    sees positions ``> num_computed_tokens - window``, so a page is dead
    once its last position lies before that. ONE rule for the manager
    that frees those pages (core/kv_cache_manager.py) and the runner
    that nulls their slots in the table the kernel gets
    (worker/model_runner.py), which asks for all its rows at once (an
    array of computed tokens gives an array)."""
    return np.maximum(0, num_computed_tokens - window + 1) // block_size


def window_step_work(q_len, end, window: int):
    """What one step's rows cost a window layer and a full layer, row by
    row: ``q_len`` queries a row whose context ends at ``end`` (computed
    plus scheduled). A query at position ``p`` sees ``p + 1`` keys in a
    full layer and ``min(p + 1, window)`` in a window layer (the window
    rule of ``num_window_dead_blocks``), so a window layer reads the
    ``min(end, window - 1 + q_len)`` tokens that some query of the row
    sees. Returns (window tokens read, full pairs, window pairs), int64
    arrays of the rows' shape. The one closed form of a step's attention
    pairs: the runner's histograms observe its sums
    (worker/model_runner.py ``_fill_window_group``)."""
    q_len = np.asarray(q_len, np.int64)
    end = np.asarray(end, np.int64)
    tokens_read = np.minimum(end, window - 1 + q_len)
    full = q_len * (end - q_len) + q_len * (q_len + 1) // 2
    over = np.clip(end - window, 0, q_len)   # queries with p + 1 > window
    under = q_len - over
    windowed = (over * window + under * (end - q_len)
                + under * (under + 1) // 2)
    return tokens_read, full, windowed


def window_group_pool_blocks(window: int, block_size: int,
                             max_num_seqs: int,
                             max_num_batched_tokens: int,
                             max_pages_per_req: int) -> int:
    """Pages of the window group's pool: all it can ever hold. A request
    at ``c`` computed tokens granted ``n`` more holds slots
    ``[dead(c), cdiv(c + n, block))``, at most ``(window + n) / block +
    2`` pages; the grants of a step sum to the token budget. Two more
    pages a request cover a multi-step burst's lookahead and a grant
    made ahead of the step that retires (async scheduling). Sized so,
    the window group never refuses an allocation that the full group
    grants; it follows from the configuration alone, so the scheduler
    and the worker compute the same count without talking."""
    per_req = min(cdiv(window, block_size) + 4, max_pages_per_req)
    return (max_num_seqs * per_req
            + cdiv(max_num_batched_tokens, block_size))


def config_window_group_pool_blocks(config, window: int) -> int:
    """``window_group_pool_blocks`` of an engine configuration: what the
    scheduler (its manager) and the loader (the model's cache arrays)
    both size the window group's pool by."""
    sc = config.scheduler_config
    return window_group_pool_blocks(
        window, config.cache_config.block_size, sc.max_num_seqs,
        sc.max_num_batched_tokens, config.max_pages_per_req)


@dataclass
class KVCacheSpec:
    """Geometry of one KV cache group (reference:
    v1/kv_cache_interface.py:20-208 FullAttentionSpec et al.).

    One spec a group. A model whose layers all keep every token (or all
    share one window) has one group; a model that mixes window and full
    layers has two, each with a pool and a page table of its own
    (``GroupedKVCacheManager``); state rows (SSM, KDA, carried
    convolution inputs) are a third kind that is not paged at all
    (core/state_cache.py).
    """

    block_size: int
    num_kv_heads: int
    head_size: int
    dtype: str
    num_layers: int

    @property
    def page_size_bytes(self) -> int:
        itemsize = {"bfloat16": 2, "float16": 2, "float32": 4}[self.dtype]
        # K and V planes.
        return (2 * self.block_size * self.num_kv_heads * self.head_size *
                itemsize * self.num_layers)
