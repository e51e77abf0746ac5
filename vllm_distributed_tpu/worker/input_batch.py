"""Persistent host-side batch state for in-flight requests.

Reference: vllm/v1/worker/gpu_input_batch.py (persistent token/block-table/
sampling arrays updated incrementally from SchedulerOutput) and
tpu_input_batch.py. Rows are slotted (free-list), not compacted: padding
discipline lives in the per-step flat arrays the runner builds, so row
stability is worth more than density.
"""

from typing import Optional

import numpy as np

from vllm_distributed_tpu.core.sched.output import (CachedRequestData,
                                                    NewRequestData,
                                                    SchedulerOutput)
from vllm_distributed_tpu.sampling_params import SamplingParams


class InputBatch:

    def __init__(self, max_num_reqs: int, max_model_len: int,
                 max_pages_per_req: int, page_size: int) -> None:
        self.max_num_reqs = max_num_reqs
        self.max_model_len = max_model_len
        self.max_pages_per_req = max_pages_per_req
        self.page_size = page_size

        R, L, P = max_num_reqs, max_model_len, max_pages_per_req
        self.token_ids = np.zeros((R, L), np.int32)
        self.num_tokens = np.zeros((R, ), np.int32)
        self.num_computed = np.zeros((R, ), np.int32)
        self.block_table = np.zeros((R, P), np.int32)
        self.num_blocks = np.zeros((R, ), np.int32)
        # The window group's table, slot for slot beside block_table
        # (made by the first request that brings window page ids: a
        # model with one cache group never has it).
        self.window_block_table: Optional[np.ndarray] = None

        self.temperature = np.zeros((R, ), np.float32)
        self.top_k = np.zeros((R, ), np.int32)
        self.top_p = np.ones((R, ), np.float32)
        self.min_p = np.zeros((R, ), np.float32)
        self.seed = np.full((R, ), -1, np.int64)

        # Extended sampling (penalties / bias / logprobs / min-tokens).
        self.presence_penalty = np.zeros((R, ), np.float32)
        self.frequency_penalty = np.zeros((R, ), np.float32)
        self.repetition_penalty = np.ones((R, ), np.float32)
        self.min_tokens = np.zeros((R, ), np.int32)
        self.num_logprobs = np.zeros((R, ), np.int32)  # 0 = sampled only
        # prompt_logprobs top-k per row; -1 = not requested (reference:
        # SamplingParams.prompt_logprobs).
        self.prompt_logprobs = np.full((R, ), -1, np.int32)
        self.prompt_len = np.zeros((R, ), np.int32)
        # Lifetime (static) extended-graph need; min-tokens activity is
        # checked dynamically via extended_active().
        self.needs_extended = np.zeros((R, ), np.bool_)
        # Multi-LoRA adapter slot per row (0 = no adapter).
        self.lora_slot = np.zeros((R, ), np.int32)
        # Pooling type per row (None = generation request).
        self.pooling: list = [None] * R
        # Multimodal inputs per row (list[MultiModalInput] | None).
        self.mm: list = [None] * R
        # Sparse per-row python state (lowered to fixed [R, B] arrays in
        # the runner only when a batch contains extended rows).
        self.logit_bias: list[Optional[dict[int, float]]] = [None] * R
        self.allowed_token_ids: list[Optional[list[int]]] = [None] * R
        self.stop_token_ids: list[tuple[int, ...]] = [()] * R

        # Bumped whenever a row's token content is REWRITTEN (not
        # appended): admission, preemption resume. The runner's
        # device-resident history mirror re-uploads such rows in full
        # and follows appends with small deltas (model_runner.
        # _hist_rows_device).
        self.row_version = np.zeros((R, ), np.int64)

        self.req_id_to_index: dict[str, int] = {}
        self.index_to_req_id: dict[int, str] = {}
        self._free_rows = list(range(R - 1, -1, -1))

    @property
    def num_reqs(self) -> int:
        return len(self.req_id_to_index)

    # ------------------------------------------------------------------
    def add_request(self, data: NewRequestData) -> int:
        assert data.req_id not in self.req_id_to_index
        assert self._free_rows, "input batch overflow"
        row = self._free_rows.pop()
        self.req_id_to_index[data.req_id] = row
        self.index_to_req_id[row] = data.req_id

        tokens = data.prompt_token_ids
        n = len(tokens)
        self.token_ids[row, :n] = tokens
        self.token_ids[row, n:] = 0
        self.num_tokens[row] = n
        self.row_version[row] += 1
        self.num_computed[row] = data.num_computed_tokens
        nb = len(data.block_ids)
        self.block_table[row, :nb] = data.block_ids
        self.block_table[row, nb:] = 0
        self.num_blocks[row] = nb
        if data.window_block_ids is not None:
            if self.window_block_table is None:
                self.window_block_table = np.zeros_like(self.block_table)
            self._set_window_row(row, data.window_block_ids)

        sp: SamplingParams = data.sampling_params
        self.temperature[row] = sp.temperature
        self.top_k[row] = sp.top_k
        self.top_p[row] = sp.top_p
        self.min_p[row] = sp.min_p
        self.seed[row] = -1 if sp.seed is None else sp.seed

        self.presence_penalty[row] = sp.presence_penalty
        self.frequency_penalty[row] = sp.frequency_penalty
        self.repetition_penalty[row] = sp.repetition_penalty
        self.min_tokens[row] = sp.min_tokens
        self.num_logprobs[row] = sp.logprobs or 0
        self.prompt_logprobs[row] = (-1 if sp.prompt_logprobs is None
                                     else sp.prompt_logprobs)
        self.prompt_len[row] = n
        self.needs_extended[row] = sp.needs_extended_static
        self.lora_slot[row] = 0  # runner sets after adapter resolution
        self.pooling[row] = (data.pooling_params or {}).get("type") \
            if data.pooling_params else None
        self.logit_bias[row] = sp.logit_bias
        self.allowed_token_ids[row] = sp.allowed_token_ids
        self.stop_token_ids[row] = tuple(sp.all_stop_token_ids)
        self.mm[row] = data.mm_inputs
        return row

    def _set_window_row(self, row: int, ids: list[int]) -> None:
        self.window_block_table[row, :len(ids)] = ids
        self.window_block_table[row, len(ids):] = 0

    def update_cached(self, data: CachedRequestData) -> None:
        for i, req_id in enumerate(data.req_ids):
            row = self.req_id_to_index[req_id]
            window_ids = (data.new_window_block_ids[i]
                          if data.new_window_block_ids else None)
            if window_ids is not None:
                # Before num_blocks moves: the groups' tables grow in
                # step, so the new ids land at the same slots.
                if data.resumed_from_preemption[i]:
                    self._set_window_row(row, window_ids)
                elif window_ids:
                    nb = self.num_blocks[row]
                    self.window_block_table[
                        row, nb:nb + len(window_ids)] = window_ids
            if data.resumed_from_preemption[i]:
                # Full state replacement: block table was re-allocated.
                tokens = data.new_token_ids[i]
                self.token_ids[row, :len(tokens)] = tokens
                self.num_tokens[row] = len(tokens)
                self.row_version[row] += 1
                nb = len(data.new_block_ids[i])
                self.block_table[row, :nb] = data.new_block_ids[i]
                self.block_table[row, nb:] = 0
                self.num_blocks[row] = nb
            else:
                new_blocks = data.new_block_ids[i]
                if new_blocks:
                    nb = self.num_blocks[row]
                    self.block_table[row, nb:nb + len(new_blocks)] = \
                        new_blocks
                    self.num_blocks[row] = nb + len(new_blocks)
            self.num_computed[row] = data.num_computed_tokens[i]

    def extended_active(self, row: int) -> bool:
        """Does this row need the extended sampling graph RIGHT NOW?
        (static features, or min-tokens stop suppression still in its
        window)."""
        return bool(self.needs_extended[row]
                    or (self.num_tokens[row] - self.prompt_len[row]
                        < self.min_tokens[row]))

    def append_token(self, req_id: str, token_id: int) -> None:
        """Record a token sampled this step (so the next step's input
        includes it). A request already removed (its finish raced a
        trailing async batch's retirement) is a no-op."""
        row = self.req_id_to_index.get(req_id)
        if row is None:
            return
        n = self.num_tokens[row]
        if n < self.max_model_len:
            self.token_ids[row, n] = token_id
            self.num_tokens[row] = n + 1

    def remove_request(self, req_id: str) -> Optional[int]:
        row = self.req_id_to_index.pop(req_id, None)
        if row is None:
            return None
        del self.index_to_req_id[row]
        self._free_rows.append(row)
        self.num_tokens[row] = 0
        self.num_computed[row] = 0
        self.num_blocks[row] = 0
        self.block_table[row, :] = 0
        if self.window_block_table is not None:
            self.window_block_table[row, :] = 0
        self.needs_extended[row] = False
        self.lora_slot[row] = 0
        self.pooling[row] = None
        self.num_logprobs[row] = 0
        self.prompt_logprobs[row] = -1
        self.min_tokens[row] = 0
        self.presence_penalty[row] = 0.0
        self.frequency_penalty[row] = 0.0
        self.repetition_penalty[row] = 1.0
        self.logit_bias[row] = None
        self.allowed_token_ids[row] = None
        self.stop_token_ids[row] = ()
        self.mm[row] = None
        return row
