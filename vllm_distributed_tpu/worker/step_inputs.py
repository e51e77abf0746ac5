"""One host-to-device transfer per step for the forward's integer inputs.

The model runner writes a step's token ids and every integer field of the
``AttentionBatch`` into ONE int32 host buffer and ships it with one
transfer; the jitted programs slice it at static offsets and build the
``AttentionBatch`` as their first act (``StepInputs.unpack``), so
``AttentionBatch`` stays the model's interface. The layout is a pure
function of shapes the forward program is compiled for anyway
(``StepLayout``), so the packed signature keys exactly the programs the
per-array signature keyed. Optional fields (token parallelism, LoRA,
multimodal rows, M-RoPE ids, cascade pages) ride beside the buffer as
arrays of their own: their steps are rare.

Under the pipelined step (``StepLayout.chain``) the buffer also says, a
token, which batch row's last sample stands in for a token id the host
does not know yet, and the forward reads it from the runner's
device-resident mirror (``StepInputs.chain_last``) as it unpacks: no
program and no transfer of its own.
"""

import dataclasses
from dataclasses import dataclass
from functools import cached_property

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from vllm_distributed_tpu.models.common import AttentionBatch


@dataclass(frozen=True)
class StepLayout:
    """Where each field lies in the packed buffer: ``T`` padded tokens,
    ``G`` KV-write runs, ``max_reqs`` batch rows of ``pages_per_req``
    pages, ``desc_programs`` rows of the mega-kernel's partition
    descriptor (0: no descriptor, the legacy dispatch).
    ``window_group``: the model keeps a second cache group for its
    window layers (core/kv_cache_manager.py GroupedKVCacheManager), so
    the buffer carries that group's table, slots and write runs too; a
    model with one group has the layout it always had. ``chain``: the
    runner serves the pipelined step, so the buffer carries
    ``chain_src``, per token the batch row whose last sampled token (on
    the device, ``StepInputs.chain_last``) is this position's input, or
    -1 where ``token_ids`` holds it."""

    T: int
    G: int
    max_reqs: int
    pages_per_req: int
    desc_programs: int
    window_group: bool = False
    chain: bool = False

    @cached_property
    def fields(self) -> tuple[tuple[str, int, int, tuple[int, ...]], ...]:
        """(name, start, stop, shape) in buffer order."""
        T, G, R = self.T, self.G, self.max_reqs
        shapes = [("token_ids", (T, )), ("req_idx", (T, )),
                  ("positions", (T, )), ("slot_mapping", (T, )),
                  ("block_tables", (R, self.pages_per_req)),
                  ("seq_lens", (R, )), ("seq_info", (R, 4)),
                  ("num_seqs", (1, )), ("kv_runs", (G, 4)),
                  ("num_kv_runs", (1, ))]
        if self.desc_programs:
            shapes += [("attn_desc", (self.desc_programs, 3)),
                       ("decode_list", (R, ))]
        if self.window_group:
            shapes += [("window_slot_mapping", (T, )),
                       ("window_block_tables", (R, self.pages_per_req)),
                       ("window_kv_runs", (G, 4))]
        if self.chain:
            shapes += [("chain_src", (T, ))]
        out, start = [], 0
        for name, shape in shapes:
            stop = start + int(np.prod(shape))
            out.append((name, start, stop, shape))
            start = stop
        return tuple(out)

    @property
    def size(self) -> int:
        return self.fields[-1][2]

    @cached_property
    def _blank(self) -> np.ndarray:
        buf = np.zeros((self.size, ), np.int32)
        views = self.views(buf)
        views["slot_mapping"][:] = -1   # padding: no KV write
        if self.window_group:
            views["window_slot_mapping"][:] = -1
        if self.chain:
            views["chain_src"][:] = -1      # every token id is the host's
        return buf

    def new_buffer(self) -> np.ndarray:
        """An inert step: zero counts, padding slots. A fresh buffer a
        step, never one reused: a dispatched step may still be reading
        its buffer (batches in flight; the CPU backend aliases host
        memory instead of copying it)."""
        return self._blank.copy()

    def views(self, buf) -> dict:
        """The fields as reshaped slices of ``buf``: writable views of a
        host buffer, static slices of a (traced) device one."""
        return {name: buf[start:stop].reshape(shape)
                for name, start, stop, shape in self.fields}


@dataclass
class StepInputs:
    """What ``_prepare_inputs`` hands the forward programs."""

    # [layout.size] int32: token ids and the AttentionBatch's integer
    # fields (StepLayout.fields).
    packed: jax.Array
    # The AttentionBatch less those fields (they are None here): its
    # optional arrays (None on the default path) and its statics.
    rest: AttentionBatch
    # Static: where each field lies in ``packed``.
    layout: StepLayout
    # [max_reqs] int32, with ``layout.chain``: each batch row's last
    # sampled token as the sampler left it on the device.
    chain_last: Optional[jax.Array] = None

    @classmethod
    def of(cls, packed, layout: StepLayout, chain_last=None,
           **rest) -> "StepInputs":
        """``rest``: AttentionBatch's optional fields and statics."""
        packed_fields = [name for name, *_ in layout.fields[1:]
                         if name != "chain_src"]
        return cls(packed, AttentionBatch(**dict.fromkeys(packed_fields),
                                          **rest), layout, chain_last)

    def unpack(self) -> tuple[jax.Array, AttentionBatch]:
        """(token_ids, AttentionBatch); inside a jitted program the
        slices are static and cost nothing."""
        f = self.layout.views(self.packed)
        token_ids = f.pop("token_ids")
        src = f.pop("chain_src", None)
        if src is not None:
            token_ids = jnp.where(
                src >= 0, self.chain_last[jnp.maximum(src, 0)], token_ids)
        return token_ids, dataclasses.replace(self.rest, **f)


jax.tree_util.register_dataclass(
    StepInputs, data_fields=["packed", "rest", "chain_last"],
    meta_fields=["layout"])
