"""The short depthwise causal convolution of the state-space and
linear-attention mixers over the flat ragged token batch, its carried
inputs read and replaced IN PLACE.

A sequence carries the last ``K - 1`` inputs of the convolution in its
state row (``ops/mamba.py`` has the rows' contract; row S is the dump
row of padding), layer-stacked in the model's dtype, every input cut
into tiles of 128 lanes: ``[L, S + 1, K - 1, Di / 128, 128]``
(``rows_shape``; the same bytes as ``[L, S + 1, K - 1, Di]``, in a shape
whose layout on the TPU is the one written). ``causal_conv1d_rows`` has
``ops/mamba.causal_conv1d_ragged``'s semantics (its oracle) on the whole
stack and a layer index, so no layer's rows are ever sliced out,
gathered from or copied back (``ops/ssd.py`` and ``ops/kda.py`` set the
pattern):

* what a token reads inside its own chunk (lag ``j`` at offset ``>= j``)
  is a shifted sum of ``x``, plain XLA, elementwise;
* what its first ``K - 1`` tokens read of the row, and the row's new
  inputs (the chunk's last ``K - 1``, reaching back into the old ones
  where the chunk is shorter), is one Pallas kernel, ``conv_rows``: one
  grid step an ENTRY, a (segment, head token) pair. The entry's row is a
  ``[K - 1, Di / 128, 128]`` block found by the scalar-prefetched row
  index, the token's ``y`` a block of the aliased ``[T + 1, Di / 128,
  128]`` sum; the segment's first entry also writes the row. Rows with
  no segment are never touched; entries past the step's last map to the
  dump row and the dump token, whose blocks then stay where they are.

What selects the form is each segment's ``q_len`` at run time: a step of
one-token segments runs one entry a state row; a step that brings a
longer segment runs the longer list (``lax.cond``), whose further
entries are the head tokens 1 .. K - 2 of such segments. The lists are
the same for every layer and width of a step: ``step_entries`` makes
them once, outside the layer loop.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_distributed_tpu import envs
from vllm_distributed_tpu.ops.mamba import SegmentInfo

KERNEL = "conv_rows"

# An entry's code: bit 0 live, bit 1 the row holds carried inputs, then
# the head token's offset and the segment's length cut to K - 1.
_VALID, _HAS_INIT, _HEAD_SHIFT, _LEN_SHIFT = 1, 2, 8, 16


def rows_shape(layers: int, rows: int, taps: int, channels: int) -> tuple:
    """The stack's shape: a row's ``K - 1`` carried inputs, each cut
    into tiles of 128 lanes (every served width is a multiple; a toy
    width keeps 8 lanes a tile, so that its tiles still divide over a
    mesh). A stack shaped ``[L, S + 1, K - 1, Di]`` is what XLA's TPU
    layout turns inside out: K - 1 = 3 rows fill no tile, so it puts L
    (or S + 1, whichever pads less) on the sublanes, a layer then lies
    strewn over every tile, and a kernel that wants the rows as they
    read gets a copy of the whole stack before and after. The same
    happens to a count of tiles that is neither 2, 4 nor a multiple of
    8 (``[16, 65, 1, 10, 128]`` comes in as ``{4,0,3,2,1}``, probed on a
    described v5e, PR 41): such an input is cut into the most of 8, 4 or
    2 wider tiles that divide it (10 tiles: two of 640 lanes), and a
    caller whose count is odd widens it first (``whole_tiles``)."""
    if channels % 128:
        lanes = math.gcd(channels, 8)
        return (layers, rows, taps - 1, channels // lanes, lanes)
    tiles = channels // 128
    if tiles % 8 and tiles not in (2, 4):
        tiles = next(n for n in (4, 2, 1) if tiles % n == 0)
    return (layers, rows, taps - 1, tiles, channels // tiles)


def whole_tiles(channels: int) -> int:
    """``channels`` as ``rows_shape`` can keep them in the layout
    written: an odd count of 128-lane tiles past the first takes one
    more (1 408 channels are carried as 1 536)."""
    tiles, rest = divmod(channels, 128)
    return channels + 128 if not rest and tiles > 1 and tiles % 2 else channels


def _kernel(layer_ref, row_ref, tok_ref, tail_ref, code_ref, w_ref, y_ref,
            *refs):
    del layer_ref, row_ref, tok_ref, tail_ref
    K = w_ref.shape[0]
    tail_refs, s_ref, yo_ref, so_ref = (refs[:K - 1], refs[K - 1],
                                        refs[K], refs[K + 1])
    f32 = jnp.float32
    code = code_ref[pl.program_id(0)]
    valid = (code & _VALID) != 0
    head = (code >> _HEAD_SHIFT) & 0xFF
    q_len = code >> _LEN_SHIFT

    @pl.when(valid)
    def _():
        has_init = (code & _HAS_INIT) != 0
        # Zero where the request starts at position 0: the left pad.
        old = [jnp.where(has_init, s_ref[0, 0, c].astype(f32), 0.0)
               for c in range(K - 1)]                           # [Dt, lanes]
        for h in range(K - 1):

            @pl.when(head == h)
            def _():
                # Token h of the chunk reads old input c at lag
                # K - 1 + h - c: tap c - h.
                acc = y_ref[0]
                for c in range(h, K - 1):
                    acc = acc + w_ref[c - h] * old[c]
                yo_ref[0] = acc

        @pl.when(head == 0)
        def _():
            # New input r is element q_len + r of [old, chunk]: the
            # chunk's token r of its last K - 1, or an old input.
            for r in range(K - 1):
                new = tail_refs[r][0].astype(f32)
                for c in range(r + 1, K - 1):
                    new = jnp.where(q_len + r == c, old[c], new)
                so_ref[0, 0, r] = new.astype(so_ref.dtype)

    @pl.when(jnp.logical_not(valid))
    def _():
        # The dump token and the dump row, kept finite.
        yo_ref[...] = jnp.zeros_like(yo_ref)
        so_ref[...] = jnp.zeros_like(so_ref)


def _entries(seg: SegmentInfo, S: int, T: int, K: int, heads: int,
             count: int):
    """(segment, head token < ``heads``) pairs, live ones first in the
    order of their rows: scalar arrays of ``count`` entries."""
    q_len = seg.q_len_by_row[:S]
    q_start = seg.q_start_by_row[:S]
    live = (jnp.arange(heads)[None, :]
            < jnp.minimum(q_len, K - 1)[:, None]).reshape(-1)
    order = jnp.argsort(jnp.logical_not(live),
                        stable=True)[:count].astype(jnp.int32)
    live = live[order]
    slot, head = order // heads, order % heads
    code = (live.astype(jnp.int32) * _VALID
            + seg.has_init_by_row[:S][slot].astype(jnp.int32) * _HAS_INIT
            + (head << _HEAD_SHIFT)
            + (jnp.minimum(q_len[slot], K - 1) << _LEN_SHIFT))
    return (jnp.where(live, slot, S),
            jnp.where(live, q_start[slot] + head, T),
            jnp.where(live, q_start[slot] + q_len[slot] - (K - 1), 0),
            jnp.where(live, code, 0))


def step_entries(seg: SegmentInfo, num_tokens: int, taps: int,
                 decode_only: bool = False) -> dict:
    """The kernel's entries of a step, the same for every layer and
    width: made once, outside the layer loop. ``one`` is an entry a state
    row (all a step of one-token segments needs); ``longer`` adds the
    head tokens 1 .. K - 2 of longer segments, at most K - 2 a segment,
    and ``any_longer`` says whether the step has such. ``decode_only``
    (static: the batch's ``max_q`` is 1) leaves them out of the
    program."""
    S = seg.q_len_by_row.shape[0] - 1
    T, K = num_tokens, taps
    entries = {"one": _entries(seg, S, T, K, 1, S)}
    if not decode_only:
        entries["longer"] = _entries(seg, S, T, K, K - 1,
                                     S + (K - 2) * min(S, T // 2))
        entries["any_longer"] = jnp.any(seg.q_len_by_row[:S] > 1)
    return entries


def _call(y, rows, x, w, layer, entries, interpret: bool):
    """``y`` ``[T + 1, Dt, lanes]`` float32 (token T the dump token),
    ``x`` ``[T, Dt, lanes]``, ``w`` ``[K, Dt, lanes]`` float32. Returns
    (y, rows)."""
    T = x.shape[0]
    K = w.shape[0]
    tile = rows.shape[3:]

    def tok(e, layer, row, tok, tail, code):
        return (tok[e], 0, 0)

    def state(e, layer, row, tok, tail, code):
        return (layer[0], row[e], 0, 0, 0)

    def tail_at(r):
        return lambda e, layer, row, tok, tail, code: (
            jnp.clip(tail[e] + r, 0, T - 1), 0, 0)

    one = (1, ) + tile
    row_block = pl.BlockSpec((1, 1, K - 1) + tile, state)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(entries[0].shape[0], ),
        in_specs=[pl.BlockSpec((K, ) + tile, lambda e, *_: (0, 0, 0)),
                  pl.BlockSpec(one, tok)]
        + [pl.BlockSpec(one, tail_at(r)) for r in range(K - 1)]
        + [row_block],
        out_specs=(pl.BlockSpec(one, tok), row_block))
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(rows.shape, rows.dtype)),
        # 5 scalar-prefetch operands, then w, y, K - 1 times x, rows.
        input_output_aliases={6: 0, 6 + K: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", )),
        interpret=interpret,
        name=KERNEL,
    )(layer, *entries, w, y, *([x] * (K - 1)), rows)


@functools.partial(jax.jit, static_argnames=("interpret", ))
def causal_conv1d_rows(x, weight, bias, rows, layer, seg: SegmentInfo,
                       entries: dict, *, interpret: bool | None = None):
    """The depthwise causal convolution over the step's flat batch.
    ``x`` ``[T, Di]`` the inputs before the activation, ``weight``
    ``[K, Di]`` the taps (tap 0 the oldest), ``bias`` ``[Di]`` or None;
    ``rows`` the layer-stacked carried inputs in ``rows_shape``'s tiles,
    ``layer`` ``[1]`` int32, ``entries`` the step's (``step_entries``).
    Accumulates in float32 over inputs held in ``x``'s dtype. Returns
    (y ``[T, Di]`` float32, rows)."""
    if interpret is None:
        interpret = envs.VDT_PALLAS_INTERPRET
    f32 = jnp.float32
    T, Di = x.shape
    K = weight.shape[0]
    tile = rows.shape[3:]
    w = weight.astype(f32)
    xz = jnp.where(seg.valid[:, None], x, 0)
    y = w[K - 1] * xz.astype(f32)
    # Without the longer entries every token is its chunk's first.
    for j in range(1, K if "longer" in entries else 1):
        shifted = jnp.pad(xz[:T - j], ((j, 0), (0, 0))).astype(f32)
        y = y + w[K - 1 - j] * jnp.where((seg.off >= j)[:, None], shifted,
                                         0.0)
    if bias is not None:
        y = y + bias.astype(f32)
    y = jnp.pad(y, ((0, 1), (0, 0))).reshape((T + 1, ) + tile)
    x3, w3 = xz.reshape((T, ) + tile), w.reshape((K, ) + tile)

    def run(name):
        return lambda y, rows: _call(y, rows, x3, w3, layer, entries[name],
                                     interpret)

    if "longer" in entries:
        y, rows = jax.lax.cond(entries["any_longer"], run("longer"),
                               run("one"), y, rows)
    else:
        y, rows = run("one")(y, rows)
    return y[:T].reshape(T, Di), rows
