"""Power retention of degree 2 (Manifest AI's Brumby; the power attention
of arXiv:2507.04239 under a learned decay) over the flat ragged token
batch.

Per K/V head ``h`` and each of its ``G`` query heads ``n``, positions
``j <= t`` of one sequence, ``g`` a decay in (0, 1] a token and head::

    a[t, j] = (q[t, n] . k[j, h])^2 * prod over l in (j, t] of g[l, h]
    o[t, n] = (sum_j a[t, j] v[j, h]) / (sum_j a[t, j])

In recurrent form, with ``phi(a) . phi(b) = (a . b)^2``::

    S_t = g_t S_{t-1} + phi(k_t) v_t^T       z_t = g_t z_{t-1} + phi(k_t)
    o[t, n] = phi(q[t, n])^T S_t / (phi(q[t, n]) . z_t)

**The basis.** ``phi`` is held by CYCLIC OFFSET, not as the 8 256
products ``a_i a_j, i <= j``: for ``d = 0 ... D/2`` (65 offsets at a
head of 128) the row ``a * roll(a, d)``, ``D`` lanes each: 65 x 128 =
8 320 entries. Offset ``d`` and ``D - d`` hold the same unordered
pairs, so offsets ``1 ... D/2 - 1`` count twice; offset ``D/2`` holds
each of its pairs twice already, and offset 0 is the squares. The
factor two stands on the QUERY side alone (``_mult``), so ``phi_k`` is
a plain product and ``phi_q . phi_k = (q . k)^2`` exactly, whatever the
rounding of a square root would have been. A row of the basis is one
lane rotation and one multiply of a vector the kernel already holds;
the state of a K/V head is ``[65, Dv, 128]`` float32, every offset an
MXU tile, 4.26 MB: 0.8% over the 8 256-row symmetric square (the 64
pairs of offset ``D/2`` held twice), and no padding that is zero.
``state[d, c, i]`` is the sum over keys of ``k_i k_(i+d) v_c``: the
value's channel on the sublanes, the basis on the lanes, so that a
row of ``phi`` broadcasts over sublanes, the read against the state
contracts lanes with lanes, and the rank-one update is a plain
product.

Both kernels read and write the layer-stacked state rows IN PLACE under
``ops/mamba.py``'s row contract (one row a request, row S the dump row
of padding; ``build_segment_info``), handed the whole buffer and a layer
index:

* ``power_retention_recurrent``: one grid step a (K/V head, token): the
  head's state block (4.26 MB at the published size; KDA's whole row of
  ``[N, 128, 128]`` would be 34 MB here) comes into VMEM once, every
  offset's tile is decayed, added to and read against all ``G`` query
  heads of the K/V head on the VPU in float32, and goes back once. A
  run of tokens of one row keeps the block where it is (the token is
  the fastest grid axis). Exact for any mix of segments, so it is also
  the chunked form's fallback.
* ``power_retention_chunk``: a chunk of ``CHUNK`` tokens a grid step.
  Among themselves the chunk's tokens take the quadratic form (scores
  squared under the decay from a cumulative sum of ``log g``, the causal
  mask); against the state that entered the chunk each offset's
  ``phi_q`` tile is formed in VMEM (never in HBM: ``[2048, 40, 8320]``
  would be 1.4 GB a layer) and multiplied into the state's tile, which
  is then decayed and takes the chunk's ``phi_k^T (w v)``; the state
  stays in VMEM from chunk to chunk of a segment. Float32 throughout;
  the chunk's own products at ``Precision.HIGHEST``, the two products a
  tile of the state takes in three bfloat16 passes (``_three``: 2^-17 of
  a product, under the 1e-6 that sums of either sign already cost the
  quotient; 13 ms of a 118 ms chunk step on the v5e).

The quotient has no epsilon; a live token always sees itself, and
``(q . k)^2`` of a token with itself is not zero in any weights that
are not. Read from the state, numerator and denominator are sums of
8 320 products of either sign that add up to a sum of squares: in
float32 the quotient is good to about ``1e-7 x |q|^2 sum |k|^2 / sum
(q . k)^2``, a few 1e-6 once a state holds a few dozen keys (1e-5 of a
logit's scale over 700 tokens on the chip) and as much as 1e-3 while it
holds one or two, which is why a sequence's FIRST token takes its value
as it is and a chunk's own tokens the quadratic form. The published inference path keeps keys and values until a
sequence is long enough to switch over to the state; this program goes
through the state from the first token (the same function).

A step's segments are split by kind, by the code that splits them for
``ops/kda.py`` (``ops/mamba.py``: ``decode_slots``, ``chunk_windows``,
``by_form``): one-token segments run the recurrent kernel over the state
rows; longer ones are cut into chunks (each starts a chunk of its own)
and run the chunked form when they fit its static chunk count, else the
recurrent kernel token by token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_distributed_tpu import envs
from vllm_distributed_tpu.ops import mamba
from vllm_distributed_tpu.ops.mamba import (SLOT_CONT, SLOT_HAS_INIT,
                                            SLOT_VALID, SegmentInfo)

KERNEL_RECURRENT = "power_retention_recurrent"
KERNEL_CHUNK = "power_retention_chunk"
CHUNK = 128
# Sublane pieces of a state tile the recurrent kernel walks one after
# the other: G accumulators of [Dv / pieces, D] stay in registers.
_PIECES = 2
_HI = jax.lax.Precision.HIGHEST
_VMEM_LIMIT = 64 * 1024 * 1024


def num_offsets(head_dim: int) -> int:
    """Rows of the cyclic basis: offsets ``0 ... D/2``."""
    assert head_dim % 2 == 0, head_dim
    return head_dim // 2 + 1


def state_shapes(depth: int, rows: int, kv_heads: int, head_dim: int):
    """(state, normaliser) shapes of ``rows`` state rows (the dump row
    among them), both float32."""
    nd = num_offsets(head_dim)
    return ((depth, rows, kv_heads, nd, head_dim, head_dim),
            (depth, rows, kv_heads, _z_rows(nd), head_dim))


def _z_rows(nd: int) -> int:
    """The normaliser's rows, whole sublane tiles: with 65 of them XLA
    lays the array out heads-minor to save the padding and copies it to
    the kernels' layout and back around every call."""
    return -(-nd // 8) * 8


def _mult(d, nd):
    """The query side's count of offset ``d``: 2 where the offset and
    its mirror are two rows' worth of pairs, 1 at the squares and at
    ``D/2``."""
    return jnp.where((d == 0) | (d == nd - 1), 1.0, 2.0)


def phi(x: jax.Array, query: bool) -> jax.Array:
    """The basis of ``x`` ``[..., D]`` as plain ``jax.numpy``:
    ``[..., D/2 + 1, D]``. What the kernels form tile by tile; the tests
    hold it to ``(q . k)^2`` whole."""
    nd = num_offsets(x.shape[-1])
    rows = jnp.stack([x * jnp.roll(x, d, axis=-1) for d in range(nd)],
                     axis=-2)
    if query:
        rows = rows * _mult(jnp.arange(nd), nd)[:, None]
    return rows


def _sources(flags, z_ref, zo_ref, body):
    """Run ``body(source)`` where the slot is live: a continued run finds
    its state in the output block (same block index as the step before:
    the buffer stays, ``"out"``), a new run reads the row's stored state
    (``"in"``) or starts from zero (``None``). Returns whether live."""
    valid = (flags & SLOT_VALID) != 0
    has_init = (flags & SLOT_HAS_INIT) != 0
    cont = (flags & SLOT_CONT) != 0
    pl.when(valid & cont)(lambda: body("out"))

    @pl.when(valid & jnp.logical_not(cont) & has_init)
    def _():
        zo_ref[...] = z_ref[...]     # the rows past the last offset too
        body("in")

    @pl.when(valid & jnp.logical_not(cont) & jnp.logical_not(has_init))
    def _():
        zo_ref[...] = jnp.zeros_like(zo_ref)
        body(None)

    return valid


def _eye(n):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _as_col(row):
    """``[1, n]`` -> ``[n, 1]`` without a transpose of one sublane."""
    return jnp.sum(jnp.where(_eye(row.shape[1]), row, 0.0), axis=1,
                   keepdims=True)


def _as_row(col):
    return jnp.sum(jnp.where(_eye(col.shape[0]), col, 0.0), axis=0,
                   keepdims=True)


# ---------------------------------------------------------------------
# power_retention_recurrent: one token of one K/V head a grid step
# ---------------------------------------------------------------------
def _recurrent_kernel(layer_ref, row_ref, flag_ref, x_ref, s_ref, z_ref,
                      o_ref, so_ref, zo_ref, phi_ref, *, groups: int):
    del layer_ref, row_ref
    G = groups
    nd, Dv, D = s_ref.shape[3:]
    R = x_ref.shape[2]
    x = x_ref[0, 0]                       # q_0 .. q_(G-1), k, g, v: [R, D]
    is_q = jax.lax.broadcasted_iota(jnp.int32, (R, D), 0) < G
    for d in range(nd):                   # one rotation serves every row
        turned = x if d == 0 else pltpu.roll(x, d, 1)
        phi_ref[d] = x * turned * jnp.where(is_q, _mult(d, nd), 1.0)
    g = x[G + 1:G + 2]                    # [1, D], the decay on every lane
    piece = Dv // _PIECES if Dv % (8 * _PIECES) == 0 else Dv

    def body(source):
        src = {"out": so_ref, "in": s_ref}.get(source)
        z_src = {"out": zo_ref, "in": z_ref}.get(source)
        v_col = _as_col(x[G + 2:G + 3])                          # [Dv, 1]
        cols = []
        for lo in range(0, Dv, piece):
            rows = slice(lo, lo + piece)

            def offset(d, carry, rows=rows, first=lo == 0):
                acc, den = carry
                p = phi_ref[d]                                   # [R, D]
                s = v_col[rows] * p[G:G + 1]
                if src is not None:
                    s = s + g * src[0, 0, 0, d, rows, :]
                so_ref[0, 0, 0, d, rows, :] = s
                if src is not None:      # else the token is alone: below
                    acc = tuple(a + s * p[n:n + 1]
                                for n, a in enumerate(acc))
                if first:
                    z = p[G:G + 1]
                    if z_src is not None:
                        z = z + g * z_src[0, 0, 0, pl.ds(d, 1), :]
                        den = den + p * z
                    zo_ref[0, 0, 0, pl.ds(d, 1), :] = z
                return acc, den

            acc, den = jax.lax.fori_loop(
                0, nd, offset,
                (tuple(jnp.zeros((piece, D), jnp.float32)
                       for _ in range(G)),
                 jnp.zeros((R, D), jnp.float32)))
            cols.append([jnp.sum(a, axis=1, keepdims=True) for a in acc])
            if lo == 0:
                below = jnp.sum(den, axis=1, keepdims=True)      # [R, 1]
        for n in range(G):
            if src is None:
                # A sequence's first token sees itself alone: its value,
                # not that value times a quotient of two equal sums.
                o_ref[0, 0, n:n + 1, :] = x[G + 2:G + 3]
                continue
            col = (cols[0][n] if len(cols) == 1 else
                   jnp.concatenate([c[n] for c in cols], axis=0))
            o_ref[0, 0, n:n + 1, :] = _as_row(col) / below[n:n + 1]
        o_ref[0, 0, G:, :] = jnp.zeros((R - G, Dv), jnp.float32)

    valid = _sources(flag_ref[pl.program_id(1)], z_ref, zo_ref, body)

    @pl.when(jnp.logical_not(valid))
    def _():
        # Padding: the dump row, kept finite; nothing of a live row.
        o_ref[...] = jnp.zeros_like(o_ref)
        so_ref[...] = jnp.zeros_like(so_ref)
        zo_ref[...] = jnp.zeros_like(zo_ref)


def _pack_rows(q, k, g, v, groups: int):
    """A slot's rows as the recurrent kernel reads them, ``[M, Hkv, R,
    D]``: the K/V head's ``G`` query heads, its key, its decay on every
    lane, its value; ``R`` the next multiple of eight."""
    M, Hkv, D = k.shape
    R = -(-(groups + 3) // 8) * 8
    rows = jnp.concatenate([
        q.reshape(M, Hkv, groups, D), k[:, :, None],
        jnp.broadcast_to(g[:, :, None, None], (M, Hkv, 1, D)),
        v[:, :, None],
        jnp.zeros((M, Hkv, R - groups - 3, D), jnp.float32)], axis=2)
    return rows


def _recurrent_call(state, zstate, layer, rows, flags, q, k, v, g,
                    interpret: bool):
    """``M`` slots in order: slot m is a token of state row ``rows[m]``
    (q ``[M, Hq, D]``, k, v ``[M, Hkv, D]``, g ``[M, Hkv]`` the decay,
    float32) with ``flags[m]``. Returns (o ``[M, Hq, D]``, state,
    normaliser)."""
    M, Hq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    nd = num_offsets(D)
    assert state.shape[2:] == (Hkv, nd, D, D), (state.shape, q.shape)
    x = _pack_rows(q, k, g, v, G)
    R = x.shape[2]
    tok = pl.BlockSpec((1, 1, R, D), lambda h, m, *_: (m, h, 0, 0))
    row = pl.BlockSpec((1, 1, 1, nd, D, D),
                       lambda h, m, layer, rows, flags:
                       (layer[0], rows[m], h, 0, 0, 0))
    zrow = pl.BlockSpec((1, 1, 1, _z_rows(nd), D),
                        lambda h, m, layer, rows, flags:
                        (layer[0], rows[m], h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(Hkv, M),
        in_specs=[tok, row, zrow], out_specs=(tok, row, zrow),
        scratch_shapes=[pltpu.VMEM((nd, R, D), jnp.float32)])
    o, state, zstate = pl.pallas_call(
        functools.partial(_recurrent_kernel, groups=G),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((M, Hkv, R, D), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(zstate.shape, zstate.dtype)),
        # 3 scalar-prefetch operands, then the rows, state, normaliser.
        input_output_aliases={4: 1, 5: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_RECURRENT,
    )(layer, rows, flags, x, state, zstate)
    return o[:, :, :G].reshape(M, Hq, D), state, zstate


# ---------------------------------------------------------------------
# power_retention_chunk: the chunked form
# ---------------------------------------------------------------------
def _dot(x, y, precision=_HI):
    return jnp.dot(x, y, preferred_element_type=jnp.float32,
                   precision=precision)


def _dot_nt(x, y, precision=_HI):
    """``x y^T``: both contract their lanes."""
    return jax.lax.dot_general(x, y, (((1, ), (1, )), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=precision)


def _split(x):
    """``x`` as a bfloat16 head and the bfloat16 of what it leaves."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _three(dot, x, y):
    """``dot`` of two ``_split`` pairs in three bfloat16 passes where
    ``_HI`` makes six (what ``Precision.HIGH`` names; Mosaic takes only
    the two ends): the product of the two remainders is left out, 2^-17
    of a product. For the walk over the state's tiles, nine tenths of
    the chunked form's products; the chunk's own quadratic form, where a
    score is squared, keeps all six."""
    (xh, xl), (yh, yl) = x, y
    return dot(xh, yh, None) + (dot(xh, yl, None) + dot(xl, yh, None))


def _chunk_kernel(layer_ref, row_ref, flag_ref, q_ref, k_ref, v_ref, aux_ref,
                  s_ref, z_ref, o_ref, so_ref, zo_ref, acc_ref, den_ref,
                  qs_ref, *, groups: int):
    del layer_ref, row_ref
    G = groups
    nd, Dv, D = s_ref.shape[3:]
    C = k_ref.shape[2]

    def body(source):
        src = {"out": so_ref, "in": s_ref}.get(source)
        z_src = {"out": zo_ref, "in": z_ref}.get(source)
        k, v = k_ref[0, 0], v_ref[0, 0]
        # The cumulative log decay at every token of the chunk (constant
        # past its last one), as a row and as a column.
        b_row = aux_ref[0, 0, 0:1, :]                            # [1, C]
        b_col = _as_col(b_row)
        total = b_row[:, C - 1:C]                                # [1, 1]
        at = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        to = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        decay = jnp.where(at >= to,
                          jnp.exp(jnp.minimum(b_col - b_row, 0.0)), 0.0)
        first_lane = jax.lax.broadcasted_iota(jnp.int32, (C, D), 1) == 0
        # Half the decay from the chunk's start on each factor of phi_q.
        half = jnp.exp(0.5 * b_col)
        # Among the chunk's own tokens: the quadratic form. The sum of
        # the weights stands on lane 0 of ``den_ref``; what the state
        # adds below is spread over the lanes.
        for n in range(G):
            rows = slice(n * C, (n + 1) * C)
            q = q_ref[0, 0, rows, :]
            score = _dot_nt(q, k)
            a = score * score * decay
            acc_ref[rows, :] = _dot(a, v)
            den_ref[rows, :] = jnp.where(
                first_lane, jnp.sum(a, axis=1, keepdims=True), 0.0)
            qs_ref[rows, :] = q * half
        # Against the state that entered the chunk, offset by offset; the
        # same walk decays the state and adds the chunk's keys to it.
        weight = jnp.exp(total - b_col)      # from a token to the chunk's end
        wv = _split((v * weight).T)                              # [Dv, C]
        # The chunk's whole decay on every lane of a row.
        kept = jnp.exp(jnp.broadcast_to(b_col, (C, D))[C - 1:C, :])

        def offset(d, carry):
            k = k_ref[0, 0]
            pk = k * pltpu.roll(k, d, 1)                         # [C, D]
            fresh = _three(_dot, wv, _split(pk))                 # [Dv, D]
            z_fresh = jnp.sum(pk * weight, axis=0, keepdims=True)
            if src is not None:
                qs = qs_ref[...]
                pq = qs * pltpu.roll(qs, d, 1) * _mult(d, nd)
                tile = src[0, 0, 0, d]
                z = z_src[0, 0, 0, pl.ds(d, 1), :]
                acc_ref[...] += _three(_dot_nt, _split(pq), _split(tile))
                den_ref[...] += pq * z
                fresh = fresh + kept * tile
                z_fresh = z_fresh + kept * z
            so_ref[0, 0, 0, d] = fresh
            zo_ref[0, 0, 0, pl.ds(d, 1), :] = z_fresh
            return carry

        jax.lax.fori_loop(0, nd, offset, 0)
        below = jnp.sum(den_ref[...], axis=1, keepdims=True)
        # Rows past the chunk's tokens are zero over zero: kept finite.
        o_ref[0, 0] = jnp.where(below > 0.0, acc_ref[...] / below, 0.0)

    valid = _sources(flag_ref[pl.program_id(1)], z_ref, zo_ref, body)

    @pl.when(jnp.logical_not(valid))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        so_ref[...] = jnp.zeros_like(so_ref)
        zo_ref[...] = jnp.zeros_like(zo_ref)


def _chunk_call(state, zstate, layer, rows, flags, q, k, v, aux,
                interpret: bool):
    """``NC`` chunks in order, chunk n of state row ``rows[n]``: q ``[NC,
    Hkv, G * CHUNK, D]`` (a K/V head's query heads one after the other),
    k, v ``[NC, Hkv, CHUNK, D]``, zero outside the chunk's tokens; ``aux``
    ``[NC, Hkv, 8, CHUNK]`` holds in row 0 the cumulative log decay.
    Returns (o like q, state, normaliser)."""
    NC, Hkv, GC, D = q.shape
    C = k.shape[2]
    nd = num_offsets(D)

    def tok(rows_, width):
        return pl.BlockSpec((1, 1, rows_, width),
                            lambda h, n, *_: (n, h, 0, 0))

    row = pl.BlockSpec((1, 1, 1, nd, D, D),
                       lambda h, n, layer, rows, flags:
                       (layer[0], rows[n], h, 0, 0, 0))
    zrow = pl.BlockSpec((1, 1, 1, _z_rows(nd), D),
                        lambda h, n, layer, rows, flags:
                        (layer[0], rows[n], h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(Hkv, NC),
        in_specs=[tok(GC, D), tok(C, D), tok(C, D), tok(8, C), row, zrow],
        out_specs=(tok(GC, D), row, zrow),
        scratch_shapes=[pltpu.VMEM((GC, D), jnp.float32),
                        pltpu.VMEM((GC, D), jnp.float32),
                        pltpu.VMEM((GC, D), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_chunk_kernel, groups=GC // C),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(q.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(zstate.shape, zstate.dtype)),
        # 3 scalar-prefetch operands, then q, k, v, aux, state, normaliser.
        input_output_aliases={7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_CHUNK,
    )(layer, rows, flags, q, k, v, aux, state, zstate)


# ---------------------------------------------------------------------
# The step's segments, by kind
# ---------------------------------------------------------------------
def _decode_part(state, zstate, layer, q, k, v, g, seg: SegmentInfo,
                 interpret: bool):
    """One-token segments, one slot a state row, live rows first."""
    rows, flags, tok, live = mamba.decode_slots(seg)
    o, state, zstate = _recurrent_call(
        state, zstate, layer, rows, flags,
        q[tok], k[tok], v[tok], g[tok], interpret)
    return mamba.scatter_slots(o, tok, live, q.shape[0]), state, zstate


def _token_part(state, zstate, layer, q, k, v, g, seg: SegmentInfo, multi,
                interpret: bool):
    """Multi-token segments token by token: the recurrent form."""
    return _recurrent_call(
        state, zstate, layer, *mamba.token_slots(seg, multi),
        q, k, v, g, interpret)


def _chunk_part(state, zstate, layer, q, k, v, log_g, seg: SegmentInfo,
                multi, chunk_base, chunks, num_chunks: int, interpret: bool):
    """Multi-token segments chunk by chunk: a row's segment is chunks
    ``chunk_base ... chunk_base + chunks`` of the step; each chunk's
    tokens are gathered from the flat batch into a window of its own."""
    T, Hq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    C = CHUNK
    used, row, done, first, count = mamba.chunk_windows(
        seg, chunk_base, chunks, num_chunks, C)
    at = jnp.arange(C, dtype=jnp.int32)[None, :]
    inside = at < count[:, None]                                  # [NC, C]
    idx = jnp.clip(first[:, None] + at, 0, T - 1)

    def window(x):                 # [T, H, ...] -> [NC, H, C, ...]
        x = jnp.where(inside.reshape(inside.shape + (1, ) * (x.ndim - 1)),
                      x[idx], 0.0)
        return jnp.swapaxes(x, 1, 2)

    qw = window(q).reshape(num_chunks, Hkv, G * C, D)
    reach = jnp.cumsum(window(log_g), axis=-1)                # [NC, Hkv, C]
    aux = jnp.pad(reach[:, :, None, :], ((0, 0), (0, 0), (0, 7), (0, 0)))
    o, state, zstate = _chunk_call(
        state, zstate, layer, row,
        mamba.slot_flags(used, seg.has_init_by_row[row], done > 0),
        qw, window(k), window(v), aux, interpret)
    # Token t's output row: its chunk's window, its offset in.
    o = jnp.swapaxes(o.reshape(num_chunks, Hq, C, D), 1, 2)
    chunk = mamba.chunk_of_token(seg, multi, chunk_base, C)
    return (jnp.where(multi[:, None, None], o[chunk, seg.off % C], 0.0),
            state, zstate)


@functools.partial(jax.jit, static_argnames=("decode_only", "prefill_form",
                                             "interpret"))
def retention_ragged(q, k, v, log_g, state, zstate, layer, seg: SegmentInfo,
                     *, decode_only: bool = False,
                     prefill_form: str = "auto",
                     interpret: bool | None = None):
    """Power retention over the step's flat batch. ``q`` ``[T, Hq, D]``
    (at whatever scale: it cancels), ``k``, ``v`` ``[T, Hkv, D]``,
    ``log_g`` ``[T, Hkv]`` the log decay; ``state`` ``[L, S + 1, Hkv,
    D/2 + 1, D, D]`` and ``zstate`` ``[L, S + 1, Hkv, 8 ceil((D/2 + 1) / 8), D]`` the
    layer-stacked float32 rows, ``layer`` ``[1]`` int32. Returns (o ``[T,
    Hq, D]`` float32, state, zstate). ``decode_only`` (static: the
    batch's ``max_q`` is 1) leaves the prefill forms out of the program;
    ``prefill_form`` ``"chunk"`` / ``"recurrent"`` pins the form of
    multi-token segments (tests)."""
    if interpret is None:
        interpret = envs.VDT_PALLAS_INTERPRET
    f32 = jnp.float32
    q, k, v, log_g = (x.astype(f32) for x in (q, k, v, log_g))
    # Padding tokens are inert wherever they land.
    live = seg.valid
    log_g = jnp.where(live[:, None], log_g, 0.0)
    g = jnp.exp(log_g)
    o, state, zstate = _decode_part(state, zstate, layer, q, k, v, g, seg,
                                    interpret)
    if decode_only:
        return o, state, zstate

    multi, chunks, chunk_base = mamba.multi_token_segments(seg, CHUNK)
    num_chunks = mamba.num_chunks_for(q.shape[0], CHUNK)

    def none(state, zstate):
        return jnp.zeros_like(o), state, zstate

    def chunked(state, zstate):
        return _chunk_part(state, zstate, layer, q, k, v, log_g, seg, multi,
                           chunk_base, chunks, num_chunks, interpret)

    def by_token(state, zstate):
        return _token_part(state, zstate, layer, q, k, v, g, seg, multi,
                           interpret)

    o_multi, state, zstate = mamba.by_form(
        prefill_form, chunks, num_chunks, none, chunked, by_token, state,
        zstate)
    return o + o_multi, state, zstate
