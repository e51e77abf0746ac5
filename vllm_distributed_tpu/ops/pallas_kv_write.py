"""Pallas KV-cache write kernel (in-place, aliased).

TPU-native equivalent of the reference's reshape_and_cache CUDA kernel
(csrc/cache_kernels.cu:211) and the kv_cache_update Pallas op its TPU
backend uses (vllm/attention/ops/pallas_kv_cache_update.py, wired with
input/output aliasing at v1/attention/backends/pallas.py:282). Key design
points:

* Operates on the FULL stacked cache [L, N, KVH, PS, D] with the layer as
  a scalar operand, so the per-layer loop never materializes a layer
  slice — XLA would otherwise copy the whole cache through every
  ``lax.scan`` iteration (the original cause of decode steps costing
  ~cache-size in HBM traffic).
* ``input_output_aliases`` make the op update the cache buffer in place;
  only the touched pages move.
* Writes are grouped into page *runs* (maximal consecutive-slot spans
  within one page; a decode token is a run of length 1, a full prefill
  page a run of length PS). Each run is a read-modify-write of one page:
  DMA the page to VMEM, blend the new rows in with a vector select, DMA
  it back. Runs in one step always touch distinct pages, and the TPU grid
  executes programs in order, so RMW is race-free.
* New K/V arrive head-leading [KVH, T + 3*PS, D] with PS padding rows at
  the front and 2*PS at the back, so each run can fetch a page-aligned
  2*PS window around its rows: target window row p corresponds to flat
  token (window_start - PS) + p.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_distributed_tpu import envs


def page_rmw(page, off_start, window_start, run_len, layer, k_new, v_new,
             k_dst, v_dst, k_page, v_page, k_win, v_win, sems, *,
             page_size: int):
    """Read-modify-write ONE cache page with a run of new K/V rows —
    the body shared by the standalone write kernel below and the
    attention mega-kernel's fused kind-3 programs
    (ops/pallas_attention.py). Traced scalars + refs in, DMAs out; the
    caller guards activity (run_len > 0) with pl.when."""
    full = run_len == page_size
    # Mosaic requires provably tile-aligned starts when slicing the
    # sublane dim of an HBM ref: fetch a page-aligned 2*PS window and
    # shift to the exact rows in-register below.
    aligned = pl.multiple_of(
        (window_start // page_size) * page_size, page_size)
    shift = window_start - aligned
    kw = pltpu.make_async_copy(
        k_new.at[:, pl.ds(aligned, 2 * page_size)], k_win, sems.at[0])
    vw = pltpu.make_async_copy(
        v_new.at[:, pl.ds(aligned, 2 * page_size)], v_win, sems.at[1])
    kw.start()
    vw.start()

    @pl.when(jnp.logical_not(full))
    def _read_page():
        kp = pltpu.make_async_copy(k_dst.at[layer, page], k_page,
                                   sems.at[2])
        vp = pltpu.make_async_copy(v_dst.at[layer, page], v_page,
                                   sems.at[3])
        kp.start()
        vp.start()
        kp.wait()
        vp.wait()

    kw.wait()
    vw.wait()

    # Shift the 2*PS window down by `shift` rows via a one-hot
    # selection matmul (Mosaic has no dynamic_slice on values; the
    # 0/1 matrix keeps the selection exact in any dtype).
    num_kv_heads = k_page.shape[0]
    w_ids = jax.lax.broadcasted_iota(jnp.int32,
                                     (page_size, 2 * page_size), 1)
    p_ids = jax.lax.broadcasted_iota(jnp.int32,
                                     (page_size, 2 * page_size), 0)
    sel = (w_ids == p_ids + shift).astype(jnp.float32)

    # Window rows outside the run hold neighbouring flat-batch tokens
    # (or padding garbage, possibly NaN/Inf): zero them before the
    # selection matmul — 0 * NaN = NaN would otherwise poison every
    # selected row of the page.
    w_row = jax.lax.broadcasted_iota(jnp.int32, (2 * page_size, 1), 0)
    w_valid = jnp.logical_and(w_row >= shift + off_start,
                              w_row < shift + off_start + run_len)

    def shifted(win_ref):
        return jnp.stack([
            jax.lax.dot(sel,
                        jnp.where(w_valid,
                                  win_ref[h].astype(jnp.float32), 0.0),
                        preferred_element_type=jnp.float32)
            for h in range(num_kv_heads)
        ]).astype(k_page.dtype)

    k_rows = shifted(k_win)
    v_rows = shifted(v_win)
    row = jax.lax.broadcasted_iota(jnp.int32,
                                   (1, page_size, 1), 1)
    mask = jnp.logical_and(row >= off_start,
                           row < off_start + run_len)
    mask = jnp.logical_or(full, mask)
    k_page[...] = jnp.where(mask, k_rows, k_page[...])
    v_page[...] = jnp.where(mask, v_rows, v_page[...])

    kb = pltpu.make_async_copy(k_page, k_dst.at[layer, page],
                               sems.at[2])
    vb = pltpu.make_async_copy(v_page, v_dst.at[layer, page],
                               sems.at[3])
    kb.start()
    vb.start()
    kb.wait()
    vb.wait()


def _kernel(
    # scalar prefetch
    runs_ref,  # [G, 4] int32: page, off_start, window_start, run_len
    num_runs_ref,  # [1] int32
    layer_ref,  # [1] int32
    # tensors (HBM)
    k_new,  # [KVH, T + 2*PS, D]
    v_new,
    k_all,  # [L, N, KVH, PS, D] (aliased input)
    v_all,
    # outputs (aliased to k_all, v_all)
    k_out,
    v_out,
    # scratch
    k_page,  # [KVH, PS, D]
    v_page,
    k_win,  # [KVH, PS, D]
    v_win,
    sems,  # DMA [4]
    *,
    page_size: int,
):
    g = pl.program_id(0)
    page = runs_ref[g, 0]
    off_start = runs_ref[g, 1]
    window_start = runs_ref[g, 2]
    run_len = runs_ref[g, 3]
    layer = layer_ref[0]
    active = jnp.logical_and(g < num_runs_ref[0], run_len > 0)

    @pl.when(active)
    def _run():
        page_rmw(page, off_start, window_start, run_len, layer, k_new,
                 v_new, k_out, v_out, k_page, v_page, k_win, v_win,
                 sems, page_size=page_size)


@functools.partial(jax.jit, static_argnames=("interpret", ))
def write_kv_pages_pallas(
    k_all: jax.Array,  # [L, N, KVH, PS, D]
    v_all: jax.Array,
    k_new_hl: jax.Array,  # [KVH, T + 2*PS, D] head-leading, padded
    v_new_hl: jax.Array,
    runs: jax.Array,  # [G, 4] int32 (page, off_start, window_start, len)
    num_runs: jax.Array,  # [1] int32
    layer: jax.Array,  # [1] int32
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Blend the step's new K/V rows into their cache pages in place."""
    if interpret is None:
        interpret = envs.VDT_PALLAS_INTERPRET
    L, N, KVH, PS, D = k_all.shape
    G = runs.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(G, ),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # k_new
            pl.BlockSpec(memory_space=pl.ANY),  # v_new
            pl.BlockSpec(memory_space=pl.ANY),  # k_all
            pl.BlockSpec(memory_space=pl.ANY),  # v_all
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ),
        scratch_shapes=[
            pltpu.VMEM((KVH, PS, D), k_all.dtype),
            pltpu.VMEM((KVH, PS, D), v_all.dtype),
            pltpu.VMEM((KVH, 2 * PS, D), k_all.dtype),
            pltpu.VMEM((KVH, 2 * PS, D), v_all.dtype),
            pltpu.SemaphoreType.DMA((4, )),
        ],
    )
    kernel = functools.partial(_kernel, page_size=PS)
    # Operand order: 3 scalar-prefetch args, then tensor inputs; the cache
    # arrays (flat input indices 5 and 6) alias the two outputs.
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct(k_all.shape, k_all.dtype),
            jax.ShapeDtypeStruct(v_all.shape, v_all.dtype),
        ),
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
    )(runs, num_runs, layer, k_new_hl, v_new_hl, k_all, v_all)
