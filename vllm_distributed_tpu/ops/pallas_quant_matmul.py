"""Fused dequantize-matmul Pallas kernel (w4a16 / w8a16 / fp8-w8a16).

Reference capability: csrc/quantization/gptq_marlin/ (marlin-class fused
dequant GEMM — the reference streams packed 4-bit weights from HBM and
dequantizes inside the GEMM pipeline so quantized decode beats fp16).
TPU-native re-design rather than a port:

* Decode matmuls are HBM-bound on the weight stream: [T<=64, K] x
  [K, N] reads K*N weight bytes once. Streaming int4 instead of bf16
  is a 4x traffic cut — IF the dequant never materializes a bf16 copy
  of the weight in HBM. This kernel keeps the packed payload all the
  way into VMEM and dequantizes tile-by-tile into the MXU:
  grid over N tiles; the K loop double-buffers packed weight blocks
  (DMA block k+1 while block k computes), converts int4/int8/fp8 ->
  bf16 in VMEM registers, applies the per-output-channel scale on the
  f32 accumulator once at the end.
* The activation tile [T, K] rides whole in VMEM (decode T is tiny).
* GSPMD cannot see through pallas_call, so the kernel serves the
  tp == 1 path; multi-chip keeps XLA's dequant-in-dot (the convert
  fuses into the sharded dot's operand load).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_vmem, scale_vmem, w_hbm, out_vmem, w_vmem, sems,
            *, bk: int, bn: int, dtype):
    """One N tile: out[:, n*bn:(n+1)*bn] = x @ dequant(w[:, tile])."""
    n = pl.program_id(0)
    K = x_vmem.shape[1]
    num_k = K // bk

    def fetch(k, slot):
        pltpu.make_async_copy(
            w_hbm.at[pl.ds(k * bk, bk), pl.ds(n * bn, bn)],
            w_vmem.at[slot], sems.at[slot]).start()

    fetch(0, 0)

    def body(k, acc):
        slot = jax.lax.rem(k, 2)

        @pl.when(k + 1 < num_k)
        def _prefetch():
            fetch(k + 1, jax.lax.rem(k + 1, 2))

        pltpu.make_async_copy(
            w_hbm.at[pl.ds(0, bk), pl.ds(0, bn)], w_vmem.at[slot],
            sems.at[slot]).wait()
        w_blk = w_vmem[slot].astype(dtype)  # dequant in VMEM regs
        x_blk = x_vmem[:, pl.ds(k * bk, bk)].astype(dtype)
        return acc + jax.lax.dot_general(
            x_blk, w_blk, (((1, ), (0, )), ((), ())),
            preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(
        0, num_k, body,
        jnp.zeros((x_vmem.shape[0], bn), jnp.float32))
    out_vmem[...] = (acc * scale_vmem[0, pl.ds(n * bn, bn)][None, :]
                     ).astype(out_vmem.dtype)


def _gkernel(x_vmem, gs_vmem, gm_vmem, w_hbm, out_vmem, w_vmem, sems,
             *, bk: int, bn: int, dtype, g: int):
    """Group-wise variant: w = q * scale[group] + min[group] (uint4
    int4g payloads, GPTQ/AWQ group structure preserved)."""
    n = pl.program_id(0)
    K = x_vmem.shape[1]
    num_k = K // bk

    def fetch(k, slot):
        pltpu.make_async_copy(
            w_hbm.at[pl.ds(k * bk, bk), pl.ds(n * bn, bn)],
            w_vmem.at[slot], sems.at[slot]).start()

    fetch(0, 0)
    ng = bk // g

    def body(k, acc):
        slot = jax.lax.rem(k, 2)

        @pl.when(k + 1 < num_k)
        def _prefetch():
            fetch(k + 1, jax.lax.rem(k + 1, 2))

        pltpu.make_async_copy(
            w_hbm.at[pl.ds(0, bk), pl.ds(0, bn)], w_vmem.at[slot],
            sems.at[slot]).wait()
        # Mosaic has no unsigned -> float cast: go through int32.
        w_blk = w_vmem[slot].astype(jnp.int32).astype(jnp.float32)
        gs = gs_vmem[pl.ds(k * ng, ng), :]  # [ng, bn]
        gm = gm_vmem[pl.ds(k * ng, ng), :]
        wf = (w_blk.reshape(ng, g, bn) * gs[:, None, :] +
              gm[:, None, :]).reshape(bk, bn)
        x_blk = x_vmem[:, pl.ds(k * bk, bk)].astype(jnp.float32)
        return acc + jax.lax.dot_general(
            x_blk, wf, (((1, ), (0, )), ((), ())),
            preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(
        0, num_k, body,
        jnp.zeros((x_vmem.shape[0], bn), jnp.float32))
    out_vmem[...] = acc.astype(out_vmem.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", ))
def quant_matmul_grouped(x: jax.Array,  # [T, K]
                         w_q: jax.Array,  # [K, N] uint4
                         gscale: jax.Array,  # [G, N] f32
                         gmin: jax.Array,  # [G, N] f32
                         *, interpret: bool = False) -> jax.Array:
    """x @ (w_q * gscale[group] + gmin[group]); packed-bytes streaming
    with per-group dequant inside the pipeline."""
    T, K = x.shape
    _, N = w_q.shape
    G = gscale.shape[0]
    g = K // G
    bn = 128 if N % 128 == 0 else N
    bk = K
    for cand in (2048, 1024, 512, 256, 128):
        if K % cand == 0 and cand % g == 0:
            bk = cand
            break
    kernel = functools.partial(_gkernel, bk=bk, bn=bn, dtype=x.dtype,
                               g=g)
    grid = (N // bn, )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=grid,
            in_specs=[
                pl.BlockSpec((T, K), lambda n: (0, 0)),
                pl.BlockSpec((G, bn), lambda n: (0, n)),
                pl.BlockSpec((G, bn), lambda n: (0, n)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((T, bn), lambda n: (0, n)),
            scratch_shapes=[
                pltpu.VMEM((2, bk, bn), w_q.dtype),
                pltpu.SemaphoreType.DMA((2, )),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((T, N), x.dtype),
        interpret=interpret,
    )(x, gscale, gmin, w_q)


@functools.partial(jax.jit, static_argnames=("interpret", ))
def quant_matmul(x: jax.Array,  # [T, K] activations (bf16/f32)
                 w_q: jax.Array,  # [K, N] int4 | int8 | float8_e4m3fn
                 scale: jax.Array,  # [1, N] f32 per-output-channel
                 *, interpret: bool = False) -> jax.Array:
    """x @ (w_q * scale) streaming only packed weight bytes from HBM."""
    T, K = x.shape
    _, N = w_q.shape
    bn = 128 if N % 128 == 0 else N
    # K block: big enough to amortize DMA latency, small enough that two
    # slots of packed payload + the bf16 dequant tile stay comfortably
    # in VMEM.
    bk = K
    for cand in (2048, 1024, 512, 256, 128):
        if K % cand == 0:
            bk = cand
            break
    kernel = functools.partial(_kernel, bk=bk, bn=bn, dtype=x.dtype)
    grid = (N // bn, )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=grid,
            in_specs=[
                pl.BlockSpec((T, K), lambda n: (0, 0)),  # x in VMEM
                pl.BlockSpec((1, N), lambda n: (0, 0)),  # scales
                pl.BlockSpec(memory_space=pl.ANY),  # packed weights
            ],
            out_specs=pl.BlockSpec((T, bn), lambda n: (0, n)),
            scratch_shapes=[
                pltpu.VMEM((2, bk, bn), w_q.dtype),
                pltpu.SemaphoreType.DMA((2, )),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((T, N), x.dtype),
        interpret=interpret,
    )(x, scale, w_q)
