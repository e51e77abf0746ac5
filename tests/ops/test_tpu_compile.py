"""The Pallas launchers of the serving path, compiled by the TPU's own
compiler at real widths for a described (not attached) v5e — what
interpret mode cannot show: a slice off the 128-lane tiling, too much
VMEM, a kernel Mosaic refuses. Nothing runs; a pass is not a chip run.

All of it lives in this one file and describes the topology inside a
fixture: only the xdist worker that is handed this file loads libtpu.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# Llama widths (3.2-1B heads; the 128-lane storage head dim the TPU path
# uses), page 16, bf16, and the smoke's engine limits.
QH, KVH, D, PS = 32, 8, 128, 16
L, NP, R, PAGES_PER_REQ = 16, 2048, 64, 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep these tests out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture()
def compile_for_chip(one_chip, no_compile_cache):
    """compile_for_chip(fn, *shapes): jit ``fn`` and compile it for the
    described chip; ShapeDtypeStructs get the one-chip sharding."""

    def run(fn, *shapes):
        placed = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
                  for s in shapes]
        compiled = jax.jit(fn).lower(*placed).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    return run


def sds(shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


def attention_shapes(T, head_dim=D, max_reqs=R):
    """(q, k_pages, v_pages, seq_info, block_tables, layer) at T tokens."""
    cache = sds((L, NP, KVH, PS, head_dim), jnp.bfloat16)
    return (sds((T, QH, head_dim), jnp.bfloat16), cache, cache,
            sds((max_reqs, 4)), sds((max_reqs, PAGES_PER_REQ)), sds((1, )))


def unified_statics(head_dim=D):
    from vllm_distributed_tpu.ops.pallas_attention import (
        decode_group_size, prefill_tile_size)
    return prefill_tile_size(QH, head_dim), decode_group_size(QH, KVH)


@pytest.mark.parametrize("t_bucket", [16, 2048])
def test_unified_attention_compiles(compile_for_chip, t_bucket):
    from vllm_distributed_tpu.ops.pallas_attention import (
        Q_TILE_PAD, num_partition_programs,
        unified_ragged_paged_attention_pallas)
    bq, sb = unified_statics()
    q, k, v, si, bt, layer = attention_shapes(t_bucket + Q_TILE_PAD)
    P = num_partition_programs(t_bucket, R, bq=bq, sb=sb)

    def fn(q, k, v, desc, si, dl, bt, layer):
        return unified_ragged_paged_attention_pallas(
            q, k, v, desc, si, dl, bt, layer, sm_scale=0.125, bq=bq,
            sb=sb, interpret=False)

    compile_for_chip(fn, q, k, v, sds((P, 3)), si, sds((R, )), bt, layer)


@pytest.mark.parametrize("t_bucket", [16, 2048])
def test_unified_write_attend_compiles(compile_for_chip, t_bucket):
    """The fused KV-write + attention call the default path makes once a
    layer (`ops/attention.write_kv_and_attend`)."""
    from vllm_distributed_tpu.ops.pallas_attention import (
        Q_TILE_PAD, num_partition_programs, unified_write_attend_pallas)
    bq, sb = unified_statics()
    T = t_bucket + Q_TILE_PAD
    G = 128
    q, k, v, si, bt, layer = attention_shapes(T)
    P = num_partition_programs(t_bucket, R, bq=bq, sb=sb, num_kv_writes=G)
    new = sds((KVH, T + 3 * PS, D), jnp.bfloat16)

    def fn(q, k, v, kn, vn, desc, si, dl, runs, bt, layer):
        return unified_write_attend_pallas(
            q, k, v, kn, vn, desc, si, dl, runs, bt, layer, sm_scale=0.125,
            bq=bq, sb=sb, interpret=False)

    compile_for_chip(fn, q, k, v, new, new, sds((P, 3)), si, sds((R, )),
                     sds((G, 4)), bt, layer)


@pytest.mark.parametrize("max_q", [1, 32, 256])
def test_ragged_attention_compiles(compile_for_chip, max_q):
    """The descriptor-less kernel (multi-step bursts, EAGLE, cascade)."""
    from vllm_distributed_tpu.ops.pallas_attention import \
        ragged_paged_attention_pallas
    q, k, v, si, bt, layer = attention_shapes(256 + min(max_q, 128))

    def fn(q, k, v, si, ns, bt, layer):
        return ragged_paged_attention_pallas(
            q, k, v, si, ns, bt, layer, sm_scale=0.125, max_q=max_q,
            interpret=False)

    compile_for_chip(fn, q, k, v, si, sds((1, )), bt, layer)


def test_kv_write_compiles(compile_for_chip):
    from vllm_distributed_tpu.ops.pallas_kv_write import \
        write_kv_pages_pallas
    T, G = 2048, 200
    cache = sds((L, NP, KVH, PS, D), jnp.bfloat16)
    new = sds((KVH, T + 3 * PS, D), jnp.bfloat16)

    def fn(k, v, kn, vn, runs, n, layer):
        return write_kv_pages_pallas(k, v, kn, vn, runs, n, layer,
                                     interpret=False)

    compile_for_chip(fn, cache, cache, new, new, sds((G, 4)), sds((1, )),
                     sds((1, )))


def test_true_head_dim_compiles_through_the_padding_helpers(
        compile_for_chip, monkeypatch):
    """Llama-3.2-1B's head size is 64. Mosaic refuses the unified kernel
    on a 64-lane cache ("Slice shape along dimension 2 must be aligned to
    tiling (128), but is 64"); the program avoids that by padding the
    storage head dim on TPU. Steer that helper as the TPU path would see
    it and compile the real dispatch entry at head_dim 64."""
    from vllm_distributed_tpu.ops import attention
    from vllm_distributed_tpu.ops.pallas_attention import (
        Q_TILE_PAD, num_partition_programs)
    from vllm_distributed_tpu.worker.model_runner import AttentionBatch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", "pallas")
    monkeypatch.setenv("VDT_PALLAS_INTERPRET", "0")
    d_store = attention.storage_head_dim(64)
    assert d_store == 128
    bq, sb = unified_statics(d_store)
    t_bucket, G = 256, 128
    T = t_bucket + Q_TILE_PAD
    P = num_partition_programs(t_bucket, R, bq=bq, sb=sb, num_kv_writes=G)
    cache = sds((L, NP, KVH, PS, d_store), jnp.bfloat16)

    def fn(q, k, v, kn, vn, desc, si, dl, runs, bt, slots, layer):
        batch = AttentionBatch(
            req_idx=slots, positions=slots, slot_mapping=slots,
            block_tables=bt, seq_lens=dl, seq_info=si, num_seqs=layer,
            kv_runs=runs, num_kv_runs=layer, attn_desc=desc,
            decode_list=dl, max_q=1, attn_bq=bq, attn_sb=sb)
        k2, v2, out = attention.write_kv_and_attend(
            q, k, v, kn, vn, batch, sm_scale=0.125, layer=layer)
        assert out.shape == (T, QH, 64)
        return k2, v2, out

    compile_for_chip(
        fn, sds((T, QH, 64), jnp.bfloat16), cache, cache,
        sds((T, KVH, 64), jnp.bfloat16), sds((T, KVH, 64), jnp.bfloat16),
        sds((P, 3)), sds((R, 4)), sds((R, )), sds((G, 4)),
        sds((R, PAGES_PER_REQ)), sds((T, )), sds((1, )))


def test_unpadded_head_dim_is_refused(compile_for_chip):
    """The regression the padding guards: the raw kernel on a 64-lane
    cache does not compile for the chip."""
    from vllm_distributed_tpu.ops.pallas_attention import (
        Q_TILE_PAD, num_partition_programs,
        unified_ragged_paged_attention_pallas)
    bq, sb = unified_statics(64)
    q, k, v, si, bt, layer = attention_shapes(256 + Q_TILE_PAD, head_dim=64)
    P = num_partition_programs(256, R, bq=bq, sb=sb)

    def fn(q, k, v, desc, si, dl, bt, layer):
        return unified_ragged_paged_attention_pallas(
            q, k, v, desc, si, dl, bt, layer, sm_scale=0.125, bq=bq,
            sb=sb, interpret=False)

    with pytest.raises(Exception, match="aligned to tiling"):
        compile_for_chip(fn, q, k, v, sds((P, 3)), si, sds((R, )), bt,
                         layer)


@pytest.mark.parametrize("k_dim,n_dim", [(2048, 8192), (8192, 2048)])
def test_int8_quant_matmul_compiles(compile_for_chip, k_dim, n_dim):
    from vllm_distributed_tpu.ops.pallas_quant_matmul import quant_matmul

    def fn(x, w, s):
        return quant_matmul(x, w, s, interpret=False)

    compile_for_chip(fn, sds((256, k_dim), jnp.bfloat16),
                     sds((k_dim, n_dim), jnp.int8),
                     sds((1, n_dim), jnp.float32))


def test_int4_grouped_quant_matmul_compiles(compile_for_chip):
    from vllm_distributed_tpu.ops.pallas_quant_matmul import \
        quant_matmul_grouped
    k_dim, n_dim, group = 2048, 8192, 128

    def fn(x, w, gs, gm):
        return quant_matmul_grouped(x, w, gs, gm, interpret=False)

    compile_for_chip(fn, sds((256, k_dim), jnp.bfloat16),
                     sds((k_dim, n_dim), jnp.uint4),
                     sds((k_dim // group, n_dim), jnp.float32),
                     sds((k_dim // group, n_dim), jnp.float32))


@pytest.mark.parametrize("max_q", [1, 256])
def test_mla_latent_attention_compiles(compile_for_chip, monkeypatch,
                                       max_q):
    """DeepSeek-V2-Lite widths: kv_lora_rank 512, rope 64, 16 heads."""
    from vllm_distributed_tpu.ops import mla
    from vllm_distributed_tpu.ops.pallas_mla import \
        ragged_latent_attention_pallas
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lkv, rope, heads = 512, 64, 16
    cs = mla.latent_storage_dim(lkv, rope)
    assert cs == 640
    T = 256 + min(max_q, 128)

    def fn(q, c, si, ns, bt, layer):
        return ragged_latent_attention_pallas(
            q, c, si, ns, bt, layer, sm_scale=0.1, max_q=max_q,
            kv_lora_rank=lkv, rope_dim=rope, interpret=False)

    compile_for_chip(fn, sds((T, heads, cs), jnp.bfloat16),
                     sds((27, NP, PS, cs), jnp.bfloat16), sds((R, 4)),
                     sds((1, )), sds((R, PAGES_PER_REQ)), sds((1, )))


@pytest.mark.parametrize("max_q,T", [(1, 65), (2048, 2048 + 128)])
def test_mla_latent_attention_compiles_at_published_widths(
        compile_for_chip, monkeypatch, max_q, T):
    """GigaChat3 / DeepSeek-V3 widths as `gigachat3-702b-ep16` serves
    them: 64 heads, 576 -> 640 stored lanes, page 16, block tables of
    512 pages (max_model_len 8192), a decode batch and a 2048-token
    chunk; and the custom call carries the kernel's own name."""
    from vllm_distributed_tpu.ops import mla
    from vllm_distributed_tpu.ops.pallas_mla import (
        KERNEL_NAME, ragged_latent_attention_pallas)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    lkv, rope, heads = 512, 64, 64
    cs = mla.latent_storage_dim(lkv, rope)
    assert cs == 640

    def fn(q, c, si, ns, bt, layer):
        return ragged_latent_attention_pallas(
            q, c, si, ns, bt, layer, sm_scale=0.1, max_q=max_q,
            kv_lora_rank=lkv, rope_dim=rope, interpret=False)

    compiled = compile_for_chip(
        fn, sds((T, heads, cs), jnp.bfloat16),
        sds((6, 26000, PS, cs), jnp.bfloat16), sds((R, 4)), sds((1, )),
        sds((R, 512)), sds((1, )))
    assert f"%{KERNEL_NAME}" in compiled.as_text()


@pytest.mark.xfail(
    strict=True,
    reason="Mosaic failed to compile TPU kernel: infer-vector-layout: "
    "unsupported shape cast (pallas_block.py, the active_rows bool "
    "vector); off the default path (VDT_BLOCK_FUSION=0), ROADMAP D3")
def test_fused_block_decode_compiles(compile_for_chip):
    """Off the default path (VDT_BLOCK_FUSION=0); Llama-3-8B widths,
    whose head size is the 128 the kernel requires."""
    from vllm_distributed_tpu.ops.pallas_block import \
        fused_block_decode_pallas
    H, I, T = 4096, 14336, 64 + 8
    cache = sds((L, NP, KVH, PS, D), jnp.bfloat16)
    bf = jnp.bfloat16

    def fn(h, k, v, wqkv, wo, wg, wu, wd, ln, rope, feat, si, ns, bt,
           layer):
        return fused_block_decode_pallas(
            h, k, v, wqkv, wo, wg, wu, wd, ln, rope, feat, si, ns, bt,
            layer, sm_scale=0.088, eps=1e-5, num_q_heads=QH, head_dim=D,
            interpret=False)

    compile_for_chip(
        fn, sds((T, H), bf), cache, cache,
        sds((H, QH * D + 2 * KVH * D), bf), sds((QH * D, H), bf),
        sds((H, I), bf), sds((H, I), bf), sds((I, H), bf), sds((2, H), bf),
        sds((2, T, D), jnp.float32), sds((2, QH), jnp.float32),
        sds((R, 4)), sds((1, )), sds((R, PAGES_PER_REQ)), sds((1, )))
