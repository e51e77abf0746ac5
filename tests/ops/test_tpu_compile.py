"""The Pallas launchers of the serving path, compiled by the TPU's own
compiler at real widths for a described (not attached) v5e — what
interpret mode cannot show: a slice off the 128-lane tiling, too much
VMEM, a kernel Mosaic refuses. Nothing runs; a pass is not a chip run.

The K/V-paged kernels compile at the geometry of every benchmark
configuration that runs them (heads, depth and engine limits read from
``benchmarks/configs``, the pool sizes the chip gave: PERF.md section
4) and over the serving lattice's own token buckets; the grouped GEMMs
and a one-layer forward of each configuration compile with their
weights as shapes.

All of it lives in this one file and describes the topology inside a
fixture: only the xdist worker that is handed this file loads libtpu.
"""

import json
import os
import re
from typing import NamedTuple

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from vllm_distributed_tpu.utils import cdiv, make_buckets, pad_to_bucket

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PS = 16


class Geometry(NamedTuple):
    """What a K/V-paged kernel sees of a configuration on one chip."""
    QH: int
    KVH: int
    D: int              # the stored head size (128 lanes on the TPU)
    L: int
    NP: int             # pages in the pool
    R: int              # max_num_seqs
    pages_per_req: int
    max_tokens: int     # max_num_batched_tokens
    window: int = 0     # a window layer's bound: the trimmed page loop


def bench_config(name: str) -> dict:
    with open(os.path.join(REPO, "benchmarks", "configs",
                           f"{name}.json")) as f:
        return json.load(f)


def bench_geometry(name: str, pages: int) -> Geometry:
    cf = bench_config(name)
    ea = cf["engine_args"]
    assert ea["block_size"] == PS
    heads = cf["num_attention_heads"]
    return Geometry(heads, cf["num_key_value_heads"],
                    cf.get("head_dim") or cf["hidden_size"] // heads,
                    cf["num_hidden_layers"],
                    pages, ea["max_num_seqs"],
                    cdiv(ea["max_model_len"], PS),
                    ea["max_num_batched_tokens"])


GEOMETRIES = {
    # Llama-3.2-1B heads (head size 64 stored in 128 lanes), the
    # smoke's engine limits.
    "llama-3.2-1b": Geometry(32, 8, 128, 16, 2048, 64, 256, 2048),
    # Pool sizes as the HBM profile gave them on the chip (PERF.md,
    # section 4).
    "olmo2-1b": bench_geometry("olmo2-1b", 4992),
    "olmoe-1b-7b": bench_geometry("olmoe-1b-7b", 6912),
}
# The other two shapes the fused kernel serves, at the largest bucket
# (their one-layer forwards below hold it at both ends of the lattice):
# ZAYA's 8 query heads on 2 K/V heads in groups of 8 sequences, and a
# window layer of Command A+, 128 query heads on 8 K/V heads in tiles of
# 16 rows and groups of 2, its three window layers' own pool: 128 and
# 256 score rows a K/V head, where the OLMo geometries above have 32.
WIDE_GEOMETRIES = {
    "zaya1-8b": bench_geometry("zaya1-8b", 12032),
    "command-a-plus-ep8": bench_geometry(
        "command-a-plus-ep8", 6368)._replace(
            L=3, window=bench_config("command-a-plus-ep8")["sliding_window"]),
}
# SmallThinker's 28 query heads on 4 K/V heads: groups of SEVEN, which
# the launcher pads to eight rows (``padded_q_heads``; Mosaic refuses a
# slice of 28 sublanes): its six window layers on the trimmed loop over
# the window group's pool, its two full layers over a sized pool, each at
# a decode bucket and at the chunk bucket.
GROUP_OF_SEVEN = {
    "smallthinker-21b-a3b-window": bench_geometry(
        "smallthinker-21b-a3b", 8448)._replace(
            L=6, window=bench_config(
                "smallthinker-21b-a3b")["sliding_window_size"]),
    "smallthinker-21b-a3b-full": bench_geometry(
        "smallthinker-21b-a3b", 16384)._replace(L=2),
}
QH, KVH, D, L, NP, R, PAGES_PER_REQ, _, _ = GEOMETRIES["llama-3.2-1b"]
# The token buckets the runner serves at the cells' engine limits
# (worker/model_runner.py: make_buckets(16, max_num_batched_tokens)).
TOKEN_BUCKETS = make_buckets(16, 2048)
assert all(g.max_tokens == TOKEN_BUCKETS[-1] for g in GEOMETRIES.values())

geometries = pytest.mark.parametrize("geo", list(GEOMETRIES.values()),
                                     ids=list(GEOMETRIES))
# The benchmark's configurations at every bucket; the smoke's at the two
# ends of the lattice, as before (2.3 s a case, and the file has 150 s).
served_lattice = pytest.mark.parametrize("geo,t_bucket", [
    pytest.param(geo, t, id=f"{name}-{t}")
    for name, geo in GEOMETRIES.items() for t in TOKEN_BUCKETS
    if name != "llama-3.2-1b" or t in (TOKEN_BUCKETS[0], TOKEN_BUCKETS[-1])
] + [
    pytest.param(geo, TOKEN_BUCKETS[-1], id=f"{name}-{TOKEN_BUCKETS[-1]}")
    for name, geo in WIDE_GEOMETRIES.items()
] + [
    pytest.param(geo, t, id=f"{name}-{t}")
    for name, geo in GROUP_OF_SEVEN.items()
    for t in (32, TOKEN_BUCKETS[-1])
])


def kv_write_runs(T: int, geo: Geometry) -> int:
    """G of a step of T token rows, as ``_batch_shape`` pads it."""
    buckets = make_buckets(8, cdiv(geo.max_tokens + 128, PS) + geo.R)
    return pad_to_bucket(cdiv(T, PS) + geo.R, buckets)


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


def attention_shapes(T, geo=GEOMETRIES["llama-3.2-1b"], head_dim=None):
    """(q, k_pages, v_pages, seq_info, block_tables, layer) at T tokens."""
    head_dim = head_dim or geo.D
    cache = sds((geo.L, geo.NP, geo.KVH, PS, head_dim), jnp.bfloat16)
    return (sds((T, geo.QH, head_dim), jnp.bfloat16), cache, cache,
            sds((geo.R, 4)), sds((geo.R, geo.pages_per_req)), sds((1, )))


def unified_statics(geo=GEOMETRIES["llama-3.2-1b"], head_dim=None):
    from vllm_distributed_tpu.ops.pallas_attention import (
        decode_group_size, prefill_tile_size)
    return (prefill_tile_size(geo.QH, head_dim or geo.D),
            decode_group_size(geo.QH, geo.KVH))


@geometries
@pytest.mark.parametrize("t_bucket", [16, 2048])
def test_unified_attention_compiles(compile_for_chip, geo, t_bucket):
    from vllm_distributed_tpu.ops.pallas_attention import (
        Q_TILE_PAD, num_partition_programs,
        unified_ragged_paged_attention_pallas)
    bq, sb = unified_statics(geo)
    q, k, v, si, bt, layer = attention_shapes(t_bucket + Q_TILE_PAD, geo)
    P = num_partition_programs(t_bucket, geo.R, bq=bq, sb=sb)

    def fn(q, k, v, desc, si, dl, bt, layer):
        return unified_ragged_paged_attention_pallas(
            q, k, v, desc, si, dl, bt, layer, sm_scale=0.125, bq=bq,
            sb=sb, interpret=False)

    compile_for_chip(fn, q, k, v, sds((P, 3)), si, sds((geo.R, )), bt,
                     layer)


@served_lattice
def test_unified_write_attend_compiles(compile_for_chip, geo, t_bucket):
    """The fused KV-write + attention call the default path makes once a
    layer (`ops/attention.write_kv_and_attend`): the kernel of every
    K/V-paged cell, at each token bucket a step can present."""
    from vllm_distributed_tpu.ops.pallas_attention import (
        Q_TILE_PAD, num_partition_programs, unified_write_attend_pallas)
    bq, sb = unified_statics(geo)
    T = t_bucket + Q_TILE_PAD
    G = kv_write_runs(T, geo)
    q, k, v, si, bt, layer = attention_shapes(T, geo)
    P = num_partition_programs(t_bucket, geo.R, bq=bq, sb=sb,
                               num_kv_writes=G)
    new = sds((geo.KVH, T + 3 * PS, geo.D), jnp.bfloat16)

    def fn(q, k, v, kn, vn, desc, si, dl, runs, bt, layer):
        return unified_write_attend_pallas(
            q, k, v, kn, vn, desc, si, dl, runs, bt, layer, sm_scale=0.125,
            bq=bq, sb=sb, interpret=False, window=geo.window)

    compile_for_chip(fn, q, k, v, new, new, sds((P, 3)), si,
                     sds((geo.R, )), sds((G, 4)), bt, layer)


@pytest.mark.parametrize("features", [
    dict(emit_state=True),
    dict(window=4096, logit_cap=30.0, has_alibi=True, has_sinks=True),
], ids=["cascade_state", "window_cap_alibi_sinks"])
def test_prefill_tile_features_compile(compile_for_chip, features):
    """What no cell runs of the prefill tile, whose queries lie on the
    lanes (at ZAYA's geometry, 128 score rows a K/V head): the per-head
    slopes and sinks broadcast along the lanes, the soft cap, and the
    cascade state stacked down the sublanes and turned with the tile.
    The state-emitting call has no K/V write; the rest is the fused call
    a layer makes."""
    from vllm_distributed_tpu.ops.pallas_attention import (
        Q_TILE_PAD, num_partition_programs,
        unified_ragged_paged_attention_pallas, unified_write_attend_pallas)
    geo, t_bucket = WIDE_GEOMETRIES["zaya1-8b"], 256
    bq, sb = unified_statics(geo)
    assert bq * geo.QH // geo.KVH == 128
    T = t_bucket + Q_TILE_PAD
    q, k, v, si, bt, layer = attention_shapes(T, geo)
    dl, feat = sds((geo.R, )), sds((2, geo.QH), jnp.float32)
    if "emit_state" in features:
        P = num_partition_programs(t_bucket, geo.R, bq=bq, sb=sb)

        def fn(q, k, v, desc, si, dl, bt, layer):
            return unified_ragged_paged_attention_pallas(
                q, k, v, desc, si, dl, bt, layer, sm_scale=0.125, bq=bq,
                sb=sb, interpret=False, **features)

        compile_for_chip(fn, q, k, v, sds((P, 3)), si, dl, bt, layer)
        return
    G = kv_write_runs(T, geo)
    P = num_partition_programs(t_bucket, geo.R, bq=bq, sb=sb,
                               num_kv_writes=G)
    new = sds((geo.KVH, T + 3 * PS, geo.D), jnp.bfloat16)

    def fn(q, k, v, kn, vn, desc, si, dl, runs, bt, layer, feat):
        return unified_write_attend_pallas(
            q, k, v, kn, vn, desc, si, dl, runs, bt, layer, feat,
            sm_scale=0.125, bq=bq, sb=sb, interpret=False, **features)

    compile_for_chip(fn, q, k, v, new, new, sds((P, 3)), si, dl,
                     sds((G, 4)), bt, layer, feat)


@pytest.mark.parametrize("name", [
    "olmo2-1b", "zaya1-8b", "command-a-plus-ep8",
    "smallthinker-21b-a3b-window", "smallthinker-21b-a3b-full"])
def test_decode_groups_compile_with_the_cascade_state(compile_for_chip,
                                                      name):
    """The decode group that starts and waits for a slot's page copies
    only while the slot's own sequence has a kv block left (PR 54), in
    the call no cell makes: the attention-only launcher with the
    online-softmax state out, at a decode bucket, for each group width
    the cells have (4 sequences OLMo-2's, 8 on ZAYA's two K/V heads, 2 at
    Command A+'s 128 query heads where the per-row columns come from an
    iota, 8 on SmallThinker's 28 padded to 32), under the window where
    the configuration has one. The served lattice above holds the fused
    call at the same widths."""
    from vllm_distributed_tpu.ops.pallas_attention import (
        Q_TILE_PAD, num_partition_programs,
        unified_ragged_paged_attention_pallas)
    geo = {**GEOMETRIES, **WIDE_GEOMETRIES, **GROUP_OF_SEVEN}[name]
    t_bucket = 64
    bq, sb = unified_statics(geo)
    q, k, v, si, bt, layer = attention_shapes(t_bucket + Q_TILE_PAD, geo)
    P = num_partition_programs(t_bucket, geo.R, bq=bq, sb=sb)

    def fn(q, k, v, desc, si, dl, bt, layer):
        return unified_ragged_paged_attention_pallas(
            q, k, v, desc, si, dl, bt, layer, sm_scale=0.125, bq=bq,
            sb=sb, interpret=False, emit_state=True, window=geo.window)

    compile_for_chip(fn, q, k, v, sds((P, 3)), si, sds((geo.R, )), bt,
                     layer)


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


@geometries
def test_kv_write_compiles(compile_for_chip, geo):
    from vllm_distributed_tpu.ops.pallas_kv_write import \
        write_kv_pages_pallas
    T = geo.max_tokens
    G = kv_write_runs(T, geo)
    cache = sds((geo.L, geo.NP, geo.KVH, PS, geo.D), jnp.bfloat16)
    new = sds((geo.KVH, T + 3 * PS, geo.D), jnp.bfloat16)

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
    bq, sb = unified_statics(head_dim=d_store)
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
    bq, sb = unified_statics(head_dim=64)
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


def latent_lattice(buckets) -> list:
    """(max_q, T) of the legacy lattice MLA batches keep (``_batch_shape``:
    a chunk of t tokens runs at max_q = t and T = t + min(t, 128)), and
    the decode batch of 64 requests."""
    return [(1, 64 + 1)] + [(t, t + min(t, 128)) for t in buckets]


@pytest.mark.parametrize("max_q,T", latent_lattice(TOKEN_BUCKETS))
def test_mla_latent_attention_compiles_at_published_widths(
        compile_for_chip, monkeypatch, max_q, T):
    """GigaChat3 / DeepSeek-V3 widths as `gigachat3-702b-ep16` serves
    them: heads, latent and rope widths, depth and engine limits from its
    configuration file (64 heads, 576 -> 640 stored lanes, block tables
    of 512 pages), the pool the chip gave it (33 280 pages: PERF.md,
    section 4), a decode batch and every chunk bucket of the lattice;
    and the custom call carries the kernel's own name."""
    from vllm_distributed_tpu.ops import mla
    from vllm_distributed_tpu.ops.pallas_mla import (
        KERNEL_NAME, ragged_latent_attention_pallas)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cf = bench_config("gigachat3-702b-ep16")
    ea = cf["engine_args"]
    assert TOKEN_BUCKETS == make_buckets(16, ea["max_num_batched_tokens"])
    lkv, rope = cf["kv_lora_rank"], cf["qk_rope_head_dim"]
    heads, reqs = cf["num_attention_heads"], ea["max_num_seqs"]
    cs = mla.latent_storage_dim(lkv, rope)
    assert cs == 640

    def fn(q, c, si, ns, bt, layer):
        return ragged_latent_attention_pallas(
            q, c, si, ns, bt, layer, sm_scale=0.1, max_q=max_q,
            kv_lora_rank=lkv, rope_dim=rope, interpret=False)

    compiled = compile_for_chip(
        fn, sds((T, heads, cs), jnp.bfloat16),
        sds((cf["num_hidden_layers"], 33280, PS, cs), jnp.bfloat16),
        sds((reqs, 4)), sds((1, )),
        sds((reqs, cdiv(ea["max_model_len"], PS))), sds((1, )))
    assert f"%{KERNEL_NAME}" in compiled.as_text()


@pytest.mark.parametrize("t_bucket", [TOKEN_BUCKETS[0], TOKEN_BUCKETS[-1]])
@pytest.mark.parametrize("name", ["gigachat3-702b-ep16", "ling3-flash-ep4"])
def test_mla_chunk_step_compiles_at_published_widths(
        compile_for_chip, monkeypatch, name, t_bucket):
    """One latent layer's attention of a chunk-bearing step
    (``ops/mla.latent_attention_values`` at ``max_q`` > 1) as both
    latent-attention configurations serve it, heads, widths and engine
    limits from their files (64 heads and V 192, padded to 256 lanes;
    32 heads and V 128): the multi-head kernel over the chunks, the
    absorbed kernel over ``max_num_seqs`` gathered rows, in one program,
    and K and V go to the kernel as the up-projection leaves them (no
    copy of a context-sized buffer)."""
    from vllm_distributed_tpu.ops import mla
    from vllm_distributed_tpu.ops.pallas_mla import (KERNEL_NAME,
                                                     PREFILL_KERNEL_NAME)
    from vllm_distributed_tpu.parallel import mesh as mesh_state
    from vllm_distributed_tpu.worker.model_runner import AttentionBatch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", "pallas")
    monkeypatch.setenv("VDT_PALLAS_INTERPRET", "0")
    cf = bench_config(name)
    ea = cf["engine_args"]
    lkv, rope = cf["kv_lora_rank"], cf["qk_rope_head_dim"]
    heads, reqs = cf["num_attention_heads"], ea["max_num_seqs"]
    nope, v_dim = cf["qk_nope_head_dim"], cf["v_head_dim"]
    ctx = ea["max_model_len"]
    T = t_bucket + min(t_bucket, 128)

    def fn(qn, qp, c, wk, wv, si, ns, bt, slots, layer):
        batch = AttentionBatch(
            req_idx=slots, positions=slots, slot_mapping=slots,
            block_tables=bt, seq_lens=ns, seq_info=si, num_seqs=ns,
            max_q=t_bucket)
        return mla.latent_attention_values(
            qn, qp, c, batch, wk, wv, sm_scale=0.07, kv_lora_rank=lkv,
            rope_dim=rope, layer=layer)

    bf16 = jnp.bfloat16
    # One chip: no mesh of an engine that another test file started.
    monkeypatch.setattr(mesh_state, "_GLOBAL_MESH", None)
    text = compile_for_chip(
        fn, sds((T, heads, nope), bf16), sds((T, heads, rope), bf16),
        sds((2, 4096, PS, mla.latent_storage_dim(lkv, rope)), bf16),
        sds((lkv, heads, nope), bf16), sds((lkv, heads, v_dim), bf16),
        sds((reqs, 4)), sds((1, )), sds((reqs, cdiv(ctx, PS))),
        sds((T, )), sds((1, ))).as_text()
    assert f"%{KERNEL_NAME}" in text and f"%{PREFILL_KERNEL_NAME}" in text
    lanes = heads * (-(-v_dim // 128) * 128)
    assert not re.search(rf"bf16\[{ctx},{lanes}\]\S* copy\(", text)


@pytest.mark.parametrize("tokens,form", [
    (1, "decode"), (2048, "chunk"), (2048, "auto")])
def test_gated_delta_kernels_compile_at_published_widths(
        compile_for_chip, tokens, form):
    """``ops/kda.py`` as `ling3-flash-ep4` serves it: 32 heads of 128 x
    128 float32 state, 96 state rows and the dump row, six layers in one
    buffer; a decode batch (one token a row: ``kda_recurrent``), and a
    2 048-token chunk bucket in the chunked form (``kda_chunk``), token
    by token, and as the program picks between them at run time. What a
    chunk computes without its incoming state is formed inside
    ``kda_chunk`` (PR 42): the program holds none of the arrays over 38
    chunks that plain XLA made beside it."""
    from benchmarks.rooflines.gated_delta_state import kda_layers
    from vllm_distributed_tpu.ops import kda
    from vllm_distributed_tpu.ops.mamba import SegmentInfo
    cf = bench_config("ling3-flash-ep4")
    ea = cf["engine_args"]
    N, D, S = cf["num_attention_heads"], cf["head_dim"], ea["max_num_seqs"]
    layers = kda_layers(cf)
    assert (N, D, S, layers) == (32, 128, 96, 6)
    T = S + 1 if form == "decode" else tokens + 128
    f32, flag = jnp.float32, jnp.bool_
    seg = SegmentInfo(
        row=sds((T, )), valid=sds((T, ), flag), off=sds((T, )),
        start=sds((T, ), flag), end=sds((T, ), flag),
        has_init=sds((T, ), flag), q_len_by_row=sds((S + 1, )),
        q_start_by_row=sds((S + 1, )),
        has_init_by_row=sds((S + 1, ), flag))
    leaves, tree = jax.tree.flatten(seg)

    def fn(q, k, v, g, beta, state, layer, *seg_leaves):
        return kda.kda_ragged(
            q, k, v, g, beta, state, layer,
            jax.tree.unflatten(tree, seg_leaves),
            decode_only=form == "decode",
            prefill_form="auto" if form == "decode" else form,
            interpret=False)

    tok = sds((T, N, D), f32)
    text = compile_for_chip(
        fn, tok, tok, tok, tok, sds((T, N), f32),
        sds((layers, S + 1, N, D, D), f32), sds((1, )), *leaves).as_text()
    assert f"%{kda.KERNEL_RECURRENT}" in text
    assert (f"%{kda.KERNEL_CHUNK}" in text) == (form in ("chunk", "auto"))
    chunks = cdiv(T, kda.CHUNK) + kda.MAX_CHUNK_SEGMENTS
    assert chunks == 38 or form == "decode"
    for local in ("16,16", "64,64", "64,256", "64,128", "128,64"):
        assert f"f32[{chunks},{N},{local}]" not in text, local


@pytest.mark.parametrize("tokens,form", [
    (1, "decode"), (2048, "chunk"), (2048, "auto")])
def test_power_retention_kernels_compile_at_published_widths(
        compile_for_chip, tokens, form):
    """``ops/power_retention.py`` as `brumby-14b` serves it: 40 query
    heads on 8 K/V heads of 128, a state of 65 tiles of 128 x 128 float32
    a K/V head (4.26 MB: one block of the recurrent kernel), 16 state
    rows and the dump row, six layers in one buffer; a decode batch (one
    token a row: ``power_retention_recurrent``), and a 2 048-token chunk
    bucket in the chunked form (``power_retention_chunk``) and as the
    program picks between the forms at run time. ``phi(q)`` is never an
    array of the program: nothing has 8 320 (or 8 256) rows a token."""
    from vllm_distributed_tpu.ops import power_retention as pr
    cf = bench_config("brumby-14b")
    ea = cf["engine_args"]
    Hq, Hkv, D = (cf["num_attention_heads"], cf["num_key_value_heads"],
                  cf["head_dim"])
    S, layers = ea["max_num_seqs"], cf["num_hidden_layers"]
    assert (Hq, Hkv, D, S, layers) == (40, 8, 128, 16, 6)
    T = S if form == "decode" else tokens
    leaves, tree = segment_shapes(T, S)

    def fn(q, k, v, log_g, state, zstate, layer, *seg_leaves):
        return pr.retention_ragged(
            q, k, v, log_g, state, zstate, layer,
            jax.tree.unflatten(tree, seg_leaves),
            decode_only=form == "decode",
            prefill_form="auto" if form == "decode" else form,
            interpret=False)

    f32 = jnp.float32
    ret, ret_z = pr.state_shapes(layers, S + 1, Hkv, D)
    assert ret == (6, 17, 8, 65, 128, 128)
    text = compile_for_chip(
        fn, sds((T, Hq, D), f32), sds((T, Hkv, D), f32),
        sds((T, Hkv, D), f32), sds((T, Hkv), f32), sds(ret, f32),
        sds(ret_z, f32), sds((1, )), *leaves).as_text()
    assert f"%{pr.KERNEL_RECURRENT}" in text
    assert (f"%{pr.KERNEL_CHUNK}" in text) == (form in ("chunk", "auto"))
    for rows in (8320, 8256, 65 * 128 * 5):
        assert f",{rows}," not in text and f",{rows}]" not in text, rows


def segment_shapes(T: int, S: int):
    from vllm_distributed_tpu.ops.mamba import SegmentInfo
    flag = jnp.bool_
    seg = SegmentInfo(
        row=sds((T, )), valid=sds((T, ), flag), off=sds((T, )),
        start=sds((T, ), flag), end=sds((T, ), flag),
        has_init=sds((T, ), flag), q_len_by_row=sds((S + 1, )),
        q_start_by_row=sds((S + 1, )),
        has_init_by_row=sds((S + 1, ), flag))
    return jax.tree.flatten(seg)


@pytest.mark.parametrize("tokens,form", [
    (1, "decode"), (2048, "chunk"), (2048, "auto")])
@pytest.mark.parametrize("name,geometry", [
    ("granite-4.0-h-micro", (64, 64, 128, 1, 36)),
    ("granite-4.0-h-small-ep2", (128, 64, 128, 1, 9))])
def test_mamba2_kernels_compile_at_published_widths(
        compile_for_chip, name, geometry, tokens, form):
    """``ops/ssd.py`` as `granite-4.0-h-micro` serves it: 64 heads of 64
    channels on 128 state channels, a row of 128 x 4096 float32, 56
    state rows and the dump row, 36 layers in one buffer of 4.9 GB that
    no instruction of the program makes again; a decode batch (one
    token a row: ``ssd_recurrent``), and the 2 048-token bucket in the
    chunked form at Q = 256 (``ssd_chunk``) and as the program picks
    between the forms at run time. And as `granite-4.0-h-small-ep2`
    does: 128 heads, a row of 128 x 8192 float32 (4 MiB a layer, twice
    Micro's), 9 layers: the lane blocks a grid step holds still fit
    VMEM."""
    from benchmarks.rooflines.ssd_state import mamba_layers
    from vllm_distributed_tpu.ops import ssd
    cf = bench_config(name)
    Hm, Pd, N, G = (cf["mamba_n_heads"], cf["mamba_d_head"],
                    cf["mamba_d_state"], cf["mamba_n_groups"])
    S, layers = cf["engine_args"]["max_num_seqs"], mamba_layers(cf)
    assert (Hm, Pd, N, G, layers) == geometry
    T = S + 8 if form == "decode" else tokens + 8
    leaves, tree = segment_shapes(T, S)
    f32 = jnp.float32

    def fn(x, dt, A, B, C, D, state, layer, *seg_leaves):
        return ssd.ssd_ragged(
            x, dt, A, B, C, D, state, layer,
            jax.tree.unflatten(tree, seg_leaves),
            chunk_size=cf["mamba_chunk_size"],
            decode_only=form == "decode",
            prefill_form="auto" if form == "decode" else form,
            interpret=False)

    rows = (layers, S + 1, N, Hm * Pd)
    compiled = compile_for_chip(
        fn, sds((T, Hm, Pd), jnp.bfloat16), sds((T, Hm), f32),
        sds((Hm, ), f32), sds((T, G, N), f32), sds((T, G, N), f32),
        sds((Hm, ), f32), sds(rows, f32), sds((1, )), *leaves)
    text = compiled.as_text()
    assert f"%{ssd.KERNEL_RECURRENT}" in text
    assert (f"%{ssd.KERNEL_CHUNK}" in text) == (form in ("chunk", "auto"))
    # No state per token: the largest temporaries are the chunks' masks
    # (13 chunks x 64 heads x 256 x 256; twice that at 128 heads), far
    # under a state a token (2 MiB x 2 056).
    assert compiled.memory_analysis().temp_size_in_bytes < \
        600 * 2 ** 20 * Hm // 64


@pytest.mark.parametrize("tokens", [64, 2048])
@pytest.mark.parametrize("name,layers,rows,width,bias", [
    ("granite-4.0-h-micro", 36, 56, 4096, True),
    ("granite-4.0-h-micro", 36, 56, 256, True),
    ("granite-4.0-h-small-ep2", 9, 64, 8192, True),
    ("granite-4.0-h-small-ep2", 9, 64, 256, True),
    ("ling3-flash-ep4", 6, 96, 12288, False)])
def test_conv_rows_kernel_compiles_at_published_widths(
        one_chip, no_compile_cache, name, layers, rows, width, bias, tokens):
    """``ops/conv1d.py`` as `granite-4.0-h-micro` serves it (the
    convolution of x, 4 096 channels, and of B and C, 256, four taps and
    a bias, 56 state rows and the dump row, 36 layers in one buffer)
    and as `ling3-flash-ep4` does (q, k and v, 12 288 channels, no bias,
    96 rows, 6 layers), at a decode bucket and at the 2 048-token one:
    the stack keeps the layout it is given in (the shape
    ``[L, S + 1, 3, Di]`` would come in with L on the sublanes and be
    copied whole before and after the kernel), and nothing makes it
    again."""
    from vllm_distributed_tpu.ops import conv1d
    cf = bench_config(name)
    S = cf["engine_args"]["max_num_seqs"]
    K = cf.get("mamba_d_conv") or cf["short_conv_kernel_size"]
    assert (S, K) == (rows, 4)
    T = tokens + 8
    leaves, tree = segment_shapes(T, S)
    bf16 = jnp.bfloat16

    def fn(x, w, b, stack, layer, *seg_leaves):
        seg = jax.tree.unflatten(tree, seg_leaves)
        return conv1d.causal_conv1d_rows(
            x, w, b if bias else None, stack, layer, seg,
            conv1d.step_entries(seg, T, K), interpret=False)

    shape = conv1d.rows_shape(layers, S + 1, K, width)
    assert shape == (layers, S + 1, K - 1, width // 128, 128)
    placed = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
              for a in (sds((T, width), bf16), sds((K, width), bf16),
                        sds((width, ), bf16), sds(shape, bf16), sds((1, )),
                        *leaves)]
    # The stack is the program's to overwrite, as the runner's caches are.
    compiled = jax.jit(fn, donate_argnums=(3, )).lower(*placed).compile()
    text = compiled.as_text()
    assert text.count(f"%{conv1d.KERNEL}") >= 2      # either list
    slab = "bf16[" + ",".join(map(str, shape)) + "]"
    # It comes in as it is read, a row's inputs in whole tiles...
    assert slab + "{4,3,2,1,0:T(" in text.split("\n", 1)[0]
    # ... and no instruction makes it again.
    assert slab_copies(compiled, slab) == []


def test_two_64_lane_kv_heads_share_a_page_row(compile_for_chip,
                                               monkeypatch):
    """`granite-4.0-h-micro`'s attention: 32 query heads on 8 K/V heads
    of 64 lanes, scale 1/64, no rotation. Mosaic refuses a 64-lane page
    (``test_unpadded_head_dim_is_refused``); the model hands the kernel
    4 K/V heads of 128 lanes, two heads to a row
    (``models/bamba.py`` ``_attention``): 8 192 B of K and V a token and
    no padding. The real dispatch entry compiles at those shapes."""
    from vllm_distributed_tpu.ops import attention
    from vllm_distributed_tpu.ops.pallas_attention import (
        Q_TILE_PAD, num_partition_programs)
    from vllm_distributed_tpu.worker.model_runner import AttentionBatch
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", "pallas")
    monkeypatch.setenv("VDT_PALLAS_INTERPRET", "0")
    geo = Geometry(32, 4, 128, 4, 16384, 56, 256, 2048)
    assert attention.storage_head_dim(2 * 64) == geo.D
    # The tile sizes are the runner's, from the model's own 32 / 8 heads
    # (worker/model_runner.py _tile_params).
    bq, sb = unified_statics(geo._replace(KVH=8))
    t_bucket = 64
    T = t_bucket + Q_TILE_PAD
    G = kv_write_runs(T, geo)
    P = num_partition_programs(t_bucket, geo.R, bq=bq, sb=sb,
                               num_kv_writes=G)
    cache = sds((geo.L, geo.NP, geo.KVH, PS, geo.D), jnp.bfloat16)
    new = sds((T, geo.KVH, geo.D), jnp.bfloat16)

    def fn(q, k, v, kn, vn, desc, si, dl, runs, bt, slots, layer):
        batch = AttentionBatch(
            req_idx=slots, positions=slots, slot_mapping=slots,
            block_tables=bt, seq_lens=dl, seq_info=si, num_seqs=layer,
            kv_runs=runs, num_kv_runs=layer, attn_desc=desc,
            decode_list=dl, max_q=1, attn_bq=bq, attn_sb=sb)
        return attention.write_kv_and_attend(
            q, k, v, kn, vn, batch, sm_scale=1 / 64, layer=layer)

    compile_for_chip(
        fn, sds((T, geo.QH, geo.D), jnp.bfloat16), cache, cache, new, new,
        sds((P, 3)), sds((geo.R, 4)), sds((geo.R, )), sds((G, 4)),
        sds((geo.R, geo.pages_per_req)), sds((T, )), sds((1, )))


# ---------------------------------------------------------------------
# The benchmark configurations' own programs, weights as shapes
# ---------------------------------------------------------------------
SPARSE = ("olmoe-1b-7b", "gigachat3-702b-ep16", "ling3-flash-ep4",
          "command-a-plus-ep8")
# Depth cut to one layer of each kind a configuration has: widths,
# heads, experts and engine limits stay the file's.
ONE_LAYER = {
    "olmo2-1b": {"num_hidden_layers": 1},
    "olmoe-1b-7b": {"num_hidden_layers": 1},
    "gigachat3-702b-ep16": {"num_hidden_layers": 2,
                            "first_k_dense_replace": 1},
    # KDA with the dense MLP, KDA with the experts, latent attention
    # with the experts: a period of three in place of the file's six.
    "ling3-flash-ep4": {"num_hidden_layers": 3, "layer_group_size": 3},
    # Two periods of the published ten: the walk is a scan whose body is
    # one period whatever the depth.
    "granite-4.0-h-micro": {
        "num_hidden_layers": 20,
        "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 2},
    # Every layer is of one kind; two, so that the scan and the expert
    # switch are there.
    "zaya1-8b": {"num_hidden_layers": 2, "layer_types": ["hybrid"] * 2},
    # A window layer and a full layer: both cache groups, both tables,
    # the trimmed and the untrimmed kernel.
    "command-a-plus-ep8": {
        "num_hidden_layers": 2,
        "layer_types": ["sliding_attention", "full_attention"]},
    # A full layer without rope and a window layer with it, in that
    # order: both cache groups, the early router, the ReLU experts.
    "smallthinker-21b-a3b": {
        "num_hidden_layers": 2, "sliding_window_layout": [0, 1],
        "rope_layout": [0, 1]},
    # Every layer is a retention layer; two, so that the scan is there.
    "brumby-14b": {"num_hidden_layers": 2},
    # A mamba and an attention layer, each with its router, its held
    # experts and its shared MLP: one period, as the file's ten are, so
    # a layer's expert buffers are picked outright.
    "granite-4.0-h-small-ep2": {"num_hidden_layers": 2,
                                "layer_types": ["mamba", "attention"]},
}


@pytest.fixture()
def abstract_runner(topo, no_compile_cache, monkeypatch, tmp_path):
    """build(name) -> a ``TPUModelRunner`` of the benchmark configuration
    ``name`` cut to ``ONE_LAYER``, on a mesh of the described chip, its
    weights ``ShapeDtypeStruct``s in the serving layout: nothing is
    allocated. The steering a program that asks ``jax.default_backend()``
    needs is done here, in the test."""
    from benchmarks.run import hf_config
    from vllm_distributed_tpu.engine.arg_utils import EngineArgs
    from vllm_distributed_tpu.models import loader, mixtral
    from vllm_distributed_tpu.parallel.mesh import build_mesh
    from vllm_distributed_tpu.worker.model_runner import TPUModelRunner
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", "pallas")
    monkeypatch.setenv("VDT_PALLAS_INTERPRET", "0")
    monkeypatch.setattr(
        loader, "_init_dummy_params",
        lambda model, rng, mesh: jax.eval_shape(model.init_params, rng))
    monkeypatch.setattr(
        mixtral, "_unstack",
        lambda stack: tuple(sds(stack.shape[1:], stack.dtype)
                            for _ in range(stack.shape[0])))

    def build(name):
        cf = bench_config(name)
        model_dir = tmp_path / name
        model_dir.mkdir()
        with open(model_dir / "config.json", "w") as f:
            json.dump({**hf_config(cf), **ONE_LAYER[name]}, f)
        config = EngineArgs(
            model=str(model_dir), load_format="dummy",
            skip_tokenizer_init=True,
            **cf["engine_args"]).create_engine_config()
        mesh = build_mesh(config.parallel_config, devices=topo.devices[:1])
        model, params = loader.get_model(config, mesh, shard=False)
        params["layers"] = model.serving_layout(params["layers"])
        return TPUModelRunner(config, mesh, model=model,
                              params=on_chip(params, mesh))

    return build


def on_chip(tree, mesh):
    """``tree``'s shapes, whole on the one chip of ``mesh``."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    chip = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        tree)


def slab_copies(compiled, slab: str) -> list:
    """Instructions of the compiled program that MAKE a buffer of an
    expert slab's shape: anything but the parameter that brings it in
    (and the views of it that move nothing)."""
    made = re.compile(rf"= {re.escape(slab)}(?:{{[^}}]*}})? (\S+?)\(")
    return [op for op in made.findall(compiled.as_text())
            if op not in ("parameter", "bitcast", "get-tuple-element")]


def expert_slab(cf: dict) -> tuple:
    """(shape string, bytes) of one layer's gate or up projection."""
    E = (cf.get("num_experts") or cf.get("n_routed_experts")
         or cf["moe_num_primary_experts"])
    H = cf["hidden_size"]
    I = (cf.get("moe_intermediate_size") or cf.get("intermediate_size")
         or cf["moe_ffn_hidden_size"])
    return f"bf16[{E},{H},{I}]", E * H * I * 2


def last_layer(runner) -> dict:
    """The last layer's weights as shapes on the runner's chip."""
    return on_chip(
        {k: v[-1] if isinstance(v, tuple) else sds(v.shape[1:], v.dtype)
         for k, v in runner.params["layers"].items()}, runner.mesh)


@pytest.mark.parametrize("rows", [64, 512 + 8])
@pytest.mark.parametrize("name", SPARSE)
def test_grouped_gemms_read_their_weights_in_place(abstract_runner, name,
                                                   rows):
    """One expert layer of a sparse configuration at its published
    widths, the layer's weights arguments of the program, at a decode
    batch and at the 512-token bucket: the three ``ragged_dot`` compiled
    for the chip make no copy of a slab and hold a fraction of one in
    temporaries (the CPU lowering at toy widths,
    tests/models/test_expert_layout.py, cannot show the TPU
    compiler's). The largest bucket's are compiled inside
    ``test_one_layer_forward_compiles`` (16 s a case alone)."""
    runner = abstract_runner(name)
    model, cf = runner.model, bench_config(name)
    x = sds((rows, cf["hidden_size"]), jnp.bfloat16)
    with runner.mesh:
        compiled = jax.jit(model.mlp_block).lower(
            last_layer(runner), on_chip(x, runner.mesh)).compile()
    slab, slab_bytes = expert_slab(cf)
    text = compiled.as_text()
    assert text.count("ragged-dot") >= 3
    assert ragged_dot_rows(compiled) == {dispatch_rows(model, rows)}
    assert slab in text                          # it is an argument
    assert slab_copies(compiled, slab) == []
    assert compiled.memory_analysis().temp_size_in_bytes < slab_bytes / 4


def ragged_dot_rows(compiled) -> set:
    """Row counts of the compiled program's grouped GEMMs."""
    return {int(rows) for rows in re.findall(
        r"%ragged-dot-none\S* = \w+\[(\d+),", compiled.as_text())}


def dispatch_rows(model, T: int) -> int:
    """Rows the model's dispatch hands ``ragged_dot`` at ``T`` tokens:
    the whole-expert branch pads its sorted picks (``padded_picks``), a
    share's works a pass at a time (``share_capacity``)."""
    from vllm_distributed_tpu.models.mixtral import padded_picks
    k = model.cfg.num_experts_per_tok
    if getattr(model.cfg, "experts_held", None) is not None:
        return model.share_capacity(T, k)
    return padded_picks(T * k, model.num_physical)


WHOLE_EXPERT = ("olmoe-1b-7b", "zaya1-8b", "smallthinker-21b-a3b")


@pytest.mark.parametrize("name", WHOLE_EXPERT)
def test_a_decode_step_s_grouped_gemms_take_the_rule_s_rows(
        abstract_runner, name):
    """One expert layer's dispatch at published widths at the cell's
    decode batch (its sequences' token bucket plus the attention
    kernel's 8 rows), compiled for the chip: every ``ragged-dot`` has
    ``padded_picks``' rows. The 2 048-token bucket's are read off
    ``test_one_layer_forward_compiles``' programs, the shares' off
    those and ``test_grouped_gemms_read_their_weights_in_place``'s."""
    from vllm_distributed_tpu.ops.pallas_attention import Q_TILE_PAD
    runner = abstract_runner(name)
    model, c = runner.model, runner.model.cfg
    T = pad_to_bucket(bench_config(name)["engine_args"]["max_num_seqs"],
                      TOKEN_BUCKETS) + Q_TILE_PAD
    k = c.num_experts_per_tok
    args = (sds((T, c.hidden_size), jnp.bfloat16), sds((T, k)),
            sds((T, k), jnp.float32))
    with runner.mesh:
        compiled = jax.jit(model.moe_dispatch).lower(
            last_layer(runner), *on_chip(args, runner.mesh)).compile()
    assert ragged_dot_rows(compiled) == {dispatch_rows(model, T)}


def forward_lowered(runner, t_bucket: int):
    """(the runner's forward program lowered at ``t_bucket`` for the
    described chip, its padded token rows)."""
    _, shapes, shardings = runner._cache_layout(1024)
    caches = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        shapes, shardings)
    runner._build_step_fn()
    T, max_q, G = runner._batch_shape(t_bucket, 2)
    inputs = on_chip(runner._dummy_step_inputs(T, max_q, G), runner.mesh)
    with runner.mesh:
        return runner._forward_fn.lower(runner.params, caches, inputs), T


@pytest.mark.parametrize("name,t_bucket", [
    (name, t) for name in ONE_LAYER
    for t in (TOKEN_BUCKETS[0], TOKEN_BUCKETS[-1])
    # 20 s alone, and the file has 150 s: its latent kernel compiles at
    # this bucket above, and olmoe-1b-7b's grouped GEMMs here.
    if (name, t) != ("gigachat3-702b-ep16", TOKEN_BUCKETS[-1])])
def test_one_layer_forward_compiles(abstract_runner, name, t_bucket):
    """The runner's own forward program (packed step inputs, attention
    kernel, the layer loop, the expert switch) of each benchmark
    configuration at its published widths, at the smallest and the
    largest token bucket."""
    runner = abstract_runner(name)
    lowered, T = forward_lowered(runner, t_bucket)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    if name in SPARSE + WHOLE_EXPERT:
        # The grouped GEMMs' rows are the dispatch's rule's, at the
        # 2 048-token bucket a count in tiles of 128 rows or more.
        assert ragged_dot_rows(compiled) == {dispatch_rows(runner.model, T)}
    if name in SPARSE or name == "smallthinker-21b-a3b":
        slab, _ = expert_slab(bench_config(name))
        assert slab in compiled.as_text()
        assert slab_copies(compiled, slab) == []
    if name == "smallthinker-21b-a3b":
        # Two layers through the padded launcher (one call a layer) and
        # their grouped GEMMs; the attention matrices come in a buffer a
        # layer (XLA may prefetch one to the fast memory and back, as it
        # does ZAYA's stacks: no copy in HBM).
        text = compiled.as_text()
        assert text.count("tpu_custom_call") >= 2
        assert text.count("ragged-dot") >= 6
        assert "bf16[2,2560,3584]" not in text.split("\n", 1)[0]
    if name == "granite-4.0-h-micro":
        # The state rows are updated in place (18 mamba layers x 57 rows
        # of 2 MiB here), and the step holds no state per token.
        assert slab_copies(compiled, "f32[18,57,128,4096]") == []
        text = compiled.as_text()
        assert not re.search(r"= f32\[(1,)?57,128,4096\]\S* "
                             r"(copy|dynamic-slice|dynamic-update-slice)\(",
                             text)
        assert text.count(" while(") == 1
        assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * 2 ** 30
    if name == "granite-4.0-h-small-ep2":
        # Both layers' grouped GEMMs have the share's rows (640 picks
        # worked on whole at the decode bucket, 15 552 of 20 560 at the
        # largest), read the 36 held experts' slabs in place, and the
        # state rows (4 MiB a layer) are updated in place.
        text = compiled.as_text()
        assert ragged_dot_rows(compiled) == {dispatch_rows(runner.model, T)}
        assert text.count("ragged-dot") >= 6
        for slab in ("bf16[36,4096,768]", "bf16[36,768,4096]"):
            assert slab in text.split("\n", 1)[0]
            assert slab_copies(compiled, slab) == []
        S = bench_config(name)["engine_args"]["max_num_seqs"]
        assert slab_copies(compiled, f"f32[1,{S + 1},128,8192]") == []
        assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * 2 ** 30
    if name == "zaya1-8b":
        # The carried rows (u; c0 and vb widened to twelve tiles) come
        # in as they are written and read, not with the layers on the
        # sublanes as ten and eleven 128-lane tiles would
        # (ops/conv1d.rows_shape); the experts' slabs are arguments that
        # nothing makes again; the layers are one loop; every layer's
        # kernels are there (two passes over the rows, either list).
        text = compiled.as_text()
        head = text.split("\n", 1)[0]
        assert "bf16[2,65,1,2,640]{4,3,2,1,0:T(2,128)" in head
        assert "bf16[2,65,1,4,384]{4,3,2,1,0:T(4,128)" in head
        # (The 2 048-token program may move a stack of a few hundred KB
        # to the fast memory and back, XLA's own prefetch: no re-layout.)
        for stack in ("bf16[2,65,1,2,640]", "bf16[2,65,1,4,384]"):
            assert set(slab_copies(compiled, stack)) <= {
                "copy-start", "copy-done"}, stack
        assert slab_copies(compiled, expert_slab(bench_config(name))[0]) \
            == []
        assert text.count(" while(") == 1
        assert text.count("%conv_rows") >= 4
        assert text.count("ragged-dot") >= 3
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 28
    if name == "brumby-14b":
        # Both kernels at either bucket (a step that may hold a segment
        # of two tokens has the chunked form); the state rows (2 layers
        # x 17 rows x 8 K/V heads of 65 tiles of 128 x 128) are an
        # argument that nothing makes again, the layers one loop.
        from vllm_distributed_tpu.ops import power_retention
        text = compiled.as_text()
        assert f"%{power_retention.KERNEL_RECURRENT}" in text
        assert f"%{power_retention.KERNEL_CHUNK}" in text
        assert "f32[2,17,8,65,128,128]" in text.split("\n", 1)[0]
        assert slab_copies(compiled, "f32[2,17,8,65,128,128]") == []
        # (XLA may take the normaliser, 10 MB, to the fast memory and
        # back, its own prefetch: no re-layout, which 65 rows a K/V head
        # in place of 72 brought, a copy either side of every call.)
        assert set(slab_copies(compiled, "f32[2,17,8,72,128]")) <= {
            "custom-call", "copy-start", "copy-done"}
        assert text.count(" while(") == 1
    # The convolution's carried inputs are replaced in place too: no
    # copy of a stack, no layer of one as a temporary.
    for stack, layer in {
            "granite-4.0-h-micro": [("18,57,3,32,128", "57,3,32,128"),
                                    ("18,57,3,2,128", "57,3,2,128")],
            "ling3-flash-ep4": [("2,97,3,96,128", "97,3,96,128")],
    }.get(name, []):
        assert f"bf16[{stack}]" in compiled.as_text()    # an argument
        assert slab_copies(compiled, f"bf16[{stack}]") == []
        assert not re.search(
            rf"= bf16\[(1,)?{layer}\]\S* "
            r"(copy|dynamic-slice|dynamic-update-slice|fusion)\(",
            compiled.as_text())


def test_share_dispatch_holds_no_array_of_all_the_picks(abstract_runner):
    """The 2 048-token forward at the GigaChat cell's widths (16 of 256
    experts held, 8 picks a token): nothing in the compiled program has
    `[T x k, hidden]` or `[T x k, moe_intermediate]` rows, the shapes
    the share's dispatch worked on until PR 40 (250 MB and 71 MB each,
    for about 1 100 picks held); its passes are one loop around the
    three grouped GEMMs, whose rows are `share_capacity`'s."""
    name = "gigachat3-702b-ep16"
    runner = abstract_runner(name)
    cf = bench_config(name)
    lowered, T = forward_lowered(runner, TOKEN_BUCKETS[-1])
    compiled = lowered.compile()
    text = compiled.as_text()
    picks = T * cf["num_experts_per_tok"]
    C = runner.model.share_capacity(T, cf["num_experts_per_tok"])
    assert T >= 2048 and C < picks / 8
    for width in (cf["hidden_size"], cf["moe_intermediate_size"]):
        assert not re.search(rf"\[{picks},{width}\]", text), (picks, width)
        assert re.search(rf"bf16\[{C},{width}\]", text), (C, width)
    assert text.count("ragged-dot") >= 3
    slab, _ = expert_slab(cf)
    assert slab_copies(compiled, slab) == []


# sha256 of OLMoE's 64-token forward as lowered (StableHLO text without
# source locations and without the attention kernel's Mosaic payload,
# which carries its file's path). A PR that means to change that program
# records the new digest here and says so. PR 48 did: the whole-expert
# dispatch selects away every row in no group (at this bucket's 576 rows
# the padding is none; 8d0bf2246de138f9... from PR 40's parent until
# then).
OLMOE_FORWARD_64 = (
    "968400df0b5be3b4af56897d4111b84df027154bd39598d79db4a8c0ef40cc94")


def test_a_model_without_a_share_lowers_to_the_same_program(
        abstract_runner):
    """A PR that changes the dispatch of an expert SHARE only (PR 40),
    or nothing of the Mixtral dispatch, leaves the program of a
    configuration without a share (OLMoE) as it was, instruction for
    instruction."""
    import hashlib
    lowered, _ = forward_lowered(abstract_runner("olmoe-1b-7b"), 64)
    text = re.sub(r'loc\([^)]*\)|^#loc.*$|\\22body\\22: \\22[^\\]*\\22',
                  "", lowered.as_text(), flags=re.M)
    assert "ragged_dot" in text and "tpu_custom_call" in text
    assert hashlib.sha256(text.encode()).hexdigest() == OLMOE_FORWARD_64
