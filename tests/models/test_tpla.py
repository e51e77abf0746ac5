"""TPLA: tensor-parallel latent attention (ops/mla.py, PAPERS.md
"TPLA") — the MLA latent cache shards over the TP axis so per-rank
latent-pool bytes drop ~TP-fold. These tests pin the acceptance
criteria: greedy outputs token-identical to the replicated layout on
2- and 4-device CPU meshes (XLA scan and interpret-mode Pallas
backends), per-rank capacity scaling ~TP x at a fixed HBM budget
through the worker's real accounting path, wholesale VDT_TPLA=0
revert, and the KV-transfer latent wire format round-tripping between
meshes of DIFFERENT TP degree bit-exactly (shared_storage raw files)."""

import os

import pytest
from transformers import DeepseekV2Config

from vllm_distributed_tpu.config import (CacheConfig, EngineConfig,
                                         KVTransferConfig, LoadConfig,
                                         ModelConfig, ParallelConfig,
                                         SchedulerConfig)
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.sampling_params import SamplingParams

PROMPTS = [
    [3, 17, 92, 45, 8],
    [5, 9, 33, 71],
    [11, 12, 13, 14, 15, 16, 17, 18, 19],
]


def _hf_config():
    # kv_lora_rank divisible by 4 so TP {2, 4} both shard evenly; rope
    # dim kept small so the replicated "pe" sidecar stays a minor cost
    # (the capacity ratio approaches TP x as Lkv/R grows, like the real
    # DeepSeek 512/64 geometry).
    return DeepseekV2Config(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=48, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, q_lora_rank=None,
        kv_lora_rank=64, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_routed_experts=4, num_experts_per_tok=2,
        n_shared_experts=1, first_k_dense_replace=1,
        routed_scaling_factor=1.0, topk_method="greedy", n_group=1,
        topk_group=1, norm_topk_prob=False, max_position_embeddings=64,
        eos_token_id=1, head_dim=8,
        architectures=["DeepseekV2ForCausalLM"])


def make_config(tp=1, tpla=True, storage=None, role=None,
                num_blocks=64) -> EngineConfig:
    os.environ["VDT_TPLA"] = "1" if tpla else "0"
    mc = ModelConfig(model="dummy-dsv2-tpla", dtype="float32",
                     max_model_len=64, skip_tokenizer_init=True)
    mc.hf_config = _hf_config()
    cfg = EngineConfig(
        model_config=mc,
        cache_config=CacheConfig(block_size=4, num_gpu_blocks=num_blocks),
        scheduler_config=SchedulerConfig(max_num_batched_tokens=64,
                                         max_num_seqs=8,
                                         max_model_len=64),
        parallel_config=ParallelConfig(tensor_parallel_size=tp),
        load_config=LoadConfig(load_format="dummy"),
    )
    if storage is not None:
        cfg.kv_transfer_config = KVTransferConfig(
            kv_connector="SharedStorageConnector", kv_role=role,
            kv_connector_extra_config={"shared_storage_path": storage})
    return cfg


def make_engine(**kw) -> LLMEngine:
    return LLMEngine(make_config(**kw), load_tokenizer=False)


def run(engine, tag, max_tokens=8, shutdown=True):
    sp = SamplingParams(temperature=0.0, max_tokens=max_tokens,
                        ignore_eos=True)
    for i, p in enumerate(PROMPTS):
        engine.add_request(f"{tag}-{i}", list(p), sp)
    done = {}
    for _ in range(300):
        for out in engine.step():
            if out.finished:
                done[out.request_id] = list(out.outputs[0].token_ids)
        if not engine.has_unfinished_requests():
            break
    assert len(done) == len(PROMPTS)
    out = [done[f"{tag}-{i}"] for i in range(len(PROMPTS))]
    if shutdown:
        engine.shutdown()
    return out


def _runner(engine):
    return engine.engine_core.engine_core.executor.worker.model_runner


@pytest.fixture(autouse=True)
def _restore_tpla_env():
    saved = os.environ.get("VDT_TPLA")
    yield
    if saved is None:
        os.environ.pop("VDT_TPLA", None)
    else:
        os.environ["VDT_TPLA"] = saved


@pytest.fixture(scope="module")
def engines():
    """``engines(tp, tpla)``: the engine of that layout on the default
    (XLA) backend with no connector, built at its first use and kept to
    the module's end. An engine built is what a test here costs (its
    programs compiled again: JAX caches by function object), and the
    layout is fixed when the model is loaded, so tests that want the
    same one share it, run with ``shutdown=False`` and read nothing the
    other left (counters, which pages are cached). A test on another
    backend, with a quantized plane or with a connector builds its
    own."""
    built = {}

    def get(tp, tpla=True):
        if (tp, tpla) not in built:
            built[tp, tpla] = make_engine(tp=tp, tpla=tpla)
        return built[tp, tpla]

    yield get
    for engine in built.values():
        engine.shutdown()


@pytest.fixture(scope="module")
def baseline_tokens(engines):
    """TP=1 replicated-layout greedy outputs (the parity reference; the
    dummy loader's seeded init gives every engine of this config
    identical weights)."""
    return run(engines(1), "base", shutdown=False)


# ---------------------------------------------------------------------------
# Parity matrix: TPLA-sharded vs replicated, 2- and 4-device meshes,
# XLA scan and interpret-mode Pallas.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("tp", [2, 4])
def test_tpla_token_identical_xla(baseline_tokens, engines, tp,
                                  monkeypatch):
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", "xla")
    engine = engines(tp)
    assert _runner(engine).model.tpla_shards == tp
    assert set(_runner(engine).kv_caches) == {"c", "pe"}
    assert run(engine, f"tpla{tp}", shutdown=False) == baseline_tokens


@pytest.mark.parametrize("tp", [2, 4])
def test_tpla_token_identical_pallas_interpret(baseline_tokens, tp,
                                               monkeypatch):
    # conftest sets VDT_PALLAS_INTERPRET=1; forcing the pallas backend
    # exercises the TPLA dispatch the real TPU path takes.
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", "pallas")
    assert run(make_engine(tp=tp), f"tplap{tp}") == baseline_tokens


def test_replicated_pallas_kernel_still_token_identical(baseline_tokens,
                                                        monkeypatch):
    # VDT_TPLA=0 on the pallas backend keeps the per-rank latent KERNEL
    # serving the replicated cache — the revert leg of the matrix.
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", "pallas")
    assert run(make_engine(tp=2, tpla=False), "repl2p") == baseline_tokens


def test_tpla_combine_rides_quantized_plane(baseline_tokens,
                                            monkeypatch):
    """VDT_QCOMM path "tpla" quantizes the per-layer W_UV output
    combine (greedy token parity at block 16, like the tknp/tp paths'
    e2e gates) and the trace counters record its savings. The score
    psum stays exact by design, so parity holds at toy scale."""
    from vllm_distributed_tpu.parallel import collectives
    monkeypatch.setenv("VDT_QCOMM", "1")
    monkeypatch.setenv("VDT_QCOMM_PATHS", "tpla")
    monkeypatch.setenv("VDT_QCOMM_BLOCK", "16")
    collectives.refresh()
    collectives.reset_counters()
    try:
        assert run(make_engine(tp=2), "qtpla") == baseline_tokens
        assert collectives.traced_snapshot()["bytes_saved"].get(
            "tpla", 0) > 0
    finally:
        collectives.refresh()


# ---------------------------------------------------------------------------
# VDT_TPLA=0 reverts wholesale to the replicated layout.
# ---------------------------------------------------------------------------
def test_tpla_off_reverts_to_replicated_layout(baseline_tokens, engines):
    engine = engines(2, tpla=False)
    runner = _runner(engine)
    assert runner.model.tpla_shards == 1
    assert set(runner.kv_caches) == {"c"}  # no rope sidecar
    from jax.sharding import PartitionSpec as P
    from vllm_distributed_tpu.config import MESH_AXIS_TOKEN
    assert runner.model.kv_cache_specs() == {
        "c": P(None, MESH_AXIS_TOKEN, None, None)}
    assert run(engine, "repl2", shutdown=False) == baseline_tokens


def test_tpla_falls_back_when_lkv_indivisible():
    # kv_lora_rank=64 does not divide 3 ways... the loader cannot see a
    # TP=3 mesh on this 8-device pool (2x4 factorization only), so pin
    # the indivisible case directly at the arch level.
    from vllm_distributed_tpu.ops.mla import tpla_applicable
    assert not tpla_applicable(30, 4)
    assert tpla_applicable(64, 4)


# ---------------------------------------------------------------------------
# Capacity: per-rank latent pool page count scales ~TP x at a fixed HBM
# budget, through the worker's real sizing path and the new gauges.
# ---------------------------------------------------------------------------
def test_latent_pool_capacity_scales_with_tp(monkeypatch):
    from vllm_distributed_tpu.worker.worker import TPUWorker

    budget = 1 << 20  # 1 MiB fixed per-device HBM budget

    def pages_for(tp, tpla):
        cfg = make_config(tp=tp, tpla=tpla)
        cfg.cache_config.num_gpu_blocks_override = None
        worker = TPUWorker(cfg)
        worker.init_device()
        worker.load_model()
        monkeypatch.setattr(worker.model_runner, "profile_memory_bytes",
                            lambda: budget)
        return worker.determine_num_available_blocks()

    pages_repl = pages_for(2, False)
    pages_tpla2 = pages_for(2, True)
    pages_tpla4 = pages_for(4, True)
    # Geometry: replicated row = 64 + 8 = 72 lanes/page/rank; TPLA(2) =
    # 32 + 8 = 40; TPLA(4) = 16 + 8 = 24. The rope sidecar is the
    # replicated remainder, so the scaling is ~TP x, not exactly TP x.
    assert pages_tpla2 >= int(1.7 * pages_repl)
    assert pages_tpla4 >= int(2.8 * pages_repl)
    assert pages_tpla4 == budget // (3 * 4 * 24 * 4)  # L*PS*lanes*f32


def test_tpla_gauges_flow_to_metrics(engines):
    from vllm_distributed_tpu.metrics.prometheus import render_metrics

    stats = engines(2).get_stats()
    workers = stats.get("workers") or {}
    assert workers, "worker telemetry map missing from stats"
    entry = next(iter(workers.values()))
    assert entry["tpla_latent_shards"] == 2
    # 3 layers x page_size 4 x (32 + 8) lanes x 4 bytes.
    assert entry["mla_latent_page_bytes"] == 3 * 4 * 40 * 4
    text = render_metrics(stats)
    assert "vdt:tpla_latent_shards{" in text
    assert "vdt:mla_latent_page_bytes{" in text
    assert 'vdt:kv_blocks{state="free"}' in text


# ---------------------------------------------------------------------------
# KV transfer: latent pages round-trip between meshes of DIFFERENT TP
# degree (TP=1 replicated producer -> TP=2 TPLA consumer) bit-exactly.
# ---------------------------------------------------------------------------
def test_latent_pages_transfer_across_tp_degrees(tmp_path,
                                                 baseline_tokens):
    storage = str(tmp_path / "kv")

    producer = make_engine(tp=1, storage=storage, role="kv_producer")
    assert run(producer, "prod") == baseline_tokens
    assert os.listdir(storage), "producer wrote no latent page files"

    consumer = make_engine(tp=2, tpla=True, storage=storage,
                           role="kv_consumer")
    core = consumer.engine_core.engine_core
    wc = core.executor.worker.model_runner.kv_connector
    out = run(consumer, "cons", shutdown=False)
    try:
        # Identical greedy continuations prove the externally-loaded
        # latent pages decoded bit-exactly into the TPLA-sharded cache
        # (raw wire format; VDT_QCOMM default off).
        assert out == baseline_tokens
        assert wc.num_pages_loaded > 0
    finally:
        consumer.shutdown()


def test_check_latent_wire_rejects_foreign_stores():
    """A same-geometry-but-deeper (or geometry-foreign) latent store
    must be REJECTED before any scatter — truncating another model's
    layer stack into the cache would be silent corruption."""
    import numpy as np

    from vllm_distributed_tpu.distributed.kv_transfer.page_io import \
        check_latent_wire

    class _Cfg:
        mla = True
        kv_lora_rank = 64
        qk_rope_head_dim = 8
        tpla_shards = 2
        num_layers = 3

    class _Runner:
        class model:
            cfg = _Cfg()

    r = _Runner()
    k = np.zeros((3, 2, 4, 64), np.float32)
    v = np.zeros((3, 2, 4, 8), np.float32)
    check_latent_wire(r, k, v)  # exact layout: accepted
    with pytest.raises(RuntimeError):  # deeper producer stack
        check_latent_wire(r, np.zeros((4, 2, 4, 64), np.float32),
                          np.zeros((4, 2, 4, 8), np.float32))
    with pytest.raises(RuntimeError):  # foreign latent width
        check_latent_wire(r, np.zeros((3, 2, 4, 32), np.float32), v)
    with pytest.raises(RuntimeError):  # meta disagrees with the model
        check_latent_wire(r, k, v, {"kv_lora_rank": 32, "rope_dim": 8})


@pytest.mark.parametrize("tp,tpla", [(2, True), (1, True), (2, False)])
def test_latent_stage_and_chunked_scatter_roundtrip(engines, tp, tpla):
    """The dcn_pull staging path (stage_pages -> donated
    scatter_pages_chunk) must round-trip latent pages bit-exactly in
    every layout: TPLA-sharded, TP=1 replicated, and TP>1 replicated
    (VDT_TPLA=0)."""
    import jax.numpy as jnp
    import numpy as np

    from vllm_distributed_tpu.distributed.kv_transfer import page_io

    engine = engines(tp, tpla)
    runner = _runner(engine)
    run(engine, f"warm{tp}{tpla}", shutdown=False)  # populate pages
    page_ids = [0, 1, 2]
    k_w, v_w = page_io.gather_pages(runner, page_ids)
    assert k_w.shape[-1] == 64 and v_w.shape[-1] == 8
    assert np.abs(k_w).max() > 0
    # Wipe the pages, then restore them through the staged +
    # chunked donated-scatter path the async pull uses (the engine
    # gets back what it held).
    for key, arr in list(runner.kv_caches.items()):
        runner.kv_caches[key] = arr.at[:, jnp.asarray(page_ids)].set(0)
    k_dev, v_dev = page_io.stage_pages(runner, k_w, v_w)
    page_io.scatter_pages_chunk(runner, page_ids, k_dev, v_dev,
                                lo=0, chunk=2)
    page_io.scatter_pages_chunk(runner, page_ids, k_dev, v_dev,
                                lo=2, chunk=2)
    k_2, v_2 = page_io.gather_pages(runner, page_ids)
    assert np.array_equal(k_2, k_w)
    assert np.array_equal(v_2, v_w)


def test_tpla_producer_feeds_replicated_consumer(tmp_path,
                                                 baseline_tokens):
    # The reverse asymmetry: a TPLA-sharded engine gathers FULL rows
    # into the store; a TP=1 replicated engine re-slices on receipt.
    storage = str(tmp_path / "kv")
    producer = make_engine(tp=2, tpla=True, storage=storage,
                           role="kv_producer")
    assert run(producer, "prod2") == baseline_tokens
    consumer = make_engine(tp=1, storage=storage, role="kv_consumer")
    core = consumer.engine_core.engine_core
    wc = core.executor.worker.model_runner.kv_connector
    out = run(consumer, "cons1", shutdown=False)
    try:
        assert out == baseline_tokens
        assert wc.num_pages_loaded > 0
    finally:
        consumer.shutdown()
