"""vdt:recompiles_total enforces the _compiled_shapes contract.

The recompile guard used to be a comment + log line; the telemetry
plane turns it into a counter an alert (and this tier-1 test) can
watch: after ``precompile()`` a steady-state decode loop must report
ZERO recompiles, and traffic over a deliberately un-warmed lattice
must report more than zero — through the full stats path (runner ->
worker label -> engine get_stats -> /metrics rendering)."""

import numpy as np

from tests.engine.test_llm_engine import (PALLAS_GEOMETRY,  # noqa: F401
                                          checkpoint, make_engine)
from vllm_distributed_tpu.metrics.prometheus import render_metrics
from vllm_distributed_tpu.sampling_params import SamplingParams


def _runner(engine):
    return engine.engine_core.executor.worker.model_runner


def _run_traffic(engine, n_prompts=4, max_tokens=6):
    rng = np.random.default_rng(7)
    prompts = [[int(x) for x in rng.integers(2, 127, size=n)]
               for n in (3, 9, 5, 12)][:n_prompts]
    for i, p in enumerate(prompts):
        engine.add_request(f"rg{i}", p,
                           SamplingParams(temperature=0.0,
                                          max_tokens=max_tokens,
                                          ignore_eos=True))
    for _ in range(200):
        engine.step()
        if not engine.has_unfinished_requests():
            break
    assert not engine.has_unfinished_requests()


def test_steady_state_decode_reports_zero_recompiles(checkpoint,
                                                     monkeypatch):
    monkeypatch.setenv("VDT_PRECOMPILE", "1")
    path, _ = checkpoint
    engine = make_engine(path, max_num_batched_tokens=16, max_num_seqs=4)
    assert _runner(engine)._precompiled
    _run_traffic(engine)
    stats = engine.get_stats()
    assert stats["num_recompiles"] == 0
    # The labeled per-worker series flows up the same stats RPC and
    # renders on /metrics.
    workers = stats["workers"]
    [(label, per)] = workers.items()
    assert label == "dp0-h0"
    assert per["num_recompiles"] == 0
    # Device-wait telemetry rode along: the runner blocked on at least
    # one device fetch during decode.
    assert per["device_wait_seconds"]["count"] > 0
    # The other telemetry legs ride the same get_stats poll: the
    # core's transport snapshot (empty — no connector configured) and
    # the scheduler's block-pool introspection.
    assert stats["transport"] == {"kv": {}, "shm": {},
                                  "shm_lag_chunks": 0, "qcomm": {}}
    kv = stats["kv_cache"]
    assert kv["total_blocks"] == 128
    assert kv["free_blocks"] + kv["used_blocks"] == kv["total_blocks"]
    text = render_metrics(stats)
    assert 'vdt:recompiles_total{worker="dp0-h0"} 0.0' in text
    assert 'vdt:kv_blocks{state="free"}' in text


def test_mixed_wave_zero_recompiles_after_precompile(checkpoint,
                                                     monkeypatch):
    """ROADMAP item #1's acceptance test: after precompile(), a wave
    mixing a chunked-prefill chunk with running decodes must trigger 0
    recompiles — the mega-kernel batch shape carries the composition in
    the partition descriptor, not in any static."""
    monkeypatch.setenv("VDT_PRECOMPILE", "1")
    monkeypatch.setenv("VDT_ASSERT_NO_RECOMPILE", "1")
    path, _ = checkpoint
    engine = make_engine(path, max_num_batched_tokens=16, max_num_seqs=4)
    runner = _runner(engine)
    assert runner._precompiled
    rng = np.random.default_rng(11)
    # Two short prompts reach decode first; then a 40-token prompt
    # chunk-prefills across >= 3 waves (budget 16) while they decode,
    # so several waves mix a prefill chunk with running decode rows.
    for i in range(2):
        engine.add_request(
            f"mx{i}", [int(x) for x in rng.integers(2, 127, size=3)],
            SamplingParams(temperature=0.0, max_tokens=14,
                           ignore_eos=True))
    for _ in range(3):
        engine.step()
    engine.add_request(
        "mx-long", [int(x) for x in rng.integers(2, 127, size=40)],
        SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True))
    for _ in range(200):
        engine.step()  # raises RuntimeError on any post-warmup compile
        if not engine.has_unfinished_requests():
            break
    assert not engine.has_unfinished_requests()
    stats = engine.get_stats()
    assert stats["num_recompiles"] == 0
    assert stats["workers"]["dp0-h0"]["num_recompiles"] == 0
    # The warmed lattice is itself observable (and collapsed: one
    # forward graph per token bucket — see test_precompile).
    assert stats["precompile_graphs"] > 0
    assert (f'vdt:precompile_graphs_total '
            f'{float(stats["precompile_graphs"])}'
            in render_metrics(stats))


def test_windowed_model_reaches_unified_kernel(tmp_path_factory,
                                               monkeypatch):
    """ISSUE 11 acceptance: sliding-window waves no longer increment the
    XLA-fallback counter — the window rides the mega-kernel's per-layer
    statics, so a Mistral-class model's every wave dispatches
    unified. (The mixed waves of a model without a window:
    ``tests/engine/test_llm_engine.py``, on that file's Pallas engine.)"""
    import torch
    from transformers import MistralConfig, MistralForCausalLM
    monkeypatch.setenv("VDT_ATTENTION_BACKEND", "pallas")
    torch.manual_seed(0)
    cfg = MistralConfig(vocab_size=128, hidden_size=64,
                        intermediate_size=128, num_hidden_layers=2,
                        num_attention_heads=4, num_key_value_heads=2,
                        sliding_window=6, max_position_embeddings=64,
                        eos_token_id=1)
    path = tmp_path_factory.mktemp("tiny_mistral_rg")
    MistralForCausalLM(cfg).save_pretrained(path,
                                            safe_serialization=True)
    engine = make_engine(str(path), max_num_batched_tokens=16,
                         max_num_seqs=4, **PALLAS_GEOMETRY)
    _run_traffic(engine, max_tokens=8)
    stats = engine.get_stats()
    calls = stats["attn_kernel_calls"]
    assert calls.get("unified", 0) > 0
    assert "naive" not in calls and "general" not in calls


def test_unwarmed_shape_reports_recompiles(checkpoint, monkeypatch):
    """An empty warm-up set marked as precompiled: every compile the
    traffic triggers is, by the guard's contract, a recompile — the
    counter must say so."""
    monkeypatch.setenv("VDT_PRECOMPILE", "0")
    path, _ = checkpoint
    engine = make_engine(path, max_num_batched_tokens=16, max_num_seqs=4)
    runner = _runner(engine)
    assert not runner._precompiled
    runner._precompiled = True  # deliberately un-warmed lattice
    _run_traffic(engine)
    stats = engine.get_stats()
    assert stats["num_recompiles"] > 0
    assert stats["workers"]["dp0-h0"]["num_recompiles"] > 0
    text = render_metrics(stats)
    assert 'vdt:recompiles_total{worker="dp0-h0"}' in text
