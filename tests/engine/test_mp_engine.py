"""Engine core in a subprocess: parity, shutdown, and death handling
(reference: vllm/v1/engine/core.py:362 EngineCoreProc,
tests/v1/shutdown/)."""

import os
import time

import pytest

from tests.engine.test_llm_engine import (checkpoint, hf_greedy,  # noqa: F401
                                          make_engine, run_engine)
from vllm_distributed_tpu.engine.core_client import EngineDeadError
from vllm_distributed_tpu.sampling_params import SamplingParams


@pytest.fixture()
def mp_env(monkeypatch):
    # The spawned child inherits JAX_PLATFORMS=cpu from conftest.py.
    monkeypatch.setenv("VDT_RPC_TIMEOUT", "300")


def test_mp_engine_parity_and_shutdown(checkpoint, mp_env):
    path, hf = checkpoint
    engine = make_engine(path, multiprocess_engine_core=True)
    try:
        proc = engine.engine_core.proc
        assert proc.is_alive()
        prompts = [[3, 17, 92, 45, 8], [5, 9, 101], [120, 44]]
        sps = [SamplingParams(temperature=0.0, max_tokens=6,
                              ignore_eos=True) for _ in prompts]
        outs = run_engine(engine, prompts, sps)
        for prompt, out in zip(prompts, outs):
            assert out.outputs[0].token_ids == hf_greedy(hf, prompt, 6), \
                f"mp-engine mismatch for prompt {prompt}"
        # Utility RPC round-trip.
        stats = engine.get_stats()
        assert isinstance(stats, dict) and "hits" in stats
    finally:
        engine.shutdown()
    deadline = time.time() + 10
    while proc.is_alive() and time.time() < deadline:
        time.sleep(0.1)
    assert not proc.is_alive(), "engine core proc must exit on shutdown"


def test_mp_engine_dead_raises(checkpoint, mp_env):
    path, _ = checkpoint
    engine = make_engine(path, multiprocess_engine_core=True)
    try:
        proc = engine.engine_core.proc
        proc.kill()
        proc.join(timeout=10)
        with pytest.raises(EngineDeadError):
            engine.add_request("r0", [3, 4, 5],
                               SamplingParams(temperature=0.0,
                                              max_tokens=4))
            for _ in range(50):
                engine.step()
    finally:
        try:
            engine.shutdown()
        except Exception:
            pass
