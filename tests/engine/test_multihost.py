"""Multi-host SPMD scaffold: two controller processes, one global mesh.

Reference boundary: the multi-node executors
(v1/executor/multiproc_executor.py:42, ray_distributed_executor.py) with
their StatelessProcessGroup bootstrap (distributed/utils.py:138). JAX
analogue validated here: each host process calls
``jax.distributed.initialize`` (the worker does it from
ParallelConfig.num_hosts/host_rank/coordinator_address), after which
``jax.devices()`` spans both processes and one engine step executes SPMD
across them — the same multi-controller layout a v5e pod uses, here with
2 processes x 4 virtual CPU devices.
"""

import functools
import os
import subprocess
import sys

import pytest

from vllm_distributed_tpu.utils import get_open_port

# Capability probe: some jax builds cannot run multi-controller
# computations on the CPU backend at all ("Multiprocess computations
# aren't implemented on the CPU backend" during the cross-process
# device_put model load). Probing once with a minimal 2-process
# sharded computation keeps tier-1 signal clean on such containers —
# a known-red environment skips instead of failing every SPMD test.
_PROBE = r"""
import os, sys
rank = int(sys.argv[1]); port = sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import numpy as np
import jax, jax.numpy as jnp
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=rank)
from jax.sharding import Mesh, NamedSharding, PartitionSpec
mesh = Mesh(np.array(jax.devices()).reshape(-1), ("x",))
x = jax.device_put(jnp.arange(8.0),
                   NamedSharding(mesh, PartitionSpec("x")))
y = jax.jit(lambda a: a + 1, out_shardings=NamedSharding(
    mesh, PartitionSpec()))(x)
np.asarray(jax.device_get(y))
print("PROBE-OK", flush=True)
"""


@functools.lru_cache(maxsize=1)
def _multiprocess_cpu_supported() -> bool:
    import time
    port = get_open_port()
    procs = [
        subprocess.Popen([sys.executable, "-c", _PROBE, str(rank),
                          str(port)],
                         stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
        for rank in range(2)
    ]
    # One SHARED deadline, not per-process: a warning-then-silent jax
    # init hang (the documented bench-probe failure mode) must cost the
    # tier-1 budget at most ~2 minutes total, not 2 x 3 minutes.
    deadline = time.monotonic() + 120
    ok = True
    for p in procs:
        try:
            out, _ = p.communicate(
                timeout=max(5.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            return False
        ok = ok and p.returncode == 0 and "PROBE-OK" in out
    return ok


def _require_multiprocess_cpu() -> None:
    if not _multiprocess_cpu_supported():
        pytest.skip("jax multiprocess computations unavailable on this "
                    "container's CPU backend")

_CHILD = r"""
import os, sys
rank = int(sys.argv[1]); port = sys.argv[2]
n_hosts = int(sys.argv[3]); dev_per_host = int(sys.argv[4])
tp = int(sys.argv[5]); pp = int(sys.argv[6])
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={dev_per_host}")
os.environ["VDT_PALLAS_INTERPRET"] = "1"
import jax

from vllm_distributed_tpu.config import (CacheConfig, EngineConfig,
                                         LoadConfig, ModelConfig,
                                         ParallelConfig, SchedulerConfig)
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.sampling_params import SamplingParams
from transformers import LlamaConfig

config = EngineConfig(
    model_config=ModelConfig(
        model="dummy-multihost", dtype="float32", max_model_len=64,
        skip_tokenizer_init=True,
        hf_overrides=dict(vocab_size=128, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=64,
                          architectures=["LlamaForCausalLM"])),
    cache_config=CacheConfig(block_size=4, num_gpu_blocks=64),
    scheduler_config=SchedulerConfig(max_num_batched_tokens=64,
                                     max_num_seqs=8, max_model_len=64),
    load_config=LoadConfig(load_format="dummy"),
    parallel_config=ParallelConfig(
        tensor_parallel_size=tp, pipeline_parallel_size=pp,
        num_hosts=n_hosts, host_rank=rank,
        coordinator_address=f"127.0.0.1:{port}"),
)
config.model_config.hf_config = LlamaConfig(**config.model_config.hf_overrides)

# Multi-controller SPMD: every host runs the identical engine program on
# the identical request stream; collectives tie the step together.
engine = LLMEngine(config, load_tokenizer=False)
assert jax.process_count() == n_hosts, jax.process_count()
assert len(jax.devices()) == n_hosts * dev_per_host, jax.devices()

sp = SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True)
engine.add_request("mh-0", [3, 17, 92, 45, 8], sp)
engine.add_request("mh-1", [5, 9, 33, 71], sp)
done = {}
for _ in range(100):
    for out in engine.step():
        if out.finished:
            done[out.request_id] = out.outputs[0].token_ids
    if len(done) == 2:
        break
print("RESULT", rank, sorted(done.items()), flush=True)
"""


_DRIVER = r"""
import os, sys
rank = int(sys.argv[1]); port = sys.argv[2]; baddr = sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["VDT_PALLAS_INTERPRET"] = "1"
import jax

from vllm_distributed_tpu.config import (CacheConfig, EngineConfig,
                                         LoadConfig, ModelConfig,
                                         ParallelConfig, SchedulerConfig)
from transformers import LlamaConfig

def make_config(rank, port, baddr):
    config = EngineConfig(
        model_config=ModelConfig(
            model="dummy-mh-exec", dtype="float32", max_model_len=64,
            skip_tokenizer_init=True,
            hf_overrides=dict(vocab_size=128, hidden_size=64,
                              intermediate_size=128, num_hidden_layers=2,
                              num_attention_heads=8, num_key_value_heads=8,
                              max_position_embeddings=64,
                              architectures=["LlamaForCausalLM"])),
        cache_config=CacheConfig(block_size=4, num_gpu_blocks=64,
                                 num_gpu_blocks_override=64),
        scheduler_config=SchedulerConfig(max_num_batched_tokens=64,
                                         max_num_seqs=8, max_model_len=64),
        load_config=LoadConfig(load_format="dummy"),
        parallel_config=ParallelConfig(
            tensor_parallel_size=8, num_hosts=2, host_rank=rank,
            coordinator_address=f"127.0.0.1:{port}",
            broadcast_addr=baddr),
    )
    config.model_config.hf_config = LlamaConfig(
        **config.model_config.hf_overrides)
    return config

config = make_config(rank, port, baddr)
if rank == 0:
    from vllm_distributed_tpu.engine.llm_engine import LLMEngine
    from vllm_distributed_tpu.sampling_params import SamplingParams
    engine = LLMEngine(config, load_tokenizer=False)
    from vllm_distributed_tpu.executor.multihost import MultiHostExecutor
    assert isinstance(engine.engine_core.engine_core.executor,
                      MultiHostExecutor)
    sp = SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True)
    engine.add_request("mh-0", [3, 17, 92, 45, 8], sp)
    engine.add_request("mh-1", [5, 9, 33, 71], sp)
    done = {}
    for _ in range(100):
        for out in engine.step():
            if out.finished:
                done[out.request_id] = out.outputs[0].token_ids
        if len(done) == 2:
            break
    print("RESULT", rank, sorted(done.items()), flush=True)
    engine.shutdown()
else:
    from vllm_distributed_tpu.executor.multihost import run_worker_follower
    steps = run_worker_follower(config)
    assert steps >= 2, steps
    print("RESULT", rank, "follower-steps", steps, flush=True)
"""


@pytest.mark.parametrize("transport", ["tcp", "shm"])
def test_scheduler_broadcast_executor(tmp_path, transport):
    """Host 0 schedules + broadcasts; host 1 replays worker steps SPMD
    (the MultiprocExecutor-boundary equivalent). Runs over both the ZMQ
    TCP transport and the native shared-memory ring (shm://)."""
    _require_multiprocess_cpu()
    port, bport = get_open_port(), get_open_port()
    baddr = (f"tcp://127.0.0.1:{bport}" if transport == "tcp"
             else f"shm://vdt_mh_{os.getpid()}_{bport}")
    procs = [
        subprocess.Popen([sys.executable, "-c", _DRIVER, str(rank),
                          str(port), baddr],
                         stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
        for rank in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out[-3000:]}"
    assert any("RESULT 0" in o for o in outs)
    assert any("follower-steps" in o for o in outs)
    driver_line = [ln for ln in outs[0].splitlines()
                   if ln.startswith("RESULT 0")]
    assert driver_line and "mh-0" in driver_line[0]


# A wait is bounded by five times what the slowest of these tests took
# beside five loaded workers (46 s, PR 45's profile of its parent): a hang
# fails its test and leaves the suite's clock to the others.
def _run_spmd(n_hosts, dev_per_host, tp, pp, timeout=240):
    _require_multiprocess_cpu()
    port = get_open_port()
    procs = [
        subprocess.Popen([sys.executable, "-c", _CHILD, str(rank),
                          str(port), str(n_hosts), str(dev_per_host),
                          str(tp), str(pp)],
                         stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
        for rank in range(n_hosts)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out[-3000:]}"
    results = []
    for out in outs:
        lines = [ln for ln in out.splitlines() if ln.startswith("RESULT")]
        assert lines, out[-2000:]
        results.append(lines[0].split(" ", 2)[2])
    # Every controller computed the identical step results.
    assert all(r == results[0] for r in results)


def test_two_process_spmd_engine_step(tmp_path):
    _run_spmd(n_hosts=2, dev_per_host=4, tp=8, pp=1)


def test_four_process_tp_lattice(tmp_path):
    """4 controller processes x 2 virtual devices, one TP=8 mesh
    (VERDICT r4 #8: the multihost path beyond 2 processes)."""
    _run_spmd(n_hosts=4, dev_per_host=2, tp=8, pp=1)


def test_four_process_pp_tp_lattice(tmp_path):
    """4 processes, PP=2 stages x TP=4: the staged sub-meshes each span
    two processes, activations hand off across the stage boundary."""
    _run_spmd(n_hosts=4, dev_per_host=2, tp=4, pp=2)
