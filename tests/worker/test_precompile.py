"""Precompile warm-up + recompile guard (reference:
tpu_model_runner.py:1248-1443 precompilation suite and :318
_update_num_xla_graphs recompile detection)."""

import numpy as np
import pytest

from tests.engine.test_llm_engine import checkpoint, make_engine  # noqa: F401
from vllm_distributed_tpu.sampling_params import SamplingParams


def _runner(engine):
    return engine.engine_core.executor.worker.model_runner


def test_forward_shapes_closed_lattice(checkpoint, monkeypatch):
    """Every shape mixed traffic can hit is in forward_shapes()."""
    path, _ = checkpoint
    engine = make_engine(path, max_num_batched_tokens=16)
    r = _runner(engine)
    shapes = r.forward_shapes()
    # Decode at every request count and prefill at every token count must
    # land inside the precomputed lattice.
    for n_reqs in range(1, r.max_num_reqs + 1):
        assert r._batch_shape(n_reqs, 1) in shapes
    for total in range(1, 17):
        assert r._batch_shape(total, 2) in shapes


def test_unified_lattice_strictly_smaller_than_legacy(checkpoint):
    """ISSUE 6 acceptance: at unchanged bucket configs, the mega-kernel
    lattice (one forward shape per token bucket — composition lives in
    the partition descriptor) warms strictly fewer graphs than the
    legacy decode/prefill split (max_q keyed to the token bucket)."""
    path, _ = checkpoint
    engine = make_engine(path, max_num_batched_tokens=16)
    r = _runner(engine)
    assert r._use_unified()
    unified = r.forward_shapes()
    # Every unified shape pins max_q == 1: no composition static.
    assert {s[1] for s in unified} == {1}
    r._unified = False  # same buckets, legacy composition-split shapes
    legacy = r.forward_shapes()
    r._unified = True
    assert len(unified) < len(legacy)


def test_no_recompile_after_warmup(checkpoint, monkeypatch):
    """Mixed traffic (ragged prefills, chunked prefill, decode, stops)
    after precompile() must never compile a new graph."""
    monkeypatch.setenv("VDT_PRECOMPILE", "1")
    monkeypatch.setenv("VDT_ASSERT_NO_RECOMPILE", "1")
    path, _ = checkpoint
    engine = make_engine(path, max_num_batched_tokens=16, max_num_seqs=4)
    rng = np.random.default_rng(0)
    prompts = [[int(x) for x in rng.integers(2, 127, size=n)]
               for n in (3, 11, 23, 2, 7)]  # 23 forces chunked prefill
    for i, p in enumerate(prompts):
        engine.add_request(f"r{i}", p,
                           SamplingParams(temperature=0.0,
                                          max_tokens=4 + i % 3,
                                          ignore_eos=True))
    for _ in range(200):
        engine.step()  # raises RuntimeError on any post-warmup compile
        if not engine.has_unfinished_requests():
            break
    assert not engine.has_unfinished_requests()


def test_warmup_builds_the_cascade_variants(checkpoint, monkeypatch):
    """With shared-prefix attention on, warm-up builds a second forward
    program a shape, with the shared ids in the packed inputs' rest
    (since the step inputs were packed, PR 26, it raised TypeError)."""
    monkeypatch.setenv("VDT_PRECOMPILE", "1")
    monkeypatch.setenv("VDT_CASCADE_ATTENTION", "1")
    path, _ = checkpoint
    engine = make_engine(path, max_num_batched_tokens=16, max_num_seqs=4)
    r = _runner(engine)
    fwd = [k for k in r._compiled_shapes if k[0] == "fwd"]
    plain = {k[:-1] for k in fwd if not k[-1]}
    assert plain and {k[:-1] for k in fwd if k[-1]} == plain


def test_no_recompile_after_warmup_pp(checkpoint, monkeypatch):
    """The pipeline-parallel runner's per-stage warm-up must also close
    the lattice: mixed traffic after precompile() never compiles."""
    monkeypatch.setenv("VDT_PRECOMPILE", "1")
    monkeypatch.setenv("VDT_ASSERT_NO_RECOMPILE", "1")
    path, _ = checkpoint
    engine = make_engine(path, max_num_batched_tokens=16, max_num_seqs=4,
                         pipeline_parallel_size=2)
    rng = np.random.default_rng(1)
    prompts = [[int(x) for x in rng.integers(2, 127, size=n)]
               for n in (3, 11, 23, 2)]
    for i, p in enumerate(prompts):
        engine.add_request(f"pp{i}", p,
                           SamplingParams(temperature=0.0,
                                          max_tokens=4 + i % 3,
                                          ignore_eos=True))
    for _ in range(200):
        engine.step()
        if not engine.has_unfinished_requests():
            break
    assert not engine.has_unfinished_requests()


def test_no_recompile_multi_step(checkpoint, monkeypatch):
    monkeypatch.setenv("VDT_PRECOMPILE", "1")
    monkeypatch.setenv("VDT_ASSERT_NO_RECOMPILE", "1")
    path, _ = checkpoint
    engine = make_engine(path, max_num_batched_tokens=16, max_num_seqs=4,
                         num_scheduler_steps=4)
    for i in range(3):
        engine.add_request(f"m{i}", [5 + i, 9, 3],
                           SamplingParams(temperature=0.0, max_tokens=8,
                                          ignore_eos=True))
    for _ in range(200):
        engine.step()
        if not engine.has_unfinished_requests():
            break
    assert not engine.has_unfinished_requests()


@pytest.mark.parametrize("pp", [1, 2])
def test_warmed_programs_equal_the_served_ones(checkpoint, monkeypatch,
                                               pp):
    """The packed signature (worker/step_inputs.py StepInputs, the
    [5, R] sampling buffer) is the same at warm-up and in serving. A jit
    traces its body once per distinct signature (pytree, shapes, dtypes,
    weak types, statics), so a dummy input that differs from a served
    step's in ANY of them shows as a new trace of the model's methods;
    the runner's own _compile_watch keys only on the shape bucket.
    Mixed traffic with plain, extended and prompt-logprob sampling must
    trace nothing, and the warmed count is the lattice's: one forward a
    token bucket (embed + one a stage under PP), five samplers a request
    bucket, one prompt-logprob graph a token bucket."""
    monkeypatch.setenv("VDT_PRECOMPILE", "1")
    monkeypatch.setenv("VDT_ASSERT_NO_RECOMPILE", "1")
    path, _ = checkpoint
    engine = make_engine(path, max_num_batched_tokens=16, max_num_seqs=4,
                         pipeline_parallel_size=pp)
    r = _runner(engine)
    shapes = len(r.forward_shapes())
    heads = 5 * len(r.req_buckets) + len(r.token_buckets)
    if pp == 1:
        assert r.precompile_graphs == shapes + heads
        assert len(r._compiled_shapes) == shapes + heads
    else:
        assert r.precompile_graphs == (1 + pp) * shapes
        assert len(r._compiled_shapes) == (1 + pp) * shapes + heads
    traced = []
    for name in ("forward", "embed", "run_layers", "compute_logits"):
        def counted(*a, _name=name, _fn=getattr(r.model, name), **k):
            traced.append(_name)
            return _fn(*a, **k)
        monkeypatch.setattr(r.model, name, counted)
    rng = np.random.default_rng(5)
    extras = [dict(), dict(repetition_penalty=1.2), dict(logprobs=2),
              dict(prompt_logprobs=1), dict(temperature=0.8, seed=7)]
    for i, n in enumerate((3, 11, 23, 2, 7)):
        params = dict(temperature=0.0, max_tokens=4 + i % 3,
                      ignore_eos=True)
        params.update(extras[i])
        engine.add_request(
            f"w{i}", [int(x) for x in rng.integers(2, 127, size=n)],
            SamplingParams(**params))
    for _ in range(200):
        engine.step()
        if not engine.has_unfinished_requests():
            break
    assert not engine.has_unfinished_requests()
    assert traced == []
    assert r.num_recompiles == 0
