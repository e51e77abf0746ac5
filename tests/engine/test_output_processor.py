"""OutputProcessor.process_outputs: the three output kinds over one seeded
sequence of EngineCoreOutputs (outputs.RequestOutputKind: what a
caller of ``generate`` asks for), without an engine."""

import random

import pytest
from tokenizers import Tokenizer, models, pre_tokenizers

from tests.conftest import make_config
from vllm_distributed_tpu.core.sched.scheduler import EngineCoreOutput
from vllm_distributed_tpu.engine.output_processor import OutputProcessor
from vllm_distributed_tpu.outputs import RequestOutputKind
from vllm_distributed_tpu.request import EngineCoreRequest
from vllm_distributed_tpu.sampling_params import SamplingParams

VOCAB = 512
KINDS = list(RequestOutputKind)


@pytest.fixture(scope="module")
def tokenizer():
    tok = Tokenizer(models.WordLevel(
        {f"t{i}": i for i in range(VOCAB)}, unk_token="t0"))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    return tok


def _steps(seed: int, finish: str = "length") -> list[EngineCoreOutput]:
    """One request's outputs: one token a step mostly, bursts of up to
    four, a step with none, logprobs with a top-2 alternative."""
    rng = random.Random(seed)
    steps = []
    for i in range(24):
        ids = [rng.randrange(1, VOCAB)
               for _ in range(rng.choice([1, 1, 1, 2, 4, 0] if i else [1]))]
        steps.append(EngineCoreOutput(
            "r", ids, num_cached_tokens=3,
            logprobs=[{t: -rng.random(), (t + 1) % VOCAB: -2.0}
                      for t in ids]))
    steps[-1].finish_reason = finish
    return steps


def _run(tokenizer, kind, steps, **params):
    proc = OutputProcessor(make_config(), tokenizer)
    proc.add_request(
        EngineCoreRequest(request_id="r", prompt_token_ids=[1, 2, 3],
                          sampling_params=SamplingParams(**params)),
        **({} if kind is None else {"output_kind": kind}))
    per_step, aborts = [], []
    for out in steps:
        processed = proc.process_outputs([out])
        per_step.append(processed.request_outputs)
        aborts += processed.reqs_to_abort
        if aborts:
            break
    return proc, per_step, aborts


def _whole(kind, outputs):
    """(text, token ids, logprobs, cumulative logprob) a consumer of the
    stream ends up with."""
    comps = [ro.outputs[0] for ro in outputs]
    if kind is RequestOutputKind.DELTA:
        return ("".join(c.text for c in comps),
                [t for c in comps for t in c.token_ids],
                [lp for c in comps for lp in c.logprobs or []],
                comps[-1].cumulative_logprob)
    last = comps[-1]
    return last.text, last.token_ids, last.logprobs, last.cumulative_logprob


@pytest.mark.parametrize("seed", [0, 1, 20261003])
@pytest.mark.parametrize("kind", KINDS[1:], ids=lambda k: k.value)
def test_kinds_agree_on_the_whole(tokenizer, kind, seed):
    _, want, _ = _run(tokenizer, RequestOutputKind.CUMULATIVE,
                      _steps(seed), logprobs=2)
    want = _whole(RequestOutputKind.CUMULATIVE,
                  [ro for step in want for ro in step])
    _, got, _ = _run(tokenizer, kind, _steps(seed), logprobs=2)
    flat = [ro for step in got for ro in step]
    assert _whole(kind, flat) == want
    text, token_ids, logprobs, cumulative = want
    assert text == tokenizer.decode(token_ids)
    assert len(logprobs) == len(token_ids) >= 16
    assert cumulative == pytest.approx(
        sum(lp[t] for t, lp in zip(token_ids, logprobs)))
    last = flat[-1]
    assert last.finished and last.outputs[0].finish_reason == "length"
    assert last.prompt_token_ids == [1, 2, 3]
    assert last.num_cached_tokens == 3


def test_default_kind_is_cumulative(tokenizer):
    """LLMEngine, LLM.generate and the CLI pass no kind and see what
    they saw: every output holds the whole so far."""
    steps = _steps(7)
    proc, per_step, _ = _run(tokenizer, None, steps)
    seen = []
    for out, ros in zip(steps, per_step):
        seen += out.new_token_ids
        (ro, ) = ros
        assert ro.outputs[0].token_ids == seen
        assert ro.outputs[0].text == tokenizer.decode(seen)
    assert proc.stats.request_outputs == {
        "cumulative": len(steps), "delta": 0, "final": 0}


def test_final_only_builds_nothing_before_the_finish(tokenizer):
    steps = _steps(3)
    proc, per_step, _ = _run(tokenizer, RequestOutputKind.FINAL_ONLY,
                             steps[:-1])
    assert per_step == [[]] * (len(steps) - 1)
    assert proc.stats.request_outputs == {
        "cumulative": 0, "delta": 0, "final": 0}
    (final, ) = proc.process_outputs([steps[-1]]).request_outputs
    assert final.finished
    assert final.outputs[0].token_ids == [
        t for out in steps for t in out.new_token_ids]
    assert proc.stats.request_outputs["final"] == 1
    assert 'vdt:frontend_request_outputs_total{kind="final"} 1' in \
        proc.stats.render()
    assert not proc.request_states


def test_delta_holds_the_steps_own_and_no_more(tokenizer):
    steps = _steps(5)
    proc, per_step, _ = _run(tokenizer, RequestOutputKind.DELTA, steps,
                             logprobs=2)
    for out, (ro, ) in zip(steps, per_step):
        comp = ro.outputs[0]
        n = len(out.new_token_ids)
        assert comp.token_ids == out.new_token_ids
        assert len(comp.logprobs or []) == n
        assert comp.text == "".join(
            f" t{t}" for t in out.new_token_ids)[out is steps[0]:]
        lists = [v for v in vars(comp).values() if isinstance(v, list)]
        assert lists and all(len(v) <= n for v in lists)
    assert proc.stats.request_outputs == {
        "cumulative": 0, "delta": len(steps), "final": 0}
    assert proc.stats.detokenized_tokens["window"] == 0


@pytest.mark.parametrize("kind", KINDS, ids=lambda k: k.value)
def test_front_end_stop_aborts_in_the_core(tokenizer, kind):
    """A stop string the front end finds (here across two steps'
    pieces) finishes the request there and names it for the core's
    abort, whatever the caller reads."""
    steps = [EngineCoreOutput("r", [t]) for t in (5, 42, 79, 116)]
    proc, per_step, aborts = _run(tokenizer, kind, steps,
                                  stop=["t42 t79"])
    assert aborts == ["r"] and len(per_step) == 3
    (last, ) = per_step[-1]
    assert last.finished
    assert last.outputs[0].finish_reason == "stop"
    assert last.outputs[0].stop_reason == "t42 t79"
    assert not proc.request_states
    if kind is RequestOutputKind.DELTA:
        # " t42" went out a step before the stop was seen.
        assert [ro.outputs[0].text for (ro, ) in per_step] == [
            "t5", " t42", ""]
    else:
        assert last.outputs[0].text == "t5 "
        assert len(per_step[0]) == (kind is RequestOutputKind.CUMULATIVE)
