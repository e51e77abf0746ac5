"""The plain reference against the program's engine (prefill, then decode
through the paged cache) at a toy width on the CPU, with the negative
controls the chip run relies on."""

import os

import pytest

from benchmarks import run

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.mark.parametrize("cell_name,arch", [
    ("tiny-olmo2.tiny-chat", "Olmo2ForCausalLM"),
    ("tiny-olmoe.tiny-decode", "OlmoeForCausalLM")])
def test_engine_matches_plain_reference(cell_name, arch, tmp_path,
                                        monkeypatch):
    from vllm_distributed_tpu import LLM, SamplingParams

    from benchmarks import traffic
    monkeypatch.setattr(run, "WORK", str(tmp_path))
    cell = run.load_cell(cell_name, DATA)
    cf = cell["config_file"]
    assert run.hf_config(cf)["architectures"] == [arch]
    model_dir = run.write_model_dir(cell["config"], cf)
    args = run.engine_args(model_dir, cf, seed=2 ** 31 + 3)
    assert 0 <= args.seed < 2 ** 31
    llm = LLM(model=model_dir, load_format="dummy", seed=args.seed,
              skip_tokenizer_init=True, **cf["engine_args"])
    reqs = traffic.scoring_requests(5, cf["vocab_size"])
    outs = llm.generate(
        [r["prompt"] for r in reqs],
        SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True,
                       logprobs=1, prompt_logprobs=1))
    scoring = []
    for r, o in zip(reqs, outs):
        c = o.outputs[0]
        assert len(c.token_ids) == 8
        plp = [d[t] for t, d in zip(r["prompt"][1:], o.prompt_logprobs[1:])]
        glp = [d[t] for t, d in zip(c.token_ids, c.logprobs)]
        scoring.append({"tokens": r["prompt"] + list(c.token_ids),
                        "token_logprobs": [None] + plp + glp})
    del llm
    got = run.compare_with_reference(cell, args, scoring)
    # float32 engine against a float32 reference: rounding only.
    assert got["positions"] == sum(len(s["tokens"]) - 1 for s in scoring)
    assert got["max_abs_logprob_dev"] < 1e-4, got
    assert got["ok"], got
    from benchmarks.references import olmo
    assert set(got["controls"]) == set(olmo.CONTROLS[arch])
    for name, c in got["controls"].items():
        assert c["caught"] and c["max"] > 100 * got["max_abs_logprob_dev"], \
            (name, c)
    # A served stream that is off by more than the tolerance is refused.
    bad = [dict(s, token_logprobs=[None] + [v - 0.01 for v in
                                            s["token_logprobs"][1:]])
           for s in scoring]
    assert not run.compare_with_reference(cell, args, bad)["ok"]
