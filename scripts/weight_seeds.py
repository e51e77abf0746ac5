#!/usr/bin/env python3
"""What the comparison that decides ``correct`` reads on MANY weight seeds
of one benchmark configuration, in one process and one chip call (written
for PR 55: a run of ``benchmarks.run`` gives one weight seed and costs a
whole set-up; a ``tolerance`` wants a dozen).

    python3 scripts/weight_seeds.py --workload <cell> --seeds a,b,c,... \
        [--low-precision N] [--max-num-seqs 8] [--also-controls x,y]

One ``LLM`` (the synchronous engine, programs compiled as they are met)
is built from the cell's configuration file with few state rows and
pages, so that the plain reference fits beside it. For each seed the
weights are seeded again by the program's own loader (the programs take
them as arguments: nothing compiles again), the harness's scoring prompts
of that seed (``benchmarks.traffic.scoring_requests``: other tokens each
seed, so no cached prefix is met) are served with prompt and greedy
logprobs, and ``benchmarks.run.compare_with_reference``'s arithmetic is
done on the weights in place: largest and mean deviation from the
float32 reference, and every control of the reference's ``CONTROLS``. On
the first ``--low-precision`` seeds the reference is also read in
bfloat16 at the default matrix precision and with every weight and block
input rounded through float8 e4m3 (``benchmarks/reference_precision.py``'s
readings). One JSON line a seed on standard output and in
``chiprun_out/weight_seeds/<cell>.jsonl``; a summary line last. Nothing
here is a benchmark file, and nothing decides ``correct``.
"""
import argparse
import gc
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("HF_HUB_OFFLINE", "1")
os.environ.setdefault("VDT_PRECOMPILE", "0")

from benchmarks import run, traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--low-precision", type=int, default=1)
    ap.add_argument("--max-num-seqs", type=int, default=8)
    ap.add_argument("--also-controls", default="",
                    help="controls of the reference beyond its CONTROLS")
    ap.add_argument("--data-root", default=run.HERE)
    a = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from vllm_distributed_tpu import LLM, SamplingParams
    from vllm_distributed_tpu.models.loader import get_model
    seeds = [int(s) for s in a.seeds.split(",")]
    cell = run.load_cell(a.workload, a.data_root)
    cf = cell["config_file"]
    cfg = run.hf_config(cf)
    ref = importlib.import_module(f"benchmarks.references.{cf['reference']}")
    controls = ref.CONTROLS[cfg["architectures"][0]] + [
        c for c in a.also_controls.split(",") if c]
    model_dir = run.write_model_dir(cell["config"], cf)
    ea = dict(cf["engine_args"], max_num_seqs=a.max_num_seqs)
    # Pages for the scoring prompts and no more: the reference's float32
    # copies need the room.
    llm = LLM(model=model_dir, load_format="dummy",
              seed=seeds[0] % (2 ** 31 - 1), skip_tokenizer_init=True,
              num_gpu_blocks_override=4 * ea["max_model_len"]
              // ea["block_size"], **ea)
    runner = llm.llm_engine.engine_core.engine_core.executor.worker \
        .model_runner
    out_dir = os.path.join(ROOT, "chiprun_out", "weight_seeds")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"{a.workload}.jsonl")

    def emit(line: dict) -> None:
        text = json.dumps(line)
        print(text, flush=True)
        with open(out_path, "a") as out:
            out.write(text + "\n")

    fns = {}

    def table(key, ids):
        """The reference's table under one reading: (control, compute
        dtype, rounding dtype, matrix precision), one program each."""
        control, dt, rounding, precision = key
        if key not in fns:
            fns[key] = jax.jit(lambda p, i: ref.logprobs(
                p, cfg, i, control, compute_dtype=dt, round_dtype=rounding))
        with jax.default_matmul_precision(precision):
            return fns[key](runner.params, ids)

    def deviations(scoring, key):
        S = max(len(s["tokens"]) for s in scoring)
        devs = []
        for s in scoring:
            seq = s["tokens"]
            ids = jnp.asarray(seq + [0] * (S - len(seq)), jnp.int32)
            tab = table(key, ids)
            want = jax.device_get(jnp.take_along_axis(
                tab[:len(seq) - 1], jnp.asarray(seq[1:])[:, None], 1)[:, 0])
            devs += [abs(float(w) - g)
                     for w, g in zip(want, s["token_logprobs"][1:])]
        return {"max": max(devs), "mean": sum(devs) / len(devs)}

    rows = []
    for n, seed in enumerate(seeds):
        if n:
            runner.params = None
            gc.collect()
            runner.config.model_config.seed = seed % (2 ** 31 - 1)
            _, runner.params = get_model(runner.config, runner.mesh)
            runner._to_serving_layout()
        reqs = traffic.scoring_requests(seed, cf["vocab_size"])
        outs = llm.generate(
            [r["prompt"] for r in reqs],
            SamplingParams(temperature=0.0, max_tokens=8, ignore_eos=True,
                           logprobs=1, prompt_logprobs=1))
        scoring = []
        for r, o in zip(reqs, outs):
            c = o.outputs[0]
            plp = [d[t] for t, d in zip(r["prompt"][1:],
                                        o.prompt_logprobs[1:])]
            glp = [d[t] for t, d in zip(c.token_ids, c.logprobs)]
            scoring.append({"tokens": r["prompt"] + list(c.token_ids),
                            "token_logprobs": [None] + plp + glp})
        f32 = jnp.float32
        line = {"seed": seed, "platform": jax.devices()[0].platform,
                "program": deviations(scoring, (None, f32, None, "highest")),
                "controls": {c: deviations(scoring,
                                           (c, f32, None, "highest"))
                             for c in controls}}
        if n < a.low_precision:
            bf16 = jnp.bfloat16
            line["bfloat16_default"] = deviations(
                scoring, (None, bf16, None, None))
            line["float8_e4m3_rounded"] = deviations(
                scoring, (None, bf16, jnp.float8_e4m3fn, None))
        rows.append(line)
        emit(line)
    emit({"workload": a.workload, "seeds": len(rows),
          "program_max": [min(r["program"]["max"] for r in rows),
                          max(r["program"]["max"] for r in rows)],
          "program_mean": [min(r["program"]["mean"] for r in rows),
                           max(r["program"]["mean"] for r in rows)],
          "controls": {c: {k: [min(r["controls"][c][k] for r in rows),
                               max(r["controls"][c][k] for r in rows)]
                           for k in ("max", "mean")} for c in controls}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
