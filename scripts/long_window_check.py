#!/usr/bin/env python3
"""The window, past its edge, on the chip.

    python3 scripts/long_window_check.py [--seed N] [--cell NAME]
        [--lengths 4300,5200,6100] [--targets]

The comparison that decides ``correct`` in a benchmark run scores prompts
of 48 to 209 tokens (``benchmarks/traffic.py`` ``scoring_requests``), so
it never crosses the cell's window (4 096 tokens in both cells that have
one: ``command-a-plus-ep8.long-document``,
``smallthinker-21b-a3b.mixed-length``) and a reference without the window
mask cannot be told from the right one there. This script holds the
cell's window on the chip: it starts the cell's engine (the cell's own
``engine_args``, seeded weights, the same scheduler, cache groups and
runner a server has, through ``LLM.generate``), serves seeded prompts
longer than the window with echo, logprobs and 8 greedy tokens, and hands
what came back to ``benchmarks.run.compare_with_reference`` (the
reference computes attention a K/V head and a block of queries at a
time, so the sequences fit beside the weights), with the control
``no_window`` (the reference without the window mask, which is NOT in
``CONTROLS``: the harness's short prompts cannot catch it) added to the
controls for this comparison alone. Prints one JSON line; exit 0 when
the program is within the file's ``tolerance`` and every control, with
``no_window`` among them, misses by three times it or more. ``detail``
gives the same distances position by position: the program's tail over
all positions, and the queries past the window (where alone the two
references can differ) apart from those inside it. Not part of a
benchmark run.

``--targets`` is the same check for a cell WITHOUT a window whose memory
is a recurrent state (``brumby-14b.long-document``, with ``--lengths
300,2600,9000``: inside one 2 048-token chunk step, across two, across
five, each then 8 tokens through the recurrent kernel): no window to
cross and no control to add; each prompt is scored apart, through the
reference's ``targets`` (the targets' log-probabilities alone, the head
a block of rows at a time, so that 9 000 tokens fit beside the weights),
and the exit is 0 when every prompt is within the file's ``tolerance``.
"""

import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import run, traffic  # noqa: E402


def serve(cf: dict, model_dir: str, seed: int,
          prompts: list) -> tuple[list, dict]:
    from vllm_distributed_tpu import LLM, SamplingParams
    llm = LLM(model=model_dir, load_format="dummy",
              seed=seed % (2 ** 31 - 1), skip_tokenizer_init=True,
              **cf["engine_args"])
    outs = llm.generate(
        prompts, SamplingParams(temperature=0.0, max_tokens=8,
                                ignore_eos=True, logprobs=1,
                                prompt_logprobs=1))
    stats = llm.llm_engine.get_stats()
    scoring = []
    for prompt, o in zip(prompts, outs):
        c = o.outputs[0]
        plp = [d[t] for t, d in zip(prompt[1:], o.prompt_logprobs[1:])]
        glp = [d[t] for t, d in zip(c.token_ids, c.logprobs)]
        scoring.append({"tokens": prompt + list(c.token_ids),
                        "token_logprobs": [None] + plp + glp})
    del llm
    return scoring, {k: stats.get(k) for k in (
        "kv_window_pages_freed", "num_preemptions")}


def position_deviations(cell: dict, args, scoring: list,
                        control) -> list:
    """|served - reference| at every scored position, a list a sequence
    (entry ``p`` is the logprob of token ``p + 1``), the reference with
    ``control``, each sequence at its own length."""
    import jax
    import jax.numpy as jnp

    from vllm_distributed_tpu.models.loader import get_model
    from vllm_distributed_tpu.parallel.mesh import build_mesh
    cf = cell["config_file"]
    cfg = run.hf_config(cf)
    ref = importlib.import_module(f"benchmarks.references.{cf['reference']}")
    config = args.create_engine_config()
    config.model_config.maybe_load_hf_config()
    _, params = get_model(config, build_mesh(config.parallel_config,
                                             jax.devices()[:1]))
    S = max(len(s["tokens"]) for s in scoring)
    fn = jax.jit(lambda p, ids, c: ref.logprobs(p, cfg, ids, c),
                 static_argnums=2)
    out = []
    for c in control:
        devs = []
        for s in scoring:
            seq = s["tokens"]
            ids = jnp.asarray(seq + [0] * (S - len(seq)), jnp.int32)
            with jax.default_matmul_precision("highest"):
                tab = fn(params, ids, c)
            want = jax.device_get(jnp.take_along_axis(
                tab[:len(seq) - 1], jnp.asarray(seq[1:])[:, None], 1)[:, 0])
            devs.append([abs(float(w) - g) for w, g in
                         zip(want, s["token_logprobs"][1:])])
        out.append(devs)
    return out


def target_deviations(cell: dict, args, scoring: list) -> list:
    """``position_deviations`` without a control for a reference that
    scores ``targets`` alone: no ``[S, vocabulary]`` table, each
    sequence at its own length."""
    import jax
    import jax.numpy as jnp

    from vllm_distributed_tpu.models.loader import get_model
    from vllm_distributed_tpu.parallel.mesh import build_mesh
    cf = cell["config_file"]
    cfg = run.hf_config(cf)
    ref = importlib.import_module(f"benchmarks.references.{cf['reference']}")
    config = args.create_engine_config()
    config.model_config.maybe_load_hf_config()
    _, params = get_model(config, build_mesh(config.parallel_config,
                                             jax.devices()[:1]))
    fn = jax.jit(lambda p, ids, tgt: ref.logprobs(p, cfg, ids,
                                                  targets=tgt))
    out = []
    for s in scoring:
        seq = jnp.asarray(s["tokens"], jnp.int32)
        with jax.default_matmul_precision("highest"):
            want = jax.device_get(fn(params, seq, jnp.roll(seq, -1)))[:-1, 0]
        out.append([abs(float(w) - g) for w, g in
                    zip(want, s["token_logprobs"][1:])])
    return out


def summary(devs: list) -> dict:
    devs = sorted(devs)
    n = len(devs)
    return {"positions": n, "max": devs[-1], "mean": sum(devs) / n,
            "p99": devs[int(0.99 * n)], "p999": devs[int(0.999 * n)]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default="command-a-plus-ep8.long-document")
    ap.add_argument("--seed", type=int, default=4300000001)
    ap.add_argument("--lengths", default="4300,5200,6100")
    ap.add_argument("--targets", action="store_true",
                    help="a cell without a window: each prompt apart, "
                    "through the reference's targets")
    ap.add_argument("--data-root", default=run.HERE)
    a = ap.parse_args(argv)
    import jax
    cell = run.load_cell(a.cell, a.data_root)
    cf = cell["config_file"]
    lengths = [int(n) for n in a.lengths.split(",")]
    if not a.targets:
        # The cell's window, under the key its family publishes it by.
        window = cf.get("sliding_window") or cf["sliding_window_size"]
        assert min(lengths) > window, \
            "every prompt has to cross the cell's window"
    rng = traffic.run_rng(a.seed, 2)
    prompts = [rng.integers(1, cf["vocab_size"], size=n).tolist()
               for n in lengths]
    model_dir = run.write_model_dir(cell["config"], cf)
    args = run.engine_args(model_dir, cf, a.seed)
    scoring, stats = serve(cf, model_dir, a.seed, prompts)
    if a.targets:
        tol = cf["tolerance"]
        per_prompt = [summary(d)
                      for d in target_deviations(cell, args, scoring)]
        ok = all(p["max"] <= tol["max"] and p["mean"] <= tol["mean"]
                 for p in per_prompt)
        print(json.dumps({
            "cell": a.cell, "seed": a.seed, "lengths": lengths,
            "platform": jax.devices()[0].platform, "tolerance": tol,
            "prompts": per_prompt, "ok": ok}), flush=True)
        return 0 if ok else 1
    # For these prompts alone, ``no_window`` joins the controls that the
    # comparison must catch at three tolerances.
    ref = importlib.import_module(f"benchmarks.references.{cf['reference']}")
    arch = cf["architectures"][0]
    ref.CONTROLS[arch] = [*ref.CONTROLS[arch], "no_window"]
    got = run.compare_with_reference(cell, args, scoring)
    # The same distances, position by position: the queries PAST the
    # window (where alone the two references differ) apart from the
    # rest, and how far the program's tail reaches over so many more
    # positions than the 542 the limits were set on.
    program, broken = position_deviations(cell, args, scoring,
                                          (None, "no_window"))
    past = [[d for p, d in enumerate(seq) if p + 1 >= window]
            for seq in program], \
        [[d for p, d in enumerate(seq) if p + 1 >= window]
         for seq in broken]
    flat = [d for seq in program for d in seq]
    detail = {
        "program": summary(flat),
        "program_over_max_limit": sum(d > got["tolerance"]["max"]
                                      for d in flat),
        "past_window": {"program": summary(sum(past[0], [])),
                        "no_window": summary(sum(past[1], []))},
        "inside_window": {"no_window": summary(
            [d for seq in broken for p, d in enumerate(seq)
             if p + 1 < window])},
    }
    print(json.dumps({
        "cell": a.cell, "seed": a.seed, "lengths": lengths,
        "platform": jax.devices()[0].platform, "window": window,
        "positions": got["positions"], "tolerance": got["tolerance"],
        "max_abs_logprob_dev": got["max_abs_logprob_dev"],
        "mean_abs_logprob_dev": got["mean_abs_logprob_dev"],
        "controls": got["controls"], "detail": detail, "engine": stats,
        "ok": got["ok"]}), flush=True)
    return 0 if got["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
