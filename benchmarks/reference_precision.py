#!/usr/bin/env python3
"""What a lower precision costs the comparison that decides ``correct``.

    python3 -m benchmarks.reference_precision --workload <cell> --seed <n>

Run AFTER ``benchmarks.run`` of the same cell and seed, in the same
checkout: it reads the served logprobs that run kept
(``.bench_work/<config>/loadgen.json``), makes the seeded weights again
and scores the same sequences with the cell's reference twice, in
float32 under ``highest`` (the reading ``correct`` uses), with every
weight and activation held in bfloat16 at the default matrix precision
(the precision the configuration states: the served program's own, so
this reading lies beside the first), and in bfloat16 with every weight
and block input also rounded through float8 e4m3 (the nearest precision
below it). A tolerance has to lie between the first and the last
reading: the last must come out as not correct. Prints one JSON line; only a reference
whose ``logprobs`` takes ``compute_dtype`` can be asked
(``benchmarks/references/deepseek.py``). Not part of a benchmark run.
"""

import argparse
import importlib
import json
import os
import sys

from benchmarks import run


def main(argv=None, data_root: str = run.HERE) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    a = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from vllm_distributed_tpu.models.loader import get_model
    from vllm_distributed_tpu.parallel.mesh import build_mesh
    cell = run.load_cell(a.workload, data_root)
    cf = cell["config_file"]
    cfg = run.hf_config(cf)
    scoring = run.load_json(run.WORK, cell["config"],
                            "loadgen.json")["scoring"]
    ref = importlib.import_module(f"benchmarks.references.{cf['reference']}")
    args = run.engine_args(run.write_model_dir(cell["config"], cf), cf,
                           a.seed)
    config = args.create_engine_config()
    config.model_config.maybe_load_hf_config()
    mesh = build_mesh(config.parallel_config, jax.devices()[:1])
    _, params = get_model(config, mesh)
    S = max(len(s["tokens"]) for s in scoring)
    out = {"workload": a.workload, "seed": a.seed,
           "platform": jax.devices()[0].platform,
           "tolerance": cf["tolerance"]}
    for name, dtype, rounding, precision in (
            ("float32_highest", jnp.float32, None, "highest"),
            ("bfloat16_default", jnp.bfloat16, None, None),
            ("float8_e4m3_rounded", jnp.bfloat16, jnp.float8_e4m3fn, None)):
        fn = jax.jit(lambda p, ids, dt=dtype, r=rounding: ref.logprobs(
            p, cfg, ids, None, compute_dtype=dt, round_dtype=r))
        devs = []
        for s in scoring:
            seq = s["tokens"]
            ids = jnp.asarray(seq + [0] * (S - len(seq)), jnp.int32)
            with jax.default_matmul_precision(precision):
                tab = fn(params, ids)
            want = jax.device_get(jnp.take_along_axis(
                tab[:len(seq) - 1], jnp.asarray(seq[1:])[:, None], 1)[:, 0])
            devs += [abs(float(w) - g)
                     for w, g in zip(want, s["token_logprobs"][1:])]
        ranked = sorted(devs)
        out[name] = {"max": max(devs), "mean": sum(devs) / len(devs),
                     "positions": len(devs),
                     # Where the mean comes from: the bulk or a few
                     # positions (a routing pick that flipped).
                     **{f"p{q}": ranked[min(len(ranked) - 1,
                                            len(ranked) * q // 100)]
                        for q in (50, 90, 99)}}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("HF_HUB_OFFLINE", "1")
    sys.exit(main())
