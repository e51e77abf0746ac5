#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 -m benchmarks.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process holds the chip. The server runs in the main thread exactly as
``vdt serve`` runs it (``run_server``), on weights made on the device from
``--seed``. A helper thread waits for ``/health``, then starts the load
generator as a CHILD PROCESS that imports no JAX (``benchmarks.loadgen``),
waits for it and ends the server the way an orchestrator does, with
SIGTERM. Order: start -> healthy -> ramp (unmeasured) -> window of
``--seconds`` -> drain -> scoring requests -> SIGTERM -> server gone ->
plain reference on the same seeded weights -> the result line.

The command itself is a supervisor that never touches JAX: it starts that
process as a worker and, if the worker found a program missing from the
compile cache while it started (so it compiled, and filled the cache),
lets it end before any traffic and starts it once more. Every measured
window thus runs in a process that LOADED its programs, the first run of
a checkout like the rest (PERF.md, findings of PR 24); the first run's
``setup_s`` holds both starts.

The last line of standard output is the result object and nothing else;
facts about the run go on earlier lines, one JSON object each; the
program's log goes to standard error. There is no CPU path: any platform
but a TPU, or another device count than the cell asks for, exits 1 with
no result line.
"""

import argparse
import gc
import importlib
import json
import logging
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time

# The supervisor's start, handed to its workers: one clock
# (``time.monotonic``) for every process of the machine.
START_ENV = "VDT_BENCH_STARTED"
# A worker's attempt number; absent in the supervisor.
WORKER_ENV = "VDT_BENCH_ATTEMPT"
RESTART_RC = 75   # a worker that compiled during set-up: start it again
T_PROCESS_START = float(os.environ.get(START_ENV) or time.monotonic())

from benchmarks import layers, metrics, traffic  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
MANIFEST = os.path.join(REPO, "BENCHMARK.json")
WORK = os.path.join(REPO, ".bench_work")

# Keys of a configuration file that are the benchmark's, not the model's.
BENCH_KEYS = ("source", "reduced", "assumed", "deployment", "engine_args",
              "reference", "tolerance")
RAMP_S = {"open": 8.0, "closed": 10.0}
DRAIN_CAP_S = 45.0


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, data_root: str = HERE) -> dict:
    """The cell's file, with its configuration and mix resolved by name."""
    cell = load_json(data_root, "cells", f"{name}.json")
    cell["name"] = name
    cell["config_file"] = load_json(data_root, "configs",
                                    f"{cell['config']}.json")
    cell["mix"] = traffic.load_mix(cell["traffic"], data_root)
    return cell


def cell_metrics(manifest: dict, cell: str, kind: str) -> list[dict]:
    """The manifest's metrics of one kind that this cell reports."""
    return [m for m in manifest[kind]
            if "workloads" not in m or cell in m["workloads"]]


def hf_config(config_file: dict) -> dict:
    return {k: v for k, v in config_file.items() if k not in BENCH_KEYS}


def write_model_dir(config_name: str, config_file: dict) -> str:
    """``config.json`` and a tokenizer, at a fixed path in the checkout.

    There is no network and no published tokenizer file here, and the
    server streams TEXT deltas: without a tokenizer a stream carries
    nothing until its last chunk. So a word-level tokenizer of the
    model's own vocabulary size stands in (token i <-> "t<i>"); the
    server then detokenizes every token, as a deployment does, and the
    client counts tokens as words."""
    path = os.path.join(WORK, config_name, "model")
    os.makedirs(path, exist_ok=True)
    cfg = hf_config(config_file)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(cfg, f)
    tok_path = os.path.join(path, "tokenizer.json")
    if not os.path.exists(tok_path):
        from tokenizers import Tokenizer
        from tokenizers.models import WordLevel
        from tokenizers.pre_tokenizers import WhitespaceSplit
        tok = Tokenizer(WordLevel(
            {f"t{i}": i for i in range(cfg["vocab_size"])}, unk_token="t0"))
        tok.pre_tokenizer = WhitespaceSplit()
        tok.save(tok_path + ".tmp")
        os.replace(tok_path + ".tmp", tok_path)
    # No token is declared special: the server skips special tokens when
    # it detokenizes, and a greedy stream over random weights may emit any
    # id; a skipped one would read as a missing token at the client.
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump({"tokenizer_class": "PreTrainedTokenizerFast"}, f)
    return path


def engine_args(model_dir: str, config_file: dict, seed: int):
    from vllm_distributed_tpu.engine.arg_utils import EngineArgs
    # The program's seed field is 32 bits wide in places; the traffic
    # and prompts take the whole seed.
    return EngineArgs(model=model_dir, load_format="dummy",
                      seed=seed % (2 ** 31 - 1),
                      **config_file["engine_args"])


def build_plan(cell: dict, seed: int, seconds: float, trace: bool,
               port: int, out: str, ramp_s: float | None = None) -> dict:
    cf = cell["config_file"]
    loop = cell["mix"]["loop"]
    ramp = RAMP_S[loop] if ramp_s is None else ramp_s
    max_len = cf["engine_args"]["max_model_len"]
    plan = traffic.build_schedule(cell["mix"], cell["load"], seed, seconds,
                                  ramp, cf["vocab_size"], max_len)
    if loop == "open":
        plan["window"] = {"start": ramp, "seconds": seconds}
    ea = cf["engine_args"]
    plan.update(
        warmup=traffic.shape_warmup(seed, cf["vocab_size"],
                                    ea["max_num_seqs"],
                                    ea["max_num_batched_tokens"]),
        port=port, model=cell["config"], out=out, drain_cap_s=DRAIN_CAP_S,
        scoring=traffic.scoring_requests(seed, cf["vocab_size"]),
        gauge_every_s=0.5 if trace else None,
        trace={"start_after_s": max(0.5, seconds / 2 - 1.5),
               "seconds": min(3.0, seconds / 3)} if trace else None)
    return plan


# ---------------------------------------------------------------------
# The server in this (main) thread, the load generator as a child
# ---------------------------------------------------------------------
class _Recorder(logging.Handler):

    def __init__(self, sink: list, level=logging.INFO) -> None:
        super().__init__(level)
        self.sink = sink

    def emit(self, record: logging.LogRecord) -> None:
        self.sink.append((time.monotonic(), record.getMessage()))


class CompiledInSetup(Exception):
    """The engine's start missed the compile cache; nothing was served."""


class Run:

    def __init__(self) -> None:
        self.log: list[tuple[float, str]] = []
        # (time, kind) of every persistent-cache hit/miss and backend
        # compile, from JAX's own monitoring.
        self.jax_events: list[tuple[float, str]] = []
        # (time, message) of JAX's own "Compiling <program> ..." lines.
        self.compiles: list[tuple[float, str]] = []

    def listen(self) -> None:
        import jax
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        from vllm_distributed_tpu.logger import init_logger
        init_logger("benchmarks")
        root = logging.getLogger("vllm_distributed_tpu")
        for h in root.handlers:
            if isinstance(h, logging.StreamHandler):
                h.setStream(sys.stderr)
        root.addHandler(_Recorder(self.log))
        # JAX names every program it compiles at WARNING level when asked
        # to; recorded with its time so that a compile inside the window
        # can be named.
        jax.config.update("jax_log_compiles", True)
        jl = logging.getLogger("jax")
        jl.addHandler(_Recorder(self.compiles, logging.WARNING))
        jl.propagate = False

    def _on_event(self, event: str, **_) -> None:
        kind = event.rpartition("/jax/compilation_cache/cache_")[2]
        if kind in ("hits", "misses"):
            self.jax_events.append((time.monotonic(), kind))

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.jax_events.append((time.monotonic(), "backend_compile"))

    def count(self, kind: str, t0=-1e30, t1=1e30) -> int:
        return sum(1 for t, k in self.jax_events if k == kind
                   and t0 <= t < t1)

    def serve(self, args, plan_for_port, stop_if_compiled=False) -> dict:
        """run_server until the load generator has ended; returns the
        generator's result file, parsed. With ``stop_if_compiled`` the
        server is ended as soon as it is healthy, and no traffic sent, if
        its start missed the compile cache."""
        from vllm_distributed_tpu.entrypoints.openai.api_server import \
            run_server
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        plan = plan_for_port(port)
        plan_path = plan["out"] + ".plan"
        with open(plan_path, "w") as f:
            json.dump(plan, f)
        box: dict = {}
        gone = threading.Event()

        def client():
            try:
                self.wait_healthy(port, gone)
                box["healthy"] = time.monotonic()
                if stop_if_compiled and self.count("misses"):
                    raise CompiledInSetup(
                        f"{self.count('misses')} programs compiled, ready "
                        f"after {box['healthy'] - T_PROCESS_START:.1f} s")
                env = {k: v for k, v in os.environ.items()
                       if k != "BENCH_RUN"}
                box["proc"] = subprocess.Popen(
                    [sys.executable, "-m", "benchmarks.loadgen", plan_path],
                    cwd=REPO, env=env, stdout=sys.stderr)
                box["loadgen_rc"] = box["proc"].wait()
            except BaseException as e:  # noqa: BLE001 - re-raised below
                box["error"] = e
            finally:
                if not gone.is_set():
                    os.kill(os.getpid(), signal.SIGTERM)

        thread = threading.Thread(target=client, name="bench-client")
        thread.start()
        try:
            run_server(args, host="127.0.0.1", port=port)
        finally:
            gone.set()
            if "proc" in box and box["proc"].poll() is None:
                box["proc"].terminate()   # the server died under it
            thread.join()                 # waits for the child too
        if "error" in box:
            raise box["error"]
        if box.get("loadgen_rc") != 0:
            raise RuntimeError(f"load generator: {box.get('loadgen_rc')}")
        result = load_json(plan["out"])
        result["healthy"] = box["healthy"]
        return result

    @staticmethod
    def wait_healthy(port: int, gone) -> None:
        import http.client
        while not gone.is_set():
            try:
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=5)
                conn.request("GET", "/health")
                ok = conn.getresponse().status == 200
                conn.close()
                if ok:
                    return
            except OSError:
                pass
            time.sleep(0.25)
        raise RuntimeError("the server exited before /health answered")

    def startup_facts(self) -> dict:
        """Start-up split, read off the program's own log lines."""
        pats = {
            "load_s": r"^model loaded in ([\d.]+)s",
            "kv_pages": r"^allocating (\d+) KV pages",
            "kv_pool_gib": r"^HBM for KV: ([\d.]+) GiB",
            "precompile": r"^precompiled (\d+) graphs in ([\d.]+)s",
        }
        facts: dict = {}
        for _, msg in self.log:
            for key, pat in pats.items():
                m = re.match(pat, msg)
                if m and key not in facts:
                    facts[key] = [float(g) for g in m.groups()]
        return {k: v[0] if len(v) == 1 else v for k, v in facts.items()}


# ---------------------------------------------------------------------
# The comparison that decides `correct`
# ---------------------------------------------------------------------
def compare_with_reference(cell: dict, args, scoring: list) -> dict:
    """Served prompt and greedy logprobs against the plain reference on
    the same seeded weights (made again by the program's own loader now
    that the server's are freed). Every sequence is padded to one length
    (causal: what follows a position cannot change it), so the reference
    compiles once per control."""
    import jax
    import jax.numpy as jnp

    from vllm_distributed_tpu.models.loader import get_model
    from vllm_distributed_tpu.parallel.mesh import build_mesh
    cf = cell["config_file"]
    cfg = hf_config(cf)
    ref = importlib.import_module(f"benchmarks.references.{cf['reference']}")
    arch = cfg["architectures"][0]
    config = args.create_engine_config()
    config.model_config.maybe_load_hf_config()
    mesh = build_mesh(config.parallel_config, jax.devices()[:1])
    _, params = get_model(config, mesh)
    S = max(len(s["tokens"]) for s in scoring)

    def table(seq, control):
        ids = jnp.asarray(seq + [0] * (S - len(seq)), jnp.int32)
        with jax.default_matmul_precision("highest"):
            return fn(params, ids, control)

    fn = jax.jit(lambda p, ids, control: ref.logprobs(p, cfg, ids, control),
                 static_argnums=2)

    def deviations(s, control=None):
        seq = s["tokens"]
        tab = table(seq, control)
        tgt = jnp.asarray(seq[1:], jnp.int32)
        want = jax.device_get(jnp.take_along_axis(
            tab[:len(seq) - 1], tgt[:, None], 1)[:, 0])
        got = s["token_logprobs"][1:]
        return [abs(float(w) - g) for w, g in zip(want, got)]

    tol = cf["tolerance"]
    devs = [d for s in scoring for d in deviations(s)]
    out = {"positions": len(devs), "max_abs_logprob_dev": max(devs),
           "mean_abs_logprob_dev": sum(devs) / len(devs),
           "tolerance": tol, "controls": {}}
    ok = out["max_abs_logprob_dev"] <= tol["max"] and \
        out["mean_abs_logprob_dev"] <= tol["mean"]
    for control in ref.CONTROLS[arch]:
        d = [x for s in scoring for x in deviations(s, control)]
        c = {"max": max(d), "mean": sum(d) / len(d)}
        # A control must miss by three times the tolerance or more, or
        # the comparison proves nothing about that path.
        c["caught"] = c["max"] >= 3 * tol["max"] and \
            c["mean"] >= 3 * tol["mean"]
        out["controls"][control] = c
        ok = ok and c["caught"]
    out["ok"] = bool(ok)
    del params
    return out


def compared_numbers(comparison: dict, in_window: dict) -> list[str]:
    """Each number that decided ``correct`` beside its limit, one line
    each: the result line carries them and the run's standard error ends
    with them."""
    tol = comparison.get("tolerance")
    rows = [(f"compiled_in_window.{k}", v, "<=", 0)
            for k, v in in_window.items()]
    if tol is None:
        rows.append(("reference.error", str(comparison.get("error"))[:200],
                     "==", None))
    else:
        for key in ("max", "mean"):
            rows.append((f"reference.{key}_abs_logprob_dev",
                         comparison[f"{key}_abs_logprob_dev"], "<=",
                         tol[key]))
            rows += [(f"control.{name}.{key}", c[key], ">=", 3 * tol[key])
                     for name, c in comparison["controls"].items()]
    return [f"compared {name} {value} limit {op} {limit}"
            for name, value, op, limit in rows]


# ---------------------------------------------------------------------
def device_facts(chips: int, require_platform: str = "tpu") -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != require_platform or len(devs) != chips:
        raise SystemExit(
            f"benchmarks.run: need {chips} {require_platform} device(s), "
            f"found {dev}")
    if dev["platform"] == "tpu" and layers.load_peaks(dev["kind"]) is None:
        raise SystemExit(f"benchmarks.run: no peaks for device kind "
                         f"{dev['kind']!r} in benchmarks/peaks.json")
    return dev


def memory_peak() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             data_root: str = HERE, manifest_path: str = MANIFEST,
             require_platform: str = "tpu", ramp_s=None,
             stop_if_compiled: bool = False) -> dict:
    """Everything but printing the last line. ``require_platform`` and
    ``data_root`` exist for the tests' tiny CPU configuration; the
    command line cannot set them. ``stop_if_compiled``: see
    ``Run.serve``."""
    manifest = load_json(manifest_path)
    cell = load_cell(workload, data_root)
    os.environ.setdefault("HF_HUB_OFFLINE", "1")
    trace_dir = os.path.join(WORK, cell["config"], "trace")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.environ["VDT_PROFILER_DIR"] = trace_dir
    device = device_facts(cell["chips"], require_platform)
    run = Run()
    run.listen()
    emit("devices", device=device, workload=workload, seed=seed,
         seconds=seconds, trace=trace,
         compile_cache_env=os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    model_dir = write_model_dir(cell["config"], cell["config_file"])
    args = engine_args(model_dir, cell["config_file"], seed)
    out_path = os.path.join(WORK, cell["config"], "loadgen.json")

    def plan_for_port(port):
        plan = build_plan(cell, seed, seconds, trace, port, out_path, ramp_s)
        emit("traffic", loop=plan["loop"], load=cell["load"],
             **traffic.describe(plan))
        return plan

    res = run.serve(args, plan_for_port, stop_if_compiled)
    marks, t0, t1 = res["marks"], res["marks"]["t0"], res["marks"]["t1"]
    gc.collect()
    device["memory_peak_bytes"] = memory_peak()
    emit("startup", ready_s=res["healthy"] - T_PROCESS_START,
         cache_hits=run.count("hits", t1=t0),
         cache_misses=run.count("misses", t1=t0), **run.startup_facts())

    loop = cell["mix"]["loop"]
    e2e = metrics.end_to_end(res["records"], loop, t0, t1)
    e2e["metrics"]["setup_s"] = t0 - T_PROCESS_START
    before = metrics.parse_prometheus(res["scrapes"]["before"])
    after = metrics.parse_prometheus(res["scrapes"]["after"])
    in_window = {
        "recompiles": metrics.counter_delta(before, after,
                                            "vdt:recompiles_total"),
        "cache_misses": run.count("misses", t0, t1),
        "backend_compiles": run.count("backend_compile", t0, t1)}
    errors = sorted({r["error"] for r in res["records"] if r.get("error")})
    named = [m[:160] for t, m in run.compiles
             if t0 <= t < t1 and "ompiling" in m]
    emit("window", compiled_names=named[:12], attempted=e2e["attempted"], failed=e2e["failed"],
         samples=e2e["samples"], in_flight_at_end=marks.get(
             "in_flight_at_end"), drain_s=marks["drained"] - t1,
         compiled_in_window=in_window, errors=errors[:5],
         all_metrics=e2e["metrics"])

    scoring = res["scoring"]
    bad = [s for s in scoring if "error" in s]
    if bad or not scoring:
        comparison = {"ok": False, "error": bad[:1] or "no scoring request"}
    else:
        comparison = compare_with_reference(cell, args, scoring)
    emit("reference", **comparison)
    correct = bool(comparison["ok"]) and not any(in_window.values())
    compared = compared_numbers(comparison, in_window)

    if trace:
        gauges = [(t, metrics.parse_prometheus(text))
                  for t, text in res["gauges"]]
        ctx = layers.Context(
            before=before, after=after, gauges=[g for _, g in gauges],
            client=e2e["metrics"], trace_dir=trace_dir,
            config=cell["config_file"], device_kind=device["kind"],
            capture=layers.capture_scrapes(gauges, marks))
        wanted = cell_metrics(manifest, workload, "per_layer")
        values = layers.evaluate_all(wanted, ctx)
        device.update(ctx.device_times())
        breakdown = ctx.breakdown()
        emit("trace", **ctx.facts())
    else:
        wanted = cell_metrics(manifest, workload, "end_to_end")
        values = {m["name"]: e2e["metrics"].get(m["name"]) for m in wanted}
        breakdown = None
    units = {m["name"]: m["unit"] for m in wanted}
    line = {"correct": correct, "attempted": e2e["attempted"],
            "failed": e2e["failed"],
            "metrics": {k: {"value": v, "unit": units[k]}
                        for k, v in values.items() if v is not None},
            "device": device, "compared": compared}
    if breakdown:
        line["breakdown"] = breakdown
    return line


def supervise(argv: list[str]) -> int:
    """Start the worker; once more if it compiled during set-up. Ends the
    worker when it is itself told to end, and waits for it."""
    env = dict(os.environ, **{START_ENV: repr(T_PROCESS_START)})
    child: list[subprocess.Popen] = []
    told_to_end: list = []

    def forward(*_):
        told_to_end.append(True)
        for c in child:
            if c.poll() is None:
                c.terminate()

    signals = (signal.SIGTERM, signal.SIGINT)
    before = [signal.signal(sig, forward) for sig in signals]
    try:
        rc = RESTART_RC
        for attempt in ("1", "2"):
            child.append(subprocess.Popen(
                [sys.executable, "-m", "benchmarks.run", *argv], cwd=REPO,
                env=dict(env, **{WORKER_ENV: attempt})))
            rc = child[-1].wait()
            if rc != RESTART_RC or told_to_end:
                break
    finally:
        for sig, handler in zip(signals, before):
            signal.signal(sig, handler)
    return rc if 0 <= rc < 256 else 1


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    attempt = os.environ.get(WORKER_ENV)
    if attempt is None:
        return supervise(argv)
    try:
        line = run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                        stop_if_compiled=attempt == "1")
    except CompiledInSetup as e:
        emit("restart", why=str(e))
        return RESTART_RC
    except BaseException as e:  # noqa: BLE001 - every failure exits 1
        import traceback
        traceback.print_exc()
        print(f"benchmarks.run failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    print(json.dumps(line), flush=True)
    print("\n".join(line["compared"]), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
