#!/usr/bin/env python3
"""Serve Llama-3.2-1B on the chip through the normal entry points and
check what comes out.

    python chip_smoke.py                  # one chip: the server path
    python chip_smoke.py --chips 4        # four chips: TP=4 (+ TKNP 2x2)
    python chip_smoke.py --cpu-rehearsal  # tiny width on the CPU

One process holds the chip from start to end: the server runs in the main
thread exactly as ``vdt serve`` runs it (``run_server``), the HTTP client
is a thread that uses no JAX, and it ends the server the way an
orchestrator does, with SIGTERM. Every line of standard output is one
JSON object naming its phase; the last is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed check, any exception, a platform other than the one asked for
or a wrong device count ends the run with exit code 1 and ``"ok": false``.
Times printed here are set-up facts, not performance results.
"""

import argparse
import gc
import http.client
import json
import logging
import math
import os
import re
import signal
import socket
import sys
import tempfile
import threading
import time

# Llama-3.2-1B as published (meta-llama/Llama-3.2-1B config.json).
LLAMA_3_2_1B = {
    "architectures": ["LlamaForCausalLM"],
    "attention_bias": False,
    "attention_dropout": 0.0,
    "bos_token_id": 128000,
    "eos_token_id": 128001,
    "head_dim": 64,
    "hidden_act": "silu",
    "hidden_size": 2048,
    "initializer_range": 0.02,
    "intermediate_size": 8192,
    "max_position_embeddings": 131072,
    "mlp_bias": False,
    "model_type": "llama",
    "num_attention_heads": 32,
    "num_hidden_layers": 16,
    "num_key_value_heads": 8,
    "pretraining_tp": 1,
    "rms_norm_eps": 1e-05,
    "rope_scaling": {
        "factor": 32.0,
        "high_freq_factor": 4.0,
        "low_freq_factor": 1.0,
        "original_max_position_embeddings": 8192,
        "rope_type": "llama3",
    },
    "rope_theta": 500000.0,
    "tie_word_embeddings": True,
    "torch_dtype": "bfloat16",
    "use_cache": True,
    "vocab_size": 128256,
}

# The same architecture at a width the CPU interpreter finishes in
# seconds. head_dim stays 64 so the rehearsal exercises the lane-padded
# KV layout (see _steer_padded_layout).
REHEARSAL_MODEL = dict(
    LLAMA_3_2_1B, hidden_size=128, intermediate_size=256,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    vocab_size=512, torch_dtype="float32")

# Engine limits a 16 GB chip would be deployed with / rehearsal sizes.
# Three bf16 bounds on logprobs, each with its own job:
# - mean_tol: the mean deviation from the plain reference over every
#   scored position. The tight one: measured 0.021 on the chip, and any
#   systematic fault (layout, rope, a page write) moves the mean.
# - tol: the largest single deviation from the reference (or, across
#   chips, from the one-device engine). Measured 0.075-0.079 as the worst
#   of ~300 positions after 16 bf16 layers; the bound is twice that so a
#   new seed or another tiling does not trip it, and it is still 39x
#   below what a model whose attention is cut out shows (5.88).
# - tie_tol: how far apart, in the first run's own ranking, two tokens
#   may be where two greedy runs of one prompt part. Two paths that each
#   sit within d of the true logprobs can swap only tokens that are
#   within 2d of each other; d is the typical single-position deviation
#   (0.04: the mean is 0.02, the repeat run's worst 0.040), so 0.08.
#   Seeded random weights put the top two about 0.2 apart, so this
#   admits a flip at a near tie and nothing else. The one flip seen on
#   the chip (repeat through the prefix cache, token 1) had 0.023.
REAL = dict(dtype="bfloat16", max_model_len=4096,
            max_num_batched_tokens=2048, max_num_seqs=64,
            fixed_prompt=300, long_prompt=3000, streams=8,
            stream_tokens=96, tol=0.15, mean_tol=0.05, tie_tol=0.08)
TINY = dict(dtype="float32", max_model_len=512, max_num_batched_tokens=32,
            max_num_seqs=8, fixed_prompt=40, long_prompt=100, streams=4,
            stream_tokens=16, tol=2e-3, mean_tol=1e-3, tie_tol=2e-3)

GREEDY_TOKENS = 8
PHASES = ("devices", "cold_start", "requests", "metrics", "reference",
          "warm_start", "one_device", "tp4", "tknp2_tp2")


class CheckFailed(AssertionError):
    pass


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


class Smoke:

    def __init__(self, args) -> None:
        self.args = args
        self.size = TINY if args.cpu_rehearsal else REAL
        self.model = REHEARSAL_MODEL if args.cpu_rehearsal else LLAMA_3_2_1B
        self.vocab = self.model["vocab_size"]
        self.device = None
        self.records: list[tuple[float, str]] = []
        # Persistent compile cache traffic, as JAX's own monitoring
        # reports it (the listener is registered in devices()).
        self.cache_events = {"hits": 0, "misses": 0}

    def phase(self, name: str, fn, *a, **kw):
        """Run one phase; ``--inject-failure NAME`` fails it after it ran
        (proof that no phase's failure can end in exit code 0)."""
        out = fn(*a, **kw)
        check(self.args.inject_failure != name,
              f"injected failure in phase {name!r}")
        return out

    # ------------------------------------------------------------------
    def devices(self) -> None:
        import importlib.metadata as md

        import jax
        import jaxlib
        jax.monitoring.register_event_listener(self.on_jax_event)
        devs = jax.devices()
        self.device = {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}
        try:
            libtpu = md.version("libtpu")
        except md.PackageNotFoundError:
            libtpu = None
        emit("devices", device=self.device, jax=jax.__version__,
             jaxlib=jaxlib.__version__, libtpu=libtpu,
             compile_cache_env=os.environ.get("JAX_COMPILATION_CACHE_DIR"))
        want = "cpu" if self.args.cpu_rehearsal else "tpu"
        check(self.device["platform"] == want,
              f"platform is {self.device['platform']!r}, need {want!r}")
        check(len(devs) == self.args.chips,
              f"{len(devs)} devices visible, {self.args.chips} asked for")

    def on_jax_event(self, event: str, **_) -> None:
        kind = event.rpartition("/jax/compilation_cache/cache_")[2]
        if kind in self.cache_events:
            self.cache_events[kind] += 1

    def write_model_dir(self) -> str:
        path = tempfile.mkdtemp(prefix="chip_smoke_model_")
        with open(os.path.join(path, "config.json"), "w") as f:
            json.dump(self.model, f)
        return path

    def engine_args(self, model_dir: str, **over):
        from vllm_distributed_tpu.engine.arg_utils import EngineArgs
        s = self.size
        return EngineArgs(
            model=model_dir, skip_tokenizer_init=True, load_format="dummy",
            seed=self.args.seed, dtype=s["dtype"], block_size=16,
            max_model_len=s["max_model_len"],
            max_num_batched_tokens=s["max_num_batched_tokens"],
            max_num_seqs=s["max_num_seqs"], **over)

    def prompt(self, n: int, salt: int) -> list[int]:
        """Deterministic token ids from --seed (no tokenizer)."""
        import numpy as np
        rng = np.random.default_rng([self.args.seed, salt])
        return [int(t) for t in rng.integers(1, self.vocab, size=n)]

    # ------------------------------------------------------------------
    # The server, as `vdt serve` runs it, and its HTTP client thread
    # ------------------------------------------------------------------
    def serve(self, model_dir: str, client) -> dict:
        """run_server in this (main) thread until the client thread,
        done or failed, sends SIGTERM; re-raises what the client raised."""
        from vllm_distributed_tpu.entrypoints.openai.api_server import \
            run_server
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        box: dict = {"t0": time.monotonic()}
        server_gone = threading.Event()

        def run_client():
            try:
                self.wait_healthy(port, server_gone)
                box["ready_s"] = time.monotonic() - box["t0"]
                box["result"] = client(port)
            except BaseException as e:  # noqa: BLE001 - re-raised below
                box["error"] = e
            finally:
                if not server_gone.is_set():
                    os.kill(os.getpid(), signal.SIGTERM)

        self.records.clear()
        box["cache_files_before"] = self.cache_files()
        events_before = dict(self.cache_events)
        thread = threading.Thread(target=run_client, name="smoke-client")
        thread.start()
        try:
            run_server(self.engine_args(model_dir), host="127.0.0.1",
                       port=port)
        finally:
            server_gone.set()
            thread.join()
        if "error" in box:
            raise box["error"]
        check("result" in box, "the server stopped before the client ran")
        box["startup"] = dict(
            self.startup_facts(),
            cache_files_before=box["cache_files_before"],
            cache_files_after=self.cache_files(),
            **{f"cache_{k}": v - events_before[k]
               for k, v in self.cache_events.items()})
        return box

    @staticmethod
    def cache_files() -> int:
        """Entries in the persistent compile cache, wherever the worker
        keeps it (JAX_COMPILATION_CACHE_DIR, else the in-checkout path)."""
        from vllm_distributed_tpu.worker.worker import \
            DEFAULT_COMPILE_CACHE_DIR
        path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                or DEFAULT_COMPILE_CACHE_DIR)
        return len(os.listdir(path)) if os.path.isdir(path) else 0

    def wait_healthy(self, port: int, server_gone) -> None:
        while not server_gone.is_set():
            try:
                status, _ = http_json(port, "GET", "/health", timeout=5)
                if status == 200:
                    return
            except OSError:
                pass
            time.sleep(0.5)
        raise CheckFailed("the server exited before /health answered")

    def startup_facts(self) -> dict:
        """Cold-start split read off the program's own log lines (set-up
        facts; the log handler is installed in main())."""
        facts: dict = {}
        stamps: dict = {}
        pats = {
            "devices": r"^devices: ",
            "loaded": r"^model loaded in ([\d.]+)s",
            "profiled": r"^profiled HBM: ([\d.]+) GiB in use \(weights\) \+ "
                        r"([\d.]+) GiB program workspace",
            "pool": r"^HBM for KV: ([\d.]+) GiB -> (\d+) pages of (\d+) "
                    r"bytes",
            "default_pool": r"^no memory stats; defaulting to (\d+) KV",
            "allocating": r"^allocating (\d+) KV pages",
            "precompiled": r"^precompiled (\d+) graphs in ([\d.]+)s",
        }
        for t, msg in self.records:
            for key, pat in pats.items():
                m = re.match(pat, msg)
                if m and key not in stamps:
                    stamps[key] = (t, m.groups())
        if "loaded" in stamps:
            facts["load_s"] = float(stamps["loaded"][1][0])
        if "profiled" in stamps and "loaded" in stamps:
            facts["hbm_profile_s"] = round(
                stamps["profiled"][0] - stamps["loaded"][0], 2)
            facts["weights_in_use_gib"] = float(stamps["profiled"][1][0])
            facts["program_workspace_gib"] = float(stamps["profiled"][1][1])
        if "pool" in stamps:
            gib, pages, page_bytes = stamps["pool"][1]
            facts.update(kv_pool_gib=float(gib), kv_pages_profiled=int(pages),
                         kv_page_bytes=int(page_bytes))
        if "default_pool" in stamps:
            facts["kv_pages_default"] = int(stamps["default_pool"][1][0])
        if "allocating" in stamps:
            facts["kv_pages"] = int(stamps["allocating"][1][0])
        if "precompiled" in stamps:
            graphs, secs = stamps["precompiled"][1]
            facts.update(precompile_graphs=int(graphs),
                         precompile_s=float(secs))
        return facts

    # ------------------------------------------------------------------
    def complete(self, port: int, prompt: list[int], max_tokens: int,
                 **body) -> dict:
        """One /v1/completions request; checks status, token count and
        vocabulary range, returns ids + logprobs."""
        body = {"model": "smoke", "prompt": prompt,
                "max_tokens": max_tokens, "ignore_eos": True,
                "logprobs": 5, **body}
        status, out = http_json(port, "POST", "/v1/completions", body,
                                timeout=600)
        check(status == 200, f"/v1/completions -> {status}: {out}")
        lp = out["choices"][0]["logprobs"]
        ids = [int(t) for t in lp["tokens"]]
        n_prompt = len(prompt) if body.get("echo") else 0
        check(len(ids) - n_prompt == max_tokens,
              f"asked for {max_tokens} tokens, got {len(ids) - n_prompt}")
        check(out["usage"]["completion_tokens"] == max_tokens,
              f"usage says {out['usage']}")
        check(all(0 <= t < self.vocab for t in ids),
              "token id outside the vocabulary")
        lps = lp["token_logprobs"]
        check(all(v is None or math.isfinite(v) for v in lps),
              "non-finite logprob")
        top = [{int(t): v for t, v in (d or {}).items()}
               for d in lp["top_logprobs"][n_prompt:]]
        return {"ids": ids[n_prompt:], "lps": lps[n_prompt:],
                "prompt_lps": lps[:n_prompt], "top": top}

    def tie_facts(self, a: dict, b: dict) -> dict:
        """Where two greedy runs of one prompt part, and how far apart
        run ``a`` itself ranks the two tokens there (None: ``b``'s token
        is not among ``a``'s top candidates at all)."""
        n = len(a["ids"])
        facts: dict = {"greedy_tokens_agreeing": n, "of": n}
        for i, (x, y) in enumerate(zip(a["ids"], b["ids"])):
            if x != y:
                other = a["top"][i].get(y)
                facts.update(
                    greedy_tokens_agreeing=i,
                    first_divergence={
                        "token": i, "ids": [x, y],
                        "gap_in_first_run": (None if other is None else
                                             a["lps"][i] - other)},
                    tie_tolerance=self.size["tie_tol"])
                break
        return facts

    def check_ties(self, what: str, facts: dict) -> None:
        """Greedy ids of two runs of one prompt must agree up to a near
        tie flipped by bf16 rounding on another compute path (prefix
        cache, another batch shape, another reduction order); anything
        further apart is a fault. See tie_tol above."""
        div = facts.get("first_divergence")
        if div is not None:
            gap, tie = div["gap_in_first_run"], self.size["tie_tol"]
            check(gap is not None and gap <= tie,
                  f"{what}: ids part at token {div['token']} "
                  f"({div['ids'][0]} vs {div['ids'][1]}) and it is no tie: "
                  f"the first run ranks them {gap} apart (tolerance {tie})")

    def traffic(self, port: int) -> dict:
        """(a) pure prefill, (b) concurrent decodes over mixed waves,
        (c) a prompt longer than the token budget, (d) a repeated prompt
        through the prefix cache."""
        s = self.size
        fixed = self.prompt(s["fixed_prompt"], salt=1)
        t0 = time.monotonic()
        first = self.complete(port, fixed, GREEDY_TOKENS, temperature=0)
        first_request_s = time.monotonic() - t0
        emit("requests", name="prefill_greedy", prompt_tokens=len(fixed),
             tokens=len(first["ids"]), first_request_s=round(
                 first_request_s, 3))

        results: dict = {}
        errors: list = []

        def worker(i: int):
            try:
                time.sleep(0.25 * (i // 2))  # arrivals land mid-decode
                p = self.prompt(24 + 23 * i, salt=100 + i)
                kw = ({"temperature": 0} if i % 2 == 0 else
                      {"temperature": 0.8, "top_p": 0.95, "seed": i})
                if i == 1:
                    results[i] = self.sse(port, p, s["stream_tokens"], **kw)
                else:
                    results[i] = self.complete(port, p, s["stream_tokens"],
                                               **kw)
            except BaseException as e:  # noqa: BLE001 - re-raised below
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(i, ))
                   for i in range(s["streams"])]
        long_prompt = self.prompt(s["long_prompt"], salt=2)
        long_out: dict = {}

        def long_worker():
            try:
                time.sleep(0.5)
                long_out.update(self.complete(port, long_prompt, 16,
                                              temperature=0))
            except BaseException as e:  # noqa: BLE001 - re-raised below
                errors.append(e)

        threads.append(threading.Thread(target=long_worker))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        emit("requests", name="concurrent_streams",
             streams=s["streams"], tokens_each=s["stream_tokens"],
             sse_streams=1)
        emit("requests", name="chunked_prefill",
             prompt_tokens=len(long_prompt),
             token_budget=s["max_num_batched_tokens"],
             tokens=len(long_out["ids"]))

        hits_before = self.metric_sum(port, "vdt:prefix_cache_hits_total")
        again = self.complete(port, fixed, GREEDY_TOKENS, temperature=0)
        hits_after = self.metric_sum(port, "vdt:prefix_cache_hits_total")
        ties = self.tie_facts(first, again)
        emit("requests", name="repeat_prefix_cache",
             prefix_cache_hits=hits_after - hits_before, **ties)
        self.check_ties("repeat through the prefix cache", ties)
        check(hits_after > hits_before,
              "the repeated prompt did not hit the prefix cache")

        # The same prompt scored position by position (echo) for the
        # reference comparison; prompt-logprob requests recompute.
        echo = self.complete(port, fixed, GREEDY_TOKENS, temperature=0,
                             echo=True)
        ties = self.tie_facts(first, echo)
        emit("requests", name="repeat_echo", **ties)
        self.check_ties("repeat with echo", ties)
        return {"fixed": fixed, "first": first, "repeat": again,
                "echo": echo, "first_request_s": first_request_s}

    def sse(self, port: int, prompt: list[int], max_tokens: int,
            **body) -> dict:
        """One streamed completion: must end with a `length` finish and
        [DONE]. (Without a tokenizer the deltas carry no text; `length`
        says the engine stopped at max_tokens.)"""
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
        conn.request("POST", "/v1/completions", json.dumps(
            {"model": "smoke", "prompt": prompt, "max_tokens": max_tokens,
             "ignore_eos": True, "stream": True, **body}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        check(resp.status == 200, f"stream -> {resp.status}")
        lines = [ln for ln in resp.read().decode().splitlines()
                 if ln.startswith("data: ")]
        conn.close()
        check(lines and lines[-1] == "data: [DONE]", "stream without [DONE]")
        last = json.loads(lines[-2][len("data: "):])
        check(last["choices"][0]["finish_reason"] == "length",
              f"stream finished with {last['choices'][0]}")
        return {}

    # ------------------------------------------------------------------
    def metric_samples(self, port: int) -> list[tuple[str, dict, float]]:
        status, text = http_json(port, "GET", "/metrics", timeout=60,
                                 raw=True)
        check(status == 200, f"/metrics -> {status}")
        out = []
        for line in text.splitlines():
            m = re.match(r"^([a-zA-Z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$", line)
            if m and not line.startswith("#"):
                labels = dict(re.findall(r'(\w+)="([^"]*)"', m.group(2) or ""))
                out.append((m.group(1), labels, float(m.group(3))))
        return out

    def metric_sum(self, port: int, name: str) -> float:
        return sum_metric(self.metric_samples(port), name)

    def metrics(self, port: int) -> dict:
        status, _ = http_json(port, "GET", "/health", timeout=30)
        check(status == 200, f"/health -> {status}")
        samples = self.metric_samples(port)

        def total(name):
            return sum_metric(samples, name)

        kernels = {lab["kernel"]: v for n, lab, v in samples
                   if n == "vdt:attn_kernel_calls_total"}
        facts = {
            "attn_kernel_calls": kernels,
            "num_recompiles": total("vdt:recompiles_total"),
            "precompile_graphs": total("vdt:precompile_graphs_total"),
            "nan_steps": total("vdt:logits_nan_steps_total"),
            "kv_blocks": {lab.get("state"): v for n, lab, v in samples
                          if n == "vdt:kv_blocks"},
            "peak_hbm_bytes": total("vdt:device_memory_peak_bytes"),
        }
        emit("metrics", **facts)
        check(kernels.get("unified", 0) > 0, "no unified kernel dispatch")
        check(set(kernels) == {"unified"},
              f"attention left the unified kernel: {kernels}")
        check(facts["num_recompiles"] == 0,
              f"{facts['num_recompiles']} recompiles after warm-up")
        check(facts["precompile_graphs"] > 0, "nothing was precompiled")
        check(facts["nan_steps"] == 0, "NaN-step counter raised")
        return facts

    def client(self, port: int) -> dict:
        out = self.phase("requests", self.traffic, port)
        out["metrics"] = self.phase("metrics", self.metrics, port)
        return out

    def warm_client(self, served: dict):
        def run(port: int) -> dict:
            again = self.complete(port, served["fixed"], GREEDY_TOKENS,
                                  temperature=0)
            ties = self.tie_facts(served["first"], again)
            emit("warm_start", name="same_prompt", **ties)
            self.check_ties("across starts", ties)
            return {"metrics": self.metrics(port)}
        return run

    def check_pool(self, startup: dict) -> None:
        if self.args.cpu_rehearsal:
            # The CPU backend reports no memory: the documented default.
            check("kv_pages_default" in startup, "no KV pool size logged")
            return
        check("kv_pages_profiled" in startup and
              "kv_pages_default" not in startup,
              f"the KV pool was not sized by the HBM profile: {startup}")
        check(startup["kv_pages"] == startup["kv_pages_profiled"],
              f"allocated pool differs from the profiled one: {startup}")

    # ------------------------------------------------------------------
    # The plain reference: dense causal attention in jnp, no paged
    # cache, no Pallas, parameters from the same loader and seed
    # ------------------------------------------------------------------
    def reference(self, model_dir: str, served: dict) -> None:
        import jax
        import jax.numpy as jnp

        from vllm_distributed_tpu.models.loader import get_model
        from vllm_distributed_tpu.parallel.mesh import build_mesh
        config = self.engine_args(model_dir).create_engine_config()
        mesh = build_mesh(config.parallel_config, jax.devices()[:1])
        _, params = get_model(config, mesh)
        lp = jax.jit(lambda p, t, attend: reference_logprobs(
            p, self.model, t, attend), static_argnums=2)
        tol = self.size["tol"]
        n_gen = GREEDY_TOKENS

        def scored(run: dict, attend: bool = True):
            """The reference's logprob of each served token (teacher
            forced on the run's own ids) and its own best logprob."""
            seq = served["fixed"] + run["ids"]
            table = lp(params, jnp.asarray(seq[:-1], jnp.int32), attend)
            tgt = jnp.asarray(seq[1:], jnp.int32)
            return (jax.device_get(jnp.take_along_axis(
                table, tgt[:, None], 1)[:, 0]),
                jax.device_get(jnp.max(table, axis=-1)))

        # Each of the three runs of the fixed prompt took another path
        # (full prefill; prefix cache + short prefill; prompt scoring).
        for name in ("first", "repeat", "echo"):
            run = served[name]
            ref, ref_max = scored(run)
            got = run["prompt_lps"][1:] + run["lps"]
            ref = ref[len(ref) - len(got):]
            dev = [abs(float(r) - g) for r, g in zip(ref, got)]
            # A served greedy token must be the reference's argmax or
            # tie it.
            gap = [float(m - r) for m, r in
                   zip(ref_max[-n_gen:], ref[-n_gen:])]
            facts = dict(run=name, positions=len(dev),
                         max_abs_logprob_dev=max(dev),
                         mean_abs_logprob_dev=sum(dev) / len(dev),
                         greedy_max_gap_to_argmax=max(gap), tolerance=tol)
            if name == "echo":
                # Negative control: the same comparison against a
                # reference whose attention output is zeroed. If that
                # passed too, the check would prove nothing about the
                # attention path.
                blind, _ = scored(run, attend=False)
                blind_dev = [abs(float(b) - g) for b, g in zip(blind, got)]
                facts.update(
                    control_no_attention_max_dev=max(blind_dev),
                    control_no_attention_mean_dev=(sum(blind_dev) /
                                                   len(blind_dev)))
            emit("reference", **facts)
            check(max(dev) <= tol,
                  f"{name}: served logprobs deviate {max(dev):.4f} from "
                  f"the plain reference (tolerance {tol})")
            check(sum(dev) / len(dev) <= self.size["mean_tol"],
                  f"{name}: served logprobs deviate "
                  f"{sum(dev) / len(dev):.4f} on average from the plain "
                  f"reference (tolerance {self.size['mean_tol']})")
            check(max(gap) <= tol,
                  f"{name}: a served greedy token is {max(gap):.4f} below "
                  f"the reference's argmax (tolerance {tol})")
            if name == "echo":
                check(max(blind_dev) > 3 * tol,
                      "the comparison cannot tell a model without "
                      "attention from the served one")
        del params

    # ------------------------------------------------------------------
    def run_one_chip(self) -> None:
        model_dir = self.write_model_dir()
        cold = self.phase("cold_start", self.serve, model_dir, self.client)
        served = cold["result"]
        emit("cold_start", ready_s=round(cold["ready_s"], 2),
             first_request_s=round(served["first_request_s"], 3),
             **cold["startup"])
        self.check_pool(cold["startup"])
        emit("cold_start", name="after_shutdown",
             live_array_bytes=self.live_bytes())
        self.phase("reference", self.reference, model_dir, served)
        warm = self.phase("warm_start", self.serve, model_dir,
                          self.warm_client(served))
        emit("warm_start", ready_s=round(warm["ready_s"], 2),
             **warm["startup"])
        self.check_pool(warm["startup"])
        if not self.args.cpu_rehearsal:
            # The CPU backend keeps no persistent cache (worker.py).
            self.check_compile_cache(cold["startup"], warm["startup"])

    @staticmethod
    def check_compile_cache(cold: dict, warm: dict) -> None:
        """The second start finds every program the first one compiled:
        judged on the cache's own hits and misses while each server ran,
        not on the number of files (a cache that is kept to a size loses
        old entries while it gains new ones: seen on the chip)."""
        emit("warm_start", name="compile_cache",
             hits=[cold["cache_hits"], warm["cache_hits"]],
             misses=[cold["cache_misses"], warm["cache_misses"]],
             precompile_s=[cold["precompile_s"], warm["precompile_s"]])
        check(warm["cache_misses"] == 0,
              f"the second start compiled {warm['cache_misses']} programs "
              "anew: the first should have left them in the compile cache")
        check(warm["cache_hits"] > 0,
              "the second start never found a program in the compile cache")
        # The times can only be told apart when the first start was cold.
        # A machine that comes with the cache filled starts warm both
        # times; a first start that compiled a quarter of its lattice or
        # more was cold enough to show.
        if 4 * cold["cache_misses"] >= cold["precompile_graphs"]:
            c, w = cold["precompile_s"], warm["precompile_s"]
            check(w < 0.5 * c, f"warm precompile {w}s vs cold {c}s: "
                  "the compile cache did not carry over")

    @staticmethod
    def live_bytes() -> int:
        import jax
        gc.collect()
        return sum(a.nbytes for a in jax.live_arrays())

    # ------------------------------------------------------------------
    # --chips 4: the sharded path and what it is compared with
    # ------------------------------------------------------------------
    def offline(self, model_dir: str, label: str, **parallel) -> dict:
        import jax

        from vllm_distributed_tpu import LLM, SamplingParams
        a = self.engine_args(model_dir, **parallel)
        if not self.args.cpu_rehearsal:
            # Three cold engine builds at four chips' price: a smaller
            # lattice (6 token buckets, 1 request bucket) than the
            # server phase, same model, same kernels.
            a.max_num_batched_tokens, a.max_num_seqs = 512, 8
        t0 = time.monotonic()
        llm = LLM(**{f: getattr(a, f) for f in (
            "model", "skip_tokenizer_init", "load_format", "seed", "dtype",
            "block_size", "max_model_len", "max_num_batched_tokens",
            "max_num_seqs", "tensor_parallel_size", "token_parallel_size")})
        start_s = time.monotonic() - t0
        prompts = [self.prompt(n, salt=200 + i)
                   for i, n in enumerate((self.size["fixed_prompt"], 64, 17))]
        sp = SamplingParams(temperature=0.0, max_tokens=16, ignore_eos=True,
                            logprobs=5, prompt_logprobs=1)
        outs = llm.generate(prompts, sp)
        rows = []
        for p, o in zip(prompts, outs):
            c = o.outputs[0]
            check(len(c.token_ids) == 16, f"{label}: short output")
            rows.append({
                "ids": list(c.token_ids),
                "lps": [d[t] for t, d in zip(c.token_ids, c.logprobs)],
                "top": [dict(d) for d in c.logprobs],
                "prompt_lps": [d[t] for t, d in
                               zip(p[1:], o.prompt_logprobs[1:])],
            })
        runner = (llm.llm_engine.engine_core.engine_core.executor.worker
                  .model_runner)
        facts = {"start_s": round(start_s, 2),
                 "num_recompiles": runner.num_recompiles,
                 "attn_kernel_calls": dict(runner.attn_kernel_calls)}
        if parallel:
            facts.update(self.sharding_proof(runner))
        emit(label, **facts)
        check(runner.num_recompiles == 0, f"{label}: recompiled")
        check(set(runner.attn_kernel_calls) == {"unified"},
              f"{label}: attention left the unified kernel")
        del llm, runner, outs
        emit(label, name="after_free", live_array_bytes=self.live_bytes(),
             bytes_in_use=[(d.memory_stats() or {}).get("bytes_in_use")
                           for d in jax.devices()])
        return {"rows": rows}

    def sharding_proof(self, runner) -> dict:
        """Per-device bytes and the step program's collectives."""
        import jax
        devs = list(runner.mesh.devices.flat)

        def per_device(tree):
            out = {d.id: 0 for d in devs}
            for leaf in jax.tree.leaves(tree):
                for sh in leaf.addressable_shards:
                    out[sh.device.id] += sh.data.nbytes
            return [out[d.id] for d in devs]

        weights = per_device(runner.params)
        pool = per_device(runner.kv_caches)
        leaves = jax.tree.leaves(runner.params)
        total = sum(x.nbytes for x in leaves)
        # The embedding table is replicated by design (models/llama.py
        # param_specs); everything else splits over the mesh.
        replicated = sum(x.nbytes for x in leaves
                         if x.sharding.is_fully_replicated)
        in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
                  for d in devs]
        T, max_q, G = max(runner.forward_shapes())
        inputs = runner._dummy_step_inputs(T, max_q, G)
        from vllm_distributed_tpu.parallel.mesh import global_mesh
        with runner.mesh, global_mesh(runner.mesh):
            text = runner._forward_fn.lower(
                runner.params, runner.kv_caches, inputs).compile(
                ).as_text()
        facts = {
            "weight_bytes_per_device": weights,
            "weight_bytes_unsharded": total,
            "weight_bytes_replicated_on_each": replicated,
            "sharded_weight_share_per_device": [
                round((w - replicated) / (total - replicated), 4)
                for w in weights],
            "kv_pool_bytes_per_device": pool,
            "bytes_in_use_per_device": in_use,
            "all_reduces_in_step": len(re.findall(r"all-reduce(?:-start)?\(",
                                                  text)),
            "pallas_calls_in_step": text.count("tpu_custom_call"),
        }
        mean = sum(weights) / len(weights)
        check(all(abs(w - mean) <= 0.1 * mean for w in weights),
              f"weights are not spread evenly: {weights}")
        # Weights split over the model axis only (a token-parallel axis
        # replicates them and splits the KV pool instead).
        n = runner.config.parallel_config.tensor_parallel_size
        check(all(abs((w - replicated) * n - (total - replicated))
                  <= 0.1 * (total - replicated) for w in weights),
              f"sharded weights are not 1/{n} on each device: {weights}")
        if in_use[0]:
            for w, p, u in zip(weights, pool, in_use):
                check(abs(u - (w + p)) <= 0.1 * (w + p),
                      f"device holds {u} bytes, weights+pool are {w + p}")
        check(facts["all_reduces_in_step"] > 0, "no all-reduce in the step")
        check(self.args.cpu_rehearsal or facts["pallas_calls_in_step"] > 0,
              "no Pallas call in the step")
        return facts

    def compare(self, label: str, base: dict, other: dict) -> None:
        """Logprobs within tolerance (every prompt position, and every
        greedy token while the ids agree); greedy tokens counted, and
        where a prompt's ids first part the one-device engine itself
        must rank the two tokens as a near tie (check_ties). With seeded
        random weights the top logits are near ties, so token identity
        across reduction orders is not a sound criterion."""
        tol = self.size["tol"]
        max_dev, agree, total, parted = 0.0, 0, 0, []
        for row, (a, b) in enumerate(zip(base["rows"], other["rows"])):
            ties = self.tie_facts(a, b)
            n = ties["greedy_tokens_agreeing"]
            agree, total = agree + n, total + ties["of"]
            max_dev = max(
                [max_dev] +
                [abs(x - y) for x, y in zip(a["prompt_lps"],
                                            b["prompt_lps"])] +
                [abs(x - y) for x, y in zip(a["lps"][:n], b["lps"][:n])])
            if "first_divergence" in ties:
                parted.append(dict(ties["first_divergence"], prompt=row))
        emit(label, name="vs_one_device", max_abs_logprob_dev=max_dev,
             tolerance=tol, greedy_tokens_agreeing=agree,
             greedy_tokens_compared=total, first_divergences=parted,
             tie_tolerance=self.size["tie_tol"])
        check(max_dev <= tol, f"{label}: logprobs deviate {max_dev:.4f} "
              f"from the one-device engine (tolerance {tol})")
        for div in parted:
            self.check_ties(f"{label}, prompt {div['prompt']}",
                            {"first_divergence": div})

    def run_four_chips(self) -> None:
        model_dir = self.write_model_dir()
        base = self.phase("one_device", self.offline, model_dir,
                          "one_device")
        tp4 = self.phase("tp4", self.offline, model_dir, "tp4",
                         tensor_parallel_size=4)
        self.compare("tp4", base, tp4)
        tknp = self.phase("tknp2_tp2", self.offline, model_dir, "tknp2_tp2",
                          tensor_parallel_size=2, token_parallel_size=2)
        self.compare("tknp2_tp2", base, tknp)


def reference_logprobs(params: dict, cfg: dict, ids, attend: bool = True):
    """Llama forward over one full sequence with dense causal attention,
    written against the HF parameter layout in plain jnp; returns the
    log-softmax over the vocabulary at every position."""
    import jax
    import jax.numpy as jnp
    S = ids.shape[0]
    QH, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D, eps = cfg["head_dim"], cfg["rms_norm_eps"]

    def rms(x, w):
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
        return (x32 * w.astype(jnp.float32)).astype(x.dtype)

    # Llama-3 rope: HF rotate-half with the piecewise frequency scaling.
    inv = 1.0 / (cfg["rope_theta"] ** (
        jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    rs = cfg["rope_scaling"]
    wavelen = 2 * math.pi / inv
    lo = rs["original_max_position_embeddings"] / rs["low_freq_factor"]
    hi = rs["original_max_position_embeddings"] / rs["high_freq_factor"]
    smooth = ((rs["original_max_position_embeddings"] / wavelen -
               rs["low_freq_factor"]) /
              (rs["high_freq_factor"] - rs["low_freq_factor"]))
    inv = jnp.where(wavelen > lo, inv / rs["factor"],
                    jnp.where(wavelen < hi, inv,
                              (1 - smooth) * inv / rs["factor"] +
                              smooth * inv))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]

    def rope(x):
        x32 = x.astype(jnp.float32)
        half = jnp.concatenate([-x32[..., D // 2:], x32[..., :D // 2]], -1)
        return (x32 * cos + half * sin).astype(x.dtype)

    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, lp):
        h = rms(x, lp["input_ln"])
        q = rope(jnp.dot(h, lp["wq"]).reshape(S, QH, D))
        k = rope(jnp.dot(h, lp["wk"]).reshape(S, KVH, D))
        v = jnp.dot(h, lp["wv"]).reshape(S, KVH, D)
        k = jnp.repeat(k, QH // KVH, axis=1)
        v = jnp.repeat(v, QH // KVH, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * D ** -0.5
        w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", w, v.astype(jnp.float32))
        o = o.astype(x.dtype).reshape(S, QH * D)
        if attend:
            x = x + jnp.dot(o, lp["wo"])
        h = rms(x, lp["post_ln"])
        mlp = jax.nn.silu(jnp.dot(h, lp["gate"])) * jnp.dot(h, lp["up"])
        return x + jnp.dot(mlp, lp["down"]), None

    keys = ("input_ln", "wq", "wk", "wv", "wo", "post_ln", "gate", "up",
            "down")
    x, _ = jax.lax.scan(layer, params["embed"][ids],
                        {k: params["layers"][k] for k in keys})
    logits = jnp.dot(rms(x, params["final_ln"]), params["lm_head"],
                     preferred_element_type=jnp.float32)
    return jax.nn.log_softmax(logits, axis=-1)


def sum_metric(samples: list, name: str) -> float:
    """A metric family summed over its label sets."""
    return sum(v for n, _, v in samples if n == name)


def http_json(port: int, method: str, path: str, body=None, timeout=60,
              raw: bool = False):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path,
                     json.dumps(body) if body is not None else None,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        text = resp.read().decode()
    finally:
        conn.close()
    if raw:
        return resp.status, text
    try:
        return resp.status, json.loads(text)
    except json.JSONDecodeError:
        return resp.status, text


def _steer_padded_layout() -> None:
    """Rehearsal only: the program pads the KV head dim to the 128-lane
    tile when the backend is a TPU; steer its helper to do so on the CPU
    too, so the rehearsal runs the padded layout the chip will run."""
    from vllm_distributed_tpu.ops import attention
    attention.storage_head_dim = lambda d: -(-d // 128) * 128


class _Recorder(logging.Handler):

    def __init__(self, sink: list) -> None:
        super().__init__(logging.INFO)
        self.sink = sink

    def emit(self, record: logging.LogRecord) -> None:
        self.sink.append((time.monotonic(), record.getMessage()))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--inject-failure", choices=PHASES, default=None,
                    help="fail the named phase (self-test of the exit code)")
    args = ap.parse_args()

    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.chips}")
        os.environ["VDT_PALLAS_INTERPRET"] = "1"
        os.environ["VDT_ATTENTION_BACKEND"] = "pallas"
        os.environ["VDT_PRECOMPILE"] = "1"
    else:
        # Nothing on the way to the chip may pick interpret mode, another
        # backend or a smaller lattice.
        for var in ("VDT_PALLAS_INTERPRET", "VDT_ATTENTION_BACKEND",
                    "VDT_PRECOMPILE"):
            os.environ.pop(var, None)
    os.environ["VDT_ASSERT_NO_RECOMPILE"] = "1"
    os.environ["VDT_CORRECTNESS"] = "1"  # makes the NaN-step counter live
    os.environ.setdefault("HF_HUB_OFFLINE", "1")

    smoke = Smoke(args)
    try:
        smoke.phase("devices", smoke.devices)
        # The program logs to stdout; standard output here is JSON only.
        from vllm_distributed_tpu.logger import init_logger
        init_logger("chip_smoke")  # configures the package's root logger
        root = logging.getLogger("vllm_distributed_tpu")
        for h in root.handlers:
            if isinstance(h, logging.StreamHandler):
                h.setStream(sys.stderr)
        root.addHandler(_Recorder(smoke.records))
        if args.cpu_rehearsal:
            _steer_padded_layout()
        if args.chips == 4:
            smoke.run_four_chips()
        else:
            smoke.run_one_chip()
    except BaseException as e:  # noqa: BLE001 - every failure exits 1
        import traceback
        traceback.print_exc()
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}",
                          "device": smoke.device}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": smoke.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
