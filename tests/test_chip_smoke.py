"""chip_smoke.py's contract, as far as a machine without the chip can
hold it to: the rehearsal runs the whole script green on the CPU and can
only ever print the `cpu` line; no failure ends in exit code 0; its
checks on the compile cache and on greedy ties refuse what the chip runs
of PR 21 showed to be faults; and the compile cache is placed from
outside."""

import argparse
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def run_smoke(*args, cwd=REPO, script=SCRIPT, timeout=600):
    # The bound is the rehearsal's (the longest call): 340-350 s beside
    # five other workers (PR 45's profile), about 120 s alone. A hang
    # then fails this test and leaves the suite's clock (1,470 s) to
    # the others.
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the script sizes its own device count
    res = subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                         capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in res.stdout.splitlines() if ln.strip()]
    return res, lines


def test_cpu_rehearsal_runs_green():
    res, lines = run_smoke("--cpu-rehearsal")
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-4000:]
    # Standard output is JSON only, one object per line.
    records = [json.loads(ln) for ln in lines]
    assert records[-1] == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert '"tpu"' not in res.stdout
    by_phase = {}
    for rec in records[:-1]:
        by_phase.setdefault(rec["phase"], []).append(rec)
    for phase in ("devices", "requests", "metrics", "cold_start",
                  "reference", "warm_start"):
        assert phase in by_phase, phase
    metrics = by_phase["metrics"][0]
    assert set(metrics["attn_kernel_calls"]) == {"unified"}
    assert metrics["num_recompiles"] == 0
    echo = [r for r in by_phase["reference"] if r["run"] == "echo"][0]
    assert echo["max_abs_logprob_dev"] <= echo["tolerance"]
    assert echo["control_no_attention_max_dev"] > 3 * echo["tolerance"]


@pytest.mark.parametrize("args", [
    pytest.param(("--cpu-rehearsal", "--inject-failure", "devices"),
                 id="failed-check"),
    pytest.param((), id="no-chip"),
    pytest.param(("--chips", "4"), id="no-four-chips"),
])
def test_failure_exits_nonzero_and_prints_ok_false(args):
    res, lines = run_smoke(*args, timeout=300)
    assert res.returncode != 0
    last = json.loads(lines[-1])
    assert last["ok"] is False
    assert '"ok": true' not in res.stdout


def _startup(hits, misses, precompile_s, graphs=40):
    return {"cache_hits": hits, "cache_misses": misses,
            "precompile_s": precompile_s, "precompile_graphs": graphs}


@pytest.mark.parametrize("cold, warm, ok", [
    # Chip call 5 of PR 21: the pool's page count jittered between the
    # two starts, so the second compiled every forward graph again.
    pytest.param(_startup(40, 18, 82.5), _startup(40, 18, 76.1), False,
                 id="second-start-compiled-again"),
    pytest.param(_startup(40, 18, 78.7), _startup(58, 0, 12.1), True,
                 id="forwards-cold-then-warm"),
    pytest.param(_startup(0, 58, 343.0), _startup(58, 0, 12.1), True,
                 id="from-empty"),
    # Every program found, but one with a Mosaic kernel inside loads in
    # 6.5 s in a new process against 0.7 s later (chip call 14).
    pytest.param(_startup(58, 0, 61.9), _startup(58, 0, 12.9), True,
                 id="machine-came-with-the-cache-filled"),
    pytest.param(_startup(40, 18, 78.7), _startup(58, 0, 70.0), False,
                 id="cache-hit-but-no-faster"),
    pytest.param(_startup(0, 0, 78.7), _startup(0, 0, 12.1), False,
                 id="cache-never-asked"),
])
def test_compile_cache_check(capsys, cold, warm, ok):
    import chip_smoke
    if ok:
        chip_smoke.Smoke.check_compile_cache(cold, warm)
    else:
        with pytest.raises(chip_smoke.CheckFailed):
            chip_smoke.Smoke.check_compile_cache(cold, warm)
    # The figures are printed before they are judged.
    assert '"misses"' in capsys.readouterr().out


@pytest.mark.parametrize("other_id, other_lp, ok", [
    pytest.param(7, None, True, id="same-ids"),
    pytest.param(9, -2.05, True, id="near-tie"),
    pytest.param(9, -2.12, False, id="flip-away-from-a-tie"),
    pytest.param(9, None, False, id="token-the-first-run-never-ranked"),
])
def test_greedy_runs_may_part_only_at_a_near_tie(other_id, other_lp, ok):
    import chip_smoke
    smoke = chip_smoke.Smoke(argparse.Namespace(
        cpu_rehearsal=False, chips=1, seed=0, inject_failure=None))
    top = {7: -2.0, 8: -2.5}
    if other_lp is not None:
        top[9] = other_lp
    a = {"ids": [3, 7, 5], "lps": [-1.0, -2.0, -1.5],
         "top": [{3: -1.0}, top, {5: -1.5}]}
    facts = smoke.tie_facts(a, {"ids": [3, other_id, 5]})
    if other_id == 7:
        assert facts == {"greedy_tokens_agreeing": 3, "of": 3}
    else:
        assert facts["greedy_tokens_agreeing"] == 1
        assert facts["first_divergence"]["token"] == 1
        assert facts["tie_tolerance"] == 0.08  # well under the bf16 `tol`
    if ok:
        smoke.check_ties("test", facts)
    else:
        with pytest.raises(chip_smoke.CheckFailed):
            smoke.check_ties("test", facts)


def test_script_alone_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo there is no program to drive."""
    alone = shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    res, lines = run_smoke("--cpu-rehearsal", cwd=tmp_path, script=alone,
                           timeout=300)
    assert res.returncode != 0
    assert json.loads(lines[-1])["ok"] is False
    assert '"ok": true' not in res.stdout


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path,
                                              from_env):
    """With JAX_COMPILATION_CACHE_DIR set the worker sets no directory
    in code; unset, the cache goes to the fixed in-checkout path."""
    from vllm_distributed_tpu.worker import worker
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: updates.__setitem__(key, value))
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    chosen = worker.configure_compile_cache()
    if from_env:
        assert chosen == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates
    else:
        assert chosen == os.path.join(REPO, ".jax_cache")
        assert updates["jax_compilation_cache_dir"] == chosen
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
