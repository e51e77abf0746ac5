"""chip_smoke.py's contract, as far as a machine without the chip can
hold it to: the rehearsal runs the whole script green on the CPU and can
only ever print the `cpu` line; no failure ends in exit code 0; and the
compile cache is placed from outside."""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def run_smoke(*args, cwd=REPO, script=SCRIPT, timeout=900):
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
