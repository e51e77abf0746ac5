"""One whole run of each loop kind through the harness on the CPU, on the
tests' own tiny cells: they exist as files and manifest entries only, so
this also shows that a new cell, configuration, mix or layer metric needs
no harness code. (The command line itself refuses the CPU; the tests call
``run_cell`` with the platform they have.)"""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), "..", ".."))
DATA = os.path.join(os.path.dirname(__file__), "data")
SCRIPT = """
import json, sys
sys.path.insert(0, {repo!r})
from benchmarks import run
run.WORK = {work!r}
line = run.run_cell({cell!r}, 2**31 + 7, 4.0, {trace}, data_root={data!r},
                    manifest_path={data!r} + "/manifest.json",
                    require_platform="cpu", ramp_s=1.5)
print(json.dumps(line))
"""


@pytest.mark.parametrize("cell,trace,wants", [
    ("tiny-olmo2.tiny-chat", False, {"ttft_p50_ms", "itl_p95_ms",
                                     "tpot_p50_ms", "setup_s"}),
    ("tiny-olmoe.tiny-decode", True, {"sched.running_mean", "kv.preemptions",
                                      "runner.prepare_inputs_mean_ms"})])
def test_run_cell_end_to_end_on_cpu(cell, trace, wants, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", VDT_PALLAS_INTERPRET="1",
               VDT_PRECOMPILE="1", HF_HUB_OFFLINE="1", BENCH_RUN="ignored",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(
            repo=REPO, work=str(tmp_path), cell=cell, trace=trace,
            data=DATA)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    last = json.loads(lines[-1])
    assert set(last) <= {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "compared"}
    # Each number that decided `correct` stands beside its limit.
    assert len(last["compared"]) >= 5 and all(
        re.match(r"^compared \S+ \S+ limit (<=|>=) \S+$", c)
        for c in last["compared"]), last["compared"]
    assert last["correct"] is True, lines[-3:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert wants <= set(last["metrics"]), last["metrics"]
    if not trace:
        assert set(last["metrics"]) == wants
    for v in last["metrics"].values():
        assert set(v) == {"value", "unit"} and v["value"] is not None
    assert last["metrics"].get("setup_s", {"value": 1})["value"] > 0
    assert last["device"]["platform"] == "cpu"
    phases = [json.loads(ln)["phase"] for ln in lines[:-1]]
    assert phases[:3] == ["devices", "traffic", "startup"]
    window = json.loads(lines[phases.index("window")])
    assert window["compiled_in_window"] == {
        "recompiles": 0.0, "cache_misses": 0, "backend_compiles": 0}


@pytest.mark.parametrize("first_rc,want_rc,want_attempts", [
    (75, 0, ["1", "2"]),     # compiled during set-up: started once more
    (1, 1, ["1"])])          # any other failure is the run's own
def test_supervisor_starts_a_worker_that_compiled_once_more(
        first_rc, want_rc, want_attempts, tmp_path, monkeypatch, capfd):
    """The command is a supervisor without JAX; a stand-in worker shows
    its loop: one more start after RESTART_RC, the same start time handed
    to both, and the worker's own code passed through."""
    from benchmarks import run
    log = tmp_path / "log"
    fake = tmp_path / "worker.sh"
    fake.write_text(
        "#!/bin/sh\n"
        f'echo "$VDT_BENCH_ATTEMPT $VDT_BENCH_STARTED $*" >> {log}\n'
        f'[ "$VDT_BENCH_ATTEMPT" = "1" ] && exit {first_rc}\n'
        "echo '{\"correct\": true}'\n")
    fake.chmod(0o755)
    monkeypatch.setattr(sys, "executable", str(fake))
    monkeypatch.delenv(run.WORKER_ENV, raising=False)
    argv = ["--workload", "x", "--seed", "3", "--seconds", "1"]
    assert run.RESTART_RC == 75 and run.main(argv) == want_rc
    rows = [ln.split(" ", 2) for ln in log.read_text().splitlines()]
    assert [r[0] for r in rows] == want_attempts
    assert len({r[1] for r in rows}) == 1 and float(rows[0][1]) > 0
    assert all(r[2] == "-m benchmarks.run " + " ".join(argv) for r in rows)
    out = capfd.readouterr().out
    assert ('"correct"' in out) == (want_rc == 0)
