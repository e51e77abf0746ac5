"""CI guard: BENCH_*.json scoreboard records stay machine-comparable.

Runs scripts/lint_bench.py over the real repo records (tier-1
mechanical check) and unit-tests the linter's failure modes on
synthetic records: a tpu capture without decode_mfu, a schema>=2 tpu
capture without decode_mbu / engine-sourced fields, unparseable JSON,
and the driver-wrapper shape."""

import json
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
SCRIPT = REPO / "scripts" / "lint_bench.py"


def _run(*args: str) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, str(SCRIPT), *args],
                          capture_output=True, text=True, timeout=60)


def _write(tmp_path, name: str, rec) -> None:
    (tmp_path / name).write_text(
        rec if isinstance(rec, str) else json.dumps(rec))


GOOD_V2_TPU = {
    "metric": "decode_throughput", "value": 448.1, "unit": "tok/s",
    "backend": "tpu", "schema_version": 2, "decode_mfu": 0.21,
    "decode_mbu": 0.63, "engine_mfu": 0.2, "engine_mbu": 0.6,
}

GOOD_V3_TPU = {
    **GOOD_V2_TPU, "schema_version": 3,
    "tiering_on_turns_per_s": 1.4, "tiering_off_turns_per_s": 1.1,
    "tiering_on_hit_rate_window": 0.7,
    "tiering_off_hit_rate_window": 0.4, "tiering_parity": True,
}

GOOD_V4_TPU = {
    **GOOD_V3_TPU, "schema_version": 4,
    "fleet_on_reqs_per_s": 3.1, "fleet_off_reqs_per_s": 3.4,
    "fleet_on_req_p99_ms": 410.0, "fleet_off_req_p99_ms": 350.0,
    "fleet_scale_outs": 1, "fleet_scale_ins": 1,
    "fleet_replica_timeline": [2, 1, 2], "fleet_parity": True,
}

GOOD_V5_TPU = {
    **GOOD_V4_TPU, "schema_version": 5,
    "ha_leader_transitions": 2, "ha_failover_gap_s": 0.31,
    "ha_journal_replays": 1, "ha_fenced_actions": {"resurrect": 1},
    "ha_replica_timeline": [2, 1, 2], "ha_parity": True,
}

GOOD_V6_TPU = {
    **GOOD_V5_TPU, "schema_version": 6,
    "trace_overhead_frac": 0.011, "trace_stitched_traces": 2,
    "trace_flow_links": 2,
}

GOOD_V7_TPU = {
    **GOOD_V6_TPU, "schema_version": 7,
    "canary_soak_probes": 60, "canary_false_positives": 0,
    "canary_detection_probes": 1, "canary_vote_attribution": True,
    "canary_quarantine_hint": True, "canary_overhead_frac": 0.009,
    "canary_parity": True,
}


def test_repo_records_are_clean():
    res = _run()
    assert res.returncode == 0, (
        f"BENCH record schema drifted:\n{res.stderr}")


def test_good_v2_record_passes(tmp_path):
    _write(tmp_path, "BENCH_x.json", GOOD_V2_TPU)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 0, res.stderr


def test_tpu_record_without_mfu_fails(tmp_path):
    rec = dict(GOOD_V2_TPU)
    del rec["decode_mfu"]
    del rec["schema_version"]
    _write(tmp_path, "BENCH_x.json", rec)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 1
    assert "decode_mfu" in res.stderr


def test_v2_tpu_record_without_mbu_fails(tmp_path):
    rec = dict(GOOD_V2_TPU)
    del rec["decode_mbu"]
    _write(tmp_path, "BENCH_x.json", rec)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 1
    assert "decode_mbu" in res.stderr


def test_v2_record_without_engine_perf_fails(tmp_path):
    rec = dict(GOOD_V2_TPU)
    del rec["engine_mbu"]
    _write(tmp_path, "BENCH_x.json", rec)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 1
    assert "engine_mbu" in res.stderr


def test_v1_cpu_record_is_grandfathered(tmp_path):
    # Pre-plane records carry no schema_version and no mfu on CPU.
    _write(tmp_path, "BENCH_old.json", {
        "metric": "decode_throughput", "value": 385.0,
        "unit": "tok/s", "backend": "cpu"})
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 0, res.stderr


def test_unparseable_and_bad_backend_fail(tmp_path):
    _write(tmp_path, "BENCH_broken.json", "{not json")
    _write(tmp_path, "BENCH_weird.json", {
        "metric": "m", "value": 1.0, "unit": "tok/s",
        "backend": "quantum"})
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 1
    assert "unparseable" in res.stderr
    assert "quantum" in res.stderr


def test_wrapper_shape_validates_payload(tmp_path):
    # rc!=0 with parsed=null is a capture failure, not schema drift...
    _write(tmp_path, "BENCH_fail.json",
           {"n": 1, "rc": 1, "parsed": None})
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 0, res.stderr
    # ...but an rc=0 wrapper must carry a record, and the payload is
    # held to the same schema.
    _write(tmp_path, "BENCH_empty.json",
           {"n": 2, "rc": 0, "parsed": None})
    bad = dict(GOOD_V2_TPU, decode_mfu=7.0)
    _write(tmp_path, "BENCH_wrap.json", {"n": 3, "rc": 0,
                                         "parsed": bad})
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 1
    assert "no parsed record" in res.stderr
    assert "decode_mfu" in res.stderr


def test_good_v3_record_passes(tmp_path):
    _write(tmp_path, "BENCH_x.json", GOOD_V3_TPU)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 0, res.stderr


def test_v3_record_without_tiering_fields_fails(tmp_path):
    rec = dict(GOOD_V3_TPU)
    del rec["tiering_on_turns_per_s"]
    del rec["tiering_parity"]
    _write(tmp_path, "BENCH_x.json", rec)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 1
    assert "tiering_on_turns_per_s" in res.stderr
    assert "tiering_parity" in res.stderr


def test_v3_parity_false_fails(tmp_path):
    # Tiering is contractually token-invisible: a recorded parity
    # failure is schema drift, not a shrug.
    _write(tmp_path, "BENCH_x.json",
           dict(GOOD_V3_TPU, tiering_parity=False))
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 1
    assert "token-invisible" in res.stderr


def test_good_v4_record_passes(tmp_path):
    _write(tmp_path, "BENCH_x.json", GOOD_V4_TPU)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 0, res.stderr


def test_v4_record_without_fleet_fields_fails(tmp_path):
    rec = dict(GOOD_V4_TPU)
    del rec["fleet_on_reqs_per_s"]
    del rec["fleet_replica_timeline"]
    _write(tmp_path, "BENCH_x.json", rec)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 1
    assert "fleet_on_reqs_per_s" in res.stderr
    assert "fleet_replica_timeline" in res.stderr


def test_v4_fleet_parity_false_fails(tmp_path):
    # Elasticity is contractually token-invisible — a migration that
    # changed a token is a correctness bug the scoreboard must flag.
    _write(tmp_path, "BENCH_x.json",
           dict(GOOD_V4_TPU, fleet_parity=False))
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 1
    assert "token-invisible" in res.stderr


def test_v4_fleet_leg_error_is_accepted(tmp_path):
    rec = {k: v for k, v in GOOD_V4_TPU.items()
           if not k.startswith("fleet_")}
    rec["fleet_leg_error"] = "RuntimeError: needs >= 2 devices"
    _write(tmp_path, "BENCH_x.json", rec)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 0, res.stderr
    rec["fleet_leg_error"] = ""
    _write(tmp_path, "BENCH_x.json", rec)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 1


def test_good_v5_record_passes(tmp_path):
    _write(tmp_path, "BENCH_x.json", GOOD_V5_TPU)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 0, res.stderr


def test_v5_record_without_ha_fields_fails(tmp_path):
    rec = dict(GOOD_V5_TPU)
    del rec["ha_leader_transitions"]
    del rec["ha_journal_replays"]
    _write(tmp_path, "BENCH_x.json", rec)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 1
    assert "ha_leader_transitions" in res.stderr
    assert "ha_journal_replays" in res.stderr


def test_v5_ha_parity_false_fails(tmp_path):
    # Leader failover is contractually token-invisible — a takeover
    # that changed a stream is a correctness bug, not a shrug.
    _write(tmp_path, "BENCH_x.json",
           dict(GOOD_V5_TPU, ha_parity=False))
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 1
    assert "token-invisible" in res.stderr


def test_v5_ha_leg_error_is_accepted(tmp_path):
    rec = {k: v for k, v in GOOD_V5_TPU.items()
           if not k.startswith("ha_")}
    rec["ha_leg_error"] = "RuntimeError: needs >= 2 devices"
    _write(tmp_path, "BENCH_x.json", rec)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 0, res.stderr
    rec["ha_leg_error"] = ""
    _write(tmp_path, "BENCH_x.json", rec)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 1


def test_good_v6_record_passes(tmp_path):
    _write(tmp_path, "BENCH_x.json", GOOD_V6_TPU)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 0, res.stderr


def test_v6_record_without_trace_fields_fails(tmp_path):
    rec = dict(GOOD_V6_TPU)
    del rec["trace_stitched_traces"]
    del rec["trace_flow_links"]
    _write(tmp_path, "BENCH_x.json", rec)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 1
    assert "trace_stitched_traces" in res.stderr
    assert "trace_flow_links" in res.stderr


def test_v6_overhead_above_budget_fails(tmp_path):
    # The ISSUE 19 acceptance bound: the trace plane may cost at most
    # 3% of decode steps/s; a hotter capture is a regression.
    _write(tmp_path, "BENCH_x.json",
           dict(GOOD_V6_TPU, trace_overhead_frac=0.08))
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 1
    assert "trace_overhead_frac" in res.stderr


def test_v6_trace_leg_error_is_accepted(tmp_path):
    rec = {k: v for k, v in GOOD_V6_TPU.items()
           if not k.startswith("trace_")}
    rec["trace_leg_error"] = "RuntimeError: needs >= 2 devices"
    _write(tmp_path, "BENCH_x.json", rec)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 0, res.stderr
    rec["trace_leg_error"] = ""
    _write(tmp_path, "BENCH_x.json", rec)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 1


def test_good_v7_record_passes(tmp_path):
    _write(tmp_path, "BENCH_x.json", GOOD_V7_TPU)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 0, res.stderr


def test_v7_record_without_canary_fields_fails(tmp_path):
    rec = dict(GOOD_V7_TPU)
    del rec["canary_soak_probes"]
    del rec["canary_vote_attribution"]
    _write(tmp_path, "BENCH_x.json", rec)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 1
    assert "canary_soak_probes" in res.stderr
    assert "canary_vote_attribution" in res.stderr


def test_v7_false_positives_and_slow_detection_fail(tmp_path):
    # The sentinel's acceptance bounds are hard: ANY false positive on
    # the clean soak, or detection slower than 3 probes, is drift.
    _write(tmp_path, "BENCH_a.json",
           dict(GOOD_V7_TPU, canary_false_positives=1))
    _write(tmp_path, "BENCH_b.json",
           dict(GOOD_V7_TPU, canary_detection_probes=7))
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 1
    assert "canary_false_positives" in res.stderr
    assert "canary_detection_probes" in res.stderr


def test_v7_canary_leg_error_is_accepted(tmp_path):
    rec = {k: v for k, v in GOOD_V7_TPU.items()
           if not k.startswith("canary_")}
    rec["canary_leg_error"] = "RuntimeError: needs >= 2 devices"
    _write(tmp_path, "BENCH_x.json", rec)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 0, res.stderr
    rec["canary_leg_error"] = ""
    _write(tmp_path, "BENCH_x.json", rec)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 1


def test_v3_leg_error_is_accepted(tmp_path):
    rec = {k: v for k, v in GOOD_V3_TPU.items()
           if not k.startswith("tiering_")}
    rec["tiering_leg_error"] = "RuntimeError: no devices"
    _write(tmp_path, "BENCH_x.json", rec)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 0, res.stderr
    # ...but an empty error string is not an excuse.
    rec["tiering_leg_error"] = ""
    _write(tmp_path, "BENCH_x.json", rec)
    res = _run("--dir", str(tmp_path))
    assert res.returncode == 1
