#!/usr/bin/env python3
"""Validate BENCH_*.json records against the bench schema.

The BENCH files are the project's scoreboard: ROADMAP item 5 compares
real-TPU captures against them, and the perf-attribution plane (ISSUE
14) makes CPU-smoke and TPU records directly comparable ONLY if every
record keeps the machine-readable fields. This linter fails tier-1 when
a record drifts:

* **Every record** is a single JSON object with ``metric`` (str),
  ``value`` (finite number >= 0), ``unit`` (non-empty str) and
  ``backend`` in {tpu, cpu}.
* **tpu-backend records** must carry a numeric ``decode_mfu`` in
  (0, 1] — the scoreboard's roofline axis.
* **schema_version >= 2 records** (everything bench.py writes since
  the perf-attribution plane) must additionally carry ``decode_mbu``
  on tpu-backend records (decode is bandwidth-bound; MBU is the honest
  headline) and engine-sourced ``engine_mfu``/``engine_mbu`` on every
  backend (analytic fallback values count — the keys must exist and be
  numeric). Records WITHOUT ``schema_version`` are grandfathered
  pre-plane captures and validate against the v1 rules only.
* **schema_version >= 3 records** (the hierarchical KV-memory plane)
  must additionally carry the ``_tiering_leg`` comparison — turns/s
  and window hit rate for both legs plus the greedy-parity flag — or
  an explicit ``tiering_leg_error`` string recording why the leg
  could not run. A parity field that is present must be ``true``:
  tiering is contractually token-invisible.
* **schema_version >= 4 records** (the elastic fleet) must carry the
  ``_fleet_leg`` comparison — request throughput and peak-phase
  p50/p99 for both legs, the scale counters, the replica timeline and
  the greedy-parity flag — or an explicit ``fleet_leg_error`` string.
  ``fleet_parity`` must be ``true``: elasticity is contractually
  token-invisible, migrations included.
* **schema_version >= 5 records** (the HA fleet control plane) must
  carry the ``_ha_leg`` failover drill — leader transitions, the
  fenced-action counters, the observed failover gap, journal replays,
  the replica timeline and the greedy-parity flag — or an explicit
  ``ha_leg_error`` string. ``ha_parity`` must be ``true``: leader
  failover is contractually token-invisible, journal replays included.
* **schema_version >= 6 records** (the distributed trace plane) must
  carry the ``_trace_leg`` acceptance — ``trace_overhead_frac`` <= 3%
  (VDT_TRACE_PLANE on vs off), at least one stitched two-replica
  disagg trace and at least one Perfetto flow link across the KV
  handoff — or an explicit ``trace_leg_error`` string.
* **schema_version >= 7 records** (the correctness sentinel) must
  carry the ``_canary_leg`` acceptance — a clean soak of >= 60 canary
  probes with ZERO false positives, the seeded single-replica
  corruption detected within 3 probes with vote attribution and a
  quarantine hint, a bounded plane-on overhead fraction and greedy
  token parity — or an explicit ``canary_leg_error`` string.

Usage::

    python scripts/lint_bench.py [--dir REPO_ROOT]

Exit 0 when clean; exit 1 listing violations otherwise.
"""

import argparse
import glob
import json
import math
import os
import sys

BACKENDS = {"tpu", "cpu"}
REQUIRED = ("metric", "value", "unit", "backend")


def _is_num(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) \
        and math.isfinite(x)


def check_record(name: str, rec) -> list:
    errs = []
    if not isinstance(rec, dict):
        return [f"{name}: not a JSON object"]
    for key in REQUIRED:
        if key not in rec:
            errs.append(f"{name}: missing required field {key!r}")
    if "metric" in rec and not (isinstance(rec["metric"], str)
                                and rec["metric"]):
        errs.append(f"{name}: metric must be a non-empty string")
    if "value" in rec and not (_is_num(rec["value"])
                               and rec["value"] >= 0):
        errs.append(f"{name}: value must be a finite number >= 0")
    if "unit" in rec and not (isinstance(rec["unit"], str)
                              and rec["unit"]):
        errs.append(f"{name}: unit must be a non-empty string")
    backend = rec.get("backend")
    if backend is not None and backend not in BACKENDS:
        errs.append(f"{name}: backend {backend!r} not in "
                    f"{sorted(BACKENDS)}")
    is_tpu = backend == "tpu"
    if is_tpu:
        mfu = rec.get("decode_mfu")
        if not (_is_num(mfu) and 0 < mfu <= 1):
            errs.append(f"{name}: tpu record needs decode_mfu in "
                        f"(0, 1], got {mfu!r}")
    version = rec.get("schema_version")
    if version is not None:
        if not (_is_num(version) and version >= 1):
            errs.append(f"{name}: schema_version must be a number >= 1")
            return errs
        if version >= 2:
            if is_tpu and not _is_num(rec.get("decode_mbu")):
                errs.append(f"{name}: schema>=2 tpu record needs a "
                            "numeric decode_mbu next to decode_mfu")
            for key in ("engine_mfu", "engine_mbu"):
                if not _is_num(rec.get(key)):
                    errs.append(
                        f"{name}: schema>=2 record needs engine-"
                        f"sourced {key} (analytic fallback counts), "
                        f"got {rec.get(key)!r}")
        if version >= 3:
            errs.extend(_check_tiering_fields(name, rec))
        if version >= 4:
            errs.extend(_check_fleet_fields(name, rec))
        if version >= 5:
            errs.extend(_check_ha_fields(name, rec))
        if version >= 6:
            errs.extend(_check_trace_fields(name, rec))
        if version >= 7:
            errs.extend(_check_canary_fields(name, rec))
    return errs


# _tiering_leg comparison fields required on schema >= 3 records
# ((validator, description) per field; see bench.py _tiering_leg).
TIERING_FIELDS = {
    "tiering_on_turns_per_s": (
        lambda v: _is_num(v) and v > 0, "positive number"),
    "tiering_off_turns_per_s": (
        lambda v: _is_num(v) and v > 0, "positive number"),
    "tiering_on_hit_rate_window": (
        lambda v: _is_num(v) and 0 <= v <= 1, "number in [0, 1]"),
    "tiering_off_hit_rate_window": (
        lambda v: _is_num(v) and 0 <= v <= 1, "number in [0, 1]"),
    "tiering_parity": (lambda v: v is True,
                       "true (tiering must be token-invisible)"),
}


def _check_tiering_fields(name: str, rec: dict) -> list:
    err = rec.get("tiering_leg_error")
    if err is not None:
        if isinstance(err, str) and err:
            return []  # leg failed and says why — valid record
        return [f"{name}: tiering_leg_error must be a non-empty "
                f"string, got {err!r}"]
    errs = []
    for key, (ok, want) in TIERING_FIELDS.items():
        if not ok(rec.get(key)):
            errs.append(f"{name}: schema>=3 record needs {key} "
                        f"({want}), got {rec.get(key)!r}")
    return errs


# _fleet_leg comparison fields required on schema >= 4 records
# ((validator, description) per field; see bench.py _fleet_leg).
FLEET_FIELDS = {
    "fleet_on_reqs_per_s": (
        lambda v: _is_num(v) and v > 0, "positive number"),
    "fleet_off_reqs_per_s": (
        lambda v: _is_num(v) and v > 0, "positive number"),
    "fleet_on_req_p99_ms": (
        lambda v: _is_num(v) and v > 0, "positive number"),
    "fleet_off_req_p99_ms": (
        lambda v: _is_num(v) and v > 0, "positive number"),
    "fleet_scale_outs": (
        lambda v: _is_num(v) and v >= 0, "number >= 0"),
    "fleet_scale_ins": (
        lambda v: _is_num(v) and v >= 0, "number >= 0"),
    "fleet_replica_timeline": (
        lambda v: (isinstance(v, list) and v
                   and all(_is_num(x) and x >= 1 for x in v)),
        "non-empty list of replica counts >= 1"),
    "fleet_parity": (lambda v: v is True,
                     "true (elasticity must be token-invisible)"),
}


def _check_fleet_fields(name: str, rec: dict) -> list:
    err = rec.get("fleet_leg_error")
    if err is not None:
        if isinstance(err, str) and err:
            return []  # leg failed and says why — valid record
        return [f"{name}: fleet_leg_error must be a non-empty "
                f"string, got {err!r}"]
    errs = []
    for key, (ok, want) in FLEET_FIELDS.items():
        if not ok(rec.get(key)):
            errs.append(f"{name}: schema>=4 record needs {key} "
                        f"({want}), got {rec.get(key)!r}")
    return errs


# _ha_leg failover-drill fields required on schema >= 5 records
# ((validator, description) per field; see bench.py _ha_leg).
HA_FIELDS = {
    "ha_leader_transitions": (
        lambda v: _is_num(v) and v >= 2,
        "number >= 2 (election + the failover takeover)"),
    "ha_failover_gap_s": (
        lambda v: _is_num(v) and v >= 0, "number >= 0"),
    "ha_journal_replays": (
        lambda v: _is_num(v) and v >= 1,
        "number >= 1 (the successor replayed the mid-drain intent)"),
    "ha_fenced_actions": (
        lambda v: (isinstance(v, dict)
                   and all(isinstance(k, str) and _is_num(n) and n >= 0
                           for k, n in v.items())),
        "dict of action -> rejection count"),
    "ha_replica_timeline": (
        lambda v: (isinstance(v, list) and v
                   and all(_is_num(x) and x >= 1 for x in v)),
        "non-empty list of replica counts >= 1"),
    "ha_parity": (lambda v: v is True,
                  "true (leader failover must be token-invisible)"),
}


def _check_ha_fields(name: str, rec: dict) -> list:
    err = rec.get("ha_leg_error")
    if err is not None:
        if isinstance(err, str) and err:
            return []  # leg failed and says why — valid record
        return [f"{name}: ha_leg_error must be a non-empty "
                f"string, got {err!r}"]
    errs = []
    for key, (ok, want) in HA_FIELDS.items():
        if not ok(rec.get(key)):
            errs.append(f"{name}: schema>=5 record needs {key} "
                        f"({want}), got {rec.get(key)!r}")
    return errs


# _trace_leg acceptance fields required on schema >= 6 records
# ((validator, description) per field; see bench.py _trace_leg).
TRACE_FIELDS = {
    "trace_overhead_frac": (
        lambda v: _is_num(v) and v <= 0.03,
        "number <= 0.03 (the trace plane may cost at most 3%)"),
    "trace_stitched_traces": (
        lambda v: _is_num(v) and v >= 1,
        "number >= 1 (a disagg request must stitch both replicas "
        "into one trace)"),
    "trace_flow_links": (
        lambda v: _is_num(v) and v >= 1,
        "number >= 1 (the KV handoff must carry a Perfetto s/f "
        "flow pair)"),
}


def _check_trace_fields(name: str, rec: dict) -> list:
    err = rec.get("trace_leg_error")
    if err is not None:
        if isinstance(err, str) and err:
            return []  # leg failed and says why — valid record
        return [f"{name}: trace_leg_error must be a non-empty "
                f"string, got {err!r}"]
    errs = []
    for key, (ok, want) in TRACE_FIELDS.items():
        if not ok(rec.get(key)):
            errs.append(f"{name}: schema>=6 record needs {key} "
                        f"({want}), got {rec.get(key)!r}")
    return errs


# _canary_leg acceptance fields required on schema >= 7 records
# ((validator, description) per field; see bench.py _canary_leg).
CANARY_FIELDS = {
    "canary_soak_probes": (
        lambda v: _is_num(v) and v >= 60,
        "number >= 60 (the clean soak must be long enough to trust "
        "the zero-false-positive claim)"),
    "canary_false_positives": (
        lambda v: v == 0 and not isinstance(v, bool),
        "exactly 0 (a sentinel that cries wolf gets ignored)"),
    "canary_detection_probes": (
        lambda v: _is_num(v) and 1 <= v <= 3,
        "number in [1, 3] (the seeded corruption must be caught "
        "within 3 probes)"),
    "canary_vote_attribution": (
        lambda v: v is True,
        "true (the vote must isolate exactly the corrupted replica)"),
    "canary_quarantine_hint": (
        lambda v: v is True,
        "true (sustained divergence must emit a quarantine hint)"),
    "canary_overhead_frac": (
        lambda v: _is_num(v) and v <= 0.05,
        "number <= 0.05 (the always-on numerics tap may cost at "
        "most 5%)"),
    "canary_parity": (
        lambda v: v is True,
        "true (the sentinel must be invisible to tenant tokens)"),
}


def _check_canary_fields(name: str, rec: dict) -> list:
    err = rec.get("canary_leg_error")
    if err is not None:
        if isinstance(err, str) and err:
            return []  # leg failed and says why — valid record
        return [f"{name}: canary_leg_error must be a non-empty "
                f"string, got {err!r}"]
    errs = []
    for key, (ok, want) in CANARY_FIELDS.items():
        if not ok(rec.get(key)):
            errs.append(f"{name}: schema>=7 record needs {key} "
                        f"({want}), got {rec.get(key)!r}")
    return errs


def main(argv) -> int:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--dir", default=repo,
                        help="directory holding BENCH_*.json")
    args = parser.parse_args(argv)
    paths = sorted(glob.glob(os.path.join(args.dir, "BENCH_*.json")))
    errors: list = []
    for path in paths:
        name = os.path.basename(path)
        try:
            with open(path, encoding="utf-8") as f:
                rec = json.load(f)
        except Exception as e:  # noqa: BLE001 - that IS the finding
            errors.append(f"{name}: unparseable JSON ({e})")
            continue
        if isinstance(rec, dict) and "parsed" in rec:
            # Driver wrapper shape: {"n", "cmd", "rc", "tail",
            # "parsed": <bench record>}. A failed capture (rc != 0)
            # legitimately carries parsed=null — the schema binds the
            # RECORD, not the driver's failure bookkeeping.
            payload = rec.get("parsed")
            if payload is None:
                if rec.get("rc", 1) == 0:
                    errors.append(
                        f"{name}: rc=0 wrapper with no parsed record")
                continue
            rec = payload
        errors.extend(check_record(name, rec))
    if errors:
        print(f"lint_bench: {len(errors)} violation(s) in "
              f"{len(paths)} record(s):", file=sys.stderr)
        for e in errors:
            print(f"  {e}", file=sys.stderr)
        return 1
    print(f"lint_bench: {len(paths)} record(s) clean")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
