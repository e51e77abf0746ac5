"""CI guard: every emitted vdt: metric stays documented.

Runs scripts/lint_metrics.py over the real package + README (tier-1
mechanical check) and unit-tests the linter's failure modes on
synthetic trees: an emitted-but-undocumented metric, a metric without
HELP/TYPE exposition, an orphaned README row, and a stats key that
``get_stats`` writes for no reader."""

import importlib.util
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
SCRIPT = REPO / "scripts" / "lint_metrics.py"


def _run(*args: str) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, str(SCRIPT), *args],
                          capture_output=True, text=True, timeout=60)


def _tree(tmp_path, source: str, readme: str, prometheus: str = ""):
    pkg = tmp_path / "pkg"
    (pkg / "metrics").mkdir(parents=True)
    (pkg / "emitter.py").write_text(source)
    if prometheus:
        (pkg / "metrics" / "prometheus.py").write_text(prometheus)
    readme_path = tmp_path / "README.md"
    readme_path.write_text(readme)
    return pkg, readme_path


def test_package_metrics_are_documented():
    res = _run()
    assert res.returncode == 0, (
        f"vdt: metric documentation drifted:\n{res.stderr}")


def test_undocumented_metric_is_caught(tmp_path):
    """A metric emitted with exposition but missing its README row."""
    src = ('LINES = ["# HELP vdt:bogus_total x",\n'
           '         "# TYPE vdt:bogus_total counter",\n'
           '         "vdt:bogus_total 1"]\n')
    pkg, readme = _tree(tmp_path, src, "# nothing here\n")
    res = _run("--package", str(pkg), "--readme", str(readme))
    assert res.returncode == 1
    assert "vdt:bogus_total" in res.stderr
    assert "missing from the README" in res.stderr


def test_unexposed_metric_is_caught(tmp_path):
    """A metric emitted as a bare literal with no HELP/TYPE anywhere."""
    pkg, readme = _tree(tmp_path, 'NAME = "vdt:sneaky_total"\n',
                        "| `vdt:sneaky_total` | counter | x |\n")
    res = _run("--package", str(pkg), "--readme", str(readme))
    assert res.returncode == 1
    assert "without HELP/TYPE exposition" in res.stderr


def test_orphaned_readme_row_is_caught(tmp_path):
    pkg, readme = _tree(tmp_path, "x = 1\n",
                        "| `vdt:ghost_total` | counter | gone |\n")
    res = _run("--package", str(pkg), "--readme", str(readme))
    assert res.returncode == 1
    assert "orphaned row" in res.stderr


def test_clean_synthetic_tree_passes(tmp_path):
    src = ('LINES = ["# HELP vdt:ok_total x",\n'
           '         "# TYPE vdt:ok_total counter",\n'
           '         "vdt:ok_total 1"]\n')
    pkg, readme = _tree(tmp_path, src,
                        "| `vdt:ok_total` | counter | fine |\n")
    res = _run("--package", str(pkg), "--readme", str(readme))
    assert res.returncode == 0, res.stderr


def test_missing_package_is_a_usage_error(tmp_path):
    res = _run("--package", str(tmp_path / "nope"),
               "--readme", str(tmp_path / "also-nope"))
    assert res.returncode == 2


# ---------------------------------------------------------------------------
# Labeled families: LABELED_METRICS registry <-> README label sets.
# ---------------------------------------------------------------------------
_LABELED_PROM = (
    '# HELP vdt:labeled_total x\n'
    '# TYPE vdt:labeled_total counter\n'
    'LABELED_METRICS = {\n'
    '    "vdt:labeled_total": ("conn", "dir"),\n'
    '}\n')


def test_undocumented_label_set_is_caught(tmp_path):
    """A labeled family whose README row lacks its {label} set."""
    pkg, readme = _tree(tmp_path, "x = 1\n",
                        "| `vdt:labeled_total` | counter | x |\n",
                        prometheus=_LABELED_PROM)
    res = _run("--package", str(pkg), "--readme", str(readme))
    assert res.returncode == 1
    assert "does not document them" in res.stderr
    assert "vdt:labeled_total{conn,dir}" in res.stderr


def test_spurious_readme_labels_are_caught(tmp_path):
    """A README label set the registry never declared."""
    pkg, readme = _tree(
        tmp_path, "x = 1\n",
        "| `vdt:labeled_total{conn,dir}` | counter | x |\n"
        "| `vdt:labeled_total{bogus}` | counter | dup |\n",
        prometheus=_LABELED_PROM)
    res = _run("--package", str(pkg), "--readme", str(readme))
    assert res.returncode == 1
    assert "registry declares" in res.stderr


def test_clean_labeled_tree_passes(tmp_path):
    pkg, readme = _tree(
        tmp_path, "x = 1\n",
        "| `vdt:labeled_total{conn,dir}` | counter | x |\n",
        prometheus=_LABELED_PROM)
    res = _run("--package", str(pkg), "--readme", str(readme))
    assert res.returncode == 0, res.stderr


# ---------------------------------------------------------------------------
# Dynamic (traffic-valued) labels: the {tenant} family must carry a
# bounded-cardinality note naming VDT_QOS_MAX_TRACKED_TENANTS.
# ---------------------------------------------------------------------------
_TENANT_PROM = (
    '# HELP vdt:tenant_x_total x\n'
    '# TYPE vdt:tenant_x_total counter\n'
    'LABELED_METRICS = {\n'
    '    "vdt:tenant_x_total": ("tenant", ),\n'
    '}\n')


def test_dynamic_label_without_cardinality_note_is_caught(tmp_path):
    """A {tenant} family documented with its label set but WITHOUT the
    bucketing-bound note on the row: series-explosion hazard."""
    pkg, readme = _tree(
        tmp_path, "x = 1\n",
        "| `vdt:tenant_x_total{tenant}` | counter | per tenant |\n",
        prometheus=_TENANT_PROM)
    res = _run("--package", str(pkg), "--readme", str(readme))
    assert res.returncode == 1
    assert "cardinality note" in res.stderr
    assert "VDT_QOS_MAX_TRACKED_TENANTS" in res.stderr


def test_dynamic_label_with_cardinality_note_passes(tmp_path):
    pkg, readme = _tree(
        tmp_path, "x = 1\n",
        "| `vdt:tenant_x_total{tenant}` | counter | per tenant "
        "(bounded by `VDT_QOS_MAX_TRACKED_TENANTS`) |\n",
        prometheus=_TENANT_PROM)
    res = _run("--package", str(pkg), "--readme", str(readme))
    assert res.returncode == 0, res.stderr


# ---------------------------------------------------------------------------
# Stats keys: what a runner, worker or scheduler get_stats writes, one of
# the render, the DP merge or a /debug handler names.
# ---------------------------------------------------------------------------
def test_package_stats_keys_all_have_a_reader():
    spec = importlib.util.spec_from_file_location("lint_metrics", SCRIPT)
    lint_metrics = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint_metrics)
    package = REPO / "vllm_distributed_tpu"
    written = {rel: lint_metrics._written_stats_keys(
        (package / rel).read_text()) for rel in lint_metrics.STATS_WRITERS}
    # The walk finds the keys (an empty walk would pass anything).
    assert "step_kv_tokens_read" in written["worker/model_runner.py"]
    assert "state_row_bytes" in written["worker/model_runner.py"]
    assert "workers" in written["worker/worker.py"]
    assert "num_preemptions" in written["core/sched/scheduler.py"]
    assert lint_metrics.orphan_stats_keys(package) == []


def test_orphaned_stats_key_is_caught(tmp_path):
    """A key that outlived its readers (PR 46 took both ends of the
    attribution table at once): written on the step path, named by no
    render, merge or handler."""
    pkg, readme = _tree(tmp_path, "x = 1\n", "# nothing here\n",
                        prometheus='KEYS = ("kept", "also_kept")\n')
    (pkg / "worker").mkdir()
    (pkg / "worker" / "model_runner.py").write_text(
        "class Runner:\n"
        "    def get_stats(self):\n"
        '        stats = {"kept": 1}\n'
        '        stats["ghost_key"] = 2\n'
        '        stats.update({"also_kept": 3})\n'
        "        return stats\n")
    res = _run("--package", str(pkg), "--readme", str(readme))
    assert res.returncode == 1
    assert '"ghost_key"' in res.stderr
    assert "orphaned stats key" in res.stderr
    assert "kept\"" not in res.stderr
