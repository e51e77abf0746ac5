#!/usr/bin/env python3
"""Ban undocumented (and orphaned) ``vdt:`` metrics and label sets.

Every metric name the package emits must be (a) exposed with HELP/TYPE
lines and (b) listed in the README metrics table — otherwise dashboards
silently miss new families and the README rots. Mechanically:

* **emitted** — every quoted ``"vdt:..."`` string literal under the
  package tree (metric names only ever cross the code as literals).
* **has exposition** — the name appears in ``metrics/prometheus.py`` or
  ``metrics/stats.py`` (their render paths emit HELP/TYPE for every
  name they carry), or some package file contains a literal
  ``# HELP <name>`` (ad-hoc exposition blocks, e.g. the admission gauges
  in the API server).
* **documented** — the name appears in the README metrics table
  (any backticked ``vdt:...`` token in the README counts).
* **labels documented** — every family in the ``LABELED_METRICS``
  registry of ``metrics/prometheus.py`` (the single source of truth
  for label names) must appear in the README with its exact label set,
  as ``` `vdt:name{label1,label2}` ```; a README row carrying labels
  the registry does not declare is equally a failure.

Failures: emitted without exposition, emitted without a README row, a
README row naming a metric nothing emits (orphan), a labeled family
whose README row is missing its label set, or a README label set the
registry does not declare.

* **dynamic labels need a cardinality note** — label names whose
  values come from TRAFFIC rather than a fixed enum (today:
  ``tenant``) are a series-explosion hazard; every registry family
  carrying one must document, on its README table row, the mechanism
  that bounds the label space (for ``tenant``: the
  ``VDT_QOS_MAX_TRACKED_TENANTS`` hash/bucket cap). A row that names
  such a family without naming its bound fails.

* **stats keys are read** — a top-level key that a runner, worker or
  scheduler ``get_stats`` writes (``stats["key"] = ...``, a key of the
  ``stats = {...}`` / ``stats.update({...})`` / ``return {...}``
  literal) must be named, quoted, by one of its readers:
  ``metrics/prometheus.py`` (the render), ``engine/dp_client.py`` (the
  DP merge) or ``entrypoints/openai/api_server.py`` (the ``/debug/*``
  handlers). A key none of them names is an orphan: a counter kept up
  on the step path for nobody.

Usage::

    python scripts/lint_metrics.py [--package DIR] [--readme FILE]

Exit 0 when clean; exit 1 listing violations otherwise.
"""

import argparse
import ast
import re
import sys
from pathlib import Path

METRIC_LITERAL_RE = re.compile(r"""["'](vdt:[a-z0-9_]+)""")
# Backticked README token, optionally carrying a {label1,label2} set.
METRIC_NAME_RE = re.compile(
    r"`(vdt:[a-z0-9_]+)(?:\{([a-z_][a-z_,]*)\})?")
# One LABELED_METRICS entry: "vdt:name": ("label", ...),
REGISTRY_ENTRY_RE = re.compile(
    r'"(vdt:[a-z0-9_]+)":\s*\(([^)]*)\)')
LABEL_NAME_RE = re.compile(r'"([a-z_]+)"')

# Modules whose registries/render helpers always emit HELP/TYPE for the
# names they carry.
EXPOSITION_MODULES = ("metrics/prometheus.py", "metrics/stats.py")

# Label names whose value space comes from traffic (not a fixed enum):
# a family carrying one must document its cardinality bound — the
# named token must appear on the metric's README table row.
DYNAMIC_LABELS = {"tenant": "VDT_QOS_MAX_TRACKED_TENANTS"}


# get_stats of the step path's three layers, and who may read a key.
STATS_WRITERS = ("worker/model_runner.py", "worker/worker.py",
                 "core/sched/scheduler.py")
STATS_READERS = ("metrics/prometheus.py", "engine/dp_client.py",
                 "entrypoints/openai/api_server.py")


def _written_stats_keys(source: str) -> dict[str, int]:
    """-> {top-level key a ``get_stats`` of this module writes: line}."""
    keys: dict[str, int] = {}

    def is_stats(node) -> bool:
        return isinstance(node, ast.Name) and node.id == "stats"

    for fn in ast.walk(ast.parse(source)):
        if not (isinstance(fn, ast.FunctionDef)
                and fn.name == "get_stats"):
            continue
        for node in ast.walk(fn):
            literal = None
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    if (isinstance(t, ast.Subscript) and is_stats(t.value)
                            and isinstance(t.slice, ast.Constant)
                            and isinstance(t.slice.value, str)):
                        keys[t.slice.value] = node.lineno
                    elif is_stats(t):
                        literal = node.value
            elif (isinstance(node, ast.Call)
                  and isinstance(node.func, ast.Attribute)
                  and node.func.attr == "update"
                  and is_stats(node.func.value) and node.args):
                literal = node.args[0]
            elif isinstance(node, ast.Return):
                literal = node.value
            if isinstance(literal, ast.Dict):
                for k in literal.keys:
                    if (isinstance(k, ast.Constant)
                            and isinstance(k.value, str)):
                        keys[k.value] = k.lineno
    return keys


def orphan_stats_keys(package: Path) -> list[tuple[str, str, int]]:
    """-> [(writer module, key, line)] of keys no reader names."""
    readers = "".join((package / rel).read_text(encoding="utf-8")
                      for rel in STATS_READERS
                      if (package / rel).is_file())
    orphans = []
    for rel in STATS_WRITERS:
        path = package / rel
        if not path.is_file():
            continue
        written = _written_stats_keys(path.read_text(encoding="utf-8"))
        orphans += [(rel, key, line)
                    for key, line in sorted(written.items())
                    if f'"{key}"' not in readers
                    and f"'{key}'" not in readers]
    return orphans


def collect(package: Path) -> tuple[set, set]:
    """-> (emitted names, names with HELP/TYPE exposition)."""
    emitted: set[str] = set()
    exposed: set[str] = set()
    registry_text = ""
    for rel in EXPOSITION_MODULES:
        path = package / rel
        if path.is_file():
            registry_text += path.read_text(encoding="utf-8")
    for path in sorted(package.rglob("*.py")):
        text = path.read_text(encoding="utf-8")
        for name in METRIC_LITERAL_RE.findall(text):
            emitted.add(name)
            if f"# HELP {name}" in text or name in registry_text:
                exposed.add(name)
    return emitted, exposed


def labeled_registry(package: Path) -> dict[str, frozenset]:
    """The LABELED_METRICS literal of metrics/prometheus.py, parsed
    textually (this linter runs without importing the package)."""
    path = package / "metrics" / "prometheus.py"
    if not path.is_file():
        return {}
    text = path.read_text(encoding="utf-8")
    marker = text.find("LABELED_METRICS")
    if marker < 0:
        return {}
    # Stop at the end of the dict literal so stray tuples elsewhere in
    # the module can't parse as registry entries.
    block = text[marker:text.find("}", marker)]
    return {
        name: frozenset(LABEL_NAME_RE.findall(labels))
        for name, labels in REGISTRY_ENTRY_RE.findall(block)
    }


def readme_metrics(readme: Path) -> dict[str, set]:
    """-> {name: set of documented label frozensets} (an unlabeled
    mention contributes an empty frozenset)."""
    out: dict[str, set] = {}
    for name, labels in METRIC_NAME_RE.findall(
            readme.read_text(encoding="utf-8")):
        sets = out.setdefault(name, set())
        sets.add(frozenset(labels.split(",")) if labels
                 else frozenset())
    return out


def main(argv: list[str]) -> int:
    repo = Path(__file__).resolve().parent.parent
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--package", type=Path,
                        default=repo / "vllm_distributed_tpu",
                        help="package tree to scan for emitted metrics")
    parser.add_argument("--readme", type=Path,
                        default=repo / "README.md",
                        help="README carrying the metrics table")
    args = parser.parse_args(argv)
    if not args.package.is_dir():
        print(f"lint_metrics: no such directory: {args.package}",
              file=sys.stderr)
        return 2
    if not args.readme.is_file():
        print(f"lint_metrics: no such file: {args.readme}",
              file=sys.stderr)
        return 2

    emitted, exposed = collect(args.package)
    documented = readme_metrics(args.readme)
    registry = labeled_registry(args.package)
    problems: list[str] = []
    for name in sorted(emitted - exposed):
        problems.append(f"{name}: emitted without HELP/TYPE exposition "
                        f"(add it to metrics/prometheus.py or an "
                        f"explicit '# HELP {name}' block)")
    for name in sorted(emitted - documented.keys()):
        problems.append(f"{name}: missing from the README metrics table "
                        f"({args.readme.name})")
    for name in sorted(documented.keys() - emitted):
        problems.append(f"{name}: in the README metrics table but "
                        f"emitted nowhere (orphaned row)")
    # Labeled families: the registry's label set must appear verbatim
    # in the README, and the README must not invent label sets.
    for name in sorted(registry):
        labels = registry[name]
        if not labels or name not in documented:
            continue  # missing row already reported above
        if labels not in documented[name]:
            want = ",".join(sorted(labels))
            problems.append(
                f"{name}: emitted with labels {{{want}}} but the "
                f"README row does not document them (write "
                f"`{name}{{{want}}}` in the metrics table)")
    for name in sorted(documented):
        declared = registry.get(name, frozenset())
        for labels in documented[name]:
            if labels and labels != declared:
                got = ",".join(sorted(labels))
                problems.append(
                    f"{name}: README documents labels {{{got}}} but "
                    f"the LABELED_METRICS registry declares "
                    f"{sorted(declared) if declared else 'none'}")
    # Dynamic (traffic-valued) labels: the family's README table row
    # must name the mechanism bounding the label space.
    readme_lines = args.readme.read_text(encoding="utf-8").splitlines()
    for name in sorted(registry):
        bounds = sorted({DYNAMIC_LABELS[lb] for lb in registry[name]
                         if lb in DYNAMIC_LABELS})
        if not bounds or name not in documented:
            continue
        rows = [ln for ln in readme_lines if f"`{name}{{" in ln]
        for bound in bounds:
            if rows and not any(bound in ln for ln in rows):
                problems.append(
                    f"{name}: carries a dynamic label but its README "
                    f"row has no cardinality note (mention `{bound}`, "
                    f"the bucketing bound, on the row)")
    for rel, key, line in orphan_stats_keys(args.package):
        problems.append(
            f'{rel}:{line}: get_stats writes "{key}" and no reader '
            f"names it ({', '.join(STATS_READERS)}): an orphaned "
            f"stats key")
    if not problems:
        return 0
    print("vdt: metric documentation drift:", file=sys.stderr)
    for p in problems:
        print(f"  {p}", file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
