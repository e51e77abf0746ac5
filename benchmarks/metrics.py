"""Metric arithmetic of the benchmark: client timestamps to end-to-end
metrics, and Prometheus text snapshots to counts and histogram
reductions. Imports no JAX and nothing of the program.

A request record is what the load generator writes for each request:

    {"due": s, "sent": s, "chunks": [[t, n_tokens], ...], "end": s|None,
     "ok": bool, "want": max_tokens, "error": str|None}

All times are ``time.monotonic()`` seconds of one machine. ``chunks``
lists only token-bearing chunks of the stream.
"""

import math
import re


def percentile(values, p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least p% of
    the sample at or below it. No interpolation, so a tail is a time
    that was really seen."""
    if not values:
        raise ValueError("percentile of an empty sample")
    s = sorted(values)
    return float(s[max(0, math.ceil(p / 100.0 * len(s)) - 1)])


def request_ok(r: dict) -> bool:
    """Finished, without error, with the number of tokens asked for."""
    return bool(r.get("ok")) and r.get("end") is not None and \
        sum(n for _, n in r["chunks"]) == r["want"]


def ttft_s(r: dict) -> float:
    """Time to first token, from when the request was DUE (open loop) so
    that a stall of the generator or the server is charged to every
    request it delayed."""
    return r["chunks"][0][0] - r["due"]


def gaps_s(r: dict) -> list[float]:
    """Gaps between successive token-bearing chunks of one stream."""
    t = [c[0] for c in r["chunks"]]
    return [b - a for a, b in zip(t, t[1:])]


def tpot_s(r: dict):
    """(last token - first token) / (tokens - 1); None under 2 tokens."""
    n = sum(c[1] for c in r["chunks"])
    if n < 2 or len(r["chunks"]) < 2:
        return None
    return (r["chunks"][-1][0] - r["chunks"][0][0]) / (n - 1)


def end_to_end(records: list[dict], loop: str, t0: float, t1: float) -> dict:
    """End-to-end numbers of one window [t0, t1].

    Open loop: the measured requests are those DUE in the window; all
    their chunks count, also those after t1 (the drain). Closed loop:
    the requests that ENDED in the window give ``tpot``, every gap whose
    later chunk fell in the window gives ``itl``, every token received
    in the window gives the rate. A request that failed, was cut short
    or had not finished when the drain cap ended counts in ``failed``
    and in no latency sample."""
    if loop == "open":
        mine = [r for r in records if t0 <= r["due"] < t1]
    else:
        mine = [r for r in records
                if (r.get("end") is not None and t0 <= r["end"] < t1)
                or (r.get("end") is None and not r.get("cancelled")
                    and r.get("sent") is not None and r["sent"] < t1)]
    good = [r for r in mine if request_ok(r)]
    out = {"attempted": len(mine), "failed": len(mine) - len(good),
           "metrics": {}, "samples": {}}
    if loop == "open":
        gaps = [g for r in good for g in gaps_s(r)]
        ttft = [ttft_s(r) for r in good]
        if ttft:
            out["metrics"]["ttft_p95_ms"] = 1e3 * percentile(ttft, 95)
            out["metrics"]["ttft_p50_ms"] = 1e3 * percentile(ttft, 50)
        out["samples"]["ttft"] = len(ttft)
        lateness = [r["sent"] - r["due"] for r in mine
                    if r.get("sent") is not None]
        if lateness:
            out["metrics"]["client.send_lateness_p95_ms"] = \
                1e3 * percentile(lateness, 95)
    else:
        gaps = []
        for r in records:
            t = [c[0] for c in r["chunks"]]
            gaps += [b - a for a, b in zip(t, t[1:]) if t0 <= b < t1]
    tokens_in_window = sum(n for r in records for t, n in r["chunks"]
                           if t0 <= t < t1)
    out["metrics"]["output_tokens_per_s"] = tokens_in_window / (t1 - t0)
    tpot = [v for v in map(tpot_s, good) if v is not None]
    if gaps:
        out["metrics"]["itl_p95_ms"] = 1e3 * percentile(gaps, 95)
        out["metrics"]["itl_p50_ms"] = 1e3 * percentile(gaps, 50)
    if tpot:
        out["metrics"]["tpot_p50_ms"] = 1e3 * percentile(tpot, 50)
    out["samples"].update(gaps=len(gaps), tpot=len(tpot),
                          tokens_in_window=tokens_in_window)
    return out


# ---------------------------------------------------------------------
# Prometheus text
# ---------------------------------------------------------------------
_LINE = re.compile(r"^([a-zA-Z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="([^"]*)"')


def parse_prometheus(text: str) -> list[tuple[str, dict, float]]:
    out = []
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        m = _LINE.match(line.strip())
        if m:
            out.append((m.group(1), dict(_LABEL.findall(m.group(2) or "")),
                        float(m.group(3))))
    return out


def _match(labels: dict, want: dict) -> bool:
    return all(labels.get(k) == v for k, v in want.items())


def series_sum(samples, name: str, labels: dict | None = None) -> float:
    """A family summed over every label set that matches."""
    return sum(v for n, lab, v in samples
               if n == name and _match(lab, labels or {}))


def counter_delta(before, after, name: str, labels=None) -> float:
    return series_sum(after, name, labels) - series_sum(before, name, labels)


def histogram_delta(before, after, name: str, labels=None):
    """(sorted [(le, count)], sum, count) of what was observed between
    two scrapes; buckets are cumulative, as Prometheus writes them."""
    want = labels or {}

    def buckets(samples):
        acc = {}
        for n, lab, v in samples:
            if n == f"{name}_bucket" and _match(lab, want):
                le = float("inf") if lab["le"] in ("+Inf", "inf") \
                    else float(lab["le"])
                acc[le] = acc.get(le, 0.0) + v
        return acc

    b0, b1 = buckets(before), buckets(after)
    bs = sorted((le, b1[le] - b0.get(le, 0.0)) for le in b1)
    return (bs, counter_delta(before, after, f"{name}_sum", want),
            counter_delta(before, after, f"{name}_count", want))


def histogram_mean(before, after, name: str, labels=None):
    _, total, count = histogram_delta(before, after, name, labels)
    return total / count if count > 0 else None


def histogram_quantile(before, after, name: str, q: float, labels=None):
    """Prometheus's own rule: linear inside the bucket that holds the
    rank; the last finite bound where the rank falls in +Inf."""
    bs, _, count = histogram_delta(before, after, name, labels)
    if count <= 0 or not bs:
        return None
    rank = q * count
    prev_le, prev_c = 0.0, 0.0
    for le, c in bs:
        if c >= rank:
            if math.isinf(le):
                return prev_le
            if c == prev_c:
                return le
            return prev_le + (le - prev_le) * (rank - prev_c) / (c - prev_c)
        prev_le, prev_c = le, c
    return prev_le


def gauge_mean(snapshots, name: str, labels=None):
    """Mean of a gauge over scrapes taken at even intervals."""
    vals = [series_sum(s, name, labels) for s in snapshots]
    return sum(vals) / len(vals) if vals else None
