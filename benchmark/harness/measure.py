"""Arithmetic from request records to end-to-end numbers."""

import statistics


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile, q in [0, 100] (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def tail(values, which: str) -> float:
    """The cell's stated tail: "max", or "pNN".  `auto_tail` says which a
    sample of this size supports."""
    if which == "max":
        return max(values)
    if which.startswith("p"):
        return percentile(values, float(which[1:]))
    raise ValueError(f"tail {which!r}: expected 'max' or 'pNN'")


def auto_tail(n: int) -> str:
    """The highest of p99 / p95 / p90 with ten samples beyond it, else max."""
    for q in (99, 95, 90):
        if n * (100 - q) / 100.0 >= 10:
            return f"p{q}"
    return "max"


def latencies(records):
    """Seconds from when each finished request was DUE to its result on the
    host."""
    return [r["done"] - r["due"] for r in records if r["ok"]]


def completed_rate(records) -> float:
    """Completed requests over first-due -> last-done: whole requests from
    their own time stamps, never completions counted inside a fixed window."""
    ok = [r for r in records if r["ok"]]
    if not ok:
        return 0.0
    span = max(r["done"] for r in ok) - min(r["due"] for r in records)
    return len(ok) / span


def lateness_ms(records):
    """(median, max) milliseconds between a request's due time and the
    moment the generator handed it to the server."""
    late = [(r["sent"] - r["due"]) * 1e3 for r in records]
    return statistics.median(late), max(late)
