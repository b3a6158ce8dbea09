"""The arithmetic of the end-to-end metrics (pure, tested).

An *event* is what the generator recorded for one transaction:
`{"i", "due", "sent", "done", "status", "message", "error"}` — seconds
relative to the opening of the window on the generator's monotonic clock;
`done` is None when no finality came back before the generator stopped.
"""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def due_in_window(events, seconds: float):
    return [e for e in events if 0.0 <= e["due"] < seconds]


def attempted(events, seconds: float, backlog: bool):
    """The transactions the window tried: those due in it. A backlog is
    sized to outlast the window, so what is still queued, unanswered and
    unrefused, when it closes was not tried yet."""
    due = due_in_window(events, seconds)
    if backlog:
        due = [e for e in due if e["done"] is not None or e.get("error")]
    return due


def is_failed(e, seconds: float, grace_s: float) -> bool:
    """Due in the window and refused, errored, or not final within the
    grace after it. An `Invalid` verdict is an answer, not a failure."""
    return (e.get("error") is not None or e["done"] is None
            or e["done"] > seconds + grace_s)


def committed_tps(events, seconds: float, grace_s: float, rule: str) -> float:
    """`Valid` finalities per second of window.

    `due_in_window` (open loop): the transactions due inside the window
    that became final, at the latest `grace_s` after it, over the window's
    length: the same count for every seed unless the node falls behind.
    `last_commit` (backlog): the finalities inside the window over the
    time of the last of them, so that one long block is not quantised by
    the window's end."""
    if rule == "due_in_window":
        return sum(1 for e in due_in_window(events, seconds)
                   if e.get("status") == "Valid"
                   and not is_failed(e, seconds, grace_s)) / seconds
    if rule == "last_commit":
        done = [e["done"] for e in events
                if e["done"] is not None and e.get("status") == "Valid"
                and 0.0 <= e["done"] <= seconds]
        return len(done) / max(done) if done else 0.0
    raise ValueError(f"unknown committed_tps rule {rule!r}")


def finality_latencies(events, seconds: float, grace_s: float):
    """due -> finality of the transactions due inside the window that got
    an answer in time. Failed ones are counted apart (`is_failed`)."""
    return [e["done"] - e["due"] for e in due_in_window(events, seconds)
            if not is_failed(e, seconds, grace_s)]


def lateness_ms(events, seconds: float):
    return [(e["sent"] - e["due"]) * 1e3 for e in due_in_window(events, seconds)
            if e.get("sent") is not None]
