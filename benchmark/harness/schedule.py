"""The one traffic generator: a mix file's parameters + a seed -> the plan.

Pure functions (no clock, no I/O): the same mix and seed give the same
plan. Every seed gets the same *set* of inter-arrival gaps, in another
order, so that the amount of work in a window does not depend on the seed.
The gaps are the quantiles of the exponential distribution and an arrival
sits at the end of each, so the times between arrivals are that set itself:
a Poisson process's (coefficient of variation 1, 2.4 % of them under 1/40
of the mean), not a smoothed one.

A plan is a list of entries in sending order, each
`{"i", "due_s", "group", "slot", "kind", "client"}`: `due_s` is relative
to the opening of the window (negative while warming), `group`/`slot` say
where the request lives in the corpus, `kind` is "ok" or one of the bad
kinds, `client` the connection that hands it over (backlog only), `joint`
the hand-over it shares with its neighbours (one `submit_many`).
"""

from __future__ import annotations

import math
import random

GROUP_TXS = 64  # transfers per issue request: a 128-output issue is 4.5 MB


def _gaps(rate: float, span: float) -> list:
    """round(rate * span) inter-arrival gaps that sum to `span`: the
    quantiles of the exponential distribution, so the set is the same for
    every seed."""
    n = max(1, round(rate * span))
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = span / sum(gaps)
    return [g * scale for g in gaps]


def _spread(gaps: list, start: float, rng) -> list:
    """Arrival times in [start, start + sum(gaps)): the seed orders the
    gaps, the first arrival is at `start` and each later one a whole gap
    after the one before (the last gap runs to the end of the span, where
    the next span's first arrival sits)."""
    rng.shuffle(gaps)
    t, out = start, []
    for g in gaps:
        out.append(t)
        t += g
    return out


def arrivals(mix: dict, seconds: float, seed: int, draw: int = 0) -> list:
    """Due times, relative to the window's opening, sorted. The window
    holds the same number of arrivals whatever the seed. `draw` > 0 is the
    seed's next order of the same gaps (`joint_layout`)."""
    proc = mix["arrivals"]
    if proc == "at_open":
        return [0.0] * int(mix["backlog_txs"])
    if proc != "poisson":
        raise ValueError(f"unknown arrival process {proc!r}")
    rate, warm = float(mix["rate_tps"]), float(mix.get("warm_s", 0.0))
    rng = random.Random(f"{seed}/arrivals" + (f"/{draw}" if draw else ""))
    out = _spread(_gaps(rate, warm), -warm, rng) if warm > 0 else []
    return out + _spread(_gaps(rate, seconds), 0.0, rng)


DRAWS = 1000  # orders of the gaps tried for a `joint_layout`


def plan(mix: dict, bad_kinds: list, seconds: float, seed: int) -> list:
    """The seed's plan. Where the mix fixes a `joint_layout`, the first of
    the seed's orders of the gaps that has it: the layout is part of the
    work (how many hand-overs arrive whole, how many find a single arrival
    just ahead of them), and every seed gets the same work in another
    order."""
    layout = mix.get("joint_layout")
    for draw in range(DRAWS if layout else 1):
        entries = _ordered(mix, seconds, seed, draw)
        if not layout or _has_layout(entries, mix, layout, seconds):
            break
    else:
        raise ValueError(f"no order of seed {seed}'s gaps in {DRAWS} has the "
                         f"joint_layout {layout}")
    rng = random.Random(f"{seed}/bad")
    _place_bad(entries, bad_kinds, mix, seconds, rng)
    return entries


def _has_layout(entries, mix, layout, seconds) -> bool:
    """Every hand-over holds its `txs`, and exactly `meetings` of them have
    an arrival of their own (not in a hand-over) due in the
    `meet_within_s` before them."""
    joint = mix.get("joint", [])
    sizes = [0] * len(joint)
    for e in entries:
        if "joint" in e:
            sizes[e["joint"]] += 1
    if sizes != [int(j["txs"]) for j in joint]:
        return False
    within = float(layout["meet_within_s"])
    alone = [e["due_s"] for e in entries if "joint" not in e]
    met = sum(1 for j in joint
              if any(0.0 <= float(j["at_share"]) * seconds - t < within
                     for t in alone))
    return met == int(layout["meetings"])


def _ordered(mix: dict, seconds: float, seed: int, draw: int) -> list:
    due = arrivals(mix, seconds, seed, draw)
    n = len(due)
    entries = [{"i": i, "due_s": due[i], "group": f"g{i // GROUP_TXS}",
                "slot": i % GROUP_TXS, "kind": "ok", "client": 0}
               for i in range(n)]
    if mix["arrivals"] == "at_open":
        h = mix["handover"]
        clients, stagger = int(h["clients"]), float(h.get("stagger_s", 0.0))
        per = -(-n // clients)
        for e in entries:
            e["client"] = e["i"] // per
            e["due_s"] = e["client"] * stagger
    for k, joint in enumerate(mix.get("joint", [])):
        # one client hands `txs` requests over in one call, at `at_share`
        # of the window (where the mix's traced slice waits for it): the
        # next ones due after that, brought forward to it
        at = float(joint["at_share"]) * seconds
        k_txs = int(joint["txs"])
        start = next((i for i, e in enumerate(entries) if e["due_s"] >= at), n)
        start = max(0, min(start, n - k_txs))  # the window's last ones at the latest
        for e in entries[start:start + k_txs]:
            e["joint"], e["due_s"] = k, at
    return entries


def _place_bad(entries, bad_kinds, mix, seconds, rng) -> None:
    """Seeded places for the bad requests: due inside the window, early
    enough to be judged in it; a double spend after the slot it re-spends,
    in the same group and at least `min_gap_s` later (or, in a backlog,
    later in the same hand-over)."""
    if mix["arrivals"] == "at_open":
        # the first hand-over is the block that commits inside the window
        pool = [e for e in entries if e["client"] == 0]
        min_gap = 0.0
    else:
        last = seconds * float(mix.get("bad_before_share", 0.6))
        pool = [e for e in entries if 0.0 <= e["due_s"] <= last
                and "joint" not in e]
        min_gap = float(mix.get("min_gap_s", 0.5))
    if len(pool) < 2 * len(bad_kinds) + 2:
        raise ValueError("too few requests in the window for the bad ones")
    taken = set()
    for kind in bad_kinds:
        for _ in range(1000):
            e = rng.choice(pool)
            if e["i"] in taken or e["kind"] != "ok":
                continue
            if kind == "double_spend":
                earlier = [p for p in entries
                           if p["group"] == e["group"] and p["kind"] == "ok"
                           and p["i"] < e["i"] and p["i"] not in taken
                           and e["due_s"] - p["due_s"] >= min_gap]
                if not earlier:
                    continue
                first = earlier[-1]
                e["of"] = first["slot"]
                taken.add(first["i"])  # stays "ok", but no other bad lands on it
            e["kind"] = kind
            taken.add(e["i"])
            break
        else:
            raise ValueError(f"no place for the {kind} request")


def groups(entries: list) -> dict:
    """group name -> its slot plan, in slot order."""
    out = {}
    for e in entries:
        slot = {"kind": e["kind"]}
        if "of" in e:
            slot["of"] = e["of"]
        out.setdefault(e["group"], []).append(slot)
    return out
