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
the hand-over it shares with its neighbours (one `submit_many`), `form`
the request form the slot sends (mixes with `requests` only: a mix with one
`transfer` sends that everywhere and its entries carry no form).

Every seed gets the same *multiset* of forms too, in another order: the
counts are the mix's shares of the arrivals due, by largest remainder.
"""

from __future__ import annotations

import math
import random

GROUP_TXS = 64  # requests a group at the most
# outputs of a group's set-up issue at the most (what its slots spend): at
# 78.8 KB an output at base 300 / exponent 5 the request is 10 MB, under the
# wire's 16 MiB; 64 two-input requests fill it exactly
GROUP_OUTPUTS = 128


def forms_of(mix: dict) -> dict:
    """form name -> form, `{"op", "in_values", "out_values", ...}`. A mix
    with one `transfer` has that one form, unnamed (its entries and slots
    carry no `form`). Values are the mix's, never drawn: the range proof's
    digits are part of the work."""
    if ("transfer" in mix) == ("requests" in mix):
        raise ValueError("a mix has exactly one of `transfer` and `requests`")
    if "transfer" in mix:
        return {"": dict(mix["transfer"], op="transfer")}
    forms = {f["form"]: f for f in mix["requests"]}
    if len(forms) != len(mix["requests"]):
        raise ValueError("two forms of one name")
    for name, f in forms.items():
        if not _conserves(f):
            raise ValueError(f"form {name!r} does not conserve")
    return forms


def _conserves(f: dict) -> bool:
    spent = sum(f.get("in_values", []))
    if f["op"] == "transfer":
        return spent == sum(f["out_values"]) > 0
    if f["op"] == "redeem":  # the first output has no owner, the rest is change
        return spent == f["redeem_value"] + sum(f["change_values"]) > 0
    if f["op"] == "issue":  # the issuer's request: it spends nothing
        return "in_values" not in f and sum(f["out_values"]) > 0
    raise ValueError(f"form {f['form']!r}: unknown op {f['op']!r}")


def slot_plan(kind: str, form: str = "") -> dict:
    """One slot of a group's plan, as the corpus worker reads it."""
    return {"kind": kind, "form": form} if form else {"kind": kind}


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
    forms = forms_of(mix)
    if "requests" in mix:
        _place_forms(entries, mix, random.Random(f"{seed}/forms"))
    _group(entries, forms)
    rng = random.Random(f"{seed}/bad")
    _place_bad(entries, bad_kinds, mix, seconds, rng, forms)
    return entries


def form_counts(requests: list, n: int) -> dict:
    """form -> how many of `n` arrivals send it: the shares by largest
    remainder (a tie goes to the form listed first)."""
    if abs(sum(float(f["share"]) for f in requests) - 1.0) > 1e-9:
        raise ValueError("the shares of `requests` do not sum to 1")
    quota = [float(f["share"]) * n for f in requests]
    counts = [int(q) for q in quota]
    # (rounded: 0.35 * 64 and 0.1 * 64 leave the same remainder, not two
    # that differ in the sixteenth digit)
    by_rest = sorted(range(len(requests)),
                     key=lambda k: round(counts[k] - quota[k], 9))
    for k in by_rest[:n - sum(counts)]:
        counts[k] += 1
    return {f["form"]: c for f, c in zip(requests, counts)}


def _place_forms(entries, mix, rng) -> None:
    """Every entry gets its form. The warm-up's arrivals and the window's
    are counted apart, so that the window holds the same multiset for every
    seed; a hand-over whose `joint` entry lists its `forms` takes those, in
    that order, out of the window's multiset (the block a median or a traced
    slice sits on then holds the same rows in every seed); the seed places
    the rest."""
    for k, joint in enumerate(mix.get("joint", [])):
        if "forms" in joint:
            if len(joint["forms"]) != int(joint["txs"]):
                raise ValueError(f"joint {k}: `forms` must list its {joint['txs']}")
            share = [e for e in entries if e.get("joint") == k]
            for e, form in zip(share, joint["forms"]):
                e["form"] = form
    for part in ([e for e in entries if e["due_s"] < 0.0],
                 [e for e in entries if e["due_s"] >= 0.0]):
        counts = form_counts(mix["requests"], len(part))
        for e in part:
            if "form" in e:
                counts[e["form"]] = counts.get(e["form"], 0) - 1
        if min(counts.values()) < 0:
            raise ValueError("the hand-overs' `forms` take more of a form than "
                             f"its share of the arrivals holds: {counts}")
        rest = [form for form, c in counts.items() for _ in range(c)]
        rng.shuffle(rest)
        for e, form in zip([e for e in part if "form" not in e], rest):
            e["form"] = form


def _group(entries, forms) -> None:
    """Groups in sending order. A group's set-up issue covers what its
    slots spend; the group is closed when the next slot would take that
    issue past GROUP_OUTPUTS, or the group past GROUP_TXS requests."""
    g = slots = outputs = 0
    for e in entries:
        spends = len(forms[e.get("form", "")].get("in_values", []))
        if slots == GROUP_TXS or outputs + spends > GROUP_OUTPUTS:
            g, slots, outputs = g + 1, 0, 0
        e["group"], e["slot"] = f"g{g}", slots
        slots, outputs = slots + 1, outputs + spends


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
    entries = [{"i": i, "due_s": due[i], "kind": "ok", "client": 0}
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


def _place_bad(entries, bad_kinds, mix, seconds, rng, forms) -> None:
    """Seeded places for the bad requests: due inside the window, early
    enough to be judged in it; a double spend after the slot it re-spends,
    in the same group and at least `min_gap_s` later (or, in a backlog,
    later in the same hand-over). On transfers only: the bad kinds are
    faults of a transfer (its proof, its inputs, its owners' signatures)."""
    def transfers(e):
        return forms[e.get("form", "")]["op"] == "transfer"

    if mix["arrivals"] == "at_open":
        # the first hand-over is the block that commits inside the window
        pool = [e for e in entries if e["client"] == 0 and transfers(e)]
        min_gap = 0.0
    else:
        last = seconds * float(mix.get("bad_before_share", 0.6))
        pool = [e for e in entries if 0.0 <= e["due_s"] <= last
                and "joint" not in e and transfers(e)]
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
                           and transfers(p)
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
        slot = slot_plan(e["kind"], e.get("form", ""))
        if "of" in e:
            slot["of"] = e["of"]
        out.setdefault(e["group"], []).append(slot)
    return out
