"""Readers of the per-layer metrics, registered by kind.

A per-layer metric is a data file `benchmark/layer_metrics/<name>.json`:
`{"reader": <kind>, ...arguments}`. A reader takes the run's `Sources`
and its arguments and returns a number, or None when it finds nothing to
read (the harness then leaves the metric out of the line). A metric over
an existing kind of source is therefore data only.
"""

from __future__ import annotations

import dataclasses
import json
import os

import stats

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Sources:
    """What one traced run leaves behind, already cut to the window."""

    events: list            # the generator's events (client clock)
    seconds: float
    grace_s: float
    counters: dict          # counter name -> delta over the window
    histograms: dict        # name -> (bucket bounds, count deltas)
    blocks: list            # `block.commit` flight events in the window
    dispatch: dict          # "plane:program" -> rows, padded_rows, dispatches, wall_s deltas
    trace: dict             # harness/trace.py:reduce() of the traced slice
    device_kind: str


READERS = {}


def reader(kind):
    def deco(fn):
        READERS[kind] = fn
        return fn
    return deco


def read(src: Sources, spec: dict):
    spec = dict(spec)
    kind = spec.pop("reader")
    if kind not in READERS:
        raise KeyError(f"no reader {kind!r}; registered: {sorted(READERS)}")
    return READERS[kind](src, **spec)


def names_read(specs: list) -> tuple:
    """(counters, histograms) of the program that these reader files name:
    what the harness has to snapshot around the window."""
    counters, histograms = set(), set()
    for spec in specs:
        for v in spec.values():
            for x in (v if isinstance(v, list) else [v]):
                if isinstance(x, dict) and "counter" in x:
                    counters.add(x["counter"])
        if spec["reader"] == "counter_ratio":
            counters.update(spec["num"] + spec["den"])
        if spec["reader"] == "histogram_quantile":
            histograms.add(spec["histogram"])
    return sorted(counters), sorted(histograms)


def _ratio(num, den, scale=1.0):
    return None if not den else scale * num / den


@reader("client_percentile")
def client_percentile(src, of, q):
    """`of`: "finality_s" (due -> finality) or "late_ms" (sent - due)."""
    xs = (stats.finality_latencies(src.events, src.seconds, src.grace_s)
          if of == "finality_s" else stats.lateness_ms(src.events, src.seconds))
    return stats.percentile(xs, q) if xs else None


@reader("counter_ratio")
def counter_ratio(src, num, den, scale=1.0):
    return _ratio(sum(src.counters.get(c, 0) for c in num),
                  sum(src.counters.get(c, 0) for c in den), scale)


@reader("histogram_quantile")
def histogram_quantile(src, histogram, q):
    """Bucket-interpolated quantile of the window's observations."""
    bounds, counts = src.histograms.get(histogram, ((), ()))
    total = sum(counts)
    if not total:
        return None
    rank, cum, prev = q * total, 0, 0.0
    for b, c in zip(bounds, counts):
        if c and cum + c >= rank:
            return prev + (b - prev) * (rank - cum) / c
        cum += c
        prev = b
    return prev  # the rank fell in the +Inf bucket: its lower bound


@reader("blocks_ratio")
def blocks_ratio(src, num, den, scale=1.0):
    """Sum over the window's blocks of field `num` ("txs": the block's
    transaction count) over `den`: "blocks", "txs", another field, or
    {"counter": name}."""
    def total(what):
        if isinstance(what, dict):
            return src.counters.get(what["counter"], 0)
        if what == "blocks":
            return len(src.blocks)
        if what == "txs":
            return sum(len(b["txs"]) for b in src.blocks)
        return sum(b.get(what, 0.0) for b in src.blocks)
    return _ratio(total(num), total(den), scale)


@reader("window_idle_share")
def window_idle_share(src, busy_fields):
    """Percent of the whole window in which no device plane was at work:
    100 x (1 - sum of the blocks' `busy_fields` / window). The fields are
    the program's host-clock spans around device work that ends in a
    read-back (`device_verify_s`, `sign_verify_s`: zero for a block the
    node's policy kept on the host), so this is a host-clock number over
    the window, where the traced slice is a fraction of a second."""
    if not src.blocks:
        return None
    busy = sum(b.get(f, 0.0) for b in src.blocks for f in busy_fields)
    return 100.0 * (1.0 - busy / src.seconds)


@reader("padding_share")
def padding_share(src, planes=None):
    rows = padded = 0
    for key, e in src.dispatch.items():
        if planes is None or key.split(":")[0] in planes:
            rows += e["rows"]
            padded += e["padded_rows"]
    return _ratio(100.0 * padded, rows + padded)


@reader("dispatch_ms")
def dispatch_ms(src, programs, rows_per_tile):
    """Host-clock milliseconds per `rows_per_tile` dispatched rows from the
    program's dispatch ledger, whatever height a dispatch has: a
    "plane:program" frame spans all the tiles of one call, transfers and
    read-back included, and counts their rows."""
    hit = [e for k, e in src.dispatch.items() if k in programs]
    return _ratio(1e3 * sum(e["wall_s"] for e in hit),
                  sum(e["rows"] + e["padded_rows"] for e in hit) / rows_per_tile)


def _whole_tiles(src, program, height_of, rows_per_tile):
    """(device seconds, `rows_per_tile`-row tiles) of the whole dispatches
    of `program` in the traced slice. A dispatch holds as many rows as the
    ledger entry `height_of` says its newest dispatch had (`tile_rows`),
    so the result does not depend on the height the program runs at."""
    p = src.trace.get("programs", {}).get(program)
    height = src.dispatch.get(height_of, {}).get("tile_rows", 0)
    if not p or not p["dispatches"] or not height:
        return 0.0, 0.0
    return p["seconds"], p["dispatches"] * height / rows_per_tile


@reader("trace_program_ms")
def trace_program_ms(src, programs, height_of, rows_per_tile):
    """Device milliseconds per `rows_per_tile` rows of the named programs:
    the device time of their whole dispatches in the traced slice over the
    rows those dispatches held."""
    hit = [_whole_tiles(src, p, height_of, rows_per_tile) for p in programs]
    return _ratio(1e3 * sum(s for s, _t in hit), sum(t for _s, t in hit))


@reader("trace_idle_share")
def trace_idle_share(src):
    w = src.trace.get("window_s")
    return None if not w else 100.0 * (1.0 - src.trace["busy_s"] / w)


def peak(device_kind: str, key: str) -> float:
    with open(os.path.join(HERE, "peaks.json")) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise KeyError(f"device {device_kind!r} is not in harness/peaks.json")
    return table[device_kind][key]


@reader("trace_roofline")
def trace_roofline(src, work, peak_key):
    """Share of the compute roofline: needed operations / peak / kernel
    time on the device. `work` maps a program of the traced slice to the
    ops file's key for one row of it and to the ledger entry that says how
    many rows a dispatch holds; needed = whole dispatches x rows x that
    count x limb operations per field multiplication."""
    with open(os.path.join(HERE, "pairing_ops.json")) as fh:
        ops = json.load(fh)
    needed = seconds = 0.0
    for program, w in work.items():
        s, rows = _whole_tiles(src, program, w["height_of"], 1)
        needed += rows * ops[w["per_row"]] * ops["limb_ops_per_fp_mul"]
        seconds += s
    if not seconds:
        return None
    return 100.0 * needed / peak(src.device_kind, peak_key) / seconds
