"""`BENCHMARK.json` and the data files it names: loading and validation.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name in the manifest:

    benchmark/configs/<config>.json
    benchmark/traffic/<traffic>.json
    benchmark/layer_metrics/<metric>.json

so a later PR adds files and manifest entries and edits nothing.
"""

from __future__ import annotations

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def _load(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def data_file(kind: str, name: str, bench_dir: str = BENCH_DIR) -> str:
    return os.path.join(bench_dir, kind, f"{name}.json")


def cell(manifest: dict, workload: str, bench_dir: str = BENCH_DIR) -> dict:
    """-> {"name", "chips", "config": {...}, "mix": {...}, "end_to_end":
    [metric entries of this cell], "per_layer": [entry + its data file]}."""
    rows = [w for w in manifest["workloads"] if w["name"] == workload]
    if not rows:
        raise ManifestError(
            f"no workload {workload!r}; the manifest has "
            f"{[w['name'] for w in manifest['workloads']]}")
    w = rows[0]
    cfg = [c for c in manifest["configs"] if c["name"] == w["config"]]
    if not cfg:
        raise ManifestError(f"workload {workload!r} names no known config")
    config = _load(os.path.join(os.path.dirname(bench_dir), cfg[0]["file"]))
    mix = _load(data_file("traffic", w["traffic"], bench_dir))

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "name": workload, "chips": w["chips"], "config_name": w["config"],
        "traffic_name": w["traffic"], "config": config, "mix": mix,
        "end_to_end": [m for m in manifest["end_to_end"] if mine(m)],
        "per_layer": [
            dict(m, reader=_load(data_file("layer_metrics", m["name"], bench_dir)))
            for m in manifest["per_layer"] if mine(m)],
    }


def validate(manifest: dict, bench_dir: str = BENCH_DIR) -> list:
    """The rules a manifest has to keep; -> list of faults (empty: sound)."""
    faults = []

    def check(ok, msg):
        if not ok:
            faults.append(msg)

    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    check(set(manifest) == keys, f"top-level keys {sorted(manifest)}")
    cells = [w["name"] for w in manifest.get("workloads", [])]
    configs = [c["name"] for c in manifest.get("configs", [])]
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in manifest.get(group, [])]
        check(len(names) == len(set(names)), f"duplicate name in {group}")
        for n in names:
            check(NAME.match(n), f"{group}: bad name {n!r}")
    e2e = {m["name"]: m for m in manifest.get("end_to_end", [])}
    check("setup_s" in e2e, "no setup_s among the end-to-end metrics")
    for w in manifest.get("workloads", []):
        check(w["config"] in configs, f"{w['name']}: unknown config")
        check(w["chips"] in (1, 4), f"{w['name']}: chips {w['chips']}")
        check(NAME.match(w["traffic"]), f"{w['name']}: bad traffic name")
        check(os.path.exists(data_file("traffic", w["traffic"], bench_dir)),
              f"{w['name']}: no traffic file {w['traffic']}.json")
        check(len(w["why"]) <= 200 and "\n" not in w["why"],
              f"{w['name']}: why over 200 characters")
    pairs = [(w["config"], w["traffic"]) for w in manifest.get("workloads", [])]
    check(len(pairs) == len(set(pairs)), "a (config, traffic) pair twice")
    for c in manifest.get("configs", []):
        check(any(w["config"] == c["name"] for w in manifest["workloads"]),
              f"config {c['name']} is used by no cell")
        check(os.path.exists(os.path.join(os.path.dirname(bench_dir), c["file"])),
              f"config {c['name']}: no file {c['file']}")
    for m in list(e2e.values()) + manifest.get("per_layer", []):
        check(UNIT.match(m["unit"]), f"{m['name']}: bad unit {m['unit']!r}")
        check(m["better"] in ("lower", "higher"), f"{m['name']}: better")
        check(m["source"] in SOURCES, f"{m['name']}: source {m['source']!r}")
        for wl in m.get("workloads", []):
            check(wl in cells, f"{m['name']}: unknown workload {wl!r}")
    for m in e2e.values():
        check(m["source"] in ("host_clock", "device_trace"),
              f"{m['name']}: an end-to-end metric is taken by the benchmark")
        check(0 < m["bound"] <= 0.25, f"{m['name']}: bound {m['bound']}")

    def reports(metric, wl):
        return "workloads" not in metric or wl in metric["workloads"]

    for m in manifest.get("per_layer", []):
        check(m.get("moves") in e2e, f"{m['name']}: moves {m.get('moves')!r}")
        check(os.path.exists(data_file("layer_metrics", m["name"], bench_dir)),
              f"{m['name']}: no reader file")
        if m.get("moves") in e2e:
            for wl in m.get("workloads", cells):
                check(reports(e2e[m["moves"]], wl),
                      f"{m['name']}: cell {wl} does not report {m['moves']}")
    for wl in cells:
        check(sum(1 for m in e2e.values() if reports(m, wl)) >= 2,
              f"{wl}: needs setup_s and one more end-to-end metric")
        check(any(reports(m, wl) for m in manifest.get("per_layer", [])),
              f"{wl}: no per-layer metric")
    return faults
