"""CI drift check: the metric taxonomy in docs/OBSERVABILITY.md cannot
rot. Every counter/gauge/histogram/span/flight-event name emitted by the
codebase must appear in the doc, and every metric-shaped name the doc
claims must still exist in the code — so removed metrics get pruned and
new metrics get documented in the same PR that touches them.
"""

import os
import re

REPO = os.path.join(os.path.dirname(__file__), "..")
DOC_PATH = os.path.join(REPO, "docs", "OBSERVABILITY.md")

# sources that emit metrics (tests excluded: scratch names are fine there)
SRC_DIRS = ("fabric_token_sdk_tpu", "cmd")
SRC_FILES = ("bench.py", "__graft_entry__.py")

# literal first-arg instrument/span/flight call sites; f-strings keep
# their "{placeholder}" tail, normalized to a prefix below
_PATTERNS = (
    ("counter", re.compile(r'\.counter\(\s*f?"([^"]+)"')),
    ("gauge", re.compile(r'\.gauge\(\s*f?"([^"]+)"')),
    ("histogram", re.compile(r'\.histogram\(\s*f?"([^"]+)"')),
    ("histogram", re.compile(r'\.timed\(\s*f?"([^"]+)"')),
    ("span", re.compile(r'\.span\(\s*f?"([^"]+)"')),
    ("span", re.compile(r'\.record_span\(\s*f?"([^"]+)"')),
    ("span", re.compile(r'_spanned\(\s*f?"([^"]+)"')),
    ("flight", re.compile(r'\.flight\(\s*f?"([^"]+)"')),
    ("flight", re.compile(r'FLIGHT\.record\(\s*f?"([^"]+)"')),
)

# doc tokens that look metric-shaped but are file/module references
_DOC_SKIP_SUFFIXES = (".py", ".pyc", ".c", ".cc", ".md", ".json", ".go")
_DOC_SKIP = {"jax.monitoring"}


def _source_files():
    for d in SRC_DIRS:
        for root, _dirs, files in os.walk(os.path.join(REPO, d)):
            for f in files:
                if f.endswith(".py"):
                    yield os.path.join(root, f)
    for f in SRC_FILES:
        yield os.path.join(REPO, f)


def _emitted():
    """{(kind, name)}: every literal metric/span/flight name in the code.
    f-string names are cut at the first '{' and marked as prefixes by
    their trailing '.'."""
    out = set()
    corpus = []
    for path in _source_files():
        with open(path) as fh:
            text = fh.read()
        corpus.append(text)
        for kind, pat in _PATTERNS:
            for name in pat.findall(text):
                out.add((kind, name.split("{")[0]))
    return out, "\n".join(corpus)


def _expand_doc_token(token):
    """Expand one backticked doc token into concrete names: `{a,b}`
    groups, trailing `x/y/z` and `x|y` alternations over the last dotted
    segment. Tokens containing `<placeholder>` become prefixes (cut at
    '<')."""
    m = re.search(r"\{([^}]*,[^}]*)\}", token)
    if m:
        out = []
        for alt in m.group(1).split(","):
            out.extend(_expand_doc_token(token[: m.start()] + alt + token[m.end():]))
        return out
    names = [token]
    for sep in ("/", "|"):
        new = []
        for t in names:
            if sep in t:
                parts = t.split(sep)
                head = parts[0]
                prefix = head.rsplit(".", 1)[0] + "." if "." in head else ""
                new.append(head)
                new.extend(prefix + p for p in parts[1:])
            else:
                new.append(t)
        names = new
    return names


_METRIC_SHAPE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+\.?$")


def _doc_names(doc_text):
    """(exact names, prefix names) the doc claims, from backticked
    metric-shaped tokens."""
    exact, prefixes = set(), set()
    for token in re.findall(r"`([^`\n]+)`", doc_text):
        if token.startswith("<"):
            continue
        if token.endswith(_DOC_SKIP_SUFFIXES) or token in _DOC_SKIP:
            continue
        for name in _expand_doc_token(token):
            cut = name.split("<")[0]
            is_prefix = cut != name or name.endswith(".")
            cut = cut.rstrip(".") + ("." if is_prefix else "")
            if not _METRIC_SHAPE.match(cut.rstrip(".") + (".x" if is_prefix else "")):
                if not (is_prefix and _METRIC_SHAPE.match(cut + "x")):
                    continue
            (prefixes if is_prefix else exact).add(cut)
    return exact, prefixes


def _doc_flight_kinds(doc_text):
    """Event kinds claimed by the flight-recorder taxonomy table (first
    column of each row, between the section heading and the next one)."""
    m = re.search(
        r"## Flight-recorder event taxonomy(.*?)\n## ", doc_text, re.S
    )
    assert m, "docs/OBSERVABILITY.md lost its flight-recorder taxonomy section"
    return set(
        re.findall(r"^\|\s*`([a-z][a-z0-9_.]*)`\s*\|", m.group(1), re.M)
    )


def test_every_emitted_metric_is_documented():
    emitted, _corpus = _emitted()
    with open(DOC_PATH) as fh:
        doc = fh.read()
    doc_flight = _doc_flight_kinds(doc)
    exact, prefixes = _doc_names(doc)

    def documented(name):
        if name.endswith("."):
            # emitted prefix (f-string name): any doc prefix or exact
            # name under it counts as documentation
            return any(
                d.startswith(name) or name.startswith(d) for d in prefixes
            ) or any(d.startswith(name) for d in exact)
        if name in exact:
            return True
        return any(name.startswith(p) for p in prefixes)

    missing = []
    for kind, name in sorted(emitted):
        base = name.rstrip(".") if not name.endswith(".") else name
        if kind == "flight":
            if base not in doc_flight:
                missing.append(f"flight event `{base}`")
            continue
        # spans are documented either by span name or their auto-fed
        # `<name>.seconds` histogram
        needles = [base]
        if kind == "span":
            needles.append(base + ".seconds")
        if not any(documented(n) for n in needles):
            missing.append(f"{kind} `{base}`")
    assert not missing, (
        "metric names emitted but absent from docs/OBSERVABILITY.md "
        "(document them in the taxonomy):\n  " + "\n  ".join(missing)
    )


def test_profiler_and_slo_names_pinned_both_ways():
    """The observability-PR names cannot drift in either direction: the
    host sub-leg histograms, the sampler counters, the SLO gauges and
    the `slo.breach` flight kind must be emitted by the code AND
    documented; the `FTS_PROF_*`/`FTS_SLO_*` env knobs referenced by the
    code must appear in the doc's switches table and vice versa."""
    from fabric_token_sdk_tpu.utils import profiler

    emitted, corpus = _emitted()
    emitted_names = {name for _kind, name in emitted}
    with open(DOC_PATH) as fh:
        doc = fh.read()
    exact, prefixes = _doc_names(doc)

    # sub-leg histograms: emitted as the f-string prefix `ledger.host.`,
    # documented as the five concrete `ledger.host.<leg>.seconds` names
    assert ("histogram", "ledger.host.") in emitted
    assert set(profiler.LEGS) == {
        "unmarshal", "fiat_shamir", "sig_verify", "conservation",
        "input_match",
    }
    for leg in profiler.LEGS:
        assert f"ledger.host.{leg}.seconds" in exact, leg

    # sampler + SLO instruments, both ways
    for name in ("prof.samples", "prof.dropped", "prof.errors",
                 "prof.stacks", "slo.breaches"):
        assert name in emitted_names, f"{name} no longer emitted"
        assert name in exact, f"{name} undocumented"
    for prefix in ("slo.burn.", "slo.budget."):
        assert prefix in emitted_names, f"{prefix}* no longer emitted"
        assert prefix in prefixes, f"{prefix}* undocumented"

    # the breach flight kind rides the taxonomy table
    assert ("flight", "slo.breach") in emitted
    assert "slo.breach" in _doc_flight_kinds(doc)

    # exemplar meta key: published by the engine, named in the doc
    assert '"slo.exemplars"' in corpus
    assert "`slo.exemplars`" in doc

    # env knobs both ways: every FTS_PROF_*/FTS_SLO_* the code reads is
    # in the switches table, and the table names no dead knobs
    code_knobs = set(re.findall(r'"(FTS_(?:PROF|SLO)_[A-Z0-9_]+)"', corpus))
    doc_knobs = set(re.findall(r"`(FTS_(?:PROF|SLO)_[A-Z0-9_]+)`", doc))
    assert code_knobs, "no FTS_PROF_*/FTS_SLO_* knobs found (parser drift?)"
    assert code_knobs - doc_knobs == set(), (
        f"env knobs missing from the doc: {sorted(code_knobs - doc_knobs)}"
    )
    assert doc_knobs - code_knobs == set(), (
        f"doc names knobs the code no longer reads: "
        f"{sorted(doc_knobs - code_knobs)}"
    )


def test_device_ledger_names_pinned_both_ways():
    """The dispatch-ledger PR's names cannot drift in either direction:
    the aggregate + per-program dispatch histograms, the per-plane
    occupancy histogram, the padding-waste counter, the clamp-site
    counters and the degrade flight kinds must be emitted by the code
    AND documented; the `FTS_DEVOBS` switch the code reads must appear
    in the doc's switches table."""
    emitted, corpus = _emitted()
    emitted_names = {name for _kind, name in emitted}
    with open(DOC_PATH) as fh:
        doc = fh.read()
    exact, prefixes = _doc_names(doc)

    # aggregate dispatch histogram: exact name, both ways
    assert ("histogram", "device.dispatch.seconds") in emitted
    assert "device.dispatch.seconds" in exact

    # f-string families: emitted as prefixes, documented as
    # `<placeholder>`-style prefixes
    for prefix in ("device.dispatch.", "device."):
        assert prefix in emitted_names, f"{prefix}* no longer emitted"
        assert prefix in prefixes, f"{prefix}* undocumented"
    for token in ("device.dispatch.<program>.seconds",
                  "device.<plane>.occupancy",
                  "device.<program>.padded_rows"):
        assert f"`{token}`" in doc, f"{token} undocumented"

    # the ledger switch, both ways
    assert '"FTS_DEVOBS"' in corpus, "code no longer reads FTS_DEVOBS"
    assert "`FTS_DEVOBS`" in doc, "FTS_DEVOBS missing from switches table"


def test_host_batch_names_pinned_both_ways():
    """The batch-first host-validation PR's names cannot drift in
    either direction: the proved-row counters, the request/parse cache
    counters, the per-pass block histograms, the multiexp path
    counters, the host-batch flight kinds, and the four switches the
    code reads must be emitted by the code AND documented."""
    emitted, corpus = _emitted()
    with open(DOC_PATH) as fh:
        doc = fh.read()
    exact, _prefixes = _doc_names(doc)

    counters = (
        "hostbatch.sign.rows",
        "hostbatch.proof.rows",
        "hostbatch.conservation.rows",
        "request.cache.hits",
        "request.cache.misses",
        "request.cache.evictions",
        "parse.cache.hits",
        "parse.cache.misses",
        "hostmath.g1_multiexp_rows.native",
        "hostmath.g1_multiexp_rows.python",
    )
    for name in counters:
        assert ("counter", name) in emitted, f"{name} no longer emitted"
        assert name in exact, f"{name} undocumented"

    for name in (
        "ledger.block.host_sign_batch.seconds",
        "ledger.block.host_proof_batch.seconds",
        "ledger.block.host_conservation.seconds",
    ):
        assert ("histogram", name) in emitted, f"{name} no longer emitted"
        assert name in exact, f"{name} undocumented"

    doc_flight = _doc_flight_kinds(doc)
    for kind in ("sign.host_batch", "verify.host_batch",
                 "request.cache.evict"):
        assert ("flight", kind) in emitted, f"{kind} no longer emitted"
        assert kind in doc_flight, f"{kind} missing from flight taxonomy"

    for knob in ("FTS_HOST_BATCH", "FTS_COMMIT_WORKERS",
                 "FTS_REQUEST_CACHE", "FTS_PARSE_CACHE"):
        assert f'"{knob}"' in corpus, f"code no longer reads {knob}"
        assert f"`{knob}`" in doc, f"{knob} missing from switches table"


def test_replication_names_pinned_both_ways():
    """The replicated-ledger-plane PR's names cannot drift in either
    direction: the shipping/apply/bootstrap counters, the fencing and
    role-change counters, the client-failover counters, the ship-wait
    histogram, the replication flight kinds, and the switches the code
    reads must be emitted by the code AND documented."""
    emitted, corpus = _emitted()
    with open(DOC_PATH) as fh:
        doc = fh.read()
    exact, _prefixes = _doc_names(doc)

    counters = (
        "repl.shipped.records",
        "repl.ship.dropped",
        "repl.ship.ack_timeouts",
        "repl.ship.unsynced",
        "repl.applied.records",
        "repl.apply.skipped",
        "repl.bootstraps",
        "repl.bootstraps.sent",
        "repl.heartbeats",
        "repl.promotions",
        "repl.demotions",
        "repl.stale_rejected",
        "repl.link.errors",
        "repl.link.node_stopped",
        "remote.dispatch.not_leader",
        "remote.failover.switches",
    )
    for name in counters:
        assert ("counter", name) in emitted, f"{name} no longer emitted"
        assert name in exact, f"{name} undocumented"

    name = "repl.ship.wait.seconds"
    assert ("histogram", name) in emitted, f"{name} no longer emitted"
    assert name in exact, f"{name} undocumented"

    doc_flight = _doc_flight_kinds(doc)
    for kind in ("repl.bootstrap", "repl.promote", "repl.demoted",
                 "repl.fenced", "repl.link.stopped", "repl.ship.drop",
                 "failover"):
        assert ("flight", kind) in emitted, f"{kind} no longer emitted"
        assert kind in doc_flight, f"{kind} missing from flight taxonomy"

    for knob in ("FTS_REPL", "FTS_REPL_SHIP_TIMEOUT_S",
                 "FTS_REPL_QUEUE_MAX", "FTS_REPL_HEARTBEAT_S",
                 "FTS_REPL_LEASE_S", "FTS_REPL_AUTO_PROMOTE",
                 "FTS_REMOTE_ENDPOINTS", "FTS_BENCH_SOAK_FAILOVER"):
        assert f'"{knob}"' in corpus, f"code no longer reads {knob}"
        assert f"`{knob}`" in doc, f"{knob} missing from switches table"


def _wire_ops():
    """Every RPC op name `LedgerServer._dispatch_op` handles (the live
    wire protocol, ops plane included)."""
    path = os.path.join(
        REPO, "fabric_token_sdk_tpu", "services", "network", "remote.py"
    )
    with open(path) as fh:
        text = fh.read()
    ops = set(re.findall(r'op == "([a-z_.]+)"', text))
    assert ops, "no dispatch ops found in remote.py (parser drift?)"
    return ops


def _doc_rpc_ops(doc_text):
    """Op names claimed by the RPC catalog table in the Live ops plane
    section (first column of each row)."""
    m = re.search(r"### RPC catalog(.*?)\n###? ", doc_text, re.S)
    assert m, "docs/OBSERVABILITY.md lost its RPC catalog section"
    return set(re.findall(r"^\|\s*`([a-z_.]+)`\s*\|", m.group(1), re.M))


def test_rpc_catalog_matches_dispatch():
    """The Live ops plane RPC catalog cannot rot: every wire op the
    server dispatches is documented, and every documented op is still
    dispatched."""
    with open(DOC_PATH) as fh:
        doc = fh.read()
    code_ops, doc_ops = _wire_ops(), _doc_rpc_ops(doc)
    assert code_ops - doc_ops == set(), (
        f"wire ops missing from the RPC catalog: {sorted(code_ops - doc_ops)}"
    )
    assert doc_ops - code_ops == set(), (
        f"RPC catalog documents ops no longer dispatched: "
        f"{sorted(doc_ops - code_ops)}"
    )


def test_quantile_suffixes_and_memory_gauges_documented():
    """The quantile export (histogram `p50`/`p95`/`p99` keys and the
    Prometheus companion series) and the memory-telemetry gauge families
    (`stages.mem.*`, `proc.rss.*`) must be documented."""
    from fabric_token_sdk_tpu.utils import metrics

    with open(DOC_PATH) as fh:
        doc = fh.read()
    labels = [label for label, _q in metrics.QUANTILES]
    assert labels == ["p50", "p95", "p99"]
    for label in labels:
        assert f"`{label}`" in doc, f"quantile suffix {label} undocumented"
    # the quantile keys must actually exist in a snapshot
    h = metrics.Histogram("doccheck", buckets=(1.0,))
    h.observe(0.5)
    snap = h.snapshot()
    for label in labels:
        assert label in snap
    for needle in ("stages.mem.high_water.bytes", "stages.mem.device.bytes",
                   "proc.rss.bytes", "proc.rss.peak.bytes",
                   "device.mem.bytes", "orderer.queue.depth",
                   "ledger.inflight"):
        assert f"`{needle}`" in doc, f"ops-plane gauge {needle} undocumented"


def test_every_documented_metric_still_exists():
    emitted, corpus = _emitted()
    emitted_names = {name for _kind, name in emitted}
    emitted_exact = {n for n in emitted_names if not n.endswith(".")}
    emitted_prefixes = {n for n in emitted_names if n.endswith(".")}
    # span names also exist as `<name>.seconds` histograms
    for kind, name in list(emitted):
        if kind == "span" and not name.endswith("."):
            emitted_exact.add(name + ".seconds")
    with open(DOC_PATH) as fh:
        doc = fh.read()
    exact, prefixes = _doc_names(doc)
    exact |= _doc_flight_kinds(doc)

    def exists(name):
        base = name.rstrip(".")
        if base in emitted_exact or name in emitted_prefixes:
            return True
        if any(base.startswith(p) for p in emitted_prefixes):
            return True
        if name.endswith(".") and any(
            e.startswith(name) for e in emitted_exact
        ):
            return True
        # dynamically-built names (the jax.* monitoring plane) must at
        # least appear verbatim somewhere in the source tree
        return base in corpus

    stale = sorted(n for n in exact | prefixes if not exists(n))
    assert not stale, (
        "docs/OBSERVABILITY.md documents metrics no longer emitted "
        "anywhere (prune or fix them):\n  " + "\n  ".join(stale)
    )
