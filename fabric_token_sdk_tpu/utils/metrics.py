"""Metrics core: counters, gauges, histograms, span trees, trace
contexts, a crash flight recorder, and the export plane.

Reference parity: fabric-smart-client threads a metrics provider
(`platform/view/services/metrics`) and `flogging` through every token
service; this module is our equivalent, grown out of the original
70-line `utils/tracing.py` span tracer.

Design:

* One process-wide thread-safe ``Registry`` (``REGISTRY``) holding named
  counters / gauges / histograms, completed span trees, and phase
  timelines. Instruments are get-or-create by name, so call sites never
  coordinate.
* **Counters are always live** — an increment is one lock + int add,
  unmeasurable next to any group operation — while **spans and
  heartbeats are env-gated** (``FTS_METRICS=1``, or ``enable()``):
  the disabled ``span()`` fast path is a single global check.
* **Trace contexts** (Dapper/OpenTelemetry style): ``new_trace()`` mints
  a ``trace_id``; ``use_trace(ctx)`` activates it for the thread; spans
  opened under it carry ``trace_id``/``span_id``/``parent_span_id`` and
  a wall-clock ``start_unix``, so per-transaction causal timelines can
  be stitched across threads AND processes (``TraceContext.to_wire`` /
  ``from_wire`` is the propagation format `remote.py` injects into
  request frames). ``cmd/ftstrace.py`` assembles the timelines.
* **Flight recorder** (``FLIGHT`` / ``flight(kind, ...)``): an always-on
  bounded ring of structured lifecycle events (submits, block cuts,
  verify decisions, WAL appends, faults, retries, compile/cache events),
  each tagged with the active trace id. Dumped to a ``*.flight.json``
  sidecar alongside every metrics sidecar flush — an rc=124 death
  leaves *what was happening*, not just final counter values.
* Export: ``to_json()`` (the ``*.metrics.json`` sidecar format read by
  ``cmd/ftsmetrics.py``) and ``to_prometheus()`` (text exposition
  format, counters/gauges/histograms only).
* Crash-proofing: ``install_sidecar(path)`` registers an ``atexit``
  hook plus SIGTERM/SIGINT handlers that flush the registry to a JSON
  sidecar, so a killed benchmark (rc=124) still leaves a full
  accounting. ``flush_sidecar()`` can also be called explicitly (e.g.
  from a watchdog thread about to ``os._exit``).
* ``Heartbeat`` emits phase-stamped progress lines to stderr from a
  daemon thread (``[fts] phase=compile elapsed=134s``) and records the
  phase timeline in the registry (and the flight recorder).
"""

from __future__ import annotations

import atexit
import collections
import contextlib
import json
import os
import signal
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

_TRUTHY = ("1", "true", "yes", "on")

_enabled = os.environ.get("FTS_METRICS", "0").strip().lower() in _TRUTHY


def enabled() -> bool:
    return _enabled


def enable(flag: bool = True) -> None:
    """Turn span/heartbeat recording on (bench does this unconditionally)."""
    global _enabled
    _enabled = flag


# ------------------------------------------------------------ instruments


class Counter:
    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._value = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        return self._value


class Gauge:
    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self._value = v

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._value += n

    def dec(self, n: float = 1.0) -> None:
        self.inc(-n)

    @property
    def value(self) -> float:
        return self._value


# Latency buckets sized for this codebase: sub-ms host ops up through
# multi-minute XLA pairing compiles.
DEFAULT_BUCKETS = (
    0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
    10.0, 30.0, 60.0, 120.0, 300.0, 600.0,
)

# Quantile labels every histogram exports (JSON snapshot keys and
# Prometheus `<name>_<label>` series) — the latency numbers the live ops
# plane (`ops.metrics` RPC, `cmd/ftstop.py top`) reads.
QUANTILES = (("p50", 0.50), ("p95", 0.95), ("p99", 0.99))


class Histogram:
    __slots__ = ("name", "buckets", "_counts", "_count", "_sum", "_min",
                 "_max", "_lock")

    def __init__(self, name: str, buckets: Sequence[float] = DEFAULT_BUCKETS):
        self.name = name
        self.buckets = tuple(sorted(buckets))
        self._counts = [0] * (len(self.buckets) + 1)  # +inf bucket
        self._count = 0
        self._sum = 0.0
        self._min = float("inf")
        self._max = float("-inf")
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        with self._lock:
            self._count += 1
            self._sum += v
            if v < self._min:
                self._min = v
            if v > self._max:
                self._max = v
            for i, b in enumerate(self.buckets):
                if v <= b:
                    self._counts[i] += 1
                    return
            self._counts[-1] += 1

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    @staticmethod
    def _interp(q: float, buckets, counts, total: int,
                lo: float, hi: float) -> float:
        """Bucket-interpolated quantile estimate (Prometheus
        `histogram_quantile` style): find the bucket where the cumulative
        count crosses rank ``q*total`` and interpolate linearly between
        its bounds. The result is clamped to the OBSERVED ``[min, max]``
        — a single observation reports itself exactly, and the first
        bucket can never report below the true minimum. A rank landing
        in the +Inf bucket reports the observed max (the best bounded
        estimate an unbounded bucket allows)."""
        rank = q * total
        cum, prev = 0, 0.0
        for b, c in zip(buckets, counts):
            if c and cum + c >= rank:
                v = prev + (b - prev) * (rank - cum) / c
                return min(max(v, lo), hi)
            cum += c
            prev = b
        return hi

    def quantile(self, q: float) -> Optional[float]:
        """Estimate the q-quantile (0 < q < 1); None when empty."""
        with self._lock:
            if not self._count:
                return None
            counts = list(self._counts)
            total, lo, hi = self._count, self._min, self._max
        return self._interp(q, self.buckets, counts, total, lo, hi)

    def state(self) -> tuple:
        """`(counts, count, sum)` — a consistent copy of the cumulative
        internal state, the primitive sliding-window consumers (the SLO
        engine) DIFF between two instants. Read-only: windowing lives
        entirely in the consumer's ring of these copies, so the
        cumulative `snapshot()`/`to_prometheus()` semantics are
        untouched by construction."""
        with self._lock:
            return list(self._counts), self._count, self._sum

    @staticmethod
    def fraction_le(buckets, counts, threshold: float) -> Optional[float]:
        """Fraction of observations <= `threshold` given per-bucket
        counts (typically a window DELTA of two `state()` copies),
        interpolating linearly inside the bucket the threshold falls in
        (Prometheus `histogram_quantile` style, inverted). None when the
        counts are empty — no data is not the same as all-good."""
        total = sum(counts)
        if total <= 0:
            return None
        good = 0.0
        prev = 0.0
        for b, c in zip(buckets, counts):
            if threshold >= b:
                good += c
                prev = b
                continue
            if threshold > prev and c:
                good += c * (threshold - prev) / (b - prev)
            break
        return min(1.0, good / total)

    def snapshot(self) -> dict:
        # timed acquire: may run under a signal handler (see Registry)
        acquired = self._lock.acquire(timeout=1.0)
        try:
            d = {
                "count": self._count,
                "sum": round(self._sum, 6),
                "buckets": {
                    ("%g" % b): c
                    for b, c in zip(self.buckets, self._counts)
                    if c
                },
            }
            if self._counts[-1]:
                d["buckets"]["+Inf"] = self._counts[-1]
            if self._count:
                d["min"] = round(self._min, 6)
                d["max"] = round(self._max, 6)
                d["mean"] = round(self._sum / self._count, 6)
                counts = list(self._counts)
                for label, q in QUANTILES:
                    d[label] = round(
                        self._interp(
                            q, self.buckets, counts, self._count,
                            self._min, self._max,
                        ),
                        6,
                    )
            return d
        finally:
            if acquired:
                self._lock.release()


# ------------------------------------------------------------ span trees


@dataclass
class Span:
    name: str
    start: float  # monotonic
    end: Optional[float] = None
    attrs: dict = field(default_factory=dict)
    children: List["Span"] = field(default_factory=list)
    # trace plane: wall-clock anchor + ids for cross-process stitching
    start_unix: float = 0.0
    trace_id: str = ""
    span_id: str = ""
    parent_span_id: str = ""

    @property
    def duration(self) -> float:
        return (self.end if self.end is not None else time.monotonic()) - self.start

    def to_dict(self) -> dict:
        d = {"name": self.name, "duration_s": round(self.duration, 6)}
        if self.start_unix:
            d["start_unix"] = round(self.start_unix, 6)
        if self.trace_id:
            d["trace_id"] = self.trace_id
        if self.span_id:
            d["span_id"] = self.span_id
        if self.parent_span_id:
            d["parent_span_id"] = self.parent_span_id
        if self.attrs:
            d["attrs"] = {k: _jsonable(v) for k, v in self.attrs.items()}
        if self.children:
            d["children"] = [c.to_dict() for c in self.children]
        return d


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    return str(v)


_tls = threading.local()


# ------------------------------------------------------------ trace context


def _new_id(nbytes: int = 8) -> str:
    return os.urandom(nbytes).hex()


@dataclass
class TraceContext:
    """Propagatable trace identity (Dapper / OpenTelemetry trace-context
    style): ``trace_id`` names one end-to-end transaction; ``span_id``
    is the id new child spans adopt as their parent. ``to_wire()`` /
    ``from_wire()`` is the cross-process format `remote.py` carries in
    request frames."""

    trace_id: str
    span_id: str = ""

    def to_wire(self) -> list:
        return [self.trace_id, self.span_id]

    @classmethod
    def from_wire(cls, wire) -> Optional["TraceContext"]:
        if not wire:
            return None
        try:
            return cls(str(wire[0]), str(wire[1]) if len(wire) > 1 else "")
        except (TypeError, KeyError, IndexError):
            return None


def new_trace() -> TraceContext:
    """Mint a fresh trace context. Always available — trace ids tag
    flight-recorder events even when span recording is disabled."""
    REGISTRY.counter("trace.traces").inc()
    return TraceContext(_new_id(8), _new_id(4))


def current_trace() -> Optional[TraceContext]:
    """The thread's active trace context: derived from the innermost
    open span when it belongs to the `use_trace`-activated trace (so new
    children nest correctly), else the activation itself — an explicit
    `use_trace` of a DIFFERENT trace overrides enclosing spans. That
    override is what lets a group-commit thread attribute per-tx work to
    each submitting tx's trace while its own spans stay open."""
    ctx = getattr(_tls, "trace", None)
    stack = getattr(_tls, "stack", None)
    if stack:
        s = stack[-1]
        if s.trace_id and (ctx is None or ctx.trace_id == s.trace_id):
            return TraceContext(s.trace_id, s.span_id)
    return ctx


@contextlib.contextmanager
def use_trace(ctx: Optional[TraceContext]):
    """Activate `ctx` for this thread (None = no-op): spans opened and
    flight events recorded inside join the trace."""
    if ctx is None:
        yield None
        return
    prev = getattr(_tls, "trace", None)
    _tls.trace = ctx
    try:
        yield ctx
    finally:
        _tls.trace = prev


@contextlib.contextmanager
def span(name: str, **attrs):
    """Timed span; nests into the per-thread open span, inherits the
    active trace context, auto-observes its duration into histogram
    ``<name>.seconds``. No-op (yields None) when metrics are disabled."""
    if not _enabled:
        yield None
        return
    s = Span(name, time.monotonic(), attrs=attrs)
    s.start_unix = time.time()
    s.span_id = _new_id(4)
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    parent = stack[-1] if stack else None
    # trace linkage: inherit from the parent span when it belongs to the
    # same trace as the active `use_trace` context (or no context is
    # active); an explicitly activated DIFFERENT trace wins — the
    # group-commit thread validates other submitters' txs under their
    # traces while its own (traceless or other-trace) spans stay open
    ctx = getattr(_tls, "trace", None)
    if parent is not None and parent.trace_id and (
        ctx is None or ctx.trace_id == parent.trace_id
    ):
        s.trace_id = parent.trace_id
        s.parent_span_id = parent.span_id
    elif ctx is not None:
        s.trace_id = ctx.trace_id
        s.parent_span_id = ctx.span_id
    if s.trace_id:
        REGISTRY.counter("trace.spans").inc()
    stack.append(s)
    try:
        yield s
    finally:
        s.end = time.monotonic()
        stack.pop()
        if parent is not None:
            parent.children.append(s)
        else:
            REGISTRY.record_span_root(s)
        REGISTRY.histogram(name + ".seconds").observe(s.duration)


def record_timed_span(name: str, start: float, end: float,
                      **attrs) -> Optional[Span]:
    """Record a span that its owner already timed (monotonic `start` /
    `end`, on this thread) under the thread's open span, in its trace —
    for a timer that must stay the only one at its boundary: the device
    dispatch frame (`utils/devobs.py`) records itself so. Feeds no
    `<name>.seconds` histogram (the owner keeps its own). Gated like
    `span`."""
    if not _enabled:
        return None
    s = Span(name, start, end=end, attrs=attrs)
    s.start_unix = time.time() - (time.monotonic() - start)
    s.span_id = _new_id(4)
    stack = getattr(_tls, "stack", None)
    if stack:
        parent = stack[-1]
        if parent.trace_id:
            s.trace_id = parent.trace_id
            s.parent_span_id = parent.span_id
            REGISTRY.counter("trace.spans").inc()
        parent.children.append(s)
    else:
        REGISTRY.record_span_root(s)
    return s


def record_span(name: str, start_unix: float, end_unix: float,
                trace: Optional[TraceContext] = None, **attrs) -> Optional[Span]:
    """Record an already-timed root span (for work measured across
    threads — e.g. a submission's queue wait stamped at block cut, or
    the per-tx client leg of a batched wire call). Gated like `span`."""
    if not _enabled:
        return None
    s = Span(name, 0.0, end=max(0.0, end_unix - start_unix), attrs=attrs)
    s.start_unix = start_unix
    s.span_id = _new_id(4)
    if trace is not None:
        s.trace_id = trace.trace_id
        s.parent_span_id = trace.span_id
        REGISTRY.counter("trace.spans").inc()
    REGISTRY.record_span_root(s)
    REGISTRY.histogram(name + ".seconds").observe(s.duration)
    return s


# ------------------------------------------------------------ registry


class Registry:
    """Thread-safe named-instrument store + export plane."""

    MAX_SPAN_ROOTS = 2000

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, Histogram] = {}
        self._span_roots: List[Span] = []
        self._phases: List[dict] = []
        self._meta: Dict[str, object] = {}

    # -- get-or-create -------------------------------------------------

    def counter(self, name: str) -> Counter:
        c = self._counters.get(name)
        if c is None:
            with self._lock:
                c = self._counters.setdefault(name, Counter(name))
        return c

    def gauge(self, name: str) -> Gauge:
        g = self._gauges.get(name)
        if g is None:
            with self._lock:
                g = self._gauges.setdefault(name, Gauge(name))
        return g

    def histogram(self, name: str, buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
        """Get-or-create by name; `buckets` applies only on FIRST creation
        — a later caller passing different buckets gets the existing
        instrument unchanged."""
        h = self._histograms.get(name)
        if h is None:
            with self._lock:
                h = self._histograms.setdefault(name, Histogram(name, buckets))
        return h

    # -- spans / phases / meta ----------------------------------------

    def record_span_root(self, s: Span) -> None:
        with self._lock:
            self._span_roots.append(s)
            if len(self._span_roots) > self.MAX_SPAN_ROOTS:
                del self._span_roots[: self.MAX_SPAN_ROOTS // 2]

    MAX_PHASES = 500

    def record_phase(self, name: str, start: float, end: Optional[float],
                     **attrs) -> None:
        row = {"name": name, "start_unix": round(start, 3)}
        if end is not None:
            row["elapsed_s"] = round(end - start, 3)
        if attrs:
            row["attrs"] = {k: _jsonable(v) for k, v in attrs.items()}
        with self._lock:
            self._phases.append(row)
            if len(self._phases) > self.MAX_PHASES:
                del self._phases[: self.MAX_PHASES // 2]

    def set_meta(self, key: str, value) -> None:
        # timed acquire: called from the SIGTERM handler, which may have
        # interrupted the very thread holding this non-reentrant lock
        acquired = self._lock.acquire(timeout=1.0)
        try:
            self._meta[key] = _jsonable(value)
        finally:
            if acquired:
                self._lock.release()

    # -- export --------------------------------------------------------

    def span_summary(self) -> Dict[str, dict]:
        """Aggregate completed span trees by name (depth-first)."""
        agg: Dict[str, dict] = {}

        def walk(s: Span):
            a = agg.setdefault(s.name, {"count": 0, "total_s": 0.0})
            a["count"] += 1
            a["total_s"] += s.duration
            for c in s.children:
                walk(c)

        acquired = self._lock.acquire(timeout=1.0)
        try:
            roots = list(self._span_roots)
        finally:
            if acquired:
                self._lock.release()
        for s in roots:
            walk(s)
        for a in agg.values():
            a["total_s"] = round(a["total_s"], 6)
        return agg

    def snapshot(self) -> dict:
        # timed acquire: flush_sidecar() runs from signal handlers, which
        # can interrupt a thread that already holds this (non-reentrant)
        # lock — fall back to a best-effort unlocked read over deadlock
        acquired = self._lock.acquire(timeout=1.0)
        try:
            counters = {n: c.value for n, c in sorted(self._counters.items())}
            gauges = {n: g.value for n, g in sorted(self._gauges.items())}
            hists = {n: h for n, h in sorted(self._histograms.items())}
            phases = list(self._phases)
            meta = dict(self._meta)
            roots = list(self._span_roots)
        finally:
            if acquired:
                self._lock.release()
        return {
            "meta": meta,
            "pid": os.getpid(),
            "flushed_unix": round(time.time(), 3),
            "phases": phases,
            "counters": counters,
            "gauges": gauges,
            "histograms": {n: h.snapshot() for n, h in hists.items()},
            "span_summary": self.span_summary(),
            "spans": [s.to_dict() for s in roots[-200:]],
        }

    def to_json(self, indent: Optional[int] = 2) -> str:
        return json.dumps(self.snapshot(), indent=indent, sort_keys=False)

    def to_prometheus(self) -> str:
        """Text exposition format. Metric names sanitized to [a-z0-9_]."""
        lines: List[str] = []
        with self._lock:
            counters = sorted(self._counters.items())
            gauges = sorted(self._gauges.items())
            hists = sorted(self._histograms.items())
        for name, c in counters:
            m = _prom_name(name)
            lines.append(f"# TYPE {m} counter")
            lines.append(f"{m} {c.value}")
        for name, g in gauges:
            m = _prom_name(name)
            lines.append(f"# TYPE {m} gauge")
            lines.append(f"{m} {_prom_num(g.value)}")
        for name, h in hists:
            m = _prom_name(name)
            lines.append(f"# TYPE {m} histogram")
            cum = 0
            with h._lock:
                counts = list(h._counts)
                total, s = h._count, h._sum
                lo, hi = h._min, h._max
            for b, n in zip(h.buckets, counts):
                cum += n
                lines.append(f'{m}_bucket{{le="{_prom_num(b)}"}} {cum}')
            cum += counts[-1]
            lines.append(f'{m}_bucket{{le="+Inf"}} {cum}')
            lines.append(f"{m}_sum {_prom_num(s)}")
            lines.append(f"{m}_count {total}")
            if total:
                # bucket-interpolated quantiles as companion gauges (the
                # buckets above allow server-side histogram_quantile too)
                for label, q in QUANTILES:
                    v = Histogram._interp(q, h.buckets, counts, total, lo, hi)
                    lines.append(f"{m}_{label} {_prom_num(round(v, 9))}")
        return "\n".join(lines) + "\n"

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._histograms.clear()
            self._span_roots.clear()
            self._phases.clear()
            self._meta.clear()


def _prom_name(name: str) -> str:
    out = "".join(c if c.isalnum() else "_" for c in name.lower())
    if out and out[0].isdigit():
        out = "_" + out
    return "fts_" + out


def _prom_num(v: float) -> str:
    return ("%d" % v) if float(v).is_integer() else repr(float(v))


REGISTRY = Registry()


# convenience module-level aliases used throughout the runtime
def counter(name: str) -> Counter:
    return REGISTRY.counter(name)


def gauge(name: str) -> Gauge:
    return REGISTRY.gauge(name)


def histogram(name: str, buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
    return REGISTRY.histogram(name, buckets)


@contextlib.contextmanager
def timed(hist_name: str):
    """Observe the block's wall time into a histogram (gated like span)."""
    if not _enabled:
        yield
        return
    t0 = time.monotonic()
    try:
        yield
    finally:
        REGISTRY.histogram(hist_name).observe(time.monotonic() - t0)


# ------------------------------------------------------------ heartbeat


class Heartbeat:
    """Phase-stamped progress lines on stderr from a daemon thread.

    ``[fts] phase=compile program=miller_tile elapsed=134s total=250s``

    Phases (and their wall times) are also recorded in the registry so a
    sidecar flushed at death reports exactly where the time went.
    """

    def __init__(self, tag: str = "fts", interval_s: Optional[float] = None,
                 stream=None):
        self.tag = tag
        self.interval_s = (
            float(os.environ.get("FTS_HEARTBEAT_SECS", "15"))
            if interval_s is None
            else interval_s
        )
        self.stream = stream if stream is not None else sys.stderr
        self._t0 = time.time()
        self._phase = "init"
        self._phase_start = self._t0
        self._attrs: dict = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def set_phase(self, name: str, **attrs) -> None:
        now = time.time()
        with self._lock:
            prev, prev_start, prev_attrs = self._phase, self._phase_start, self._attrs
            self._phase, self._phase_start, self._attrs = name, now, attrs
        # lifecycle events are always flight-recorded (the ring is how a
        # killed run answers "which phase was live, after what history")
        FLIGHT.record("phase", phase=name, **attrs)
        if _enabled:  # phases are gated like spans/heartbeat lines
            # per-phase memory telemetry: stamp the COMPLETING phase with
            # the process/device footprint it ended at (sysmon never
            # triggers jax backend init — safe before the platform probe)
            done_attrs = dict(prev_attrs)
            try:
                from . import sysmon

                mem = sysmon.sample()
                done_attrs.setdefault("rss_mb", round(mem["rss_bytes"] / 1e6, 1))
                if mem.get("device_bytes") is not None:
                    done_attrs.setdefault(
                        "dev_mem_mb", round(mem["device_bytes"] / 1e6, 1)
                    )
            except Exception:
                pass  # telemetry must never break a phase change
            REGISTRY.record_phase(prev, prev_start, now, **done_attrs)
            REGISTRY.gauge("progress.phase_start_unix").set(now)
            REGISTRY.set_meta("progress.phase", name)
        self.emit()

    def emit(self) -> None:
        if not _enabled:
            return  # heartbeats are env-gated like spans (FTS_METRICS=1)
        with self._lock:
            phase, phase_start, attrs = self._phase, self._phase_start, self._attrs
        now = time.time()
        extra = "".join(f" {k}={_jsonable(v)}" for k, v in attrs.items())
        try:
            print(
                f"[{self.tag}] phase={phase}{extra} "
                f"elapsed={now - phase_start:.0f}s total={now - self._t0:.0f}s",
                file=self.stream,
                flush=True,
            )
        except Exception:
            pass  # stderr may be gone at interpreter teardown

    def start(self) -> "Heartbeat":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="fts-heartbeat", daemon=True
            )
            self._thread.start()
        return self

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            self.emit()

    def stop(self) -> None:
        self._stop.set()
        with self._lock:
            phase, phase_start, attrs = self._phase, self._phase_start, self._attrs
        if _enabled:
            REGISTRY.record_phase(phase, phase_start, time.time(), **attrs)


# ------------------------------------------------------------ flight recorder


class FlightRecorder:
    """Bounded ring buffer of structured lifecycle events — the crash
    flight recorder. Always on (recording is one lock + deque append on
    rare events: submits, block cuts, verify decisions, WAL appends,
    faults, retries, compiles), so an rc=124 death leaves a causal trail
    of *what was happening*, not just final counter values. The ring is
    dumped to a ``*.flight.json`` sidecar by every `flush_sidecar` (and
    on demand via `dump`); capacity comes from ``FTS_FLIGHT_EVENTS``
    (default 1024) — sustained load evicts the oldest events only."""

    def __init__(self, capacity: Optional[int] = None):
        if capacity is None:
            try:
                capacity = int(os.environ.get("FTS_FLIGHT_EVENTS", "1024"))
            except ValueError:
                capacity = 1024
        self.capacity = max(1, capacity)
        self._ring: collections.deque = collections.deque(maxlen=self.capacity)
        self._lock = threading.Lock()

    def record(self, kind: str, trace: Optional[TraceContext] = None,
               **attrs) -> None:
        ctx = trace if trace is not None else current_trace()
        evt = {"ts": round(time.time(), 6), "kind": kind}
        if ctx is not None:
            evt["trace_id"] = ctx.trace_id
        for k, v in attrs.items():
            if v is not None:
                evt[k] = _jsonable(v)
        # timed acquire: tail()/dump() may run under a signal handler
        acquired = self._lock.acquire(timeout=1.0)
        try:
            self._ring.append(evt)
        finally:
            if acquired:
                self._lock.release()
        REGISTRY.counter("flight.events").inc()

    def tail(self, n: Optional[int] = None) -> List[dict]:
        acquired = self._lock.acquire(timeout=1.0)
        try:
            if acquired:
                events = list(self._ring)
            else:
                # unlocked best-effort read (signal-handler path, lock
                # held by the interrupted thread): a concurrent append
                # can invalidate iteration — retry, then settle for an
                # empty tail rather than raising out of the flush
                events = []
                for _ in range(3):
                    try:
                        events = list(self._ring)
                        break
                    except RuntimeError:
                        continue
        finally:
            if acquired:
                self._lock.release()
        return events if n is None else events[-n:]

    def __len__(self) -> int:
        return len(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def dump(self, path: str) -> Optional[str]:
        """Write the ring to `path` (atomic rename); returns the path,
        or None on failure. Safe under signal handlers — NEVER raises
        (the SIGTERM flush must not die building its own payload)."""
        try:
            payload = json.dumps(
                {
                    "dumped_unix": round(time.time(), 3),
                    "pid": os.getpid(),
                    "capacity": self.capacity,
                    "events": self.tail(),
                }
            )
        except Exception:
            return None
        tmp = f"{path}.{os.getpid()}.tmp"
        try:
            with open(tmp, "w") as fh:
                fh.write(payload)
            os.replace(tmp, path)
        except Exception:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return None
        REGISTRY.counter("flight.dumps").inc()
        return path


FLIGHT = FlightRecorder()


def flight(kind: str, trace: Optional[TraceContext] = None, **attrs) -> None:
    """Record one flight-recorder event (always on; tags the active —
    or explicitly passed — trace context)."""
    FLIGHT.record(kind, trace=trace, **attrs)


def flight_sidecar_path(metrics_path: str) -> str:
    """Derive the flight sidecar path from a metrics sidecar path
    (``X.metrics.json`` -> ``X.flight.json``)."""
    if metrics_path.endswith(".metrics.json"):
        return metrics_path[: -len(".metrics.json")] + ".flight.json"
    return metrics_path + ".flight.json"


# ------------------------------------------------------------ sidecar


_sidecar_lock = threading.Lock()
_sidecar_path: Optional[str] = None
_sidecar_installed = False


def flush_sidecar(path: Optional[str] = None) -> Optional[str]:
    """Write the registry snapshot to the sidecar JSON (atomic rename)
    and the flight-recorder ring to the derived ``*.flight.json``.

    Safe to call from signal handlers and watchdog threads; returns the
    metrics path written, or None if no path is configured.
    """
    p = path or _sidecar_path
    if not p:
        return None
    payload = REGISTRY.to_json()
    acquired = _sidecar_lock.acquire(timeout=2.0)  # may run under a signal
    try:
        tmp = f"{p}.{os.getpid()}.tmp"
        try:
            with open(tmp, "w") as fh:
                fh.write(payload)
            os.replace(tmp, p)
        except Exception:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return None
    finally:
        if acquired:
            _sidecar_lock.release()
    FLIGHT.dump(flight_sidecar_path(p))
    return p


def install_sidecar(path: str,
                    signals: Sequence[int] = (signal.SIGTERM, signal.SIGINT)) -> None:
    """Flush a metrics sidecar on normal exit AND on SIGTERM/SIGINT.

    This is what turns an rc=124 (``timeout`` sends SIGTERM) from a
    zero-information outcome into a full per-phase accounting. Signal
    handlers chain to the default disposition so the exit code still
    reflects the kill.
    """
    global _sidecar_path, _sidecar_installed
    _sidecar_path = path
    if _sidecar_installed:
        return
    _sidecar_installed = True
    atexit.register(flush_sidecar)

    def _on_signal(signum, frame):
        REGISTRY.set_meta("killed_by_signal", signum)
        flush_sidecar()
        signal.signal(signum, signal.SIG_DFL)
        os.kill(os.getpid(), signum)

    for sig in signals:
        try:
            signal.signal(sig, _on_signal)
        except (ValueError, OSError):
            pass  # not the main thread / unsupported platform
