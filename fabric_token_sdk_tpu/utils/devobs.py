"""Device-plane dispatch ledger: occupancy, padding waste, and compile
forensics for every staged XLA dispatch.

The staged execution model buys a tiny, fixed program set by padding
every batch up to the tile row count — which makes two numbers the
whole story of device efficiency: how full each tile was (occupancy)
and how much work was padding (waste). This module is the single place
those numbers are recorded. Every device entry point
(`ops/stages.run_rows`, the staged pairing dispatches, and through
them the batched verifiers/signer/prover) opens a `dispatch(...)`
frame naming the canonical XLA program it is about to run. A frame
lasts until the program's results are back on the host and is the ONLY
timer at that boundary: it records requested vs padded rows, wall time
and how that wall divides into `stage_s` (the host preparing and
enqueueing tiles) and `wait_s` (the host blocked on a read-back), and
feeds the metrics registry:

  * ``device.dispatch.seconds``            — all dispatches, one histogram
  * ``device.dispatch.<program>.seconds``  — per-program wall time
  * ``device.<plane>.occupancy``           — rows / (rows + padding)
  * ``device.<program>.padded_rows``       — cumulative padding waste
  * ``device.<plane>.{span,stage,wait,glue}_us`` — per plane span (below)

The outermost `plane(...)` block on a thread is a **plane span** (one
batched verify / sign / prove call). Its time outside any frame is host
glue — Fiat-Shamir hashing, limb encode/decode, numpy reshapes — so per
plane `span_s = stage_s + wait_s + glue_s` exactly (`plane_snapshot()`).
A frame that no plane span encloses is its own span with no glue.

Frames, tiles, read-backs and plane spans are also on the profiler's
clock: while a `jax.profiler` session runs they show in the host plane
of the trace as `fts:<plane>`, `fts:<plane>:<program>` (one tile's
enqueue) and `fts:wait:<plane>:<program>` (one read-back), next to the
device's `XLA Ops` (`annotate()` marks the host layers the same way).
With no session a `TraceAnnotation` is one atomic check; `jax` is never
imported from here (no `jax` in the process, no session to write to).

Frames are thread-local, so the `jax.monitoring` compile/cache
listeners (ops/__init__) can attribute backend compile wall time and
persistent-cache hits to the program that triggered them — the join
between XLA's anonymous compile events and `stages.stage_programs()`.
Degrade decisions land in the same per-program ledger via
`note_degrade`, so "this program ran slow because it ran on the host"
is visible next to its occupancy.

Contract (mirrors utils/profiler.py): **zero cost when off**. The
ledger is on by default (it is pure dict arithmetic on the dispatch
path — no threads, no sampling); ``FTS_DEVOBS=0`` turns every entry
point into a passthrough that touches neither the ledger nor the
metrics registry. On or off, it only observes: verify verdicts and
committed state are identical either way (tests/test_devobs.py pins
both properties differentially).
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
import time
from typing import Dict, Optional, Tuple

from . import metrics as mx

__all__ = [
    "enabled",
    "dispatch",
    "plane",
    "annotate",
    "attribute",
    "current_program",
    "note_compile",
    "note_cache",
    "note_degrade",
    "snapshot",
    "plane_snapshot",
    "reset",
    "health_section",
    "section",
]

UNATTRIBUTED = "(unattributed)"
DEFAULT_PLANE = "stages"

# occupancy lives in (0, 1]; the default latency buckets would collapse
# it into two bins
_OCC_BUCKETS = tuple(i / 10.0 for i in range(1, 11))

_tl = threading.local()
_lock = threading.Lock()
# (plane, program) -> aggregate dict
_programs: Dict[Tuple[str, str], dict] = {}
# plane -> aggregate of its plane spans (see `plane_snapshot`)
_planes: Dict[str, dict] = {}
# best-effort fallback for compile events fired on a thread that has
# no frame open (the dispatch frame lives on the caller's thread)
_last_frame: Optional[Tuple[str, str]] = None

_NULL = contextlib.nullcontext()
_trace_annotation = None  # jax.profiler.TraceAnnotation, once jax is there


def enabled() -> bool:
    """Ledger switch; read per entry so tests/operators can flip it."""
    return os.environ.get("FTS_DEVOBS", "1") != "0"


def _annotation(name: str):
    """A `jax.profiler.TraceAnnotation` — one atomic check unless a
    profiler session is running. A process that never imported jax
    cannot have a session, and this module must not be the one that
    imports it (the load generator and host-only nodes stay off jax)."""
    global _trace_annotation
    ta = _trace_annotation
    if ta is None:
        prof = getattr(sys.modules.get("jax"), "profiler", None)
        if prof is None:
            return _NULL
        ta = _trace_annotation = prof.TraceAnnotation
    return ta(name)


def annotate(name: str):
    """`with devobs.annotate("validate"):` puts the block into the host
    plane of a running `jax.profiler` trace as `fts:<name>` — the mark
    of a host layer boundary (stage A proof/sign, host validate, WAL
    append, server dispatch) on the same clock as the device's events.
    Records nothing else; passthrough when the ledger is off."""
    return _annotation("fts:" + name) if enabled() else _NULL


def _entry(frame: Tuple[str, str]) -> dict:
    e = _programs.get(frame)
    if e is None:
        e = _programs[frame] = {
            "dispatches": 0,
            "rows": 0,
            "padded_rows": 0,
            "tile_rows": 0,
            "window_bits": 0,
            "wall_s": 0.0,
            "stage_s": 0.0,
            "wait_s": 0.0,
            "compiles": 0,
            "compile_s": 0.0,
            "cache_hits": 0,
            "cache_misses": 0,
            "degrades": {},
        }
    return e


def current_plane() -> str:
    return getattr(_tl, "plane", None) or DEFAULT_PLANE


def _close_plane_span(
    pl: str, span_s: float, frames_s: float, wait_s: float
) -> None:
    """One plane span into the per-plane aggregate and the always-on
    microsecond counters (`span_us = stage_us + wait_us + glue_us`
    exactly: the three parts are rounded, the total is their sum)."""
    stage_s = frames_s - wait_s
    glue_s = max(0.0, span_s - frames_s)
    with _lock:
        p = _planes.get(pl)
        if p is None:
            p = _planes[pl] = {
                "calls": 0, "span_s": 0.0, "stage_s": 0.0,
                "wait_s": 0.0, "glue_s": 0.0,
            }
        p["calls"] += 1
        p["span_s"] += stage_s + wait_s + glue_s
        p["stage_s"] += stage_s
        p["wait_s"] += wait_s
        p["glue_s"] += glue_s
    stage_us = round(stage_s * 1e6)
    wait_us = round(wait_s * 1e6)
    glue_us = round(glue_s * 1e6)
    mx.counter(f"device.{pl}.span_us").inc(stage_us + wait_us + glue_us)
    mx.counter(f"device.{pl}.stage_us").inc(stage_us)
    mx.counter(f"device.{pl}.wait_us").inc(wait_us)
    mx.counter(f"device.{pl}.glue_us").inc(glue_us)


@contextlib.contextmanager
def plane(name: str):
    """Tag dispatches in this block with a logical plane (verify, sign,
    prove, ...). The OUTERMOST such block on a thread is the plane span:
    its wall time splits into the frames it encloses (`stage_s` +
    `wait_s`) and the rest, host glue (`glue_s`); a nested block (the
    transfer verifier calling the wf / membership / PS verifiers) only
    re-tags and is never counted twice. Passthrough when the ledger is
    off."""
    if not enabled():
        yield
        return
    prev = getattr(_tl, "plane", None)
    _tl.plane = name
    if getattr(_tl, "span", None) is not None:
        try:
            yield
        finally:
            _tl.plane = prev
        return
    # [sum of the enclosed frames' wall_s, of their wait_s]
    acc = _tl.span = [0.0, 0.0]
    t0 = time.monotonic()
    try:
        with _annotation("fts:" + name):
            yield
    finally:
        _tl.plane = prev
        _tl.span = None
        _close_plane_span(name, time.monotonic() - t0, acc[0], acc[1])


@contextlib.contextmanager
def attribute(program: str, plane_name: Optional[str] = None):
    """Attribute compile/cache events in this block to `program`
    WITHOUT recording a dispatch — the warmup precompiler's frame."""
    if not enabled():
        yield
        return
    global _last_frame
    frame = (plane_name or current_plane(), program)
    prev = getattr(_tl, "frame", None)
    _tl.frame = frame
    _last_frame = frame
    try:
        yield
    finally:
        _tl.frame = prev


class _Wait:
    """One read-back of an open frame: timed into the frame's `wait_s`
    and marked `fts:wait:<plane>:<program>` on the profiler's clock."""

    __slots__ = ("_waits", "_ann", "_t0")

    def __init__(self, frame: "_Frame"):
        self._waits = frame.waits
        self._ann = _annotation(frame.wait_name)

    def __enter__(self):
        self._t0 = time.monotonic()
        self._ann.__enter__()

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        self._waits.append(time.monotonic() - self._t0)


class _Frame:
    """What `dispatch(...)` yields: the caller marks each tile's enqueue
    with `tile()` and wraps each blocking read-back in `wait()`."""

    __slots__ = ("tile_name", "wait_name", "waits", "window_bits")

    def __init__(self, pl: str, program: str):
        self.tile_name = f"fts:{pl}:{program}"
        self.wait_name = f"fts:wait:{pl}:{program}"
        # seconds of every read-back
        self.waits: list = []
        self.window_bits = 0

    def form(self, window_bits: int):
        """Which form of the program's arithmetic this dispatch runs:
        the bits a digit of its scalar window (0: it walks none)."""
        self.window_bits = window_bits

    def tile(self):
        return _annotation(self.tile_name)

    def wait(self):
        return _Wait(self)


class _OffFrame:
    """`dispatch(...)` with the ledger off: nothing timed or marked."""

    __slots__ = ()

    def form(self, window_bits: int):
        pass

    def tile(self):
        return _NULL

    def wait(self):
        return _NULL


_OFF = _OffFrame()


@contextlib.contextmanager
def dispatch(
    program: str,
    *,
    rows: int,
    padded_rows: int = 0,
    tiles: int = 0,
    plane: Optional[str] = None,
):
    """Record one device dispatch of `program`, from the first byte of
    host preparation until its results are on the host: requested vs
    padded rows, the height of its tiles (`tile_rows` = dispatched rows
    / `tiles`, of the newest dispatch: it says which shape of the
    program ran), the form of its arithmetic where the caller names it
    (`frame.form(...)`: `window_bits`, of the newest dispatch), and
    `wall_s = stage_s + wait_s`.

    `wait_s` is the host blocked on a device result — what the caller
    wrapped in `frame.wait()`; `stage_s` is the rest of the frame — the
    host padding, transferring, enqueueing and reassembling. Where each
    tile is enqueue-then-read-back (the pairing walks) both are summed
    over the tiles. A frame is walked on the thread that opened it, so
    the read-backs never overlap and both are exact.

    When span recording is on (`FTS_METRICS=1`) the frame records
    itself as the `device.dispatch` span — no second timer. Yields the
    frame; passthrough (an inert frame) when off."""
    if not enabled():
        yield _OFF
        return
    global _last_frame
    pl = plane or current_plane()
    frame = (pl, program)
    prev = getattr(_tl, "frame", None)
    _tl.frame = frame
    _last_frame = frame
    fr = _Frame(pl, program)
    t0 = time.monotonic()
    try:
        yield fr
    finally:
        t1 = time.monotonic()
        wall = t1 - t0
        wait = sum(fr.waits)
        total = rows + padded_rows
        height = total // tiles if tiles else 0
        _tl.frame = prev
        with _lock:
            e = _entry(frame)
            e["dispatches"] += 1
            e["rows"] += rows
            e["padded_rows"] += padded_rows
            if height:
                e["tile_rows"] = height
            e["window_bits"] = fr.window_bits
            e["wall_s"] += wall
            e["stage_s"] += wall - wait
            e["wait_s"] += wait
        span = getattr(_tl, "span", None)
        if span is not None:
            span[0] += wall
            span[1] += wait
        else:
            # no plane span around it: the frame is its own, glue-free
            _close_plane_span(pl, wall, wall, wait)
        mx.histogram("device.dispatch.seconds").observe(wall)
        mx.histogram(f"device.dispatch.{program}.seconds").observe(wall)
        if total:
            mx.histogram(
                f"device.{pl}.occupancy", buckets=_OCC_BUCKETS
            ).observe(rows / total)
        if padded_rows:
            mx.counter(f"device.{program}.padded_rows").inc(padded_rows)
        if mx.enabled():
            mx.record_timed_span(
                "device.dispatch", t0, t1, plane=pl, program=program,
                rows=rows, tiles=tiles, tile_rows=height,
                stage_s=round(wall - wait, 6), wait_s=round(wait, 6),
            )


def _active_frame() -> Tuple[str, str]:
    f = getattr(_tl, "frame", None)
    return f or _last_frame or (DEFAULT_PLANE, UNATTRIBUTED)


def current_program() -> Optional[str]:
    """The program of the innermost dispatch/attribute frame (this
    thread first, then the process-wide last frame), else None."""
    f = getattr(_tl, "frame", None) or _last_frame
    return f[1] if f else None


def note_compile(seconds: float) -> None:
    """Called by the jax.monitoring duration listener: attribute one
    backend compile's wall time to the active program."""
    if not enabled():
        return
    frame = _active_frame()
    with _lock:
        e = _entry(frame)
        e["compiles"] += 1
        e["compile_s"] += seconds


def note_cache(event: str) -> None:
    """Called by the jax.monitoring event listener: attribute a
    persistent-compilation-cache hit/miss to the active program."""
    if not enabled():
        return
    if event.endswith("cache_hits"):
        key = "cache_hits"
    elif event.endswith("cache_misses"):
        key = "cache_misses"
    else:
        return
    frame = _active_frame()
    with _lock:
        _entry(frame)[key] += 1


def note_degrade(
    reason: str,
    program: Optional[str] = None,
    plane: Optional[str] = None,
) -> None:
    """Record a degrade decision against the active — or explicitly
    named — program."""
    if not enabled():
        return
    if program is not None:
        frame = (plane or current_plane(), program)
    else:
        frame = _active_frame()
    with _lock:
        degrades = _entry(frame)["degrades"]
        degrades[reason] = degrades.get(reason, 0) + 1


def snapshot() -> Dict[Tuple[str, str], dict]:
    """Raw per-(plane, program) aggregates — for window diffing in
    tests and bench; values are copies."""
    with _lock:
        return {
            frame: dict(e, degrades=dict(e["degrades"]))
            for frame, e in _programs.items()
        }


def plane_snapshot() -> Dict[str, dict]:
    """Raw per-plane aggregates of the plane spans: `calls`, `span_s`,
    `stage_s`, `wait_s`, `glue_s` with `span_s = stage_s + wait_s +
    glue_s` — for window diffing (the orderer takes a block's share so);
    values are copies. Kept apart from `snapshot()`, whose entries are
    programs."""
    with _lock:
        return {pl: dict(p) for pl, p in _planes.items()}


def reset() -> None:
    """Drop all ledger state (registry metrics are untouched)."""
    global _last_frame
    with _lock:
        _programs.clear()
        _planes.clear()
    _last_frame = None


def _occ(rows: int, padded: int) -> Optional[float]:
    total = rows + padded
    return round(rows / total, 4) if total else None


def _waste(rows: int, padded: int) -> Optional[float]:
    total = rows + padded
    return round(padded / total, 4) if total else None


def health_section() -> dict:
    """The `device` block of `Network.health()` / the `ops.health` RPC:
    per-plane occupancy plus the full per-program ledger."""
    snap = snapshot()
    spans = plane_snapshot()
    programs: Dict[str, dict] = {}
    planes: Dict[str, dict] = {}
    for (pl, prog), e in sorted(snap.items()):
        q = mx.REGISTRY.histogram(f"device.dispatch.{prog}.seconds")
        p50 = q.quantile(0.5)
        p99 = q.quantile(0.99)
        programs[f"{pl}:{prog}"] = {
            "plane": pl,
            "program": prog,
            "dispatches": e["dispatches"],
            "rows": e["rows"],
            "padded_rows": e["padded_rows"],
            "tile_rows": e["tile_rows"],
            "window_bits": e["window_bits"],
            "occupancy": _occ(e["rows"], e["padded_rows"]),
            "waste_frac": _waste(e["rows"], e["padded_rows"]),
            "wall_s": round(e["wall_s"], 6),
            "stage_s": round(e["stage_s"], 6),
            "wait_s": round(e["wait_s"], 6),
            "p50_s": round(p50, 6) if p50 is not None else None,
            "p99_s": round(p99, 6) if p99 is not None else None,
            "compiles": e["compiles"],
            "compile_s": round(e["compile_s"], 3),
            "cache_hits": e["cache_hits"],
            "cache_misses": e["cache_misses"],
            "degrades": sum(e["degrades"].values()),
            "degrade_reasons": dict(e["degrades"]),
        }
        agg = planes.setdefault(
            pl, {"dispatches": 0, "rows": 0, "padded_rows": 0}
        )
        agg["dispatches"] += e["dispatches"]
        agg["rows"] += e["rows"]
        agg["padded_rows"] += e["padded_rows"]
    for pl, agg in planes.items():
        agg["occupancy"] = _occ(agg["rows"], agg["padded_rows"])
        agg["waste_frac"] = _waste(agg["rows"], agg["padded_rows"])
        sp = spans.get(pl)
        if sp is not None:
            agg["calls"] = sp["calls"]
            for k in ("span_s", "stage_s", "wait_s", "glue_s"):
                agg[k] = round(sp[k], 6)
    return {"enabled": enabled(), "planes": planes, "programs": programs}


def section() -> dict:
    """The schema-validated `device` section of a bench result
    (utils/benchschema.py): top-level scalars the `ftstop compare
    --device` gate reads, plus the per-plane / per-program breakdown."""
    h = health_section()
    rows = sum(e["rows"] for e in h["programs"].values())
    padded = sum(e["padded_rows"] for e in h["programs"].values())
    agg = mx.REGISTRY.histogram("device.dispatch.seconds")
    p50 = agg.quantile(0.5)
    p99 = agg.quantile(0.99)
    return {
        "dispatches": sum(
            e["dispatches"] for e in h["programs"].values()
        ),
        "rows": rows,
        "padded_rows": padded,
        "occupancy": _occ(rows, padded),
        "waste_frac": _waste(rows, padded),
        "dispatch_p50_s": round(p50, 6) if p50 is not None else None,
        "dispatch_p99_s": round(p99, 6) if p99 is not None else None,
        "compiles": sum(e["compiles"] for e in h["programs"].values()),
        "compile_s": round(
            sum(e["compile_s"] for e in h["programs"].values()), 3
        ),
        "cache_hits": sum(
            e["cache_hits"] for e in h["programs"].values()
        ),
        "cache_misses": sum(
            e["cache_misses"] for e in h["programs"].values()
        ),
        "degrades": sum(e["degrades"] for e in h["programs"].values()),
        "planes": h["planes"],
        "programs": h["programs"],
    }
