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
  * ``device.<plane>.glue.<part>_us``      — the glue by part (below)

The outermost `plane(...)` block on a thread is a **plane span** (one
batched verify / sign / prove call). Its time outside any frame is host
glue — Fiat-Shamir hashing, limb encode/decode, numpy reshapes — so per
plane `span_s = stage_s + wait_s + glue_s` exactly (`plane_snapshot()`).
A frame that no plane span encloses is its own span with no glue.

The glue has names: inside a plane span `glue(part)` bills the host
time of its block to one of `GLUE_PARTS` (`parse`, `hostec`, `encode`,
`decode`, `challenge`), less the frames and the nested `glue` blocks it
encloses. What no block claimed is `other`, computed at the close of
the span and never timed, so per plane `glue_us = parse_us + hostec_us +
encode_us + decode_us + challenge_us + other_us` exactly.

Frames, tiles, read-backs, glue blocks and plane spans are also on the
profiler's clock: while a `jax.profiler` session runs they show in the
host plane of the trace as `fts:<plane>`, `fts:<plane>:<program>` (one
tile's enqueue), `fts:wait:<plane>:<program>` (one read-back) and
`fts:<plane>:glue:<part>`, next to the device's `XLA Ops` (`annotate()`
marks the host layers the same way). Every instant of a plane span is
under exactly one of the last three, or under the bare `fts:<plane>`
(= `other`).
With no session a `TraceAnnotation` is one atomic check; `jax` is never
imported from here (no `jax` in the process, no session to write to).

Frames are thread-local, so the `jax.monitoring` compile/cache
listeners (ops/__init__) can attribute backend compile wall time and
persistent-cache hits to the program that triggered them — the join
between XLA's anonymous compile events and `stages.stage_programs()`.

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
from typing import Dict, Optional, Sequence, Tuple

from . import metrics as mx

__all__ = [
    "enabled",
    "dispatch",
    "plane",
    "glue",
    "GLUE_PARTS",
    "annotate",
    "attribute",
    "current_program",
    "note_compile",
    "note_cache",
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
# the form of each limb product under every program, as `ops.limbs`
# declares it at import; empty in a process that never imported ops
_product_forms: Dict[str, str] = {}

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
        }
    return e


def current_plane() -> str:
    return getattr(_tl, "plane", None) or DEFAULT_PLANE


# what the host does between a plane's dispatches, by cure: deserialise
# proofs and signatures / curve arithmetic on host integers / limb
# encoding and row assembly / limb decoding after a read-back /
# Fiat-Shamir. Closed, like `profiler.LEGS`; the rest of the glue is
# `other`
GLUE_PARTS = ("parse", "hostec", "encode", "decode", "challenge")
_GLUE_INDEX = {part: i for i, part in enumerate(GLUE_PARTS)}
# plane -> the parts' `fts:<plane>:glue:<part>` names, formatted once
_glue_names: Dict[str, Tuple[str, ...]] = {}


class _Span:
    """The open plane span of a thread: what its frames and its `glue`
    blocks have added up so far."""

    __slots__ = ("frames_s", "wait_s", "parts", "glue", "names")

    def __init__(self, pl: str):
        self.frames_s = 0.0  # the enclosed frames' wall_s
        self.wait_s = 0.0  # of that, their wait_s
        self.parts = [0.0] * len(GLUE_PARTS)  # exclusive seconds a part
        self.glue: Optional["_Glue"] = None  # the innermost open block
        names = _glue_names.get(pl)
        if names is None:
            names = _glue_names[pl] = tuple(
                f"fts:{pl}:glue:{part}" for part in GLUE_PARTS
            )
        self.names = names


class _Glue:
    """One `glue(part)` block of an open plane span: its wall time less
    the frames closed inside it and less the blocks nested in it goes to
    the part; marked `fts:<plane>:glue:<part>` on the profiler's clock."""

    __slots__ = ("_span", "_idx", "_ann", "_outer", "_t0", "_frames0",
                 "_inner_s")

    def __init__(self, span: _Span, idx: int):
        self._span = span
        self._idx = idx
        self._ann = _annotation(span.names[idx])

    def __enter__(self):
        span = self._span
        self._outer = span.glue
        span.glue = self
        self._inner_s = 0.0
        self._frames0 = span.frames_s
        self._t0 = time.monotonic()
        self._ann.__enter__()

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        span = self._span
        # this block's host time: the frames inside it are stage / wait
        host_s = (
            time.monotonic() - self._t0 - (span.frames_s - self._frames0)
        )
        span.parts[self._idx] += host_s - self._inner_s
        outer = span.glue = self._outer
        if outer is not None:
            outer._inner_s += host_s


def glue(part: str):
    """`with devobs.glue("encode"):` inside a plane span bills the host
    time of the block to `part` (one of `GLUE_PARTS`; anything else
    raises) — exclusively: a dispatch frame the block encloses stays
    `stage_us` / `wait_us`, and a nested `glue` block is billed to its
    own part. Enter one per batch, not per row: an entry is two clock
    reads and a thread-local update, the counters move at the close of
    the plane span. Passthrough outside a plane span, inside an open
    frame (that time is the frame's), and so with the ledger off."""
    idx = _GLUE_INDEX.get(part)
    if idx is None:
        raise ValueError(
            f"unknown glue part {part!r}; one of {', '.join(GLUE_PARTS)}"
        )
    span = getattr(_tl, "span", None)
    if span is None or getattr(_tl, "frame", None) is not None:
        return _NULL
    return _Glue(span, idx)


def _close_plane_span(
    pl: str, span_s: float, frames_s: float, wait_s: float,
    parts: Sequence[float] = (0.0,) * len(GLUE_PARTS),
) -> None:
    """One plane span into the per-plane aggregate and the always-on
    microsecond counters. `span_us = stage_us + wait_us + glue_us` and
    `glue_us = the five parts + other_us`, both exactly: the addends are
    rounded, each total is their sum, and `other` is what is left."""
    stage_s = frames_s - wait_s
    glue_s = max(0.0, span_s - frames_s)
    stage_us = round(stage_s * 1e6)
    wait_us = round(wait_s * 1e6)
    glue_us = round(glue_s * 1e6)
    parts_us = [max(0, round(v * 1e6)) for v in parts]
    over = sum(parts_us) - glue_us
    if over > 0:
        # the parts cover the whole glue and their rounding went up: the
        # microseconds come off the largest
        parts_us[parts_us.index(max(parts_us))] -= over
    by_part = dict(zip(GLUE_PARTS, parts_us), other=glue_us - sum(parts_us))
    with _lock:
        p = _planes.get(pl)
        if p is None:
            p = _planes[pl] = {
                "calls": 0, "span_s": 0.0, "stage_s": 0.0,
                "wait_s": 0.0, "glue_s": 0.0,
                "glue_parts": dict.fromkeys(by_part, 0.0),
            }
        p["calls"] += 1
        p["span_s"] += stage_s + wait_s + glue_s
        p["stage_s"] += stage_s
        p["wait_s"] += wait_s
        p["glue_s"] += glue_s
        for part, us in by_part.items():
            p["glue_parts"][part] += us / 1e6
    mx.counter(f"device.{pl}.span_us").inc(stage_us + wait_us + glue_us)
    mx.counter(f"device.{pl}.stage_us").inc(stage_us)
    mx.counter(f"device.{pl}.wait_us").inc(wait_us)
    mx.counter(f"device.{pl}.glue_us").inc(glue_us)
    for part, us in by_part.items():
        mx.counter(f"device.{pl}.glue.{part}_us").inc(us)


@contextlib.contextmanager
def plane(name: str):
    """Tag dispatches in this block with a logical plane (verify, sign,
    prove, ...). The OUTERMOST such block on a thread is the plane span:
    its wall time splits into the frames it encloses (`stage_s` +
    `wait_s`) and the rest, host glue (`glue_s`, by part where `glue`
    blocks name it); a nested block (the transfer verifier calling the
    wf / membership / PS verifiers) only re-tags and is never counted
    twice. Passthrough when the ledger is off."""
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
    span = _tl.span = _Span(name)
    t0 = time.monotonic()
    try:
        with _annotation("fts:" + name):
            yield
    finally:
        _tl.plane = prev
        _tl.span = None
        _close_plane_span(
            name, time.monotonic() - t0, span.frames_s, span.wait_s,
            span.parts,
        )


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
            span.frames_s += wall
            span.wait_s += wait
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


def snapshot() -> Dict[Tuple[str, str], dict]:
    """Raw per-(plane, program) aggregates — for window diffing in
    tests and bench; values are copies."""
    with _lock:
        return {frame: dict(e) for frame, e in _programs.items()}


def plane_snapshot() -> Dict[str, dict]:
    """Raw per-plane aggregates of the plane spans: `calls`, `span_s`,
    `stage_s`, `wait_s`, `glue_s` with `span_s = stage_s + wait_s +
    glue_s`, and `glue_parts`, the glue by part and `other` (the
    counters' split in seconds: whole microseconds a span) — for window
    diffing (the orderer takes a block's share so); values are copies.
    Kept apart from `snapshot()`, whose entries are programs."""
    with _lock:
        return {
            pl: dict(p, glue_parts=dict(p["glue_parts"]))
            for pl, p in _planes.items()
        }


def note_product_forms(forms: Dict[str, str]) -> None:
    """`ops.limbs` hands over the form of each limb product (`fp_mul` in
    `health_section`), as frames hand over `window_bits`: ops pushes, this
    module never imports ops. Not ledger state: `reset` keeps it."""
    _product_forms.update(forms)


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
    per-plane occupancy plus the full per-program ledger, and the form of
    each limb product under every program (`fp_mul`)."""
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
            agg["glue_parts"] = {
                part: round(v, 6) for part, v in sp["glue_parts"].items()
            }
    return {
        "enabled": enabled(),
        "fp_mul": dict(_product_forms),
        "planes": planes,
        "programs": programs,
    }


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
        "planes": h["planes"],
        "programs": h["programs"],
    }
