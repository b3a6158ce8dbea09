"""Resilience layer: bounded device dispatch + per-plane circuit breakers.

The degrade chains built so far (device -> host) only handle device
calls that *fail fast*: an exception falls through to the host path and
the block commits with identical verdicts. A call that HANGS — a wedged
device backend — blocks the commit worker forever.
This module closes that gap with the two primitives every serving stack
pairs:

* ``bounded_call(fn, deadline_s)`` — run one device dispatch on a daemon
  worker thread and give the caller back control when the wall budget
  expires (``DeviceTimeout``). An abandoned JAX call **cannot be
  cancelled**: the worker keeps running until the backend returns, and
  its eventual result is DISCARDED, never applied (counted under
  ``resilience.bounded.stragglers``). Discarding is safe because every
  device plane here is read-only over request bytes — verdicts/proofs
  only take effect when the supervisor returns them, and a timed-out
  supervisor never does.

* ``CircuitBreaker`` — per-plane closed/open/half-open breaker. Bounded
  dispatch alone would let every new block pay a full deadline against a
  sick backend (and stack one abandoned worker per attempt); the breaker
  is what stops new work from piling on: after
  ``FTS_BREAKER_FAILURES`` consecutive failures or
  ``FTS_BREAKER_TIMEOUTS`` consecutive timeouts it OPENS, rejecting
  dispatches outright (instant host fallback) for
  ``FTS_BREAKER_COOLDOWN_S`` of monotonic-clock cooldown, then admits
  exactly ONE half-open probe; a probe success closes the breaker (the
  plane heals itself — no restart, no operator), a probe failure re-opens
  it and restarts the cooldown.

Accept/reject can never depend on this layer: a rejected or timed-out
dispatch falls to the exact host path the degrade chain already proves
verdict-identical (differential-tested including the ``hang`` fault kind
in tests/test_resilience.py).

Planes wired (one breaker each, registered lazily by name):

    verify  — `BlockValidationPipeline.proof_verdicts` block calls
    sign    — `BlockValidationPipeline.sign_verdicts` (REPLACES the old
              permanent construction-failure latch: a transient OOM now
              heals via the half-open probe)
    prove   — `TransferProver.batch` group routing

Deadlines resolve per plane via ``device_deadline_s(plane)``:
``FTS_DEVICE_DEADLINE_<PLANE>_S`` wins, else ``FTS_DEVICE_DEADLINE_S``,
else the default — commit-path planes (verify/sign) are bounded at
``ACCEL_DEADLINE_S`` (120s) when the live jax backend is a real
accelerator and UNBOUNDED on the CPU-emulated plane (where a legitimate
cold compile or big-block verify takes minutes and a tight default would
open the breaker against a healthy backend); the client-side plane
(prove) defaults unbounded. ``0`` always means unbounded, and an
unbounded call runs inline (no supervisor thread).

Observability: counters ``resilience.breaker.{open,close,probe,
rejected}`` and ``resilience.bounded.{calls,timeouts,stragglers}``, a
per-plane state gauge (0=closed, 1=half-open, 2=open), and a ``breaker``
flight event per transition/timeout/straggler — surfaced as the breaker
column in ``ftstop top`` (via ``ops.health``) and the resilience summary
line of ``ftsmetrics show``.
"""

from __future__ import annotations

import atexit
import os
import threading
import time
from typing import Callable, Dict, List, Optional

from . import metrics as mx
from .tracing import logger

CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half-open"

_STATE_CODE = {CLOSED: 0, HALF_OPEN: 1, OPEN: 2}

# default wall budget of commit-path device dispatch on a REAL
# accelerator (generous: a healthy warmed-up device verify is seconds;
# only a wedged backend runs into minutes)
ACCEL_DEADLINE_S = 120.0

# planes bounded by default (on accelerators) — the commit path
_COMMIT_PLANES = ("verify", "sign")


class DeviceTimeout(RuntimeError):
    """A bounded device dispatch exceeded its wall deadline. The
    abandoned worker may still be running (a JAX call cannot be
    cancelled); its late result is discarded, never applied."""


def _env_num(name: str, default: float) -> float:
    v = os.environ.get(name)
    if not v:
        return default
    try:
        return float(v)
    except ValueError:
        return default


class CircuitBreaker:
    """closed/open/half-open breaker guarding one device plane.

    Thread-safe; all transitions happen under one lock and are counted +
    flight-recorded OUTSIDE it. The clock is injectable for tests
    (monotonic by default — wall-clock jumps must not early-close a
    breaker).
    """

    def __init__(self, plane: str,
                 failure_threshold: Optional[int] = None,
                 timeout_threshold: Optional[int] = None,
                 cooldown_s: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.plane = plane
        self.failure_threshold = int(
            _env_num("FTS_BREAKER_FAILURES", 5)
            if failure_threshold is None else failure_threshold
        )
        self.timeout_threshold = int(
            _env_num("FTS_BREAKER_TIMEOUTS", 2)
            if timeout_threshold is None else timeout_threshold
        )
        self.cooldown_s = float(
            _env_num("FTS_BREAKER_COOLDOWN_S", 30.0)
            if cooldown_s is None else cooldown_s
        )
        self._clock = clock
        self._lock = threading.Lock()
        self._state = CLOSED
        self._failures = 0  # consecutive failures of any kind
        self._timeouts = 0  # consecutive deadline timeouts
        self._opened_at = 0.0
        self._probing = False  # the single half-open probe is in flight
        self._gauge()  # live-state gauge exists from creation (0=closed)

    # ------------------------------------------------------------ state

    @property
    def state(self) -> str:
        """Current state, with the open->half-open cooldown transition
        applied (so observers see `half-open` once a probe is due)."""
        with self._lock:
            self._tick()
            return self._state

    def _tick(self) -> None:
        # lock held: promote open -> half-open once the cooldown expires
        if (
            self._state == OPEN
            and self._clock() - self._opened_at >= self.cooldown_s
        ):
            self._state = HALF_OPEN
            self._probing = False
            self._gauge()  # the live-state gauge tracks the promotion too

    def _gauge(self) -> None:
        mx.gauge(f"resilience.breaker.state.{self.plane}").set(
            _STATE_CODE[self._state]
        )

    def rejecting(self) -> bool:
        """Non-consuming admission preview: True while the plane is
        hard-open (cooldown not yet expired). Half-open is NOT rejecting
        — a probe is available. Cheap enough for per-block fast-path
        gates that want to skip even collection work."""
        with self._lock:
            self._tick()
            rejected = self._state == OPEN
        if rejected:
            mx.counter("resilience.breaker.rejected").inc()
        return rejected

    def allow(self) -> bool:
        """Consuming admission check, called immediately before one
        dispatch: True in closed state, True for exactly ONE caller in
        half-open (the probe — everyone else is rejected until the probe
        reports), False while open. The caller that got True MUST report
        back via `record_success`/`record_failure`."""
        with self._lock:
            self._tick()
            if self._state == CLOSED:
                return True
            if self._state == HALF_OPEN and not self._probing:
                self._probing = True
                probe = True
            else:
                probe = False
        if probe:
            mx.counter("resilience.breaker.probe").inc()
            mx.flight("breaker", plane=self.plane, event="probe")
            return True
        mx.counter("resilience.breaker.rejected").inc()
        return False

    def cancel_probe(self) -> None:
        """Release a consumed `allow()` admission WITHOUT recording an
        outcome — for the caller that discovered there is nothing to
        dispatch after all (e.g. the driver has no batched plane). The
        half-open probe slot re-opens for the next dispatcher; state is
        otherwise unchanged. Without this, an unreported probe would
        wedge the breaker in half-open forever."""
        with self._lock:
            self._probing = False

    def record_success(self) -> None:
        with self._lock:
            was = self._state
            self._state = CLOSED
            self._failures = 0
            self._timeouts = 0
            self._probing = False
            self._gauge()
        if was != CLOSED:
            mx.counter("resilience.breaker.close").inc()
            mx.flight("breaker", plane=self.plane, event="close")
            logger.info(
                "resilience: %s breaker closed (plane healed)", self.plane
            )

    def record_failure(self, timeout: bool = False,
                       trip_now: bool = False) -> None:
        """`trip_now` opens the breaker on THIS failure regardless of
        thresholds — for structural failures (e.g. verifier construction
        OOM) where per-block retries are known-useless; unlike the old
        process-lifetime latch, the half-open probe still heals it."""
        with self._lock:
            self._failures += 1
            self._timeouts = self._timeouts + 1 if timeout else 0
            tripped = trip_now or self._state == HALF_OPEN  # failed probe
            if self._state == CLOSED and (
                self._failures >= self.failure_threshold
                or self._timeouts >= self.timeout_threshold
            ):
                tripped = True
            if tripped:
                self._state = OPEN
                self._opened_at = self._clock()
                self._probing = False
            self._gauge()
        if tripped:
            mx.counter("resilience.breaker.open").inc()
            mx.flight(
                "breaker", plane=self.plane, event="open",
                timeout=bool(timeout), cooldown_s=self.cooldown_s,
            )
            logger.warning(
                "resilience: %s breaker OPEN (%s) — dispatches fall "
                "straight to host for %.1fs, then one half-open probe",
                self.plane, "timeout" if timeout else "failures",
                self.cooldown_s,
            )


# ---------------------------------------------------------------- registry

_breakers: Dict[str, CircuitBreaker] = {}
_breakers_lock = threading.Lock()


def breaker(plane: str) -> CircuitBreaker:
    """Process-wide breaker for one plane (created lazily; env-config is
    read at creation, so tests set `FTS_BREAKER_*` then `reset()`)."""
    with _breakers_lock:
        b = _breakers.get(plane)
        if b is None:
            b = _breakers[plane] = CircuitBreaker(plane)
        return b


def breaker_states() -> Dict[str, str]:
    """{plane: state} snapshot of every breaker that exists — the body
    of the `ops.health` breaker section and the `ftstop top` column."""
    with _breakers_lock:
        bs = list(_breakers.items())
    return {plane: b.state for plane, b in bs}


def reset() -> None:
    """Drop every breaker (test isolation — breakers are process-global
    by design, like the fault registry)."""
    with _breakers_lock:
        _breakers.clear()


# ---------------------------------------------------------------- deadlines


def _accelerator_backend() -> bool:
    """True when jax is ALREADY imported and its default backend is a
    real accelerator. Mirrors `sign_enabled` auto-resolution: this must
    never be the call that initializes a backend on the commit path."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return False
    try:
        return jax.default_backend() != "cpu"
    except Exception:
        return False


def device_deadline_s(plane: str) -> float:
    """Wall budget for one bounded dispatch of `plane`. Resolution:
    `FTS_DEVICE_DEADLINE_<PLANE>_S` > `FTS_DEVICE_DEADLINE_S` > default.
    0 = unbounded (runs inline, no supervisor thread). Default: the
    commit-path planes (verify/sign) are bounded at `ACCEL_DEADLINE_S`
    on a real accelerator and unbounded on the CPU-emulated plane —
    there a cold compile or big-block verify legitimately takes minutes,
    and a tight default would open the breaker against a healthy
    backend. The client-side plane (prove) defaults unbounded."""
    v = os.environ.get(f"FTS_DEVICE_DEADLINE_{plane.upper()}_S")
    if v is None:
        v = os.environ.get("FTS_DEVICE_DEADLINE_S")
    if v is not None:
        try:
            return max(0.0, float(v))
        except ValueError:
            pass
    if plane in _COMMIT_PLANES and _accelerator_backend():
        return ACCEL_DEADLINE_S
    return 0.0


# ---------------------------------------------------------------- bounded

# live ABANDONED workers (timed-out dispatches still running). A daemon
# thread executing native XLA code while the interpreter tears down can
# segfault the process at exit (observed: rc=139 after a chaos run), so
# exit waits a bounded `FTS_STRAGGLER_DRAIN_S` for stragglers to finish
# — short stragglers drain cleanly; a truly hung one still cannot block
# shutdown for more than the budget.
_stragglers: List[threading.Thread] = []
_stragglers_lock = threading.Lock()

# thread-local view of the CURRENT bounded worker's abandonment event —
# the hook completion-contract counters consult (see call_abandoned)
_tls = threading.local()


def call_abandoned() -> bool:
    """True when called from inside a bounded worker whose supervisor
    already timed out and abandoned it. The device planes guard their
    counted-on-COMPLETION metrics (`batch.sign.rows`,
    `batch.prove.{batches,txs}`, `batch.transfer.txs`) with this, so a
    discarded straggler's work is never reported as device-served —
    those rows were ALSO counted as host fallbacks by the caller, and
    double-reporting would corrupt the soak's `sign_plane`/summary
    accounting. False on every ordinary thread."""
    evt = getattr(_tls, "abandon_evt", None)
    return evt is not None and evt.is_set()


def _track_straggler(worker: threading.Thread) -> None:
    with _stragglers_lock:
        _stragglers[:] = [t for t in _stragglers if t.is_alive()]
        _stragglers.append(worker)


def drain_stragglers(timeout_s: float = 5.0) -> bool:
    """Join abandoned workers for up to `timeout_s` total; True when
    none remain alive. Called automatically at interpreter exit."""
    deadline = time.monotonic() + max(0.0, timeout_s)
    with _stragglers_lock:
        live = [t for t in _stragglers if t.is_alive()]
        _stragglers[:] = live
    for t in live:
        t.join(max(0.0, deadline - time.monotonic()))
    with _stragglers_lock:
        _stragglers[:] = [t for t in _stragglers if t.is_alive()]
        return not _stragglers


atexit.register(
    lambda: drain_stragglers(
        _env_num("FTS_STRAGGLER_DRAIN_S", 5.0)
    )
)


def bounded_call(fn: Callable, deadline_s: Optional[float], *args,
                 plane: str = "device", **kwargs):
    """Run `fn(*args, **kwargs)` under a wall deadline.

    `deadline_s` None/0 runs inline (unbounded — zero overhead, the
    default on emulated backends). Otherwise `fn` runs on a daemon
    worker thread with the caller's trace context propagated; if it does
    not finish within the budget, `DeviceTimeout` raises on the CALLER's
    stack and the worker is abandoned — it keeps running (a JAX call
    cannot be cancelled), but whatever it eventually returns or raises
    is discarded, never applied, and counted as a straggler. Exceptions
    from a non-abandoned `fn` re-raise on the caller's stack unchanged.
    """
    if not deadline_s or deadline_s <= 0:
        return fn(*args, **kwargs)
    mx.counter("resilience.bounded.calls").inc()
    box: dict = {}
    done = threading.Event()
    abandon_evt = threading.Event()
    lock = threading.Lock()
    state = {"finished": False, "abandoned": False}
    ctx = mx.current_trace()

    def _run():
        _tls.abandon_evt = abandon_evt  # visible to call_abandoned()
        try:
            with mx.use_trace(ctx):
                box["result"] = fn(*args, **kwargs)
            box["ok"] = True
        except BaseException as e:  # delivered to (or discarded for) caller
            box["error"] = e
        finally:
            with lock:
                state["finished"] = True
                straggler = state["abandoned"]
            done.set()
            if straggler:
                # completed AFTER the caller gave up: the result above is
                # dead — the host fallback already resolved the block
                mx.counter("resilience.bounded.stragglers").inc()
                mx.flight(
                    "breaker", plane=plane, event="straggler",
                    ok="error" not in box,
                )

    worker = threading.Thread(
        target=_run, name=f"fts-bounded-{plane}", daemon=True
    )
    worker.start()
    if not done.wait(deadline_s):
        with lock:
            finished = state["finished"]
            if not finished:
                state["abandoned"] = True
                abandon_evt.set()
        if not finished:
            _track_straggler(worker)
            mx.counter("resilience.bounded.timeouts").inc()
            mx.flight(
                "breaker", plane=plane, event="timeout",
                deadline_s=deadline_s,
            )
            raise DeviceTimeout(
                f"{plane}: device dispatch exceeded its {deadline_s}s wall "
                "deadline (worker abandoned; a late result is discarded)"
            )
        # finished in the race window between wait() expiry and the lock:
        # box is fully populated before `finished` flips — take the result
    if box.get("ok"):
        return box["result"]
    raise box["error"]
