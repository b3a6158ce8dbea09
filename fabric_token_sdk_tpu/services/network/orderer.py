"""Orderer: multi-tx block cutting + the batched block-validation plane.

Reference: Fabric's ordering service in front of the committing peers,
and the validator scope note in SURVEY §3 — "the validator runs batched
verification for a whole block". Submissions enter an ordering queue;
blocks are cut by size/linger policy; a block validation pipeline
verifies a block's zkatdlog transfers, whatever their shapes
`(n_in, n_out)`, in ONE `BatchedTransferVerifier` call over the
compile-once stage tiles (`ops/stages.py`), with the host
`RequestValidator` as the fallback for fabtoken transfers, issues, and
blocks with too few transfer rows to batch. The ledger
(`ledger.py`) then applies intra-block MVCC — a double-spend inside a
block invalidates the LATER tx, never the block — and commits the block
atomically with per-tx finality events.

Concurrency model: **group commit without a dedicated thread.**
Submitters enqueue, then race for the commit lock; the winner takes the
next block the cutter has for it and commits it; losers either find
their submission finalized by the winner's block or drive the next
block themselves. A waiter drives until the block that holds its own
submission is taken (by itself or another driver) and from then on
waits on that submission's event alone: the blocks cut behind it are
their own waiters' to drive, so a reply is never held back by a later
block's verification.

The cutter is Fabric's `orderer/common/blockcutter` `Ordered()` plus
the chain's batch timer, applied message by message in enqueue order
(`Orderer._order`; plain reference
`benchmark/reference/fabric_blockcutter.py`):

1. a message over `absolute_max_bytes` is refused before ordering
   (`MessageTooLarge`, nothing enqueued);
2. a message over `preferred_max_bytes` cuts the open batch, if any,
   and is then cut alone as its own block;
3. a message that would carry the open batch past
   `preferred_max_bytes` cuts that batch first and opens the next one;
4. an open batch that reaches `max_block_txs` messages is cut;
5. the batch timer starts with the first message of an open batch and,
   `linger_s` later, cuts whatever the batch holds.

A byte rule set to 0 is off. `linger_s` 0 (the default) means there is
no batch timer: a driver takes whatever is open when it comes by, so
sequential callers see one-tx blocks with zero added latency, concurrent
load batches naturally, and `Network.submit_many` / `Orderer.flush` cut
"everything pending, `max_block_txs` at a time". With a timer, a client
decides no block: `flush` drives the blocks the rules have cut and
leaves an open batch to its timer. No thread owns the timer: a driver
whose transaction sits in the open batch sleeps until the timer runs
out (a condition wait, woken early by a cut), and every enqueue first
closes a batch whose timer ran out before it, so which messages share a
block depends on their arrival times alone.

Pipelined mode (`pipeline.PipelinedBlockEngine`, default on, opt-out
`FTS_BLOCK_PIPELINE=0`): the driving thread runs only the CUT + batched
device verify of block N+1 while a commit worker finishes block N's
host-validate/WAL/merge — verify overlaps commit, height order is
preserved at the hand-off queue, and waiters park on their submission's
event (condition wait, no spinning on the commit lock).

Admission control: `BlockPolicy.queue_max` (`FTS_ORDERER_QUEUE_MAX`)
bounds the ordering queue; a full queue rejects the submission BEFORE it
enters ordering with a typed `Backpressure` error — retry-safe by
construction (nothing was enqueued, nothing can commit), carried over
the wire to remote submitters.
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ...api.request import TokenRequest
from ...api.validator import SIG_AUDITOR, RequestValidator
from ...drivers import identity
from ...utils import devobs, faults, resilience, slo
from ...utils import metrics as mx
from ...utils.tracing import logger


def host_batch_enabled() -> bool:
    """Master switch for the batch-first HOST validation passes
    (`FTS_HOST_BATCH`, default on): block-level Fiat-Shamir + native
    batch multiply for signatures/proofs the device plane left behind,
    and the vectorized conservation pass. `0` restores the exact per-tx
    scalar path — the differential baseline. All host batch passes are
    degrade-only: they emit True-only verdicts, and every None/False row
    falls back to the scalar check that owns the precise error message."""
    return os.environ.get("FTS_HOST_BATCH", "1") != "0"


class MessageTooLarge(ValueError):
    """The request is longer than `BlockPolicy.absolute_max_bytes`
    (Fabric's `AbsoluteMaxBytes`): refused BEFORE ordering, nothing was
    enqueued — and, unlike `Backpressure`, no retry can succeed. The
    remote server maps it to a typed wire error
    (`error_class: "MessageTooLarge"`) and the remote client raises it
    back as this same type."""


class Backpressure(RuntimeError):
    """The ordering queue is at `BlockPolicy.queue_max` capacity: the
    submission was rejected BEFORE entering ordering, so a retry (with
    backoff) is always safe — nothing was enqueued, nothing can commit,
    and the exactly-once contract is untouched. The remote server maps
    this to a typed wire error (`error_class: "Backpressure"`) and the
    remote client raises it back as this same type."""


@dataclass
class BlockPolicy:
    """Block-cut + batched-validation policy.

    The cut rules (module docstring; Fabric's names in brackets):
    `max_block_txs`  — an open batch is cut when it holds this many
                       messages [BatchSize.MaxMessageCount].
    `linger_s`       — the batch timer [BatchTimeout]: it starts when a
                       message enters an empty batch and cuts whatever
                       the batch holds when it runs out; a cut by count
                       or bytes does not wait for it, and no caller's
                       `flush()` / `submit_many` cuts before it. 0 = no
                       timer: a driver cuts whatever is pending when it
                       comes by.
    `preferred_max_bytes` — a message that would carry the open batch
                       past this many bytes cuts it first; a message
                       longer than this is cut alone
                       [BatchSize.PreferredMaxBytes]. 0 = off.
    `absolute_max_bytes` — a longer message is refused before ordering
                       with `MessageTooLarge`
                       [BatchSize.AbsoluteMaxBytes]. 0 = off.
                       A message's size is its request's wire length.
    `min_batch`      — the least number of planned records in a block
                       (transfers of any shapes and issues: a block's
                       rows ride one call) worth a device batch call; a
                       block with fewer takes the host path.
    `use_batched`    — master switch for the batched proof plane.
    `queue_max`      — admission control: ordering-queue depth beyond
                       which enqueues are rejected with `Backpressure`
                       (0 = unbounded, the default).
    `pipeline`       — verify/commit overlap via the pipelined block
                       engine (`FTS_BLOCK_PIPELINE=0` force-disables it
                       regardless of this field — the env kill switch
                       always restores the exact sequential path).
    `sign_batched`   — the batched SIGNATURE plane: True forces it on,
                       False off, None (default, env `auto`) engages it
                       only when the jax backend is a real accelerator —
                       on the CPU-emulated plane a device Schnorr row
                       costs ~3 orders of magnitude more than the host
                       check (measured ~0.4s/row vs ~0.6ms), the same
                       asymmetry the prove plane routes around.
    `sign_min_batch` — smallest per-block pk-obligation count worth the
                       one batched signature call; smaller blocks stay
                       on the host path.
    """

    max_block_txs: int = 64
    linger_s: float = 0.0
    min_batch: int = 2
    use_batched: bool = True
    queue_max: int = 0
    pipeline: bool = True
    sign_batched: Optional[bool] = None
    sign_min_batch: int = 4
    preferred_max_bytes: int = 0
    absolute_max_bytes: int = 0

    @classmethod
    def from_env(cls) -> "BlockPolicy":
        sign_env = os.environ.get("FTS_SIGN_BATCHED", "auto").lower()
        return cls(
            max_block_txs=int(os.environ.get("FTS_BLOCK_MAX_TXS", "64")),
            linger_s=float(os.environ.get("FTS_BLOCK_LINGER_S", "0")),
            min_batch=int(os.environ.get("FTS_BLOCK_MIN_BATCH", "2")),
            use_batched=os.environ.get("FTS_BLOCK_BATCHED", "1") != "0",
            queue_max=int(os.environ.get("FTS_ORDERER_QUEUE_MAX", "0")),
            pipeline=os.environ.get("FTS_BLOCK_PIPELINE", "1") != "0",
            sign_batched=(
                None if sign_env == "auto" else sign_env not in ("0", "false")
            ),
            sign_min_batch=int(os.environ.get("FTS_SIGN_MIN_BATCH", "4")),
        )


class Submission:
    """Handle for one ordered tx. `result()` drives block cutting until
    the tx is final — under group commit any waiter may end up committing
    the block that contains it. Carries the tx's trace context (captured
    at enqueue) so block-commit work done by WHICHEVER thread wins the
    commit race still lands in the submitting tx's trace."""

    __slots__ = ("request", "event", "_done", "_orderer", "trace",
                 "enqueued_at", "enqueued_unix", "_commit_error", "size",
                 "_taken")

    def __init__(self, orderer: Optional["Orderer"], request: TokenRequest):
        self.request = request
        self.event = None  # FinalityEvent once resolved
        self._done = threading.Event()
        self._orderer = orderer
        self.trace = None  # TraceContext captured at enqueue
        self.enqueued_at = 0.0  # monotonic, for queue-wait timing
        self.enqueued_unix = 0.0
        self.size = 0  # wire bytes, what the cutter's byte rules count
        # a driver has taken the block that holds it (set under the
        # orderer's mutex): from then on its own event is what to wait for
        self._taken = False
        # pipelined mode: a commit exception from the worker thread is
        # attached here (alongside the transient stranded event) so
        # `result()` re-raises it on the waiter's own stack — the same
        # contract the sequential engine gives its driving thread
        self._commit_error = None

    def done(self) -> bool:
        return self._done.is_set()

    def _resolve(self, event) -> None:
        if self._done.is_set():
            return  # idempotent: a submission resolves exactly once
        self.event = event
        self._done.set()
        if self._orderer is not None and self.enqueued_at:
            # live in-flight accounting + the submit→finality latency
            # histogram (always on: the ops plane reads its quantiles)
            self._orderer._mark_resolved()
            finality_s = max(0.0, time.monotonic() - self.enqueued_at)
            mx.histogram("network.submit_to_finality.seconds").observe(
                finality_s
            )
            # slow-tx exemplar ring (utils/slo.py): the K slowest txs
            # keep their trace ids so `ftstrace timeline` has a concrete
            # target after a soak
            slo.record_exemplar(
                finality_s, event.tx_id,
                self.trace.trace_id if self.trace else None,
            )
        mx.flight(
            "finality", trace=self.trace,
            tx=event.tx_id, status=event.status.value,
        )

    def result(self, timeout: Optional[float] = None):
        """Block (driving commits as needed) until this tx has finality.
        Re-raises a pipelined commit-worker exception on the waiter's own
        stack (the sequential engine raises it in the driving thread)."""
        if not self._done.is_set() and self._orderer is not None:
            self._orderer.drive(self, timeout)
        if self._commit_error is not None:
            raise self._commit_error
        return self.event


# `block.cut` reasons that have a counter of their own; "drain" (no batch
# timer: a driver took what was open) counts in blocks and bytes alone
_CUT_COUNTERS = {
    "count": "orderer.cut.by_count",
    "bytes": "orderer.cut.by_bytes",
    "timeout": "orderer.cut.by_timeout",
    "oversize": "orderer.cut.oversize",
}
# first message of a batch -> its cut: nothing under a millisecond, fine
# around the second or two a channel's BatchTimeout is
_BATCH_WAIT_BUCKETS = (
    0.001, 0.01, 0.05, 0.1, 0.25, 0.5, 0.75, 1.0, 1.25, 1.5, 1.75, 1.9, 2.0,
    2.1, 2.5, 3.0, 5.0, 10.0, 30.0, 60.0,
)


class Orderer:
    """Ordering queue + block cutter + group-commit driving.

    `commit_block` is the ledger's callback: it takes the cut list of
    Submissions, validates + commits them as ONE block, and resolves each
    submission with its per-tx finality event. `clock` is the cutter's
    monotonic clock (tests pass a fake one: which messages share a block
    depends on their arrival times on it alone).
    """

    def __init__(self, commit_block: Callable[[List[Submission]], None],
                 policy: Optional[BlockPolicy] = None,
                 clock: Callable[[], float] = time.monotonic):
        self._commit_block = commit_block
        self.policy = policy or BlockPolicy()
        self._clock = clock
        # the open batch (messages ordered, block not cut yet) and the
        # cut batches no driver has taken yet, oldest first
        self._pending: List[Submission] = []
        self._pending_bytes = 0
        self._ready: collections.deque = collections.deque()
        self._queued = 0  # messages in `_pending` + `_ready`
        self._mutex = threading.Lock()  # guards the queues + _inflight
        # a driver whose tx sits in the open batch waits here for the
        # batch timer; a cut by count or bytes wakes it early
        self._cut_cond = threading.Condition(self._mutex)
        # submissions enqueued but not yet resolved (queued OR inside a
        # block being committed) — the instantaneous signal `ops.health`
        # serves; queue-wait histograms only exist after commit
        self._inflight = 0
        # RLock: a finality listener that (re)submits must not deadlock
        self._commit_lock = threading.RLock()
        # pipelined block engine (ledger.Network wires it when the
        # policy + FTS_BLOCK_PIPELINE enable the verify/commit overlap)
        self._engine = None

    def set_engine(self, engine) -> None:
        self._engine = engine

    # ------------------------------------------------------------ queue

    def enqueue(self, request: TokenRequest,
                size: Optional[int] = None) -> Submission:
        """Order one request (`size`: its wire length, computed when not
        given). Raises `MessageTooLarge` or `Backpressure` BEFORE
        ordering: nothing was enqueued."""
        return self.enqueue_many([(request, size, mx.current_trace())])[0]

    def enqueue_many(self, items: Sequence[tuple]) -> List[Submission]:
        """Order `(request, size, trace)` triples under ONE hold of the
        queue mutex, so no driver can cut between two of them (a
        `submit_many` hand-over meets the cut rules whole). Returns the
        Submissions of the prefix the bounded queue admitted (all of them
        with `queue_max` 0); the caller drains and hands the rest over
        again. A lone request the queue refuses raises `Backpressure`. A
        message over `absolute_max_bytes` anywhere in the batch raises
        `MessageTooLarge` before any of it is ordered."""
        sized = [
            (request, len(request.wire_bytes()) if size is None else size,
             trace)
            for request, size, trace in items
        ]
        amax = self.policy.absolute_max_bytes
        for request, size, trace in sized:
            if 0 < amax < size:
                mx.counter("orderer.reject.too_large").inc()
                mx.flight("reject.too_large", trace=trace,
                          tx=request.anchor, bytes=size, max=amax)
                raise MessageTooLarge(
                    f"tx {request.anchor} is {size} bytes, over the "
                    f"channel's absolute_max_bytes {amax}; rejected "
                    "before ordering"
                )
        subs: List[Submission] = []
        qmax = self.policy.queue_max
        with self._mutex:
            depth = self._queued
            for request, size, trace in sized:
                if qmax > 0 and self._queued >= qmax:
                    break
                sub = Submission(self, request)
                sub.trace, sub.size = trace, size
                sub.enqueued_at = self._clock()
                sub.enqueued_unix = time.time()
                self._queued += 1
                self._order(sub, sub.enqueued_at)
                subs.append(sub)
            self._inflight += len(subs)
            mx.gauge("orderer.queue.depth").set(self._queued)
            mx.gauge("ledger.inflight").set(self._inflight)
        if not subs and len(sized) == 1:
            # admission control: rejected BEFORE ordering, so a retry is
            # always safe — nothing enqueued, nothing can commit
            request, _size, trace = sized[0]
            mx.counter("orderer.backpressure.rejects").inc()
            mx.flight("backpressure", trace=trace,
                      tx=request.anchor, depth=depth, max=qmax)
            raise Backpressure(
                f"ordering queue at capacity ({depth}/{qmax}); "
                f"tx {request.anchor} rejected before ordering — "
                "retry with backoff"
            )
        mx.counter("ledger.ordering.enqueued").inc(len(subs))
        for sub in subs:
            mx.flight("submit", trace=sub.trace, tx=sub.request.anchor)
        return subs

    def pending(self) -> int:
        """Messages ordered and not yet taken by a driver."""
        with self._mutex:
            return self._queued

    def inflight(self) -> int:
        """Submissions enqueued but not yet resolved (includes the block
        currently being committed, unlike `pending`)."""
        with self._mutex:
            return self._inflight

    def _mark_resolved(self) -> None:
        with self._mutex:
            self._inflight -= 1
            mx.gauge("ledger.inflight").set(self._inflight)

    # ------------------------------------------------------------ cutter
    # (all three under `_mutex`)

    def _timer_left(self, now: float) -> float:
        """Seconds until the open batch's timer runs out (<= 0: it has,
        or the policy has none)."""
        return self._pending[0].enqueued_at + self.policy.linger_s - now

    def _order(self, sub: Submission, now: float) -> None:
        """Fabric's `blockcutter.Ordered()` for one message arriving at
        `now`, after the chain's timer: rules 2-5 of the module
        docstring, in its order."""
        pol = self.policy
        if (pol.linger_s > 0 and self._pending
                and self._timer_left(now) <= 0):
            # the timer ran out before this message arrived: it cannot
            # join that batch, whenever a driver gets to look
            self._close("timeout", now)
        pref = pol.preferred_max_bytes
        if self._pending and 0 < pref < self._pending_bytes + sub.size:
            # rules 2 and 3: the message does not fit the open batch
            self._close("bytes", now)
        self._pending.append(sub)
        self._pending_bytes += sub.size
        if 0 < pref < sub.size:
            self._close("oversize", now)  # rule 2: alone, at once
        elif len(self._pending) >= max(1, pol.max_block_txs):
            self._close("count", now)

    def _close(self, reason: str, now: float) -> None:
        """Cut the open batch: it joins the blocks awaiting a driver."""
        batch, nbytes = self._pending, self._pending_bytes
        # first message -> cut; a timer's cut is dated when it ran out,
        # not when a thread came to look
        waited_s = (self.policy.linger_s if reason == "timeout"
                    else max(0.0, now - batch[0].enqueued_at))
        self._pending, self._pending_bytes = [], 0
        self._ready.append(batch)
        mx.counter("orderer.cut.blocks").inc()
        mx.counter("orderer.cut.bytes").inc(nbytes)
        if reason in _CUT_COUNTERS:
            mx.counter(_CUT_COUNTERS[reason]).inc()
        mx.histogram(
            "orderer.batch.wait.seconds", _BATCH_WAIT_BUCKETS
        ).observe(waited_s)
        mx.flight("block.cut", txs=len(batch), bytes=nbytes, reason=reason,
                  waited_s=round(waited_s, 6))
        self._cut_cond.notify_all()

    def _cut(self) -> List[Submission]:
        """The next block for a driver: the oldest cut batch, else the
        open one if the policy has no timer or its timer has run out,
        else nothing."""
        # fault point BEFORE the pop: an injected cut failure strands
        # nothing — every pending submission survives for the next drive
        faults.fire("orderer.cut")
        with self._mutex:
            if self._pending:
                now = self._clock()
                if self.policy.linger_s > 0:
                    if self._timer_left(now) <= 0:
                        self._close("timeout", now)
                elif not self._ready:
                    self._close("drain", now)
            if not self._ready:
                return []
            batch = self._ready.popleft()
            for sub in batch:
                sub._taken = True
            self._queued -= len(batch)
            mx.gauge("orderer.queue.depth").set(self._queued)
        return batch

    # ------------------------------------------------------------ drive

    def _pipelining(self) -> bool:
        """True when drives should route through the pipelined engine.
        The commit WORKER thread itself must never route back into the
        engine (a finality listener resubmitting from inside stage B
        would deadlock waiting on itself) — it drives inline instead."""
        return self._engine is not None and not self._engine.on_worker_thread()

    def _stage(self) -> tuple:
        """(the lock that serializes cut + hand-off, what runs a cut
        block under it): the engine's stage A — device verify of this
        cut overlaps the worker's commit of the previous block — or the
        whole sequential commit."""
        if self._pipelining():
            return self._engine.stage_lock, self._engine.submit
        return self._commit_lock, self._commit_block

    def flush(self, wait: bool = False) -> None:
        """Drive every block the cutter has until it has none left (and,
        in pipelined mode, every in-flight block has committed). Without
        a batch timer that empties the ordering queue; with one, an open
        batch whose timer still runs is not the caller's to cut: it is
        left to it, or with `wait` (a batch submitter making room in a
        bounded queue) slept out and then driven."""
        lock, run = self._stage()
        while True:
            with lock:
                batch = self._cut()
                if batch:
                    run(batch)
            if not batch and not (wait and self._await_cut()):
                break
        if self._pipelining():
            self._engine.drain()

    def _sleep_out_timer(self, limit: Optional[float]) -> None:
        """Under `_cut_cond`: wait for the open batch's timer (`limit`
        seconds at the most), woken early by a cut."""
        left = self._timer_left(self._clock())
        if left > 0:
            self._cut_cond.wait(left if limit is None else min(left, limit))

    def _await_cut(self) -> bool:
        """`flush(wait=True)` found nothing to cut: sleep out the open
        batch's timer. -> whether there may be a block to drive now."""
        with self._cut_cond:
            if not self._ready and self._pending:
                self._sleep_out_timer(None)
            return bool(self._ready or self._pending)

    def _park(self, sub: Submission, remaining: Optional[float]) -> None:
        """Wait for what `sub` waits for, never spinning on a lock. Still
        with the cutter: a cut block awaits a driver (return and drive:
        blocks go oldest first), or `sub` sits in the open batch (sleep
        out its timer). Taken by a driver, this one or another: its block
        is being verified or committed, and its own event says when: the
        blocks cut after it are their own waiters' to drive, so `sub` is
        answered at its commit and not a verification or two later."""
        with self._cut_cond:
            if not sub._taken:
                if not self._ready and self._pending:
                    self._sleep_out_timer(remaining)
                return
        sub._done.wait(remaining)

    def drive(self, sub: Submission, timeout: Optional[float] = None):
        """Commit blocks until the one that holds `sub` is taken, then
        wait for `sub` to resolve; returns its finality event.

        The timeout is honored even while another thread holds the commit
        lock mid-block (timed acquire), not just between commit attempts,
        and while `sub` waits in an open batch for its timer.
        """
        deadline = None if timeout is None else time.monotonic() + timeout

        def _remaining() -> Optional[float]:
            return None if deadline is None else deadline - time.monotonic()

        while not sub._done.is_set():
            if not sub._taken:
                lock, run = self._stage()
                remaining = _remaining()
                if remaining is None:
                    acquired = lock.acquire()
                else:
                    acquired = remaining > 0 and lock.acquire(timeout=remaining)
                if acquired:
                    try:
                        if sub._done.is_set():
                            break
                        batch = self._cut()
                        if batch:
                            run(batch)
                    finally:
                        lock.release()
            if not sub._done.is_set():
                self._park(sub, _remaining())
            if (not sub._done.is_set() and deadline is not None
                    and time.monotonic() > deadline):
                raise TimeoutError(
                    f"tx {sub.request.anchor} not ordered within {timeout}s"
                )
        return sub.event


@contextlib.contextmanager
def _plane_share(plane: str, timings: dict):
    """Put this block's share of one device plane's dispatch ledger
    (`utils/devobs.py`) into `timings`: `<plane>_frames_s` (inside
    dispatch frames: enqueue + read-back), `<plane>_wait_s` (of that,
    blocked on the device) and `<plane>_glue_s` (inside the plane's
    verify calls, outside any frame: Fiat-Shamir, limb encode/decode).
    Taken as a window delta of the plane's aggregate: stage A runs one
    block at a time. Zeros for a block kept on the host, or with the
    ledger off."""
    keys = ("stage_s", "wait_s", "glue_s")
    zero = dict.fromkeys(keys, 0.0)
    before = devobs.plane_snapshot().get(plane, zero)
    try:
        yield
    finally:
        after = devobs.plane_snapshot().get(plane, zero)
        stage_s, wait_s, glue_s = (after[k] - before[k] for k in keys)
        for name, v in (("frames_s", stage_s + wait_s), ("wait_s", wait_s),
                        ("glue_s", glue_s)):
            key = f"{plane}_{name}"
            timings[key] = timings.get(key, 0.0) + v


class BlockValidationPipeline:
    """The batched proof plane for one block.

    Phase 1 (plan): ask the driver for a batch plan per action record —
    a transfer's `(shape, (input_points, output_points, proof_bytes))`,
    an issue's row (`issue_batch_plan`), or None for host validation
    (fabtoken, malformed bytes, non-batchable kinds). The shape
    `(n_in, n_out)` is a transfer row's label, not a grouping key.

    Phase 2 (batched verify): the call is the block's, whatever its
    operations. If the block has at least `min_batch` planned records
    they go, in request order and whatever their shapes, through ONE
    `BatchedTransferVerifier` call (constant XLA program count
    regardless of shape/batch, and the block's fixed cost — a padded
    dispatch per stage call, one Miller walk, one final exponentiation —
    paid once: see `crypto/batch.py`). Verdicts come back per operation,
    keyed `{tx_index: {record_index: bool}}`.

    Phase 3 is the ledger's: sequential per-tx `RequestValidator.validate`
    with MVCC over the block view; records with a verdict skip (True) or
    fail (False) the host proof check, everything else verifies on host
    (who may issue is checked there for every issue).

    The SIGNATURE plane (`sign_verdicts`) is the same idea for the
    block's pk-kind signature obligations — owner/issuer/auditor Schnorr
    checks, collected across every tx and verified in ONE
    `BatchedSchnorrVerifier` call (no shape grouping needed: Schnorr
    rows are uniform). Non-pk identity kinds (nym, htlc) always stay
    host-verified; any device error degrades every row back to the host
    loop (`batch.sign.host_fallbacks`).

    The degrade chain is device -> host (here, `ledger.block.
    batch_errors`): accept/reject never depends on where a proof ran.

    Resilience (utils/resilience.py): each device dispatch runs under
    `bounded_call` with the plane's `FTS_DEVICE_DEADLINE_S` wall budget
    (a hung XLA call is abandoned at the deadline, its late result
    discarded, and the block falls to host), and each plane carries a
    circuit breaker — repeated failures/timeouts OPEN it so later
    blocks skip straight to host with no deadline paid, and a half-open
    probe after cooldown re-engages the device plane by itself.
    """

    def __init__(self, validator: RequestValidator, policy: BlockPolicy):
        self.validator = validator
        self.policy = policy
        # batched signature plane state: the verifier is built lazily on
        # first use (jax import); `sign_batched=None` (auto) resolves
        # once against the live backend. A construction failure records
        # into the `sign` circuit breaker (utils/resilience.py) — an
        # open breaker skips even obligation collection until its
        # cooldown expires and a half-open probe re-tries, so a
        # transient failure (one-off OOM) heals instead of disabling
        # device signatures for the process lifetime.
        self._sign_verifier = None
        self._sign_auto: Optional[bool] = None

    def proof_verdicts(
        self, requests: Sequence[TokenRequest],
        timings: Optional[dict] = None,
        host_verdicts: Optional[Dict[int, Dict[int, bool]]] = None,
        issue_verdicts: Optional[Dict[int, Dict[int, bool]]] = None,
    ) -> Dict[int, Dict[int, bool]]:
        """-> the device plane's verdicts on the block's transfer
        records, `{tx_index: {transfer_index: bool}}`.

        `issue_verdicts`, when passed as a dict, receives the same
        call's verdicts on the block's issue records, `{tx_index:
        {issue_index: bool}}`: the issues are planned beside the
        transfers and ride the block's one call. `None` (the default)
        leaves the issues to the host: nobody would read their verdicts.

        `timings`, when passed, is filled with the critical-path
        split of this call: `grouping_s` (planning the block's rows),
        `device_verify_s` (time inside the batched verify call,
        including a failed one that degraded to host) and `verify_calls`
        (completed plane calls of the block: 0 or 1).

        `host_verdicts`, when passed as a dict, receives True-only
        verdicts from the batch-first HOST pass over every transfer row
        the device plane left behind (`_host_proof_batch`). They are kept
        OUT of the returned device verdicts so the
        `ledger.validate.batched/host` accounting (and every fallback
        counter) still describes the device plane alone; the ledger
        merges the two maps only when handing verdicts to the per-tx
        validator. `None` (the default) skips the host pass — direct
        callers see the exact device-only behavior.

        `timings` also gains the block's share of the `verify` plane's
        dispatch ledger: `verify_frames_s`, `verify_wait_s`,
        `verify_glue_s` (`_plane_share`); the call is `fts:stageA.proof`
        in a profiler trace."""
        if timings is None:
            timings = {}
        with devobs.annotate("stageA.proof"), _plane_share("verify", timings):
            return self._proof_verdicts(
                requests, timings, host_verdicts, issue_verdicts
            )

    def _proof_verdicts(
        self, requests: Sequence[TokenRequest], timings: dict,
        host_verdicts: Optional[Dict[int, Dict[int, bool]]],
        issue_verdicts: Optional[Dict[int, Dict[int, bool]]],
    ) -> Dict[int, Dict[int, bool]]:
        timings.setdefault("grouping_s", 0.0)
        timings.setdefault("device_verify_s", 0.0)
        timings.setdefault("verify_calls", 0)
        if not self.policy.use_batched:
            return {}
        driver = self.validator.driver
        plan = getattr(driver, "transfer_batch_plan", None)
        if plan is None:
            return {}
        plan_issue = (
            None if issue_verdicts is None
            else getattr(driver, "issue_batch_plan", None)
        )
        t0 = time.monotonic()
        # the block's plannable records in request order (a request's
        # issues before its transfers, as the validator walks them), a
        # transfer labelled with its shape: they ride ONE plane call
        # whatever their shapes and operations
        rows: List[Tuple[int, str, int, tuple]] = []
        shapes = set()
        for ti, req in enumerate(requests):
            if plan_issue is not None:
                for ii, rec in enumerate(req.issues):
                    row = plan_issue(rec.action)
                    if row is not None:
                        rows.append((ti, "issue", ii, row))
            for ri, rec in enumerate(req.transfers):
                p = plan(rec.action)
                if p is None:
                    continue
                shape, row = p
                shapes.add(shape)
                rows.append((ti, "transfer", ri, row))
        timings["grouping_s"] = time.monotonic() - t0

        ok = self._device_proof_call(rows, len(shapes), timings)
        if ok is None:
            # rows the device plane leaves behind (a block under
            # `min_batch`, open breaker, failed/timed-out dispatch, no
            # device plane at all): the batch-first HOST pass still
            # verifies the transfers among them in one native multiexp +
            # one block-level Fiat-Shamir call before the per-tx scalar
            # loop sees them; an issue left behind is the scalar path's
            if host_verdicts is not None:
                self._host_proof_batch(
                    [(ti, ri, row) for ti, kind, ri, row in rows
                     if kind == "transfer"],
                    host_verdicts, timings,
                )
            return {}
        verdicts: Dict[int, Dict[int, bool]] = {}
        for (ti, kind, ri, _), good in zip(rows, ok):
            into = issue_verdicts if kind == "issue" else verdicts
            into.setdefault(ti, {})[ri] = bool(good)
        return verdicts

    def _device_proof_call(
        self, rows: List[Tuple[int, str, int, tuple]], n_shapes: int,
        timings: dict,
    ) -> Optional[Sequence[bool]]:
        """The block's one `BatchedTransferVerifier.verify` call over
        `rows`, bounded and behind the `verify` breaker. -> one verdict a
        row, or None where the rows are the host's: fewer than
        `min_batch`, an open breaker, no device plane, or a call that
        failed or timed out (a fallback, counted and logged)."""
        if len(rows) < max(1, self.policy.min_batch):
            return None
        n_issues = sum(1 for r in rows if r[1] == "issue")
        # what the span and the flight events say of the call: its
        # transfer rows under `txs`, as ever, its issue rows beside them
        held = dict(shapes=n_shapes, txs=len(rows) - n_issues, issues=n_issues)
        driver = self.validator.driver
        brk = resilience.breaker("verify")
        if not brk.allow():
            # open breaker: instant host fallback — no deadline paid,
            # no worker stacked onto a sick backend. The host plane
            # re-verifies these rows with verdicts unchanged.
            mx.flight("verify.host_fallback", reason="breaker_open", **held)
            return None
        try:
            verifier = driver.batch_verifier()
        except Exception:
            # construction failures (device stack unavailable, OOM
            # building tables) degrade to host validation, same as
            # verify failures — never fail a block
            brk.record_failure()
            mx.counter("ledger.block.batch_errors").inc()
            mx.flight("verify.host_fallback", reason="construct")
            return None
        if verifier is None:
            # the driver HAS no batched plane: neither success nor
            # failure — release the admission (else a half-open probe
            # would stay consumed forever)
            brk.cancel_probe()
            return None

        def _device_verify():
            # device-plane fault point: firing here (INSIDE the bounded
            # worker, so a `hang` kind is governed by the deadline)
            # exercises the degrade-to-host path below
            faults.fire("batch.verify")
            return verifier.verify([r[3] for r in rows])

        tg = time.monotonic()
        try:
            with mx.span("ledger.block.batch_verify", **held):
                ok = resilience.bounded_call(
                    _device_verify, resilience.device_deadline_s("verify"),
                    plane="verify",
                )
        except resilience.DeviceTimeout:
            # the dispatch outlived its wall budget: abandon it (the
            # straggler's late result is discarded by the supervisor)
            # and fall to host — the block must not stall
            brk.record_failure(timeout=True)
            mx.counter("ledger.block.batch_errors").inc()
            mx.flight("verify.host_fallback", reason="timeout", **held)
            return None
        except Exception:
            # the host plane re-verifies these rows; never fail a block
            # on a device-plane error
            brk.record_failure()
            mx.counter("ledger.block.batch_errors").inc()
            mx.flight("verify.host_fallback", **held)
            return None
        finally:
            timings["device_verify_s"] += time.monotonic() - tg
        brk.record_success()
        timings["verify_calls"] += 1
        mx.flight(
            "verify.device", ok=int(sum(1 for g in ok if g)), **held
        )
        return ok

    def _host_proof_batch(
        self, rows: List[Tuple[int, int, tuple]],
        verdicts: Dict[int, Dict[int, bool]], timings: dict,
    ) -> None:
        """Batch-first HOST pass over transfer rows the device plane left
        behind: the driver's `transfer_host_batch` hook recomputes every
        proof's commitments in one native multiexp call and derives all
        Fiat-Shamir challenges in one block-level sha256 batch
        (`hostmath.hash_to_zr_many`). True-only: a True verdict skips the
        per-tx scalar proof check; None/False rows (undecidable shapes,
        malformed bytes, failed proofs) fall through to the scalar path
        that owns the precise error. An exception here degrades to the
        scalar path wholesale — accept/reject can never depend on it."""
        timings.setdefault("host_proof_batch_s", 0.0)
        if not rows or not host_batch_enabled():
            return
        hook = getattr(self.validator.driver, "transfer_host_batch", None)
        if hook is None:
            return
        from .pipeline import host_map

        t0 = time.monotonic()
        try:
            try:
                oks = host_map(hook, [row for _, _, row in rows])
            except Exception:
                logger.exception(
                    "host proof batch failed; scalar path verifies"
                )
                return
            batched = 0
            for (ti, ri, _), good in zip(rows, oks):
                if good is True:
                    batched += 1
                    verdicts.setdefault(ti, {})[ri] = True
            if batched:
                mx.counter("hostbatch.proof.rows").inc(batched)
                mx.flight(
                    "verify.host_batch", rows=len(rows), verified=batched
                )
        finally:
            timings["host_proof_batch_s"] += time.monotonic() - t0

    # ------------------------------------------------------ signature plane

    def sign_enabled(self) -> bool:
        """Whether pk-kind signature obligations route to the batched
        device plane. `sign_batched=None` (auto) resolves ONCE against
        the live jax backend: device only on a real accelerator — and
        only if something else already imported jax (this resolver must
        never be the call that initializes a backend on the block-commit
        path; a fabtoken-only node may have no device stack at all)."""
        if self.policy.sign_batched is not None:
            return self.policy.sign_batched
        if self._sign_auto is None:
            import sys

            jax = sys.modules.get("jax")
            if jax is None:
                # NOT latched: jax may arrive later (e.g. the proof
                # plane's first zk block) and the answer would change
                return False
            try:
                self._sign_auto = jax.default_backend() != "cpu"
            except Exception:
                self._sign_auto = False
        return self._sign_auto

    def _collect_sign_obligations(self, requests: Sequence[TokenRequest]):
        """Walk a block's requests and split every signature obligation
        into batched rows (pk-kind identities from the shared identity
        cache) and a host count (non-pk kinds, unplannable records,
        empty/missing signatures — all verified by the host loop
        unchanged). Rows are `(pk_point, message, sig_raw)`; keys are
        `(tx_index, obligation_key, identity_bytes)`."""
        rows, keys, host = [], [], 0
        auditor = self.validator.auditor
        auditor_pk = identity.public_key(auditor) if auditor else None
        driver = self.validator.driver
        issue_plan = getattr(driver, "issue_sign_plan", None)
        transfer_plan = getattr(driver, "transfer_sign_plan", None)
        for ti, req in enumerate(requests):
            # the sign payload is marshalled lazily: a request with no
            # collectable pk obligation never pays the serialization
            # (the host validate pass re-marshals its own copy anyway)
            payload = None

            def _payload():
                nonlocal payload
                if payload is None:
                    payload = req.marshal_to_sign()
                return payload

            if auditor and req.auditor_signature:
                if auditor_pk is not None:
                    rows.append(
                        (auditor_pk.point, req.marshal_to_audit(),
                         req.auditor_signature)
                    )
                    keys.append((ti, SIG_AUDITOR, auditor))
                else:
                    host += 1
            for ii, rec in enumerate(req.issues):
                if not rec.signature or issue_plan is None:
                    continue  # no obligation / legacy driver: host decides
                ident = issue_plan(rec.action)
                if ident is None:
                    continue  # anonymous or unplannable: nothing to check
                pk = identity.public_key(ident)
                if pk is None:
                    host += 1
                    continue
                rows.append((pk.point, _payload(), rec.signature))
                keys.append((ti, ("issue", ii), ident))
            for ri, rec in enumerate(req.transfers):
                if transfer_plan is None:
                    continue
                owners = transfer_plan(rec.action)
                if owners is None or len(owners) != len(rec.signatures):
                    # unplannable / signature-count mismatch (the host
                    # check rejects the latter with its precise error)
                    host += len(rec.signatures)
                    continue
                for si, (ident, sig) in enumerate(zip(owners, rec.signatures)):
                    pk = identity.public_key(ident)
                    if pk is None:
                        host += 1  # nym/htlc/malformed: host-verified
                        continue
                    rows.append((pk.point, _payload(), sig))
                    keys.append((ti, ("transfer", ri, si), ident))
        return rows, keys, host

    def sign_verdicts(
        self, requests: Sequence[TokenRequest],
        timings: Optional[dict] = None,
    ) -> Dict[int, Dict[tuple, tuple]]:
        """One batched `BatchedSchnorrVerifier` pass over ALL pk-kind
        signature obligations of a block. Returns
        `{tx_index: {obligation_key: (identity_bytes, bool)}}` for
        `RequestValidator.validate(sig_verified=...)`. The degrade chain
        is the proof plane's: any device error, deadline timeout, or
        verifier construction failure drops every row to the host loop
        (`batch.sign.host_fallbacks`) and records into the `sign`
        circuit breaker — accept/reject can never depend on this plane,
        and an OPEN breaker skips even the obligation collection until
        a half-open probe heals it (replacing the old process-lifetime
        construction-failure latch). `timings` gains `sign_verify_s`
        (time inside the batched call, including failed ones) and the
        block's share of the `sign` plane's dispatch ledger:
        `sign_frames_s`, `sign_wait_s`, `sign_glue_s` (`_plane_share`);
        the call is `fts:stageA.sign` in a profiler trace."""
        if timings is None:
            timings = {}
        with devobs.annotate("stageA.sign"), _plane_share("sign", timings):
            return self._sign_verdicts(requests, timings)

    def _sign_verdicts(
        self, requests: Sequence[TokenRequest], timings: dict,
    ) -> Dict[int, Dict[tuple, tuple]]:
        timings.setdefault("sign_verify_s", 0.0)
        if not self.sign_enabled():
            # device plane off (CPU auto / forced host): the batch-first
            # HOST pass still folds every pk obligation of the block into
            # one native multiexp + one Fiat-Shamir sha256 batch
            return self._host_sign_batch(requests, timings)
        brk = resilience.breaker("sign")
        if brk.rejecting():
            # open breaker (cooldown running): skip even the collection —
            # later blocks must not pay per-block marshal/parse work
            # against a plane known sick; the half-open probe after
            # cooldown re-engages it off this fast path
            return {}
        rows, keys, host = self._collect_sign_obligations(requests)
        if host:
            mx.counter("batch.sign.host").inc(host)
        if not rows:
            return {}
        if len(rows) < max(1, self.policy.sign_min_batch):
            mx.counter("batch.sign.host").inc(len(rows))
            return {}
        if not brk.allow():
            # raced another thread's half-open probe: host-verify this
            # block rather than stacking a second dispatch on the probe
            mx.counter("batch.sign.host").inc(len(rows))
            mx.flight(
                "sign.host_fallback", rows=len(rows), reason="breaker_open"
            )
            return {}
        if self._sign_verifier is None:
            try:
                from ...crypto.batch_sign import BatchedSchnorrVerifier

                self._sign_verifier = BatchedSchnorrVerifier()
            except Exception:
                # one strike, like the latch this breaker replaced: a
                # construction failure is structural (import/OOM) and
                # per-block retries only re-pay marshal/import/log cost
                # — trip immediately; the half-open probe still heals a
                # transient one after cooldown
                brk.record_failure(trip_now=True)
                mx.counter("batch.sign.host_fallbacks").inc(len(rows))
                mx.flight("sign.host_fallback", reason="construct")
                logger.exception(
                    "sign plane: verifier construction failed; block "
                    "signatures host-verify (breaker heals via probe)"
                )
                return {}

        def _device_sign():
            # device-plane fault point: inside the bounded worker, so a
            # `hang` kind is governed by the deadline, never the block
            faults.fire("batch.sign")
            return self._sign_verifier.verify(rows)

        t0 = time.monotonic()
        try:
            with mx.span("ledger.block.batch_sign", rows=len(rows)):
                verdicts = resilience.bounded_call(
                    _device_sign, resilience.device_deadline_s("sign"),
                    plane="sign",
                )
        except resilience.DeviceTimeout:
            brk.record_failure(timeout=True)
            mx.counter("batch.sign.host_fallbacks").inc(len(rows))
            mx.flight("sign.host_fallback", rows=len(rows), reason="timeout")
            logger.warning(
                "sign plane: batched verify timed out; block signatures "
                "host-verify (worker abandoned, result discarded)"
            )
            return {}
        except Exception:
            brk.record_failure()
            mx.counter("batch.sign.host_fallbacks").inc(len(rows))
            mx.flight("sign.host_fallback", rows=len(rows))
            logger.exception(
                "sign plane: batched verify failed; block signatures "
                "host-verify"
            )
            return {}
        finally:
            timings["sign_verify_s"] += time.monotonic() - t0
        brk.record_success()
        out: Dict[int, Dict[tuple, tuple]] = {}
        device = 0
        for (ti, okey, ident), v in zip(keys, verdicts):
            if v is None:
                # the verifier could not parse this signature blob: the
                # host loop re-verifies and reports the precise error
                mx.counter("batch.sign.host").inc()
                continue
            device += 1
            out.setdefault(ti, {})[okey] = (ident, bool(v))
        mx.flight(
            "sign.device", rows=len(rows), device=device,
            ok=sum(1 for v in verdicts if v),
        )
        return out

    def _host_sign_batch(
        self, requests: Sequence[TokenRequest], timings: dict,
    ) -> Dict[int, Dict[tuple, tuple]]:
        """Batch-first HOST signature pass — the block's pk obligations
        verified via `crypto.sign.verify_many`: ONE native bn254 batch
        multiexp recomputes every Schnorr commitment and ONE block-level
        sha256 batch (`hostmath.hash_to_zr_many`) derives every
        Fiat-Shamir challenge, fanned over the commit-host worker pool
        (`FTS_COMMIT_WORKERS`). True-only verdicts: rows that fail or
        don't parse get NO verdict and fall to the per-obligation scalar
        loop, which owns the precise error message — accept/reject can
        never depend on this pass. Shares the device plane's obligation
        collector, so statement pinning (`identity_bytes` echoed with
        each verdict) is identical."""
        timings.setdefault("host_sign_batch_s", 0.0)
        if not host_batch_enabled():
            return {}
        t0 = time.monotonic()
        try:
            rows, keys, host = self._collect_sign_obligations(requests)
            if host:
                mx.counter("batch.sign.host").inc(host)
            if not rows:
                return {}
            try:
                from ...crypto import sign as sign_mod
                from .pipeline import host_map

                oks = host_map(sign_mod.verify_many, rows)
            except Exception:
                mx.counter("batch.sign.host").inc(len(rows))
                logger.exception(
                    "host sign batch failed; block signatures scalar-verify"
                )
                return {}
            out: Dict[int, Dict[tuple, tuple]] = {}
            batched = 0
            for (ti, okey, ident), v in zip(keys, oks):
                if v is not True:
                    # None (unparseable blob) or False (bad signature):
                    # the scalar loop re-verifies and reports precisely
                    mx.counter("batch.sign.host").inc()
                    continue
                batched += 1
                out.setdefault(ti, {})[okey] = (ident, True)
            if batched:
                mx.counter("hostbatch.sign.rows").inc(batched)
                mx.flight(
                    "sign.host_batch", rows=len(rows), verified=batched
                )
            return out
        finally:
            timings["host_sign_batch_s"] += time.monotonic() - t0

    # ------------------------------------------------------ conservation

    def conservation_verdicts(
        self, requests: Sequence[TokenRequest],
        timings: Optional[dict] = None,
    ) -> Dict[int, Dict[int, bool]]:
        """Block-level vectorized conservation/type checks: every
        transfer action's tokens decode into one flat column and the
        per-action verdicts fall out of segment sums
        (`driver.validate_conservation_many`). True-only, keyed
        `{tx_index: {record_index: True}}` for
        `RequestValidator.validate(conservation=...)` — an action with
        no verdict runs the full scalar arithmetic, so the pass can only
        make blocks faster, never change accept/reject."""
        if timings is None:
            timings = {}
        timings.setdefault("host_conservation_batch_s", 0.0)
        if not host_batch_enabled():
            return {}
        hook = getattr(
            self.validator.driver, "validate_conservation_many", None
        )
        if hook is None:
            return {}
        t0 = time.monotonic()
        try:
            actions, keys = [], []
            for ti, req in enumerate(requests):
                for ri, rec in enumerate(req.transfers):
                    actions.append(rec.action)
                    keys.append((ti, ri))
            if not actions:
                return {}
            try:
                oks = hook(actions)
            except Exception:
                logger.exception(
                    "conservation batch failed; scalar checks run per tx"
                )
                return {}
            out: Dict[int, Dict[int, bool]] = {}
            batched = 0
            for (ti, ri), good in zip(keys, oks):
                if good is True:
                    batched += 1
                    out.setdefault(ti, {})[ri] = True
            if batched:
                mx.counter("hostbatch.conservation.rows").inc(batched)
            return out
        finally:
            timings["host_conservation_batch_s"] += time.monotonic() - t0
