"""The plain reference of the block cutter: Fabric's
`orderer/common/blockcutter` `Ordered()` and the consenter's batch timer,
transcribed as a pure function (no clock, no threads, no import of the
package under test).

    cut(sizes, arrival_times, batch_timeout, max_message_count,
        preferred_max_bytes, absolute_max_bytes)
        -> [(indices, reason, cut_time), ...]

`sizes[i]` is message i's length in bytes and `arrival_times[i]` the time
it reaches the orderer (seconds, non-decreasing, any origin). Each block is
the tuple of its messages' indices in order, why it was cut, and when. A
message that is refused appears in no block.

The rules, as Fabric applies them to every message in arrival order
(`blockcutter.go:Ordered`, `solo/consensus.go:main` and
`etcdraft/chain.go:run` for the timer; recalled, no network in this
sandbox):

1. size > AbsoluteMaxBytes: the broadcast handler's size filter refuses
   the message before ordering (`msgprocessor/sizefilter.go`).
2. size > PreferredMaxBytes: cut the pending batch if it is not empty,
   then cut the message alone as its own batch.
3. else, if pending bytes + size > PreferredMaxBytes: cut the pending
   batch first; the message opens the next one.
4. append the message; if the pending batch now holds MaxMessageCount
   messages, cut it.
5. the timer: when `Ordered()` leaves a batch pending and no timer runs,
   one is started (`BatchTimeout`); a cut stops it; when it fires, whatever
   is pending is cut. So a batch's timer runs from the arrival of its
   first message, and a message that arrives at or after `first +
   BatchTimeout` finds that batch already cut.

Reasons: "count" (rule 4), "bytes" (the pending batch that rule 2 or 3
displaced), "oversize" (rule 2's lone message), "timeout" (rule 5).

Departures from Fabric, each because the system under test is one node:
no Raft among orderers (a cut batch is a block at once; nothing is
proposed or re-ordered on a leader change); no configuration messages and
so no `isolated` batches (`ProcessConfigMsg` cuts the pending batch and
orders a config transaction alone); `size` is the length the caller gives
(the token request's wire bytes, not a marshalled `cb.Envelope`); a byte
rule given as 0 is off (Fabric's channel config always sets both); a
timeout's `cut_time` is the instant the timer fires, where a real orderer
notices it a scheduling delay later.
"""

from __future__ import annotations


def cut(sizes, arrival_times, batch_timeout, max_message_count,
        preferred_max_bytes=0, absolute_max_bytes=0) -> list:
    if len(sizes) != len(arrival_times):
        raise ValueError("one arrival time per message")
    if batch_timeout <= 0 or max_message_count < 1:
        raise ValueError("BatchTimeout > 0 and MaxMessageCount >= 1")
    if any(b < a for a, b in zip(arrival_times, arrival_times[1:])):
        raise ValueError("arrival times must not decrease")
    blocks = []
    pending, pending_bytes, timer_at = [], 0, None

    def close(reason, at):
        nonlocal pending, pending_bytes, timer_at
        blocks.append((tuple(pending), reason, at))
        pending, pending_bytes, timer_at = [], 0, None

    for i, (size, t) in enumerate(zip(sizes, arrival_times)):
        if pending and t >= timer_at:
            close("timeout", timer_at)  # rule 5: fired before this arrival
        if 0 < absolute_max_bytes < size:
            continue  # rule 1: refused, never ordered
        if 0 < preferred_max_bytes < size:  # rule 2
            if pending:
                close("bytes", t)
            pending, pending_bytes = [i], size
            close("oversize", t)
            continue
        if pending and 0 < preferred_max_bytes < pending_bytes + size:
            close("bytes", t)  # rule 3
        if not pending:
            timer_at = t + batch_timeout  # rule 5: the timer starts
        pending.append(i)
        pending_bytes += size
        if len(pending) >= max_message_count:
            close("count", t)  # rule 4
    if pending:
        close("timeout", timer_at)
    return blocks
