"""The load generator: a process of its own, outside the server's.

It never imports `jax` or the package's `ops` (it says so in its result),
proves and signs nothing: it reads the pre-built, pre-signed requests the
corpus workers wrote, keeps a pool of `RemoteNetwork` connections, sends
each request when it is due on the plan's schedule, and times it from the
instant it was due to its finality, on its own monotonic clock. How late
it sent is part of every event.

Two hand-overs, chosen by the mix file:

  submit       one `submit` per request from a pool of connections (open
               loop); the reply is the finality event.
  submit_many  each client hands its share over in one `submit_many`
               (closed, saturating). The node answers such a call only
               when its whole queue has drained, so finality is read the
               way a recipient reads it: a watcher polls `height()` and,
               when a block has committed, asks `status()` of what is
               still pending.

Protocol with the parent: prints `READY` when the requests are loaded and
every connection is dialled, reads `GO <t_open>` (the parent's monotonic
clock, which Linux shares between processes), writes its events to the
job's `out` file and exits.
"""

from __future__ import annotations

import json
import os
import queue
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from corpus import read_group  # noqa: E402  (imports nothing of jax)
from fabric_token_sdk_tpu.services.network.remote import RemoteNetwork  # noqa: E402


class Generator:
    def __init__(self, job: dict):
        self.handover = job["handover"]
        self.address = tuple(job["address"])
        self.seconds = float(job["seconds"])
        self.grace_s = float(job["grace_s"])
        self.timeout_s = float(job["timeout_s"])
        self.entries = job["plan"]
        self.raw = {}
        for g in sorted({e["group"] for e in self.entries}):
            _meta, blobs = read_group(os.path.join(job["corpus_dir"], f"group-{g}.bin"))
            for e in self.entries:
                if e["group"] == g:
                    self.raw[e["i"]] = blobs[1 + e["slot"]]
        self.events = {
            e["i"]: {"i": e["i"], "tx_id": e["tx_id"], "due": e["due_s"],
                     "sent": None, "done": None, "status": None,
                     "message": None, "error": None}
            for e in self.entries}
        self.lock = threading.Lock()
        self.t_open = None
        self.drained_at = None

    def client(self) -> RemoteNetwork:
        # no retry: a refused or broken submit is a failed transaction
        c = RemoteNetwork(self.address, timeout=self.timeout_s, retries=0)
        c.height()  # dial now, not at the first due time
        return c

    def now(self) -> float:
        return time.monotonic() - self.t_open

    def _final(self, i: int, status, message, at: float) -> None:
        with self.lock:
            ev = self.events[i]
            if ev["done"] is None:
                ev.update(done=at, status=status, message=message)

    def _sleep_until(self, t: float) -> None:
        while True:
            d = t - self.now()
            if d <= 0:
                return
            time.sleep(min(d, 0.05))

    # ------------------------------------------------------ submit (open loop)

    def run_submit(self, clients: list) -> None:
        q: queue.Queue = queue.Queue()

        def worker(c: RemoteNetwork) -> None:
            while True:
                share = q.get()
                if share is None:
                    return
                at = self.now()
                for e in share:
                    self.events[e["i"]]["sent"] = at
                raws = [self.raw[e["i"]] for e in share]
                try:
                    # requests handed over jointly go in one call
                    fins = ([c.submit(raws[0])] if len(share) == 1
                            else c.submit_many(raws))
                    at = self.now()
                    for e, fin in zip(share, fins):
                        self._final(e["i"], fin.status.value, fin.message, at)
                except Exception as err:  # refused, transport, timeout: failed
                    for e in share:
                        self.events[e["i"]]["error"] = f"{type(err).__name__}: {err}"

        threads = [threading.Thread(target=worker, args=(c,), daemon=True)
                   for c in clients]
        for t in threads:
            t.start()
        shares, joint = [], {}
        for e in self.entries:
            if "joint" in e and e["joint"] in joint:
                joint[e["joint"]].append(e)
            else:
                shares.append([e])
                if "joint" in e:
                    joint[e["joint"]] = shares[-1]
        for share in shares:
            self._sleep_until(share[0]["due_s"])
            q.put(share)
        end = self.seconds + self.grace_s
        while self.now() < end and not self._all_final():
            time.sleep(0.02)
        for _ in threads:
            q.put(None)

    # ------------------------------------------------ submit_many (backlog)

    def run_submit_many(self, clients: list, watcher: RemoteNetwork,
                        poll_s: float) -> None:
        shares = {}
        for e in self.entries:
            shares.setdefault(e["client"], []).append(e)

        def hand_over(c: RemoteNetwork, share: list) -> None:
            self._sleep_until(share[0]["due_s"])
            at = self.now()
            for e in share:
                self.events[e["i"]]["sent"] = at
            try:
                fins = c.submit_many([self.raw[e["i"]] for e in share])
            except Exception as err:
                for e in share:
                    self.events[e["i"]]["error"] = f"{type(err).__name__}: {err}"
                return
            at = self.now()
            for e, fin in zip(share, fins):
                self._final(e["i"], fin.status.value, fin.message, at)

        for k, share in sorted(shares.items()):
            threading.Thread(target=hand_over, args=(clients[k], share),
                             daemon=True).start()
        height = watcher.height()
        end = self.seconds + self.grace_s
        while self.now() < end:
            time.sleep(poll_s)
            h = watcher.height()
            if h == height:
                continue
            height, at = h, self.now()
            if at >= end:
                # noticed after the close: still queued as far as the
                # window goes, not an attempt (and never a late answer)
                return
            for e in self.entries:
                if self.events[e["i"]]["done"] is None:
                    fin = watcher.status(e["tx_id"])
                    if fin is not None:
                        self._final(e["i"], fin.status.value, fin.message, at)
            if self._all_final():
                self.drained_at = at
                return

    def _all_final(self) -> bool:
        with self.lock:
            return all(ev["done"] is not None or ev["error"] is not None
                       for ev in self.events.values())

    # ------------------------------------------------------------------ main

    def run(self) -> dict:
        h = self.handover
        if h["call"] == "submit":
            clients = [self.client() for _ in range(int(h["pool"]))]
        else:
            clients = [self.client() for _ in range(int(h["clients"]))]
            watcher = self.client()
        print("READY", flush=True)
        word, t_open = sys.stdin.readline().split()
        if word != "GO":
            raise RuntimeError(f"expected GO, got {word!r}")
        self.t_open = float(t_open)
        if h["call"] == "submit":
            self.run_submit(clients)
        else:
            self.run_submit_many(clients, watcher, float(h["poll_s"]))
        with self.lock:
            events = [dict(ev) for ev in self.events.values()]
        return {
            "events": events,
            "drained_at": self.drained_at,
            "ended_at": self.now(),
            "jax_imported": "jax" in sys.modules,
            "ops_imported": any(m.startswith("fabric_token_sdk_tpu.ops")
                                for m in sys.modules),
        }


def main(argv) -> int:
    with open(argv[1]) as fh:
        job = json.load(fh)
    result = Generator(job).run()
    tmp = job["out"] + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(result, fh)
    os.replace(tmp, job["out"])
    # the hand-over threads of a backlog may still sit in their calls
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main(sys.argv)
