"""Process + accelerator memory telemetry for the live ops plane.

Post-mortem sidecars answer "where did the time go"; this module answers
"how much memory is this node holding" — live, cheaply, and without ever
perturbing the data plane:

* **Host**: current RSS from ``/proc/self/statm`` (peak from
  ``getrusage``), exported as the ``proc.rss.bytes`` /
  ``proc.rss.peak.bytes`` gauges.
* **Device**: per-device allocator stats via ``Device.memory_stats()``
  where the backend reports them (TPU/GPU), falling back to the summed
  byte size of live ``jax`` arrays (the CPU backend has no allocator
  report). Exported as ``device.mem.bytes`` / ``device.mem.peak.bytes``.
* Sampling never imports jax or initializes a backend itself — a
  fabtoken-only node may have no device stack at all, and a memory probe
  is not the place to acquire the chip. If jax is absent or no backend
  is initialized the device reading is simply ``None``.
* **Stage-runner high-water** (``sample_stages``): the batched
  verify/prove planes call this from ``ops/stages.run_rows`` after every
  tile dispatch; it is throttled to one real sample per
  ``FTS_MEM_SAMPLE_S`` (default 0.5s) and keeps the ``stages.mem.*``
  high-water gauges — the peak device/host footprint the data plane
  reached, which is what capacity planning needs from a bench round.

Zero XLA programs are ever compiled by sampling (reading live-array
sizes and allocator stats is pure bookkeeping), so the post-warmup
zero-cache-miss guarantee is untouched.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Optional

from . import metrics as mx

try:
    _PAGE = os.sysconf("SC_PAGE_SIZE")
except (AttributeError, ValueError, OSError):
    _PAGE = 4096


def host_rss_bytes() -> int:
    """Current resident set size in bytes (0 if unreadable)."""
    try:
        with open("/proc/self/statm") as fh:
            return int(fh.read().split()[1]) * _PAGE
    except (OSError, ValueError, IndexError):
        return host_rss_peak_bytes()  # non-/proc platforms: peak is all we have


def host_rss_peak_bytes() -> int:
    """Peak RSS in bytes (``ru_maxrss`` is KiB on Linux)."""
    try:
        import resource

        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:
        return 0


def device_memory_bytes() -> Optional[int]:
    """Device-resident bytes across every device of the initialized jax
    backend(s), or None when jax is absent / no backend is initialized.

    Never initializes a backend itself (see the module docstring).
    """
    if "jax" not in sys.modules:
        return None
    try:
        from jax._src import xla_bridge

        if not getattr(xla_bridge, "_backends", None):
            return None  # nothing initialized yet — do not trigger it
        import jax

        total, reported = 0, False
        for dev in jax.devices():
            try:
                stats = dev.memory_stats()
            except Exception:
                stats = None
            if stats and "bytes_in_use" in stats:
                total += int(stats["bytes_in_use"])
                reported = True
        if reported:
            return total
        # CPU (and any backend without an allocator report): the live
        # committed arrays are the device-resident set
        return sum(int(getattr(a, "nbytes", 0)) for a in jax.live_arrays())
    except Exception:
        return None


def sample() -> dict:
    """Take one memory sample and publish the process-wide gauges
    (`proc.rss.bytes`, `proc.rss.peak.bytes`, `device.mem.bytes`,
    `device.mem.peak.bytes`). Returns the raw readings."""
    rss = host_rss_bytes()
    peak = host_rss_peak_bytes()
    mx.gauge("proc.rss.bytes").set(rss)
    if peak:
        mx.gauge("proc.rss.peak.bytes").set(peak)
    out = {"rss_bytes": rss, "rss_peak_bytes": peak}
    dev = device_memory_bytes()
    out["device_bytes"] = dev
    if dev is not None:
        mx.gauge("device.mem.bytes").set(dev)
        g = mx.gauge("device.mem.peak.bytes")
        if dev > g.value:
            g.set(dev)
    return out


def _min_interval_s() -> float:
    try:
        return float(os.environ.get("FTS_MEM_SAMPLE_S", "0.5"))
    except ValueError:
        return 0.5


_lock = threading.Lock()
_last_stage_sample = 0.0


def sample_stages() -> Optional[dict]:
    """Throttled sampling hook for the stage-runner hot path: at most one
    real sample per `FTS_MEM_SAMPLE_S`, maintaining the `stages.mem.*`
    high-water gauges (peak device/host footprint of the batched
    verify/prove planes). Returns the sample, or None when throttled."""
    global _last_stage_sample
    now = time.monotonic()
    interval = _min_interval_s()
    with _lock:
        if now - _last_stage_sample < interval:
            return None
        _last_stage_sample = now
    s = sample()
    dev = s.get("device_bytes")
    if dev is not None:
        mx.gauge("stages.mem.device.bytes").set(dev)
        hw = mx.gauge("stages.mem.high_water.bytes")
        if dev > hw.value:
            hw.set(dev)
    rss_hw = mx.gauge("stages.mem.rss_high_water.bytes")
    if s["rss_bytes"] > rss_hw.value:
        rss_hw.set(s["rss_bytes"])
    return s
