"""TPU compute path: limb-tensor bigint, prime fields, curves, pairing.

Design (TPU-first, not a port — reference delegates to gnark's x86-64
assembly; we target the VPU/MXU instead):

* Field elements are tensors of 32 radix-2^8 limbs in ``int32``
  (little-endian limb order), batched over leading axes. 8-bit limbs keep
  every partial product and column sum inside int32 — no 64-bit emulation —
  and map onto TPU-native integer lanes.
* Multiplication is Montgomery (REDC, R = 2^256) built from branch-free
  column convolutions; carries use signed arithmetic-shift passes under
  ``lax.while_loop``.
* Group ops are batched Jacobian formulas with select-based (branch-free)
  edge handling; variable-base scalar multiplication is a ``lax.scan``
  over four-bit digits against a per-row table built on the device.
* Hot multiexps use fixed-base window tables contracted with one-hot digit
  matrices — dense matmuls that ride the MXU.
"""

import os as _os

import jax as _jax


# ------------------------------------------------------- cache host keying
#
# XLA AOT cache entries bake in the compiling host's CPU features; loading
# an entry produced on a different machine triggers cpu_aot_loader
# machine-feature-mismatch warnings ("could lead to SIGILL") and, worse,
# can crash mid-kernel (the BENCH_r05 rc=124). The DEFAULT (in-checkout)
# cache dir is therefore HOST-KEYED: the first process writes a
# HOST_FINGERPRINT marker (platform + codegen-relevant CPU flags); any
# later process whose fingerprint differs — a checkout copied to another
# machine with its cache — is diverted to a per-host subdirectory, so
# foreign AOT entries are NEVER loaded. Diversions count the entries they
# skipped under `jax.cache.foreign_skipped`. Opt out:
# FTS_CACHE_FINGERPRINT=0. A directory named by JAX_COMPILATION_CACHE_DIR
# is used exactly as given — whoever placed it owns its contents.

_FINGERPRINT_MARKER = "HOST_FINGERPRINT"

# CPU-feature flags that change XLA:CPU codegen (vector ISA + carryless
# mul/AES used by some kernels); hypervisor/power-management flags are
# deliberately excluded so equivalent VMs of one fleet share a cache.
_CODEGEN_FLAG_PREFIXES = (
    "sse", "ssse", "avx", "fma", "bmi", "f16c", "aes", "pclmul",
    "popcnt", "movbe", "adx", "sha", "vaes", "gfni", "amx",
)


def host_fingerprint() -> str:
    """Stable fingerprint of this host's codegen-relevant CPU surface."""
    import hashlib
    import platform

    parts = [platform.machine(), platform.system()]
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.lower().startswith(("flags", "features")):
                    feats = sorted(
                        f for f in line.split(":", 1)[1].split()
                        if f.startswith(_CODEGEN_FLAG_PREFIXES)
                    )
                    parts.append(" ".join(feats))
                    break
    except OSError:  # non-Linux: machine/system only
        pass
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]


def _resolve_cache_dir(base: str, fingerprint: str) -> str:
    """Claim `base` for this host, or divert to a host-keyed subdir.

    * no marker: write one — this host owns the cache from now on;
    * marker matches: reuse the (warm) cache;
    * marker differs: the cache was populated on a FOREIGN host — count
      its entries under `jax.cache.foreign_skipped` and use
      `base/host-<fingerprint>` instead, so no mismatched AOT entry is
      ever handed to the loader.
    """
    from ..utils import metrics as _mx

    marker = _os.path.join(base, _FINGERPRINT_MARKER)
    try:
        _os.makedirs(base, exist_ok=True)
        try:
            # O_EXCL claim: exactly ONE host ever wins an unclaimed dir —
            # a lost race falls through to reading the winner's marker,
            # so two first-run hosts on a shared FS can never both write
            # AOT entries into the same dir
            fd = _os.open(marker, _os.O_WRONLY | _os.O_CREAT | _os.O_EXCL)
            with _os.fdopen(fd, "w") as fh:
                fh.write(fingerprint + "\n")
            return base
        except FileExistsError:
            pass
        with open(marker) as fh:
            recorded = fh.read().strip()
        if not recorded:
            # torn claim (a claimant died between O_EXCL create and
            # write): repair it, otherwise host-keying would be silently
            # disabled forever — the exact mixed-host hazard this guards
            with open(marker, "w") as fh:
                fh.write(fingerprint + "\n")
            return base
        if recorded != fingerprint:
            # count real AOT entries only (each program has a `-cache`
            # payload file; `-atime` companions and stray files would
            # double the number) — fall back to every file when the
            # naming convention is absent
            names = [
                n
                for n in _os.listdir(base)
                if n != _FINGERPRINT_MARKER
                and _os.path.isfile(_os.path.join(base, n))
            ]
            entries = [n for n in names if n.endswith("-cache")] or names
            _mx.REGISTRY.counter("jax.cache.foreign_skipped").inc(len(entries))
            _mx.REGISTRY.set_meta(
                "jax.cache.foreign_host", f"{recorded}!={fingerprint}"
            )
            sub = _os.path.join(base, f"host-{fingerprint}")
            _os.makedirs(sub, exist_ok=True)
            return sub
    except OSError:
        # unwritable/unreadable cache dir: let jax handle (or reject) it
        pass
    return base


# Persistent compilation cache: the pairing/Miller programs are large and
# XLA compiles them slowly; cache them across processes. The directory is
# placed from OUTSIDE: when JAX_COMPILATION_CACHE_DIR is set, jax has
# already read it into `jax_compilation_cache_dir` and nothing here
# touches it. Otherwise the cache lives at the fixed path
# `<checkout>/.jax_cache` (git-ignored), derived from this package's own
# location — never from the home directory, a temp name, a pid or the
# time, because a cache that moves between runs never hits.
_CHECKOUT = _os.path.dirname(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = _os.path.join(_CHECKOUT, ".jax_cache")


if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _cache_dir = DEFAULT_CACHE_DIR
    if _os.environ.get("FTS_CACHE_FINGERPRINT", "1") != "0":
        _cache_dir = _resolve_cache_dir(_cache_dir, host_fingerprint())
    _jax.config.update("jax_compilation_cache_dir", _cache_dir)
_jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)


# ---------------------------------------------------------- observability
#
# Compile/cache instrumentation: every XLA compile and persistent-cache
# hit/miss/load-failure lands in the metrics registry. This is the signal
# that diagnoses a silent rc=124 (unbounded recompiles after cache-load
# failures) in one read of the sidecar:
#   jax.core.compile.backend_compile_duration.seconds  — per-program wall
#     time histogram; its `count` IS the distinct-compiled-program count
#   jax.compilation_cache.cache_hits / cache_misses    — persistent cache
#   jax.cache.load_failures                            — AOT entries that
#     exist but refuse to load (e.g. cpu_aot_loader machine mismatch)


def _install_jax_monitoring() -> None:
    from ..utils import devobs as _devobs
    from ..utils import metrics as _mx

    def _event_name(raw: str) -> str:
        return "jax." + raw.strip("/").replace("/", ".").removeprefix("jax.")

    from jax import monitoring as _mon

    def _on_event(name, **kw):
        _mx.REGISTRY.counter(_event_name(name)).inc()
        # cache traffic is a lifecycle event: a run that suddenly
        # starts MISSING the persistent cache shows up in the flight
        # ring right next to the phase that triggered it
        if "compilation_cache" in name:
            ev = _event_name(name)
            _mx.flight("cache", event=ev)
            # listeners fire synchronously on the compiling thread,
            # so the dispatch ledger's active frame names the
            # program whose cache entry this was
            _devobs.note_cache(ev)

    def _on_duration(name, duration, **kw):
        # the histogram's own `count` is the event count — e.g. the
        # backend_compile histogram count IS the distinct-program count
        _mx.REGISTRY.histogram(_event_name(name) + ".seconds").observe(duration)
        if "backend_compile" in name:
            _mx.flight(
                "compile", seconds=round(duration, 3),
                program=_devobs.current_program(),
            )
            _devobs.note_compile(duration)

    _mon.register_event_listener(_on_event)
    _mon.register_event_duration_secs_listener(_on_duration)

    # Persistent-cache load failures surface as `warnings.warn(...)` from
    # jax._src.compiler (`Error reading persistent compilation cache
    # entry ...`) — chain-wrap showwarning to count them. The message
    # includes the module name, so the once-per-location warning filter
    # still counts each failing program once.
    import warnings as _warnings

    _prev_showwarning = _warnings.showwarning

    def _classify_cache_error(text: str):
        # reads and writes fail for different reasons (unloadable entry
        # vs. full/read-only dir) — misfiling one as the other sends the
        # rc=124 investigation the wrong way
        if "persistent compilation cache" not in text:
            return None
        return (
            "jax.cache.write_failures"
            if "Error writing" in text
            else "jax.cache.load_failures"
        )

    def _count_cache_error(text: str) -> None:
        name = _classify_cache_error(text)
        if name:
            _mx.REGISTRY.counter(name).inc()
            _mx.REGISTRY.set_meta(name.replace("failures", "last_failure"),
                                  text[:500])

    def _counting_showwarning(message, category, filename, lineno,
                              file=None, line=None):
        _count_cache_error(str(message))
        _prev_showwarning(message, category, filename, lineno, file, line)

    _warnings.showwarning = _counting_showwarning

    # ... and some jax versions route them through logging instead.
    import logging as _logging

    class _CacheFailureCounter(_logging.Handler):
        def emit(self, record):
            if record.levelno >= _logging.WARNING:
                # same read/write classification as the showwarning hook
                _count_cache_error(record.getMessage())

    _h = _CacheFailureCounter(level=_logging.WARNING)
    for _name in ("jax._src.compilation_cache", "jax._src.compiler"):
        _logging.getLogger(_name).addHandler(_h)


_install_jax_monitoring()

from . import limbs  # noqa: F401
from .field import FP, FR, FieldSpec  # noqa: F401
