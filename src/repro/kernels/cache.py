"""Persistent warm-start cache: compiled serve executables, keyed like tiles.

BinarEye keeps *everything* resident — weights in SRAM, instructions in
the 16-slot program memory — so a chip powers up serving-ready the
moment its image is loaded.  The TPU mapping's cold start is dominated
by something the chip never pays: tracing + XLA-compiling each resident
program's serve function.  For a single server that cost amortizes; for
a *fleet* it is the failover recovery path — a replacement replica's
cold-start-to-first-served-frame is exactly one trace+compile of every
resident program (tracked in the bench as
``fleet_failover_recovery_ms`` / ``replica_warm_start_speedup``).

This module makes that start warm, following the autotuner's
schema-versioned-key discipline (:mod:`repro.kernels.autotune`):

* **Process tier** — a keyed memo of built (jit'd) serve functions.
  Keys fingerprint the *computation*: program instruction words + S
  (``autotune.program_key``), the serve options that change the traced
  graph (megakernel / donation / interpret / composite member order),
  the mesh's device set, and the backend (platform + device kind + JAX
  version).  Two servers asking for the same key share one function —
  and therefore one set of compiled executables — so a replacement
  replica built after a host loss skips straight past trace+compile.
  The key schema carries a ``v1/`` prefix: when the serve-fn signature
  or kernel schedule changes shape, the version bumps and stale entries
  silently degrade to a cold build (never an error, never a wrong
  executable — a cache hit may only ever change *speed*).
* **Persistent tier** — JAX's own compilation cache: XLA executables
  are serialized per (computation fingerprint, device kind, compiler
  version) by JAX itself, so a replica in a *new process* also comes up
  hot.  :func:`enable_persistent` turns it on from the entry points
  (``chip_smoke.py``, ``chip_serve``, the kernel microbench), never at
  import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and
  no directory is set in code; otherwise the cache lives at one fixed,
  gitignored path inside the checkout (:data:`DEFAULT_DIR`) — the path
  is part of the cache key, so it never moves between runs.

The in-process ledger (:func:`stats`) records hits/misses and the
seconds spent building on misses — the bench derives its warm-start
speedup from wall-clock around real server bring-up, but the ledger is
what tests pin.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax

from repro.core.chip import isa
from repro.kernels import autotune

SCHEMA = 1          # bump when serve-fn signatures / kernel schedule change
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"     # JAX's own variable
# the checkout root is three levels above this package file
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_compilation_cache"))

_fns: Dict[str, Any] = {}
_stats = {"hits": 0, "misses": 0, "build_s": 0.0}
_persistent_dir: Optional[str] = None


def backend_fingerprint() -> str:
    """The machine class + compiler an executable is valid for: the
    autotuner's platform/device-kind/host-ISA triple plus the JAX
    version (a jaxlib upgrade invalidates serialized executables)."""
    return f"{autotune.backend_fingerprint()}:jax{jax.__version__}"


def serve_fn_key(programs: Iterable[isa.Program], *,
                 mesh=None, megakernel: bool = False,
                 donate_frames: bool = False,
                 interpret: Optional[bool] = None,
                 kind: str = "serve") -> str:
    """Cache key for a (composite) serve function.

    ``programs`` is the ordered member tuple — one program for a solo
    serve fn, the composite's member order for a shared-array fn (order
    is part of the traced graph, exactly like ``autotune.composite_key``).
    The mesh contributes its device ids: a function traced through
    ``shard_map`` closes over its mesh, so sub-meshes of different
    simulated hosts must never share an entry.
    """
    programs = tuple(programs)
    pkey = (autotune.program_key(programs[0]) if len(programs) == 1
            else autotune.composite_key(programs))
    devs = ("nodev" if mesh is None else
            "d" + "-".join(str(getattr(d, "id", d)) for d in
                           mesh.devices.flatten()))
    opts = f"mk{int(megakernel)}.dn{int(donate_frames)}.it{interpret}"
    return (f"v{SCHEMA}/{kind}/{pkey}/{devs}/{opts}/"
            f"{backend_fingerprint()}")


def lookup_fn(key: str) -> Optional[Any]:
    """Process-tier hit (None = cold).  Ledger counts the outcome."""
    fn = _fns.get(key)
    if fn is None:
        _stats["misses"] += 1
    else:
        _stats["hits"] += 1
    return fn


def record_fn(key: str, fn: Any, build_s: float = 0.0) -> Any:
    _fns[key] = fn
    _stats["build_s"] += build_s
    return fn


def get_or_build(key: str, build: Callable[[], Any]) -> Any:
    """The one-call form: hit returns the cached fn, miss runs ``build``
    (timed into the ledger) and records the result."""
    fn = lookup_fn(key)
    if fn is None:
        t0 = time.perf_counter()
        fn = build()
        record_fn(key, fn, time.perf_counter() - t0)
    return fn


def stats() -> Dict[str, Any]:
    """Ledger snapshot: process-tier hits/misses, seconds spent building
    on misses, entry count, and the persistent dir (None = disabled)."""
    return dict(_stats, entries=len(_fns), persistent_dir=_persistent_dir)


def invalidate() -> None:
    """Drop the process tier and zero the ledger (tests / cold-start
    measurement).  The persistent tier is untouched — on-disk executables
    stay valid; only the in-process memo goes cold."""
    global _fns
    _fns = {}
    _stats.update(hits=0, misses=0, build_s=0.0)


def cache_dir() -> str:
    """Where the persistent tier lives: ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else the fixed in-checkout :data:`DEFAULT_DIR`."""
    return os.environ.get(CACHE_ENV) or DEFAULT_DIR


def persistent_dir() -> Optional[str]:
    return _persistent_dir


def enable_persistent() -> str:
    """Turn JAX's persistent compilation cache on for this process and
    return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and no
    directory is set here; otherwise the cache goes to
    :data:`DEFAULT_DIR`.  The min-compile-time/entry-size floors drop to
    zero so every serve function is cached.  Any failure raises: a run
    that asked for the cache never silently runs without it.
    """
    global _persistent_dir
    if not os.environ.get(CACHE_ENV):
        os.makedirs(DEFAULT_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _persistent_dir = cache_dir()
    return _persistent_dir
