"""Persistent tile autotuner: measure-and-cache kernel tile sizes.

The chip has one fixed datapath; the TPU mapping has schedule knobs —
the megakernel's frame-tile ``bb`` and conv f-tile ``ft``, the staged conv
kernel's neuron/frame tiles ``bf``/``bb`` — whose best values depend on
the (program, backend, batch) triple (VMEM headroom vs per-step overhead
trade exactly like ChewBaccaNN's tiling/scheduling match between network
shape and datapath).  This module owns that choice:

* ``tune_mega`` / ``tune_staged_conv`` measure a small candidate grid on
  the live backend and record the winner.
* The cache is a flat JSON file (default ``BENCH_autotune.json`` in the
  CWD, override with ``REPRO_AUTOTUNE_CACHE``) keyed by
  ``kind/program-fingerprint/batch/backend-fingerprint``.  The program
  fingerprint hashes the *assembled instruction words* plus S — two
  programs with identical SRAM geometry share an entry; the backend
  fingerprint pins platform + device kind + host ISA, so a cache tuned on
  one machine class never silently mis-tunes another.
* ``mega_tiles`` / ``composite_tiles`` / ``conv_tiles`` are the read
  side, consulted by ``InferencePlan.forward``/``forward_mega`` and
  ``CompositePlan.forward`` at trace time: explicit arguments win, then
  an exact cache hit, then the nearest-batch entry for the same
  program+backend, and a cold cache falls back to the historical
  defaults — tuning is always a pure perf choice, never a numeric one.
* Entry keys carry a schema version prefix (``v2/...``): when the tuned
  fields or the kernel schedule they describe change shape (e.g. v2
  added per-member-group composite f-tiles and the member-DMA/compute
  overlap), the version bumps and every stale entry silently degrades
  to the cold-cache defaults instead of mis-steering the new kernel —
  a stale ``BENCH_autotune.json`` is never an error, just cold.

The committed ``BENCH_autotune.json`` holds CPU entries only, so a run
on the chip finds no entry for its backend and takes the cold defaults.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Dict, Iterable, Optional

import jax

from repro.core.chip import isa

DEFAULT_CACHE = "BENCH_autotune.json"
CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
SCHEMA = 2          # bump when tuned fields / kernel schedule change shape

# the cold-cache defaults (staged bf=256: one F tile at every S-mode)
DEFAULTS = {
    "mega": {"bb": 8, "ft": 0},
    "staged_conv": {"bf": 256, "bb": 8},
}

_cache: Optional[Dict[str, dict]] = None
_cache_file: Optional[str] = None


def cache_path() -> str:
    return os.environ.get(CACHE_ENV, DEFAULT_CACHE)


def backend_fingerprint() -> str:
    """Platform + device kind + host ISA: the machine class a measurement
    is valid for (mirrors the bench baseline's ``host`` fingerprint)."""
    import platform
    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", "unknown").replace(" ", "_")
    return f"{jax.default_backend()}:{kind}:{platform.machine()}"


def program_key(program: isa.Program) -> str:
    """Fingerprint of the assembled program words + S (the SRAM geometry:
    identical instruction streams tune identically)."""
    words = isa.assemble(program)
    return hashlib.sha1(words.tobytes()
                        + bytes([program.s])).hexdigest()[:12]


def composite_key(programs: Iterable[isa.Program]) -> str:
    """Order-sensitive fingerprint of a composite's member programs."""
    joined = "+".join(program_key(p) for p in programs)
    return "comp-" + hashlib.sha1(joined.encode()).hexdigest()[:12]


def _entry_key(kind: str, pkey: str, batch: int) -> str:
    # the vN prefix versions the schema: entries written for an older
    # kernel schedule never match and degrade gracefully to defaults
    return f"v{SCHEMA}/{kind}/{pkey}/b{int(batch)}/{backend_fingerprint()}"


def _load() -> Dict[str, dict]:
    global _cache, _cache_file
    path = cache_path()
    if _cache is None or _cache_file != path:
        try:
            with open(path) as f:
                _cache = json.load(f)
        except (FileNotFoundError, json.JSONDecodeError, OSError):
            _cache = {}
        if not isinstance(_cache, dict):
            # valid JSON but not a cache (e.g. a truncated/foreign file):
            # degrade to cold — the cache may only ever change perf
            _cache = {}
        _cache_file = path
    return _cache


def invalidate() -> None:
    """Drop the in-process cache (tests / after an external refresh)."""
    global _cache, _cache_file
    _cache, _cache_file = None, None


def lookup(kind: str, pkey: str, batch: int) -> Optional[dict]:
    """Exact (kind, program, batch, backend) entry, else the same
    program+backend's nearest-batch entry, else None (cold)."""
    cache = _load()
    hit = cache.get(_entry_key(kind, pkey, batch))
    if hit is not None:
        return hit
    prefix = f"v{SCHEMA}/{kind}/{pkey}/b"
    suffix = f"/{backend_fingerprint()}"
    nearest = None
    for key, entry in cache.items():
        if not (key.startswith(prefix) and key.endswith(suffix)):
            continue
        try:
            b = int(key[len(prefix):len(key) - len(suffix)])
        except ValueError:
            continue
        d = abs(b - batch)
        if nearest is None or d < nearest[0]:
            nearest = (d, entry)
    return nearest[1] if nearest else None


def record(kind: str, pkey: str, batch: int, entry: dict) -> dict:
    """Persist a tuned entry (merged into the JSON cache file)."""
    cache = _load()
    cache[_entry_key(kind, pkey, batch)] = dict(entry)
    path = cache_path()
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(cache, f, indent=2, sort_keys=True)
    os.replace(tmp, path)
    return cache[_entry_key(kind, pkey, batch)]


# ---------------------------------------------------------------------------
# Read side: tile resolution (explicit args > cache > defaults)
# ---------------------------------------------------------------------------

def _resolve(kind: str, pkey: str, batch: int, **overrides):
    """Shared resolution: each ``None`` override falls through to the
    cache entry, then to ``DEFAULTS[kind]``; explicit values always win.
    Field names come from DEFAULTS[kind] (insertion order)."""
    defaults = DEFAULTS[kind]
    entry = (lookup(kind, pkey, batch) or {}
             if any(v is None for v in overrides.values()) else {})
    return tuple(int(entry.get(f, defaults[f])) if overrides[f] is None
                 else overrides[f] for f in defaults)


def mega_tiles(program: isa.Program, batch: int,
               bb: Optional[int] = None, ft: Optional[int] = None):
    """(bb, ft) for the solo megakernel on ``program`` at ``batch``."""
    return _resolve("mega", program_key(program), batch, bb=bb, ft=ft)


def composite_tiles(programs: Iterable[isa.Program], batch: int,
                    bb: Optional[int] = None, ft=None, *,
                    per_group: bool = False, n_groups: Optional[int] = None):
    """(bb, ft) for a composite dispatch of ``programs`` at ``batch``.

    Default resolution returns the composite's single tuned ``ft``.
    With ``per_group=True`` (and ``n_groups``, the member-group count of
    the composite's spec) a tuned per-group entry (``ftg``) resolves to
    a tuple with one f-tile per group; entries whose group count doesn't
    match (or predate per-group tuning) fall back to the global ``ft``.
    Explicit arguments always win, in either form.
    """
    if ft is not None:
        return (_resolve("mega", composite_key(programs), batch,
                         bb=bb, ft=0)[0], ft)
    pkey = composite_key(programs)
    bb_r, ft_r = _resolve("mega", pkey, batch, bb=bb, ft=ft)
    if per_group:
        entry = lookup("mega", pkey, batch) or {}
        ftg = entry.get("ftg")
        if isinstance(ftg, (list, tuple)) and (
                n_groups is None or len(ftg) == n_groups):
            return bb_r, tuple(int(f) for f in ftg)
    return bb_r, ft_r


def conv_tiles(program: isa.Program, batch: int,
               bf: Optional[int] = None, bb: Optional[int] = None):
    """(bf, bb) for the staged fused conv kernel."""
    return _resolve("staged_conv", program_key(program), batch, bf=bf, bb=bb)


# ---------------------------------------------------------------------------
# Write side: measure-and-cache tuners
# ---------------------------------------------------------------------------

def _time_us(fn, *args, iters: int = 3) -> float:
    """Best-of-iters wall time (us); min is the least noisy estimator on a
    shared host (contention only ever adds time)."""
    jax.block_until_ready(fn(*args))              # compile + warm
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _ft_candidates(f: int, candidates) -> list:
    """Valid f-tile sizes for an F-wide conv stack (0 = untiled), rounded
    the same way the kernel rounds them (whole packed words) so cached
    winners match the measured configurations exactly."""
    from repro.core.binarize import PACK_WIDTH
    out = {0}
    for ft in candidates:
        if ft and ft < f:
            out.add(max(PACK_WIDTH, ft // PACK_WIDTH * PACK_WIDTH))
    return sorted(out)


def tune_mega(plan, image, frames, *, bb_candidates=(2, 4, 8, 16, 32),
              ft_candidates=(0, 16, 32, 64, 128, 256), iters: int = 3,
              interpret: Optional[bool] = None) -> dict:
    """Measure the megakernel candidate grid for ``plan`` on this backend
    and cache the winner under (program, backend, batch).  Returns the
    recorded entry ({"bb", "ft", "us"})."""
    program = plan.program
    batch = frames.shape[0]
    f = isa.ARRAY_CHANNELS // program.s
    best = None
    for bb in sorted({min(b, batch) for b in bb_candidates}):
        for ft in _ft_candidates(f, ft_candidates):
            def fwd(image, frames, _bb=bb, _ft=ft):
                return plan.forward_mega(image, frames, interpret=interpret,
                                         bb=_bb, ft=_ft)
            us = _time_us(jax.jit(fwd), image, frames, iters=iters)
            if best is None or us < best[0]:
                best = (us, bb, ft)
    entry = {"bb": best[1], "ft": best[2], "us": round(best[0], 1)}
    return record("mega", program_key(program), batch, entry)


def tune_composite(cplan, image, frames, *, bb_candidates=(2, 4, 8, 16, 32),
                   ft_candidates=(0, 16, 32, 64, 128), iters: int = 3,
                   per_group: bool = True,
                   interpret: Optional[bool] = None) -> dict:
    """Tune a composite's (bb, ft) and cache under the composite
    fingerprint.

    Phase 1 sweeps one global (bb, ft) grid exactly like ``tune_mega``.
    Phase 2 (``per_group=True``, the default) refines each member
    *group's* f-tile independently around the phase-1 winner — groups of
    different sub-array widths (a 2xS2 group next to two S=4 singletons,
    say) rarely share a best ``ft``.  The entry records both: ``ft`` is
    the global winner (what pre-per-group readers resolve), ``ftg`` the
    per-group tuple (what ``CompositePlan.forward`` resolves).
    """
    from repro.kernels.megakernel import member_groups

    frames = tuple(frames)
    batch = max(f.shape[0] for f in frames)
    fmin = min(isa.ARRAY_CHANNELS // p.s for p in cplan.programs)
    groups = member_groups(cplan.spec)

    def timed(bb, ft):
        def fwd(image, frames, _bb=bb, _ft=ft):
            return cplan.forward(image, frames, interpret=interpret,
                                 bb=_bb, ft=_ft)
        return _time_us(jax.jit(fwd), image, frames, iters=iters)

    best = None
    for bb in sorted({min(b, batch) for b in bb_candidates}):
        for ft in _ft_candidates(fmin, ft_candidates):
            us = timed(bb, ft)
            if best is None or us < best[0]:
                best = (us, bb, ft)
    best_us, bb, ft = best

    ftg = [ft] * len(groups)
    if per_group and len(groups) > 1:
        for gi, group in enumerate(groups):
            # this group's conv width bounds its valid f-tiles
            convs = [st[4] for st in cplan.spec[group[0]]
                     if st[0] == "conv"]
            fg = min(convs) if convs else 0
            for cand in _ft_candidates(fg, ft_candidates) if fg else [0]:
                if cand == ftg[gi]:
                    continue
                trial = tuple(ftg[:gi] + [cand] + ftg[gi + 1:])
                us = timed(bb, trial)
                if us < best_us:
                    best_us, ftg[gi] = us, cand
    entry = {"bb": bb, "ft": ft, "ftg": list(ftg),
             "us": round(best_us, 1)}
    return record("mega", composite_key(cplan.programs), batch, entry)


def tune_staged_conv(plan, packed, frames, *,
                     bf_candidates=(16, 32, 64, 128, 256),
                     bb_candidates=(2, 4, 8, 16, 32), iters: int = 3,
                     interpret: Optional[bool] = None) -> dict:
    """Tune the staged pipeline's fused-conv (bf, bb) tiles for ``plan``
    and cache under (program, backend, batch)."""
    program = plan.program
    batch = frames.shape[0]
    f = isa.ARRAY_CHANNELS // program.s
    best = None
    for bf in sorted({min(c, f) for c in bf_candidates}):
        for bb in sorted({min(c, batch) for c in bb_candidates}):
            def fwd(packed, frames, _bf=bf, _bb=bb):
                return plan.forward(packed, frames, interpret=interpret,
                                    conv_tiles=(_bf, _bb))
            us = _time_us(jax.jit(fwd), packed, frames, iters=iters)
            if best is None or us < best[0]:
                best = (us, bf, bb)
    entry = {"bf": best[1], "bb": best[2], "us": round(best[0], 1)}
    return record("staged_conv", program_key(program), batch, entry)
