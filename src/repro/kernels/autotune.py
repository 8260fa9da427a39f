"""First-call tile-size autotuner (v2) with a bucketed, source-keyed cache.

For each (kernel, backend, shape-*bucket*) the tuner times every candidate
in the spec's small tile grid on synthesized inputs and records the winner:

* in-process  — a dict, so a jitted trace asks at most once per bucket;
* on disk     — JSON at ``$REPRO_TUNE_CACHE`` (default
  ``<checkout>/.kernel_tune/kernel_tune.json``), so winners survive across
  runs and can be shipped with a deployment.

v2 cache semantics:

* **Shape buckets.** Dimensions ≤ 128 key exactly; larger dimensions round
  up to the next power of two. N = 49k and N = 50k land in the same bucket
  (65536) and share one sweep — tile winners are a function of tiling
  regime, not of the exact row count, and per-exact-shape entries made the
  cache grow without bound on ragged workloads. Sweeps run at the bucketed
  shape (``pad_minor`` in every kernel makes any shape legal).
* **Source-hash invalidation.** Each entry records a hash of the kernel
  package's ``.py`` sources; entries whose hash no longer matches are
  ignored at load (a kernel edit re-tunes instead of serving stale tiles).
* **Versioned envelope** ``{"version": 2, "entries": {...}}``. Corrupt,
  truncated or legacy-v1 files are ignored wholesale and rewritten on the
  next store; stores are read-modify-write with an atomic replace, so two
  racing processes each leave a valid file (last writer wins).

The sweep times candidates on freshly synthesized concrete inputs (from
``spec.make_inputs``), so it runs only eagerly — e.g. from
``benchmarks/kernel_micro.py --autotune``. Under a ``jit`` trace every
operation is staged, so a sweep there would time the tracer, not the
kernel: :func:`tiles_for` then serves the cached winner or the spec's
default tiles and never sweeps. A sweep in which every candidate fails
raises the first candidate's error. ``sweep(..., report=True)``
additionally returns every candidate's wall time for the
achieved-vs-roofline report in ``benchmarks/kernel_micro.py``.

Enablement policy (``REPRO_AUTOTUNE``): "1" forces tuning on, "0" forces it
off; unset ⇒ tune only when the Pallas path actually compiles (i.e. not in
interpret mode) — interpret-mode wall-times say nothing about Mosaic, so
CPU CI silently falls back to the spec's per-backend default tiles.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import sys
import tempfile
import time
from typing import Mapping, Optional

import jax

from repro.kernels.registry import KernelSpec, ShapeSig, backend, interpret_default
from repro.runtime import CHECKOUT

CACHE_VERSION = 2

_memory_cache: dict[str, dict] = {}
_disk_loaded_from: Optional[str] = None

_SWEEP_REPS = 3  # timed reps per candidate (after one compile/warmup call)


def cache_path() -> str:
    return os.environ.get(
        "REPRO_TUNE_CACHE", os.path.join(CHECKOUT, ".kernel_tune", "kernel_tune.json")
    )


# ---------------------------------------------------------------------------
# Cache keys: shape buckets + kernel-source hash
# ---------------------------------------------------------------------------


def bucket_dim(n: int) -> int:
    """≤ 128 exact; above, the next power of two (49k and 50k → 65536)."""
    n = int(n)
    if n <= 128:
        return n
    p = 128
    while p < n:
        p *= 2
    return p


def bucket_sig(sig: ShapeSig) -> ShapeSig:
    """Bucket every dimension of every argument (dtypes key exactly)."""
    return tuple((tuple(bucket_dim(d) for d in shape), dt) for shape, dt in sig)


def cache_key(name: str, back: str, sig: ShapeSig) -> str:
    return f"{name}|{back}|{bucket_sig(sig)!r}"


@functools.lru_cache(maxsize=None)
def _dir_source_hash(pkg_dir: str) -> str:
    h = hashlib.sha256()
    try:
        for fn in sorted(os.listdir(pkg_dir)):
            if fn.endswith(".py"):
                with open(os.path.join(pkg_dir, fn), "rb") as f:
                    h.update(fn.encode())
                    h.update(f.read())
    except OSError:
        return "unknown"
    return h.hexdigest()[:16]


def source_hash(spec: KernelSpec) -> str:
    """Hash of the kernel package's ``.py`` sources (cache-entry validity)."""
    if spec.pallas is None:
        return "jnp-only"
    mod = sys.modules.get(spec.pallas.__module__)
    mod_file = getattr(mod, "__file__", None)
    if not mod_file:
        return "unknown"
    return _dir_source_hash(os.path.dirname(os.path.abspath(mod_file)))


def autotune_enabled() -> bool:
    env = os.environ.get("REPRO_AUTOTUNE")
    if env is not None:
        return env != "0"
    return not interpret_default()


# ---------------------------------------------------------------------------
# Disk cache
# ---------------------------------------------------------------------------


def _load_disk() -> None:
    """Merge valid on-disk entries into memory (once per path).

    Anything unusable — unreadable/corrupt JSON, a legacy v1 flat dict, a
    foreign version, entries for unregistered kernels, entries whose
    recorded source hash no longer matches the kernel package — is simply
    skipped; the next winner store rewrites the file in v2 form.
    """
    global _disk_loaded_from
    path = cache_path()
    if _disk_loaded_from == path:
        return
    _disk_loaded_from = path
    try:
        with open(path) as f:
            on_disk = json.load(f)
    except (OSError, ValueError):
        return
    if not isinstance(on_disk, dict) or on_disk.get("version") != CACHE_VERSION:
        return
    entries = on_disk.get("entries")
    if not isinstance(entries, dict):
        return
    from repro.kernels import registry

    for k, v in entries.items():
        if not isinstance(v, dict) or "tiles" not in v:
            continue
        name = str(k).split("|", 1)[0]
        try:
            spec = registry.get(name)
        except KeyError:
            continue
        if v.get("src") != source_hash(spec):
            continue
        _memory_cache.setdefault(k, v)


def _store_disk(key: str, entry: dict) -> None:
    """Read-modify-write with an atomic replace (best-effort on failure).

    The per-candidate ``"candidates"`` report never goes to disk — only
    the winner. A damaged or legacy file is replaced with a fresh v2
    envelope rather than propagated.
    """
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        try:
            with open(path) as f:
                on_disk = json.load(f)
        except (OSError, ValueError):
            on_disk = None
        if (
            not isinstance(on_disk, dict)
            or on_disk.get("version") != CACHE_VERSION
            or not isinstance(on_disk.get("entries"), dict)
        ):
            on_disk = {"version": CACHE_VERSION, "entries": {}}
        on_disk["entries"][key] = {k: v for k, v in entry.items() if k != "candidates"}
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), suffix=".tmp")
        with os.fdopen(fd, "w") as f:
            json.dump(on_disk, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # read-only FS etc. — the in-memory winner still applies


def clear_memory_cache() -> None:
    """Testing hook: forget in-process winners (disk is untouched)."""
    global _disk_loaded_from
    _memory_cache.clear()
    _disk_loaded_from = None


# ---------------------------------------------------------------------------
# The sweep
# ---------------------------------------------------------------------------


def _time_candidate(spec: KernelSpec, args: tuple, tiles: Mapping[str, int], interpret: bool) -> float:
    """Median-free min-of-reps wall time (µs) for one tile candidate."""
    run = lambda: jax.block_until_ready(spec.pallas(*args, tiles=tiles, interpret=interpret))
    run()  # compile / warm up
    best = float("inf")
    for _ in range(_SWEEP_REPS):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def sweep(
    spec: KernelSpec,
    sig: ShapeSig,
    *,
    interpret: Optional[bool] = None,
    report: bool = False,
) -> dict:
    """Time every tile candidate at ``sig``; return the winning entry.

    Runs eagerly on synthesized inputs — never touches caller data.
    ``report=True`` adds a ``"candidates"`` list (every candidate's tiles
    and wall time) for roofline reporting; it is stripped before disk.
    A candidate that fails is skipped; when all fail, the first error is
    raised.
    """
    if interpret is None:
        interpret = interpret_default()
    args = spec.make_inputs(jax.random.key(0), sig)
    results, first_err = [], None
    for tiles in spec.tile_candidates:
        try:
            us = _time_candidate(spec, args, tiles, interpret)
        except Exception as e:  # noqa: BLE001 — a tiling this shape cannot take
            first_err = first_err or e
            continue
        results.append((us, dict(tiles)))
    if not results:
        raise RuntimeError(
            f"autotune: every tile candidate of {spec.name!r} failed at {sig}"
        ) from first_err
    us, tiles = min(results, key=lambda r: r[0])
    entry = {"tiles": tiles, "us": us, "n_candidates": len(results), "src": source_hash(spec)}
    if report:
        entry["candidates"] = [{"tiles": t, "us": u} for u, t in results]
    return entry


def record(spec: KernelSpec, sig: ShapeSig, entry: dict) -> None:
    """Store a sweep winner (memory + disk) — e.g. from an explicit
    ``kernel_micro.py --autotune`` run warming the cache for a deployment."""
    entry = dict(entry)
    entry.setdefault("src", source_hash(spec))
    key = cache_key(spec.name, backend(), sig)
    _memory_cache[key] = entry
    _store_disk(key, entry)


def tiles_for(spec: KernelSpec, sig: ShapeSig, *, tracing: bool = False) -> Mapping[str, int]:
    """The dispatcher's entry point: cached winner, else sweep, else defaults.

    Keys — and sweeps — at the *bucketed* signature, so every shape in a
    bucket shares one entry and one sweep. ``tracing=True`` (the caller is
    being staged under ``jit``) never sweeps: it serves the cached winner
    or the defaults, and leaves the entry open for a later eager sweep.
    """
    back = backend()
    key = cache_key(spec.name, back, sig)
    _load_disk()
    entry = _memory_cache.get(key)
    if entry is not None:
        return entry["tiles"]
    defaults = {"tiles": dict(spec.tiles_for_backend(back)), "us": None}
    if tracing:
        return defaults["tiles"]
    if autotune_enabled():
        entry = sweep(spec, bucket_sig(sig))
        _store_disk(key, entry)
    else:
        entry = defaults
    _memory_cache[key] = entry
    return entry["tiles"]
