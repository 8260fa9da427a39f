"""Autotuner v2: shape buckets, source-hash invalidation, cache robustness.

The property tests use ``hypothesis`` when it is installed (it is in the
``[test]`` extra, so CI runs them); without it, ``conftest.py``'s stub
turns each ``@given`` test into a clean skip.

The robustness block is the "hostile filesystem" contract: corrupt,
truncated or legacy-v1 cache files, winners recorded by an older kernel
source, and two processes racing on the store must all degrade to a
fresh sweep (or the defaults) — never a crash, never stale tiles.
"""

from __future__ import annotations

import json
import threading

import jax
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import autotune, registry


@pytest.fixture
def tune_env(tmp_path, monkeypatch):
    path = tmp_path / "tune.json"
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(path))
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    autotune.clear_memory_cache()
    yield path
    autotune.clear_memory_cache()


# ---------------------------------------------------------------------------
# Shape buckets
# ---------------------------------------------------------------------------


def test_bucket_examples():
    assert autotune.bucket_dim(64) == 64
    assert autotune.bucket_dim(128) == 128
    assert autotune.bucket_dim(129) == 256
    # the motivating case: N = 49k and N = 50k share one sweep
    assert autotune.bucket_dim(49_000) == autotune.bucket_dim(50_000) == 65_536


@given(n=st.integers(min_value=1, max_value=10_000_000))
@settings(max_examples=200, deadline=None)
def test_bucket_dim_is_idempotent_and_covers(n):
    b = autotune.bucket_dim(n)
    assert b >= n  # a sweep at the bucket shape covers the real shape
    assert autotune.bucket_dim(b) == b  # idempotent: buckets are fixpoints
    if n <= 128:
        assert b == n  # small dims key exactly (tile regimes differ there)
    else:
        assert b & (b - 1) == 0  # power of two
        assert b < 2 * n  # never over-pads by more than 2×


@given(n=st.integers(min_value=129, max_value=10_000_000))
@settings(max_examples=100, deadline=None)
def test_same_bucket_means_same_cache_key(n):
    b = autotune.bucket_dim(n)
    lo = max(b // 2 + 1, 129)  # smallest large-dim member of n's bucket
    key_n = autotune.cache_key("k", "cpu", (((n, 64), "float32"),))
    key_lo = autotune.cache_key("k", "cpu", (((lo, 64), "float32"),))
    assert key_n == key_lo


@given(
    entries=st.lists(
        st.tuples(
            st.lists(st.integers(min_value=1, max_value=100_000), min_size=1, max_size=3),
            st.sampled_from(["float32", "bfloat16", "int32"]),
        ),
        min_size=1,
        max_size=4,
    )
)
@settings(max_examples=100, deadline=None)
def test_cache_key_stable_under_bucketing(entries):
    """cache_key(sig) == cache_key(bucket_sig(sig)): the key is a pure
    function of the bucket, so every shape in a bucket shares an entry."""
    sig = tuple((tuple(shape), dt) for shape, dt in entries)
    assert autotune.cache_key("k", "cpu", sig) == autotune.cache_key(
        "k", "cpu", autotune.bucket_sig(sig)
    )
    # and it is deterministic across calls (no dict/set ordering leaks)
    assert autotune.cache_key("k", "cpu", sig) == autotune.cache_key("k", "cpu", sig)


def test_shapes_in_one_bucket_share_a_recorded_winner(tune_env, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")  # no sweeps: cache hits only
    spec = registry.get("pairwise")
    sig_a = (((49_000, 64), "float32"), ((256, 64), "float32"))
    sig_b = (((50_000, 64), "float32"), ((256, 64), "float32"))
    planted = {"tiles": {"block_n": 128, "block_m": 128, "block_d": 256}, "us": 5.0}
    autotune.record(spec, sig_a, planted)
    assert autotune.tiles_for(spec, sig_b) == planted["tiles"]
    # a fresh process (cleared memory) reloads the same winner from disk
    autotune.clear_memory_cache()
    assert autotune.tiles_for(spec, sig_b) == planted["tiles"]


# ---------------------------------------------------------------------------
# Source-hash invalidation
# ---------------------------------------------------------------------------


def _plant(path, key, tiles, src):
    blob = {
        "version": autotune.CACHE_VERSION,
        "entries": {key: {"tiles": tiles, "us": 1.0, "src": src}},
    }
    path.write_text(json.dumps(blob))


def test_matching_source_hash_serves_cached_tiles(tune_env, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    spec = registry.get("pairwise")
    sig = spec.check_shapes[0]
    key = autotune.cache_key(spec.name, registry.backend(), sig)
    planted = {"block_n": 128, "block_m": 128, "block_d": 256}
    _plant(tune_env, key, planted, autotune.source_hash(spec))
    autotune.clear_memory_cache()
    assert autotune.tiles_for(spec, sig) == planted


def test_stale_source_hash_is_ignored(tune_env, monkeypatch):
    """A winner timed against an older kernel source must not be served."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    spec = registry.get("pairwise")
    sig = spec.check_shapes[0]
    key = autotune.cache_key(spec.name, registry.backend(), sig)
    _plant(tune_env, key, {"block_n": 1, "block_m": 1, "block_d": 1}, "0000deadbeef0000")
    autotune.clear_memory_cache()
    assert autotune.tiles_for(spec, sig) == dict(
        spec.tiles_for_backend(registry.backend())
    )


def test_unknown_kernel_entries_are_skipped(tune_env, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    _plant(tune_env, "no_such_kernel|cpu|()", {"bb": 1}, "whatever")
    autotune.clear_memory_cache()
    spec = registry.get("pairwise")
    autotune.tiles_for(spec, spec.check_shapes[0])  # must not raise
    assert "no_such_kernel|cpu|()" not in autotune._memory_cache


# ---------------------------------------------------------------------------
# Hostile-filesystem robustness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "content",
    [
        "{definitely not json",  # corrupt
        '{"version": 2, "entries": {"k": {"til',  # truncated mid-write
        '{"pairwise|cpu|()": {"tiles": {"block_n": 1}}}',  # legacy v1 flat dict
        '{"version": 99, "entries": {}}',  # future version
        '[1, 2, 3]',  # wrong toplevel type
    ],
)
def test_unusable_cache_file_degrades_to_fresh_sweep(tune_env, content):
    tune_env.write_text(content)
    spec = registry.get("pairwise")
    sig = spec.check_shapes[0]
    tiles = autotune.tiles_for(spec, sig)  # sweeps: REPRO_AUTOTUNE=1
    assert tiles in [dict(t) for t in spec.tile_candidates]
    # ...and the rewritten file is a valid v2 envelope with the new winner
    blob = json.loads(tune_env.read_text())
    assert blob["version"] == autotune.CACHE_VERSION
    key = autotune.cache_key(spec.name, registry.backend(), sig)
    assert blob["entries"][key]["tiles"] == dict(tiles)


def test_concurrent_stores_leave_a_valid_cache(tune_env):
    """Two processes racing on _store_disk: atomic replace means the last
    writer wins wholesale — the file is never interleaved garbage."""
    threads = [
        threading.Thread(
            target=autotune._store_disk,
            args=(f"k{i}|cpu|()", {"tiles": {"bb": i}, "us": 1.0, "src": "x"}),
        )
        for i in range(16)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    blob = json.loads(tune_env.read_text())
    assert blob["version"] == autotune.CACHE_VERSION
    assert blob["entries"]  # at least the last writer's entry survived
    for entry in blob["entries"].values():
        assert "tiles" in entry


# ---------------------------------------------------------------------------
# sweep --report
# ---------------------------------------------------------------------------


def test_sweep_report_lists_candidates_and_disk_strips_them(tune_env):
    spec = registry.get("pairwise")
    sig = spec.check_shapes[0]
    entry = autotune.sweep(spec, sig, interpret=True, report=True)
    assert entry["src"] == autotune.source_hash(spec)
    assert len(entry["candidates"]) == entry["n_candidates"]
    for cand in entry["candidates"]:
        assert cand["us"] > 0 and cand["tiles"] in [dict(t) for t in spec.tile_candidates]
    assert min(c["us"] for c in entry["candidates"]) == entry["us"]
    autotune.record(spec, sig, entry)
    blob = json.loads(tune_env.read_text())
    key = autotune.cache_key(spec.name, registry.backend(), sig)
    assert "candidates" not in blob["entries"][key]  # winner only on disk


# ---------------------------------------------------------------------------
# Sweeps run eagerly only, and fail loud
# ---------------------------------------------------------------------------


def test_traced_dispatch_never_sweeps(tune_env, monkeypatch):
    """Under jit every op is staged: a sweep there would time the tracer.
    A traced dispatch serves the defaults; the eager one sweeps."""
    timed = []
    monkeypatch.setattr(
        autotune, "_time_candidate", lambda spec, args, tiles, interpret: timed.append(args) or 1.0
    )
    spec = registry.get("cauchy_mean")
    args = spec.make_inputs(jax.random.key(0), spec.check_shapes[1])
    jax.jit(lambda *a: registry.dispatch("cauchy_mean", *a, impl="pallas"))(*args)
    assert timed == []
    assert not tune_env.exists()  # nothing recorded from the trace
    registry.dispatch("cauchy_mean", *args, impl="pallas")
    assert len(timed) == len(spec.tile_candidates)
    assert not any(isinstance(a, jax.core.Tracer) for a in timed[0])


def test_sweep_where_every_candidate_fails_raises_the_first_error(tune_env, monkeypatch):
    def refuse(spec, args, tiles, interpret):
        raise ValueError(f"tiling {dict(tiles)} refused")

    monkeypatch.setattr(autotune, "_time_candidate", refuse)
    spec = registry.get("pairwise")
    with pytest.raises(RuntimeError, match="every tile candidate") as err:
        autotune.tiles_for(spec, spec.check_shapes[0])
    assert str(dict(spec.tile_candidates[0])) in str(err.value.__cause__)
    assert not tune_env.exists()  # a failed sweep records nothing
