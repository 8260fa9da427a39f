"""Kernel registry + dispatcher: one seam for every fused kernel.

Each compute hot-spot registers a :class:`KernelSpec` declaring

* ``ref``     — the pure-jnp oracle (differentiable via ordinary AD),
* ``pallas``  — the fused Pallas implementation, parameterized by a
  ``tiles`` mapping of block/tile sizes (and ``interpret``),
* ``tile_candidates`` / ``default_tiles`` — the autotune search grid and
  the per-backend fallback winners,
* ``make_inputs`` + ``check_shapes`` + ``oracle_check`` — a correctness
  oracle: synthesize inputs for any shape signature and validate the
  Pallas path against ``ref`` (used by tests, benchmarks and the tuner).

Callers go through :func:`dispatch`, which resolves pallas-vs-jnp *per
backend* with overrides, then asks the autotuner for tile sizes:

    resolution order (first match wins)
      1. explicit ``impl=`` argument ("pallas" | "jnp"; "auto"/None falls
         through; legacy bools are accepted: True→"pallas", False→"jnp")
      2. env ``REPRO_KERNEL_<NAME>``   (per-kernel override)
      3. env ``REPRO_KERNELS``         (global override)
      4. backend policy: tpu/gpu → "pallas" (compiled); cpu → "jnp"
         (Pallas on CPU means interpret mode — an oracle-checking tool,
         not a fast path)

``REPRO_PALLAS_INTERPRET`` ("0"/"1") forces interpret mode off/on; unset
⇒ interpret on CPU, compiled on TPU/GPU. (This changes the pre-registry
default, which interpreted on *every* backend until the env var was set
to "0" — TPU runs now compile out of the box.) CPU CI thus exercises the
same kernel bodies that Mosaic compiles on a real TPU.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Mapping, Optional, Sequence, Tuple

import jax

# (shape, dtype-name) per public argument — the unit the autotune cache is
# keyed on and ``make_inputs`` synthesizes from.
ShapeSig = Tuple[Tuple[Tuple[int, ...], str], ...]


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Everything the dispatcher/autotuner/benchmarks need about one kernel.

    ``pallas=None`` registers a **jnp-only** kernel: a hot spot that wants
    the registry seam today (named dispatch, env/config overrides, a place
    for tests and benchmarks to find it) before a fused implementation has
    landed. Such kernels always resolve to the ref path; ``validate``
    raises, and the autotuner never sees them. The capacity-bounded
    admission step of the index build (``"capacity_admit"``) is the first:
    sort-bound, VPU-bound either way, but its dispatch seam keeps the
    build's inner loops uniform.
    """

    name: str
    ref: Callable[..., Any]
    pallas: Optional[Callable[..., Any]]  # pallas(*args, tiles=Mapping, interpret=bool)
    tile_candidates: Tuple[Mapping[str, int], ...]
    default_tiles: Mapping[str, Mapping[str, int]]  # backend → tiles ("" = fallback)
    make_inputs: Callable[[jax.Array, ShapeSig], tuple]  # (key, sig) → args
    check_shapes: Tuple[ShapeSig, ...]  # correctness grid for tests
    bench_shapes: ShapeSig  # the micro-benchmark working point
    tol: Tuple[float, float] = (1e-5, 1e-5)  # (rtol, atol) vs the oracle
    # optional custom comparison (e.g. argmin ties); signature
    # oracle_check(args, got, want) -> None, raising on mismatch
    oracle_check: Optional[Callable[[tuple, Any, Any], None]] = None
    # optional analytic cost of ONE forward call at a signature:
    # cost_model(sig) -> {"flops": float, "bytes": float} — feeds the
    # roofline columns of benchmarks/kernel_micro.py and the autotuner's
    # per-candidate achieved-vs-roofline report
    cost_model: Optional[Callable[[ShapeSig], dict]] = None
    # dtype grid the parity harness (tests/test_kernel_parity.py) sweeps:
    # every floating dtype in check_shapes is rewritten to each entry
    dtype_grid: Tuple[str, ...] = ("float32", "bfloat16")

    def tiles_for_backend(self, backend: str) -> Mapping[str, int]:
        return self.default_tiles.get(backend, self.default_tiles[""])


_REGISTRY: dict[str, KernelSpec] = {}
_BUILTINS_LOADED = False


def register(spec: KernelSpec) -> KernelSpec:
    if spec.name in _REGISTRY:
        raise ValueError(f"kernel {spec.name!r} already registered")
    _REGISTRY[spec.name] = spec
    return spec


def _load_builtins() -> None:
    """Import the kernel packages (each registers its spec at import)."""
    global _BUILTINS_LOADED
    if _BUILTINS_LOADED:
        return
    _BUILTINS_LOADED = True
    import repro.kernels.capacity_admit.ops  # noqa: F401
    import repro.kernels.cauchy_mean.ops  # noqa: F401
    import repro.kernels.frozen_attract.ops  # noqa: F401
    import repro.kernels.kmeans_assign.ops  # noqa: F401
    import repro.kernels.nomad_step.ops  # noqa: F401
    import repro.kernels.pairwise.ops  # noqa: F401


def get(name: str) -> KernelSpec:
    _load_builtins()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown kernel {name!r}; registered: {names()}") from None


def names() -> list[str]:
    _load_builtins()
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# Implementation resolution
# ---------------------------------------------------------------------------

_VALID_IMPLS = ("pallas", "jnp")


def normalize_impl(impl) -> str:
    """Map legacy bools / None / strings onto {"auto", "pallas", "jnp"}."""
    if impl is None:
        return "auto"
    if isinstance(impl, bool):
        return "pallas" if impl else "jnp"
    impl = str(impl).lower()
    if impl in ("", "auto"):
        return "auto"
    if impl == "ref":
        return "jnp"
    if impl not in _VALID_IMPLS:
        raise ValueError(f"impl must be auto|pallas|jnp, got {impl!r}")
    return impl


def backend() -> str:
    return jax.default_backend()


def interpret_default() -> bool:
    """Env wins; unset ⇒ interpret iff running on CPU (TPU/GPU compile)."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env != "0"
    return backend() == "cpu"


def has_pallas(name: str) -> bool:
    """False for jnp-only kernels (registered with ``pallas=None``)."""
    return get(name).pallas is not None


def resolve(name: str, impl=None) -> str:
    """Resolve one kernel's implementation to "pallas" or "jnp".

    jnp-only kernels resolve to "jnp" under every override — the seam is
    registered, the fused path hasn't landed yet. Invalid ``impl`` strings
    still raise for them, same as for every other kernel.
    """
    choice = normalize_impl(impl)
    if not has_pallas(name):
        return "jnp"
    if choice == "auto":
        env_kernel = os.environ.get("REPRO_KERNEL_" + name.upper().replace("-", "_"))
        env_global = os.environ.get("REPRO_KERNELS")
        choice = normalize_impl(env_kernel if env_kernel else env_global)
    if choice == "auto":
        choice = "jnp" if backend() == "cpu" else "pallas"
    return choice


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------


def shape_sig(args: Sequence[Any]) -> ShapeSig:
    """Static (shape, dtype) signature — works on tracers too."""
    return tuple((tuple(a.shape), str(a.dtype)) for a in args)


def dispatch(name: str, *args, impl=None, tiles: Optional[Mapping[str, int]] = None):
    """Run kernel ``name`` on ``args`` through the resolved implementation.

    Safe to call under ``jit``/``grad``: resolution happens at trace time
    (implementation choice and tile sizes are static w.r.t. the trace), and
    a traced call never starts an autotune sweep.
    """
    spec = get(name)
    if resolve(name, impl) == "jnp":
        return spec.ref(*args)
    if tiles is None:
        from repro.kernels import autotune

        tracing = any(isinstance(a, jax.core.Tracer) for a in args)
        tiles = autotune.tiles_for(spec, shape_sig(args), tracing=tracing)
    return spec.pallas(*args, tiles=tiles, interpret=interpret_default())


# ---------------------------------------------------------------------------
# Correctness oracle
# ---------------------------------------------------------------------------


def validate(
    name: str,
    args: tuple,
    *,
    tiles: Optional[Mapping[str, int]] = None,
    interpret: Optional[bool] = None,
):
    """Run the Pallas path against the jnp oracle on ``args``; raise on
    mismatch. The spec's ``oracle_check`` (if any) arbitrates ties;
    otherwise every output leaf must be allclose within ``spec.tol``."""
    import numpy as np

    spec = get(name)
    if spec.pallas is None:
        raise ValueError(
            f"kernel {name!r} is jnp-only (pallas=None) — nothing to validate "
            "against the oracle"
        )
    if tiles is None:
        tiles = spec.tiles_for_backend(backend())
    if interpret is None:
        interpret = interpret_default()
    got = spec.pallas(*args, tiles=tiles, interpret=interpret)
    # the oracle is plain float32 math: on a TPU, XLA's default f32 matmul
    # takes one bf16 pass, so pin full precision (a no-op on CPU)
    with jax.default_matmul_precision("highest"):
        want = spec.ref(*args)
    if spec.oracle_check is not None:
        spec.oracle_check(args, got, want)
        return got, want
    rtol, atol = spec.tol
    got_leaves = jax.tree_util.tree_leaves(got)
    want_leaves = jax.tree_util.tree_leaves(want)
    assert len(got_leaves) == len(want_leaves), (len(got_leaves), len(want_leaves))
    for g, w in zip(got_leaves, want_leaves):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32), rtol=rtol, atol=atol
        )
    return got, want
