"""Optimizer substrate: schedules, AdamW (fp32/int8 moments), SGD,
block-quantisation bounds, gradient compression with error feedback."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.optim import AdamW, SGD, constant, linear_decay, warmup_cosine
from repro.optim.compression import _dequant, _quant, compressed_psum
from repro.optim.quantized import dequantize_int8, quantize_int8


def test_linear_decay_endpoints():
    s = linear_decay(10.0, 100)
    assert float(s(0)) == 10.0
    assert abs(float(s(50)) - 5.0) < 1e-6
    assert float(s(100)) == 0.0
    assert float(s(150)) == 0.0  # clamped


def test_warmup_cosine_shape():
    s = warmup_cosine(1.0, 10, 100)
    assert float(s(0)) == 0.0
    assert abs(float(s(10)) - 1.0) < 1e-6
    assert float(s(100)) < float(s(50)) < float(s(10))


@given(st.integers(0, 2**31 - 1), st.sampled_from([(64,), (7, 33), (3, 5, 17)]))
@settings(max_examples=25, deadline=None)
def test_quantize_roundtrip_error_bound(seed, shape):
    x = jax.random.normal(jax.random.key(seed), shape) * 3
    q = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q)) - np.asarray(x))
    # per-block bound: scale/2 = absmax/254
    assert err.max() <= float(jnp.max(jnp.abs(x))) / 127.0 + 1e-7
    assert q.q.dtype == jnp.int8


def test_quantize_sqrt_scaled_nonneg():
    x = jnp.abs(jax.random.normal(jax.random.key(1), (300,))) * 5
    q = quantize_int8(x, sqrt_scaled=True)
    back = np.asarray(dequantize_int8(q))
    assert (back >= 0).all()
    # error bound is absolute in sqrt space: |√x̂−√x| ≤ δ = √xmax/127
    # ⇒ |x̂−x| ≤ 2√xmax·δ + δ²  (relative error blows up only for x ≈ 0,
    # exactly where Adam's v is noise anyway)
    delta = float(jnp.sqrt(jnp.max(x))) / 127.0
    bound = 2 * float(jnp.sqrt(jnp.max(x))) * delta + delta**2
    assert np.abs(back - np.asarray(x)).max() <= bound + 1e-6


def _rosenbrockish(p):
    return jnp.sum((p["a"] - 1.0) ** 2) + 0.5 * jnp.sum(p["b"] ** 2)


def test_adamw_converges_fp32():
    params = {"a": jnp.zeros((4, 4)), "b": jnp.ones((8,))}
    opt = AdamW(schedule=constant(0.05), weight_decay=0.0, moment_dtype="float32")
    state = opt.init(params)
    for _ in range(200):
        g = jax.grad(_rosenbrockish)(params)
        params, state = opt.update(params, g, state)
    assert float(_rosenbrockish(params)) < 1e-3


def test_adamw_int8_moments_track_fp32():
    k = jax.random.key(0)
    w0 = jax.random.normal(k, (512, 256))  # big enough to hit the quant path
    tgt = jax.random.normal(jax.random.key(1), (512, 256))

    def loss(p):
        return jnp.mean((p["w"] - tgt) ** 2)

    trajs = {}
    for mdt in ("float32", "int8"):
        p = {"w": w0}
        opt = AdamW(schedule=constant(0.01), weight_decay=0.0, moment_dtype=mdt)
        s = opt.init(p)
        for _ in range(60):
            g = jax.grad(loss)(p)
            p, s = opt.update(p, g, s)
        trajs[mdt] = float(loss(p))
    assert trajs["int8"] < 1.3 * trajs["float32"] + 1e-4, trajs


def test_sgd_with_schedule_is_paper_update():
    sched = linear_decay(1.0, 10)
    opt = SGD(schedule=sched)
    p = {"t": jnp.asarray([2.0])}
    s = opt.init(p)
    g = {"t": jnp.asarray([1.0])}
    p, s = opt.update(p, g, s)  # count=1 → lr = 0.9
    np.testing.assert_allclose(np.asarray(p["t"]), [2.0 - 0.9], rtol=1e-6)


def test_compression_error_feedback_telescopes():
    """Over T steps, Σ sent ≈ Σ grads (bias is carried, not lost)."""
    rng = np.random.default_rng(0)
    total_g = np.zeros(1000, np.float32)
    total_sent = np.zeros(1000, np.float32)
    r = np.zeros(1000, np.float32)
    for _ in range(30):
        g = rng.normal(0, 1, 1000).astype(np.float32)
        acc = g + r
        q, scale, pad = _quant(jnp.asarray(acc))
        sent = np.asarray(_dequant(q, scale, pad, (1000,)))
        r = acc - sent
        total_g += g
        total_sent += sent
    # residual bound: ≤ one quantisation step of the last accumulated value
    np.testing.assert_allclose(total_sent + r, total_g, rtol=1e-5, atol=1e-4)
    assert np.abs(r).max() < 0.1


def test_compressed_psum_single_axis():
    import functools
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("d",))
    g = {"w": jnp.linspace(-2, 2, 512)}
    r = jax.tree.map(jnp.zeros_like, g)

    @functools.partial(shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False)
    def run(g, r):
        return compressed_psum(g, "d", r)

    red, new_r = run(g, r)
    np.testing.assert_allclose(np.asarray(red["w"]), np.asarray(g["w"]), atol=0.02)
