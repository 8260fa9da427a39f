"""Attention: GQA with RoPE, optional qk-norm and sliding windows.

Weight layout is TP-native: projections are stored head-major —
``wq (D, H, hd)``, ``wk/wv (D, KV, hd)``, ``wo (H, hd, D)`` — so the tensor
axis shards the explicit H dimension and **no sharded dimension is ever
reshaped** (sharded reshapes are where XLA SPMD inserts surprise
collectives). GQA repeats k/v to H heads at use (replicated KV → local
slice; no communication). ``wo`` is row-parallel: the output contraction
over (H, hd) produces the one expected psum per attention block.

Three execution paths, numerically equivalent (tested against each other):

* ``attend_full``     — materialises the (Sq, Sk) score matrix; the oracle.
* ``attend_chunked``  — online-softmax over (q-chunk, kv-chunk) tiles via a
  double ``lax.scan`` (FlashAttention recurrence at the jnp level, so the
  dry-run HLO stays compact and live memory is O(Sq·chunk)).
* ``attend_decode``   — single query against a cache whose length axis may
  be sharded (distributed flash-decode: softmax max/sum lower to small
  all-reduces under pjit).

Layouts: q (B, S, H, hd); k/v (B, S, KV, hd); caches (B, Sc, KV, hd).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import apply_rope, rms_norm

NEG_INF = -0.7 * float(np.finfo(np.float32).max)


class AttnParams(NamedTuple):
    wq: jax.Array  # (D, H, hd)
    wk: jax.Array  # (D, KV, hd)
    wv: jax.Array  # (D, KV, hd)
    wo: jax.Array  # (H, hd, D)
    q_norm: Optional[jax.Array] = None  # (hd,) — qwen3-style qk-norm
    k_norm: Optional[jax.Array] = None  # (hd,)


def init_attention(key, cfg) -> AttnParams:
    from repro.models.layers import dtype_of

    dt = dtype_of(cfg.param_dtype)
    kq, kk, kv, ko = jax.random.split(key, 4)
    D, hd, KV = cfg.d_model, cfg.head_dim, cfg.n_kv_heads
    Hp = cfg.n_heads_padded  # pad heads live but masked (head_mask)
    s_in = 1.0 / np.sqrt(D)
    s_out = 1.0 / np.sqrt(cfg.n_heads * hd)
    return AttnParams(
        wq=(jax.random.normal(kq, (D, Hp, hd)) * s_in).astype(dt),
        wk=(jax.random.normal(kk, (D, KV, hd)) * s_in).astype(dt),
        wv=(jax.random.normal(kv, (D, KV, hd)) * s_in).astype(dt),
        wo=(jax.random.normal(ko, (Hp, hd, D)) * s_out).astype(dt),
        q_norm=jnp.ones((hd,), dt) if cfg.qk_norm else None,
        k_norm=jnp.ones((hd,), dt) if cfg.qk_norm else None,
    )


def head_mask(cfg) -> Optional[jax.Array]:
    """(Hp,) 1/0 mask: within each kv group of g_pad padded q slots, the
    first g are real. Masking attention outputs keeps pad heads inert
    (zero forward contribution AND zero wo gradients)."""
    Hp, H, KV = cfg.n_heads_padded, cfg.n_heads, max(cfg.n_kv_heads, 1)
    if Hp == H:
        return None
    g, g_pad = H // KV, Hp // KV
    return (jnp.arange(Hp) % g_pad < g).astype(jnp.float32)


def qkv_project(p: AttnParams, x: jax.Array, positions: jax.Array, cfg):
    """x (B, S, D) → q (B,S,H,hd), k/v (B,S,KV,hd), RoPE'd and normed."""
    q = jnp.einsum("bsd,dhe->bshe", x, p.wq)
    k = jnp.einsum("bsd,dhe->bshe", x, p.wk)
    v = jnp.einsum("bsd,dhe->bshe", x, p.wv)
    if p.q_norm is not None:
        q = rms_norm(q, p.q_norm)
        k = rms_norm(k, p.k_norm)
    if not cfg.encoder_only:  # the audio encoder is position-free (stub CNN)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, S, KV, hd) → (B, S, H, hd) by repeating each kv head H/KV times."""
    kv = k.shape[2]
    if kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // kv, axis=2)


# ---------------------------------------------------------------------------
# Full (oracle) attention
# ---------------------------------------------------------------------------


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, causal: bool, window: int) -> jax.Array:
    """(…, Sq, Sk) additive bias: 0 where visible, NEG_INF elsewhere."""
    d = q_pos[..., :, None] - k_pos[..., None, :]
    ok = jnp.ones(d.shape, bool)
    if causal:
        ok &= d >= 0
    if window > 0:
        ok &= d < window
    return jnp.where(ok, 0.0, NEG_INF)


def attend_full(q, k, v, q_pos, k_pos, *, causal: bool, window: int = 0) -> jax.Array:
    H, hd = q.shape[-2], q.shape[-1]
    k, v = repeat_kv(k, H), repeat_kv(v, H)
    scale = 1.0 / np.sqrt(hd)
    scores = jnp.einsum("bqhe,bkhe->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    bias = _mask_bias(q_pos, k_pos, causal, window)  # (B, Sq, Sk)
    probs = jax.nn.softmax(scores + bias[:, None, :, :], axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhe->bqhe", probs, v)


# ---------------------------------------------------------------------------
# Chunked (memory-efficient) attention — training / prefill hot path
# ---------------------------------------------------------------------------


def attend_chunked(q, k, v, q_pos, k_pos, *, causal: bool, window: int = 0, chunk: int = 1024):
    """Online-softmax attention; O(Sq·chunk) live memory instead of O(Sq·Sk).

    Outer scan over q chunks, inner scan over kv chunks with the running
    (max, sum, acc) recurrence. Fully-masked tiles still execute (static
    schedule); the roofline carries this ~2× score-FLOP overhead and §Perf
    attacks it.
    """
    B, Sq, H, hd = q.shape
    k, v = repeat_kv(k, H), repeat_kv(v, H)
    Sk = k.shape[1]
    cq, ck = min(chunk, Sq), min(chunk, Sk)
    assert Sq % cq == 0 and Sk % ck == 0, (Sq, Sk, chunk)
    nq, nk = Sq // cq, Sk // ck
    scale = 1.0 / np.sqrt(hd)

    q_r = q.reshape(B, nq, cq, H, hd).transpose(1, 0, 2, 3, 4)
    qp_r = q_pos.reshape(B, nq, cq).transpose(1, 0, 2)
    k_r = k.reshape(B, nk, ck, H, hd).transpose(1, 0, 2, 3, 4)
    v_r = v.reshape(B, nk, ck, H, hd).transpose(1, 0, 2, 3, 4)
    kp_r = k_pos.reshape(B, nk, ck).transpose(1, 0, 2)

    def q_step(_, qc):
        qi, qpi = qc

        def kv_step(carry, kc):
            m, l, acc = carry
            ki, vi, kpi = kc
            s = jnp.einsum("bqhe,bkhe->bhqk", qi, ki, preferred_element_type=jnp.float32)
            s = s * scale + _mask_bias(qpi, kpi, causal, window)[:, None, :, :]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhe->bhqe", p.astype(vi.dtype), vi, preferred_element_type=jnp.float32
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, cq), jnp.float32)
        a0 = jnp.zeros((B, H, cq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (k_r, v_r, kp_r))
        out = acc / jnp.maximum(l, 1e-37)[..., None]  # (B, H, cq, hd)
        return None, out.transpose(0, 2, 1, 3)  # (B, cq, H, hd)

    _, outs = jax.lax.scan(q_step, None, (q_r, qp_r))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)
    return out.astype(v.dtype)


# ---------------------------------------------------------------------------
# Flash attention (custom VJP): §Perf iteration 2
# ---------------------------------------------------------------------------
# The plain chunked path is memory-optimal FORWARD, but jax AD of the double
# scan stores every (cq, ck) probability tile for the backward — measured
# ~0.9 GiB/layer and the dominant HBM term fleet-wide. This custom VJP stores
# only (out, L = m + log l) per row (FlashAttention-2's residuals) and
# recomputes tiles in the backward, which is also how the TPU kernel would
# behave. Inputs are MHA-shaped (k/v already repeated to H heads); the GQA
# head-sum in the k/v gradient falls out of jax's transpose of repeat_kv.


def _flash_fwd_impl(q, k, v, q_pos, k_pos, causal, window, chunk):
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    cq, ck = min(chunk, Sq), min(chunk, Sk)
    assert Sq % cq == 0 and Sk % ck == 0, (Sq, Sk, chunk)
    nq, nk = Sq // cq, Sk // ck
    scale = 1.0 / np.sqrt(hd)

    q_r = q.reshape(B, nq, cq, H, hd).transpose(1, 0, 2, 3, 4)
    qp_r = q_pos.reshape(B, nq, cq).transpose(1, 0, 2)
    k_r = k.reshape(B, nk, ck, H, hd).transpose(1, 0, 2, 3, 4)
    v_r = v.reshape(B, nk, ck, H, hd).transpose(1, 0, 2, 3, 4)
    kp_r = k_pos.reshape(B, nk, ck).transpose(1, 0, 2)

    def q_step(_, qc):
        qi, qpi = qc

        def kv_step(carry, kc):
            m, l, acc = carry
            ki, vi, kpi = kc
            s = jnp.einsum("bqhe,bkhe->bhqk", qi, ki, preferred_element_type=jnp.float32)
            s = s * scale + _mask_bias(qpi, kpi, causal, window)[:, None, :, :]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhe->bhqe", p.astype(vi.dtype), vi, preferred_element_type=jnp.float32
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, cq), jnp.float32)
        a0 = jnp.zeros((B, H, cq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (k_r, v_r, kp_r))
        out = acc / jnp.maximum(l, 1e-37)[..., None]
        L = m + jnp.log(jnp.maximum(l, 1e-37))  # (B, H, cq)
        return None, (out.transpose(0, 2, 1, 3), L)

    _, (outs, Ls) = jax.lax.scan(q_step, None, (q_r, qp_r))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd).astype(v.dtype)
    L = Ls.transpose(1, 2, 0, 3).reshape(B, H, Sq)  # (nq,B,H,cq) → (B,H,Sq)
    return out, L


def _flash_bwd_impl(q, k, v, q_pos, k_pos, out, L, dout, causal, window, chunk):
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    cq, ck = min(chunk, Sq), min(chunk, Sk)
    nq, nk = Sq // cq, Sk // ck
    scale = 1.0 / np.sqrt(hd)

    D = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # (B,Sq,H)
    D = D.transpose(0, 2, 1)  # (B, H, Sq)

    q_r = q.reshape(B, nq, cq, H, hd).transpose(1, 0, 2, 3, 4)
    do_r = dout.reshape(B, nq, cq, H, hd).transpose(1, 0, 2, 3, 4)
    qp_r = q_pos.reshape(B, nq, cq).transpose(1, 0, 2)
    L_r = L.reshape(B, H, nq, cq).transpose(2, 0, 1, 3)  # (nq, B, H, cq)
    D_r = D.reshape(B, H, nq, cq).transpose(2, 0, 1, 3)
    k_r = k.reshape(B, nk, ck, H, hd).transpose(1, 0, 2, 3, 4)
    v_r = v.reshape(B, nk, ck, H, hd).transpose(1, 0, 2, 3, 4)
    kp_r = k_pos.reshape(B, nk, ck).transpose(1, 0, 2)

    def q_step(carry, qc):
        dk, dv = carry  # (nk, B, ck, H, hd) fp32
        qi, doi, qpi, Li, Di = qc

        def kv_step(carry2, kc):
            dq_i = carry2
            j, ki, vi, kpi = kc
            s = jnp.einsum("bqhe,bkhe->bhqk", qi, ki, preferred_element_type=jnp.float32)
            s = s * scale + _mask_bias(qpi, kpi, causal, window)[:, None, :, :]
            p = jnp.exp(s - Li[..., None])  # (B,H,cq,ck)
            dv_j = jnp.einsum("bhqk,bqhe->bkhe", p, doi.astype(jnp.float32))
            dp = jnp.einsum("bqhe,bkhe->bhqk", doi.astype(jnp.float32), vi.astype(jnp.float32))
            ds = p * (dp - Di[..., None]) * scale  # (B,H,cq,ck)
            dq_i = dq_i + jnp.einsum("bhqk,bkhe->bqhe", ds, ki.astype(jnp.float32))
            dk_j = jnp.einsum("bhqk,bqhe->bkhe", ds, qi.astype(jnp.float32))
            return dq_i, (j, dk_j, dv_j)

        dq0 = jnp.zeros((B, cq, H, hd), jnp.float32)
        dq_i, (js, dk_js, dv_js) = jax.lax.scan(
            kv_step, dq0, (jnp.arange(nk), k_r, v_r, kp_r)
        )
        dk = dk + dk_js
        dv = dv + dv_js
        return (dk, dv), dq_i

    dk0 = jnp.zeros((nk, B, ck, H, hd), jnp.float32)
    dv0 = jnp.zeros((nk, B, ck, H, hd), jnp.float32)
    (dk, dv), dqs = jax.lax.scan(q_step, (dk0, dv0), (q_r, do_r, qp_r, L_r, D_r))
    dq = dqs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd).astype(q.dtype)
    dk = dk.transpose(1, 0, 2, 3, 4).reshape(B, Sk, H, hd).astype(k.dtype)
    dv = dv.transpose(1, 0, 2, 3, 4).reshape(B, Sk, H, hd).astype(v.dtype)
    return dq, dk, dv


import functools as _ft


@_ft.lru_cache(maxsize=None)
def _flash_fn(causal: bool, window: int, chunk: int):
    @jax.custom_vjp
    def fa(q, k, v, q_pos, k_pos):
        return _flash_fwd_impl(q, k, v, q_pos, k_pos, causal, window, chunk)[0]

    def fwd(q, k, v, q_pos, k_pos):
        out, L = _flash_fwd_impl(q, k, v, q_pos, k_pos, causal, window, chunk)
        return out, (q, k, v, q_pos, k_pos, out, L)

    def bwd(res, dout):
        q, k, v, q_pos, k_pos, out, L = res
        dq, dk, dv = _flash_bwd_impl(
            q, k, v, q_pos, k_pos, out, L, dout, causal, window, chunk
        )
        return dq, dk, dv, None, None

    fa.defvjp(fwd, bwd)
    return fa


def attend_flash(q, k, v, q_pos, k_pos, *, causal: bool, window: int = 0, chunk: int = 1024):
    """Memory-optimal fwd+bwd attention (k/v repeated to H by the caller)."""
    H = q.shape[2]
    k, v = repeat_kv(k, H), repeat_kv(v, H)
    return _flash_fn(causal, window, chunk)(q, k, v, q_pos, k_pos)


# ---------------------------------------------------------------------------
# Decode attention (single query vs. a possibly-sharded cache)
# ---------------------------------------------------------------------------


def _decode_local(q, k_cache, v_cache, q_pos, k_pos, valid, window):
    """Single-shard decode attention → unnormalised (o_partial, m, l)."""
    H, hd = q.shape[-2], q.shape[-1]
    k_cache, v_cache = repeat_kv(k_cache, H), repeat_kv(v_cache, H)
    scale = 1.0 / np.sqrt(hd)
    s = jnp.einsum("bqhe,bkhe->bhqk", q, k_cache, preferred_element_type=jnp.float32) * scale
    d = q_pos[:, :, None] - k_pos[:, None, :]  # (B, 1, S)
    ok = (d >= 0) & valid[:, None, :]
    if window > 0:
        ok &= d < window
    s = jnp.where(ok[:, None, :, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)  # (B, H, 1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhe->bqhe", p.astype(v_cache.dtype), v_cache)
    return o, m, l


def attend_decode(q, k_cache, v_cache, q_pos, k_pos, valid, *, window: int = 0):
    """q: (B, 1, H, hd); caches: (B, S, KV, hd); valid: (B, S) bool."""
    o, m, l = _decode_local(q, k_cache, v_cache, q_pos, k_pos, valid, window)
    return o / jnp.maximum(l, 1e-37).transpose(0, 2, 1)[..., None]


# --- distributed flash-decode (§Perf iteration 7) ----------------------------
# Left to global-view pjit, repeat_kv + masking around the sharded cache made
# XLA all-gather the whole KV cache per layer (measured 4 GB of wire per
# layer per token on yi-34b). This shard_map version keeps the cache's
# length shards local and combines (o, m, l) softmax stats — a few KB of
# psum per layer, the textbook flash-decode reduction.

_DECODE_CTX: "tuple | None" = None  # (mesh, batch_axes, s_axes)


def set_decode_context(mesh, batch_axes, s_axes) -> None:
    global _DECODE_CTX
    _DECODE_CTX = None if mesh is None else (mesh, batch_axes, tuple(s_axes))


def attend_decode_sharded(q, k_cache, v_cache, q_pos, k_pos, valid, *, window: int = 0):
    import functools

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh, baxes, saxes = _DECODE_CTX

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(baxes, None, None, None),  # q replicated over the S shards
            P(baxes, saxes, None, None),
            P(baxes, saxes, None, None),
            P(baxes, None),
            P(baxes, saxes),
            P(baxes, saxes),
        ),
        out_specs=P(baxes, None, None, None),
        check_vma=False,
    )
    def block(q, kc, vc, qp, kp, vd):
        o, m, l = _decode_local(q, kc, vc, qp, kp, vd, window)
        g_m = jax.lax.pmax(m, saxes)  # (B, H, 1)
        corr = jnp.exp(m - g_m)
        l = jax.lax.psum(l * corr, saxes)
        o = jax.lax.psum(o * corr.transpose(0, 2, 1)[..., None].astype(o.dtype), saxes)
        return o / jnp.maximum(l, 1e-37).transpose(0, 2, 1)[..., None].astype(o.dtype)

    return block(q, k_cache, v_cache, q_pos, k_pos, valid)


def dispatch_attend_decode(q, k_cache, v_cache, q_pos, k_pos, valid, *, window: int = 0):
    if _DECODE_CTX is not None:
        return attend_decode_sharded(q, k_cache, v_cache, q_pos, k_pos, valid, window=window)
    return attend_decode(q, k_cache, v_cache, q_pos, k_pos, valid, window=window)


def attention_block(p: AttnParams, x, positions, cfg, *, causal: bool):
    """Projection → attention → output projection, for train/prefill."""
    q, k, v = qkv_project(p, x, positions, cfg)
    window = cfg.sliding_window
    if x.shape[1] > cfg.attn_chunk:
        impl = attend_flash if getattr(cfg, "attn_impl", "flash") == "flash" else attend_chunked
        out = impl(q, k, v, positions, positions, causal=causal, window=window, chunk=cfg.attn_chunk)
    else:
        out = attend_full(q, k, v, positions, positions, causal=causal, window=window)
    hm = head_mask(cfg)
    if hm is not None:
        out = out * hm[None, None, :, None].astype(out.dtype)
    y = jnp.einsum("bqhe,hed->bqd", out, p.wo)  # row-parallel: one psum
    return y, (k, v)


def attention_decode_block(p: AttnParams, x, pos, k_cache, v_cache, k_pos, valid, cfg):
    """One decode step. x: (B, 1, D); returns (y, (k_new, v_new))."""
    q, k_new, v_new = qkv_project(p, x, pos, cfg)
    out = dispatch_attend_decode(q, k_cache, v_cache, pos, k_pos, valid, window=cfg.sliding_window)
    hm = head_mask(cfg)
    if hm is not None:
        out = out * hm[None, None, :, None].astype(out.dtype)
    y = jnp.einsum("bqhe,hed->bqd", out, p.wo)
    return y, (k_new, v_new)
