"""Mixture-of-Experts FFN: top-k routing with capacity, GShard-style.

The default dispatch is the einsum/one-hot ("dense dispatch") formulation:
it is the canonical pjit-shardable pattern — with tokens sharded over the
``data`` axis and experts over ``model``, the SPMD partitioner emits the
dispatch all-reduce automatically.  A sort-based (gather/scatter) dispatch is
also provided (``dispatch="sort"``); it trades the one-hot memory for
data-dependent gathers and is one of the §Perf hillclimb levers.

Routing follows GShard/Switch: softmax router in fp32, top-k experts per
token, per-expert position via cumulative sum, tokens beyond capacity are
dropped (their combine weight is zero — the residual path carries them).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import init_linear, init_swiglu


class MoEParams(NamedTuple):
    router: jax.Array  # (D, E) — kept fp32
    w_gate: jax.Array  # (E, D, F)
    w_up: jax.Array  # (E, D, F)
    w_down: jax.Array  # (E, F, D)
    shared: dict | None  # SwiGLU params of the shared expert(s), or None


def init_moe(key, cfg) -> MoEParams:
    from repro.models.layers import dtype_of

    dt = dtype_of(cfg.param_dtype)
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    s_in, s_out = 1.0 / np.sqrt(D), 1.0 / np.sqrt(F)
    shared = None
    if cfg.n_shared_experts:
        shared = init_swiglu(ks, D, F * cfg.n_shared_experts, dt)
    return MoEParams(
        router=(jax.random.normal(kr, (D, E)) * s_in).astype(jnp.float32),
        w_gate=(jax.random.normal(kg, (E, D, F)) * s_in).astype(dt),
        w_up=(jax.random.normal(ku, (E, D, F)) * s_in).astype(dt),
        w_down=(jax.random.normal(kd, (E, F, D)) * s_out).astype(dt),
        shared=shared,
    )


def expert_capacity(n_tokens: int, n_experts: int, top_k: int, factor: float) -> int:
    cap = int(np.ceil(n_tokens * top_k * factor / n_experts))
    return max(cap, 4)


def _route(x_flat: jax.Array, p: MoEParams, top_k: int):
    """Return (probs (T,E) fp32, topk gate weights (T,k), topk expert ids (T,k))."""
    logits = x_flat.astype(jnp.float32) @ p.router
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, top_k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)  # renormalise over chosen
    return probs, gate, idx


def moe_einsum(p: MoEParams, x: jax.Array, cfg) -> tuple[jax.Array, jax.Array]:
    """GShard dense-dispatch MoE. x: (B, S, D) → (B, S, D), aux loss.

    The (T, E, C) dispatch/combine one-hots are the communication-friendly
    form: einsum ``tec,td->ecd`` with t sharded over data and e over model.
    """
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    C = expert_capacity(T, E, k, cfg.capacity_factor)
    x_flat = x.reshape(T, D)

    probs, gate, idx = _route(x_flat, p, k)

    # position of each (token, choice) within its expert, computed choice-major
    # so earlier choices win capacity slots (Switch/GShard convention).
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)  # (T, k, E)
    # cumulative count over the flattened (k, T) order:
    flat = onehot.transpose(1, 0, 2).reshape(k * T, E)
    pos = jnp.cumsum(flat, axis=0) - flat  # (k*T, E) position if dispatched
    pos_tok = (pos * flat).sum(-1).reshape(k, T).transpose(1, 0)  # (T, k)
    expert_of = idx  # (T, k)
    keep = pos_tok < C

    gate = gate * keep.astype(gate.dtype)

    # dispatch (T, E, C) and combine (T, E, C) tensors
    disp = (
        jax.nn.one_hot(expert_of, E, dtype=x.dtype)[..., None]
        * jax.nn.one_hot(jnp.where(keep, pos_tok, C), C, dtype=x.dtype)[:, :, None, :]
    ).sum(1)  # (T, E, C)
    comb = (
        jax.nn.one_hot(expert_of, E, dtype=jnp.float32)[..., None]
        * jax.nn.one_hot(jnp.where(keep, pos_tok, C), C, dtype=jnp.float32)[:, :, None, :]
        * gate[..., None, None].astype(jnp.float32)
    ).sum(1)

    xe = jnp.einsum("tec,td->ecd", disp, x_flat)  # (E, C, D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p.w_gate)) * jnp.einsum(
        "ecd,edf->ecf", xe, p.w_up
    )
    ye = jnp.einsum("ecf,efd->ecd", h, p.w_down)  # (E, C, D)
    y = jnp.einsum("tec,ecd->td", comb.astype(ye.dtype), ye)

    if p.shared is not None:
        from repro.models.layers import swiglu

        y = y + swiglu(x_flat, p.shared["w_gate"], p.shared["w_up"], p.shared["w_down"])

    aux = load_balance_loss(probs, expert_of, E)
    return y.reshape(B, S, D), aux


def moe_sort(p: MoEParams, x: jax.Array, cfg) -> tuple[jax.Array, jax.Array]:
    """Sort-based dispatch: gather tokens into (E, C) slots via argsort.

    Same routing decisions as ``moe_einsum`` (identical keep/drop set);
    avoids the (T, E, C) one-hots at the price of data-dependent gathers.
    """
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    C = expert_capacity(T, E, k, cfg.capacity_factor)
    x_flat = x.reshape(T, D)

    probs, gate, idx = _route(x_flat, p, k)

    onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)
    flat = onehot.transpose(1, 0, 2).reshape(k * T, E)
    pos = jnp.cumsum(flat, axis=0) - flat
    pos_tok = (pos * flat).sum(-1).reshape(k, T).transpose(1, 0)  # (T, k)
    keep = pos_tok < C
    gate = gate * keep.astype(gate.dtype)

    # flatten (token, choice) assignments and scatter token ids into slots
    slot = idx * C + jnp.where(keep, pos_tok, E * C)  # (T, k); dropped → OOB
    slot_flat = slot.reshape(T * k)
    tok_ids = jnp.tile(jnp.arange(T)[:, None], (1, k)).reshape(T * k)
    slot_to_tok = jnp.zeros((E * C + 1,), jnp.int32).at[slot_flat].set(tok_ids, mode="drop")
    slot_filled = jnp.zeros((E * C + 1,), bool).at[slot_flat].set(True, mode="drop")
    slot_to_tok = slot_to_tok[: E * C].reshape(E, C)
    slot_filled = slot_filled[: E * C].reshape(E, C)

    xe = x_flat[slot_to_tok] * slot_filled[..., None].astype(x.dtype)  # (E, C, D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p.w_gate)) * jnp.einsum(
        "ecd,edf->ecf", xe, p.w_up
    )
    ye = jnp.einsum("ecf,efd->ecd", h, p.w_down)

    # combine: scatter-add expert outputs back to tokens, weighted by gate
    ye_flat = ye.reshape(E * C, D)
    contrib = ye_flat[slot_flat.clip(0, E * C - 1)] * gate.reshape(T * k, 1).astype(ye.dtype)
    contrib = jnp.where((slot_flat < E * C)[:, None], contrib, 0)
    y = jnp.zeros((T, D), ye.dtype).at[tok_ids].add(contrib)

    if p.shared is not None:
        from repro.models.layers import swiglu

        y = y + swiglu(x_flat, p.shared["w_gate"], p.shared["w_up"], p.shared["w_down"])

    aux = load_balance_loss(probs, idx, E)
    return y.reshape(B, S, D), aux


def load_balance_loss(probs: jax.Array, expert_of: jax.Array, n_experts: int) -> jax.Array:
    """Switch-style auxiliary loss: E * Σ_e f_e · P_e."""
    T = probs.shape[0]
    f = jnp.zeros((n_experts,)).at[expert_of.reshape(-1)].add(1.0) / max(
        expert_of.size, 1
    )
    P = probs.mean(0)
    return n_experts * jnp.sum(jax.lax.stop_gradient(f) * P)


# ---------------------------------------------------------------------------
# Expert-parallel shard_map dispatch (§Perf iteration 4)
# ---------------------------------------------------------------------------
# Why: under pjit, both dense-dispatch (one-hot einsums: 2·T·E·C·D FLOPs) and
# sort-dispatch (data-dependent gathers XLA refuses to shard: measured an
# unsharded (T·k, D) fp32 combine tensor) leave huge artifacts. But our
# activations are already REPLICATED over the model axis (batch shards over
# data only), so each (data i, model j) shard can dispatch **locally**: it
# selects, from its own token block, the tokens routed to the experts living
# on model-shard j, runs them, scatters back, and one psum over `model`
# completes the combine — the same single collective a Megatron MLP pays.
#
# E % model == 0  → true EP (E/model experts per shard, full F);
# model % E == 0  → experts column-split over F (exact: SwiGLU is
#                   elementwise in F; the psum sums the column partials).

_EP_MESH: "tuple | None" = None  # (mesh, dp_axes, token_axes, model_axis, stationary)


def set_ep_mesh(
    mesh, dp_axes, token_axes=..., model_axis: str = "model", stationary: bool = False
) -> None:
    """``token_axes``: mesh axes of the batch dim (None ⇒ tokens replicated,
    e.g. batch=1 decode); defaults to ``dp_axes``. ``dp_axes`` names the
    FSDP axis the expert weights' d_model dim is sharded over.

    ``stationary`` (§Perf iteration 8 — serving 100B+ MoE): weights never
    move. Experts shard E over model and F over data; the (tiny) decode
    token batch is all-gathered to every shard instead (128 tokens × D ≈
    2 MB vs 43 GB of expert weights per jamba decode step), each shard
    computes its (expert, F-slice) partials, and one psum over
    (model, data) combines."""
    global _EP_MESH
    if mesh is None:
        _EP_MESH = None
        return
    if token_axes is ...:
        token_axes = tuple(dp_axes)
    _EP_MESH = (mesh, tuple(dp_axes), token_axes, model_axis, stationary)


def _ep_weight_specs(cfg, msize: int, fsdp):
    from jax.sharding import PartitionSpec as P

    if cfg.n_experts % msize == 0:
        return P("model", fsdp, None), P("model", None, fsdp), True
    assert msize % cfg.n_experts == 0, (cfg.n_experts, msize)
    return P(None, fsdp, "model"), P(None, "model", fsdp), False


def moe_ep(p: MoEParams, x: jax.Array, cfg):
    import functools

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh, dp_axes, token_axes, maxis, stationary = _EP_MESH
    msize = mesh.shape[maxis]
    E, k, D = cfg.n_experts, cfg.top_k, cfg.d_model
    fsdp = dp_axes[-1] if dp_axes else None
    if stationary:
        # weights-stationary serving: E over model, F over data, no gathers;
        # the (tiny) token batch is replicated instead
        assert fsdp is not None and E % msize == 0, (E, msize)
        gu_spec, d_spec, true_ep = P("model", None, fsdp), P("model", fsdp, None), True
        fsdp_gather = None
        x_spec = P(None, None, None)
        psum_axes = (maxis, fsdp)
    else:
        gu_spec, d_spec, true_ep = _ep_weight_specs(cfg, msize, fsdp)
        fsdp_gather = fsdp
        x_spec = P(token_axes, None, None) if token_axes else P(None, None, None)
        psum_axes = (maxis,)
    E_loc = E // msize if true_ep else E

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(x_spec, P(), gu_spec, gu_spec, d_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    def block(x_loc, router, wg, wu, wd):
        B_loc, S, _ = x_loc.shape
        T = B_loc * S
        C = expert_capacity(T, E, k, cfg.capacity_factor)
        x_flat = x_loc.reshape(T, D)

        if fsdp_gather is not None:
            # weights arrive FSDP-sharded on D; gather them (zero-3's weight AG)
            wg = jax.lax.all_gather(wg, fsdp_gather, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp_gather, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp_gather, axis=2, tiled=True)

        probs, gate, idx = _route(x_flat, MoEParams(router, None, None, None, None), k)
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)  # (T, k, E)
        flat = onehot.transpose(1, 0, 2).reshape(k * T, E)
        pos = jnp.cumsum(flat, axis=0) - flat
        pos_tok = (pos * flat).sum(-1).reshape(k, T).transpose(1, 0)  # (T, k)
        keep = pos_tok < C
        gate = gate * keep.astype(gate.dtype)

        if true_ep:  # keep only this shard's experts
            e0 = jax.lax.axis_index(maxis) * E_loc
            mine = (idx >= e0) & (idx < e0 + E_loc)
            slot = jnp.where(keep & mine, (idx - e0) * C + pos_tok, E_loc * C)
        else:  # every shard runs all experts on its F column slice
            slot = jnp.where(keep, idx * C + pos_tok, E_loc * C)
        slot_flat = slot.reshape(T * k)
        tok_ids = jnp.tile(jnp.arange(T)[:, None], (1, k)).reshape(T * k)
        slot_to_tok = jnp.zeros((E_loc * C + 1,), jnp.int32).at[slot_flat].set(
            tok_ids, mode="drop"
        )
        filled = jnp.zeros((E_loc * C + 1,), bool).at[slot_flat].set(True, mode="drop")
        slot_to_tok = slot_to_tok[:-1].reshape(E_loc, C)
        filled = filled[:-1].reshape(E_loc, C)

        xe = x_flat[slot_to_tok] * filled[..., None].astype(x_loc.dtype)  # (E_loc, C, D)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) * jnp.einsum(
            "ecd,edf->ecf", xe, wu
        )
        ye = jnp.einsum("ecf,efd->ecd", h, wd)  # (E_loc, C, D) (partial if !true_ep)

        ye_flat = ye.reshape(E_loc * C, D)
        contrib = ye_flat[jnp.clip(slot_flat, 0, E_loc * C - 1)]
        contrib = contrib * gate.reshape(T * k, 1).astype(ye.dtype)
        contrib = jnp.where((slot_flat < E_loc * C)[:, None], contrib, 0)
        y = jnp.zeros((T, D), ye.dtype).at[tok_ids].add(contrib)
        y = jax.lax.psum(y, psum_axes)  # combine across expert shards / F slices

        aux = load_balance_loss(probs, idx, E)
        if token_axes:
            aux = jax.lax.pmean(aux, token_axes)
        return y.reshape(B_loc, S, D), aux

    y, aux = block(x, p.router, p.w_gate, p.w_up, p.w_down)
    if p.shared is not None:
        from repro.models.layers import swiglu

        B, S, _ = x.shape
        y = y + swiglu(
            x.reshape(B * S, D), p.shared["w_gate"], p.shared["w_up"], p.shared["w_down"]
        ).reshape(B, S, D)
    return y, aux


def moe_block(p: MoEParams, x: jax.Array, cfg, dispatch: str | None = None):
    dispatch = dispatch or getattr(cfg, "moe_dispatch", "sort")
    if _EP_MESH is not None and dispatch in ("ep", "sort"):
        return moe_ep(p, x, cfg)
    if dispatch == "sort":
        return moe_sort(p, x, cfg)
    return moe_einsum(p, x, cfg)
