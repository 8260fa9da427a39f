"""The index build's plain reference, and the comparison that decides a
build cell's ``correct``.

Plain ``jax.numpy`` and NumPy, nothing of the program. What a build
computes, as the configuration states it (N rows x of width D, K cells of
capacity C, k neighbours):

1. initial centroids: b = ceil(log2 K) random hyperplanes (normal, from
   the first half of a split of ``key(seed)``) hash each row to a bucket;
   the means of the K most populated buckets (ties by bucket number), a
   bucket with no rows replaced by a row drawn from the second half;
2. Lloyd's iterations, at most ``kmeans_iters``: each row to its nearest
   centroid (ties to the lower index), each centroid to the mean of its
   rows (one with no rows stays); once the largest squared shift of a step
   is under ``kmeans_tol`` that step is not taken and the centroids stay;
3. placement under capacity C, in rounds: every unplaced row bids for the
   nearest of its ``build_candidates`` nearest centroids that has room;
   each centroid takes as many of its bidders as it has room for, nearest
   first (ties to the lower row); after ``build_max_rounds`` rounds the
   rows left take, in row order, their nearest centroid with room;
4. layout: cell c holds slots c·C .. c·C + C - 1, its rows in row order;
5. in-cell kNN: each row's k nearest other rows of its cell (ties to the
   lower slot), ascending;
6. Eq. 6 weights: for edge i -> j, r = #{m in the cell : d2(m, j) <
   d2(i, j)} (j itself counts, so r >= 1), w = exp(1/r) / Z with
   Z = sum_{t=1}^{k+1} exp(1/t) where r <= k, else 0; an edge of weight 0
   points at its own row.

Distances are sum_d (a_d - b_d)^2 in float32 (``how="exact"``), so the
reference carries no cancellation of its own. ``how="high"`` and
``how="bf16"`` compute them as the program's kernels do, ||a||^2 +
||b||^2 - 2 a.b, with the product at ``Precision.HIGH`` (three bfloat16
passes) or in bfloat16: the controls, the step below the program's
float32 at ``Precision.HIGHEST``.

:func:`compare` holds a build (the program's, or a control put in its
place) against the reference: the k-means objective against the
reference's Lloyd from the same start, the cell of every row against the
reference's placement under the build's own centroids, and each cell's
neighbours and weights against the reference's on the build's own cells.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 128  # rows of a block of direct distances
WEIGHT_TOL = 1e-6  # weights further apart than this differ in rank, not rounding


# ---- distances -------------------------------------------------------------


def _expanded(a, b, how: str):
    if how == "bf16":
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
        prec = jax.lax.Precision.DEFAULT
    else:
        prec = jax.lax.Precision.HIGH
    cross = jnp.dot(a, b.T, precision=prec, preferred_element_type=jnp.float32)
    a2 = jnp.sum(jnp.square(a.astype(jnp.float32)), -1)
    b2 = jnp.sum(jnp.square(b.astype(jnp.float32)), -1)
    return jnp.maximum(a2[:, None] + b2[None, :] - 2.0 * cross, 0.0)


def dist2(a, b, how: str = "exact"):
    """(n, m) squared distances of the rows of ``a`` to the rows of ``b``."""
    if how != "exact":
        return _expanded(a, b, how)
    n = a.shape[0]
    nb = -(-n // ROW_BLOCK)
    ap = jnp.pad(a, ((0, nb * ROW_BLOCK - n), (0, 0)))

    def block(ab):
        return jnp.sum(jnp.square(ab[:, None, :] - b[None, :, :]), axis=-1)

    return jax.lax.map(block, ap.reshape(nb, ROW_BLOCK, a.shape[1])).reshape(-1, b.shape[0])[:n]


_dist2 = jax.jit(dist2, static_argnames="how")


# ---- k-means ---------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_clusters",))
def lsh_init(x, seed, *, n_clusters):
    n, d = x.shape
    b = max(1, math.ceil(math.log2(n_clusters)))
    kh, kf = jax.random.split(jax.random.key(seed))
    planes = jax.random.normal(kh, (d, b), jnp.float32)
    with jax.default_matmul_precision("highest"):
        bits = (x @ planes) > 0
    codes = jnp.sum(bits * (2 ** jnp.arange(b, dtype=jnp.int32)), axis=1)
    sums = jax.ops.segment_sum(x, codes, num_segments=2**b)
    cnts = jax.ops.segment_sum(jnp.ones((n,), jnp.float32), codes, num_segments=2**b)
    top = jnp.argsort(-cnts, stable=True)[:n_clusters]
    cents = sums[top] / jnp.maximum(cnts[top], 1.0)[:, None]
    fallback = x[jax.random.randint(kf, (n_clusters,), 0, n)]
    return jnp.where((cnts[top] > 0)[:, None], cents, fallback)


@functools.partial(jax.jit, static_argnames=("how",))
def nearest(x, cents, how="exact"):
    d2 = dist2(x, cents, how)
    return jnp.argmin(d2, axis=1), jnp.min(d2, axis=1)


@functools.partial(jax.jit, static_argnames=("iters", "how"))
def lloyd(x, cents0, tol, *, iters, how="exact"):
    K = cents0.shape[0]

    def step(_, carry):
        cents, done, steps = carry
        a, _ = nearest(x, cents, how)
        sums = jax.ops.segment_sum(x, a, num_segments=K)
        cnts = jax.ops.segment_sum(jnp.ones(a.shape, jnp.float32), a, num_segments=K)
        new = jnp.where((cnts > 0)[:, None], sums / jnp.maximum(cnts, 1.0)[:, None], cents)
        steps = steps + jnp.where(done, 0, 1)
        done = done | (jnp.max(jnp.sum(jnp.square(new - cents), -1)) < tol)
        return jnp.where(done, cents, new), done, steps

    init = (cents0, jnp.zeros((), bool), jnp.zeros((), jnp.int32))
    cents, _, steps = jax.lax.fori_loop(0, iters, step, init)
    return cents, steps


def objective(x, cents) -> float:
    """sum_i min_c |x_i - c|^2, summed in float64."""
    _, m = nearest(x, jnp.asarray(cents, jnp.float32))
    return float(np.sum(np.asarray(m, np.float64)))


def centroids(x, cfg, how: str = "exact"):
    """``(centroids, steps)``: steps are the Lloyd iterations taken, the
    one that meets the stop included."""
    c0 = lsh_init(x, cfg.seed, n_clusters=cfg.n_clusters)
    cents, steps = lloyd(x, c0, jnp.float32(cfg.kmeans_tol), iters=cfg.kmeans_iters, how=how)
    return cents, int(steps)


# ---- placement ---------------------------------------------------------------


def place(x, cents, cfg, how: str = "exact") -> np.ndarray:
    """The cell of every row under ``cents``, by step 3's rounds."""
    d2 = np.asarray(_dist2(x, jnp.asarray(cents, jnp.float32), how))
    n, K = d2.shape
    R = min(cfg.build_candidates, K)
    order = np.argsort(d2, axis=1, kind="stable")
    cand = order[:, :R]
    free = np.full(K, cfg.cluster_capacity, np.int64)
    cell = np.full(n, -1, np.int64)
    rows = np.arange(n)
    for _ in range(cfg.build_max_rounds):
        todo = rows[cell < 0]
        if todo.size == 0:
            break
        ok = free[cand[todo]] > 0
        has = ok.any(axis=1)
        todo, ok = todo[has], ok[has]
        if todo.size == 0:
            break
        pick = cand[todo, ok.argmax(axis=1)]
        dist = d2[todo, pick]
        by = np.lexsort((todo, dist, pick))  # per centroid, nearest first
        todo, pick = todo[by], pick[by]
        first = np.searchsorted(pick, pick)  # where each centroid's run starts
        admit = np.arange(todo.size) - first < free[pick]
        cell[todo[admit]] = pick[admit]
        free -= np.bincount(pick[admit], minlength=K)
    for i in rows[cell < 0]:
        c = next(c for c in order[i] if free[c] > 0)
        cell[i] = c
        free[c] -= 1
    return cell


def layout(cell: np.ndarray, K: int, C: int):
    """``(perm, counts)`` of step 4: row i sits at slot perm[i]."""
    counts = np.bincount(cell, minlength=K)
    by = np.argsort(cell, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    perm = np.empty_like(cell)
    perm[by] = cell[by] * C + np.arange(cell.size) - starts[cell[by]]
    return perm, counts


# ---- in-cell kNN and weights ---------------------------------------------------


def normalizer(k: int) -> float:
    return float(np.exp(1.0 / np.arange(1, k + 2)).sum())


@functools.partial(jax.jit, static_argnames=("k", "how"))
def cell_knn(xc, valid, probe, *, k, how="exact"):
    """One cell of C slots. Returns the reference's neighbours (C, k) as
    slots, ascending; their distances; their Eq. 6 weights; and the
    distances from each slot to the slots ``probe`` (C, k) names (the
    build's neighbours; -1 reads +inf)."""
    C = xc.shape[0]
    d2 = dist2(xc, xc, how)
    both = valid[:, None] & valid[None, :]
    search = jnp.where(both & ~jnp.eye(C, dtype=bool), d2, jnp.inf)
    neg, nbr = jax.lax.top_k(-search, k)
    dT = jnp.where(valid[None, :], d2.T, jnp.inf)  # row j: column j; invalid m never nearer

    def rank_block(args):
        rows, cols = args  # (b,), (b, k)
        t = d2[rows[:, None], cols]  # d2(i, j)
        return jnp.sum(dT[cols] < t[..., None], axis=-1)  # #{m : d2(m, j) < d2(i, j)}

    nb = -(-C // ROW_BLOCK)
    pad = nb * ROW_BLOCK - C
    rows = jnp.pad(jnp.arange(C), (0, pad)).reshape(nb, ROW_BLOCK)
    cols = jnp.pad(nbr, ((0, pad), (0, 0))).reshape(nb, ROW_BLOCK, k)
    r = jax.lax.map(rank_block, (rows, cols)).reshape(-1, k)[:C]
    w = jnp.exp(1.0 / jnp.maximum(r, 1).astype(jnp.float32)) / normalizer(k)
    w = jnp.where((r >= 1) & (r <= k) & valid[:, None] & jnp.isfinite(-neg), w, 0.0)
    seen = jnp.where(probe >= 0, d2[jnp.arange(C)[:, None], jnp.maximum(probe, 0)], jnp.inf)
    return nbr, -neg, w, seen


# ---- a whole build -------------------------------------------------------------


def build(x, cfg, how: str = "exact") -> dict:
    """The reference's whole build of ``x``, in the fields the program's
    index has (the controls put in the program's place)."""
    K, C, k = cfg.n_clusters, cfg.cluster_capacity, cfg.n_neighbors
    with jax.default_matmul_precision("highest"):
        xd = jnp.asarray(x)
        cents, _ = centroids(xd, cfg, how)
        perm, counts = layout(place(xd, cents, cfg, how), K, C)
        x_rows = np.zeros((K * C, x.shape[1]), x.dtype)
        x_rows[perm] = x
        idx = np.zeros((K * C, k), np.int64)
        wts = np.zeros((K * C, k), np.float32)
        none = jnp.full((C, k), -1, jnp.int32)
        for c in range(K):
            valid = jnp.arange(C) < counts[c]
            nbr, _, w, _ = cell_knn(jnp.asarray(x_rows[c * C:(c + 1) * C]), valid, none, k=k, how=how)
            nbr, w = np.asarray(nbr), np.asarray(w)
            own = np.arange(c * C, (c + 1) * C)[:, None]
            idx[c * C:(c + 1) * C] = np.where(w > 0, nbr + c * C, own)
            wts[c * C:(c + 1) * C] = w
    return dict(perm=perm, counts=counts, x_rows=x_rows, centroids=np.asarray(cents),
                knn_idx=idx, knn_w=wts)


# ---- the comparison ------------------------------------------------------------


def placement_bad(x: np.ndarray, got: dict, K: int, C: int) -> int:
    """Rows not in exactly one slot of a cell within its count, or whose
    slot does not hold them bit for bit; plus cells whose count is wrong
    or over C."""
    perm = np.asarray(got["perm"]).astype(np.int64)
    counts = np.asarray(got["counts"]).astype(np.int64)
    n = x.shape[0]
    if perm.shape != (n,) or counts.shape != (K,):
        return n + K
    inside = (perm >= 0) & (perm < K * C)
    p = np.where(inside, perm, 0)
    cell, slot = p // C, p % C
    once = np.bincount(p, minlength=K * C)[p] == 1
    within = slot < counts[cell]
    same = np.all(
        np.asarray(got["x_rows"])[p].view(np.uint32) == x.view(np.uint32), axis=1
    )
    bad_rows = int(np.sum(~(inside & once & within & same)))
    held = np.bincount(cell[inside], minlength=K)
    bad_cells = int(np.sum((held != counts) | (counts > C)))
    return bad_rows + bad_cells


class Reference:
    """The reference's readings of one corpus, kept across the builds that
    :meth:`compare` holds against it (the program's, the controls')."""

    def __init__(self, x: np.ndarray, cfg):
        self.x, self.cfg = x, cfg
        with jax.default_matmul_precision("highest"):
            self.xd = jnp.asarray(x)
            cents, self.kmeans_steps = centroids(self.xd, cfg)
            self.objective = objective(self.xd, cents)

    def compare(self, got: dict) -> dict:
        """The numbers a build cell compares, for the build ``got``."""
        cfg, x = self.cfg, self.x
        K, C, k = cfg.n_clusters, cfg.cluster_capacity, cfg.n_neighbors
        n = x.shape[0]
        out = {"placement_bad": placement_bad(x, got, K, C)}
        perm = np.clip(np.asarray(got["perm"]).astype(np.int64), 0, K * C - 1)
        with jax.default_matmul_precision("highest"):
            obj = objective(self.xd, got["centroids"])
            out["kmeans_gap"] = abs(obj - self.objective) / self.objective
            cell = place(self.xd, got["centroids"], cfg)
            out["assign_apart"] = int(np.sum(cell != perm // C))
            knn_apart = weight_apart = 0
            d_gap = 0.0
            row_of = np.full(K * C, -1, np.int64)  # slot -> row
            row_of[perm] = np.arange(n)
            g_idx = np.asarray(got["knn_idx"]).astype(np.int64)
            g_w = np.asarray(got["knn_w"], np.float32)
            for c in range(K):
                lo = c * C
                slots = np.arange(lo, lo + C)
                valid = row_of[slots] >= 0
                xc = np.zeros((C, x.shape[1]), x.dtype)
                xc[valid] = x[row_of[slots[valid]]]
                gi, gw = g_idx[slots], g_w[slots]
                probe = np.where((gi >= lo) & (gi < lo + C) & (gw > 0), gi - lo, -1)
                nbr, nd2, w, seen = cell_knn(
                    jnp.asarray(xc), jnp.asarray(valid), jnp.asarray(probe, jnp.int32), k=k
                )
                nbr, nd2, w, seen = (np.asarray(a) for a in (nbr, nd2, w, seen))
                # both sides as the program writes them: an edge of weight 0
                # points at its own slot
                want = np.where(w > 0, nbr + lo, slots[:, None])
                knn_apart += int(np.sum(np.any(np.sort(gi, 1) != np.sort(want, 1), axis=1)[valid]))
                weight_apart += int(np.sum((np.abs(gw - w) > WEIGHT_TOL)[valid]))
                # the build's edges of weight > 0 against the reference's
                # neighbour of the same rank
                real = (gw > 0) & valid[:, None]
                with np.errstate(invalid="ignore"):  # inf - inf off the compared edges
                    gap = np.abs(seen - nd2) / np.maximum(nd2, np.finfo(np.float32).tiny)
                if real.any():
                    d_gap = max(d_gap, float(np.max(gap[real])))
        out.update(knn_apart=knn_apart, knn_d_gap=d_gap, weight_apart=weight_apart)
        return out
