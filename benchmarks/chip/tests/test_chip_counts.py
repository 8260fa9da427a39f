"""``lib/counts.py`` against the kernel registry's cost models where both
count the same work (the forward passes, at the registry's shapes)."""

import pytest

from lib import counts


def _registry_cost(name, sig):
    from repro.kernels import registry

    return registry.get(name).cost_model(sig)


@pytest.mark.parametrize("n,m,d", [(96, 128, 64), (7324, 7324, 768), (16384, 16, 1024)])
def test_pairwise_matches_registry(n, m, d):
    sig = (((n, d), "float32"), ((m, d), "float32"))
    assert counts.pairwise(n, m, d) == _registry_cost("pairwise", sig)


@pytest.mark.parametrize("n,K,d", [(512, 256, 64), (93750, 16, 768), (16384, 8192, 1024)])
def test_kmeans_assign_matches_registry(n, K, d):
    sig = (((n, d), "float32"), ((K, d), "float32"))
    assert counts.kmeans_assign(n, K, d) == _registry_cost("kmeans_assign", sig)


@pytest.mark.parametrize("B,k,S,K,d", [(8192, 15, 16, 8192, 2), (8192, 15, 16, 4096, 2), (100, 5, 4, 33, 2)])
def test_nomad_step_forward_matches_registry(B, k, S, K, d):
    from repro.kernels.nomad_step.ops import _sig

    assert counts.nomad_step_fwd(B, k, S, K, d) == _registry_cost("nomad_step", _sig(B, k, S, K, d))


def test_step_counts_backward_and_fit_flops():
    B, k, S, K, d = 8192, 15, 16, 8192, 2
    fwd, bwd = counts.nomad_step_fwd(B, k, S, K, d), counts.nomad_step_bwd(B, k, S, K, d)
    assert counts.fit_step_flops(B, k, S, K, d) == fwd["flops"] + bwd["flops"]
    # the backward reads what the forward reads, plus two (B,) vectors, and
    # writes a gradient for every position it read
    assert bwd["bytes"] - fwd["bytes"] == 4.0 * (B * d + B * k * d + B * S * d)
    # per head, the B x K mean term dominates: 5d + 7 FLOPs per pair
    assert counts.fit_step_flops(B, k, S, K, d) == pytest.approx(B * K * (5 * d + 7), rel=0.01)
