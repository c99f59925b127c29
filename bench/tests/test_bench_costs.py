"""Operation and byte counts of the GP kernels, against hand counts."""
import pytest

from bench import costs, peaks


@pytest.mark.parametrize("n,d", [(4, 2), (1024, 16)])
def test_nll_ops_hand_count(n, d):
    # covariance: per pair 3 per dim + 10; Cholesky n^3/3; forward solve
    # n^2; quadratic form and log-determinant 3n
    hand = n * n * (3 * d + 10) + n ** 3 // 3 + n * n + 3 * n
    assert costs.nll_ops(n, d) == hand


def test_nll_ops_at_two_shapes():
    assert costs.nll_ops(4, 2) == 16 * 16 + 21 + 16 + 12
    assert costs.nll_ops(1024, 16) == (1024 * 1024 * 58 + 357913941
                                       + 1048576 + 3072)


@pytest.mark.parametrize("n,d,want", [
    (4, 2, 4 * (8 + 8 + 2 + 2 + 16 + 4 + 1)),
    (1024, 16, 4 * (16384 + 2048 + 18 + 1048576 + 1024 + 1))])
def test_nll_bytes_hand_count(n, d, want):
    assert costs.nll_bytes(n, d) == want


def test_nll_grad_ops_adds_the_adjoint():
    n, d = 8, 3
    extra = 2 * (512 // 3) + 3 * 64 + 64 * (9 + 6) + 16
    assert costs.nll_grad_ops(n, d) == costs.nll_ops(n, d) + extra


def test_fit_ops_sums_lanes_times_steps():
    lanes = [(100, 8, 10), (50, 8, 20)]
    assert costs.fit_ops(lanes) == (10 * costs.nll_grad_ops(100, 8)
                                    + 20 * costs.nll_grad_ops(50, 8))


def test_roofline_names_its_bound():
    pk = peaks.peaks("TPU v5 lite")
    t, bound = costs.roofline_s(197e12, 1.0, pk)
    assert bound == "compute" and abs(t - 1.0) < 1e-12
    t, bound = costs.roofline_s(1.0, 819e9, pk)
    assert bound == "memory" and abs(t - 1.0) < 1e-12
    # gp_nll at 1024 rows: 0.42 GFLOP is 2.1 us at the bf16 peak, but
    # writing the 4 MiB factor L takes 5.1 us at the HBM bandwidth
    _, bound = costs.roofline_s(costs.nll_ops(1024, 16),
                                costs.nll_bytes(1024, 16), pk)
    assert bound == "memory"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
