"""The least-work counts, against counts made by hand."""
import pytest

from perfbench import work


def test_sizes_count_cells_from_the_assignment():
    z = work.sizes(6, 3, 4, [0, 0, 1, 2, 2, 2])
    assert z.cells == (2, 1, 3)
    assert z.sic_pairs == 4 + 1 + 9


def test_gd_step_by_hand():
    # U=6, N=3, M=4, cells (2, 1, 3): 14 same-cell pairs.
    z = work.sizes(6, 3, 4, [0, 0, 1, 2, 2, 2])
    flops, nbytes = work.gd_step(z)
    per_link_pass = 2 * 14 * 4 + 2 * 6 * 3 * 4          # 112 + 144
    assert flops == 4 * per_link_pass
    # both gain tensors in both passes, plus shares in and gradients out
    assert nbytes == 4 * 6 * 3 * 4 * 4 + 4 * 6 * 4 * 4


def test_kernel_calls_by_hand():
    z = work.sizes(6, 3, 4, [0, 0, 1, 2, 2, 2])
    assert work.kernel_call("intra", z) == (2 * 14 * 4, 3 * 6 * 4 * 4)
    assert work.kernel_call("per_ap", z) == (2 * 72, (72 + 24 + 12) * 4)
    assert work.kernel_call("contract", z) == (2 * 72, (72 + 12 + 24) * 4)
    with pytest.raises(KeyError):
        work.kernel_call("softmax", z)


def test_least_time_takes_the_binding_bound():
    pk = work.Peak(flops_per_s=100.0, bytes_per_s=10.0)
    assert work.least_s(1000.0, 50.0, pk) == 10.0      # compute-bound
    assert work.least_s(100.0, 500.0, pk) == 50.0      # memory-bound


def test_peak_table():
    pk = work.peak("TPU v5 lite")
    assert (pk.flops_per_s, pk.bytes_per_s) == (197e12, 819e9)
    with pytest.raises(KeyError):
        work.peak("TPU v9 imaginary")


def test_sec6_gd_step_least_time():
    # U=1250, N=16, M=250 with 16 equal cells: bytes bind, 80 MB of gains.
    z = work.sizes(1250, 16, 250, [i % 16 for i in range(1250)])
    least = work.least_s(*work.gd_step(z), work.peak("TPU v5 lite"))
    assert least == pytest.approx((4 * 1250 * 16 * 250 * 4
                                   + 4 * 1250 * 250 * 4) / 819e9)
