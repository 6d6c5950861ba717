"""The least-work counts on a toy graph, against hand counts."""
import numpy as np
import pytest

from bench import work

PEAK = {"flops_per_s": 100.0, "bytes_per_s": 10.0}


def test_spmm_counts_by_hand():
    # 5 edges, 4 source and 3 output rows of 2 features, a batch of 3.
    flops, nbytes = work.spmm(5, 4, 3, 2, 3)
    assert flops == 2 * 5 * 2 * 3                 # 60
    assert nbytes == 4 * 3 * (4 + 3) * 2 + 8 * 5  # 168 + 40
    # 6 halo rows as uint8 codes + 8 bytes of (scale, min) each.
    _, with_halo = work.spmm(5, 4, 3, 2, 3, halo=6)
    assert with_halo - nbytes == 3 * 6 * (2 + 8)


def test_served_model_gcn_by_hand():
    # A GCN [2, 3, 1] on 4 vertices and 6 directed edges, batch of 2.
    flops, nbytes = work.served_model(work.layer_widths("gcn", [2, 3, 1]),
                                      v=4, e=6, b=2)
    f1 = 2 * 6 * 2 * 2 + 2 * 4 * 2 * 3 * 2    # neighbour sum + dense
    f2 = 2 * 6 * 3 * 2 + 2 * 4 * 3 * 1 * 2
    assert flops == f1 + f2
    b1 = 4 * 2 * 4 * (2 + 3) + 8 * 6 + 4 * (2 * 3 + 3)
    b2 = 4 * 2 * 4 * (3 + 1) + 8 * 6 + 4 * (3 * 1 + 1)
    assert nbytes == b1 + b2


def test_sage_weights_stack_mean_over_self():
    assert list(work.layer_widths("sage", [100, 64, 2])) == [
        (100, 200, 64, 100, False), (64, 128, 2, 64, False)]
    f_gcn, _ = work.served_model(work.layer_widths("gcn", [4, 4]),
                                 v=10, e=0, b=1)
    f_sage, _ = work.served_model(work.layer_widths("sage", [4, 4]),
                                  v=10, e=0, b=1)
    assert f_sage == 2 * f_gcn


def test_served_spmm_sums_layers():
    flops, nbytes = work.served_spmm(work.layer_widths("gcn", [2, 3, 1]),
                                     v=4, e=6, b=2)
    assert flops == work.spmm(6, 4, 4, 2, 2)[0] + work.spmm(6, 4, 4, 3, 2)[0]
    assert nbytes == work.spmm(6, 4, 4, 2, 2)[1] + work.spmm(6, 4, 4, 3, 2)[1]


def test_gat_counts_by_hand():
    """GAT [2, 4, 1], two heads then three averaged, a skip on the first
    layer, on 4 vertices and 6 directed edges, batch of 2: each sum runs
    over the heads' columns and 6 + 4 edges, self loops included."""
    layers = work.layer_widths("gat", [2, 4, 1], heads=[2, 3],
                               skip=[True, False])
    assert [(s.fi, s.fo, s.sum_width, s.self_loops) for s in layers] == [
        (2, 4, 4, True), (4, 1, 3, True)]
    flops, nbytes = work.served_spmm(layers, v=4, e=6, b=2)
    assert flops == 2 * 10 * 4 * 2 + 2 * 10 * 3 * 2
    assert nbytes == work.spmm(10, 4, 4, 4, 2)[1] + work.spmm(10, 4, 4, 3, 2)[1]
    # Transform, skip, two score dot products per vertex and head, six
    # element-wise operations per edge and head.
    dense1 = 2 * (2 * 4 * 2 * 4 + 2 * 4 * 2 * 4 + 4 * 4 * 4 + 6 * 10 * 2)
    dense2 = 2 * (2 * 4 * 4 * 3 + 4 * 4 * 3 + 6 * 10 * 3)
    flops, nbytes = work.served_model(layers, v=4, e=6, b=2)
    assert flops == 2 * 10 * 4 * 2 + dense1 + 2 * 10 * 3 * 2 + dense2
    b1 = (4 * 2 * 4 * (2 + 4) + 8 * 10 + 4 * (2 * 4 + 2 * 4 + 2 * 4)
          + 2 * 4 * (8 * 4 + 16 * 2))
    b2 = (4 * 2 * 4 * (4 + 1) + 8 * 10 + 4 * (4 * 3 + 2 * 3)
          + 2 * 4 * (8 * 3 + 16 * 3))
    assert nbytes == b1 + b2


def test_least_seconds_takes_the_binding_roof():
    assert work.least_seconds(1000.0, 10.0, PEAK) == pytest.approx(10.0)
    assert work.least_seconds(10.0, 1000.0, PEAK) == pytest.approx(100.0)


def test_halo_pairs_counts_each_reader_once():
    # Fog 0 owns 0, 1; fog 1 owns 2; fog 2 owns 3.
    owner = np.array([0, 0, 1, 2])
    s = np.array([0, 0, 0, 1, 2, 1, 3])
    r = np.array([2, 2, 3, 0, 0, 0, 3])
    # (0 -> fog 1) twice counts once, (0 -> fog 2), (2 -> fog 0); the
    # edges 1 -> 0 and 3 -> 3 stay inside one fog.
    assert work.halo_pairs(s, r, owner) == 3
