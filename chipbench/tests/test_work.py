"""Work counts on a forest small enough to count by hand."""
import numpy as np

from chipbench import reference, work
from chipbench.spans import SpannedIndex, real_rows


def two_trees():
    """Tree 0: root splits coordinate 0 at 0.5 into leaves 1 {0, 1} and
    2 {2, 3}.  Tree 1: root splits coordinate 1 at 0.5 into node 1, a
    split of coordinate 0 at 0.25 into leaves 3 {0} and 4 {1}, and leaf
    2 {2, 3}."""
    nodes = 5
    feat = np.zeros((2, nodes), np.int32)
    thresh = np.zeros((2, nodes), np.float32)
    child = np.full((2, nodes), -1, np.int32)
    leaf_offset = np.zeros((2, nodes), np.int32)
    leaf_count = np.zeros((2, nodes), np.int32)
    feat[0, 0], thresh[0, 0], child[0, 0] = 0, 0.5, 1
    leaf_offset[0, 1], leaf_count[0, 1] = 0, 2
    leaf_offset[0, 2], leaf_count[0, 2] = 2, 2
    feat[1, 0], thresh[1, 0], child[1, 0] = 1, 0.5, 1
    feat[1, 1], thresh[1, 1], child[1, 1] = 0, 0.25, 3
    leaf_offset[1, 2], leaf_count[1, 2] = 2, 2
    leaf_offset[1, 3], leaf_count[1, 3] = 0, 1
    leaf_offset[1, 4], leaf_count[1, 4] = 1, 1
    perm = np.array([[0, 1, 2, 3], [0, 1, 2, 3]], np.int32)
    return {"feat": feat, "thresh": thresh, "child": child, "perm": perm,
            "leaf_offset": leaf_offset, "leaf_count": leaf_count}


def test_descent_by_hand():
    f = two_trees()
    q = np.array([[0.1, 0.2, 0.0]], np.float32)       # left, left, left
    leaves, path = reference.descend(f["feat"], f["thresh"], f["child"], q,
                                     max_depth=4, n_probes=1)
    assert leaves[:, 0, 0].tolist() == [1, 3]
    assert path[:, 0, 0].tolist() == [2, 3]
    # second probe: tree 0 flips its root (margin 0.4) -> leaf 2, path 2;
    # tree 1 flips the smaller of |0.2-0.5| = 0.3 and |0.1-0.25| = 0.15,
    # depth 1 -> leaf 4, path 3
    leaves, path = reference.descend(f["feat"], f["thresh"], f["child"], q,
                                     max_depth=4, n_probes=2)
    assert leaves[:, 0].tolist() == [[1, 2], [3, 4]]
    assert path[:, 0].tolist() == [[2, 2], [3, 3]]


def test_bytes_by_hand():
    f = two_trees()
    q = np.array([[0.1, 0.2, 0.0], [0.9, 0.9, 0.0]], np.float32)
    d = q.shape[1]
    # one probe: query 0 visits 2 + 3 nodes, query 1 visits 2 + 2
    # (right at both roots); candidates {0, 1} u {0} and {2, 3} u {2, 3}
    descent, rerank = work.batch_bytes(f, q, max_depth=4, n_probes=1,
                                       leaf_pad=12)
    assert descent == 12 * (5 + 4) + 4 * 2 * d
    assert rerank == 4 * d * (2 + 2 + 2)
    # a read limit of one point per leaf: {0} u {0} and {2} u {2}
    _, rerank = work.batch_bytes(f, q, max_depth=4, n_probes=1, leaf_pad=1)
    assert rerank == 4 * d * (1 + 1 + 2)
    # two probes: query 0 reaches all four rows through 2+2+3+3 nodes
    descent, rerank = work.batch_bytes(f, q[:1], max_depth=4, n_probes=2,
                                       leaf_pad=12)
    assert descent == 12 * 10 + 4 * d
    assert rerank == 4 * d * (4 + 1)


def test_distinct_per_row():
    ids = np.array([[3, 1, 3, -1, 1], [-1, -1, -1, -1, -1], [0, 1, 2, 3, 4]])
    assert work.distinct_per_row(ids).tolist() == [2, 0, 5]


def test_padding_is_no_work():
    """ServingRuntime pads a short batch by repeating its last query; the
    spans keep only the real rows, and the work is theirs."""
    f = two_trees()
    real = np.array([[0.1, 0.2, 0.0], [0.9, 0.9, 0.0]], np.float32)
    padded = np.concatenate([real, np.repeat(real[-1:], 6, axis=0)])
    assert real_rows(padded) == 2 and real_rows(real) == 2
    assert real_rows(real[:1]) == 1

    class Fake:
        def search(self, q, params=None):
            return q.sum(axis=1), None

    proxy = SpannedIndex(Fake(), keep_batches=True)
    proxy.search(padded)
    assert np.array_equal(proxy.batches[0], real)
    assert (work.batch_bytes(f, proxy.batches[0], 4, 1, 12)
            == work.batch_bytes(f, real, 4, 1, 12))
    assert (work.batch_bytes(f, padded, 4, 1, 12)[1]
            > work.batch_bytes(f, real, 4, 1, 12)[1])


def test_partition_faults_by_hand():
    """The hand-made forest is a sound partition of rows that fit it; a
    moved threshold, a swapped point list or an overfull leaf is not."""
    f = two_trees()
    rows = np.array([[0.1, 0.1], [0.3, 0.2], [0.7, 0.8], [0.9, 0.6]],
                    np.float32)
    assert reference.partition_faults(f, rows, capacity=2, max_depth=4) == 0
    moved = dict(f, thresh=f["thresh"].copy())
    moved["thresh"][1, 1] = 0.05          # row 0 now goes right, to leaf 4
    assert reference.partition_faults(moved, rows, 2, 4) == 1
    swapped = dict(f, perm=f["perm"][:, [0, 2, 1, 3]])
    assert reference.partition_faults(swapped, rows, 2, 4) == 4
    # at capacity 1 both leaves of tree 0 and leaf 2 of tree 1 are overfull
    assert reference.partition_faults(f, rows, capacity=1, max_depth=4) == 3
