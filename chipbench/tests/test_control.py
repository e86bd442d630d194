"""The control and the faults, at a size a test run can hold.

The control (the reference ranking in bfloat16 in the program's place)
has to come out as not correct, and so has every fault of the timed path
that a serving cell can have: an answer altered where it is produced, half
of the batch left out, and the descent run at another operating point.
(A cell of one chip has no exchange between chips; serving has no training
state to leave unchanged.)  A fault of the build, which the program's
search and the forest reference would both read, has to come out as not
correct too."""
import dataclasses

import numpy as np
import pytest

from chipbench import check, control
from chipbench.tests.test_rehearsal import CLOSED, OPEN, TINY, TINY_ISS, rehearse


def bf16_numpy(metric, q, rows):
    """bfloat16 distances on the host (the chip's control runs on device)."""
    import ml_dtypes
    bf = ml_dtypes.bfloat16
    qb = q.astype(bf).astype(np.float32)
    xb = rows.astype(bf).astype(np.float32)
    if metric == "l2":
        terms = ((xb - qb) ** 2).astype(bf)
    else:
        terms = ((xb - qb) ** 2 / (xb + qb + 1e-12)).astype(bf)
    acc = np.zeros(len(rows), np.float32)
    for j in range(terms.shape[1]):             # a bfloat16 running sum
        acc = (acc + terms[:, j].astype(np.float32)).astype(bf).astype(
            np.float32)
    return acc


@pytest.mark.parametrize("config", [TINY, TINY_ISS], ids=["l2", "chi2"])
def test_control_is_not_correct(config, tmp_path):
    out = rehearse(config, OPEN, tmp_path)
    assert check.passed(out["checks"])
    readings = control.readings(config, out["run"], dist_fn=bf16_numpy)
    assert readings["dist_err"] > 10 * config["limits"]["dist_err"]
    limits = {n: (c["limit"], c["pass_if"]) for n, c in out["checks"].items()}
    values = {"missing": 0, "partition_faults": 0, "recall_at_10": 1.0,
              **readings}
    assert not check.passed(check.checks(values, limits))


class Broken:
    """An index whose search is broken in one way."""

    def __init__(self, index, how):
        self._index, self.how = index, how

    def __getattr__(self, name):
        return getattr(self._index, name)

    def search(self, queries, params=None, **kw):
        if self.how == "descent":          # another operating point
            params = dataclasses.replace(params, n_probes=1)
        d, i = map(np.array, self._index.search(queries, params, **kw))
        if self.how == "altered":          # one answer changed at its source
            i[0, 0] = (i[0, 0] + 1) % self._index.n_rows
        elif self.how == "half":           # half of the batch left out:
            h = len(queries) // 2          # its rows get the other half's
            d[h:], i[h:] = d[:len(queries) - h], i[:len(queries) - h]
        return d, i


@pytest.mark.parametrize("how", ["altered", "half", "descent"])
def test_faults_are_not_correct(how, tmp_path):
    out = rehearse(TINY, CLOSED, tmp_path,
                   fault=lambda index: Broken(index, how))
    assert not check.passed(out["checks"]), out["checks"]


def break_build(index, how):
    """Corrupt the built forest in place, where the search reads it."""
    engine = index._primary_engine
    f = engine.forest
    if how == "threshold":          # one root split moved off its rows
        thresh = np.array(f.thresh)
        thresh[0, 0] += 0.25 * abs(thresh[0, 0]) + 0.05
        engine.forest = f._replace(thresh=thresh)
    elif how == "perm":             # two rows of different leaves swapped
        perm = np.array(f.perm)
        perm[0, [0, -1]] = perm[0, [-1, 0]]
        engine.forest = f._replace(perm=perm)
    elif how == "truncated":        # a leaf list cut short
        count = np.array(f.leaf_count)
        count[0, np.argmax(count[0])] -= 1
        engine.forest = f._replace(leaf_count=count)
    return index


@pytest.mark.parametrize("how", ["threshold", "perm", "truncated"])
def test_build_faults_are_not_correct(how, tmp_path):
    out = rehearse(TINY, CLOSED, tmp_path,
                   fault=lambda index: break_build(index, how))
    assert out["checks"]["partition_faults"]["value"] > 0
    assert not check.passed(out["checks"]), out["checks"]
