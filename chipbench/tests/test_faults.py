"""The timed path broken underneath a run: ``correct`` has to come out false.

Each fault is planted in the program where its answer is produced, and the
run is otherwise the harness's own (only its look for a TPU is stubbed).
"""

import numpy as np
import pytest
from conftest import run_tiny

from repro.api import model as model_mod
from repro.core import multi, predict


def weight_altered(mp):
    real = multi.fit_msts

    def fit_msts(*a, **k):
        res = real(*a, **k)
        res.mst_w = res.mst_w.copy()
        res.mst_w[len(res.mst_w) // 2, -1] *= 1.001
        return res

    mp.setattr(multi, "fit_msts", fit_msts)


def label_altered(mp):
    real = multi.extract_one_from_linkage

    def extract(*a, **k):
        h = real(*a, **k)
        i = int(np.flatnonzero(h.labels >= 0)[0])
        h.labels = h.labels.copy()
        h.labels[i] = -1
        return h

    mp.setattr(multi, "extract_one_from_linkage", extract)


def half_rows_left_out(mp):
    real = model_mod.FittedModel.fit.__func__

    def fit(cls, X, *a, **k):
        return real(cls, np.asarray(X)[: len(X) // 2], *a, **k)

    mp.setattr(model_mod.FittedModel, "fit", classmethod(fit))


def state_unchanged(mp):
    real = multi.fit_msts
    first = {}

    def fit_msts(*a, **k):
        res = real(*a, **k)
        res.mst_w = first.setdefault("w", np.zeros_like(res.mst_w))
        return res

    mp.setattr(multi, "fit_msts", fit_msts)


def predict_altered(mp):
    real = predict.walk_queries

    def walk(*a, **k):
        labels, probs = real(*a, **k)
        labels = labels.copy()
        labels[0] = labels[0] + 1 if labels[0] >= 0 else 0
        return labels, probs

    mp.setattr(predict, "walk_queries", walk)


def predict_half_batch(mp):
    real = predict.predict_range

    def predict_range(*a, **k):
        res = real(*a, **k)
        q = res.labels.shape[1]
        h = q - q // 2
        if q > 1:
            res.labels[:, h:] = res.labels[:, : q - h]
            res.probabilities[:, h:] = res.probabilities[:, : q - h]
        return res

    mp.setattr(predict, "predict_range", predict_range)


FAULTS = [
    ("blobs-d10.fit", weight_altered),
    ("blobs-d10.fit", label_altered),
    ("blobs-d10.fit", half_rows_left_out),
    ("blobs-d10.fit", state_unchanged),
    ("topics-d5.serve", predict_altered),
    ("topics-d5.serve", predict_half_batch),
]


@pytest.mark.parametrize("cell,fault", FAULTS, ids=[f.__name__ for _, f in FAULTS])
def test_fault_makes_the_run_incorrect(cell, fault):
    mp = pytest.MonkeyPatch()
    fault(mp)
    try:
        out, _ = run_tiny(cell, seconds=1.0)
    finally:
        mp.undo()
    assert out["correct"] is False, out["checks"]
