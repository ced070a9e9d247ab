"""Held-out quality of a model, computed by the benchmark itself.

`auc` is a copy of `chip_smoke.auc` (the rank-sum form; ties broken by a
stable sort, which is exact enough for continuous scores).  NDCG comes
with the first ranking configuration.
"""
import numpy as np


def auc(y, score):
    order = np.argsort(score, kind="stable")
    ranks = np.empty(len(score), np.float64)
    ranks[order] = np.arange(1, len(score) + 1)
    npos = float(np.sum(y > 0))
    nneg = len(y) - npos
    return (ranks[y > 0].sum() - npos * (npos + 1) / 2) / (npos * nneg)


METRICS = {"auc": auc}
