"""The plain reference for k-NN classification by a weighted vote and
the comparison that decides ``correct`` there.

Independent of ``knn_tpu``: numpy only, nothing imported from the
program and nothing the program made.  The semantics, for float32 rows
``t_i`` AS GIVEN (not assumed unit) with labels ``y_i`` in ``[0, C)``, a
query ``q``, ``k`` and a temperature ``T``:

- ``c_i = 1 - q.t_i / (|q| |t_i|)`` in float64, a zero norm on either
  side giving cosine 0 (``reference_cos.py``'s convention and its code:
  :func:`reference_cos.oracle_topk` finds the neighbours);
- ``N_k(q)`` = the first k rows in lexicographic ``(c_i, i)`` order;
- ``s_c = sum over i in N_k(q) with y_i = c of exp((1 - c_i) / T)``,
  float64, summed one neighbour at a time in that order;
- the answer: the classes with ``s_c > 0`` in lexicographic ``(-s_c,
  c)`` order, the first ``classes_out``, padded with class -1 at total
  0, and their totals.

This is the k-NN evaluation of DINO's ``eval_knn.py`` (Caron et al.,
ICCV 2021; DINOv2 keeps it) at one k, with three departures, none of
which changes a value: the source normalises its features before the
product, here the rows are as given and the cosine does it (same
values, and a program that forgets to normalise is caught); the
source's ``torch.sort`` leaves equal totals in no stated order, here the
lower class id wins; the source's ``retrieval_one_hot`` /  ``scatter_``
is an implementation of the sum, not semantics.

:func:`control` is the same vote computed WRONGLY in one stated way
(``CONTROLS``): the comparison has to fail each (``control_vote.py``,
the tests); no benchmark run calls it.  :func:`compare` gives the
numbers a configuration's ``limits`` name.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

import reference_cos

#: the ways control() goes wrong: a float32 ranking of unit rows with
#: float32 weights and totals (no certificate), the same in bfloat16, the
#: reference's unweighted vote over the right neighbours, and the right
#: vote at 1.5 times the temperature
CONTROLS = ("f32", "bf16", "majority", "temperature")
WRONG_TEMPERATURE_FACTOR = 1.5


def _rank_classes(totals: np.ndarray, classes_out: int
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """(classes [classes_out], totals [classes_out]) of one query's
    per-class totals [C]: present classes by (-total, class)."""
    order = np.lexsort((np.arange(totals.size), -totals))[:classes_out]
    there = totals[order] > 0
    cls = np.full(classes_out, -1, np.int64)
    tot = np.zeros(classes_out, totals.dtype)
    cls[: order.size] = np.where(there, order, -1)
    tot[: order.size] = np.where(there, totals[order], 0)
    return cls, tot


def vote(neighbour_labels: np.ndarray, weights: np.ndarray,
         num_classes: int, classes_out: int
         ) -> Tuple[np.ndarray, np.ndarray]:
    """(classes [Q, classes_out] int64, totals [Q, classes_out]) from
    each query's neighbours' labels and weights [Q, k] in rank order;
    totals in the weights' own precision, summed in that order."""
    out_c = np.empty((len(weights), classes_out), np.int64)
    out_t = np.empty((len(weights), classes_out), weights.dtype)
    for r, (lab, w) in enumerate(zip(neighbour_labels, weights)):
        totals = np.zeros(num_classes, weights.dtype)
        for y, x in zip(lab, w):  # one neighbour at a time, in rank order
            totals[y] += x
        out_c[r], out_t[r] = _rank_classes(totals, classes_out)
    return out_c, out_t


def oracle(db: np.ndarray, labels: np.ndarray, q: np.ndarray, k: int,
           temperature: float, num_classes: int, classes_out: int
           ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(classes [Q, classes_out] int64, totals [Q, classes_out] float64,
    neighbours [Q, k] int64): the module docstring's answer."""
    idx, c = reference_cos.oracle_topk(db, q, k)
    classes, totals = vote(labels[idx], np.exp((1.0 - c) / temperature),
                           num_classes, classes_out)
    return classes, totals, idx


def control(db: np.ndarray, labels: np.ndarray, q: np.ndarray, k: int,
            temperature: float, num_classes: int, classes_out: int,
            how: str) -> Tuple[np.ndarray, np.ndarray]:
    """(classes, totals as float64) of the vote gone wrong in the way
    ``how`` names (``CONTROLS``)."""
    if how not in CONTROLS:
        raise ValueError(f"control {how!r} not in {CONTROLS}")
    if how in reference_cos.PRECISIONS:
        # ranked in the lower precision, weighed and summed in float32
        idx, c = reference_cos.lowprec_topk(db, q, k, how)
        w = np.exp((np.float32(1) - c.astype(np.float32))
                   / np.float32(temperature))
    else:
        idx, c = reference_cos.oracle_topk(db, q, k)
        w = (np.ones_like(c) if how == "majority" else np.exp(
            (1.0 - c) / (temperature * WRONG_TEMPERATURE_FACTOR)))
    classes, totals = vote(labels[idx], w, num_classes, classes_out)
    return classes, totals.astype(np.float64)


def compare(got_classes: np.ndarray, got_totals: np.ndarray,
            want_classes: np.ndarray, want_totals: np.ndarray
            ) -> Dict[str, float]:
    """The numbers a comparison with the oracle gives for one block of
    queries: the (query, rank) entries whose class differs, and the
    widest ``|got - want| / want`` between the totals, rank by rank (an
    entry the oracle pads has to be a 0; anything else, or a total that
    is not finite, reads +inf)."""
    got_classes = np.asarray(got_classes)
    got_totals = np.asarray(got_totals, np.float64)
    if (got_classes.shape != want_classes.shape
            or got_totals.shape != want_totals.shape):
        raise ValueError(
            f"answer shapes {got_classes.shape}/{got_totals.shape} are not "
            f"the reference's {want_classes.shape}/{want_totals.shape}")
    there = want_totals > 0
    err = np.abs(got_totals - want_totals) / np.where(there, want_totals, 1.0)
    err = np.where(there | (got_totals == 0), err, np.inf)
    err = np.where(np.isfinite(got_totals), err, np.inf)
    return {"rows": int(got_classes.shape[0]),
            "mismatched_classes": int((got_classes != want_classes).sum()),
            "total_rel_err_max": float(err.max())}
