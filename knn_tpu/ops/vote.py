"""Top-K majority vote with the reference's exact tie-break semantics.

The reference votes with a per-query class histogram and a *running* argmax
with strict ``>`` over neighbors visited in ascending-distance order
(knn_mpi.cpp:324-336 val, :367-379 test): the winner is the first label to
*reach* the final maximum count.  Equivalently: among labels whose final
count equals the max, the one whose cumulative count hits the max earliest
in distance order wins.  That formulation vectorizes: one-hot -> cumsum ->
first position where a label's cumulative count reaches the global max.

This matters for parity: "fixing" the tie-break silently changes predicted
labels (SURVEY.md §7 hard part (d)).  Unlike the reference, out-of-range
labels cannot corrupt memory (knn_mpi.cpp:330 indexes the vote array with an
unchecked label) — one_hot simply drops them.

:func:`softmax_vote` is the second vote, the weighted one of the k-NN
evaluation protocol (DINO's ``eval_knn.py``): every neighbour adds its
weight to its label's total and the classes are ranked by total.  A sum
does not read the neighbours' order, so it has no tie-break among them;
equal totals rank by class id.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax


def majority_vote(neighbor_labels: jax.Array, num_classes: int) -> jax.Array:
    """Winner label per query.

    Args:
      neighbor_labels: int array [..., K], neighbors in ascending-distance
        order (as returned by ops.topk), values in [0, num_classes).
      num_classes: the reference's ``class_cnt`` (knn_mpi.cpp:113).

    Returns:
      int32 array [...] of winning labels, reference tie-break semantics.

    The one-hot and its cumulative sum are ``[..., K, C]`` int32 each:
    328 MB at 4,096 x 20 x 1,000.  So where there are more classes than
    neighbours the same rule is read off the K x K table of equal labels
    (:func:`_majority_vote_pairs`, ``[..., K, K]``: 6.5 MB there), which
    answers as this form does to the entry (tests/test_imagenet_vote.py
    holds the two together on tie-heavy labels).
    """
    k = neighbor_labels.shape[-1]
    if num_classes > k:
        return _majority_vote_pairs(neighbor_labels, num_classes)
    onehot = jax.nn.one_hot(neighbor_labels, num_classes, dtype=jnp.int32)  # [..., K, C]
    counts = jnp.sum(onehot, axis=-2)  # [..., C]
    max_count = jnp.max(counts, axis=-1, keepdims=True)  # [..., 1]

    cum = jnp.cumsum(onehot, axis=-2)  # [..., K, C]
    # The step at which a label's count *becomes* the final max: cumulative
    # count equals max AND this step incremented that label.
    reach = (cum == max_count[..., None, :]) & (onehot == 1)
    steps = lax.broadcasted_iota(jnp.int32, reach.shape, reach.ndim - 2)
    first_reach = jnp.min(jnp.where(reach, steps, k), axis=-2)  # [..., C]
    # Labels that never reach the max get sentinel k; among reachers the
    # reach steps are distinct (one increment per step), so argmin is unique.
    return jnp.argmin(jnp.where(counts == max_count, first_reach, k + 1), axis=-1).astype(
        jnp.int32
    )


def _majority_vote_pairs(neighbor_labels: jax.Array, num_classes: int
                         ) -> jax.Array:
    """:func:`majority_vote` without a class axis: step a's label has
    ``cum[a]`` votes once step a is counted and ``cnt[a]`` in the end, both
    sums over the K x K table of equal labels; the winner is the label of
    the first step at which a count becomes the final maximum.  A label
    outside ``[0, num_classes)`` votes for nothing, as one_hot drops it,
    and where no label is inside the answer is class 0, as there."""
    lab = neighbor_labels
    valid = (lab >= 0) & (lab < num_classes)
    same = ((lab[..., :, None] == lab[..., None, :])
            & valid[..., :, None] & valid[..., None, :])  # [..., K, K]
    step = lax.broadcasted_iota(jnp.int32, same.shape, same.ndim - 1)
    upto = step <= lax.broadcasted_iota(jnp.int32, same.shape, same.ndim - 2)
    cnt = jnp.sum(same, axis=-1, dtype=jnp.int32)  # [..., K]
    cum = jnp.sum(same & upto, axis=-1, dtype=jnp.int32)
    reach = valid & (cum == jnp.max(cnt, axis=-1, keepdims=True))
    first = jnp.argmax(reach, axis=-1)
    won = jnp.take_along_axis(lab, first[..., None], axis=-1)[..., 0]
    return jnp.where(reach.any(axis=-1), won, 0).astype(jnp.int32)


#: ln 2 in two float32 parts: the first has nine significant bits, so its
#: product with a whole number under 2^15 is exact (Cody and Waite)
_LN2_HI, _LN2_LO = 0.693359375, -2.12194440e-4
#: Cephes' ``expf`` polynomial for exp(r) - 1 - r over r^2, |r| <= ln 2 / 2
_EXP_POLY = (1.9875691500e-4, 1.3981999507e-3, 8.3334519073e-3,
             4.1665795894e-2, 1.6666665459e-1, 5.0000001201e-1)


def exp_weight(x: jax.Array) -> jax.Array:
    """``exp(x)`` of float32 ``x`` in ``[-87, 0]`` by float32 multiplies
    and adds alone, within 2 ulp (1.3e-7 relative; held against float64
    by tests/test_imagenet_vote.py): ``x = n ln 2 + r`` with the
    reduction in two parts, a degree-7 polynomial in r, and 2^n laid into
    the exponent bits.  ``jnp.exp`` on a TPU v5e goes through the
    chip's transcendental unit and read 3.5e-6 off (30 ulp) in the vote's
    totals (root PERF.md section 6, PR 48), which is the whole of a
    float32 control's error: a weight has to be better than that for
    ``correct`` to tell the two apart."""
    x = x.astype(jnp.float32)
    n = jnp.round(x * jnp.float32(1.4426950408889634))
    r = (x - n * jnp.float32(_LN2_HI)) - n * jnp.float32(_LN2_LO)
    p = jnp.float32(_EXP_POLY[0])
    for c in _EXP_POLY[1:]:
        p = p * r + jnp.float32(c)
    y = p * (r * r) + r + jnp.float32(1.0)
    scale = lax.bitcast_convert_type(
        (n.astype(jnp.int32) + 127) << 23, jnp.float32)
    return y * scale


def softmax_vote(neighbor_labels: jax.Array, weights: jax.Array,
                 classes_out: int) -> Tuple[jax.Array, jax.Array]:
    """The weighted vote: class totals over the K neighbours and their
    first ``classes_out`` classes.

    Args:
      neighbor_labels: int array [..., K], any order.
      weights: float array [..., K], each neighbour's weight (the caller's
        ``exp(similarity / T)``); a neighbour of weight 0 votes for nothing.
      classes_out: how many classes to return.

    Returns:
      (classes [..., classes_out] int32, totals [..., classes_out]): the
      classes with a total above 0 in lexicographic (-total, class) order,
      padded with class -1 at total 0.

    No ``[..., K, C]`` array: a neighbour's total is the sum of the
    weights of the neighbours that share its label (the K x K table of
    equal labels), the first neighbour of each label stands for its class,
    and one two-key sort of K entries ranks them.
    """
    lab = neighbor_labels.astype(jnp.int32)
    k = lab.shape[-1]
    same = lab[..., :, None] == lab[..., None, :]  # [..., K, K]
    totals = jnp.sum(jnp.where(same, weights[..., None, :], 0), axis=-1)
    a = lax.broadcasted_iota(jnp.int32, same.shape, same.ndim - 2)
    b = lax.broadcasted_iota(jnp.int32, same.shape, same.ndim - 1)
    stands = ~(same & (b < a)).any(axis=-1) & (totals > 0)
    neg, cls = lax.sort(
        (jnp.where(stands, -totals, jnp.inf),
         jnp.where(stands, lab, jnp.iinfo(jnp.int32).max)),
        dimension=lab.ndim - 1, num_keys=2)
    if classes_out > k:
        pad = [(0, 0)] * (lab.ndim - 1) + [(0, classes_out - k)]
        neg = jnp.pad(neg, pad, constant_values=jnp.inf)
        cls = jnp.pad(cls, pad, constant_values=jnp.iinfo(jnp.int32).max)
    neg, cls = neg[..., :classes_out], cls[..., :classes_out]
    there = neg < jnp.inf
    return (jnp.where(there, cls, -1),
            jnp.where(there, -neg, jnp.zeros((), totals.dtype)))


def vote_counts(neighbor_labels: jax.Array, num_classes: int) -> jax.Array:
    """Class histogram over the K neighbors, [..., num_classes] int32."""
    return jnp.sum(jax.nn.one_hot(neighbor_labels, num_classes, dtype=jnp.int32), axis=-2)
