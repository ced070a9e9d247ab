"""LambdaRank (NDCG) objective.

Role parity with the reference src/objective/rank_objective.hpp
(LambdarankNDCG: Init at :43-71, GetGradientsForOneQuery at :82-168,
sigmoid table at :172-197) and src/metric/dcg_calculator.cpp (label gains,
position discounts, CalMaxDCGAtK at :52-74).

TPU-first redesign: the reference runs a per-query O(n^2) pairwise loop
under OpenMP with a precomputed sigmoid lookup table.  Here every query
owns a row of SLOTS in one of a few BUCKETS of queries of similar length
(`plan_buckets`: at most `MAX_BUCKETS`, edges chosen from the query sizes
at `init` so that the pair slots computed, sum over buckets of queries x
length^2, are as few as that many buckets allow), and the pairwise lambda
computation is one vectorized [length, length, queries] tensor program a
bucket, scanned in chunks with `lax.map` to bound the transient memory.
Its cost follows the pairs that exist, sum(n_q^2), not queries x
longest^2: on the MS LTR shape (18,919 queries of up to 1,251 rows) the
program computes 1.9 slots for each pair that exists where the padded
layout computed 67, in 0.011 s an iteration on a v5e chip where that
took 0.75 (PERF.md section 6, PR 31).

A document's pair terms are summed along ONE axis: for document a and
every other document b of its query, the pair's lambda enters a's
gradient with the sign of (label_a > label_b) and the pair's hessian
unsigned, so the program is one reduction a bucket and XLA fuses it into
one pass with no [length, length, queries] temporary in HBM.  Each pair's
value is the reference's, computed from the pair's high and low document
as `rank_objective.hpp` does; only the order of the sum differs.  Ranks
come from counting, rank_a = #{b: s_b > s_a} + #{b < a: s_b == s_a}: the
stable descending sort's positions (ties in original order) with no
sort.  The sigmoid table becomes the exact expression (transcendentals
are cheap on the VPU; the table is a CPU trick).

Rows reach their slots and gradients come back by ONE permutation each
way: `slot_of_row` is known at `init` (a query's rows are contiguous), so
scores scatter into the slot vector and gradients gather out of it
through the same index vector, in whatever order the caller holds the
rows (`gradients_in_order`: the fast path's payload sits in partition
order and hands its index column).  Each is a pass of per-element
addressing over the 2.27M rows of the MS LTR shape, 13-18 ms on a v5e
chip (the slot lookup, the scatter, one gather of gradient and hessian
together): 0.041 s an iteration where the padded layout's seven passes
over ten times the elements took 0.64.  All of it float32.
"""
from __future__ import annotations

from typing import List, NamedTuple, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..runtime import tracing
from ..utils.log import Log
from .base import ObjectiveFunction

# reference dcg_calculator.cpp:30-38 — label_gain[i] = 2^i - 1, 31 levels
_MAX_LABEL = 31

#: buckets of query lengths at most: each is a copy of the pairwise body
#: for XLA to compile into the fused step
MAX_BUCKETS = 6
#: pair slots one chunk of a bucket's `lax.map` holds (64 MB in float32
#: for each temporary XLA does not fuse away)
CHUNK_SLOTS = 1 << 24
#: a score no row has: what an empty slot holds, so it ranks last
_EMPTY = -1e30


def default_label_gain() -> np.ndarray:
    return np.array([(1 << i) - 1 for i in range(_MAX_LABEL)], dtype=np.float64)


def position_discounts(n: int) -> np.ndarray:
    """discount[i] = 1/log2(2+i) (dcg_calculator.cpp:44-48)."""
    return 1.0 / np.log2(2.0 + np.arange(n, dtype=np.float64))


def max_dcg_at_k(k: int, labels: np.ndarray, label_gain: np.ndarray) -> float:
    """Ideal DCG@k: labels sorted descending (CalMaxDCGAtK)."""
    k = min(k, len(labels))
    top = np.sort(labels.astype(np.int64))[::-1][:k]
    disc = position_discounts(k)
    return float(np.sum(label_gain[top] * disc))


def inverse_max_dcg(k: int, labels: np.ndarray, sizes: np.ndarray,
                    label_gain: np.ndarray) -> np.ndarray:
    """1 / `max_dcg_at_k` of every query (0 where it is 0), all queries
    at once: rows sorted by (query, label descending), the first k of
    each query summed."""
    sizes = np.asarray(sizes, np.int64)
    q = np.repeat(np.arange(len(sizes)), sizes)
    lab = labels.astype(np.int64)
    order = np.lexsort((-lab, q))
    pos = np.arange(len(q)) - np.repeat(np.cumsum(sizes) - sizes, sizes)
    top = pos < k
    dcg = np.bincount(q[top], weights=label_gain[lab[order][top]]
                      / np.log2(2.0 + pos[top]), minlength=len(sizes))
    return np.where(dcg > 0.0, 1.0 / np.where(dcg > 0.0, dcg, 1.0), 0.0)


def check_rank_label(label: np.ndarray, num_levels: int) -> None:
    """DCGCalculator::CheckLabel semantics."""
    if np.any(np.abs(label - np.round(label)) > 1e-15):
        Log.fatal("label should be int type for ranking task")
    if np.any(label < 0) or np.any(label >= num_levels):
        Log.fatal("label exceeds the max range of label_gain")


def _round_up(x, m):
    return -(-x // m) * m


class Bucket(NamedTuple):
    """Queries of `lo < size <= length` share one pairwise program of
    [length, length, width] slots a chunk, `chunks` chunks of `width`
    queries (the last ones empty), its slots starting at `offset`."""
    length: int
    queries: np.ndarray     # their indices, in original order
    chunks: int
    width: int
    offset: int

    @property
    def slots(self) -> int:
        return self.chunks * self.width * self.length

    @property
    def pair_slots(self) -> int:
        return self.chunks * self.width * self.length * self.length


def plan_buckets(sizes: np.ndarray, max_buckets: int = MAX_BUCKETS
                 ) -> List[Tuple[int, int]]:
    """[(lo, length)] ascending: at most `max_buckets` ranges of query
    sizes `lo < n <= length`, every size in one of them, chosen to make
    sum(queries x length^2) least, the queries of a bucket counted in
    whole lane tiles of 128 (a dynamic program over the distinct sizes
    rounded up to the sublane's 8).  `max_buckets=1` is the padded
    layout: every query as long as the longest."""
    sizes = np.asarray(sizes, np.int64)
    cand, count = np.unique(_round_up(sizes, 8), return_counts=True)
    m = len(cand)
    upto = np.concatenate([[0], np.cumsum(count)])
    # cost[i, j]: one bucket over candidates i..j-1, as long as the last,
    # its queries side by side in whole lane tiles of 128
    n_in = _round_up(upto[None, :] - upto[:, None], 128)
    length2 = np.concatenate([[0], cand * cand]).astype(np.float64)
    cost = np.where(np.arange(m + 1)[:, None] < np.arange(m + 1)[None, :],
                    n_in * length2[None, :], np.inf)
    best = np.full((max_buckets + 1, m + 1), np.inf)
    best[0, 0] = 0.0
    cut = np.zeros((max_buckets + 1, m + 1), np.int64)
    for b in range(1, max_buckets + 1):
        total = best[b - 1][:, None] + cost
        cut[b] = np.argmin(total, axis=0)
        best[b] = total[cut[b], np.arange(m + 1)]
    b = int(np.argmin(best[:, m]))
    edges, j = [], m
    while j > 0:
        i = int(cut[b, j])
        edges.append((int(cand[i - 1]) if i else 0, int(cand[j - 1])))
        j, b = i, b - 1
    return edges[::-1]


def _pair_terms(s, lab, gain, inv_max_dcg, sigma):
    """(gradient, hessian) [S, W] of one chunk of `W` queries side by
    side, `S` slots each: `s` scores (`_EMPTY` in an empty slot), `lab`
    labels (NaN in an empty slot, so no comparison with one holds),
    `gain` their gains, all [S, W]; `inv_max_dcg` [W].

    The pair tensor is [S (b), S (a), W]: document a along the second
    axis gathers its terms over every b along the first, which is the
    cheapest reduction the chip has (whole vector registers added, the
    queries in lanes)."""
    s_a, s_b = s[None, :, :], s[:, None, :]
    pos = lax.iota(jnp.int32, s.shape[0])
    before = (pos[:, None] < pos[None, :])[:, :, None]        # b < a
    ahead = (s_b > s_a) | ((s_b == s_a) & before)
    rank = jnp.sum(ahead, axis=0, dtype=jnp.int32)
    disc = 1.0 / jnp.log2(2.0 + rank.astype(jnp.float32))
    real = lab == lab
    best = jnp.max(s, axis=0)
    worst = jnp.min(jnp.where(real, s, -_EMPTY), axis=0)
    has_range = (best != worst)[None, None, :]

    lab_a, lab_b = lab[None, :, :], lab[:, None, :]
    a_high = lab_a > lab_b
    either = a_high | (lab_b > lab_a)
    d = s_a - s_b
    gap = gain[None, :, :] - gain[:, None, :]
    # the pair's (high, low) document: its score and gain differences
    ds = jnp.where(a_high, d, -d)
    dcg_gap = jnp.where(a_high, gap, -gap)
    paired_disc = jnp.abs(disc[None, :, :] - disc[:, None, :])
    delta = dcg_gap * paired_disc * inv_max_dcg[None, None, :]
    delta = jnp.where(has_range, delta / (0.01 + jnp.abs(d)), delta)
    sig = 2.0 / (1.0 + jnp.exp(2.0 * sigma * ds))
    p_lambda = -delta * sig
    p_hess = 2.0 * delta * sig * (2.0 - sig)
    # lambda_high += p, lambda_low -= p; both hessians += h
    g = jnp.sum(jnp.where(either, jnp.where(a_high, p_lambda, -p_lambda),
                          0.0), axis=0)
    h = jnp.sum(jnp.where(either, p_hess, 0.0), axis=0)
    return g, h


class LambdarankNDCG(ObjectiveFunction):
    is_rowwise = False  # pairwise within query groups
    name = "lambdarank"
    is_constant_hessian = False

    def __init__(self, config):
        super().__init__(config)
        self.sigmoid = float(getattr(config, "sigmoid", 1.0))
        if self.sigmoid <= 0.0:
            Log.fatal("Sigmoid param %f should be greater than zero", self.sigmoid)
        gains = list(getattr(config, "label_gain", ()) or ())
        self.label_gain = np.asarray(gains, np.float64) if gains else default_label_gain()
        self.optimize_pos_at = int(getattr(config, "max_position", 20))

    def init(self, label, weight, query_boundaries=None) -> None:
        with tracing.span("objective/init"):
            self._init(label, weight, query_boundaries)

    def _init(self, label, weight, query_boundaries) -> None:
        super().init(label, weight, query_boundaries)
        if query_boundaries is None:
            Log.fatal("Lambdarank tasks require query information")
        qb = np.asarray(query_boundaries, dtype=np.int64)
        check_rank_label(self.label, len(self.label_gain))
        sizes = np.diff(qb)
        n = int(qb[-1])

        # the slot of every row: bucket's offset + its place in the
        # bucket's [chunk, slot in query, query in chunk] block
        self.buckets: List[Bucket] = []
        slot_of_query = np.zeros(len(sizes), np.int64)
        stride_of_query = np.zeros(len(sizes), np.int64)
        offset = 0
        for lo, length in plan_buckets(sizes):
            queries = np.flatnonzero((sizes > lo) & (sizes <= length))
            # whole lane tiles of queries, in as many even chunks as
            # CHUNK_SLOTS asks for
            most = max(128, CHUNK_SLOTS // (length * length) // 128 * 128)
            chunks = -(-len(queries) // most)
            width = _round_up(-(-len(queries) // chunks), 128)
            bucket = Bucket(length, queries, chunks, width, offset)
            place = np.arange(len(queries))
            slot_of_query[queries] = offset \
                + (place // width) * (length * width) + place % width
            stride_of_query[queries] = width
            self.buckets.append(bucket)
            offset += bucket.slots
        self.num_slots = offset
        q_of_row = np.repeat(np.arange(len(sizes)), sizes)
        in_query = np.arange(n) - qb[q_of_row]
        slot_of_row = slot_of_query[q_of_row] \
            + in_query * stride_of_query[q_of_row]
        self.counters = {
            "pair_slots": int(sum(b.pair_slots for b in self.buckets)),
            "pairs": int((sizes * sizes).sum()),
            "buckets": len(self.buckets)}

        def by_slot(values, empty):
            out = np.full(self.num_slots, empty, np.float32)
            out[slot_of_row] = values[:n]
            return jnp.asarray(out)

        self.label_slots = by_slot(self.label, np.nan)
        self.gain_slots = by_slot(self.label_gain[self.label.astype(np.int64)],
                                  0.0)
        # per-doc weights multiply at the end (rank_objective.hpp:162-167)
        self.weight_slots = None if self.weight is None \
            else by_slot(self.weight, 0.0)
        inv = inverse_max_dcg(self.optimize_pos_at, self.label[:n], sizes,
                              self.label_gain)
        self.inv_max_dcg = []
        for b in self.buckets:
            padded = np.zeros(b.chunks * b.width, np.float32)
            padded[:len(b.queries)] = inv[b.queries]
            self.inv_max_dcg.append(
                jnp.asarray(padded.reshape(b.chunks, b.width)))
        # rows past the last query (the data set's padding) and the fast
        # path's guard rows have no slot: they read the table's last
        # entry, `num_slots`, which the scatter drops and which reads the
        # zero appended for the gather
        self.num_rows = n
        self.slot_of_row = jnp.asarray(
            np.append(slot_of_row, self.num_slots).astype(np.int32))

    def _by_slot(self, s_slots):
        """(gradient, hessian) of every slot from the scores of every
        slot: a bucket at a time, a chunk of queries at a time."""
        def one_chunk(args):
            return _pair_terms(*args, self.sigmoid)

        grads, hesss = [], []
        for b, imd in zip(self.buckets, self.inv_max_dcg):
            block = (b.chunks, b.length, b.width)
            args = tuple(x[b.offset:b.offset + b.slots].reshape(block)
                         for x in (s_slots, self.label_slots,
                                   self.gain_slots)) + (imd,)
            if b.chunks == 1:
                g, h = one_chunk(tuple(x[0] for x in args))
            else:
                g, h = lax.map(one_chunk, args)
            grads.append(g.reshape(-1))
            hesss.append(h.reshape(-1))
        return jnp.concatenate(grads), jnp.concatenate(hesss)

    def gradients_in_order(self, score, row):
        """(gradient, hessian) float32 in the caller's row order:
        `score[p]` is the score of original row `row[p]` (int32; an index
        past the last query's row marks a row that is none: padding, a
        guard row; a row handed twice, as the feature-parallel learner's
        blocks do, brings the same score twice)."""
        from ..boosting.grower2 import phase
        with phase("grad_permute"):
            slot = self.slot_of_row[jnp.minimum(row, self.num_rows)]
            s_slots = jnp.full(self.num_slots, _EMPTY, jnp.float32) \
                .at[slot].set(score.astype(jnp.float32), mode="drop")
        with phase("grad_pairs"):
            g, h = self._by_slot(s_slots)
            if self.weight_slots is not None:
                g, h = g * self.weight_slots, h * self.weight_slots
        with phase("grad_permute"):
            # gradient and hessian in ONE gather (13 ms on the chip where
            # two took 32: the cost is the addressing, an element or two)
            zero = jnp.zeros(1, jnp.float32)
            both = jnp.stack([jnp.concatenate([g, zero]),
                              jnp.concatenate([h, zero])])[:, slot]
            return both[0], both[1]

    def get_gradients(self, score, label, weight):
        """Original row order: `score` [N_pad]; `label` and `weight` are
        what `init` was given and are not read again."""
        return self.gradients_in_order(
            score, jnp.arange(score.shape[0], dtype=jnp.int32))

    def to_string(self) -> str:
        return self.name
