"""The reference's categorical split search as the sequential walk it is,
in float64 numpy: a COPY of `leaf_gain` and `categorical_search` of
benchmarks/tasks/binary_cat.py (which says where the walk is from), kept
here so that the program's tests stand without the benchmark;
tests/test_cat_search.py holds the two copies to the same text."""
K_EPSILON = 1e-15


def leaf_gain(sum_g, sum_h, l2):
    """The gain of keeping (sum_g, sum_h) as one leaf (no L1, no
    max_delta_step: the configuration has neither)."""
    return sum_g * sum_g / (sum_h + l2)


def categorical_search(g, h, c, sum_g, sum_h, num_data, p, full):
    """FindBestThresholdCategorical over one column's histogram: g, h, c
    [bins] float64 by bin, the LAST bin the one that takes what has no bin
    (not offered unless `full`); sum_h with the reference's 2 kEpsilon.
    Returns (raw gain, bins sent left, left (sum_g, sum_h, count)) or
    None where no split stands; the gain is before the parent's is taken
    off.  Both modes: one bin against the rest where the column has at
    most max_cat_to_onehot bins, else the sorted walk."""
    l2 = p["lambda_l2"]
    used_bin = len(c) - 1 + bool(full)
    best = None                         # (gain, bins, (lg, lh, lc))
    if len(c) <= p["max_cat_to_onehot"]:
        for t in range(used_bin):
            if c[t] < p["min_data_in_leaf"] \
                    or h[t] < p["min_sum_hessian_in_leaf"]:
                continue
            if num_data - c[t] < p["min_data_in_leaf"]:
                continue
            other_h = sum_h - h[t] - K_EPSILON
            if other_h < p["min_sum_hessian_in_leaf"]:
                continue
            gain = leaf_gain(sum_g - g[t], other_h, l2) \
                + leaf_gain(g[t], h[t] + K_EPSILON, l2)
            if best is None or gain > best[0]:
                best = (gain, [t], (g[t], h[t] + K_EPSILON, c[t]))
        return best
    kept = [t for t in range(used_bin) if c[t] >= p["cat_smooth"]]
    kept.sort(key=lambda t: g[t] / (h[t] + p["cat_smooth"]))   # stable
    used = len(kept)
    l2 += p["cat_l2"]
    max_num_cat = min(p["max_cat_threshold"], (used + 1) // 2)
    for direction, start in ((1, 0), (-1, used - 1)):
        lg, lh, lc, group = 0.0, K_EPSILON, 0.0, 0.0
        pos = start
        for i in range(min(used, max_num_cat)):
            t = kept[pos]
            pos += direction
            lg += g[t]
            lh += h[t]
            lc += c[t]
            group += c[t]
            if lc < p["min_data_in_leaf"] \
                    or lh < p["min_sum_hessian_in_leaf"]:
                continue
            rc = num_data - lc
            if rc < p["min_data_in_leaf"] or rc < p["min_data_per_group"]:
                break
            rh = sum_h - lh
            if rh < p["min_sum_hessian_in_leaf"]:
                break
            if group < p["min_data_per_group"]:
                continue
            group = 0.0
            gain = leaf_gain(lg, lh, l2) + leaf_gain(sum_g - lg, rh, l2)
            if best is None or gain > best[0]:
                walked = kept[:i + 1] if direction == 1 \
                    else kept[used - 1 - i:][::-1]
                best = (gain, list(walked), (lg, lh, lc))
    return best
