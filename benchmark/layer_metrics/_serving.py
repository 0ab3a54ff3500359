"""What the serving engine's readers share: the delta of a Prometheus
series (summed over its label sets) across the window. The engine's
histograms step x4, so a mean from ``_sum``/``_count`` deltas is exact
where a percentile from the buckets would not be."""


def delta(record, series):
    before, after = (record.get("counters_before"),
                     record.get("counters_after"))
    if before is None or after is None or series not in after:
        return None
    return after[series] - before.get(series, 0.0)


def ratio(record, numerator, denominator, scale=1.0):
    num, den = delta(record, numerator), delta(record, denominator)
    if num is None or not den:
        return None
    return scale * num / den
