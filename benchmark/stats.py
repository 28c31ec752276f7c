"""The arithmetic every reported number goes through (a copy of
``tpu_hpc/obs/quantiles.py``'s estimator; the yardstick may not change
with the program)."""
import statistics


def quantile(values, q):
    """Linear-interpolation quantile (numpy's default method). None on
    no samples: a reader with nothing to read returns nothing."""
    vals = sorted(values)
    if not vals:
        return None
    pos = q * (len(vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] * (1.0 - (pos - lo)) + vals[hi] * (pos - lo)


def median(values):
    return quantile(values, 0.5)


def iqr_spread(values):
    """(Q3 - Q1) / median with ``statistics.quantiles(n=4)``: the
    spread the bounds are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
