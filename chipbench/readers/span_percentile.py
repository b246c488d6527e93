"""A percentile of one of the job's host spans, in milliseconds. A tail
needs ten samples beyond it (``min_samples``); with fewer the metric is
left out."""
import numpy as np


def read(ctx, span, q, min_samples=1):
    samples = ctx["spans"].get(span, [])
    if len(samples) < min_samples:
        return None
    return 1e3 * float(np.percentile(samples, q))
