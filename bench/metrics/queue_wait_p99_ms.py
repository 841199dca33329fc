"""99th percentile (nearest rank) of the scheduler's submit-to-dispatch
wait (``InferResult.queue_wait_ms``) over the window's answered requests."""

import numpy as np

from bench.metrics._common import SENT, nearest_rank


def read(ctx):
    r = ctx.records
    inside = (r[:, SENT] >= ctx.t0) & (r[:, SENT] < ctx.t1)
    q = ctx.queue_wait_ms[inside]
    q = q[~np.isnan(q)]
    return nearest_rank(q, 0.99) if len(q) else None
