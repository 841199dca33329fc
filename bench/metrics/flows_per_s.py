"""Flow records answered, in requests completed inside the window, per
second of the window: all the work over all the time."""

from bench.metrics._common import DONE, FLOWS, OK


def read(ctx):
    r = ctx.records
    done = (r[:, OK] == 1) & (r[:, DONE] >= ctx.t0) & (r[:, DONE] <= ctx.t1)
    return float(r[done, FLOWS].sum()) / ctx.seconds
