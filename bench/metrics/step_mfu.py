"""The whole served model's share of the chips' peak, in %: flows answered
in the traced slice times the required operations per flow
(``bench/work.py``), over the slice's length times chips times the peak
(``bench/peaks.py``)."""

from bench.metrics._common import traced_flows


def read(ctx):
    flows = traced_flows(ctx)
    if not flows or ctx.peaks is None:
        return None
    ops = flows * ctx.work.ops_per_flow(ctx.geometry)
    return 100.0 * ops / (ctx.trace["window_s"] * ctx.chips
                          * ctx.peaks["flops"])
