"""Roofline share of the fused stacked kernel (``fuzzy_lut_stack_pallas``),
in %: the least time the chip could take for the required work of its
calls in the traced slice (the larger of operations over peak and bytes
over HBM bandwidth) over the summed device time of its trace events."""

from bench.metrics._common import kernel_roofline

KERNEL = "fuzzy_lut_stack"
PATTERNS = ("fuzzy_lut_stack",)


def read(ctx):
    return kernel_roofline(ctx, KERNEL)
