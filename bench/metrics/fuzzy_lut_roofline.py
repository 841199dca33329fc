"""Roofline share of the single-bank kernel (``fuzzy_lut_pallas``), in %,
computed as for the stacked kernel."""

from bench.metrics._common import kernel_roofline

KERNEL = "fuzzy_lut"
PATTERNS = ("fuzzy_lut_pallas", "fuzzy_lut_kernel")


def read(ctx):
    return kernel_roofline(ctx, KERNEL)
