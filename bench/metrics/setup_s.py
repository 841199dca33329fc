"""Seconds from the start of the process to the first timed request:
making the banks and flows, building the plan, compiling or loading every
bucket the traffic reaches from the cache, and warming up."""


def read(ctx):
    return float(ctx.setup_s)
