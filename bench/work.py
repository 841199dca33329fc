"""Required work of the Pegasus model, per flow and per kernel call.

Required work is what the paper's model needs, not what an implementation
does, so a later rewrite of a kernel cannot move the yardstick. Per bank of
``K`` groups of ``v`` features, trees of depth ``d`` (``C = 2**d`` leaves)
and ``N`` outputs, per flow:

* Map: ``K * d`` comparisons (one per tree level per group);
* SumReduce: ``K * N`` adds (each group's row into the accumulator), plus
  ``N`` for the bias where the bank has one;
* bytes: the flow's input (``K * v`` float32) and output (``N`` float32)
  of each kernel call, and once per kernel call the true-size tables in
  float32: the ``K * C * N`` table rows and the ``K * (C - 1)`` thresholds
  and split features.

One-hot matrix products, padding rows, padded groups and padded columns
are not counted: the one-hot products are one way to do the lookups, and
padding then shows as a lower share.
"""

from __future__ import annotations


def bank_ops(g: dict) -> int:
    depth = g["c"].bit_length() - 1
    return g["k"] * depth + g["k"] * g["n"] + (g["n"] if g["bias"] else 0)


def bank_table_bytes(g: dict) -> int:
    return 4 * (g["k"] * g["c"] * g["n"] + 2 * g["k"] * (g["c"] - 1))


def ops_per_flow(geometry: list[dict]) -> int:
    return sum(bank_ops(g) for g in geometry)


def kernel_work(geometry: list[dict], calls: list[list[int]], flows: float,
                n_calls: float) -> tuple[float, float]:
    """``(ops, bytes)`` required of one kernel over a window in which it
    served ``flows`` flows in ``n_calls`` calls. ``calls`` lists the banks
    each of the kernel's calls covers for one chunk, in order."""
    per_chunk = len(calls)
    ops = flows * sum(bank_ops(geometry[b]) for c in calls for b in c)
    io = sum(4 * (geometry[c[0]]["k"] * geometry[c[0]]["v"]
                  + geometry[c[-1]]["n"]) for c in calls)
    tables = sum(bank_table_bytes(geometry[b]) for c in calls for b in c)
    return ops, flows * io + (n_calls / per_chunk) * tables


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple[float, str]:
    """Least time over measured time, in %, and which bound sets it."""
    t_ops = ops / peak["flops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
