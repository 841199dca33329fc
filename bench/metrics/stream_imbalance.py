"""Most over mean of the flows each device stream was handed over the
window (``stats()["devices"]["per_device"][i]["dispatched_flows"]``)."""


def read(ctx):
    a = ctx.stats0["devices"].get("per_device") or []
    b = ctx.stats1["devices"].get("per_device") or []
    if len(b) < 2 or len(a) != len(b):
        return None
    flows = [y["dispatched_flows"] - x["dispatched_flows"]
             for x, y in zip(a, b)]
    mean = sum(flows) / len(flows)
    return max(flows) / mean if mean > 0 else None
