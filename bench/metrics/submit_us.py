"""Mean host microseconds spent inside ``AsyncMultiModelServer.submit``
per request, timed by the harness around each call."""


def read(ctx):
    if not len(ctx.submit_s):
        return None
    return float(ctx.submit_s.mean()) * 1e6
