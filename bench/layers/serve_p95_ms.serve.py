"""serve_p95_ms.serve: the 95th percentile of request latency, in ms.

Over every request due in the traced window, from when it was due until
its values were on the host (the open loop's tail).  On one chip its
runs spread by some 20-40% (host jitter on a shared host, and stalls of
about a second in one run of six), too wide for an end-to-end bound, so
it stands here beside ``serve_p50_ms``, the median over the same
requests, which it moves.
"""


def read(ctx):
    return ctx.driver.p95_ms if ctx.driver.requests else None
