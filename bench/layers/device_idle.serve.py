"""device_idle.serve: the device's idle share of the traced window, in %.

100 * (1 - busy / window): busy is the union of the intervals of the
device's XLA operations inside the harness's ``bench.window``
annotation, averaged over the cell's chips.  Moves ``serve_p50_ms``.
"""


def read(ctx):
    from bench import devtrace

    return devtrace.idle_pct(ctx.trace, *ctx.window)
