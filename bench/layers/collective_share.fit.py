"""collective_share.fit: device time in collectives, in % of the window.

Union of the intervals of collective operations (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all; ``devtrace.is_collective``)
inside the traced window, averaged over the chips, over the window.
Moves ``fit_s``.
"""


def read(ctx):
    from bench import devtrace

    lo, hi = ctx.window
    if not ctx.trace.ops:
        return None
    t = devtrace.op_seconds(ctx.trace, lo, hi, devtrace.is_collective)
    return 100.0 * t / (hi - lo)
