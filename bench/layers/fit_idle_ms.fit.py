"""fit_idle_ms.fit: device idle time inside a fit, in ms per fit.

Inside each ``repro.fit`` annotation of the traced run, the time no
device operation runs, averaged over the chips and over the fits.  Also
notes idle ms per fit by the innermost ``repro.*`` span that covers each
idle gap's midpoint (``schedule``, ``train_op``,
``representation_build``, ``solve``, ...; ``none`` inside no span but
the fit), and the idle time in the window outside the fits.  Moves
``fit_s``.
"""


def read(ctx):
    from collections import defaultdict

    from bench import devtrace, scopes

    lo, hi = ctx.window
    fits = scopes.spans(ctx.trace, "repro.fit", lo, hi)
    if not fits or not ctx.trace.ops:
        return None
    inner = [(n[len("repro."):], a, b) for n, a, b in ctx.trace.host
             if n.startswith("repro.") and n != "repro.fit"]
    n_dev = len(ctx.trace.ops)
    by = defaultdict(float)
    outside = 0.0
    for ops in ctx.trace.ops.values():
        idle = (hi - lo) - devtrace.union_s(devtrace.clip(ops, lo, hi))
        for f0, f1 in fits:
            here = sorted((s for s in inner if s[1] < f1 and s[2] > f0),
                          key=lambda s: s[2] - s[1])
            edge = f0
            for a, b in devtrace.merged(devtrace.clip(ops, f0, f1)) + [
                    (f1, f1)]:
                if a > edge:
                    mid = 0.5 * (edge + a)
                    label = next((nm for nm, s, e in here
                                  if s <= mid <= e), "none")
                    by[label] += (a - edge) / n_dev
                    idle -= a - edge
                edge = max(edge, b)
        outside += idle / n_dev
    per_fit = {k: 1e3 * v / len(fits) for k, v in by.items()}
    ctx.notes.append(
        f"idle per fit by span ({len(fits)} fits): " + ", ".join(
            f"{k} {v:.3f} ms" for k, v in sorted(per_fit.items(),
                                                 key=lambda kv: -kv[1]))
        + f"; in the window outside the fits: {1e3 * outside:.3f} ms in "
          f"all (none)")
    return sum(per_fit.values())
