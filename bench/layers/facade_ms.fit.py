"""facade_ms.fit: the facade's own time per fit, in ms.

Mean over the traced fits of the benchmark's span around
``est.fit(A, y)`` minus the program's ``solve`` span (repro.obs host
span, blocking on alpha): input checks, schedule draw, representation
build and bookkeeping.  Moves ``fit_s``.
"""


def read(ctx):
    per = []
    for f in ctx.driver.fits:
        solve = sum(t1 - t0 for name, t0, t1 in f.spans if name == "solve")
        if solve:
            per.append((f.t1 - f.t0) - solve)
    return 1e3 * sum(per) / len(per) if per else None
