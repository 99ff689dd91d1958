"""kmv_fit_roofline.fit: the fit's own KMV's share of its roofline, in %.

The least time one KMV at the cell's (m, n, s*b) can take,
``kmv_roofline.fit``'s ``bound`` (work from the shapes alone, peaks from
``bench/peaks.json``), over the device time per round of the operations
the program names ``kmv`` inside the ``repro.solve`` annotations
(``bench/scopes.py``): the KMV inside the fit's scan, where
``kmv_roofline.fit`` times a separate jitted call.  Moves ``fit_s``.
"""


def read(ctx):
    from bench import scopes, spec

    ph = scopes.solve_phases(ctx)
    if ph is None:
        return None
    t = ph.seconds("kmv") / ph.rounds
    if t <= 0:
        return None
    c = ctx.cell.config
    o = ctx.driver.opts
    r = o["s"] * (o["b"] if c["problem"] == "krr" else 1)
    t_min, which = spec.layer_reader("kmv_roofline.fit").bound(
        c["m"], c["n"], r, ctx.peaks)
    ctx.notes.append(f"kmv_fit_roofline.fit: bound by {which}; "
                     f"{1e6 * t:.1f} us of device time per round, t_min "
                     f"{t_min:.6e} s")
    return 100.0 * t_min / t
