"""round_us.fit: time per s-step round, in us.

The program's ``solve`` spans (repro.obs host spans, blocking on alpha)
summed over the traced fits, divided by the rounds they ran
(``FitResult.rounds_run``).  Moves ``fit_s``.
"""


def read(ctx):
    solve = rounds = 0
    for f in ctx.driver.fits:
        s = sum(t1 - t0 for name, t0, t1 in f.spans if name == "solve")
        if s:
            solve += s
            rounds += f.rounds
    return 1e6 * solve / rounds if rounds else None
