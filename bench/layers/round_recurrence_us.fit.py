"""round_recurrence_us.fit: device time of the round's local phase per
round, in us.

Device seconds of the operations the program names ``recurrence`` (the
s sequential local solves) or ``scatter`` (the update of alpha)
(``bench/scopes.py``) inside the ``repro.solve`` annotations of the
traced fits, averaged over the chips, divided by the rounds those fits
ran.  Moves ``fit_s``.
"""


def read(ctx):
    from bench import scopes

    ph = scopes.solve_phases(ctx)
    if ph is None:
        return None
    return 1e6 * ph.seconds("recurrence", "scatter") / ph.rounds
