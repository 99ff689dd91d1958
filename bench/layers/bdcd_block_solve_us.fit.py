"""bdcd_block_solve_us.fit: device time of a round's b x b solves per
round, in us.

Device seconds of the operations the program names ``block_solve`` (the
s sequential ``jnp.linalg.solve`` of K-RR's b x b blocks, inside the
``recurrence``) inside the ``repro.solve`` annotations of the traced
fits (``bench/scopes.py``), averaged over the chips, divided by the
rounds those fits ran.  None where the program names no such scope, whose
solves ``round_recurrence_us.fit`` then reads.  Moves ``fit_s``.
"""

BLOCK_SOLVE = "block_solve"


def read(ctx):
    from bench import scopes

    ph = scopes.solve_phases(ctx)
    if ph is None:
        return None
    t = ph.seconds(BLOCK_SOLVE)
    return 1e6 * t / ph.rounds if t > 0 else None
