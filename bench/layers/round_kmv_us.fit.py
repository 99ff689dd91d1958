"""round_kmv_us.fit: device time of the round's KMV per round, in us.

Device seconds of the operations the program names ``kmv`` or
``kmv_apply`` (``bench/scopes.py``) inside the ``repro.solve``
annotations of the traced fits, averaged over the chips, divided by the
rounds those fits ran.  Also notes the phase table: every scope's
device us per round, ``unscoped`` with its three largest op families,
and the owners of the ``pad`` and ``copy`` families with their ``tf_op``
paths (``none`` where the op carries none).  Moves ``fit_s``.
"""


def read(ctx):
    from bench import scopes

    ph = scopes.solve_phases(ctx)
    if ph is None:
        return None
    us = 1e6 / ph.rounds
    table = sorted(ph.owners().items(), key=lambda kv: -kv[1])
    ctx.notes.append(
        f"phases per round ({ph.rounds} rounds, {ph.n_spans} solves, busy "
        f"{ph.busy * us:.1f} us): "
        + ", ".join(f"{o} {v * us:.1f} us" for o, v in table))
    top = ph.families(scopes.UNSCOPED)[:3]
    ctx.notes.append("unscoped, largest op families: " + (
        ", ".join(f"{f} {v * us:.1f} us" for f, v in top) or "none"))
    for fam in sorted({f for _, f in ph.by if f.startswith(("pad", "copy"))}):
        tf = sorted(ph.paths.get(fam, ()))
        ctx.notes.append(f"owner of {fam}: " + ", ".join(
            f"{o} {v * us:.1f} us" for o, v in ph.owners_of(fam))
            + "; tf_op " + (", ".join(p or "none" for p in tf) or "none"))
    return us * ph.seconds("kmv", "kmv_apply")
