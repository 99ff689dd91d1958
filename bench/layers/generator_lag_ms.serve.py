"""generator_lag_ms.serve: how late the load generator submitted, in ms.

95th percentile over the traced window's requests of (submit time - due
time), on the host clock.  A late generator delays requests the way a
slow engine does: read it beside ``serve_p50_ms``, which it moves.
"""


def read(ctx):
    import numpy as np

    lag = getattr(ctx.driver, "lag", None)
    if lag is None or not len(lag):
        return None
    return 1e3 * float(np.percentile(lag, 95))
