"""kmv_roofline.fit: the KMV's share of its roofline, in %.

``probe`` runs the fitted estimator's operator, ``op_.matvec(idx, X)``,
jitted as ``kmv_probe``, ``CALLS`` times (after one warm-up call) at the
cell's (m, n, s*b) in the traced run.  ``read`` takes the device time of
those programs from the trace's ``XLA Modules`` line.

The work is counted from the shapes alone (``work``), whatever
implements the KMV, at logical sizes, so row and lane padding show as
lost share.  Share = max(flops / peak, bytes / bandwidth) / device time;
``bound`` says which of the two is larger.  Moves ``fit_s``.
"""

CALLS = 50


def work(m: int, n: int, r: int, word: int = 4):
    """(flops, bytes) of U^T X with U = K(A, B), A (m, n), B (r, n),
    X (m,): the r x m kernel tile's dot products and epilogue inputs,
    and one read of A, B and X."""
    flops = 2 * m * r * (n + 1)
    nbytes = word * (m * n + r * n + m)
    return flops, nbytes


def bound(m, n, r, peaks):
    flops, nbytes = work(m, n, r)
    t_f = flops / peaks["flops_bf16"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return max(t_f, t_b), ("bytes" if t_b >= t_f else "flops")


def kmv_probe(op, idx, X):
    return op.matvec(idx, X)


def probe(ctx):
    import jax
    import jax.numpy as jnp

    est = ctx.driver.last
    o = ctx.driver.opts
    m = ctx.cell.config["m"]
    r = o["s"] * (o["b"] if ctx.cell.config["problem"] == "krr" else 1)
    idx = jax.random.randint(jax.random.key(0), (r,), 0, m)
    X = jnp.ones((m,), jnp.float32)
    kmv = jax.jit(kmv_probe)
    jax.block_until_ready(kmv(est.op_, idx, X))
    for _ in range(CALLS):
        out = kmv(est.op_, idx, X)
    jax.block_until_ready(out)
    return {"r": r}


def read(ctx):
    from bench import devtrace

    info = ctx.probes.get("kmv_roofline.fit")
    if info is None:
        return None
    # the warm-up call is traced too
    t = devtrace.module_seconds(ctx.trace, "kmv_probe") * CALLS / (CALLS + 1)
    if t <= 0:
        return None
    c = ctx.cell.config
    t_min, which = bound(c["m"], c["n"], info["r"], ctx.peaks)
    ctx.notes.append(f"kmv_roofline.fit: bound by {which}; "
                     f"{CALLS} calls in {t:.6f} s of device time, "
                     f"t_min {t_min:.6e} s per call")
    return 100.0 * CALLS * t_min / t
