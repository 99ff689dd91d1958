"""Plain references for the benchmark's comparisons.

Nothing here imports the program under test.  Each function is the
textbook algorithm in ``jax.numpy``, written from the paper's
definitions (Algorithms 1 and 3 of arXiv 2406.18001) and the problem
statements in the configuration files:

* ``rbf(P, Q)``: ``exp(-sigma * ||p - q||^2)``, every product at the
  precision the caller asks for (``HIGHEST`` for the reference).
* ``krr_bdcd``: classical block dual coordinate descent for
  ``((1/lam) K + m I) alpha = y``: one exact b x b block solve per
  iteration, on the current alpha.  The kernel columns of a round's
  ``s`` blocks are computed together, which changes no arithmetic of
  the iteration (a kernel column does not depend on alpha).
* ``ksvm_dcd``: classical dual coordinate descent for the L1/L2-loss
  SVM dual over ``Atil = diag(y) A``, with the kernel taken of the
  scaled rows (the convention the configuration states).
* ``serve_values``: ``K(Q, A) @ w`` in blocks of query rows.
* ``block_schedule``/``coordinate_schedule``: the seeded coordinate
  draws the configuration's ``seed`` semantics fix (Floyd's algorithm
  per block; uniform draws for single coordinates).

``dtype`` selects the precision of the whole computation: float32 with
``HIGHEST`` products is the reference; bfloat16 is the control, the same
code one precision below (the tiny b x b solve is done in float32, since
a TPU has no bfloat16 LU).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

HIGHEST = jax.lax.Precision.HIGHEST
DEFAULT = jax.lax.Precision.DEFAULT


def precision_for(dtype) -> jax.lax.Precision:
    return HIGHEST if jnp.dtype(dtype) == jnp.float32 else DEFAULT


def rbf(P, Q, sigma, precision):
    """(p, q) kernel block ``exp(-sigma ||p_i - q_j||^2)``."""
    d = jnp.dot(P, Q.T, precision=precision)
    sq = (jnp.sum(P * P, axis=1)[:, None] + jnp.sum(Q * Q, axis=1)[None, :]
          - 2 * d)
    return jnp.exp(-sigma * jnp.maximum(sq, 0))


# ---------------------------------------------------------------------------
# schedules (the seed semantics of SolverOptions.seed)
# ---------------------------------------------------------------------------

def block_schedule(seed: int, H: int, m: int, b: int):
    """(H, b) blocks, each b distinct coordinates drawn uniformly by
    Floyd's algorithm from ``jax.random.key(seed)``."""
    keys = jax.random.split(jax.random.key(seed), H)
    tops = jnp.arange(m - b, m)

    def one(k):
        ts = jax.random.randint(k, (b,), 0, tops + 1)
        out = jnp.full((b,), -1, ts.dtype)
        for j in range(b):
            out = out.at[j].set(jnp.where(jnp.any(out == ts[j]), tops[j],
                                          ts[j]))
        return out

    return jax.jit(jax.vmap(one))(keys)


def coordinate_schedule(seed: int, H: int, m: int):
    """(H,) coordinates drawn uniformly from ``jax.random.key(seed)``."""
    return jax.random.randint(jax.random.key(seed), (H,), 0, m)


# ---------------------------------------------------------------------------
# fits
# ---------------------------------------------------------------------------
#
# The fits run on the devices that hold A's rows (one, or the cell's
# mesh with rows over ``data``), one ``shard_map`` per round: each device
# builds the kernel columns of its own rows, chunk by chunk (a HIGHEST
# product splits its operands into bfloat16 pieces; chunk by chunk they
# stay small), and the block sums over all rows are ``psum``s.  Rows
# appended to fill the last chunk are zero and carry zero weight.  The
# rounds are driven from the host, one jitted call each: a loop over
# rounds that held A would make XLA copy it.

CHUNK = 32768


def _chunk(rows: int) -> int:
    """A chunk of a multiple of 8 rows (so reshapes keep the (8, 128)
    tiling) that divides ``rows`` when one up to CHUNK does."""
    return next((c for c in range(CHUNK, 7, -8) if rows % c == 0), CHUNK)


def _prepare(A, mesh, dtype, exact: bool):
    """(A in ``dtype`` with zero rows appended, chunk); A itself when it
    needs neither, so no copy is made.  Data exact in bfloat16 is taken
    whole (one chunk per device)."""
    m = A.shape[0]
    parts = mesh.shape["data"]
    chunk = m // parts if exact else _chunk(m // parts)
    pad = (-m) % (parts * chunk)
    if pad:
        A = jnp.pad(A, ((0, pad), (0, 0)))
    if A.dtype != dtype:
        A = A.astype(dtype)
    return A, chunk


def _local_columns(B, A_loc, chunk, sigma, prec, exact):
    """``K(B, A_loc)`` as (nc, r, chunk) for this device's rows.  For data
    exact in bfloat16 a product at the default precision is exact: one
    product over all the rows, and no HIGHEST split of A."""
    if exact:
        return rbf(B, A_loc, sigma, DEFAULT)[None]
    A3 = A_loc.reshape(-1, chunk, A_loc.shape[1])
    bn = jnp.sum(B * B, axis=1)

    def one(_, Ac):
        d = jnp.dot(B, Ac.T, precision=prec)
        sq = bn[:, None] + jnp.sum(Ac * Ac, axis=1)[None, :] - 2 * d
        return None, jnp.exp(-sigma * jnp.maximum(sq, 0))

    return jax.lax.scan(one, None, A3)[1]


def _owned(i, m_loc):
    """(is row i on this device, its local number)."""
    lo = jax.lax.axis_index("data") * m_loc
    return (i >= lo) & (i < lo + m_loc), i - lo


def _gathered(x_loc, i, m_loc):
    """x[i] for global rows i, from the device that holds each."""
    own, li = _owned(i, m_loc)
    x = x_loc[jnp.clip(li, 0, m_loc - 1)]
    own = own.reshape(own.shape + (1,) * (x.ndim - own.ndim))
    return jax.lax.psum(jnp.where(own, x, 0), "data")


@partial(jax.jit, static_argnames=("mesh", "exact"))
def _sampled(A, idx, *, mesh, exact):
    """The rows ``idx`` of A, gathered in a call of their own; for data
    exact in bfloat16 by a one-hot product (a gather from A on a TPU
    first copies A into row-major order)."""
    m_loc = A.shape[0] // mesh.shape["data"]

    @partial(jax.shard_map, mesh=mesh, in_specs=(P("data"), P()),
             out_specs=P(), check_vma=False)
    def gather(A_loc, i):
        if not exact:
            return _gathered(A_loc, i, m_loc)
        lo = jax.lax.axis_index("data") * m_loc
        hot = (i[:, None] == lo + jnp.arange(m_loc)[None, :])
        return jax.lax.psum(jnp.dot(hot.astype(A_loc.dtype), A_loc,
                                    precision=DEFAULT), "data")

    return gather(A, idx.reshape(-1))


@partial(jax.jit, static_argnames=("lam", "sigma", "m", "chunk", "mesh",
                                   "exact"), donate_argnums=2)
def _krr_round(A, y, alpha, idx, B, *, lam, sigma, m, chunk, mesh, exact):
    """One round's s blocks of classical BDCD, block after block; idx
    (s, b) are the round's blocks and B their rows."""
    prec = precision_for(A.dtype)
    s, b = idx.shape
    m_loc = A.shape[0] // mesh.shape["data"]
    KBB = rbf(B, B, sigma, prec).astype(jnp.float32)
    eye = jnp.eye(b, dtype=jnp.float32)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P("data"), P("data"), P("data"), P(), P(), P()),
             out_specs=P("data"), check_vma=False)
    def run(A_loc, y_loc, a_loc, idx, B, KBB):
        U = _local_columns(B, A_loc, chunk, sigma, prec, exact)

        def block(j, a_loc):
            I = idx[j]
            Uj = jax.lax.dynamic_slice_in_dim(U, j * b, b, axis=1)
            Ua = jax.lax.psum(jnp.einsum(
                "cbk,ck->b", Uj, a_loc.reshape(-1, chunk), precision=prec),
                "data")
            G = jax.lax.dynamic_slice(KBB, (j * b, j * b), (b, b)) / lam \
                + m * eye
            rhs = (_gathered(y_loc, I, m_loc)
                   - m * _gathered(a_loc, I, m_loc) - Ua / lam)
            d = jnp.linalg.solve(G, rhs.astype(jnp.float32))
            own, li = _owned(I, m_loc)
            return a_loc.at[jnp.where(own, li, m_loc)].add(
                d.astype(a_loc.dtype), mode="drop")

        return jax.lax.fori_loop(0, s, block, a_loc)

    return run(A, y, alpha, idx, B, KBB)


def krr_bdcd(A, y, sched, *, lam: float, sigma: float, s: int, dtype,
             mesh, exact: bool = False):
    """Classical BDCD over the (H, b) schedule; returns alpha (m,).
    ``exact``: A's values are exact in bfloat16 (see _local_columns)."""
    m = A.shape[0]
    A, chunk = _prepare(A, mesh, dtype, exact)
    mp = A.shape[0]
    y = jnp.pad(y.astype(dtype), (0, mp - m))
    rounds = sched.reshape(-1, s, sched.shape[1])
    alpha = jnp.zeros(mp, dtype)
    for r in range(rounds.shape[0]):
        B = _sampled(A, rounds[r], mesh=mesh, exact=exact)
        alpha = _krr_round(A, y, alpha, rounds[r], B, lam=lam, sigma=sigma,
                           m=m, chunk=chunk, mesh=mesh, exact=exact)
    return alpha[:m].astype(jnp.float32)


@partial(jax.jit, static_argnames=("C", "loss", "sigma", "chunk", "mesh",
                                   "exact"), donate_argnums=1)
def _ksvm_round(At, alpha, idx, B, *, C, loss, sigma, chunk, mesh, exact):
    """One round's s coordinates of classical DCD, one after another; idx
    (s,) are the round's coordinates and B their rows of diag(y) A."""
    prec = precision_for(At.dtype)
    nu = C if loss == "l1" else jnp.inf
    omega = 0.0 if loss == "l1" else 1.0 / (2.0 * C)
    m_loc = At.shape[0] // mesh.shape["data"]
    eta = jnp.diagonal(rbf(B, B, sigma, prec)).astype(jnp.float32) + omega

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P("data"), P("data"), P(), P(), P()),
             out_specs=P("data"), check_vma=False)
    def run(A_loc, a_loc, idx, B, eta):
        U = _local_columns(B, A_loc, chunk, sigma, prec, exact)

        def coord(j, a_loc):
            i = idx[j]
            a = _gathered(a_loc, i, m_loc)
            Uj = jax.lax.dynamic_index_in_dim(U, j, axis=1, keepdims=False)
            g = jax.lax.psum(jnp.sum(Uj * a_loc.reshape(-1, chunk)),
                             "data") - 1 + omega * a
            moved = jnp.clip(a - g, 0, nu) - a != 0
            theta = jnp.where(moved, jnp.clip(a - g / eta[j], 0, nu) - a, 0)
            own, li = _owned(i, m_loc)
            return a_loc.at[jnp.where(own, li, m_loc)].add(
                theta.astype(a_loc.dtype), mode="drop")

        return jax.lax.fori_loop(0, idx.shape[0], coord, a_loc)

    return run(At, alpha, idx, B, eta)


def ksvm_dcd(A, y, sched, *, C: float, loss: str, sigma: float, s: int,
             dtype, mesh, exact: bool = False):
    """Classical DCD over the (H,) schedule; returns alpha (m,).
    ``exact``: A's values are exact in bfloat16 (see _local_columns)."""
    m = A.shape[0]
    At, chunk = _prepare(y[:, None] * A, mesh, dtype, exact)
    rounds = sched.reshape(-1, s)
    alpha = jnp.zeros(At.shape[0], dtype)
    for r in range(rounds.shape[0]):
        B = _sampled(At, rounds[r], mesh=mesh, exact=exact)
        alpha = _ksvm_round(At, alpha, rounds[r], B, C=C, loss=loss,
                            sigma=sigma, chunk=chunk, mesh=mesh, exact=exact)
    return alpha[:m].astype(jnp.float32)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("sigma", "rows", "dtype"))
def serve_values(Q, A, w, *, sigma: float, rows: int, dtype):
    """``K(Q, A) @ w`` for (q, n) queries, ``rows`` query rows at a time."""
    prec = precision_for(dtype)
    A = A.astype(dtype)
    w = w.astype(dtype)
    q = Q.shape[0]
    pad = (-q) % rows
    Qb = jnp.pad(Q.astype(dtype), ((0, pad), (0, 0))).reshape(
        -1, rows, Q.shape[1])

    def block(Qr):
        return jnp.dot(rbf(Qr, A, sigma, prec), w, precision=prec)

    return jax.lax.map(block, Qb).reshape(-1)[:q].astype(jnp.float32)
