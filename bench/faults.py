"""Faults planted under the timed path, which the check has to catch.

``bench/calibrate.py --fault <name>`` reads a cell's numbers with one
planted, on the chip at the cell's size; ``tests/bench_tests`` does the
same on the CPU at a small size.  ``plant(name)`` replaces one function
of the program and returns a callable that puts it back.  Clear JAX's
caches (``jax.clear_caches()``) after planting and after undoing, so
that jitted callers are traced anew.

* ``unchanged``: a fit returns its initial state.
* ``half_rows``: the KMV sums over the first half of the rows and
  doubles the result.
* ``zero_kmv``: the KMV returns zeros.
* ``altered_fit``: a fit's alpha comes out 1% larger.
* ``altered_serve``: the first value of every served batch 5% larger.
"""
from __future__ import annotations


def _swap(owner, name, fn):
    real = getattr(owner, name)
    setattr(owner, name, fn(real))
    return lambda: setattr(owner, name, real)


def _unchanged():
    import repro.api as api
    return _swap(api, "_serial_fast",
                 lambda real: lambda problem, A, y, a0, *a, **k: a0)


def _half_rows():
    import repro.core.kernels as kernels

    def fault(real):
        def half(A, B, X, cfg, block=2048):
            h = A.shape[0] // 2
            return 2 * real(A[:h], B, X[:h], cfg, block=block)
        return half

    return _swap(kernels, "kmv_slab_free", fault)


def _zero_kmv():
    import repro.core.kernels as kernels

    def fault(real):
        def zero(A, B, X, cfg, block=2048):
            return 0 * real(A[:8], B, X[:8], cfg, block=block)
        return zero

    return _swap(kernels, "kmv_slab_free", fault)


def _altered_fit():
    import repro.api as api
    return _swap(api, "_serial_fast",
                 lambda real: lambda *a, **k: real(*a, **k) * 1.01)


def _altered_serve():
    from repro.serve import registry
    return _swap(registry.ServeGroup, "serve",
                 lambda real: lambda self, Xq: real(self, Xq).at[0].multiply(
                     1.05))


FAULTS = {"unchanged": _unchanged, "half_rows": _half_rows,
          "zero_kmv": _zero_kmv, "altered_fit": _altered_fit,
          "altered_serve": _altered_serve}


def plant(name: str):
    """Plant fault ``name``; returns the callable that undoes it."""
    return FAULTS[name]()
