"""Out-of-core streaming representation (DESIGN.md §14).

The contract under test: a ``StreamingGramOperator`` — X chunked into
row blocks, contractions streamed chunk-at-a-time (double-buffered DMA
on TPU, ``lax.scan`` elsewhere) — is numerically INTERCHANGEABLE with
the resident ``ExactGramOperator`` across every consumer (the four
round-fn factories via the facade, guarded solves, the fleet, batched
serving), while its device working set is bounded by ONE chunk instead
of all of X.  The device-memory claim is enforced through the perf
model (``streaming_required`` / ``stream_chunk_fits``): CPU CI has no
real HBM ceiling, so the acceptance test pins a budget under which the
resident representation is infeasible and the streamed one fits, then
demands ≤1e-5 solution parity anyway.
"""
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import AUTO, KernelRidge, KernelSVM, SolverOptions
from repro.core.kernels import (ExactGramOperator, KernelConfig,
                                StreamingGramOperator, gram_slab)
from repro.core.perf_model import (STREAM_CHUNK_CANDIDATES,
                                   choose_chunk_rows, modeled_predict_cost,
                                   stream_chunk_fits, stream_pipeline_cost,
                                   stream_working_set_bytes,
                                   streaming_required)
from repro.core.predict import BatchedPredictor
from repro.data.synthetic import classification_dataset, regression_dataset
from repro.kernels.kmv_stream import kmv_stream_pallas
from repro.kernels.ref import kmv_ref

KERNELS = [
    KernelConfig("linear"),
    KernelConfig("polynomial", degree=3, coef0=1.0),
    KernelConfig("rbf", sigma=0.9),
]
TOL = dict(rtol=1e-5, atol=1e-5)
M, N = 56, 9                      # 56 % 16 != 0: ragged last chunk


def _ops(cfg, m=M, n=N, chunk_rows=16, dtype=jnp.float32, seed=3):
    A = jax.random.normal(jax.random.key(seed), (m, n),
                          jnp.float32).astype(dtype)
    return (ExactGramOperator(A, cfg),
            StreamingGramOperator.from_dense(A, cfg,
                                             chunk_rows=chunk_rows))


# ---------------------------------------------------------------------------
# operator parity: every GramOperator method, chunked vs resident
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("cfg", KERNELS, ids=lambda k: k.name)
def test_operator_parity(cfg, dtype):
    exact, stream = _ops(cfg, dtype=dtype)
    tol = dict(rtol=5e-2, atol=5e-1) if dtype == jnp.bfloat16 else TOL
    idx = jnp.asarray([0, 7, 19, 55])          # spans the ragged tail
    X = jax.random.normal(jax.random.key(9), (M, 3))
    w = jax.random.normal(jax.random.key(11), (M,))
    # contractions sum kernel terms in a different order (chunk by chunk
    # vs one block): their rounding error scales with the magnitude of
    # the summed terms, not with the possibly cancelled result — so they
    # are held to the tolerance on |K|^T|x| in place of |result|
    K = np.abs(np.asarray(gram_slab(exact.A.astype(jnp.float32),
                                    exact.A.astype(jnp.float32), cfg)))
    Xa, wa = np.abs(np.asarray(X)), np.abs(np.asarray(w))
    ix = np.asarray(idx)
    for name, got, want, terms in [
        ("rows", stream.rows(idx), exact.rows(idx), None),
        ("diag", stream.diag(idx), exact.diag(idx), None),
        ("matvec", stream.matvec(idx, X), exact.matvec(idx, X),
         K[:, ix].T @ Xa),
        ("cross", stream.cross_block(idx), exact.cross_block(idx), None),
        ("apply_at", stream.apply_at(idx, X[:4]),
         exact.apply_at(idx, X[:4]), K[:, ix] @ Xa[:4]),
        ("full_mv", stream.full_matvec(X[:, 0]),
         exact.full_matvec(X[:, 0]), K @ Xa[:, 0]),
        ("serve", stream.serve_block(exact.rows(idx), w),
         exact.serve_block(exact.rows(idx), w), K[ix] @ wa),
    ]:
        got = np.asarray(got, np.float64)
        want = np.asarray(want, np.float64)
        scale = np.abs(want) if terms is None else terms
        err = np.abs(got - want)
        assert np.all(err <= tol["atol"] + tol["rtol"] * scale), (
            name, float(np.max(err - tol["rtol"] * scale)))


@pytest.mark.parametrize("cfg", KERNELS, ids=lambda k: k.name)
def test_take_and_scale_rows_rechunk(cfg):
    exact, stream = _ops(cfg)
    y = jax.random.normal(jax.random.key(4), (M,))
    keep = jnp.asarray([3, 17, 20, 41, 55])
    se, ss = exact.scale_rows(y).take(keep), stream.scale_rows(y).take(keep)
    assert isinstance(ss, StreamingGramOperator)
    assert ss.n_samples == keep.size
    idx = jnp.arange(keep.size)
    np.testing.assert_allclose(np.asarray(ss.cross_block(idx)),
                               np.asarray(se.cross_block(idx)), **TOL)


def test_operator_is_pytree_and_jittable():
    _, stream = _ops(KernelConfig("rbf", sigma=0.9))
    leaves, treedef = jax.tree_util.tree_flatten(stream)
    assert jax.tree_util.tree_unflatten(treedef, leaves).chunk_rows \
        == stream.chunk_rows

    @jax.jit
    def f(op, v):
        return op.full_matvec(v)

    v = jnp.ones((M,))
    np.testing.assert_allclose(np.asarray(f(stream, v)),
                               np.asarray(stream.full_matvec(v)), **TOL)


def test_chunk_rows_validated():
    A = jnp.zeros((8, 3))
    cfg = KernelConfig("linear")
    for bad in (0, -1, 2.5, "16"):
        with pytest.raises((ValueError, TypeError)):
            StreamingGramOperator.from_dense(A, cfg, chunk_rows=bad)
    # larger than m clips instead of failing (single-chunk degenerate)
    op = StreamingGramOperator.from_dense(A, cfg, chunk_rows=64)
    assert op.n_chunks == 1 and op.chunk_rows == 8


# ---------------------------------------------------------------------------
# the double-buffered Pallas kernel (interpret mode on CPU)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("c", [1, 5], ids=["vec", "mat"])
@pytest.mark.parametrize("cfg", KERNELS, ids=lambda k: k.name)
def test_kmv_stream_pallas_matches_oracle(cfg, c):
    k1, k2, k3 = jax.random.split(jax.random.key(7), 3)
    nc, cr, n, r = 4, 14, 9, 11            # nothing lane/sublane aligned
    Xc = jax.random.normal(k1, (nc, cr, n), jnp.float32)
    B = jax.random.normal(k2, (r, n), jnp.float32)
    Xvc = jax.random.normal(k3, (nc, cr, c), jnp.float32)
    got = kmv_stream_pallas(Xc, B, Xvc, cfg, interpret=True)
    want = kmv_ref(Xc.reshape(nc * cr, n), B, Xvc.reshape(nc * cr, c), cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_kmv_stream_ragged_tail_zero_padded():
    # a zero-padded tail chunk must contribute NOTHING even for RBF
    # (K(0, b) = exp(-s|b|^2) != 0): contraction safety comes from the
    # zero RHS rows, which is exactly what StreamingGramOperator pads
    cfg = KernelConfig("rbf", sigma=0.9)
    _, stream = _ops(cfg, m=50, chunk_rows=16)   # tail chunk: 2 live rows
    exact, _ = _ops(cfg, m=50, chunk_rows=16)
    v = jax.random.normal(jax.random.key(1), (50,))
    np.testing.assert_allclose(np.asarray(stream.full_matvec(v)),
                               np.asarray(exact.full_matvec(v)), **TOL)


# ---------------------------------------------------------------------------
# facade: streamed fits match resident fits across solvers
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def krr_data():
    return regression_dataset(jax.random.key(2), m=64, n=8)


@pytest.fixture(scope="module")
def svm_data():
    return classification_dataset(jax.random.key(0), m=64, n=8)


@pytest.mark.parametrize("method", ["classical", "sstep"])
def test_krr_stream_matches_resident(krr_data, method):
    A, y = krr_data
    kw = dict(method=method, s=4, b=4, max_iters=24, record=False)
    res = KernelRidge(lam=0.5, kernel="rbf",
                      options=SolverOptions(**kw)).fit(A, y)
    strm = KernelRidge(lam=0.5, kernel="rbf",
                       options=SolverOptions(stream=16, **kw)).fit(A, y)
    np.testing.assert_allclose(np.asarray(strm.alpha), np.asarray(
        res.alpha), **TOL)


@pytest.mark.parametrize("method", ["classical", "sstep"])
def test_ksvm_stream_matches_resident(svm_data, method):
    A, y = svm_data
    kw = dict(method=method, s=4, max_iters=24, record=False)
    res = KernelSVM(C=1.0, kernel="rbf",
                    options=SolverOptions(**kw)).fit(A, y)
    strm = KernelSVM(C=1.0, kernel="rbf",
                     options=SolverOptions(stream=16, **kw)).fit(A, y)
    np.testing.assert_allclose(np.asarray(strm.alpha), np.asarray(
        res.alpha), **TOL)


def test_stream_options_validated():
    with pytest.raises(ValueError):
        SolverOptions(stream=0)
    with pytest.raises(ValueError):
        SolverOptions(stream=16, layout="1d")
    with pytest.raises(ValueError):
        SolverOptions(stream=16, approx="nystrom")
    assert SolverOptions(stream=True).stream == AUTO
    assert SolverOptions(stream=False).stream is None
    assert SolverOptions(stream=AUTO).needs_autotune


@pytest.mark.skipif(os.environ.get("REPRO_SANITIZE") == "1",
                    reason="the guard's health machinery carries "
                           "inf/-inf sentinels by design (same reason "
                           "the resilience modules sit outside "
                           "KERNEL_TEST_MODULES) — debug_infs trips on "
                           "them, not on the streamed kernel")
def test_guarded_stream_drift_correction(krr_data):
    A, y = krr_data
    kw = dict(max_iters=24, record=False, guard=True, recompute_every=2)
    res = KernelRidge(lam=0.5, kernel="rbf",
                      options=SolverOptions(**kw)).fit(A, y)
    strm = KernelRidge(lam=0.5, kernel="rbf",
                       options=SolverOptions(stream=16, **kw)).fit(A, y)
    np.testing.assert_allclose(np.asarray(strm.alpha),
                               np.asarray(res.alpha), **TOL)
    assert strm.health is not None and strm.health.guarded
    # the guard's drift correction ran through the STREAMED full_matvec
    assert strm.health.corrections > 0


# ---------------------------------------------------------------------------
# serving: streamed predict == resident predict
# ---------------------------------------------------------------------------

def test_predict_over_streamed_operator(krr_data):
    A, y = krr_data
    kw = dict(max_iters=24, record=False)
    Aq = np.asarray(jax.random.normal(jax.random.key(5), (37, A.shape[1])))
    mr = KernelRidge(lam=0.5, kernel="rbf", options=SolverOptions(**kw))
    mr.fit(A, y)
    ms = KernelRidge(lam=0.5, kernel="rbf",
                     options=SolverOptions(stream=16, **kw))
    ms.fit(A, y)
    np.testing.assert_allclose(np.asarray(ms.predict(jnp.asarray(Aq))),
                               np.asarray(mr.predict(jnp.asarray(Aq))),
                               **TOL)


def test_batched_predictor_query_streaming():
    cfg = KernelConfig("rbf", sigma=0.9)
    exact, stream_op = _ops(cfg)
    w = jax.random.normal(jax.random.key(6), (M,))
    Xq = np.asarray(jax.random.normal(jax.random.key(8),
                                      (301, N)), np.float32)  # host array
    want = BatchedPredictor(exact, w, batch=64)(jnp.asarray(Xq))
    # query-side streaming (host chunks) x representation-side streaming
    got = BatchedPredictor(stream_op, w, batch=64, stream=48)(Xq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    with pytest.raises(ValueError):
        BatchedPredictor(exact, w, stream=0)


def test_modeled_predict_cost_stream_terms():
    base = modeled_predict_cost(4096, 64, 2048, "rbf")
    strm = modeled_predict_cost(4096, 64, 2048, "rbf", stream=256)
    assert strm["stream_chunks"] == 2048 // 256
    # overlapped streamed serving costs at least the pure-compute bound
    # and at most compute + every chunk's DMA (no-overlap worst case)
    assert base["time"] <= strm["time"] \
        <= base["time"] + 2 * strm["t_dma"] + 1e-12
    assert strm["t_overlap"] >= 0.0


# ---------------------------------------------------------------------------
# fleet over a shared streamed operator
# ---------------------------------------------------------------------------

def test_fleet_over_stream(krr_data):
    from repro.tune.fleet import solve_fleet
    A, y = krr_data
    lams = [0.1, 1.0]
    kw = dict(max_iters=16, record=False)
    f0 = solve_fleet(A, y, lams=lams, kernel="rbf",
                     options=SolverOptions(**kw))
    f1 = solve_fleet(A, y, lams=lams, kernel="rbf",
                     options=SolverOptions(stream=16, **kw))
    assert isinstance(f1.op, StreamingGramOperator)
    np.testing.assert_allclose(np.asarray(f1.alpha), np.asarray(f0.alpha),
                               **TOL)


# ---------------------------------------------------------------------------
# autotuner: chunk_rows="auto" under the working-set constraint
# ---------------------------------------------------------------------------

def test_choose_chunk_rows_respects_working_set():
    m, n, sb = 1 << 20, 256, 64
    budget = 4 * 2 ** 20                    # 4 MB on-chip budget
    cr = choose_chunk_rows(m, n, sb, "rbf", budget_bytes=budget)
    assert stream_chunk_fits(cr, n, sb, budget_bytes=budget)
    # every INfeasible candidate the search rejected really is bigger
    _, frontier = choose_chunk_rows(m, n, sb, "rbf", budget_bytes=budget,
                                    return_frontier=True)
    for row in frontier:
        if not row["feasible"]:
            assert row["working_set_bytes"] > budget
    # candidates never exceed the problem (degenerate small m)
    assert choose_chunk_rows(10, n, sb, "rbf") <= 10


def test_working_set_counts_tile_padding():
    """The streamed kernel's VMEM blocks are (8, 128)-tiled, so a c=1
    right-hand side occupies 128 lanes and chunk_rows=8192 at n=256 does
    not fit the 16 MiB budget — the v5e compiler refuses it for VMEM.
    Unpadded, n=64 at the same chunk looked feasible (4.8 MB)."""
    cr, n, sb = 8192, 256, 16
    assert stream_working_set_bytes(cr, n, sb) == 4 * (
        2 * cr * n + 2 * cr * 128 + 2 * sb * n + 3 * sb * 128 + cr * sb)
    assert not stream_chunk_fits(cr, n, sb)
    assert not stream_chunk_fits(cr, 64, sb)
    assert stream_chunk_fits(4096, n, sb)
    A, y = regression_dataset(jax.random.key(0), m=cr, n=n)
    with pytest.raises(ValueError, match="VMEM budget of 16777216 bytes"):
        KernelRidge(options=SolverOptions(stream=cr, s=16, b=1,
                                          max_iters=16)).fit(A, y)


def test_facade_resolves_stream_auto(krr_data):
    A, y = krr_data
    est = KernelRidge(lam=0.5, kernel="rbf",
                      options=SolverOptions(stream="auto", max_iters=8,
                                            record=False))
    r = est.fit(A, y)
    assert isinstance(r.options.stream, int) and r.options.stream >= 1
    assert r.plan is not None
    assert isinstance(est.op_, StreamingGramOperator)
    sb = r.options.s_eff * (r.options.b if isinstance(r.options.b, int)
                            else 1)
    assert stream_chunk_fits(r.options.stream, A.shape[1], sb)


# ---------------------------------------------------------------------------
# perf model: pipeline overlap accounting
# ---------------------------------------------------------------------------

def test_stream_pipeline_cost_overlap_bounds():
    for cr in (128, 1024, 8192):
        p = stream_pipeline_cost(1 << 18, 128, 32, cr, "rbf")
        assert p["time"] <= p["time_unoverlapped"] + 1e-18
        assert 1.0 <= p["overlap_speedup"] <= 2.0 + 1e-12
        assert p["streamed_over_resident"] >= 1.0
        if p["compute_bound"]:
            # compute-bound: streaming costs one warm-up DMA, nothing per
            # steady chunk — the fig10 gate's modeled justification
            assert p["time"] <= p["resident_time"] + p["t_dma"] + 1e-18


def test_streaming_required_gate():
    # 1M x 256 f32 X is ~1 GB: resident fails a 256 MB device, streaming
    # with a fitting chunk succeeds — the acceptance criterion's gate
    m, n, sb = 1 << 20, 256, 64
    device = 256 * 2 ** 20
    assert streaming_required(m, n, sb, device_bytes=device)
    assert not streaming_required(1 << 10, n, sb, device_bytes=device)
    cr = choose_chunk_rows(m, n, sb, "rbf", budget_bytes=4 * 2 ** 20)
    assert stream_working_set_bytes(cr, n, sb) < device


def test_out_of_core_acceptance():
    """ISSUE acceptance: solve a problem whose resident working set
    EXCEEDS the configured device budget (perf-model-enforced — CPU CI
    has no real HBM ceiling) with the streamed representation, matching
    the resident solve to 1e-5.  m is large enough that X outweighs
    the chunk's lane-padded VMEM working set (128-wide rows, however
    narrow n is)."""
    m, n = 1024, 24
    opts = SolverOptions(s=4, b=4, max_iters=24, record=False)
    sb = opts.s_eff * opts.b
    # budget chosen between the streamed and resident working sets:
    word = 4
    resident_bytes = word * (m * n + m + sb * n + sb)
    chunk = 16
    assert stream_chunk_fits(chunk, n, sb,
                             budget_bytes=resident_bytes - 1)
    assert streaming_required(m, n, sb,
                              device_bytes=resident_bytes - 1)
    A, y = regression_dataset(jax.random.key(12), m=m, n=n)
    res = KernelRidge(lam=0.5, kernel="rbf", options=opts).fit(A, y)
    strm = KernelRidge(
        lam=0.5, kernel="rbf",
        options=SolverOptions(stream=chunk, s=4, b=4, max_iters=24,
                              record=False)).fit(A, y)
    err = float(jnp.max(jnp.abs(strm.alpha - res.alpha)))
    assert err <= 1e-5, err


# ---------------------------------------------------------------------------
# analysis: CHK-DMA statics over the double-buffer discipline
# ---------------------------------------------------------------------------

_DMA_BAD = textwrap.dedent('''
    def k_never_waited(x_hbm, o_ref):
        def body(buf, sem):
            pltpu.make_async_copy(x_hbm.at[0], buf.at[0],
                                  sem.at[0]).start()
            o_ref[...] = buf[0]
        pl.run_scoped(body)


    def k_no_start(x_hbm, o_ref):
        def body(buf, sem):
            pltpu.make_async_copy(x_hbm.at[0], buf.at[0],
                                  sem.at[0]).wait()
        pl.run_scoped(body)


    def k_same_slot(x_hbm, o_ref, nc):
        def body(buf, sem):
            pltpu.make_async_copy(x_hbm.at[0], buf.at[0],
                                  sem.at[0]).start()
            def loop(i, _):
                slot = jax.lax.rem(i, 2)
                pltpu.make_async_copy(x_hbm.at[i + 1], buf.at[slot],
                                      sem.at[slot]).start()
                pltpu.make_async_copy(x_hbm.at[i], buf.at[slot],
                                      sem.at[slot]).wait()
            jax.lax.fori_loop(0, nc, loop, None)
        pl.run_scoped(body)
''')


def test_chk_dma_catches_all_three_races(tmp_path):
    from repro.analysis.pallas_check import _check_dma
    (tmp_path / "bad.py").write_text(_DMA_BAD)
    found = _check_dma(root=str(tmp_path))
    assert sorted(f.check for f in found) == ["CHK-DMA"] * 3
    msgs = " | ".join(f.message for f in found)
    assert "never waited" in msgs
    assert "no matching start" in msgs
    assert "must alternate" in msgs


def test_chk_dma_real_kernels_clean():
    from repro.analysis.pallas_check import _check_dma
    assert _check_dma() == []


def test_kmv_stream_site_is_registered():
    """The streaming pallas_call is exercised by the registry (no
    CHK-SITE blind spot) and its ANY-space inputs do not count against
    the CHK-VMEM block budget."""
    from repro.analysis.registry import capture_entry_points
    calls = [c for c in capture_entry_points()
             if c.path.endswith(os.path.join("kernels", "kmv_stream.py"))]
    assert calls, "kmv_stream_pallas not driven by any entry point"
    for call in calls:
        anys = [s for s in call.in_specs if s.is_any_space]
        assert len(anys) == 2              # Xc and Xvc stay off-chip
        assert call.block_bytes() < 2 ** 20
