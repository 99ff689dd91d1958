"""The two traffic kinds the benchmark drives, and their checks.

``fit``: fits back to back through the facade (``KernelRidge.fit`` /
``KernelSVM.fit``) for ``seconds``; the window closes at the end of a
fit.  Fit ``i`` uses schedule seed ``seed * 1000 + i`` (mod 2**31), so
every fit does the same work on a different schedule.

``serve``: an open loop.  ``round(rate * seconds)`` requests are due at
uniform random times in the window (a Poisson process conditioned on its
count), each asking for a log-uniform number of query rows; the sizes
are one fixed multiset, drawn from the mix's ``base_seed`` and shuffled
by the run's seed.  One thread submits every due request and steps the
engine; a request's latency runs from when it was due until its values
are on the host.  The window reports the median latency over every
request due in it (``serve_p50_ms``) and the query rows whose values
reached the host inside it per second (``serve_rows_per_s``, which
equals the offered load below the knee; ``bench/knee.py`` reads it).

Data (and the served model's weights) are made on the device from the
seed in one jitted call, already laid out on the cell's mesh.
"""
from __future__ import annotations

import dataclasses
import math
import time
from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import reference

MASK32 = 0xFFFFFFFF


def seed_key(seed: int, stream: int = 0):
    """A key that depends on every bit of a seed of up to 64 bits."""
    seed %= 2 ** 64
    k = jax.random.fold_in(jax.random.key(seed & MASK32), seed >> 32)
    return jax.random.fold_in(k, stream)


def sched_seed(seed: int, i: int) -> int:
    """Fit i's schedule seed, below 2**31 so every key is made alike."""
    return (seed * 1000 + i) % 2 ** 31


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2 ** 64, stream])


# ---------------------------------------------------------------------------
# data and mesh
# ---------------------------------------------------------------------------

def mesh_for(config: dict, devices) -> Optional[jax.sharding.Mesh]:
    """The mesh a distributed layout runs on: every device on ``data``
    for 2d, on ``model`` for 1d; None for the serial layout."""
    layout = config["options"].get("layout", "serial")
    if layout == "serial":
        return None
    shape = (len(devices), 1) if layout == "2d" else (1, len(devices))
    auto = (jax.sharding.AxisType.Auto,) * 2
    return jax.make_mesh(shape, ("data", "model"), axis_types=auto,
                         devices=devices)


def make_data(config: dict, seed: int, mesh=None):
    """(A, y) for the configuration's generator, made on the device (on
    the mesh, rows over ``data``, when there is one).

    regression:     A ~ N(0, 1/n), y = sin(A w) + noise * N(0, 1)
    classification: y = +1 with probability p_positive, else -1;
                    A ~ N(0, 1/n) shifted by
                    margin * y * w / sqrt(n) along a unit direction w
    pixels:         y as for classification; 8-bit pixels q / 256 with
                    q = floor(256 * (0.5 + 0.2 N(0, 1) + margin * y * w)),
                    w ~ N(0, 1) per feature, clipped to [0, 255]
    """
    m, n = config["m"], config["n"]
    d = config["data"]
    kind = d["kind"]

    def gen(key):
        k1, k2, k3 = jax.random.split(key, 3)
        if kind == "pixels":
            # 8-bit pixel values q / 256, exact in bfloat16
            z = jax.random.normal(k1, (m, n), jnp.float32)
            w = jax.random.normal(k2, (n,), jnp.float32)
            y = jnp.where(jax.random.bernoulli(k3, d["p_positive"], (m,)),
                          1.0, -1.0)
            v = 0.5 + 0.2 * z + d["margin"] * y[:, None] * w[None, :]
            X = jnp.floor(256 * jnp.clip(v, 0.0, 255 / 256)) / 256
            return X, y.astype(jnp.float32)
        if kind == "regression":
            X = jax.random.normal(k1, (m, n), jnp.float32) / math.sqrt(n)
            w = jax.random.normal(k2, (n,), jnp.float32)
            y = (jnp.sin(X @ w)
                 + d["noise"] * jax.random.normal(k3, (m,), jnp.float32))
            return X, y
        w = jax.random.normal(k1, (n,), jnp.float32)
        w = w / jnp.linalg.norm(w)
        y = jnp.where(jax.random.bernoulli(k2, d["p_positive"], (m,)),
                      1.0, -1.0)
        X = jax.random.normal(k3, (m, n), jnp.float32) / math.sqrt(n)
        X = X + d["margin"] * y[:, None] * w[None, :] / math.sqrt(n)
        return X, y.astype(jnp.float32)

    if mesh is None:
        f = jax.jit(gen)
    else:
        f = jax.jit(gen, out_shardings=(NamedSharding(mesh, P("data", None)),
                                        NamedSharding(mesh, P("data"))))
    return jax.block_until_ready(f(seed_key(seed)))


# ---------------------------------------------------------------------------
# fit traffic
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Fit:
    index: int
    t0: float
    t1: float
    alpha: object
    schedule: object
    rounds: int
    spans: list                     # program telemetry spans (traced run)


class FitDriver:
    def __init__(self, cell, seed: int, devices):
        from repro.api import KernelRidge, KernelSVM, SolverOptions
        from repro.core import KernelConfig

        self.cell = cell
        self.seed = seed
        c = cell.config
        self.opts = {**c["options"], **cell.traffic.get("options", {})}
        self.mesh = mesh_for(c, devices)
        self.exact = c["data"]["kind"] == "pixels"
        # the reference runs on the devices that hold the rows
        self.ref_mesh = self.mesh or jax.make_mesh(
            (1, 1), ("data", "model"), devices=devices[:1],
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        self.A, self.y = make_data(c, seed, self.mesh)
        kernel = KernelConfig(c["kernel"]["name"],
                              sigma=c["kernel"]["sigma"])
        if c["problem"] == "krr":
            self._est = lambda o: KernelRidge(lam=c["lam"], kernel=kernel,
                                              options=o)
        else:
            self._est = lambda o: KernelSVM(C=c["C"], loss=c["loss"],
                                            kernel=kernel, options=o)
        self._options = SolverOptions
        self.fits: List[Fit] = []
        self.last = None

    def fit(self, i: int, telemetry=None) -> Fit:
        import jax.profiler as jp

        self.last = None       # the last fit's state goes before the next
        opts = self._options(**self.opts, seed=sched_seed(self.seed, i),
                             mesh=self.mesh, telemetry=telemetry)
        est = self._est(opts)
        with jp.TraceAnnotation("bench.fit"):
            t0 = time.perf_counter()
            res = est.fit(self.A, self.y)
            jax.block_until_ready(res.alpha)
            t1 = time.perf_counter()
        self.last = est
        spans = [] if telemetry is None else [
            (s.name, s.t0, s.t1) for s in telemetry.spans]
        return Fit(i, t0, t1, res.alpha, res.schedule, res.rounds_run,
                   spans)

    def warm(self) -> None:
        self.fit(0)

    def window(self, seconds: float, traced: bool) -> dict:
        from repro.obs import Telemetry

        t_w = time.perf_counter()
        i = 1
        while True:
            self.fits.append(self.fit(i, Telemetry() if traced else None))
            i += 1
            if self.fits[-1].t1 - t_w >= seconds:
                break
        t_end = self.fits[-1].t1
        return {"t0": t_w, "t1": t_end, "attempted": len(self.fits),
                "failed": 0,
                "metrics": {"fit_s": (t_end - t_w) / len(self.fits)}}

    def free(self) -> None:
        self.last = None

    def check(self, n_check: int, control: bool = False
              ) -> Dict[str, float]:
        """Numbers compared for a sample of the window's fits drawn from
        the seed: the worst relative 2-norm gap of alpha to the
        reference's, and how many schedule entries differ from the
        reference's draw (which should be none).  ``control`` puts the
        reference computed in bfloat16 in the program's place."""
        c = self.cell.config
        o = self.opts
        H, m = o["max_iters"], c["m"]
        pick = rng(self.seed, 1).choice(len(self.fits),
                                        size=min(n_check, len(self.fits)),
                                        replace=False)
        worst, mismatch = 0.0, 0
        for k in sorted(pick):
            f = self.fits[k]
            ss = sched_seed(self.seed, f.index)
            if c["problem"] == "krr":
                sched = reference.block_schedule(ss, H, m, o["b"])
                solve = partial(reference.krr_bdcd, self.A, self.y, sched,
                                lam=c["lam"], sigma=c["kernel"]["sigma"],
                                s=o["s"], mesh=self.ref_mesh,
                                exact=self.exact)
            else:
                sched = reference.coordinate_schedule(ss, H, m)
                solve = partial(reference.ksvm_dcd, self.A, self.y, sched,
                                C=c["C"], loss=c["loss"],
                                sigma=c["kernel"]["sigma"], s=o["s"],
                                mesh=self.ref_mesh, exact=self.exact)
            got = solve(dtype=jnp.bfloat16) if control else f.alpha
            mismatch += int(np.sum(np.asarray(f.schedule).reshape(-1)
                                   != np.asarray(sched).reshape(-1)))
            worst = max(worst, rel_gap(got, solve(dtype=jnp.float32)))
        return {"alpha_rel_err": worst, "schedule_mismatch": mismatch}


def rel_gap(x, ref) -> float:
    x = np.asarray(jax.device_get(x), np.float64)
    r = np.asarray(jax.device_get(ref), np.float64)
    return float(np.linalg.norm(x - r) / np.linalg.norm(r))


# ---------------------------------------------------------------------------
# serve traffic
# ---------------------------------------------------------------------------

def serve_schedule(traffic: dict, seed: int, seconds: float):
    """(due_s, rows) of the window's requests, sorted by due time."""
    n = max(1, round(traffic["rate_rps"] * seconds))
    lo, hi = traffic["rows"]
    base = rng(traffic["base_seed"], 0)
    rows = np.floor(np.exp(base.uniform(math.log(lo), math.log(hi + 1),
                                        size=n))).astype(np.int64)
    rows = np.clip(rows, lo, hi)
    r = rng(seed, 2)
    due = np.sort(r.uniform(0.0, seconds, size=n))
    return due, r.permutation(rows)


class ServeDriver:
    def __init__(self, cell, seed: int, devices):
        from repro.api import SolverOptions
        from repro.core import ExactGramOperator, KernelConfig, KRRConfig
        from repro.serve import ModelRegistry, ServableModel

        self.cell = cell
        self.seed = seed
        c, t = cell.config, cell.traffic
        if c["problem"] != "krr":
            raise ValueError("the serve mix serves a K-RR model")
        self.A, self.y = make_data(c, seed, None)
        self.alpha = model_weights(c, seed)
        kernel = KernelConfig(c["kernel"]["name"],
                              sigma=c["kernel"]["sigma"])
        model = ServableModel(problem="krr",
                              cfg=KRRConfig(lam=c["lam"], kernel=kernel),
                              options=SolverOptions(), alpha=self.alpha,
                              y=self.y, op=ExactGramOperator(self.A, kernel))
        self.reg = ModelRegistry(predict_batch=t["slots"])
        self.reg.register("model", model)
        self.pool = np.asarray(jax.device_get(jax.jit(
            lambda k: jax.random.normal(k, (t["pool_rows"], c["n"]),
                                        jnp.float32) / math.sqrt(c["n"]))(
            seed_key(seed, 3))))
        self.engine = None
        self.requests = []

    def make_engine(self, telemetry=None):
        from repro.serve import ServingEngine
        t = self.cell.traffic
        return ServingEngine(self.reg, slots=t["slots"],
                             max_queue=t["max_queue"],
                             clock=time.perf_counter, telemetry=telemetry)

    def query(self, k: int, rows: int) -> np.ndarray:
        off = (k * 7919) % (self.pool.shape[0] - rows + 1)
        return self.pool[off:off + rows]

    def warm(self) -> None:
        eng = self.make_engine()
        eng.warmup()
        for q in eng.registry.groups()[0].predictor.bucket_sizes():
            eng.submit("model", self.query(q, q))
            eng.run_until_idle()

    def window(self, seconds: float, traced: bool) -> dict:
        import jax.profiler as jp
        from repro.obs import Telemetry

        t = self.cell.traffic
        self.tel = Telemetry() if traced else None
        eng = self.engine = self.make_engine(self.tel)
        due, rows = serve_schedule(t, self.seed, seconds)
        n = len(due)
        tickets = [None] * n
        lag = np.zeros(n)
        k = 0
        t0 = time.perf_counter()
        end = t0 + seconds
        while True:
            now = time.perf_counter()
            with jp.TraceAnnotation("bench.submit"):
                while k < n and t0 + due[k] <= now:
                    tickets[k] = eng.submit("model", self.query(k, rows[k]))
                    lag[k] = now - (t0 + due[k])
                    k += 1
            if eng.pending:
                with jp.TraceAnnotation("bench.engine_step"):
                    eng.step()
            elif k < n:
                with jp.TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, t0 + due[k] - time.perf_counter()))
            else:
                break
        # every request is due inside the window; wait up to a minute
        # past its close for the ones still queued
        while eng.pending and time.perf_counter() < end + 60.0:
            eng.step()
        self.requests = list(zip(due, rows, tickets))
        done = [tk for tk in tickets if tk.status == "done"]
        lat = np.array([tk.t_done - (t0 + d)
                        for d, _, tk in self.requests
                        if tk.status == "done"])
        rows_in = sum(int(r) for d, r, tk in self.requests
                      if tk.status == "done" and tk.t_done <= end)
        self.lag = lag
        self.drain_s = max(0.0, time.perf_counter() - end)
        self.p95_ms = float(np.percentile(lat, 95)) * 1e3
        return {"t0": t0, "t1": max(end, time.perf_counter()),
                "attempted": n, "failed": n - len(done),
                "metrics": {
                    "serve_p50_ms": float(np.percentile(lat, 50)) * 1e3,
                    "serve_rows_per_s": rows_in / seconds}}

    def free(self) -> None:
        self.engine = None
        self.reg = None

    def check(self, n_check: int, control: bool = False
              ) -> Dict[str, float]:
        """The number compared for a sample of the served requests drawn
        from the seed, the longest among them: the widest gap of a served
        value to the reference's, |f - f_ref|, over the row's absolute
        sum sum_i |w_i| K(q, a_i) -- the scale a rounding of the sum's
        terms is measured against (f itself cancels: w has both signs).
        ``control`` puts the reference computed in bfloat16 in the
        program's place."""
        c = self.cell.config
        done = [i for i, (_, _, tk) in enumerate(self.requests)
                if tk.status == "done"]
        longest = max(done, key=lambda i: self.requests[i][1])
        others = [i for i in done if i != longest]
        pick = [longest] + list(rng(self.seed, 4).choice(
            others, size=min(n_check - 1, len(others)), replace=False))
        Q = np.concatenate([self.requests[i][2].X for i in pick])
        got = np.concatenate([np.asarray(self.requests[i][2].result)
                              for i in pick])
        w = self.alpha / c["lam"]
        values = partial(reference.serve_values, jnp.asarray(Q), self.A,
                         sigma=c["kernel"]["sigma"], rows=128)
        if control:
            got = values(w, dtype=jnp.bfloat16)
        ref = np.asarray(values(w, dtype=jnp.float32), np.float64)
        scale = np.asarray(values(jnp.abs(w), dtype=jnp.float32), np.float64)
        gap = np.abs(np.asarray(got, np.float64) - ref) / scale
        return {"served_gap": float(gap.max())}


def model_weights(config: dict, seed: int):
    """The served model's dual weights, made from the seed: N(0, 1) / m.
    (Weights proportional to the targets gave served values whose
    bfloat16 control read less than twice the program's gap, so no
    limit could separate them.)"""
    return jax.random.normal(seed_key(seed, 5), (config["m"],),
                             jnp.float32) / config["m"]


DRIVERS = {"fit": FitDriver, "serve": ServeDriver}
