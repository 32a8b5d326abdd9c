"""The system under test, and the three ways traffic drives it.

``System`` builds the program's entry points from a configuration file:
the stencil from the file's own offsets and weights, the grid, the dtype,
and for a sharded deployment the mesh. The load is chosen by the
traffic file's ``kind``:

* ``closed_fixed``: back-to-back fixed-sweep solves (``engine.run``, or
  ``engine.run_distributed`` where the configuration names a mesh);
* ``closed_tol``: back-to-back ``engine.run_converged`` solves to a stated
  tolerance;
* ``open_serve``: requests sent to a ``SolveServer`` on a schedule,
  whether or not earlier ones have finished.

Each load makes its inputs from the seed at set-up, warms every program
the window will run, runs the window, and hands back what the window
produced (``answers``) for the comparison with the reference, the work it
did (``work``) for the roofline share, and its counters.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import workcount

#: Seconds past the window's close that a served request may still finish.
GRACE_S = 60.0


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def grid_form(config: dict, r: int):
    """(interior shape, ringed shape, ``ring``) of a configuration, where
    ``ring(dtype)`` gives a ringed grid whose ring holds the
    configuration's fixed (Dirichlet) values; its interior is overwritten.

    A configuration takes one of two forms:

    * 2-D: ``ny`` and ``nx`` interior points, and a ``ring`` of constant
      ``left``, ``right``, ``top`` and ``bottom`` sides (the top and bottom
      rows hold the corners);
    * n-D: ``shape``, the interior points per axis (outermost first, the
      lane axis last), and an affine ``ring`` ``{"const": c, "coef":
      [c_0, ...]}``: ``c + sum_k c_k p_k`` at ringed index ``p``, computed
      in float32. It holds PolyBench's initial boundaries, which are affine
      in the index, and a zero ring.
    """
    ring = config["ring"]
    interior = tuple(config["shape"]) if "shape" in config else (
        config["ny"], config["nx"])
    shape = workcount.ringed_shape(interior, r)
    if "shape" not in config:
        def ring_2d(dtype):
            import jax.numpy as jnp
            u = jnp.zeros(shape, dtype)
            u = u.at[:, :r].set(ring["left"]).at[:, -r:].set(ring["right"])
            return u.at[:r, :].set(ring["top"]).at[-r:, :].set(
                ring["bottom"])
        return interior, shape, ring_2d

    if len(ring["coef"]) != len(shape):
        raise ValueError(f"ring coef {ring['coef']} does not give one "
                         f"coefficient per axis of shape {interior}")

    def ring_affine(dtype):
        import jax.numpy as jnp
        from jax import lax
        v = jnp.full(shape, ring["const"], jnp.float32)
        for k, c in enumerate(ring["coef"]):
            v = v + c * lax.broadcasted_iota(jnp.float32, shape, k)
        return v.astype(dtype)
    return interior, shape, ring_affine


class System:
    """The program's entry points for one configuration."""

    def __init__(self, config: dict, devices: list):
        import jax
        import jax.numpy as jnp
        from repro import engine
        from repro.core.stencil import StencilSpec

        self.jax, self.jnp, self.engine = jax, jnp, engine
        self.config = config
        self.spec = StencilSpec(
            offsets=tuple(tuple(o) for o in config["offsets"]),
            weights=tuple(float(w) for w in config["weights"]))
        self.r = self.spec.radius
        self.dtype = jnp.dtype(config["dtype"])
        self.interior, self.shape, self._ring = grid_form(config, self.r)
        self.points = workcount.interior_points(self.interior)
        self.taps = self.spec.taps
        self.devices = devices[:config["chips"]]
        self.mesh = None
        if config.get("mesh"):
            self.mesh = jax.make_mesh(tuple(config["mesh"]["shape"]),
                                      tuple(config["mesh"]["axes"]),
                                      devices=self.devices)

    def pool(self, seed: int, n: int) -> tuple:
        """``n`` ringed grids: the configuration's fixed ring (see
        :func:`grid_form`) around an interior uniform in [0, 1), made on
        the default device in one call and left uncommitted to it, as a
        caller's grid is: run_distributed refuses a grid committed to one
        device or replicated over its mesh."""
        jax, jnp, dtype = self.jax, self.jnp, self.dtype
        inner = (slice(self.r, -self.r),) * len(self.shape)
        words = np.random.SeedSequence(seed).generate_state(2)

        def gen(k0, k1):
            key = jax.random.fold_in(jax.random.PRNGKey(k0), k1)
            u = self._ring(dtype)
            return tuple(
                u.at[inner].set(jax.random.uniform(
                    k, self.interior, jnp.float32).astype(dtype))
                for k in jax.random.split(key, n))

        return jax.jit(gen)(
            np.uint32(words[0]), np.uint32(words[1]))

    def fixed(self, u, sweeps: int):
        cfg = self.config
        if self.mesh is not None:
            return self.engine.run_distributed(
                u, self.spec, mesh=self.mesh, policy=cfg["policy"],
                iters=sweeps, t=cfg["t"], overlap=None)
        return self.engine.run(u, self.spec, policy=cfg["policy"],
                               iters=sweeps)

    def to_tol(self, u, tol: float, max_iters: int):
        return self.engine.run_converged(u, self.spec, tol=tol,
                                         max_iters=max_iters,
                                         policy=self.config["policy"])


class Reservoir:
    """A uniform sample of ``k`` items from a stream, drawn from a seed."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = np.random.default_rng(seed)
        self.items: list = []
        self.seen = 0

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
            return
        j = int(self.rng.integers(self.seen))
        if j < self.k:
            self.items[j] = item


class Load:
    """What every load hands the harness after its window."""

    def __init__(self, system: System, traffic: dict):
        self.system = system
        self.traffic = traffic
        self.window_s = 0.0
        self.answers: list[dict] = []
        self.work = {"ops": 0, "bytes": 0, "sweeps": []}
        self.counters: dict = {}
        self.attempted = 0
        self.failed = 0
        self.lateness_s = 0.0

    def reseed(self, seed: int, seconds: float) -> None:
        """The inputs of one run: a pool of grids drawn from ``seed``, or,
        where the traffic file fixes ``grid_seed``, from that: a solve to
        tolerance takes sweeps that vary widely from grid to grid, so
        every run of such a cell gets the same set of grids and the run's
        seed only orders them and draws the sample compared."""
        self.seed = seed
        self.grids = self.system.pool(self.traffic.get("grid_seed", seed),
                                      self.traffic["pool"])
        self.order = np.random.default_rng(seed).permutation(
            len(self.grids))

    def release(self) -> None:
        """Drop the program's state once the window is over; the answers
        keep what the comparison needs."""
        self.__dict__.pop("grids", None)
        self.__dict__.pop("server", None)

    def _account(self, sweeps: int) -> None:
        s = self.system
        self.work["ops"] += workcount.sweep_ops(s.points, sweeps, s.taps)
        self.work["bytes"] += workcount.compulsory_bytes(
            s.shape, s.dtype.itemsize, 1)
        self.work["sweeps"].append(sweeps)


class ClosedFixed(Load):
    """Back-to-back solves of ``sweeps`` sweeps each, cycling through the
    pool in the seed's order; one solve in flight at a time."""

    def setup(self, seed: int, seconds: float) -> None:
        self.reseed(seed, seconds)
        self.sweeps = self.traffic["sweeps"]
        self.system.jax.block_until_ready(
            self.system.fixed(self.grids[0], self.sweeps))

    def window(self, seconds: float) -> None:
        block = self.system.jax.block_until_ready
        sample = Reservoir(self.traffic["sample"], self.seed)
        n = 0
        t0 = time.perf_counter()
        while True:
            i = int(self.order[n % len(self.order)])
            with _annotate("bench.solve"):
                out = block(self.system.fixed(self.grids[i], self.sweeps))
            n += 1
            sample.offer((i, out))
            if time.perf_counter() - t0 >= seconds:
                break
        self.window_s = time.perf_counter() - t0
        self.attempted = n
        for _ in range(n):
            self._account(self.sweeps)
        self.answers = [{"input": self.grids[i], "output": out,
                         "sweeps": self.sweeps} for i, out in sample.items]

    def end_to_end(self) -> dict:
        pts = self.system.points * self.sweeps * self.attempted
        return {"gpts": pts / self.window_s / 1e9}


class ClosedTol(Load):
    """Back-to-back solves to ``tol`` (budget ``max_iters``) over the
    pool; one solve in flight at a time."""

    def setup(self, seed: int, seconds: float) -> None:
        self.reseed(seed, seconds)
        self.tol = self.traffic["tol"]
        self.max_iters = self.traffic["max_iters"]
        self.system.to_tol(self.grids[0], self.tol, self.max_iters)

    def window(self, seconds: float) -> None:
        """Whole passes over the pool, in the seed's order, until a pass
        ends at least ``seconds`` after the first began: every run does
        the same solves, so the time per solve does not hang on which
        grids a partial pass happened to hold."""
        sample = Reservoir(self.traffic["sample"], self.seed)
        n = 0
        t0 = time.perf_counter()
        while True:
            i = int(self.order[n % len(self.order)])
            with _annotate("bench.solve"):
                out, iters, res = self.system.to_tol(
                    self.grids[i], self.tol, self.max_iters)
            n += 1
            self._account(iters)
            sample.offer((i, out, iters, res))
            if (n % len(self.order) == 0
                    and time.perf_counter() - t0 >= seconds):
                break
        self.window_s = time.perf_counter() - t0
        self.attempted = n
        self.answers = [{"input": self.grids[i], "output": out,
                         "sweeps": iters, "tol": self.tol,
                         "converged": res <= self.tol,
                         "max_iters": self.max_iters}
                        for i, out, iters, res in sample.items]

    def end_to_end(self) -> dict:
        return {"solve_s": self.window_s / self.attempted}


def arrival_schedule(seed: int, n: int, rate: float, tol_range,
                     pool: int):
    """Arrival times, tolerances and pool indices of ``n`` requests.

    Every seed gets the same multiset of gaps and the same multiset of
    requests, in its own order: the gaps are the ``(i + 0.5) / n``
    quantiles of an exponential distribution of mean ``1 / rate``
    (Poisson arrivals with the sampling noise of the gaps taken out); the
    ``i``-th request pairs the same quantile of a log-uniform distribution
    over ``tol_range`` with grid ``i % pool``. So a run's work and its
    mean rate are fixed; the seed changes only the order of gaps and of
    requests.
    """
    rng = np.random.default_rng(seed)
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / rate)
    arrivals = np.cumsum(gaps) - gaps  # the first request is due at once
    lo, hi = (math.log(x) for x in tol_range)
    order = rng.permutation(n)
    return (arrivals, np.exp(lo + q * (hi - lo))[order],
            (np.arange(n) % pool)[order])


class OpenServe(Load):
    """Requests sent to one ``SolveServer`` on a fixed schedule.

    Each request is timed from its scheduled arrival to the moment the
    server hands back its result. Requests stream their progress to a
    callback that does nothing, as a client showing progress would, which
    keeps every request on the server's batched slot path.
    """

    def setup(self, seed: int, seconds: float) -> None:
        from repro.serve import SolveServer

        self.reseed(seed, seconds)
        self.server = SolveServer(max_slots=self.traffic["max_slots"])
        self._warm()

    def reseed(self, seed: int, seconds: float) -> None:
        """The inputs of one run: the pool of grids (see
        :meth:`Load.reseed`) and the schedule of requests."""
        super().reseed(seed, seconds)
        self.schedule(seed, seconds, self.traffic["rate"])

    def schedule(self, seed: int, seconds: float, rate: float) -> None:
        """The window's requests: ``rate`` per second for ``seconds``."""
        tr = self.traffic
        n = max(1, round(rate * seconds))
        self.arrivals, self.tols, self.which = arrival_schedule(
            seed, n, rate, tr["tol_range"], tr["pool"])

    def _request(self, grid, tol, max_iters):
        from repro.serve import SolveRequest
        return SolveRequest(grid=grid, spec=self.system.spec, tol=tol,
                            max_iters=max_iters,
                            policy=self.system.config["policy"],
                            stream=_ignore_progress)

    def _warm(self) -> None:
        """Run, on fixed-budget requests, every change of slot width the
        window can make: slot widths are powers of two up to
        ``max_slots``; an idle server grows or shrinks to the next burst,
        and a busy one shrinks when at most half of its lanes are still
        busy. A short request ends after one superblock
        (``superblock_sweeps``), a long one after two."""
        tr = self.traffic
        short = tr["superblock_sweeps"]
        widths = [1 << i for i in range(tr["max_slots"].bit_length())]

        def burst(n_short: int, n_long: int) -> None:
            for j in range(n_short + n_long):
                self.server.submit(self._request(
                    self.grids[j % len(self.grids)], None,
                    2 * short if j < n_long else short))
            self.server.drain()

        for a in widths:
            for b in widths:
                if b > a:
                    burst(a, 0)
                    burst(b, 0)
        for w in widths[1:]:
            for kept in range(w // 2 + 1):
                burst(w - kept, kept)
        self._launches_before = self.server.stats()["launches"]

    def window(self, seconds: float, grace_s: float = GRACE_S) -> None:
        server = self.server
        n = len(self.arrivals)
        reqs: list = [None] * n
        nxt = 0
        late = 0.0
        t0 = time.perf_counter()
        due = t0 + self.arrivals
        close = t0 + seconds + grace_s
        while True:
            now = time.perf_counter()
            while nxt < n and t0 + self.arrivals[nxt] <= now:
                late = max(late, now - due[nxt])
                with _annotate("bench.submit"):
                    reqs[nxt] = server.submit(self._request(
                        self.grids[self.which[nxt]], float(self.tols[nxt]),
                        self.traffic["max_iters"]))
                nxt += 1
            if server.busy:
                with _annotate("bench.step"):
                    server.step()
            elif nxt < n:
                with _annotate("bench.wait"):
                    time.sleep(max(0.0, t0 + self.arrivals[nxt]
                                   - time.perf_counter()))
            else:
                break
            if time.perf_counter() > close:
                break
        self.window_s = time.perf_counter() - t0
        self.lateness_s = late
        self.attempted = n
        done = [i for i, r in enumerate(reqs) if r is not None and r.done]
        self.failed = n - len(done)
        self.latencies = [math.inf] * n
        for i in done:
            self.latencies[i] = reqs[i].finished_s - due[i]
        for i in done:
            self._account(reqs[i].iters_done)
        st = server.stats()
        self.counters = {"launches": st["launches"] - self._launches_before,
                         "completed": len(done)}
        self._launches_before = st["launches"]
        self._sample(reqs, done)

    def _sample(self, reqs: list, done: list[int]) -> None:
        """The answers compared with the reference: a seeded sample of the
        finished requests, with the longest solve among them. Each is
        paired with the benchmark's own copy of its input grid."""
        k = min(self.traffic["sample"], len(done))
        if not k:
            self.answers = []
            return
        longest = max(done, key=lambda i: reqs[i].iters_done)
        rest = [i for i in done if i != longest]
        rng = np.random.default_rng(self.seed)
        pick = [longest] + [int(i) for i in rng.choice(
            rest, size=k - 1, replace=False)] if k > 1 else [longest]
        self.answers = [{"input": self.grids[self.which[i]],
                         "output": self.system.jnp.asarray(reqs[i].result),
                         "sweeps": reqs[i].iters_done, "tol": reqs[i].tol,
                         "converged": reqs[i].converged,
                         "max_iters": reqs[i].max_iters} for i in pick]

    def end_to_end(self) -> dict:
        p90 = percentile(self.latencies, 90)
        return {"serve_p90_s": p90}


def _ignore_progress(req, progress) -> None:
    return None


def percentile(values, q: float) -> float:
    """The ``q``-th percentile by the nearest-rank rule: the smallest value
    with at least ``q`` percent of the values at or below it. Missing
    requests are ``inf``, so they can only push it up."""
    v = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(v)))
    return v[rank - 1]


LOADS = {"closed_fixed": ClosedFixed, "closed_tol": ClosedTol,
           "open_serve": OpenServe}
