"""The `poisson` driver: independent requests arriving open-loop at a
fixed rate into the system's service, which drains them with its own
daemon loop (`serve_forever`) until a preemption notice stops it at a
batch boundary; and its check against the plain reference. A traffic
file names it by its `driver` key and gives:

  rate_per_s  the arrival rate, requests a second (Poisson)
  backlog     requests already queued when the window opens
  nt          [lo, hi], each request's steps, uniform
  nt_seed     the seed of the `nt` stream
  ic_scale    [lo, hi], each request's initial-field scale, uniform

The system's program has new_service() -> SimulationService and
request(request_id, nt, ic_scale) -> Request; the reference has
initial_field(config, ic_scale, dtype) and advance(config, T, nt,
dtype).

Inputs: the arrival gaps and `ic_scale` are drawn i.i.d. from the run's
seed, and `nt` i.i.d. from a stream of its own, seeded with `nt_seed`:
every run serves the same sequence of steps, request for request,
whatever its seed. A batch runs its longest lane's steps, so the share
of padding in a window of some 36 batches spreads by about 1.3% from
one draw of the steps to another, more than the throughput's bound
allows; arrivals and scales move it far less (PERF.md §6).

Set-up builds the service and serves one batch of each width the window
can meet (each power of two up to the service's `max_width`, of the
traffic's one steps bucket), which compiles every program class it will
use. The window opens with the backlog submitted and the service's loop
started; arrivals follow for `seconds`, then `preempt.request()` stops
dispatch at the next batch boundary and the batches in flight finish.
The window runs from the first submit to the return of the service's
loop, which is the last batch resolved. It counts the requests served
in it and their steps: `steps` is the sum of their `nt`, so a lane's
padding steps and idle lanes are not counted. A window that has not
closed `HARD_S` seconds after arrivals end raises.

What `correct` compares is `gap_motion`, as for the `calls` driver, but
tile by tile: the field is cut into tiles of TILE cells a side, and for
each tile max|out - ref| / max|ref - in|, where `in` is the request's
initial field and `ref` the reference's `nt` steps from it; a request
reads the largest over its tiles. Each tile is held to its own motion,
so the far field, whose values are many orders below the centre's,
is checked as closely as the centre. A request whose result kept its
initial field in any tile reads 1; a non-finite result reads inf, and
so does every request that ended failed, rejected, expired or
quarantined. The compared requests are the first and the last served
and, for each lane position a batch of the window had, one request
served in that lane, drawn from the seed: the window wraps the
service's batch resolve (`_resolve_batch`) to see each batch's lanes.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import random
import threading
import time

# The precision one step below the configuration's: the control's.
LOWER = {"f64": "float32", "f32": "bfloat16"}
HARD_S = 30.0  # how long after the arrivals end the window may last
TILE = 16  # cells a side of the tiles gap_motion is taken over
_FAILED = ("failed", "rejected", "expired", "quarantined")


def requests(seed: int, traffic: dict,
             n: int) -> list[tuple[float, int, float]]:
    """(arrival offset s, nt, ic_scale) of the first `n` requests, the
    backlog's first, whose offsets are 0."""
    lo, hi = (int(v) for v in traffic["nt"])
    s_lo, s_hi = (float(v) for v in traffic["ic_scale"])
    rng = random.Random(int(seed))
    steps = random.Random(f"nt {int(traffic['nt_seed'])}")
    rate, backlog = float(traffic["rate_per_s"]), int(traffic["backlog"])
    t, out = 0.0, []
    for k in range(n):
        if k >= backlog:
            t += rng.expovariate(rate)
        out.append((t if k >= backlog else 0.0, steps.randint(lo, hi),
                    rng.uniform(s_lo, s_hi)))
    return out


def _outstanding(svc) -> int:
    """Requests submitted and not yet ended."""
    c = svc.queue.counters()
    return c["submitted"] - c["completed"] - sum(c[k] for k in _FAILED)


def _compiles() -> int:
    from rocm_mpi_tpu.telemetry import compiles

    return int(compiles.snapshot()["totals"]["backend_compiles"])


@functools.lru_cache(maxsize=None)
def _tiled():
    """jitted (tin, tout, ref) -> (the largest tile's gap / motion, out
    finite): gap_motion of each TILE-sided tile against its own motion.
    A tile that did not move reads 0 where the result kept it, inf where
    not."""
    import jax
    import jax.numpy as jnp

    def tiles(x):
        split = [m for n in x.shape for m in (n // TILE, TILE)]
        return jnp.max(jnp.abs(x).reshape(split),
                       axis=tuple(range(1, 2 * x.ndim, 2)))

    @jax.jit
    def worst(tin, tout, ref):
        tout = tout.astype(jnp.float32)
        gap, motion = tiles(tout - ref), tiles(ref - tin)
        moved = motion > 0.0
        ratio = jnp.where(moved, gap / jnp.where(moved, motion, 1.0),
                          jnp.where(gap > 0.0, jnp.inf, 0.0))
        return jnp.max(ratio), jnp.all(jnp.isfinite(tout))

    return worst


@functools.lru_cache(maxsize=None)
def _tick():
    """A tiny jitted op: the window's first device op, so the trace's
    window lines up with the host's."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda x: x + 1), jnp.zeros((8, 128), jnp.float32)


@dataclasses.dataclass
class Window:
    requests: int  # served in the window
    steps: int  # the sum of their nt
    seconds: float
    submitted: int
    outstanding: list[int]  # submitted and not ended, each whole second
    late_max_s: float  # how late the arrivals ran, at most
    compiles: int  # backend compiles inside the window
    pipeline: dict  # the service's pipeline_stats() over the window

    @property
    def attempted(self) -> int:
        return self.requests

    def describe(self) -> list[str]:
        p = self.pipeline
        return [
            f"[window] {self.requests} of {self.submitted} requests served "
            f"({self.steps} steps) in {self.seconds:.4f} s; "
            f"{self.compiles} backend compiles inside the window; "
            f"arrivals at most {1e3 * self.late_max_s:.2f} ms late",
            "[queue] outstanding each second: "
            + " ".join(str(n) for n in self.outstanding),
            "[pipeline] " + " ".join(f"{k} {v!r}" for k, v in p.items()),
        ]


class Session:
    """Set-up for one seed: the service built and every program class the
    window can meet served once."""

    def __init__(self, cell, seed: int, program, reference, mark):
        self.cell, self.seed, self.reference = cell, int(seed), reference
        self.program = program
        t = cell.traffic
        self.svc = program.new_service()
        lo = int(t["nt"][0])
        for w in (1 << i for i in range(
                self.svc.config.max_width.bit_length())):
            rep = self.svc.run_trace([program.request(f"warm-{w}-{i}", lo, 1.0)
                                      for i in range(w)])
            if rep.served != w:
                raise RuntimeError(f"warm-up served {rep.served} of {w}")
        tick, x = _tick()
        tick(x).block_until_ready()
        mark("service warmed")
        # Enough arrivals for a window of a minute at four times the rate.
        n = int(t["backlog"]) + int(4 * float(t["rate_per_s"]) * 60) + 64
        self.plan = requests(seed, t, n)
        self.compared: list = []
        self.ended_badly: list = []

    def window(self, seconds: float) -> Window:
        from jax.profiler import TraceAnnotation

        from rocm_mpi_tpu.resilience import preempt

        svc, plan, req = self.svc, self.plan, self.program.request
        backlog = int(self.cell.traffic["backlog"])
        tickets, outstanding, late, errors = [], [], [0.0], []
        lanes: dict[int, list] = {}  # lane position -> tickets it served
        tick, x = _tick()
        resolve_batch = svc._resolve_batch

        def resolve(fl):
            for j, t in enumerate(fl.live):
                lanes.setdefault(j, []).append(t)
            return resolve_batch(fl)

        svc._resolve_batch = resolve

        def submit(k):
            _, nt, scale = plan[k]
            tickets.append(svc.queue.submit(req(f"req-{k}", nt, scale)))

        def arrivals():
            k, next_sample = backlog, 1.0
            while True:
                due = min(plan[k][0] if k < len(plan) else math.inf,
                          next_sample, seconds)
                time.sleep(max(0.0, t0 + due - time.monotonic()))
                if due == next_sample or due >= seconds:
                    outstanding.append(_outstanding(svc))
                    next_sample += 1.0
                    if due >= seconds:
                        break
                    continue
                late[0] = max(late[0], time.monotonic() - t0 - due)
                submit(k)
                k += 1
            preempt.request()

        def serve():
            try:
                svc.serve_forever()
            except Exception as e:  # re-raised by the window
                errors.append(e)

        c0, p0 = _compiles(), svc.pipeline_stats()
        producer = threading.Thread(target=arrivals, daemon=True)
        server = threading.Thread(target=serve, daemon=True)
        try:
            with TraceAnnotation("bench.window"):
                with TraceAnnotation("bench.tick"):
                    tick(x)
                t0 = time.monotonic()
                for k in range(backlog):
                    submit(k)
                server.start()
                producer.start()
                server.join(timeout=seconds + HARD_S)
                if server.is_alive():
                    raise TimeoutError(
                        f"the service's loop did not stop {HARD_S} s "
                        "after the preemption notice")
                t_end = time.monotonic()
            producer.join()
        finally:
            preempt.reset()
        if errors:
            raise errors[0]
        p1 = svc.pipeline_stats()
        pipe = {k: v - p0[k] for k, v in p1.items()
                if k not in ("depth", "bubble")}
        done = sorted((t for t in tickets if t.state == "done"),
                      key=lambda t: t.ordinal)
        self.ended_badly = [t for t in tickets if t.state in _FAILED]
        if done:
            rng = random.Random(self.seed + 1)
            drawn = [rng.choice(served) for served in (
                [t for t in lanes[j] if t.state == "done"]
                for j in sorted(lanes)) if served]
            self.compared = list({id(t): t for t in (
                done[0], *drawn, done[-1])}.values())
        return Window(
            requests=len(done), steps=sum(t.request.nt for t in done),
            seconds=t_end - t0, submitted=len(tickets),
            outstanding=outstanding, late_max_s=late[0],
            compiles=_compiles() - c0, pipeline=pipe)

    def check(self) -> list[tuple[str, dict]]:
        """(request, {"gap_motion": ratio}) for each compared request and
        each that ended badly; frees the results."""
        import jax.numpy as jnp

        cfg, ref = self.cell.config, self.reference
        if any(n % TILE for n in cfg["global_shape"]):
            raise ValueError(f"the field {cfg['global_shape']} is not cut "
                             f"into tiles of {TILE}")
        out = []
        for t in self.compared:
            r = t.request
            tin = jnp.asarray(ref.initial_field(cfg, r.ic_scale))
            want = ref.advance(cfg, tin, r.nt, tin.dtype)
            worst, finite = (v.item() for v in _tiled()(
                tin, jnp.asarray(t.result(timeout=0)[0]), want))
            sound = finite and math.isfinite(worst)
            out.append((f"request {t.ordinal} (nt {r.nt})",
                        {"gap_motion": worst if sound else math.inf}))
        out += [(f"request {t.ordinal} {t.state}", {"gap_motion": math.inf})
                for t in self.ended_badly]
        self.compared, self.ended_badly = [], []
        return out


def start(cell, seed: int, program, reference,
          mark=lambda what: None) -> Session:
    """Set-up of one run: build the service, warm every program class the
    window can meet; mark(what) is called as each part ends."""
    return Session(cell, seed, program, reference, mark)


def control(cell, program, reference):
    """The plain reference, computed one precision below the
    configuration's (LOWER) a step at a time, in the service's place:
    each batch's results are replaced by the reference's, lane by lane,
    before the service resolves them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = cell.config
    lo = jnp.dtype(LOWER[cfg["dtype"]])

    def lane(r):
        T = jnp.asarray(reference.initial_field(cfg, r.ic_scale, lo))
        for _ in range(r.nt):
            T = reference.advance(cfg, T, 1, lo)
        return np.asarray(T.astype(jnp.float32))

    def new_service():
        svc = program.new_service()
        real = svc._resolve_batch

        def resolve(fl):
            out = np.zeros((fl.width, *cfg["global_shape"]), np.float32)
            for j, t in enumerate(fl.live):
                out[j] = lane(t.request)
            fl.out = (jax.device_put(out),)
            return real(fl)

        svc._resolve_batch = resolve
        return svc

    return dataclasses.replace(program, new_service=new_service)
