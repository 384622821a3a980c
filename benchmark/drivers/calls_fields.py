"""The `calls_fields` driver: the `calls` protocol (drivers/calls.py) for
a state that is a tuple of fields, such as shallow water's (h, (u, v)).
A traffic file names it by its `driver` key and gives what `calls`
takes:

  steps_per_call  steps one call advances
  inputs          the parameters of the reference's make_state

The system's program has advance(state, aux, n) -> state, which donates
every field of the state, and the sharding of the fields; the reference
has make_state(seed, config, inputs, sharding) -> (state, aux) and
advance_fn(config, steps, dtype) -> jitted (state, aux) -> state. Every
field is copied, blocked on and compared.

A state is several whole fields (shallow water's is three), so fewer
are kept than `calls` keeps: the compared calls are j (drawn from the
seed, 2..5) and the window's last call, and at most three states are
kept at once: the states before calls j and j + 1, and one copy that is
taken before each call that starts within 1.5 mean calls of the close
and replaces the one before it. `calls` also compares call 0, which
would take a fourth.

What `correct` compares is `gap_motion`: for each compared call, the
largest over the fields of max|out - ref| / max|ref - in|, each field's
widest gap to the plain reference as a share of how far the reference
moved that field in that call. A call that returns its input reads 1; a
non-finite output reads inf.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time

from benchmark.drivers.calls import LOWER, Window, compared_call


@functools.lru_cache(maxsize=None)
def _copy():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda state: jax.tree.map(jnp.copy, state))


@functools.lru_cache(maxsize=None)
def _norms():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(tin, tout, ref):
        """(gap, motion, finite) of each field."""
        def one(a, b, r):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            r = r.astype(jnp.float32)
            return (jnp.max(jnp.abs(b - r)), jnp.max(jnp.abs(r - a)),
                    jnp.all(jnp.isfinite(b)))

        return [one(*f) for f in zip(jax.tree.leaves(tin),
                                     jax.tree.leaves(tout),
                                     jax.tree.leaves(ref))]

    return norms


def _ready(state):
    import jax

    return jax.block_until_ready(state)


class Session:
    """Set-up for one seed: the state made and the call shape warmed."""

    def __init__(self, cell, seed: int, program, reference, mark):
        self.cell, self.seed, self.reference = cell, int(seed), reference
        self.program = program
        self.steps = int(cell.traffic["steps_per_call"])
        self.state, self.aux = reference.make_state(
            seed, cell.config, cell.traffic["inputs"], program.sharding)
        _ready(_copy()(self.state))
        mark("fields made")
        self.state = _ready(program.advance(self.state, self.aux,
                                            self.steps))
        mark("warm call")
        self.pairs: list[tuple[int, object, object]] = []

    def window(self, seconds: float) -> Window:
        """Drive advance(state, aux, steps) back to back for `seconds`."""
        from jax.profiler import TraceAnnotation

        advance, copy, S, aux = (self.program.advance, _copy(), self.state,
                                 self.aux)
        self.state = None
        j = compared_call(self.seed)
        kept: dict[int, object] = {}
        latest = None  # (call, the state it started from)
        starts: list[float] = []
        ends: list[float] = []
        with TraceAnnotation("bench.window"):
            t0 = time.monotonic()
            while True:
                now = time.monotonic()
                k = len(starts)
                if k and now - t0 >= seconds:
                    break
                mean = (now - t0) / k if k else 0.0
                closing = k and seconds - (now - t0) < 1.5 * mean
                if k in (j, j + 1):
                    with TraceAnnotation("bench.copy"):
                        kept[k] = copy(S)
                    latest = (k, kept[k])
                elif closing:
                    latest = None  # free the copy before taking the next
                    with TraceAnnotation("bench.copy"):
                        latest = (k, copy(S))
                starts.append(time.monotonic())
                with TraceAnnotation("bench.dispatch"):
                    S = advance(S, aux, self.steps)
                with TraceAnnotation("bench.wait"):
                    _ready(S)
                ends.append(time.monotonic())
        last = len(starts) - 1
        kept[last + 1] = S
        if latest is not None and latest[0] == last:
            kept[last] = latest[1]
        pairs = {k for k in (j, last) if k in kept and k + 1 in kept}
        self.pairs = [(k, kept[k], kept[k + 1]) for k in sorted(pairs)]
        return Window(self.steps, starts, ends)

    def check(self) -> list[tuple[str, dict]]:
        """(call, {"gap_motion": ratio}) for each compared call; frees
        the kept states as it goes."""
        import jax.numpy as jnp

        ref_advance = self.reference.advance_fn(self.cell.config,
                                                self.steps, jnp.float32)
        out = []
        while self.pairs:
            k, tin, tout = self.pairs.pop(0)
            ref = ref_advance(tin, self.aux)
            ratio = 0.0
            for gap, motion, finite in _norms()(tin, tout, ref):
                gap, motion, finite = gap.item(), motion.item(), finite.item()
                sound = finite and math.isfinite(gap) and motion > 0.0
                ratio = max(ratio, gap / motion if sound else math.inf)
            del tin, tout, ref
            out.append((f"call {k}", {"gap_motion": ratio}))
        return out


def start(cell, seed: int, program, reference,
          mark=lambda what: None) -> Session:
    """Set-up of one run: make the state from `seed`, warm the one call
    shape; mark(what) is called as each part ends."""
    return Session(cell, seed, program, reference, mark)


def control(cell, program, reference):
    """The plain reference, computed one precision below the
    configuration's (calls.LOWER), put in the program's place."""
    import jax.numpy as jnp

    lo = jnp.dtype(LOWER[cell.config["dtype"]])
    steps = int(cell.traffic["steps_per_call"])
    ref = reference.advance_fn(cell.config, steps, lo)

    def advance(state, aux, n):
        if n != steps:
            raise ValueError(f"the control runs {steps} steps a call")
        return ref(state, aux)

    return dataclasses.replace(program, advance=advance)
