"""The schedule layer, written once for every workload model.

`Driver` is the models' base class. A model supplies, and nothing else:

  * `state_and_aux()` — its initial state, a tuple of the model's state
    arguments (diffusion `(T,)`, wave `(U, U_prev)`, SWE `(h, us)`), and
    its loop-invariant operand (`Cp`, `C2`, the face masks `Mus`);
  * `_step(variant)` — `(step, prepare)`: `step(state, P) -> state` with
    `P = prepare(aux)` (`aux` itself when `prepare` is None), and
    `_make_batched_step(bgrid, variant)` in the same form over
    lane-batched state; `_ladder_step(bgrid)` where it has a ladder;
  * `_deep_schedule(k, wire_mode)` — its parallel.deep_halo schedule;
  * `_result(state, **window)` — its RunResult;
  * its physics, initial state, variants and deep-depth policy
    (`effective_deep_depth`).

From those the base builds every loop, each jitted with every state leaf
donated and traced under layer="driver" (docs/TELEMETRY.md "Device-side
names"), each called as `advance(*state, *operands, n) -> state` (a lone
state leaf bare):

  * one loop: a fori_loop over n // q of `lax.scan(length=q, unroll=2)`,
    `prepare` traced once outside it — `scan_advance_fn` at the q both
    timing windows share, `advance_fn` at q = 1;
  * one lane-freeze loop — `batched_advance_fn`,
    `batched_ladder_advance_fn`;
  * one deep sweep loop, with or without the wire-state carry —
    `deep_advance_fn`;
  * one timed window — `run`, `run_deep` and the models' VMEM paths;
  * one result base — `Result`.
"""

from __future__ import annotations

import dataclasses
import math
import warnings

import jax
import jax.numpy as jnp
from jax import lax

from rocm_mpi_tpu.parallel.mesh import GlobalGrid, init_global_grid
from rocm_mpi_tpu.utils import compat
from rocm_mpi_tpu.utils import metrics


def effective_block_steps(
    nt: int, warmup: int, k: int, *, label: str = "block_steps",
    warn: bool = True, stacklevel: int = 3,
) -> int:
    """The sweep/chunk depth actually usable for the given step counts:
    gcd(warmup, nt-warmup, k) — both windows must be multiples of the
    depth so one compiled program serves both. The single source of truth
    for every runner (and for callers labeling artifacts by depth), so the
    warned, reported, and executed k can never diverge.
    """
    if k < 1:
        raise ValueError(f"{label} must be >= 1, got {k}")
    eff = math.gcd(math.gcd(warmup, nt - warmup), k) or 1
    if warn and eff != k:
        warnings.warn(
            f"{label} degraded: {k} requested but warmup={warmup} / "
            f"timed={nt - warmup} force k={eff}; pick step counts "
            f"divisible by {k} to keep the full k-steps-per-sweep saving.",
            stacklevel=stacklevel,
        )
    return eff


def auto_scan_chunk(op: str, grid, dtype, config: str | None) -> int | None:
    """The scan driver's `config="auto"` seam: the tuning cache's
    preferred chunk for `op` at this shard/topology, or None (= the
    default whole-window policy) on a miss or a non-auto config. The
    caller still gcd's the preference against its windows
    (effective_block_steps) — auto never breaks the divisibility
    contract, it only prefers a different quantum."""
    if config in (None, "default"):
        return None
    if config != "auto":
        raise ValueError(
            f"config must be None, 'default' or 'auto', got {config!r}"
        )
    if jax.process_count() > 1:
        # Multi-controller: every process resolves from ITS OWN cache
        # file, and a divergent chunk means divergently traced programs
        # across ranks. The defaults are deterministic everywhere; auto
        # stays hands-off until a broadcast-consistent resolve exists.
        return None
    from rocm_mpi_tpu.tuning import resolve as tuning_resolve

    tuned = tuning_resolve.resolve(
        op, grid.local_shape, dtype, topology=grid.dims
    )
    if tuned and tuned.get("chunk"):
        return int(tuned["chunk"])
    return None


def _jit(n_state: int, loop):
    """`loop(state, *operands, n) -> state` as the drivers' calling
    convention `advance(*state, *operands, n)`: jitted, every state leaf
    donated (the caller rebinds from the result — GL01), traced under
    layer="driver" so the once-per-call prepare, the loop's own ops and
    every op of the step that no inner layer (exchange, splice) names
    carry it."""

    def advance(*args):
        with compat.set_xla_metadata(layer="driver"):
            out = loop(tuple(args[:n_state]), *args[n_state:])
        return out[0] if n_state == 1 else out

    return jax.jit(advance, donate_argnums=tuple(range(n_state)))


def _scan_loop(step, prepare, q: int):
    """The one loop: a dynamic-trip fori_loop over n // q of a STATIC
    q-step `lax.scan` taking two steps an iteration (`unroll=2`). A step
    reads the field it replaces, so with one step an iteration XLA
    writes it to a temporary and copies that back into the carry, every
    step. With two, the first step writes the temporary and the second
    the carry's own buffer — XLA's double buffer, the functional analog
    of the reference's `T, T2 = T2, T` swap (perf.jl:50) — and no copy
    is left. An odd q ends each chunk with one single step, which pays
    the copy once a chunk; q = 1 is the per-step form. `n` must be a
    multiple of q (the trip count floors)."""

    def loop(state, aux, n):
        # The loop-invariant operand (e.g. the perf rung's Cm), traced
        # once OUTSIDE the loop.
        P = aux if prepare is None else prepare(aux)

        def q_steps(carry, _):
            return step(carry, P), None

        def body(_, carry):
            return lax.scan(q_steps, carry, xs=None, length=q, unroll=2)[0]

        return lax.fori_loop(0, n // q, body, state)

    return loop


def _lane_loop(step, prepare, space_ndim: int):
    """The lane-freeze loop of the batched advances: `n` steps of the
    batched step, and a lane freezes bitwise once its own
    `lane_steps` count is reached — the pass-through select is exact,
    so every lane equals a standalone run of its own length. Every state
    leaf freezes together. The operands between the state and
    `lane_steps` are the aux (a tuple of them when there are several,
    as on the ladder)."""
    shape1 = (-1,) + (1,) * space_ndim

    def loop(state, *args):
        *ops, lane_steps, n = args
        aux = ops[0] if len(ops) == 1 else tuple(ops)
        P = aux if prepare is None else prepare(aux)

        def body(i, s):
            new = step(s, P)
            # The per-lane freeze belongs to the lane step: XLA fuses
            # the stencil into this select, and a fusion carries its
            # root's tag.
            with compat.set_xla_metadata(layer="lane_step"):
                active = (i < lane_steps).reshape(shape1)
                return jax.tree.map(
                    lambda a, b: jnp.where(active, a, b), new, s
                )

        return lax.fori_loop(0, n, body, state)

    return loop


def _sweep_loop(sched, operand):
    """The deep sweep loop over n // k sweeps of `sched`
    (parallel.deep_halo.DeepSchedule): the loop-invariant operands are
    exchanged and masked ONCE, by `sched.prepare(operand(state, aux))`
    outside the loop, which carries only the state — plus, for the
    stateful wire modes, the exchange state, zero at every call (the
    first-sweep contract) and dropped from the result."""

    def loop(state, aux, n):
        P = sched.prepare(operand(state, aux))
        carry = state
        if sched.init_wire is not None:
            carry += (sched.init_wire(state[0].dtype),)

        def body(_, c):
            out = sched.sweep(*c[:len(state)], P, *c[len(state):])
            return out if len(c) > 1 else (out,)

        return lax.fori_loop(0, n // sched.k, body, carry)[:len(state)]

    return loop


@dataclasses.dataclass
class Result:
    """A timed run: `wtime` seconds over the `nt - warmup` timed steps.
    A model's subclass adds its state fields and names, as `field`, the
    field whose shape and dtype the rates read; `n_passes` is the
    whole-array memory passes a step (T_eff's A_eff, perf.jl:55)."""

    wtime: float
    nt: int
    warmup: int
    config: object
    n_passes = 3

    @property
    def wtime_it(self) -> float:
        return metrics.wtime_per_it(self.wtime, self.nt, self.warmup)

    @property
    def t_eff(self) -> float:
        return metrics.t_eff_gbs(
            self.field.shape, self.field.dtype.itemsize, self.wtime_it,
            n_passes=self.n_passes,
        )

    @property
    def gpts(self) -> float:
        return metrics.gpts_per_s(self.field.shape, self.wtime_it)


class Driver:
    """The base of the workload models (module docstring): the grid, the
    batched grid, and every schedule built from the model's hooks.

    `workload` names the model's scan tuning op (`"<workload>.scan"`)
    and stamps its timed windows; `n_state` is the number of state
    arguments every advance takes first and donates."""

    workload: str
    n_state: int = 1

    def __init__(self, config, grid: GlobalGrid | None = None,
                 devices=None):
        self.config = config
        if grid is None:
            grid = init_global_grid(
                *config.global_shape,
                lengths=config.lengths,
                dims=config.dims,
                devices=devices,
            )
        self.grid = grid

    def _windows(self, nt, warmup):
        cfg = self.config
        nt = cfg.nt if nt is None else nt
        warmup = cfg.warmup if warmup is None else warmup
        if not 0 <= warmup < nt:
            raise ValueError(f"need 0 <= warmup < nt, got {warmup}, {nt}")
        return nt, warmup

    # ---- the per-step loop ----------------------------------------------

    def advance_fn(self, variant: str = "perf"):
        """jitted `advance(*state, aux, n) -> state` after `n` steps, any
        `n`: the scan driver at q = 1 — one step a loop trip, the
        per-step reference and what checkpoint segments run. `n` is
        traced, so every call shares one compiled program. Donates the
        state — the functional analog of the reference's `T, T2 = T2, T`
        swap (perf.jl:50); the caller must not reuse what it passed."""
        return _jit(self.n_state, _scan_loop(*self._step(variant), 1))

    def scan_advance_fn(
        self,
        variant: str = "perf",
        nt: int | None = None,
        warmup: int | None = None,
        chunk: int | None = None,
        config: str | None = None,
    ):
        """(jitted `advance(*state, aux, n) -> state`, chunk q) — the scan
        driver (`_scan_loop`): steady-state stepping allocates nothing,
        the donated state and one temporary are the only field storage
        the program touches.

        `q` defaults to the largest chunk serving both timing windows with
        one compiled program (gcd of warmup and the timed window —
        effective_block_steps); `n` must be a multiple of q.
        `config="auto"` treats a tuning-cache chunk (op
        "<workload>.scan", keyed per shard/topology) as the preference an
        unset `chunk` gcd's from — bitwise-identical at any q (scan ==
        per-step is pinned), so auto only moves window quantization."""
        cfg = self.config
        nt_v = cfg.nt if nt is None else nt
        wu_v = cfg.warmup if warmup is None else warmup
        explicit = chunk is not None
        if not explicit:
            chunk = auto_scan_chunk(f"{self.workload}.scan", self.grid,
                                    cfg.jax_dtype, config)
        q = effective_block_steps(
            nt_v, wu_v, (nt_v - wu_v) if chunk is None else chunk,
            label=f"{self.workload} scan driver chunk", warn=explicit,
        )
        return _jit(self.n_state, _scan_loop(*self._step(variant), q)), q

    def run(self, variant: str = "perf", nt: int | None = None,
            warmup: int | None = None, config: str | None = None):
        """Run `nt` steps on the scan driver; time all but the first
        `warmup` (perf.jl:47-53). `config="auto"` lets the scan chunk
        consult the tuning cache (scan_advance_fn)."""
        nt, warmup = self._windows(nt, warmup)
        advance, _ = self.scan_advance_fn(variant, nt=nt, warmup=warmup,
                                          config=config)
        return self._timed(advance, nt, warmup, variant=variant,
                           driver="scan")

    def _timed(self, advance, nt, warmup, **stamp):
        """The timed window: from the initial state, warm up (`n` =
        warmup; `n` = 0 still compiles the program), sync, then time the
        `nt - warmup` steps to the last one's completion.
        `advance(*state, aux, n)` must serve both windows with one
        compiled program."""
        nt, warmup = self._windows(nt, warmup)
        state, aux = self.state_and_aux()
        timer = metrics.Timer(label="step_window", phase="step",
                              steps=nt - warmup, workload=self.workload,
                              **stamp)
        state = self._as_state(advance(*state, aux, warmup))
        metrics.force(state)
        with timer:
            state = self._as_state(advance(*state, aux, nt - warmup))
            wtime = timer.toc(state)
        return self._result(state, wtime=wtime, nt=nt, warmup=warmup,
                            config=self.config)

    def _as_state(self, out) -> tuple:
        return (out,) if self.n_state == 1 else tuple(out)

    # ---- multi-tenant batching (docs/SERVING.md) ------------------------

    def make_batched_grid(self, batch: int, batch_dims: int = 1,
                          devices=None):
        """The space×batch mesh for `batch` lanes of THIS model's space
        problem (mesh.init_batched_grid), space decomposition pinned to
        the model's own grid dims so a lane's spatial shards match its
        standalone twin's."""
        from rocm_mpi_tpu.parallel.mesh import init_batched_grid

        cfg = self.config
        return init_batched_grid(
            batch,
            *cfg.global_shape,
            lengths=cfg.lengths,
            space_dims=self.grid.dims,
            batch_dims=batch_dims,
            devices=devices,
        )

    def _bgrid(self, batch, bgrid, batch_dims, devices):
        if bgrid is not None:
            return bgrid
        if batch is None:
            raise ValueError("pass batch= or a prebuilt bgrid=")
        return self.make_batched_grid(batch, batch_dims, devices)

    def batched_advance_fn(
        self,
        batch: int | None = None,
        variant: str = "shard",
        bgrid=None,
        batch_dims: int = 1,
        devices=None,
    ):
        """(jitted `advance(*state, aux, lane_steps, n) -> state`, bgrid)
        — the multi-tenant batched advance (docs/SERVING.md): the state
        is `(batch, *space)` lane-batched, sharded `bgrid.spec`; `aux`
        the single space-shaped operand all lanes share; `lane_steps` a
        `(batch,)` int32 of per-lane step counts (the bin scheduler's
        steps padding: the batch runs `n` = max steps, and each lane
        freezes at its own count — `_lane_loop`); `n` the dynamic trip
        count. One compiled program serves any lane_steps/n mix — the
        bin scheduler's compile-amortization contract
        (`compiles.steady_state == 0`). Donates the state: the lowered
        audit proves the aliasing from the compiled program
        (analysis/lowered.audit_batched_drivers)."""
        bgrid = self._bgrid(batch, bgrid, batch_dims, devices)
        step, prepare = self._make_batched_step(bgrid, variant)
        loop = _lane_loop(step, prepare, bgrid.space.ndim)
        return _jit(self.n_state, loop), bgrid

    def batched_ladder_advance_fn(
        self,
        batch: int | None = None,
        bgrid=None,
        batch_dims: int = 1,
        devices=None,
    ):
        """(jitted `advance(*state, aux, hold, dt_term, inv_d2,
        lane_steps, n) -> state`, bgrid) — the LADDER edition of the
        batched advance (docs/SERVING.md "Continuous batching"): this
        model's shape is the ladder RUNG, and each lane may embed a
        smaller original domain at the origin corner. Geometry rides
        traced per-lane operands instead of trace constants, so ONE
        compiled program serves every original shape on the rung:

          * `hold` — (batch, *space) bool, True on a lane's held cells:
            its original domain's global Dirichlet ring AND every cell
            outside the embedded domain (pad cells freeze bitwise at
            their initial value, exactly like a finished lane's steps);
          * `dt_term` — (batch,) per-lane dt term (the model's
            `_ladder_step` says which); `inv_d2` — a TUPLE of ndim
            (batch,) per-axis reciprocal spacing² operands, each rounded
            exactly as XLA folds the standalone divide-by-constant
            (ops.diffusion.step_fused_padded_geom has the ulp
            rationale).

        Because the held ring separates each embedded interior from the
        padding, interior cells read only original-domain values — every
        lane is bitwise-equal to its standalone run ('shard' variant,
        lossless 'f32' wire; the service gates eligibility). Every state
        leaf freezes under `hold` exactly as under `lane_steps`. Donates
        the state."""
        bgrid = self._bgrid(batch, bgrid, batch_dims, devices)
        loop = _lane_loop(self._ladder_step(bgrid), None, bgrid.space.ndim)
        return _jit(self.n_state, loop), bgrid

    # ---- the deep-halo schedule -----------------------------------------

    def effective_wire_mode(self, wire_mode: str | None = None) -> str:
        """The state exchange's on-wire precision a deep run will use:
        an explicit `wire_mode`, else the config's."""
        return self.config.wire_mode if wire_mode is None else wire_mode

    def _deep_operand(self, state, aux):
        """What the deep schedule's `prepare` reads: the loop-invariant
        operand."""
        return aux

    def deep_advance_fn(
        self,
        block_steps: int | None = None,
        nt: int | None = None,
        warmup: int | None = None,
        wire_mode: str | None = None,
        **policy,
    ):
        """(jitted `advance(*state, aux, n) -> state`, executed depth k) —
        the deep-halo schedule (parallel.deep_halo): one width-k exchange
        per k-step sweep (`_sweep_loop`). `n` must be a multiple of k
        (the trip count floors) — the step-count convention every
        model's deep advance shares. `block_steps` and `policy` (the
        diffusion model's `config="auto"`) go to the model's depth
        policy, `effective_deep_depth`; `wire_mode` and `policy` to
        `effective_wire_mode`."""
        k = self.effective_deep_depth(nt, warmup, block_steps, **policy)
        sched = self._deep_schedule(
            k, self.effective_wire_mode(wire_mode, **policy)
        )
        return self._sweep_advance(sched), k

    def _sweep_advance(self, sched):
        return _jit(self.n_state, _sweep_loop(sched, self._deep_operand))

    def run_deep(
        self,
        nt: int | None = None,
        warmup: int | None = None,
        block_steps: int | None = None,
        wire_mode: str | None = None,
        **policy,
    ):
        """Sharded fast path: deep-halo sweeps — one width-k ghost
        exchange of the whole state per k steps, the multi-chip form of
        temporal blocking (deep_advance_fn), in the timed window."""
        nt, warmup = self._windows(nt, warmup)
        advance, _ = self.deep_advance_fn(block_steps, nt, warmup,
                                          wire_mode=wire_mode, **policy)
        return self._timed(advance, nt, warmup, variant="deep")
