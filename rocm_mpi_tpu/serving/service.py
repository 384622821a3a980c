"""The service driver: batches queued requests onto the space×batch
mesh (docs/SERVING.md).

One `SimulationService` owns a `RequestQueue`, a per-bin model/program
cache, and the serving accounting. The drain loop pops pending tickets,
groups them by `bins.bin_key`, packs each group into power-of-two lane
widths (`bins.plan_batches` — the occupancy floor splits an
under-occupied wide batch into a narrower program class instead of
shipping padding), and executes every batch through the workload's
`batched_advance_fn`. Compiled programs are cached by
(bin key | width | batch rows): since the persistent compile cache is
unsound on this stack, this cache IS the compile amortizer, and the
PR-5 `compiles.steady_state == 0` gate is the steady-state contract —
once a drain pass needs no new program, the service marks steady and
any further recompile is a gated regression.

Resilience integration: requests with a `session` id get their final
state saved through the PR-6 manifest machinery
(``sessions/<id>/`` — `resume=True` continues from the latest valid
step, restored template-less across whatever mesh the service now
runs); a SIGTERM preemption notice (resilience.preempt, rc 75) stops
dispatch at the next batch boundary and requeues every unserved ticket;
and the service is the first real `ElasticPolicy` consumer — the queue
depth drives batch-row growth within the device budget (policy
hysteresis included), idle drains shrink back.

Determinism: every scheduling decision (grouping, widths, lane order)
is a pure function of the submitted trace — in a multi-controller
service every rank plans identical batches, so the batched collectives
can never diverge (the GL08 hazard class). Sessions and result
fetching are single-controller (the drill pins program counts).

The drain hot path is PIPELINED (docs/SERVING.md "The pipeline"):
each batch runs four explicit stages — assemble (host lane state) →
dispatch (device transfer + the batched advance + a non-blocking host
copy, all JAX async) → fetch (ONE blocking wait on the whole batch,
then the finiteness verdicts) → resolve (session saves, ticket
resolution, accounting). At `ServeConfig.pipeline_depth >= 2`
(default 2, double-buffered) batch N+1's assemble/dispatch overlaps
batch N's device compute and batch N's fetch/resolve runs while N+1
computes; depth 1 is the serial drain. Results are bitwise-equal at
any depth, every batch resolves inside its own drain pass, and the
`serve.device_bubble` gauge reports the fraction of drain wall the
device sat idle.
"""

from __future__ import annotations

import dataclasses
import os
import pathlib
import time
from typing import Callable

from rocm_mpi_tpu.resilience.policy import CircuitPolicy, RequestRetryPolicy
from rocm_mpi_tpu.serving import bins as _bins
from rocm_mpi_tpu.serving.bins import BinKey, BinStats
from rocm_mpi_tpu.serving.queue import (
    Request,
    RequestQueue,
    Ticket,
    append_quarantine,
    quarantine_record,
)

# Physics fields each workload's config accepts from a request (anything
# else fails the request loudly — a typo'd constant must not silently
# serve default physics).
PHYSICS_FIELDS = {
    "diffusion": ("lam", "cp0"),
    "wave": ("c0", "cfl"),
    "swe": ("H0", "g", "cfl"),
}


def load_serving_budgets(path=None) -> dict:
    """The committed serving row (perf/budgets.json "serving"):
    occupancy floor + batch tolerance the scheduler and the traffic
    audit share. Absent block falls back to the bins defaults."""
    from rocm_mpi_tpu.perf.traffic import load_budgets

    try:
        doc = load_budgets(path)
    except (OSError, ValueError):
        return {}
    serving = doc.get("serving")
    return serving if isinstance(serving, dict) else {}


@dataclasses.dataclass
class ServeConfig:
    """Service knobs (docs/SERVING.md "Service driver")."""

    max_width: int = _bins.DEFAULT_MAX_WIDTH
    occupancy_floor: float | None = None  # None -> budgets "serving" row
    batch_dims: int = 1  # device rows along the lane axis
    sessions_dir: str | None = None  # checkpoint multiplex root
    fetch_results: bool | None = None  # None: auto (off multi-controller)
    # Elasticity (the ElasticPolicy consumer): policy=None disables.
    policy: object | None = None  # resilience.policy.ElasticPolicy
    # Lane-ROW budget: how many device rows the batch axis may spread
    # over (each row carries one space mesh). Default: all devices.
    device_budget: Callable[[], int] | None = None
    grow_queue_depth: int = 8  # depth that makes the policy consider a grow
    idle_shrink_drains: int = 3  # empty drains before shrinking back
    # The request-plane SLO knobs (docs/SERVING.md "SLOs and
    # admission"): admission bound (None = unbounded, the PR-13
    # behavior), the retry budget/backoff for transient batch-level and
    # numerical failures, the per-BinKey circuit breaker, and the
    # append-only poison ledger (None = records kept in-process only).
    max_depth: int | None = None
    retry: RequestRetryPolicy | None = None  # None -> defaults
    circuit: CircuitPolicy | None = None  # None -> defaults
    quarantine_path: str | None = None
    # The serving pipeline (docs/SERVING.md "The pipeline"): how many
    # batches may be in flight at once inside one drain pass. Depth 1
    # is the serial drain (assemble → dispatch → block → resolve, one
    # batch at a time); depth 2 (the default) double-buffers — batch
    # N+1's host assembly/dispatch overlaps batch N's device compute,
    # and batch N's fetch/resolve runs while N+1 computes. Results are
    # bitwise-equal at any depth (the stages reorder WAITING, never
    # work); every batch still resolves inside its own drain pass, so
    # the drain-boundary accounting invariant is depth-independent.
    pipeline_depth: int = 2
    # Host-side stage callbacks {stage: fn(stage, info)} for
    # {"assemble","dispatch","fetch","resolve"} — called AFTER the
    # stage, on the host, outside any traced region (a hook that
    # mutates service/module state inside a traced body is the GL02
    # hazard class; tests/analysis_fixtures/gl02_serving_pos.py). Used
    # by drills to inject deterministic host-stage latency.
    stage_hooks: dict | None = None
    # Continuous batching (docs/SERVING.md "Continuous batching"):
    # segments > 1 executes each batch as K fixed-size step segments of
    # ONE compiled program (segment length = steps_bucket // segments),
    # swapping resolved lanes out and queued same-class requests in at
    # segment boundaries — no recompile, every lane bitwise-equal to
    # its standalone run. 1 (the default) is the legacy
    # batch-synchronous drain. Single-controller only (swap-in decisions
    # read the local queue mid-drain); multi-controller services fall
    # back to batch-synchronous.
    segments: int = 1
    # The shape-padding ladder: pad eligible requests' space dims up a
    # rung (bins.ladder_shape) so near-rung shape classes share ONE
    # compiled program, within the committed padded-FLOPs tolerance
    # (None -> budgets "serving"/"padded_flops_tolerance" row).
    ladder: bool = False
    ladder_tolerance: float | None = None
    # Request-scoped tracing (telemetry/tracing.py): per-ticket latency-
    # decomposition marks plus tspan records on the rank stream. Off
    # means zero marks and zero tspans on the serving hot path — the
    # bench overhead rung's tracing-off arm.
    trace_requests: bool = True

    def resolved_floor(self) -> float:
        if self.occupancy_floor is not None:
            return float(self.occupancy_floor)
        row = load_serving_budgets().get("occupancy_floor")
        return float(row) if row else _bins.DEFAULT_OCCUPANCY_FLOOR

    def resolved_ladder_tolerance(self) -> float:
        if self.ladder_tolerance is not None:
            return float(self.ladder_tolerance)
        row = load_serving_budgets().get("padded_flops_tolerance")
        return float(row) if row else _bins.DEFAULT_LADDER_TOLERANCE


@dataclasses.dataclass
class ServeReport:
    """One trace/drain session's outcome."""

    served: int = 0
    failed: int = 0
    requeued: int = 0
    rejected: int = 0
    expired: int = 0
    quarantined: int = 0
    preempted: bool = False
    bins: dict = dataclasses.field(default_factory=dict)
    programs: list = dataclasses.field(default_factory=list)
    compiles: dict = dataclasses.field(default_factory=dict)
    elastic: list = dataclasses.field(default_factory=list)
    pipeline: dict = dataclasses.field(default_factory=dict)
    continuous: dict = dataclasses.field(default_factory=dict)

    @property
    def n_bins(self) -> int:
        return len(self.bins)

    @property
    def n_programs(self) -> int:
        return len(self.programs)

    def manifest_doc(self, queue_counters=None) -> dict:
        extra = {
            "served": self.served,
            "failed": self.failed,
            "requeued": self.requeued,
            "rejected": self.rejected,
            "expired": self.expired,
            "quarantined": self.quarantined,
            "preempted": self.preempted,
            "elastic": list(self.elastic),
            "compiles": dict(self.compiles),
            "pipeline": dict(self.pipeline),
        }
        if self.continuous:
            extra["continuous"] = dict(self.continuous)
        return _bins.manifest_doc(
            self.bins, list(self.programs),
            queue_counters=queue_counters,
            extra=extra,
        )


def _reshard(x, sharding):
    """Device array -> the batched mesh's aux sharding (a tiny jitted
    transfer, one per program class — compiled inside the class's own
    compile window, reused every batch). When the batched mesh spans
    MORE devices than the source's space mesh (an elastic grow added
    batch rows), XLA cannot jit across the device sets — stage through
    the host instead (single-controller by construction: multi-
    controller services never resize)."""
    import jax
    import numpy as np

    if set(sharding.device_set) == set(x.sharding.device_set):
        return jax.jit(lambda v: v, out_shardings=sharding)(x)
    if x.is_fully_addressable:
        return _to_global(np.asarray(x), sharding)
    raise ValueError(
        "cannot reshard a non-addressable array onto a different "
        "device set (multi-controller services must keep batch_dims × "
        "space within the space mesh's device set)"
    )


def _to_global(np_arr, sharding):
    """Host array -> global device array under `sharding` — works in
    multi-controller processes too (every rank holds the SAME full host
    array by the determinism contract; each contributes its addressable
    shards)."""
    import jax

    return jax.make_array_from_callback(
        np_arr.shape, sharding, lambda idx: np_arr[idx]
    )


class _Program:
    """One compiled program class: the batched advance bound to its
    space×batch grid, plus the cached base state the lanes scale.
    `base_dev` are the leaves of the workload's standard-IC state ON
    DEVICE (space-sharded) and `state_def` the state's structure, which
    the adapter rebuilds the advance's state arguments from; `base_np`
    their host copies (single-controller only — the lane-assembly fast
    path); `init` the lazily-jitted device-side lane initializer
    (scales → batched leaves) the multi-controller path uses instead."""

    def __init__(self, advance, bgrid, aux, base_state, adapter,
                 ladder: bool = False):
        import jax

        self.advance = advance
        self.bgrid = bgrid
        self.aux = aux  # device aux operand, lane-shared
        leaves, self.state_def = jax.tree.flatten(tuple(base_state))
        self.base_dev = tuple(leaves)
        self.adapter = adapter
        # Ladder program: the advance takes per-lane geometry operands
        # (hold mask, dt terms, spacing terms) so lanes of different
        # ORIGINAL shapes share this one compiled class.
        self.ladder = bool(ladder)
        self._base_np = None
        self._init = None
        self._finite = None

    @property
    def base_np(self):
        import numpy as np

        if self._base_np is None:
            self._base_np = tuple(np.asarray(l) for l in self.base_dev)
        return self._base_np

    @property
    def n_leaves(self) -> int:
        return len(self.base_dev)

    @property
    def base_np_dtype(self):
        import numpy as np

        return np.dtype(self.base_dev[0].dtype)

    def init_batched(self, scales_dev):
        """Batched state from per-lane scales, entirely on device (the
        multi-controller lane assembly; one tiny program per class,
        compiled inside the class's own compile window)."""
        import functools

        import jax

        if self._init is None:
            shardings = (self.bgrid.sharding,) * self.n_leaves

            @functools.partial(jax.jit, out_shardings=shardings)
            def init(scales, *base):
                return tuple(
                    jax.vmap(lambda s, l=leaf: s * l)(scales)
                    for leaf in base
                )

            self._init = init
        return self._init(scales_dev, *self.base_dev)

    def lane_finite(self, leaves):
        """(width,) bool, lane j True iff every element of every state
        leaf in lane j is finite — the cheap compiled per-lane
        finiteness reduction that extends tenant isolation to
        NUMERICAL failure (docs/SERVING.md "SLOs and admission"). The
        result is REPLICATED so every controller reads the identical
        verdict from its addressable shards (an all-reduce, never a
        divergence hazard); compiled once per program class, inside the
        class's own compile window."""
        import functools

        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        if self._finite is None:
            rep = NamedSharding(self.bgrid.mesh, PartitionSpec())

            @functools.partial(jax.jit, out_shardings=rep)
            def finite(*ls):
                ok = None
                for leaf in ls:
                    f = jnp.all(
                        jnp.isfinite(leaf),
                        axis=tuple(range(1, leaf.ndim)),
                    )
                    ok = f if ok is None else ok & f
                return ok

            self._finite = finite
        return self._finite(*leaves)


class _Adapter:
    """Per-workload glue: the config a BinKey names, the model, and —
    for the ladder — the per-lane geometry. Everything else is written
    once here over the model's state/aux split
    (`Driver.state_and_aux`, models/driver.py): the session-checkpoint
    pytree is exactly the state's leaves."""

    name: str = ""
    # Ladder support (docs/SERVING.md "Continuous batching"): a
    # workload opts in by providing ladder_geom. SWE stays out — its
    # face-mask aux is geometry-DEPENDENT (per-axis staggered masks
    # derived from the exact domain), so embedded lanes cannot share
    # one aux.
    supports_ladder: bool = False

    def make_config(self, key: BinKey, space_dims):
        raise NotImplementedError

    def make_model(self, cfg):
        raise NotImplementedError

    def ladder_geom(self, cfg):
        """(dt_term, per-axis spacing terms) for one lane, computed
        host-side with exactly the roundings the standalone python-float
        path produces (ops.diffusion.step_fused_padded_geom)."""
        raise NotImplementedError(f"{self.name} has no ladder support")

    def build(self, model, width, batch_dims, variant="shard",
              ladder=False):
        """-> (advance, bgrid, aux_device, base state): the batched
        advance (its ladder edition when `ladder`), the aux resharded
        onto the batched mesh, and the standard-IC state."""
        import jax

        bgrid = model.make_batched_grid(width, batch_dims)
        if ladder:
            advance, _ = model.batched_ladder_advance_fn(bgrid=bgrid)
        else:
            advance, _ = model.batched_advance_fn(bgrid=bgrid,
                                                  variant=variant)
        state, aux = model.state_and_aux()
        aux = jax.tree.map(lambda a: _reshard(a, bgrid.aux_sharding), aux)
        return advance, bgrid, aux, state

    def run(self, prog: _Program, leaves_dev, lane_steps_dev, n):
        """-> tuple of advanced state leaves (device)."""
        return self._advance(prog, leaves_dev, (prog.aux,), lane_steps_dev, n)

    def run_ladder(self, prog: _Program, leaves_dev, hold_dev, a_dev,
                   g_dev, lane_steps_dev, n):
        """-> advanced state leaves; `hold_dev` the per-lane hold mask,
        `a_dev` the per-lane dt term, `g_dev` the per-lane per-axis
        spacing term (`ladder_geom`)."""
        return self._advance(prog, leaves_dev,
                             (prog.aux, hold_dev, a_dev, g_dev),
                             lane_steps_dev, n)

    @staticmethod
    def _advance(prog, leaves_dev, operands, lane_steps_dev, n):
        import jax

        state = jax.tree.unflatten(prog.state_def, leaves_dev)
        out = prog.advance(*state, *operands, lane_steps_dev, n)
        return tuple(jax.tree.leaves(out))

    def ladder_state_leaves(self, model):
        """Unscaled standard-IC STATE leaves (numpy) of the
        original-shape model — the per-original-shape IC the service
        embeds into rung-shaped lanes."""
        import jax
        import numpy as np

        state, _ = model.state_and_aux()
        return tuple(np.asarray(l) for l in jax.tree.leaves(state))


class _DiffusionAdapter(_Adapter):
    name = "diffusion"
    supports_ladder = True

    def make_config(self, key, space_dims):
        from rocm_mpi_tpu.config import DiffusionConfig

        phys = dict(key.physics)
        return DiffusionConfig(
            global_shape=key.shape,
            lengths=(10.0,) * len(key.shape),
            dtype=key.dtype,
            dims=space_dims,
            wire_mode=key.wire_mode,
            lam=phys.get("lam", 1.0),
            cp0=phys.get("cp0", 1.0),
        )

    def make_model(self, cfg):
        from rocm_mpi_tpu.models import HeatDiffusion

        return HeatDiffusion(cfg)

    def ladder_geom(self, cfg):
        # The exact roundings of the python-float standalone path: dt
        # is cast to the compute dtype BEFORE the λ multiply
        # (models.diffusion._make_batched_step does jax_dtype(dt), and
        # dt·λ is then an in-dtype multiply); spacing² is squared in
        # f64 and weak-cast at the divide
        # (ops.diffusion.step_fused_padded) — and XLA folds that
        # divide-by-constant into a multiply by the correctly-rounded
        # reciprocal, so the geom operand is EXACTLY that reciprocal
        # (step_fused_padded_geom's bitwise contract).
        import numpy as np

        ty = np.dtype(cfg.jax_dtype)
        dtv = ty.type(cfg.dt)
        a = ty.type(dtv * ty.type(cfg.lam))
        g = tuple(
            ty.type(1.0 / float(ty.type(float(s) * float(s))))
            for s in cfg.spacing
        )
        return a, g


class _WaveAdapter(_Adapter):
    name = "wave"
    supports_ladder = True

    def make_config(self, key, space_dims):
        from rocm_mpi_tpu.models.wave import WaveConfig

        phys = dict(key.physics)
        return WaveConfig(
            global_shape=key.shape,
            lengths=(10.0,) * len(key.shape),
            dtype=key.dtype,
            dims=space_dims,
            wire_mode=key.wire_mode,
            c0=phys.get("c0", 1.0),
            cfl=phys.get("cfl", 0.5),
        )

    def make_model(self, cfg):
        from rocm_mpi_tpu.models.wave import AcousticWave

        return AcousticWave(cfg)

    def ladder_geom(self, cfg):
        # dt² in the compute dtype (the standalone path casts dt first,
        # then squares in-trace); 1/spacing² in f64 then cast
        # (ops.wave_kernels.wave_step_padded).
        import numpy as np

        ty = np.dtype(cfg.jax_dtype)
        dtv = ty.type(cfg.dt)
        a = ty.type(dtv * dtv)
        g = tuple(
            ty.type(1.0 / (float(s) * float(s))) for s in cfg.spacing
        )
        return a, g


class _SWEAdapter(_Adapter):
    name = "swe"

    def make_config(self, key, space_dims):
        from rocm_mpi_tpu.models.swe import SWEConfig

        phys = dict(key.physics)
        return SWEConfig(
            global_shape=key.shape,
            lengths=(10.0,) * len(key.shape),
            dtype=key.dtype,
            dims=space_dims,
            wire_mode=key.wire_mode,
            H0=phys.get("H0", 1.0),
            g=phys.get("g", 1.0),
            cfl=phys.get("cfl", 0.5),
        )

    def make_model(self, cfg):
        from rocm_mpi_tpu.models.swe import ShallowWater

        return ShallowWater(cfg)


_ADAPTERS = {
    a.name: a for a in (_DiffusionAdapter(), _WaveAdapter(), _SWEAdapter())
}


class _Breaker:
    """One BinKey's circuit state (docs/SERVING.md "SLOs and
    admission"): closed → (K consecutive batch failures) → open →
    (cooldown drains) → half-open probe → closed on success, re-open on
    failure. Purely a function of batch outcomes and drain counts —
    deterministic across controllers by construction."""

    __slots__ = ("consecutive", "state", "opened_drain")

    def __init__(self):
        self.consecutive = 0
        self.state = "closed"
        self.opened_drain = 0

    def note_failure(self, policy: CircuitPolicy, drain: int) -> bool:
        """Record one batch failure; True when this one OPENED (or
        re-opened, from half-open) the breaker."""
        self.consecutive += 1
        tripped = (
            policy.enabled
            and self.state != "open"
            and (self.state == "half-open"
                 or self.consecutive >= policy.k)
        )
        if tripped:
            self.state = "open"
            self.opened_drain = drain
        return tripped

    def note_success(self) -> bool:
        """Record one served batch; True when it CLOSED a half-open
        breaker (the probe proved recovery)."""
        recovered = self.state == "half-open"
        self.consecutive = 0
        self.state = "closed"
        return recovered

    def admit(self, policy: CircuitPolicy, drain: int, n: int) -> int:
        """How many of `n` popped tickets this class admits THIS drain:
        all of them (closed), none (open, cooling down), or exactly one
        probe (half-open)."""
        if not policy.enabled or self.state == "closed":
            return n
        if self.state == "open" \
                and drain - self.opened_drain >= policy.cooldown_drains:
            self.state = "half-open"
        return min(n, 1) if self.state == "half-open" else 0


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-unresolved batch riding the drain pipeline
    (docs/SERVING.md "The pipeline"): everything the resolve stage
    needs. `anchors` holds the batch's DONATED input leaves purely as
    a deletion anchor: on this stack, dropping the last Python
    reference to a donated-and-still-computing array blocks the host
    until the consuming computation finishes (measured — the silent
    re-serialization that would undo the whole pipeline), so the refs
    ride here untouched and are released at fetch time, when deletion
    is free. They are NEVER read — a retry/requeue after a dispatched
    batch re-assembles from host state only (the donated-buffer hazard
    the drill pins; reading one would raise jax's deleted-array
    error)."""

    key: BinKey
    width: int
    split: bool
    seq: int  # the service's global batch ordinal (fault site)
    prog: _Program
    live: list  # tickets that actually hold a lane
    starts: list  # per-live-lane resume start steps
    lane_steps: object  # numpy (width,) int32 per-lane step counts
    out: tuple  # advanced state leaves (device, async)
    fetch: bool  # resolve tickets with host results?
    need_host: bool  # fetch or session saves need the host copy
    anchors: tuple = ()  # donated inputs: deletion anchor ONLY


class SimulationService:
    """Multi-tenant batched simulation service (module docstring; the
    CLI driver is apps/serve.py)."""

    def __init__(self, queue: RequestQueue | None = None,
                 config: ServeConfig | None = None):
        self.config = config if config is not None else ServeConfig()
        self.queue = queue if queue is not None else RequestQueue(
            max_depth=self.config.max_depth
        )
        self._retry = self.config.retry if self.config.retry is not None \
            else RequestRetryPolicy()
        self._circuit = self.config.circuit \
            if self.config.circuit is not None else CircuitPolicy()
        self._floor = self.config.resolved_floor()
        self._batch_dims = int(self.config.batch_dims)
        self._models: dict = {}
        self._programs: dict[str, _Program] = {}
        self._ladder_tol = self.config.resolved_ladder_tolerance()
        self._ladder_bases: dict = {}  # per-original-shape IC leaves
        # Continuous-drain lifetime accounting (the manifest
        # `continuous` block): executed segmented batches/segments, the
        # swap counters, and the step-weighted occupancy numerator/
        # denominator the per-drain serve.occupancy gauge is cut from.
        self._continuous = {
            "batches": 0, "segments_run": 0, "swaps_in": 0,
            "swaps_out": 0, "occ_num": 0, "occ_den": 0,
        }
        self._drain_swaps = 0        # per-drain swap-ins (gauge)
        self._drain_occ = [0, 0]     # per-drain occupancy num/den
        self._stats: dict[BinKey, BinStats] = {}
        self._breakers: dict[BinKey, _Breaker] = {}
        self._elastic: list[dict] = []
        self._quarantined: list[dict] = []
        self._drains = 0
        self._idle_drains = 0
        self._last_resize_drain: int | None = None
        self._compiled_this_drain = False
        self._batch_seq = 0  # global executed-batch ordinal (fault site)
        self.retries_total = 0  # lifetime retry-requeues (SLO block)
        self._admission_sync = {"rejected": 0, "expired": 0}
        self._multi: bool | None = None
        # Pipeline accounting (docs/SERVING.md "The pipeline"):
        # cumulative per-stage host walls, device-busy wall (≥1 batch
        # dispatched-but-unfetched), and the drain execute wall the
        # bubble gauge is measured against. time.monotonic by design —
        # interval arithmetic on the scheduler clock, not a telemetry
        # measurement (the spans carry those).
        # `fetch_wait_s` is the part of `fetch_s` spent blocked on the
        # device, so the fetch stage's own host work is the difference.
        self._pipe = {
            "batches": 0, "assemble_s": 0.0, "dispatch_s": 0.0,
            "fetch_s": 0.0, "fetch_wait_s": 0.0, "resolve_s": 0.0,
            "busy_s": 0.0, "wall_s": 0.0,
        }
        self._inflight_n = 0
        self._inflight_since: float | None = None
        self.last_bubble: float | None = None  # most recent drain's

    def _is_multi(self) -> bool:
        """Multi-controller? Resolved once; also flips the queue's
        wall-clock SLO decisions off (deadline expiry and retry backoff
        diverge with rank-local clocks — the GL08 class; depth-based
        admission stays on everywhere)."""
        if self._multi is None:
            import jax

            self._multi = jax.process_count() > 1
            if self._multi:
                self.queue.wall_slo = False
        return self._multi

    # ---- model / program caches ----------------------------------------

    def _space_dims(self, key: BinKey):
        import jax

        from rocm_mpi_tpu.parallel.mesh import plan_dims

        avail = max(len(jax.devices()) // self._batch_dims, 1)
        return plan_dims(key.shape, avail)

    def _model_for(self, key: BinKey):
        mkey = (key.workload, key.shape, key.dtype, key.physics,
                key.wire_mode, self._batch_dims)
        model = self._models.get(mkey)
        if model is None:
            adapter = _ADAPTERS[key.workload]
            unknown = [
                k for k, _ in key.physics
                if k not in PHYSICS_FIELDS[key.workload]
            ]
            if unknown:
                raise ValueError(
                    f"unknown physics field(s) {unknown} for workload "
                    f"{key.workload!r} (accepted: "
                    f"{PHYSICS_FIELDS[key.workload]})"
                )
            cfg = adapter.make_config(key, self._space_dims(key))
            model = adapter.make_model(cfg)
            self._models[mkey] = model
        return model

    def program_key(self, key: BinKey, width: int,
                    ladder: bool = False) -> str:
        base = f"{key.key_str()}|w{width}|bd{self._batch_dims}"
        return base + "|ladder" if ladder else base

    def _program_for(self, key: BinKey, width: int,
                     ladder: bool = False) -> _Program:
        pkey = self.program_key(key, width, ladder)
        prog = self._programs.get(pkey)
        if prog is None:
            from rocm_mpi_tpu import telemetry
            from rocm_mpi_tpu.telemetry import compiles

            # A NEW program class is a legitimate compile, not a
            # steady-state regression: open the window, compile, and let
            # the drain loop re-mark steady once every class it needs
            # exists.
            compiles.unmark_steady()
            self._compiled_this_drain = True
            adapter = _ADAPTERS[key.workload]
            model = self._model_for(key)
            # The batch rows must DIVIDE the (pow2) lane width — a
            # non-pow2 batch_dims rounds down, it never bricks a batch.
            bd = _bins.pow2_floor(min(width, self._batch_dims))
            with telemetry.span("serve.compile", phase="serve",
                                bin=key.key_str(), width=width):
                advance, bgrid, aux, base = adapter.build(
                    model, width, bd, variant=key.variant, ladder=ladder
                )
            prog = _Program(advance, bgrid, aux, base, adapter,
                            ladder=ladder)
            self._programs[pkey] = prog
        return prog

    # ---- the shape-padding ladder (docs/SERVING.md) ---------------------

    def _ladder_eligible(self, req: Request) -> bool:
        """May this request ride a ladder program? Workloads with
        geometry-independent aux ('diffusion', 'wave' — SWE's face
        masks are domain-derived), the 'shard' variant (the one whose
        batched advance has a ladder twin), the lossless 'f32' wire
        (lossy codecs quantize at shard boundaries, which MOVE under
        padding), no sessions (checkpoints are exact-shape), and
        single-controller (the per-lane host embedding path)."""
        return (
            bool(self.config.ladder)
            and _ADAPTERS[req.workload].supports_ladder
            and req.variant == "shard"
            and req.wire_mode == "f32"
            and not req.session
            and not req.resume
            and not self._is_multi()
        )

    def _group_key(self, req: Request) -> tuple[BinKey, bool]:
        """(bin key, rides-the-ladder) — the drain's grouping key. An
        eligible request's shape field is laddered up a rung, so
        near-rung shape classes MERGE; ladder and non-ladder traffic of
        the same BinKey stay separate groups (different compiled
        programs)."""
        if self._ladder_eligible(req):
            return (
                _bins.bin_key(req, ladder_tolerance=self._ladder_tol),
                True,
            )
        return _bins.bin_key(req), False

    def _ladder_base_np(self, key: BinKey, orig_shape: tuple):
        """Unscaled standard-IC state leaves (numpy) at `orig_shape` —
        built from the ORIGINAL-shape model, cached per shape class.
        The first request of a new original shape compiles that
        model's IC initializer: a legitimate NEW-class compile (the
        window opens exactly like _program_for's), documented under
        "what still recompiles"."""
        okey = dataclasses.replace(key, shape=tuple(orig_shape))
        ckey = (okey.workload, okey.shape, okey.dtype, okey.physics,
                okey.wire_mode)
        base = self._ladder_bases.get(ckey)
        if base is None:
            from rocm_mpi_tpu import telemetry
            from rocm_mpi_tpu.telemetry import compiles

            compiles.unmark_steady()
            self._compiled_this_drain = True
            adapter = _ADAPTERS[okey.workload]
            model = self._model_for(okey)
            with telemetry.span("serve.compile", phase="serve",
                                bin=okey.key_str(), width=0):
                base = adapter.ladder_state_leaves(model)
            self._ladder_bases[ckey] = base
        return base

    def _ladder_lane(self, req: Request, key: BinKey, prog: _Program):
        """(embedded leaves, hold mask, dt term, spacing terms) for one
        laddered lane: the original-shape IC (×ic_scale) embedded at
        the origin corner of a rung-shaped zero block, the hold mask
        True on the original domain's Dirichlet ring AND everywhere
        outside it, and the lane's host-precomputed geometry
        (adapter.ladder_geom). The held ring separates the embedded
        interior from the padding, so the lane is bitwise-equal to its
        standalone run."""
        import numpy as np

        orig = tuple(int(n) for n in req.global_shape)
        base = self._ladder_base_np(key, orig)
        okey = dataclasses.replace(key, shape=orig)
        ocfg = self._model_for(okey).config
        a, g = prog.adapter.ladder_geom(ocfg)
        region = tuple(slice(0, n) for n in orig)
        leaves = []
        for b, z in zip(base, prog.base_np):
            e = np.zeros_like(z)
            e[region] = b * req.ic_scale
            leaves.append(e)
        hold = np.ones(prog.base_np[0].shape, dtype=bool)
        hold[tuple(slice(1, n - 1) for n in orig)] = False
        return tuple(leaves), hold, a, g

    # ---- lane assembly --------------------------------------------------

    def _session_dir(self, session: str) -> pathlib.Path:
        root = self.config.sessions_dir
        if not root:
            raise ValueError(
                "request carries a session id but the service has no "
                "sessions_dir configured"
            )
        return pathlib.Path(root) / session

    def _resume_step(self, req: Request, prog: _Program) -> int:
        """The lane's resume point: the session's latest VALID saved
        step, 0 when nothing durable exists yet. A session already PAST
        the requested nt fails loudly — there is no checkpoint at nt to
        hand back, and restoring the later state would answer a
        different question than the request asked."""
        import jax

        if jax.process_count() > 1:
            raise ValueError("session resume is single-controller only")
        from rocm_mpi_tpu.utils import checkpoint as ckpt

        step = ckpt.latest_valid_step(self._session_dir(req.session))
        if step is None:
            return 0
        if int(step) > req.nt:
            raise ValueError(
                f"session {req.session!r} is already at step {step} > "
                f"requested nt {req.nt}; re-submit with nt >= {step}"
            )
        return int(step)

    def _lane_start_state(self, req: Request, prog: _Program,
                          start: int):
        """(leaves numpy tuple, start_step) for one lane: the session's
        checkpoint at `start` when resuming (template-less restore —
        the PR-6 cross-mesh path), else ic_scale × the workload's
        standard IC."""
        import numpy as np

        if req.resume and start > 0:
            from rocm_mpi_tpu.utils import checkpoint as ckpt

            sdir = self._session_dir(req.session)
            leaves = ckpt.restore_state(sdir, start, like=None)
            leaves = tuple(np.asarray(l) for l in leaves)
            if len(leaves) != prog.n_leaves:
                raise ValueError(
                    f"session {req.session}: checkpoint has "
                    f"{len(leaves)} leaves, workload {req.workload!r} "
                    f"carries {prog.n_leaves}"
                )
            return leaves, start
        return tuple(l * req.ic_scale for l in prog.base_np), 0

    def _save_session(self, ticket: Ticket, leaves,
                      prog: _Program) -> None:
        """Multiplex the lane's final state through the PR-6 manifest
        machinery: sessions/<id>/ gets a step-nt checkpoint whose
        manifest meta carries the request id."""
        import jax

        from rocm_mpi_tpu.utils import checkpoint as ckpt

        req = ticket.request
        sdir = self._session_dir(req.session)
        # Space-sharded leaves: the manifest's topology metadata (the
        # PR-6 cross-mesh restore contract) describes a mesh, so the
        # saved state must carry one — the bin's own space grid.
        space = prog.bgrid.space
        state = tuple(
            jax.device_put(l, space.sharding) for l in leaves
        )
        ckpt.save_state(sdir, req.nt, state)
        # Re-write the manifest with the serving meta riding along —
        # write_manifest recomputes the inventory, so this is the same
        # document plus the request attribution.
        ckpt.write_manifest(
            sdir, req.nt, state,
            extra_meta={"serving": {
                "request_id": req.request_id, "session": req.session,
            }},
        )

    # ---- execution (the drain pipeline, docs/SERVING.md) ----------------

    def _stage_hook(self, stage: str, **info) -> None:
        """Fire the host-side stage callback (ServeConfig.stage_hooks)
        AFTER `stage` — outside every traced region by construction."""
        hooks = self.config.stage_hooks
        if not hooks:
            return
        fn = hooks.get(stage)
        if fn is not None:
            fn(stage, info)

    def _now(self, now: float | None = None) -> float:
        """The service's single clock seam (graftlint GL10e): every
        monotonic read in the drain/pipeline path routes through here,
        so a fleet controller can inject its clock the same way the
        router's poll_health/expire_overdue(now) seams do and the
        serving plane keeps exactly one clock owner per process."""
        return time.monotonic() if now is None else now

    def _note_dispatched(self) -> None:
        """A batch entered flight (dispatched, unfetched): the device
        is busy while >= 1 batch is in flight — the complement is the
        bubble the serve.device_bubble gauge reports."""
        if self._inflight_n == 0:
            self._inflight_since = self._now()
        self._inflight_n += 1

    def _note_fetched(self) -> None:
        if self._inflight_n > 0:
            self._inflight_n -= 1
            if self._inflight_n == 0 and self._inflight_since is not None:
                self._pipe["busy_s"] += (
                    self._now() - self._inflight_since
                )
                self._inflight_since = None

    def _execute_batch(self, key: BinKey, tickets: list[Ticket],
                       width: int, split: bool) -> None:
        """The serial per-batch chokepoint (pipeline_depth == 1, and
        the override seam the failure drills monkeypatch): prepare,
        then resolve immediately — the staged pipeline with zero
        overlap. Bitwise-identical to the pipelined drain by
        construction: both run the same stages on the same batches in
        the same order; only the waiting is scheduled differently."""
        fl = self._prepare_batch(key, tickets, width, split)
        if fl is not None:
            self._resolve_batch(fl)

    def _prepare_batch(self, key: BinKey, tickets: list[Ticket],
                       width: int, split: bool) -> _InFlight | None:
        """Pipeline stages 1+2 — assemble (host) + dispatch (async).

        Assembles every lane's start state on the host, places the
        batch on device, and dispatches the batched advance plus a
        non-blocking device-to-host copy of the results (JAX async
        dispatch: both return immediately as futures; the per-lane
        finiteness verdict is deliberately NOT dispatched here — the
        fetch stage computes it, see _resolve_batch). Nothing here
        waits on the device, so batch N+1's prepare runs while batch N
        computes. Returns the in-flight record the resolve stage
        consumes, or None when no lane survived assembly. The input
        device leaves are donated to the advance and NOT carried on
        the record — a later retry can only re-assemble from host
        state, never read a donated buffer."""
        import numpy as np

        from rocm_mpi_tpu import telemetry
        from rocm_mpi_tpu.resilience import faults
        from rocm_mpi_tpu.telemetry import flight

        # The serve-batch fault site, BEFORE the flight step bump and
        # any collective: an infrastructure clause pinned here
        # (`kill@step=2,rank=1,at=serve-batch`) strikes a rank before
        # it bumps, so its peers advance past it and the health
        # watchdog names the victim BY PROGRESS — the same ordering
        # contract as the segment-pre site. The step bump itself feeds
        # the watchdog: one progress step per executed batch.
        self._batch_seq += 1
        seq = self._batch_seq
        faults.fault_point("serve-batch", step=seq)
        clause = faults.serving_fault("batch-error", step=seq)
        if clause is not None:
            raise RuntimeError(f"injected batch-error (batch {seq})")
        flight.progress(step_inc=1)
        slow = faults.serving_fault("slow-batch", step=seq)
        if slow is not None:
            time.sleep(slow.delay_s)

        tracing_on = bool(self.config.trace_requests)
        if tracing_on:
            from rocm_mpi_tpu.telemetry import tracing as _tracing

            tnow = self._now()
            for t in tickets:
                t.trace_mark("queue_wait", tnow)
        prog = self._program_for(key, width)
        if tracing_on:
            # Telescoping decomposition marks (tracing.DECOMP_STAGES):
            # each boundary charges the interval since the previous mark
            # to ONE stage, so the stages sum exactly to the terminal
            # latency. "compile" covers program-class acquisition (a hot
            # cache charges ~0 here); everything until the blocking
            # fetch lands in "device".
            tnow = self._now()
            for t in tickets:
                t.trace_mark("compile", tnow)
        bgrid = prog.bgrid
        multi = self._is_multi()

        # Per-lane assembly, per-lane failure isolation: one tenant's
        # bad session (corrupt checkpoint, wrong workload's leaves,
        # nt behind the saved step) fails ITS ticket only — the
        # co-batched neighbors keep their lanes; the failed lane stays
        # idle padding.
        t0 = self._now()
        live: list[Ticket] = []
        starts: list[int] = []
        with telemetry.span("serve.assemble", phase="serve",
                            bin=key.key_str(), width=width):
            lanes: list[tuple] = []
            scales = np.zeros(width, dtype=prog.base_np_dtype)
            lane_steps = np.zeros(width, dtype=np.int32)
            for t in tickets:
                try:
                    if multi and (t.request.resume or t.request.session):
                        raise ValueError(
                            "session checkpoints are single-controller "
                            "only"
                        )
                    start = (
                        self._resume_step(t.request, prog)
                        if t.request.resume else 0
                    )
                    if not multi:
                        leaves, _ = self._lane_start_state(
                            t.request, prog, start
                        )
                except ValueError as e:
                    # A per-request validation error (bad session,
                    # resume past nt): the request itself is wrong —
                    # terminal, never retried.
                    self._fail_ticket(t, str(e))
                    continue
                except Exception as e:  # noqa: BLE001 — tenant isolation
                    # Transient lane-assembly failure (corrupt
                    # checkpoint, storage flap on restore): retry
                    # within budget.
                    self._retry_or_quarantine(t, str(e))
                    continue
                j = len(live)
                live.append(t)
                starts.append(start)
                lane_steps[j] = t.request.nt - start
                scales[j] = t.request.ic_scale
                if not multi:
                    lanes.append(leaves)
                if faults.serving_fault("lane-nan", request=t.ordinal) \
                        is not None:
                    # Poison THIS lane's initial state (the numerical-
                    # failure drill): the finiteness reduction must
                    # fail only this ticket while its co-batched
                    # neighbors stay bitwise-equal to their standalone
                    # twins.
                    scales[j] = float("nan")
                    if not multi:
                        lanes[j] = tuple(
                            l * float("nan") for l in lanes[j]
                        )
                t.start_step = start
        self._pipe["assemble_s"] += self._now() - t0
        self._stage_hook("assemble", key=key.key_str(), width=width,
                         seq=seq, live=len(live))
        if not live:
            return None
        n = int(lane_steps.max())

        t0 = self._now()
        with telemetry.span(
            "serve.dispatch", phase="serve",
            bin=key.key_str(), width=width, live=len(live), steps=n,
        ):
            if multi:
                # Multi-controller lane assembly is entirely on device
                # (a host-assembled batch cannot be placed onto a
                # sharding spanning other processes).
                leaves_dev = prog.init_batched(
                    _to_global(scales, bgrid.batch_sharding)
                )
            else:
                # Idle pad lanes: zero state, zero steps (frozen from
                # step 0 — pure machine padding, the waste the
                # occupancy floor bounds).
                zero = tuple(np.zeros_like(l) for l in prog.base_np)
                while len(lanes) < width:
                    lanes.append(zero)
                leaves_dev = tuple(
                    _to_global(
                        np.stack([lanes[i][leaf] for i in range(width)]),
                        bgrid.sharding,
                    )
                    for leaf in range(prog.n_leaves)
                )
            steps_dev = _to_global(lane_steps, bgrid.batch_sharding)
            out = tuple(prog.adapter.run(prog, leaves_dev, steps_dev, n))
            fetch = self.config.fetch_results
            if fetch is None:
                fetch = not multi
            # Session persistence is independent of result fetching: a
            # fetch_results=False service must still honor the durable-
            # session contract (both need the host copy).
            need_host = fetch or any(t.request.session for t in live)
            if need_host and all(
                leaf.is_fully_addressable for leaf in out
            ):
                # Start the device->host copies NOW, without blocking:
                # by the time the resolve stage reads them the transfer
                # has been riding under the next batch's compute.
                for leaf in out:
                    copy_async = getattr(leaf, "copy_to_host_async",
                                         None)
                    if copy_async is None:
                        break
                    copy_async()
        self._pipe["dispatch_s"] += self._now() - t0
        self._stage_hook("dispatch", key=key.key_str(), width=width,
                         seq=seq, live=len(live))
        fl = _InFlight(
            key=key, width=width, split=split, seq=seq, prog=prog,
            live=live, starts=starts, lane_steps=lane_steps, out=out,
            fetch=fetch, need_host=need_host,
            anchors=(leaves_dev, steps_dev),
        )
        if tracing_on:
            # ONE batch-level trace record, not one per lane: the
            # members roster ({trace_id, lane}) lets the read side
            # derive every member's device span from this record plus
            # lane occupancy (telemetry/tracing.py), keeping the stream
            # O(batches). The roster also feeds the flight recorder: a
            # wedged rank's heartbeat names the requests stuck in
            # flight.
            members = [
                {"trace_id": t.trace.trace_id, "lane": j,
                 "span_id": t.trace.span_id, "hop": t.trace.hop}
                for j, t in enumerate(live) if t.trace is not None
            ]
            _tracing.emit_tspan(
                "trace.batch",
                next((t.trace for t in live if t.trace is not None),
                     None),
                seq=seq, bin=key.key_str(), width=width,
                members=members,
            )
            flight.trace_inflight_add(m["trace_id"] for m in members)
        # Busy-mark LAST, after the stage hook and record construction:
        # a raise between a _note_dispatched and its matching
        # _note_fetched (resolve's finally) would leave _inflight_n
        # stuck high and freeze the bubble accounting for the service's
        # lifetime.
        self._note_dispatched()
        return fl

    def _resolve_batch(self, fl: _InFlight) -> None:
        """Pipeline stages 3+4 — fetch (block) + resolve (host).

        The one place the drain waits on the device: ONE blocking call
        on the whole batch (never leaf-by-leaf in Python), then the
        host copies the dispatch stage already set in motion. The
        finiteness verdict, session saves, ticket resolution, and
        accounting all run here — while the NEXT batch computes, when
        the drain is pipelined."""
        import jax
        import numpy as np

        from rocm_mpi_tpu import telemetry
        from rocm_mpi_tpu.telemetry import flight

        key, width = fl.key, fl.width
        prog, live, starts = fl.prog, fl.live, fl.starts
        lane_steps = fl.lane_steps
        n = int(lane_steps.max())
        tracing_on = bool(self.config.trace_requests)
        if tracing_on:
            # Everything since the compile mark — assembly, upload,
            # dispatch, the device compute itself — charges to "device":
            # the interval ends where the drain starts WAITING.
            tnow = self._now()
            for t in live:
                t.trace_mark("device", tnow)
        t0 = self._now()
        try:
            with telemetry.span("serve.fetch", phase="serve",
                                bin=key.key_str(), width=width):
                jax.block_until_ready(fl.out)
                self._pipe["fetch_wait_s"] += self._now() - t0
                host = None
                if fl.need_host and all(
                    leaf.is_fully_addressable for leaf in fl.out
                ):
                    host = tuple(np.asarray(leaf) for leaf in fl.out)
                # The per-lane finiteness verdict (tenant isolation
                # extended to NUMERICAL failure): a NaN/Inf lane fails
                # only its own ticket — through the retry budget, so a
                # persistently-poison request ends quarantined, never
                # re-batched forever. Computed from the HOST copies
                # when the fetch already paid for them: dispatching the
                # compiled reduction here would serialize against the
                # NEXT batch's in-flight compute (one outstanding
                # dispatch on this stack — measured, the silent
                # re-serialization class) and undo the pipeline. The
                # compiled replicated all-reduce remains the no-host
                # path — multi-controller services need every rank to
                # read one identical verdict, and they host-fetch
                # nothing.
                if host is not None:
                    finite = np.array([
                        all(
                            bool(np.isfinite(leaf[j]).all())
                            for leaf in host
                        )
                        for j in range(width)
                    ])
                else:
                    finite = np.asarray(prog.lane_finite(fl.out))
        finally:
            # The busy interval ends even when the fetch raises —
            # a failed batch must not read as a forever-busy device.
            # The donated-input anchors release HERE: the advance has
            # finished (or failed), so dropping the last references no
            # longer blocks the host (_InFlight.anchors has the why).
            fl.anchors = ()
            self._pipe["fetch_s"] += self._now() - t0
            self._note_fetched()
        if tracing_on:
            tnow = self._now()
            for t in live:
                t.trace_mark("fetch", tnow)
            # Off-device: the heartbeat's in-flight roster drops the
            # batch here (a fetch that RAISES is dropped by
            # _batch_failed instead).
            flight.trace_inflight_drop(
                t.trace.trace_id for t in live if t.trace is not None
            )
        self._stage_hook("fetch", key=key.key_str(), width=width,
                         seq=fl.seq, live=len(live))

        t0 = self._now()
        done = 0
        with telemetry.span("serve.resolve", phase="serve",
                            bin=key.key_str(), width=width,
                            live=len(live)):
            for j, t in enumerate(live):
                if not bool(finite[j]):
                    telemetry.record_event(
                        "serve.lane.nan",
                        request_id=t.request.request_id,
                        bin=key.key_str(), width=width, lane=j,
                    )
                    self._retry_or_quarantine(
                        t, "non-finite state (NaN/Inf) in lane"
                    )
                    continue
                # Lane-isolated resolution: one tenant's failing
                # session save (unwritable dir, disk full) must not
                # fail its co-batched neighbors or skew the completion
                # accounting.
                try:
                    lane = (
                        tuple(leaf[j] for leaf in host)
                        if host is not None else None
                    )
                    if t.request.session and lane is not None:
                        self._save_session(t, lane, prog)
                except ValueError as e:
                    self._fail_ticket(t, str(e))
                    continue
                except Exception as e:  # noqa: BLE001 — tenant isolation
                    self._retry_or_quarantine(t, str(e))
                    continue
                t.steps_run = int(lane_steps[j])
                t._resolve(lane if fl.fetch else None)
                done += 1
                if tracing_on:
                    t.trace_mark("resolve", self._now())
                latency = t.age_s()
                telemetry.record_event(
                    "serve.request.done",
                    request_id=t.request.request_id,
                    bin=key.key_str(), width=width,
                    steps=int(lane_steps[j]), start=starts[j],
                    latency_s=round(latency, 6),
                    deadline_miss=bool(
                        t.request.deadline_s is not None
                        and latency > t.request.deadline_s
                    ),
                    **(
                        {"hop": t.trace.hop, "decomp": t.decomp_doc()}
                        if tracing_on and t.trace is not None else {}
                    ),
                )
            self.queue.note_completed(done)
            flight.progress(serve_completed=done)

            st = self._stats.get(key)
            if st is None:
                st = self._stats[key] = BinStats(key=key)
            st.note_batch(width,
                          [int(s) for s in lane_steps[:len(live)]],
                          n, split=fl.split)
        self._pipe["resolve_s"] += self._now() - t0
        self._pipe["batches"] += 1
        self._stage_hook("resolve", key=key.key_str(), width=width,
                         seq=fl.seq, live=len(live))

    def _run_segmented_batch(self, key: BinKey, tickets: list[Ticket],
                             width: int, ladder: bool) -> int:
        """The continuous drain's batch executor (docs/SERVING.md
        "Continuous batching"): ONE compiled program of `width` lanes
        executes the whole ticket group as fixed-size step segments
        (`steps_bucket // segments` steps each). Between segments where
        no lane finishes, the output chains straight back in ON DEVICE
        — no host fetch, no bubble; the boundary plan is host-side
        arithmetic on the remaining-step counts. At a boundary where
        lanes DO finish, one blocking fetch resolves them (same
        finiteness/retry/session semantics as _resolve_batch), their
        slots re-seat from the group's backlog and then the queue's
        matching arrivals (queue.pop_matching), and the batch
        continues. Every lane — first-seated or swapped in — is
        bitwise-equal to its standalone run: the compiled advance
        freezes a lane at its own `lane_steps`, so K chained segments
        of the one program ARE the lane's single long run (the PR-9
        run_segmented discipline folded inside the program). No
        recompile at any boundary; `compiles.steady_state` stays 0.
        Single-controller only (drain_once gates). Returns the
        completed-ticket count."""
        import jax
        import numpy as np

        from rocm_mpi_tpu import telemetry
        from rocm_mpi_tpu.resilience import faults
        from rocm_mpi_tpu.telemetry import flight

        # The batch-granular fault contract — same sites, same ordering
        # as _prepare_batch: one seq/progress bump per segmented batch
        # (segments are sub-batch machinery, not scheduler units).
        self._batch_seq += 1
        seq = self._batch_seq
        faults.fault_point("serve-batch", step=seq)
        clause = faults.serving_fault("batch-error", step=seq)
        if clause is not None:
            raise RuntimeError(f"injected batch-error (batch {seq})")
        flight.progress(step_inc=1)
        slow = faults.serving_fault("slow-batch", step=seq)
        if slow is not None:
            time.sleep(slow.delay_s)

        tracing_on = bool(self.config.trace_requests)
        if tracing_on:
            from rocm_mpi_tpu.telemetry import tracing as _tracing

            tnow = self._now()
            for t in tickets:
                t.trace_mark("queue_wait", tnow)
        prog = self._program_for(key, width, ladder=ladder)
        if tracing_on:
            # Same telescoping boundaries as _prepare_batch; the
            # continuous drain adds "swap_wait" — backlog tickets charge
            # their compile->seat wait to it (they wait for a LANE, not
            # for the queue).
            tnow = self._now()
            for t in tickets:
                t.trace_mark("compile", tnow)
        bgrid = prog.bgrid
        seg_len = max(
            1, key.steps_bucket // max(1, int(self.config.segments))
        )
        fetch = self.config.fetch_results
        if fetch is None:
            fetch = True  # single-controller by construction
        gk = (key, ladder)
        kstr = key.key_str()

        backlog = list(tickets)
        lane_t: list = [None] * width
        starts = [0] * width
        remaining = np.zeros(width, dtype=np.int64)
        lanes_np: list = [None] * width  # host leaves per seated slot
        zero = tuple(np.zeros_like(l) for l in prog.base_np)
        cdtype = prog.base_np_dtype
        ndim = len(key.shape)
        hold_rows = [np.ones(zero[0].shape, dtype=bool)
                     for _ in range(width)]
        a_rows = np.zeros(width, dtype=cdtype)
        g_rows = np.ones((width, ndim), dtype=cdtype)
        padded_cells = 1
        for nn in key.shape:
            padded_cells *= int(nn)

        done = 0
        swaps_in = 0
        swaps_out = 0
        segs_run = 0
        executed = 0  # machine steps (the occupancy denominator rides
        occ_num = 0   # width × this; the numerator is per-lane useful)
        tenant_nts: list[int] = []
        tenant_cells: list[tuple[int, int]] = []

        def seat(j: int, t: Ticket) -> bool:
            """Host-assemble ticket t into slot j; route its failure
            (same ValueError-terminal / transient-retry split as
            _prepare_batch's lane loop) and report success."""
            try:
                if ladder:
                    leaves, hold, a, g = self._ladder_lane(
                        t.request, key, prog
                    )
                    start = 0
                else:
                    start = (
                        self._resume_step(t.request, prog)
                        if t.request.resume else 0
                    )
                    leaves, _ = self._lane_start_state(
                        t.request, prog, start
                    )
            except ValueError as e:
                self._fail_ticket(t, str(e))
                return False
            except Exception as e:  # noqa: BLE001 — tenant isolation
                self._retry_or_quarantine(t, str(e))
                return False
            if faults.serving_fault("lane-nan", request=t.ordinal) \
                    is not None:
                leaves = tuple(l * float("nan") for l in leaves)
            t.start_step = start
            lane_t[j] = t
            starts[j] = start
            remaining[j] = t.request.nt - start
            lanes_np[j] = leaves
            if ladder:
                hold_rows[j] = hold
                a_rows[j] = a
                g_rows[j] = np.asarray(g, dtype=cdtype)
            if tracing_on:
                # Seated: the wait-for-a-lane interval ends (first
                # seats charge ~0; boundary swap-ins charge the
                # segments they sat out).
                t.trace_mark("swap_wait", self._now())
            return True

        def fill(allow_queue: bool) -> int:
            """Seat every free slot from the backlog, then (daemon
            arrivals) from same-class queued tickets. Swap eligibility
            IS the group key: same compiled program, same ladder
            routing."""
            n_seated = 0
            for j in range(width):
                if lane_t[j] is not None:
                    continue
                while lane_t[j] is None and backlog:
                    seat(j, backlog.pop(0))
                while lane_t[j] is None and allow_queue:
                    pulled = self.queue.pop_matching(
                        lambda r: self._group_key(r) == gk, max_n=1
                    )
                    if not pulled:
                        break
                    flight.progress(serve_submitted=1)
                    if tracing_on:
                        # A daemon arrival's queue wait ends at its
                        # pop, not at the group's drain entry.
                        pulled[0].trace_mark("queue_wait", self._now())
                    # Join the batch's ticket roster so a batch-level
                    # failure (_batch_failed) covers swap-ins too.
                    tickets.append(pulled[0])
                    seat(j, pulled[0])
                if lane_t[j] is not None:
                    n_seated += 1
            return n_seated

        def to_device():
            rows = [
                lanes_np[j] if lanes_np[j] is not None else zero
                for j in range(width)
            ]
            leaves = tuple(
                _to_global(
                    np.stack([rows[j][leaf] for j in range(width)]),
                    bgrid.sharding,
                )
                for leaf in range(prog.n_leaves)
            )
            if not ladder:
                return leaves, ()
            # inv_d2 uploads as ndim separate per-axis (width,) arrays
            # — the models' per-axis scalar-operand contract (the
            # fori-fusion ulp note in step_fused_padded_geom).
            geom = (
                _to_global(np.stack(hold_rows), bgrid.sharding),
                _to_global(np.asarray(a_rows), bgrid.batch_sharding),
                tuple(
                    _to_global(
                        np.ascontiguousarray(g_rows[:, ax]),
                        bgrid.batch_sharding,
                    )
                    for ax in range(ndim)
                ),
            )
            return leaves, geom

        def roster() -> list[dict]:
            """The seated lanes' trace membership ({trace_id, lane}) —
            what trace.batch/trace.segment records and the flight
            recorder's in-flight set are built from."""
            return [
                {"trace_id": lane_t[j].trace.trace_id, "lane": j}
                for j in range(width)
                if lane_t[j] is not None
                and lane_t[j].trace is not None
            ]

        t0 = self._now()
        with telemetry.span("serve.assemble", phase="serve",
                            bin=kstr, width=width):
            fill(allow_queue=False)
        self._pipe["assemble_s"] += self._now() - t0
        self._stage_hook(
            "assemble", key=kstr, width=width, seq=seq,
            live=sum(1 for t in lane_t if t is not None),
        )
        seated_ids: set = set()
        if tracing_on:
            members = roster()
            _tracing.emit_tspan(
                "trace.batch",
                next((lane_t[j].trace for j in range(width)
                      if lane_t[j] is not None
                      and lane_t[j].trace is not None), None),
                seq=seq, bin=kstr, width=width, segmented=True,
                members=members,
            )
            seated_ids = {m["trace_id"] for m in members}
            flight.trace_inflight_add(seated_ids)

        leaves_dev = None
        geom_dev = ()
        anchors: list = []
        preempted = False
        while any(t is not None for t in lane_t):
            live_j = [j for j in range(width) if lane_t[j] is not None]
            n_seg = int(min(
                seg_len, max(int(remaining[j]) for j in live_j)
            ))
            n_seg = max(1, n_seg)
            t0 = self._now()
            new_flight = leaves_dev is None
            if new_flight:
                # One busy-mark per CHAIN (upload .. blocking fetch),
                # not per segment: chained segments are one continuous
                # flight, and _note_dispatched/_note_fetched must pair
                # 1:1 or _inflight_n wedges and the bubble gauge dies.
                # Marked BEFORE the dispatch span: the pipelined
                # classic drain preps batch N+1 under batch N's open
                # window, so its upload wall lands inside busy time.
                # Segmented chains run serially — marking after the
                # dispatch span would charge every post-swap upload
                # as bubble, work the classic path hides for free.
                self._note_dispatched()
            with telemetry.span(
                "serve.dispatch", phase="serve", bin=kstr,
                width=width, live=len(live_j), steps=n_seg,
            ):
                if leaves_dev is None:
                    leaves_dev, geom_dev = to_device()
                steps_np = np.clip(remaining, 0, n_seg).astype(np.int32)
                steps_dev = _to_global(steps_np, bgrid.batch_sharding)
                if ladder:
                    out = tuple(prog.adapter.run_ladder(
                        prog, leaves_dev, *geom_dev, steps_dev, n_seg
                    ))
                else:
                    out = tuple(prog.adapter.run(
                        prog, leaves_dev, steps_dev, n_seg
                    ))
                # Donated-input deletion anchors (_InFlight.anchors has
                # the hazard): the chained inputs ride here until the
                # next blocking fetch, when deletion is free.
                anchors.append((leaves_dev, steps_dev))
            self._pipe["dispatch_s"] += self._now() - t0
            self._stage_hook("dispatch", key=kstr, width=width,
                             seq=seq, live=len(live_j))
            segs_run += 1
            executed += n_seg
            occ_num += sum(
                min(int(remaining[j]), n_seg) for j in live_j
            )
            # The boundary plan is HOST arithmetic — no fetch needed to
            # know who finished: remaining-step counts are deterministic.
            finishing = [
                j for j in live_j if int(remaining[j]) <= n_seg
            ]
            for j in live_j:
                remaining[j] = max(0, int(remaining[j]) - n_seg)
            if not finishing:
                # Pure chain: the advance's output feeds the next
                # segment ON DEVICE. Zero host sync, zero bubble.
                leaves_dev = out
                continue

            if tracing_on:
                # A finishing lane's whole chain — every segment it
                # rode, including intermediate boundary round trips —
                # is device time from ITS seat mark to this wait.
                tnow = self._now()
                for j in finishing:
                    lane_t[j].trace_mark("device", tnow)
            t0 = self._now()
            with telemetry.span("serve.fetch", phase="serve",
                                bin=kstr, width=width):
                jax.block_until_ready(out)
                self._pipe["fetch_wait_s"] += self._now() - t0
                host = tuple(np.asarray(leaf) for leaf in out)
            anchors.clear()
            self._pipe["fetch_s"] += self._now() - t0
            self._note_fetched()
            if tracing_on:
                tnow = self._now()
                for j in finishing:
                    lane_t[j].trace_mark("fetch", tnow)
            self._stage_hook("fetch", key=kstr, width=width, seq=seq,
                             live=len(live_j))

            t0 = self._now()
            done_here = 0
            with telemetry.span("serve.resolve", phase="serve",
                                bin=kstr, width=width,
                                live=len(finishing)):
                for j in finishing:
                    t = lane_t[j]
                    nt_run = int(t.request.nt - starts[j])
                    tenant_nts.append(nt_run)
                    if ladder:
                        orig_cells = 1
                        for nn in t.request.global_shape:
                            orig_cells *= int(nn)
                        tenant_cells.append((orig_cells, padded_cells))
                    finite = all(
                        bool(np.isfinite(leaf[j]).all())
                        for leaf in host
                    )
                    if not finite:
                        telemetry.record_event(
                            "serve.lane.nan",
                            request_id=t.request.request_id,
                            bin=kstr, width=width, lane=j,
                        )
                        self._retry_or_quarantine(
                            t, "non-finite state (NaN/Inf) in lane"
                        )
                        lane_t[j] = None
                        lanes_np[j] = None
                        continue
                    try:
                        lane = tuple(leaf[j] for leaf in host)
                        if ladder:
                            region = tuple(
                                slice(0, nn)
                                for nn in t.request.global_shape
                            )
                            lane = tuple(l[region] for l in lane)
                        if t.request.session:
                            self._save_session(t, lane, prog)
                    except ValueError as e:
                        self._fail_ticket(t, str(e))
                        lane_t[j] = None
                        lanes_np[j] = None
                        continue
                    except Exception as e:  # noqa: BLE001
                        self._retry_or_quarantine(t, str(e))
                        lane_t[j] = None
                        lanes_np[j] = None
                        continue
                    t.steps_run = nt_run
                    t._resolve(lane if fetch else None)
                    done_here += 1
                    if tracing_on:
                        t.trace_mark("resolve", self._now())
                    latency = t.age_s()
                    telemetry.record_event(
                        "serve.request.done",
                        request_id=t.request.request_id,
                        bin=kstr, width=width, steps=nt_run,
                        start=starts[j],
                        latency_s=round(latency, 6),
                        deadline_miss=bool(
                            t.request.deadline_s is not None
                            and latency > t.request.deadline_s
                        ),
                        **(
                            {"hop": t.trace.hop,
                             "decomp": t.decomp_doc()}
                            if tracing_on and t.trace is not None
                            else {}
                        ),
                    )
                    lane_t[j] = None
                    lanes_np[j] = None
                self.queue.note_completed(done_here)
                flight.progress(serve_completed=done_here)
                done += done_here
                # Surviving lanes cross the boundary through an exact
                # host round trip (fetch + re-upload is bitwise).
                for j in live_j:
                    if lane_t[j] is not None:
                        lanes_np[j] = tuple(leaf[j] for leaf in host)
                # A preemption notice stops SWAP-INS at this boundary
                # (the batch-boundary analog of the rc-75 contract);
                # already-seated lanes run to completion.
                if self._preempt_requested():
                    preempted = True
                if not preempted:
                    k = fill(allow_queue=True)
                    swaps_in += k
                if any(t is not None for t in lane_t):
                    swaps_out += len(finishing)
            self._pipe["resolve_s"] += self._now() - t0
            self._stage_hook("resolve", key=kstr, width=width,
                             seq=seq, live=len(finishing))
            if tracing_on:
                # The boundary record AFTER the swap: joined lanes
                # appear in the segment they joined at (the read side
                # derives their device spans from here), and the
                # flight recorder's in-flight set moves with the seats.
                members = roster()
                _tracing.emit_tspan(
                    "trace.segment",
                    next((lane_t[j].trace for j in range(width)
                          if lane_t[j] is not None
                          and lane_t[j].trace is not None), None),
                    seq=seq, seg=segs_run, bin=kstr, width=width,
                    members=members,
                )
                ids_now = {m["trace_id"] for m in members}
                flight.trace_inflight_drop(seated_ids - ids_now)
                flight.trace_inflight_add(ids_now - seated_ids)
                seated_ids = ids_now
            leaves_dev = None  # re-assemble from host rows next round
            geom_dev = ()

        if backlog:
            # Preemption (or a breaker-sized seat drought) left group
            # tickets unseated: park them back at the queue's front —
            # the same undispatched-work requeue the classic drain does
            # at its batch boundary.
            self.queue.requeue(backlog)
            flight.progress(serve_requeued=len(backlog))

        st = self._stats.get(key)
        if st is None:
            st = self._stats[key] = BinStats(key=key)
        st.note_continuous(
            width, tenant_nts, executed, swaps_in, segs_run,
            lane_cells=tenant_cells if ladder else None,
        )
        self._pipe["batches"] += 1
        c = self._continuous
        c["batches"] += 1
        c["segments_run"] += segs_run
        c["swaps_in"] += swaps_in
        c["swaps_out"] += swaps_out
        c["occ_num"] += occ_num
        c["occ_den"] += width * executed
        self._drain_swaps += swaps_in
        self._drain_occ[0] += occ_num
        self._drain_occ[1] += width * executed
        self._sync_admission_counters()
        return done

    def _batch_failed(self, key: BinKey, batch_ts: list[Ticket],
                      width: int, e: Exception) -> None:
        """The batch-level failure chokepoint (tenant isolation): a
        batch failure — at prepare (compile error, injected
        batch-error) or at resolve (device fault surfacing at fetch) —
        fails ITS tickets and lets the other bins' batches keep
        serving; an unhandled escape would strand every later popped
        ticket in 'running' forever and kill the daemon without the
        rc-75 requeue path. The tickets ride the retry budget
        (transient faults requeue bounded, then quarantine); K
        consecutive failures open the class's circuit breaker."""
        from rocm_mpi_tpu import telemetry
        from rocm_mpi_tpu.telemetry import flight

        telemetry.record_event(
            "serve.batch.error", bin=key.key_str(), width=width,
            error=str(e),
        )
        # The failed batch is off the device however it died: the
        # heartbeat's in-flight trace roster must not carry its
        # requests forever.
        flight.trace_inflight_drop(
            t.trace.trace_id for t in batch_ts if t.trace is not None
        )
        br = self._breakers[key]
        if br.note_failure(self._circuit, self._drains):
            telemetry.record_event(
                "serve.circuit.open", bin=key.key_str(),
                consecutive=br.consecutive,
            )
        for t in batch_ts:
            if not t.done() and t.state == "running":
                # Same routing as the lane level: a ValueError is a
                # per-request/program-class validation error (unknown
                # physics) — terminal, never retried; anything else is
                # transient and rides the retry budget.
                if isinstance(e, ValueError):
                    self._fail_ticket(t, str(e))
                else:
                    self._retry_or_quarantine(t, str(e))

    def pipeline_stats(self) -> dict:
        """Lifetime pipeline accounting (the manifest's `pipeline`
        block, docs/SERVING.md "The pipeline"): per-stage host walls
        (`fetch_wait_s` the part of `fetch_s` blocked on the device),
        the resolved batches, the device bubble — the fraction of the
        cumulative drain-execute wall with NO batch in flight — and the
        lane-steps of the resolved batches: `lane_steps_executed`
        (width × steps run, idle lanes and frozen tails included) and
        `lane_steps_useful` (each live lane's own step count)."""
        p = self._pipe
        wall = p["wall_s"]
        bubble = max(0.0, 1.0 - p["busy_s"] / wall) if wall > 0 else 0.0
        return {
            "depth": max(1, int(self.config.pipeline_depth)),
            "batches": int(p["batches"]),
            "bubble": round(bubble, 4),
            "assemble_s": round(p["assemble_s"], 6),
            "dispatch_s": round(p["dispatch_s"], 6),
            "fetch_s": round(p["fetch_s"], 6),
            "fetch_wait_s": round(p["fetch_wait_s"], 6),
            "resolve_s": round(p["resolve_s"], 6),
            "busy_s": round(p["busy_s"], 6),
            "wall_s": round(p["wall_s"], 6),
            "lane_steps_executed": sum(
                st.machine_steps for st in self._stats.values()
            ),
            "lane_steps_useful": sum(
                st.useful_steps for st in self._stats.values()
            ),
        }

    def _fail_ticket(self, t: Ticket, error: str) -> None:
        """The per-request-error chokepoint: ticket, queue counter, AND
        the serve_failed flight counter — the monitor's depth formula
        must see every outcome, or a failed request reads as backlog
        forever. Terminal: validation errors never retry."""
        from rocm_mpi_tpu.telemetry import flight

        t._fail(error)
        self.queue.note_completed(0, failed=1)
        flight.progress(serve_failed=1)

    def _retry_or_quarantine(self, t: Ticket, error: str) -> None:
        """The transient-failure chokepoint (docs/SERVING.md "SLOs and
        admission"): requeue with exponential backoff while the retry
        budget lasts; a request that exhausts it is quarantined —
        terminally, with its full record banked — never requeued
        again."""
        from rocm_mpi_tpu import telemetry
        from rocm_mpi_tpu.telemetry import flight

        if t.retries < self._retry.budget:
            t.retries += 1
            self.retries_total += 1
            if self.queue.wall_slo:
                backoff = self._retry.backoff_s(t.retries)
                t.not_before = self._now() + backoff
                # The park is charged to "backoff", not "queue_wait":
                # the next queue_wait mark peels this much off first
                # (Ticket.trace_mark — the decomposition contract).
                t.backoff_pending += backoff
            # wake=False: the submitter keeps waiting for the retried
            # batch's real resolution (unlike a preemption park).
            self.queue.requeue([t], wake=False)
            flight.progress(serve_retries=1)
            telemetry.record_event(
                "serve.request.retry",
                request_id=t.request.request_id,
                retries=t.retries, budget=self._retry.budget,
                error=error,
            )
            return
        self._quarantine_ticket(t, error)

    def _quarantine_ticket(self, t: Ticket, error: str) -> None:
        """Expel a poison request: terminal `quarantined` state, the
        full request record appended to the quarantine.jsonl ledger for
        offline repro, counters bumped — and NEVER requeued."""
        from rocm_mpi_tpu import telemetry
        from rocm_mpi_tpu.telemetry import flight

        record = quarantine_record(t.request, error, t.retries)
        self._quarantined.append(record)
        if self.config.quarantine_path and self._ledger_writer():
            append_quarantine(self.config.quarantine_path, record)
        t._terminal_fail(
            "quarantined",
            f"{error} (retry budget {self._retry.budget} exhausted)",
        )
        self.queue.note_quarantined(1)
        flight.progress(serve_quarantined=1)
        telemetry.record_event(
            "serve.request.quarantined",
            request_id=t.request.request_id,
            retries=t.retries, error=error,
        )

    def _ledger_writer(self) -> bool:
        """One writer per ledger: in a multi-controller service every
        rank reaches the same deterministic quarantine decision, so
        only rank 0 appends — N identical records from N concurrent
        writers would both inflate the poison count and risk
        interleaved lines."""
        if not self._is_multi():
            return True
        import jax

        return jax.process_index() == 0

    def _reject_ticket(self, t: Ticket, error: str) -> None:
        """Admission rejection of an already-popped ticket (the circuit
        breaker's fast-fail): terminal `rejected`."""
        from rocm_mpi_tpu import telemetry
        from rocm_mpi_tpu.telemetry import flight

        t._terminal_fail("rejected", error)
        self.queue.note_rejected(1)
        flight.progress(serve_rejected=1)
        telemetry.record_event(
            "serve.request.rejected",
            request_id=t.request.request_id, error=error,
        )

    def _sync_admission_counters(self) -> None:
        """Mirror queue-side admission outcomes (submit-time
        rejections, pop-time expiries) into the flight counters and the
        telemetry stream — the SERVE badge and the SLO accounting must
        see every outcome the queue decided without the service's
        help."""
        from rocm_mpi_tpu import telemetry
        from rocm_mpi_tpu.telemetry import flight

        c = self.queue.counters()
        d_rej = self.queue.rejected_at_submit \
            - self._admission_sync["rejected"]
        if d_rej > 0:
            self._admission_sync["rejected"] = \
                self.queue.rejected_at_submit
            # serve_submitted rides along: the badge's depth formula
            # subtracts every outcome from it, and these tickets were
            # never popped into a drain's serve_submitted bump (the
            # circuit-open rejections of POPPED tickets are counted by
            # _reject_ticket itself).
            flight.progress(serve_rejected=d_rej, serve_submitted=d_rej)
        for t in self.queue.take_expired():
            telemetry.record_event(
                "serve.request.expired",
                request_id=t.request.request_id,
                deadline_s=t.request.deadline_s, error=t.error,
            )
        d_exp = c["expired"] - self._admission_sync["expired"]
        if d_exp > 0:
            self._admission_sync["expired"] = c["expired"]
            flight.progress(serve_expired=d_exp, serve_submitted=d_exp)

    def _preempt_requested(self) -> bool:
        from rocm_mpi_tpu.resilience import preempt

        return preempt.requested()

    def drain_once(self) -> tuple[int, bool]:
        """One drain pass: pop everything pending, pack, execute.
        Returns (served_count, preempted) — on preemption the unserved
        tickets are requeued and dispatch stops at the batch boundary
        (the scheduler's rc-75 requeue signal, docs/SERVING.md)."""
        from rocm_mpi_tpu import telemetry
        from rocm_mpi_tpu.telemetry import compiles, flight

        self._drains += 1
        self._is_multi()
        tickets = self.queue.pop_pending()
        self._sync_admission_counters()
        telemetry.gauge("serve.queue_depth", float(len(tickets)))
        if not tickets:
            # Backoff-parked tickets are pending-but-ineligible work,
            # not idleness — they must not trigger the idle shrink.
            if self.queue.depth() == 0:
                self._idle_drains += 1
            return 0, False
        self._idle_drains = 0
        flight.progress(serve_submitted=len(tickets))
        self._compiled_this_drain = False
        self._drain_swaps = 0
        self._drain_occ = [0, 0]

        # Groups are keyed (BinKey, ladder): the ladder bool separates
        # the padded-program route from the exact route so a ladder-
        # ineligible request (session, lossy wire, multi) sharing the
        # BinKey never collides with the laddered program class.
        groups: dict[tuple[BinKey, bool], list[Ticket]] = {}
        bad: list[tuple[Ticket, str]] = []
        for t in tickets:
            try:
                groups.setdefault(self._group_key(t.request),
                                  []).append(t)
            except ValueError as e:
                bad.append((t, str(e)))
        for t, msg in bad:
            self._fail_ticket(t, msg)

        served = 0
        # (key, tickets, width, split, ladder, segmented)
        pending: list[tuple] = []
        multi = self._is_multi()
        for gk in sorted(groups, key=lambda g: (g[0], g[1])):
            key, ladder = gk
            ts = groups[gk]
            # The circuit breaker's admission gate: an OPEN class
            # rejects fast with circuit-open (one failing shape class
            # must not starve every other tenant's throughput); a
            # cooled-down class re-admits exactly ONE half-open probe.
            # Breakers stay keyed by BinKey: the failure domain is the
            # shape class, however it is routed.
            br = self._breakers.get(key)
            if br is None:
                br = self._breakers[key] = _Breaker()
            admit = br.admit(self._circuit, self._drains, len(ts))
            if admit < len(ts):
                telemetry.record_event(
                    "serve.circuit.reject", bin=key.key_str(),
                    state=br.state, rejected=len(ts) - admit,
                )
                for t in ts[admit:]:
                    self._reject_ticket(
                        t, f"circuit-open ({key.key_str()})"
                    )
                ts = ts[:admit]
            if not ts:
                continue
            segmented = (
                (int(self.config.segments) > 1 or ladder) and not multi
            )
            widths = _bins.plan_batches(
                len(ts), self.config.max_width, self._floor
            )
            canonical = widths[0]
            if segmented:
                # The continuous drain runs the WHOLE group as one
                # segmented batch of the canonical width: overflow
                # tickets are the swap-in backlog, not separate
                # (possibly split) batches.
                pending.append((key, ts, canonical, False, ladder,
                                True))
                continue
            i = 0
            for w in widths:
                take = min(w, len(ts) - i)
                pending.append((key, ts[i:i + take], w,
                                w != canonical, ladder, False))
                i += take

        # The drain pipeline (docs/SERVING.md "The pipeline"): at
        # depth 1, each batch runs assemble → dispatch → fetch →
        # resolve serially through the _execute_batch chokepoint; at
        # depth D >= 2, up to D-1 batches ride dispatched-but-
        # unresolved, so batch N+1's host assembly and transfer overlap
        # batch N's device compute, and batch N's fetch/resolve runs
        # while N+1 computes. Every batch still resolves INSIDE this
        # drain pass (the bounded tail drain below), so the
        # drain-boundary accounting invariant and the retry/breaker/
        # preemption semantics are depth-independent — and the results
        # bitwise-equal, since the stages reorder waiting, never work.
        preempted = False
        depth = max(1, int(self.config.pipeline_depth))
        inflight: list[tuple] = []  # FIFO: (key, tickets, width, fl)
        exec_t0 = self._now()
        busy0 = self._pipe["busy_s"]

        def _finish(entry) -> None:
            nonlocal served
            fkey, fts, fw, fl = entry
            fbr = self._breakers[fkey]
            try:
                self._resolve_batch(fl)
                served += sum(1 for t in fts if t.state == "done")
                if fbr.note_success():
                    telemetry.record_event(
                        "serve.circuit.close", bin=fkey.key_str(),
                    )
            except Exception as e:  # noqa: BLE001 — tenant isolation
                self._batch_failed(fkey, fts, fw, e)

        for bi, (key, batch_ts, w, split, ladder, segmented) \
                in enumerate(pending):
            if self._preempt_requested():
                # Undispatched work requeues at the batch boundary (the
                # rc-75 contract); already-dispatched batches FINISH in
                # the tail drain below — in-flight lanes always
                # complete their batch.
                preempted = True
                rest = [
                    t for entry in pending[bi:] for t in entry[1]
                ]
                self.queue.requeue(rest)
                flight.progress(serve_requeued=len(rest))
                break
            br = self._breakers[key]
            if segmented:
                # The continuous batch IS its own pipeline (device
                # chaining between boundaries): flush the classic
                # in-flight batches first — both for the session
                # read-after-write ordering and so the two executors
                # never interleave their busy accounting.
                while inflight:
                    _finish(inflight.pop(0))
                try:
                    served += self._run_segmented_batch(
                        key, batch_ts, w, ladder
                    )
                    if br.note_success():
                        telemetry.record_event(
                            "serve.circuit.close", bin=key.key_str(),
                        )
                except Exception as e:  # noqa: BLE001 — tenant isolation
                    self._batch_failed(key, batch_ts, w, e)
                continue
            if depth == 1:
                try:
                    self._execute_batch(key, batch_ts, w, split)
                    served += sum(
                        1 for t in batch_ts if t.state == "done"
                    )
                    if br.note_success():
                        telemetry.record_event(
                            "serve.circuit.close", bin=key.key_str(),
                        )
                except Exception as e:  # noqa: BLE001 — tenant isolation
                    self._batch_failed(key, batch_ts, w, e)
                continue
            if inflight and any(t.request.resume for t in batch_ts):
                # Session read-after-write barrier: a resume lane's
                # assembly reads its session dir, and an in-flight
                # batch's resolve may still be ABOUT to write it (the
                # session save lives in the resolve stage). Flush the
                # pipeline first so the resume batch assembles against
                # exactly the state the serial drain would see — the
                # bitwise-equal contract; a rare, bounded stall.
                while inflight:
                    _finish(inflight.pop(0))
            try:
                fl = self._prepare_batch(key, batch_ts, w, split)
            except Exception as e:  # noqa: BLE001 — tenant isolation
                self._batch_failed(key, batch_ts, w, e)
                continue
            if fl is None:
                # No lane survived assembly: the serial path books this
                # as a (no-op) served batch too.
                if br.note_success():
                    telemetry.record_event(
                        "serve.circuit.close", bin=key.key_str(),
                    )
                continue
            inflight.append((key, batch_ts, w, fl))
            while len(inflight) >= depth:
                _finish(inflight.pop(0))
        # The bounded tail drain: everything still in flight resolves
        # before the drain returns.
        for entry in inflight:
            _finish(entry)

        if pending:
            d_wall = self._now() - exec_t0
            self._pipe["wall_s"] += d_wall
            d_busy = self._pipe["busy_s"] - busy0
            bubble = (
                max(0.0, 1.0 - d_busy / d_wall) if d_wall > 0 else 0.0
            )
            self.last_bubble = bubble
            telemetry.gauge("serve.pipeline_depth", float(depth))
            telemetry.gauge("serve.device_bubble", round(bubble, 4))
        if self._drain_occ[1]:
            # Per-drain continuous gauges: step-weighted slot occupancy
            # (live lane-steps / width × machine steps) and the swap-in
            # count — the two numbers the continuous-vs-batch-sync
            # regress gate reads.
            telemetry.gauge(
                "serve.occupancy",
                round(self._drain_occ[0] / self._drain_occ[1], 4),
            )
            telemetry.gauge("serve.swap", float(self._drain_swaps))

        if not preempted and not self._compiled_this_drain \
                and self._programs:
            # Every program class the live traffic needs exists: any
            # recompile from here is a steady-state regression the
            # compiles.* zero-pin gates.
            compiles.mark_steady()
        return served, preempted

    # ---- elasticity (the ElasticPolicy consumer) ------------------------

    def maybe_resize(self) -> bool:
        """Queue-driven elasticity: grow the batch rows when the queue
        is deep and the policy + device budget agree; shrink when idle.
        Resize drops every compiled program/model (they are bound to the
        old mesh — the PR-6 rebuild discipline) and reopens the compile
        window (a resize compile is elastic, not a steady regression)."""
        import jax

        policy = self.config.policy
        if policy is None or jax.process_count() > 1:
            return False
        budget_fn = self.config.device_budget
        budget = int(budget_fn() if budget_fn else len(jax.devices()))
        depth = self.queue.depth()
        bd = self._batch_dims
        target = None
        kind = None
        if depth >= self.config.grow_queue_depth and policy.wants_grow(
            bd, budget,
            step=self._drains,
            last_change_step=self._last_resize_drain,
        ):
            grown = policy.grow_target(bd, budget, _bins.pow2_floor)
            if grown > bd:
                target, kind = grown, "grow"
        elif (
            depth == 0
            and self._idle_drains >= self.config.idle_shrink_drains
            and bd > max(1, int(getattr(policy, "min_ranks", 1)))
        ):
            target, kind = max(bd // 2,
                               int(getattr(policy, "min_ranks", 1))), \
                "shrink"
        if target is None or target == bd:
            return False
        self._resize(target, kind, depth=depth, budget=budget)
        return True

    def _resize(self, new_bd: int, kind: str, **attrs) -> None:
        from rocm_mpi_tpu import telemetry
        from rocm_mpi_tpu.telemetry import compiles, flight

        old = self._batch_dims
        self._batch_dims = int(new_bd)
        self._models.clear()
        self._programs.clear()
        compiles.unmark_steady()
        self._last_resize_drain = self._drains
        event = {
            "event": f"serve.{kind}", "old_batch_dims": old,
            "new_batch_dims": int(new_bd), "drain": self._drains,
            **attrs,
        }
        self._elastic.append(event)
        telemetry.record_event(f"serve.{kind}", **event)
        flight.progress(serve_resizes=1)

    # ---- drivers --------------------------------------------------------

    def run_trace(self, requests) -> ServeReport:
        """Serve a request list to completion (the acceptance driver):
        submit everything, drain until the queue is empty (or a
        preemption notice stops dispatch), return the report."""
        tickets = [self.queue.submit(r) for r in requests]
        report = self._drain_all()
        del tickets
        return report

    def _drain_all(self) -> ServeReport:
        report = ServeReport()
        while True:
            # Resize BEFORE draining: the decision input is the backlog,
            # and drain_once pops the whole queue.
            self.maybe_resize()
            served, preempted = self.drain_once()
            report.served += served
            if preempted:
                report.preempted = True
                break
            if self.queue.depth() == 0:
                break
            # A preemption notice between drain passes (the remaining
            # work is all backoff-parked) must stop the loop at this
            # boundary — queued work stays queued, nothing requeues.
            if self._preempt_requested():
                report.preempted = True
                break
            # Pending work may all be backoff-parked: wait out the
            # earliest retry eligibility instead of spinning.
            delay = self.queue.next_ready_delay()
            if delay:
                time.sleep(min(delay, 0.25))
        self._finish_report(report)
        self._assert_accounting()
        return report

    def serve_forever(self, poll_s: float = 0.05,
                      idle_exit_s: float | None = None) -> ServeReport:
        """Daemon drain loop: serve until idle for `idle_exit_s`
        (None = only a preemption notice stops it)."""
        report = ServeReport()
        idle_since = None
        while True:
            # A SIGTERM can land BETWEEN drain passes (the daemon is
            # idle-polling, not mid-batch): notice it here, requeue
            # nothing (nothing was popped), and exit rc 75 — without
            # this check an idle daemon would poll straight through
            # its preemption grace and die to the scheduler's SIGKILL
            # with a clean-looking exit path.
            if self._preempt_requested():
                report.preempted = True
                break
            self.maybe_resize()
            served, preempted = self.drain_once()
            report.served += served
            if preempted:
                report.preempted = True
                break
            if self.queue.depth() == 0:
                now = self._now()
                if idle_since is None:
                    idle_since = now
                elif idle_exit_s is not None \
                        and now - idle_since >= idle_exit_s:
                    break
                time.sleep(poll_s)
            else:
                idle_since = None
                delay = self.queue.next_ready_delay()
                if delay:
                    time.sleep(min(delay, poll_s))
        self._finish_report(report)
        self._assert_accounting()
        return report

    def _assert_accounting(self) -> None:
        """The drain-time terminal-accounting invariant (docs/
        SERVING.md "SLOs and admission"): at a drain boundary nothing
        is in flight, so every submitted ticket must be terminally
        accounted or still queued — a leak here means some ticket
        vanished into 'running' forever, the exact bug class the
        invariant exists to catch loudly."""
        problems = self.queue.check_accounting(in_flight=0)
        if problems:
            raise RuntimeError(
                "serve accounting invariant violated at drain: "
                + "; ".join(problems)
            )

    def _finish_report(self, report: ServeReport) -> None:
        from rocm_mpi_tpu import telemetry
        from rocm_mpi_tpu.telemetry import compiles

        counters = self.queue.counters()
        report.failed = counters["failed"]
        report.requeued = counters["requeued"]
        report.rejected = counters["rejected"]
        report.expired = counters["expired"]
        report.quarantined = counters["quarantined"]
        report.bins = dict(self._stats)
        report.programs = sorted(self._programs)
        report.elastic = list(self._elastic)
        report.pipeline = self.pipeline_stats()
        c = self._continuous
        if c["batches"]:
            report.continuous = {
                "segments": max(1, int(self.config.segments)),
                "batches": c["batches"],
                "segments_run": c["segments_run"],
                "swaps_in": c["swaps_in"],
                "swaps_out": c["swaps_out"],
                "occupancy": (
                    round(c["occ_num"] / c["occ_den"], 6)
                    if c["occ_den"] else 0.0
                ),
            }
        snap = compiles.snapshot()
        report.compiles = {
            "total": snap["totals"]["backend_compiles"],
            "steady_state": snap["steady_recompiles"],
        }
        if telemetry.enabled():
            telemetry.gauge("serve.bins", float(len(report.bins)))
            telemetry.gauge("serve.programs", float(report.n_programs))
            if report.bins:
                telemetry.gauge(
                    "serve.occupancy",
                    # The continuous drain's step-weighted occupancy is
                    # the truthful lifetime number when it ran — the
                    # classic min-over-bins slot occupancy otherwise.
                    report.continuous["occupancy"]
                    if report.continuous and c["occ_den"]
                    else min(
                        st.occupancy for st in report.bins.values()
                    ),
                )
                telemetry.gauge(
                    "serve.padding_waste",
                    max(st.padding_waste for st in report.bins.values()),
                )
            compiles.emit_gauges()

    def write_manifest(self, path) -> dict:
        """Bank the bin manifest sidecar (atomic; schema-checked by
        lint.sh / `telemetry regress --check-schema`)."""
        report = ServeReport()
        self._finish_report(report)
        # The manifest's lifetime view: everything this service has
        # completed (report.served is per-drain-session), and whether
        # a preemption notice is pending at banking time — the rc-75
        # exit path banks the manifest, and a manifest that said
        # preempted=False there would misreport the daemon's exit.
        report.served = self.queue.counters()["completed"]
        report.preempted = self._preempt_requested()
        doc = report.manifest_doc(queue_counters=self.queue.counters())
        _bins.write_manifest(path, doc)
        return doc
