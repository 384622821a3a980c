"""Halo exchange (D2) — `update_halo!` re-designed for the TPU.

Reference behavior: each rank's local array overlaps its cartesian neighbors
by 2 cells; `update_halo!(T)` refreshes the overlap with MPI point-to-point,
GPU-direct when IGG_ROCMAWARE_MPI=1, staged through host memory when =0
(/root/reference/scripts/diffusion_2D_ap.jl:42, scripts/setenv.sh:11-18).

TPU-native design: shards are non-overlapping; ghost cells are *transient*.
Inside `shard_map`, `exchange_halo(u, grid)` pads every sharded axis of the
local block with `width` cells fetched from the cartesian neighbors via
`lax.ppermute` — which XLA lowers to collective-permute riding the ICI, the
interconnect analog of GPU-direct MPI (no host staging, SURVEY.md §2.4).
Axes are exchanged sequentially, so the second axis sends slices that
include the first axis's already-received ghosts and corner ghosts arrive
from diagonal neighbors for free (the standard two-stage corner trick).

Traffic discipline (the A_eff accounting the perf gate audits,
docs/PERF.md): the send/receive half, `exchange_slabs`, reads only the
block's edges and returns the received ghost slabs; nothing is staged.
A caller that reads the whole padded block (the `shard` and `perf`
steps, the deep-halo sweeps, the probes) takes `exchange_halo`: ONE
preallocated buffer — `place_core` writes the block into the
ghost-ringed buffer once, `exchange_into` then writes each received slab
in place with `lax.dynamic_update_slice`. A caller that reads only thin
windows of it (the overlap step's boundary slabs) takes the slabs
themselves and assembles each window with `ghosted_window`, so it
stages the windows' bytes, not the shard's.

Non-periodic boundaries: ppermute entries are omitted at the domain edge, so
edge ghosts arrive as zeros. Their values are never *used*: the global
boundary cells they would feed are Dirichlet-fixed and masked out by
`global_boundary_mask` (the reference equivalently never updates
`T[1,:]`-type cells — ap.jl:41 updates the interior view only).

The host-staged fallback (`HostStagedStepper`, the IGG_ROCMAWARE_MPI=0
analog) lives here too: a pure-numpy step driver usable as a transport-free
correctness oracle — "is it the device collective or my math?" (SURVEY.md §4.4).
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from rocm_mpi_tpu import telemetry
from rocm_mpi_tpu.parallel import wire
from rocm_mpi_tpu.parallel.mesh import GlobalGrid
from rocm_mpi_tpu.utils import compat


def exchange_nbytes(local_shape, itemsize: int, width: int = 1,
                    axes=None, wire_mode: str = "f32") -> int:
    """Bytes an interior device SENDS per `exchange_halo` call: two
    width-`width` edge slices per exchanged axis, sized against the
    block as it grows (the sequential corner trick means axis k's slices
    include axis <k's padding), at the ON-WIRE itemsize of `wire_mode`
    (parallel/wire.py: bf16 ships 2-byte elements, the int8 modes 1-byte
    plus a per-slab scale scalar; "f32" means the state dtype verbatim).
    Edge-of-domain devices send less (their ppermute entries are
    omitted); the interior figure is the per-device capacity number
    telemetry wants — reporting the state itemsize for a reduced-
    precision exchange would corrupt the `halo bytes/s` aggregate and
    any regress baseline built on it."""
    return wire.exchange_wire_nbytes(
        local_shape, int(itemsize), width, axes, wire_mode
    )


def neighbor_shift(x, axis_name: str, direction: int):
    """Send `x` to the neighbor `direction` steps up the mesh axis
    (non-periodic: edge devices receive zeros)."""
    from rocm_mpi_tpu.utils.compat import axis_size

    n = axis_size(axis_name)
    if direction == +1:
        perm = [(i, i + 1) for i in range(n - 1)]
    elif direction == -1:
        perm = [(i + 1, i) for i in range(n - 1)]
    else:
        raise ValueError("direction must be +1 or -1")
    return lax.ppermute(x, axis_name, perm)


def place_core(u, width: int = 1, axes=None):
    """Preallocate the ghost-ringed buffer and write `u` into its core.

    Returns a zero buffer grown by 2*width along each `axes` entry with
    `u` placed at offset `width` there — ONE staging write, the only
    whole-block copy `exchange_halo` pays. Edge-of-domain ghost
    slices that no neighbor overwrites stay zero, which IS the framework's
    zero-ghost boundary convention. Its ops carry layer="exchange"
    (docs/TELEMETRY.md "Device-side names").
    """
    axes = set(range(u.ndim) if axes is None else axes)
    shape = tuple(
        n + 2 * width if a in axes else n for a, n in enumerate(u.shape)
    )
    start = tuple(width if a in axes else 0 for a in range(u.ndim))
    with compat.set_xla_metadata(layer="exchange"):
        return lax.dynamic_update_slice(jnp.zeros(shape, u.dtype), u, start)


def exchange_into(buf, grid: GlobalGrid, width: int = 1, axes=None,
                  wire_mode: str = "f32", wire_state=None):
    """Fill the ghost ring of a padded buffer with neighbor slices
    (inside shard_map). `buf` is a `place_core`-shaped buffer: core at
    offset `width` along every exchanged axis.

    The send/receive half is `exchange_slabs` on the buffer's core; each
    received slab then lands via one `lax.dynamic_update_slice` in a
    single-consumer chain, which XLA's buffer assignment executes fully
    in place (re-slicing the updated buffer for later sends would force
    it to materialize a defensive whole-buffer copy). Slabs land in axis
    order, so a corner holds the later axis's slab. Non-periodic
    boundaries: ppermute entries are omitted at the domain edge, so edge
    devices receive zeros — harmless writes into the zero ring.

    `wire_mode` and `wire_state` are `exchange_slabs`'; the stateful
    modes return `(buf, new_state)`. Every op it traces, the codec's and
    the ppermutes included, carries layer="exchange" (docs/TELEMETRY.md
    "Device-side names").
    """
    axes = tuple(range(grid.ndim) if axes is None else axes)
    width = int(width)
    core = tuple(
        slice(width, n - width) if a in axes else slice(None)
        for a, n in enumerate(buf.shape)
    )
    with compat.set_xla_metadata(layer="exchange"):
        got = _exchange_slabs(buf[core], grid, width, axes, wire_mode,
                              wire_state)
        slabs = got[0] if wire.is_stateful(wire_mode) else got
        for i, ax in enumerate(axes):
            n = buf.shape[ax] - 2 * width
            for side, off in (("lo", 0), ("hi", n + width)):
                starts = tuple(
                    off if a == ax
                    else 0 if a in axes[:i] or a not in axes
                    else width
                    for a in range(buf.ndim)
                )
                buf = lax.dynamic_update_slice(buf, slabs[(ax, side)],
                                               starts)
    if wire.is_stateful(wire_mode):
        return buf, got[1]
    return buf


def exchange_slabs(u, grid: GlobalGrid, width: int = 1, axes=None,
                   wire_mode: str = "f32", wire_state=None):
    """The ghost slabs the cartesian neighbors send the local block `u`
    (inside shard_map), keyed by `(axis, side)`, with no ghost-ringed
    copy of `u`: for a caller that reads the ghosts of a few thin
    windows (the overlap step's slabs, `ghosted_window`), not the whole
    padded block.

    Axes are exchanged in order, and axis k's sends span the ghosts of
    axes < k (the two-stage corner trick) and only the core of axes > k,
    so the wire bytes match `exchange_nbytes` exactly. Slab `(ax, side)`
    is `width` thick along `ax`, spans core + 2*width along the axes
    exchanged before `ax` and the core along the rest: the `side` ghost
    slice of `exchange_halo(u)` along `ax`, whose ends are the corner
    ghosts. The corner extensions of a send are assembled from the
    RECEIVED slabs of earlier axes — tiny width×width concatenates.
    Domain-edge devices receive zeros (the zero-ghost convention).

    `wire_mode` selects the on-wire slab representation (the
    wire-precision plane, parallel/wire.py): "f32" ships the slab
    verbatim; "bf16" downcasts each send and upcasts on receive, BEFORE
    the slab reaches a later axis's corner assembly or the caller (the
    seam only ever consumes decoded, state-dtype slabs). The stateful
    modes ("int8", "int8_delta") additionally take `wire_state` — the
    flat per-slab state tuple `wire.init_exchange_state` builds — and
    the return value becomes `(slabs, new_state)`.

    Every op it traces carries layer="exchange".
    """
    axes = tuple(range(grid.ndim) if axes is None else axes)
    _annotate_exchange("halo.exchange", u.shape, u.dtype, width, axes,
                       wire_mode)
    with compat.set_xla_metadata(layer="exchange"):
        return _exchange_slabs(u, grid, int(width), axes, wire_mode,
                               wire_state)


def _exchange_slabs(u, grid, width, axes, wire_mode, wire_state):
    ndim = u.ndim
    stateful = wire.is_stateful(wire_mode)
    if stateful and wire_state is None:
        raise ValueError(
            f"wire_mode {wire_mode!r} carries error-feedback state across "
            "exchanges; per-step (stateless) paths support f32/bf16 only — "
            "use the deep-halo schedules (run_deep / --deep), which thread "
            "the state through their sweep carry"
        )
    codec = wire.slab_codec(wire_mode)
    arity = wire.state_arity(wire_mode)
    new_state: list = []
    slab_i = 0

    recv: dict = {}  # (axis, side) -> received slab
    done: list = []
    for ax in axes:
        name = grid.axis_names[ax]
        n = u.shape[ax]

        def send_slab(lo_side):
            # Core edge, extended along each already-exchanged axis with
            # the matching edge pieces of ITS received slabs — at each
            # step the extents line up because recv[(a, ·)] spans full
            # padded extent on axes exchanged before `a` and core extent
            # after (the same invariant this concat establishes).
            edge = slice(0, width) if lo_side else slice(n - width, n)
            sel = tuple(
                edge if a == ax else slice(None) for a in range(ndim)
            )
            piece = u[sel]
            for a in done:
                piece = jnp.concatenate(
                    [recv[(a, "lo")][sel], piece, recv[(a, "hi")][sel]],
                    axis=a,
                )
            return piece

        for side, lo_side, direction in (("lo", False, +1),
                                         ("hi", True, -1)):
            if wire_mode == "f32":
                # Bitwise-identical fast path: no codec ops traced.
                recv[(ax, side)] = neighbor_shift(
                    send_slab(lo_side), name, direction
                )
            else:
                st = tuple(
                    wire_state[slab_i * arity + j] for j in range(arity)
                ) if stateful else ()
                payload, st = codec.send(send_slab(lo_side), st)
                shipped = tuple(
                    neighbor_shift(p, name, direction) for p in payload
                )
                recv[(ax, side)], st = codec.recv(shipped, st, u.dtype)
                new_state.extend(st)
            slab_i += 1
        done.append(ax)
    if stateful:
        return recv, tuple(new_state)
    return recv


def ghosted_window(u, slabs, bounds, width: int = 1):
    """A window of the padded block without the padded block: equal to
    `exchange_halo(u, grid, width)` over core-coordinate ranges `bounds`
    (one `(lo, hi)` per axis, -width <= lo < hi <= n + width), assembled
    from `u` and the `slabs` that `exchange_slabs(u, grid, width)`
    received (every axis exchanged, in order).

    Built from the last axis back: the window's ghost rows along axis k
    come from slab `(k, side)` — which holds the corners with every
    earlier axis, as `exchange_into` places them — and the rest from the
    window narrowed to the core along k. One concatenate per axis the
    window leaves the shard along, so the bytes staged are the window's
    own (`u.size` plays no part). Its ops carry layer="exchange"."""
    width = int(width)

    def build(k, rng):
        if k < 0:
            return u[tuple(slice(lo, hi) for lo, hi in rng)]
        lo, hi = rng[k]
        n = u.shape[k]

        def slab(side, along):
            # Slab (k, side): padded extent before k, core extent after.
            idx = tuple(
                along if a == k
                else slice(r[0] + width, r[1] + width) if a < k
                else slice(*r)
                for a, r in enumerate(rng)
            )
            return slabs[(k, side)][idx]

        inner = rng[:k] + ((max(lo, 0), min(hi, n)),) + rng[k + 1:]
        parts = [build(k - 1, inner)]
        if lo < 0:
            parts.insert(0, slab("lo", slice(lo + width, width)))
        if hi > n:
            parts.append(slab("hi", slice(0, hi - n)))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, k)

    with compat.set_xla_metadata(layer="exchange"):
        return build(u.ndim - 1, tuple(tuple(b) for b in bounds))


def _annotate_exchange(name, block, dtype, width, axes, wire_mode,
                       lanes=None):
    """Trace-time annotation of one exchange: shapes are concrete while
    jax traces, so "this program moves N bytes per exchange" is
    recordable exactly once per compiled program (telemetry.events.
    annotate dedups). `bytes` is the TRUE on-wire figure for the active
    wire mode — a bf16 exchange must never book f32 bytes into the halo
    bytes/s aggregate or a regress baseline — and, for a lane-batched
    exchange, the sum over its `lanes`."""
    if not telemetry.enabled():
        return
    nbytes = exchange_nbytes(block, jnp.dtype(dtype).itemsize, width, axes,
                             wire_mode)
    extra = {} if lanes is None else {"lanes": int(lanes)}
    telemetry.annotate(
        name, **extra,
        bytes=nbytes * (1 if lanes is None else int(lanes)),
        width=width,
        block=tuple(int(n) for n in block),
        wire=wire_mode,
    )


def exchange_halo(u, grid: GlobalGrid, width: int = 1, axes=None,
                  wire_mode: str = "f32", wire_state=None):
    """Pad the local block `u` with neighbor ghost cells (inside shard_map).

    Returns an array grown by 2*width along each exchanged axis. This is the
    `update_halo!(T)` analog: one call per step, all axes
    (diffusion_2D_ap.jl:42). Composition of `place_core` + `exchange_into`
    — one staged copy, ghost slices written in place — for a caller that
    reads the whole padded block; one that reads a few windows of it takes
    `exchange_slabs` and `ghosted_window` instead.

    `wire_mode` selects the on-wire slab precision (exchange_slabs has the
    contract); the stateful modes take/return `wire_state` and the result
    becomes `(padded, new_state)`. The default "f32" traces the exact
    pre-wire-plane program — bitwise identical on every workload.
    """
    axes = tuple(range(grid.ndim) if axes is None else axes)
    _annotate_exchange("halo.exchange", u.shape, u.dtype, width, axes,
                       wire_mode)
    return exchange_into(place_core(u, width, axes), grid, width, axes,
                         wire_mode=wire_mode, wire_state=wire_state)


def _lane_space(bgrid, wire_mode):
    """The space grid of a lane-batched exchange; refuses stateful wire
    modes (their error-feedback state is per-logical-wire, and a
    lane-batched exchange would need a per-lane state plane nothing
    carries yet)."""
    if wire.is_stateful(wire_mode):
        raise ValueError(
            f"wire_mode {wire_mode!r} is stateful; batched exchanges "
            "support the stateless modes (f32/bf16) only"
        )
    return bgrid.space if hasattr(bgrid, "space") else bgrid


def exchange_halo_batched(ub, bgrid, width: int = 1, axes=None,
                          wire_mode: str = "f32"):
    """Per-lane halo exchange of a lane-leading batched block (inside a
    shard_map over a space×batch mesh, docs/SERVING.md): `ub` is the
    local block of `bgrid.spec`-sharded state, shape
    ``(local_batch, *local_space)``, and the exchange is `exchange_halo`
    vmapped over the leading lane axis — the halo collectives stay
    strictly per-space-axis (ppermute's batching rule carries the lane
    dim along each slab, so lane k's ghosts only ever come from lane
    k's spatial neighbors; nothing is permuted over the `batch` axis —
    lanes are separate tenants). Stateless wire modes only (f32/bf16)."""
    space = _lane_space(bgrid, wire_mode)
    _annotate_exchange("halo.exchange.batched", ub.shape[1:], ub.dtype,
                       width, axes, wire_mode, lanes=ub.shape[0])
    return jax.vmap(
        lambda u: exchange_into(
            place_core(u, width, axes), space, width, axes,
            wire_mode=wire_mode,
        )
    )(ub)


def exchange_slabs_batched(ub, bgrid, width: int = 1,
                           wire_mode: str = "f32"):
    """`exchange_slabs` vmapped over the leading lane axis of a batched
    block, as `exchange_halo_batched` vmaps `exchange_halo`: every slab
    gains the lane axis in front, and the collectives stay per-space-axis.
    Stateless wire modes only (f32/bf16)."""
    space = _lane_space(bgrid, wire_mode)
    _annotate_exchange("halo.exchange.batched", ub.shape[1:], ub.dtype,
                       width, None, wire_mode, lanes=ub.shape[0])
    with compat.set_xla_metadata(layer="exchange"):
        return jax.vmap(
            lambda u: _exchange_slabs(u, space, int(width),
                                      tuple(range(space.ndim)), wire_mode,
                                      None)
        )(ub)


class HaloProgram(NamedTuple):
    """A halo exchange family bound to one decomposition: the grid it was
    derived for, the ghost width, the bound `exchange(u)` closure (inside
    shard_map), and `nbytes(itemsize)` — the per-interior-device wire
    bytes of one call (the telemetry/traffic accounting figure, at the
    program's wire mode)."""

    grid: GlobalGrid
    width: int
    exchange: Callable
    nbytes: Callable
    wire_mode: str = "f32"


def build_for_mesh(grid: GlobalGrid, width: int = 1,
                   wire_mode: str = "f32") -> HaloProgram:
    """Bind the halo exchange family to `grid` — the derivation
    `rebuild_for_mesh` re-runs when the decomposition changes."""
    wire.validate_mode(wire_mode)
    return HaloProgram(
        grid=grid,
        width=width,
        exchange=lambda u, axes=None: exchange_halo(
            u, grid, width, axes, wire_mode=wire_mode
        ),
        nbytes=lambda itemsize, axes=None: exchange_nbytes(
            grid.local_shape, itemsize, width, axes, wire_mode
        ),
        wire_mode=wire_mode,
    )


def rebuild_for_mesh(
    program_or_grid, dims=None, devices=None, width: int | None = None
) -> HaloProgram:
    """Re-derive the halo programs for a NEW decomposition of the same
    global domain (docs/RESILIENCE.md "Elastic recovery"): an elastic
    resume lands a checkpoint on a different mesh, and every per-mesh
    derived quantity — neighbor structure, ghost slice shapes, wire
    bytes, the boundary-mask geometry the exchange's zero-ghost
    convention leans on — must come from the NEW dims, never be reused
    from the old. Accepts a HaloProgram (rebuilds its grid and width) or
    a GlobalGrid; `dims`/`devices` follow mesh.rebuild_for_mesh (default:
    the plan_dims sub-mesh over the current devices)."""
    from rocm_mpi_tpu.parallel import mesh as _mesh

    if isinstance(program_or_grid, HaloProgram):
        old_grid = program_or_grid.grid
        width = program_or_grid.width if width is None else width
    else:
        old_grid = program_or_grid
        width = 1 if width is None else width
    wire_mode = (
        program_or_grid.wire_mode
        if isinstance(program_or_grid, HaloProgram) else "f32"
    )
    new_grid = _mesh.rebuild_for_mesh(old_grid, dims=dims, devices=devices)
    if any(width > ln for ln in new_grid.local_shape):
        raise ValueError(
            f"halo width {width} exceeds a local shard extent "
            f"{new_grid.local_shape} on the rebuilt mesh {new_grid.dims}"
        )
    return build_for_mesh(new_grid, width, wire_mode=wire_mode)


def global_boundary_mask(grid: GlobalGrid, dtype=bool):
    """Per-shard mask of global-domain boundary cells (inside shard_map).

    True where the cell lies on the global boundary — the cells the
    reference never updates (interior-only update, ap.jl:41). Uses
    `lax.axis_index` to locate the shard in the cartesian topology.
    """
    local = grid.local_shape
    mask = jnp.zeros(local, dtype=bool)
    for ax, name in enumerate(grid.axis_names):
        ln = local[ax]
        n_g = grid.global_shape[ax]
        gidx = lax.axis_index(name) * ln + lax.broadcasted_iota(
            jnp.int32, local, ax
        )
        mask = mask | (gidx == 0) | (gidx == n_g - 1)
    return mask.astype(dtype) if dtype is not bool else mask


class HostStagedStepper:
    """Pure-numpy diffusion stepper with explicitly host-staged halos.

    The IGG_ROCMAWARE_MPI=0 analog (README.md:25-35): every step, each
    shard's boundary slices are copied through host memory to its neighbors'
    ghost buffers, then each shard is updated independently. Device-free by
    construction, so any disagreement with the `shard` variant isolates the
    device collective path — the same bisection affordance the reference's
    toggle provides. Debug/oracle use only; O(host-memory-bandwidth).
    """

    def __init__(
        self, grid: GlobalGrid, lam: float, dt: float,
        use_native: bool | None = None, wire_mode: str = "f32",
    ):
        self.grid = grid
        self.lam = lam
        self.dt = dt
        # The wire-precision oracle twin: apply the numpy wire codec to
        # every ghost slab copied between shards, with the error-feedback
        # / delta state held per logical wire in the codec itself (this
        # stepper is the oracle world's one stateful object). "f32" is
        # the identity — the classic oracle, bit for bit.
        self.wire_mode = wire.validate_mode(wire_mode)
        self._codec = (
            wire.NumpyWireCodec(wire_mode) if wire_mode != "f32" else None
        )
        if use_native is None:
            from rocm_mpi_tpu.parallel import native_halo

            use_native = native_halo.available() and grid.ndim <= 3
        # The native C++ engine stages full-precision ghosts only; any
        # reduced-precision wire must run the numpy path.
        self.use_native = use_native and wire_mode == "f32"

    def _shard_slices(self, coords) -> tuple[slice, ...]:
        local = self.grid.local_shape
        return tuple(
            slice(c * ln, (c + 1) * ln) for c, ln in zip(coords, local)
        )

    def step(self, T: np.ndarray, Cp: np.ndarray) -> np.ndarray:
        """One host-staged step. Dispatches to the native C++ engine
        (native/halostage.cpp, bit-identical, multithreaded) when built;
        falls back to the readable numpy implementation below."""
        if (
            self.use_native
            and T.dtype == np.float64
            and Cp.dtype == np.float64
        ):
            from rocm_mpi_tpu.parallel import native_halo

            return native_halo.host_staged_step(
                T, Cp, self.grid.dims, self.grid.spacing, self.lam, self.dt
            )
        return self.step_python(T, Cp)

    def step_python(self, T: np.ndarray, Cp: np.ndarray) -> np.ndarray:
        grid = self.grid
        ndim = grid.ndim
        local = grid.local_shape
        spacing = grid.spacing

        # Phase 1 — host-staged halo exchange: every shard's padded block is
        # assembled in host memory, ghost slices read from neighbor shards
        # (zeros at the domain edge, as in exchange_halo). The two phases
        # here are REAL host-level seams — the one stepper whose halo and
        # interior costs telemetry can time directly rather than probe.
        padded = {}
        with telemetry.span("halo.host_staged", phase="halo") as hsp:
            copied = 0
            for coords in np.ndindex(*grid.dims):
                block = np.zeros(
                    tuple(ln + 2 for ln in local), dtype=T.dtype
                )
                inner = tuple(slice(1, -1) for _ in range(ndim))
                core = self._shard_slices(coords)
                block[inner] = T[core]
                for ax in range(ndim):
                    for side, nb_off in (("lo", -1), ("hi", +1)):
                        nb = list(coords)
                        nb[ax] += nb_off
                        if not 0 <= nb[ax] < grid.dims[ax]:
                            continue  # domain edge: ghost stays zero (unused)
                        nb_core = self._shard_slices(nb)
                        src = list(nb_core)
                        dst = [slice(1, 1 + ln) for ln in local]
                        if nb_off == -1:  # ghost row 0 <- neighbor's last row
                            src[ax] = slice(
                                nb_core[ax].stop - 1, nb_core[ax].stop
                            )
                            dst[ax] = slice(0, 1)
                        else:  # last ghost row <- neighbor's first row
                            src[ax] = slice(
                                nb_core[ax].start, nb_core[ax].start + 1
                            )
                            dst[ax] = slice(local[ax] + 1, local[ax] + 2)
                        ghost = T[tuple(src)]
                        if self._codec is not None:
                            # One logical wire per (receiver, axis,
                            # side): the codec's residual/reconstruction
                            # state persists across steps under this key.
                            ghost = self._codec.apply(
                                (coords, ax, side), ghost
                            )
                        block[tuple(dst)] = ghost
                        copied += wire.wire_slab_nbytes(
                            ghost.size, T.dtype.itemsize, self.wire_mode
                        )
                padded[coords] = block
            hsp.set(bytes=copied)

        # Phase 2 — independent per-shard update (fused stencil), global
        # boundary cells Dirichlet-fixed. Multiply by the precomputed
        # reciprocal (not divide) so results are bit-identical to the native
        # engine (native/halostage.cpp) and the Pallas kernels.
        inv_d2 = tuple(1.0 / (d * d) for d in spacing)
        out = np.array(T, copy=True)
        with telemetry.span("interior.host_staged", phase="interior"):
            for coords, block in padded.items():
                inner = tuple(slice(1, -1) for _ in range(ndim))
                core = self._shard_slices(coords)
                lap = np.zeros(local, dtype=T.dtype)
                for ax in range(ndim):
                    hi_s = tuple(
                        slice(2, None) if a == ax else slice(1, -1)
                        for a in range(ndim)
                    )
                    lo_s = tuple(
                        slice(None, -2) if a == ax else slice(1, -1)
                        for a in range(ndim)
                    )
                    lap += (
                        block[hi_s] - 2.0 * block[inner] + block[lo_s]
                    ) * inv_d2[ax]
                new = T[core] + self.dt * self.lam / Cp[core] * lap
                # Dirichlet mask: global boundary cells keep old values.
                keep = np.zeros(local, dtype=bool)
                for ax in range(ndim):
                    gidx = coords[ax] * local[ax] + np.arange(local[ax])
                    edge = (gidx == 0) | (gidx == grid.global_shape[ax] - 1)
                    sh = [1] * ndim
                    sh[ax] = local[ax]
                    keep |= edge.reshape(sh)
                out[core] = np.where(keep, T[core], new)
        return out

    def run(self, T: np.ndarray, Cp: np.ndarray, nt: int) -> np.ndarray:
        # The one per-step HOST loop in the framework, so it feeds the
        # health plane directly: a "step" fault point (deterministic
        # drills) and a flight-recorder step bump per step — the halo /
        # interior spans in step_python already land in the flight ring
        # via the events tap. Both are one-global-read no-ops when the
        # recorder / fault plan are off.
        from rocm_mpi_tpu.resilience import faults
        from rocm_mpi_tpu.telemetry import flight

        for i in range(nt):
            faults.fault_point("step", step=i + 1)
            # Additive: the recorder's step counter is process-global,
            # and a second .run() restarting at 1 would be masked by
            # its monotonic guard.
            flight.progress(step_inc=1)
            T = self.step(T, Cp)
        return T
