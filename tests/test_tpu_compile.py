"""Main-path kernels compiled for a described TPU v5e, no chip needed.

The CPU suite runs Pallas in interpret mode, which cannot see what the
TPU compiler refuses: tiling, scoped-VMEM overruns, fusion-pass aborts.
Each test here compiles one main-path program at its real size with
`interpret=False` against a `v5e:2x2` topology description and asserts
that the kernel is in the compiled program (`tpu_custom_call`).

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, so every xdist
worker must collect the same tests and only the one running this file
loads it. Nothing here runs; compile times are ~1-2 s each except the
temporal-blocked sweep (~8 s).
"""

import collections
import contextlib
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import rocm_mpi_tpu.ops.pallas_kernels as pk

N = 12288  # diffusion_2D_perf's per-rank geometry (fact=12)
SPACING = (10.0 / N, 10.0 / N)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-chip compile is written to the persistent cache but
    # cannot be read back without the chip: keep it out of the cache.
    # x64 off as on the chip (the CPU harness turns it on for the f64
    # oracle; Mosaic refuses 64-bit index maps).
    saved = {k: getattr(jax.config, k) for k in
             ("jax_enable_compilation_cache", "jax_enable_x64")}
    for k in saved:
        jax.config.update(k, False)
    compilation_cache.reset_cache()
    yield desc
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _kernel_text(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


def test_masked_step_12288(one_chip):
    a = _sds((N, N), one_chip)
    _kernel_text(
        lambda T, Cm: pk.masked_step(T, Cm, SPACING, interpret=False), a, a
    )


def test_fused_step_cm_striped_12288(one_chip):
    _kernel_text(
        lambda Tp, Cm: pk.fused_step_cm(Tp, Cm, SPACING, interpret=False),
        _sds((N + 2, N + 2), one_chip), _sds((N, N), one_chip),
    )


def test_multi_step_cm_hbm_12288_k8(one_chip):
    a = _sds((N, N), one_chip)
    _kernel_text(
        lambda T, Cm: pk.multi_step_cm_hbm(T, Cm, SPACING, 8,
                                           interpret=False),
        a, a,
    )


def test_fused_multi_step_252_chunk16(one_chip):
    a = _sds((252, 252), one_chip)
    sp = (10.0 / 252, 10.0 / 252)
    _kernel_text(
        lambda T, Cp: pk.fused_multi_step(T, Cp, 1.0, sp[0] ** 2 / 4.1, sp,
                                          64, chunk=16, interpret=False),
        a, a,
    )


@pytest.mark.parametrize("variant,n_local", [
    ("perf", N),
    # The hide step at both 2x2 geometries chip_smoke.py runs: the
    # compiler aborted on its slab splice, and the 12224x4 slab
    # overran scoped VMEM, before PR 21.
    ("hide", 126),
    ("hide", N),
])
def test_sharded_step_2x2(topo, monkeypatch, variant, n_local):
    from rocm_mpi_tpu.config import DiffusionConfig
    from rocm_mpi_tpu.models import HeatDiffusion

    # The model picks interpret mode from the (CPU) default backend;
    # the described chip needs the compiled kernels.
    monkeypatch.setattr(pk, "_interpret_default", lambda: False)
    n = 2 * n_local
    model = HeatDiffusion(
        DiffusionConfig(global_shape=(n, n), dtype="f32", dims=(2, 2)),
        devices=topo.devices,
    )
    step, _ = model.prepared_step_fn(variant, donate=True)
    a = _sds((n, n), model.grid.sharding)
    text = step.lower(a, a).compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text


# ---- device-side names: kernel names and layer tags ---------------------

_HEAD = re.compile(r"^(ENTRY )?%([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^(?:ROOT )?%([\w.\-]+) = (.*)$")
_STRUCTURAL = {"parameter", "constant", "get-tuple-element", "tuple",
               "bitcast", "copy", "copy-start", "copy-done", "while",
               "opt-barrier", "partition-id", "replica-id"}
# What XLA's HLO importer, run by the Shardy pass on programs
# partitioned over chips, re-creates without frontend_attributes on
# jax 0.9 (tests/test_device_tags.py keeps the finding as an xfail): the
# data movement of the exchange and the splice may lose its layer tag
# there, and nothing else may.
_IMPORT_DROPS = {"slice", "pad", "dynamic-update-slice", "dynamic-slice",
                 "concatenate", "reverse", "iota"}


def _opcode(rhs: str) -> str:
    """The opcode of an instruction's right-hand side (shape first)."""
    i = 0
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        i += 1
    else:
        i = rhs.find(" ")
    return rhs[i:].lstrip().split("(", 1)[0]


def _program(text: str) -> dict:
    """{computation: [(name, opcode, shape, layer, region, line)]} of a
    compiled module's text, with `fused` and `bodies` (the while bodies,
    nested ones included) as sets of computation names."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = _HEAD.match(line)
        if head:
            cur = comps.setdefault(head.group(2), [])
            continue
        m = _INSTR.match(line.strip())
        if m and cur is not None:
            rhs = m.group(2)
            tags = dict(re.findall(r'\b(layer|region)="(\w+)"', rhs))
            cur.append((m.group(1), _opcode(rhs), rhs.split(" ", 1)[0],
                        tags.get("layer"), tags.get("region"), rhs))
    bodies = {b for ops in comps.values() for *_, rhs in ops
              for b in re.findall(r"\bbody=%([\w.\-]+)", rhs)}
    fused = {f for ops in comps.values() for *_, rhs in ops
             if " fusion(" in rhs
             for f in re.findall(r"\bcalls=%([\w.\-]+)", rhs)}
    return {"comps": comps, "bodies": bodies, "fused": fused}


def _kernels(prog) -> list:
    return [op for ops in prog["comps"].values() for op in ops
            if 'custom_call_target="tpu_custom_call"' in op[5]]


def _compiled(topo, mp, variant, n_local, dims=(2, 2), shardy=True):
    """The compiled scan driver (the benchmark's program) for the
    described chips: `variant` at `n_local`² a chip on a `dims` mesh,
    partitioned by Shardy (what runs) or GSPMD."""
    from rocm_mpi_tpu.config import DiffusionConfig
    from rocm_mpi_tpu.models import HeatDiffusion

    mp.setattr(pk, "_interpret_default", lambda: False)
    n = dims[0] * n_local
    devices = topo.devices[: dims[0] * dims[1]]
    model = HeatDiffusion(
        DiffusionConfig(global_shape=(n, n), dtype="f32", dims=dims,
                        nt=100, warmup=10),
        devices=devices,
    )
    advance, _ = model.scan_advance_fn(variant)
    a = _sds((n, n), model.grid.sharding)
    saved = jax.config.jax_use_shardy_partitioner
    jax.config.update("jax_use_shardy_partitioner", shardy)
    try:
        return advance.lower(a, a, 100).compile().as_text()
    finally:
        jax.config.update("jax_use_shardy_partitioner", saved)


CELLS = {"perf": dict(variant="perf", n_local=1024, dims=(1, 1)),
         "perf-vmem": dict(variant="perf", n_local=512, dims=(1, 1)),
         "hide": dict(variant="hide", n_local=126)}


@pytest.fixture(scope="module")
def programs(topo):
    """program(cell, shardy=True, tagged=True): the parsed compiled
    program, each compiled once for the module. Untagged means
    compat.set_xla_metadata made a no-op."""
    from rocm_mpi_tpu.utils import compat

    cache = {}

    def program(cell, shardy=True, tagged=True):
        key = (cell, shardy, tagged)
        if key not in cache:
            with pytest.MonkeyPatch.context() as mp:
                if not tagged:
                    mp.setattr(compat, "set_xla_metadata",
                               lambda **names: contextlib.nullcontext())
                cache[key] = _program(
                    _compiled(topo, mp, **CELLS[cell], shardy=shardy))
        return cache[key]

    return program


def _untagged_in_bodies(prog) -> list:
    """Loop-body ops that are no kernel, no structural op and no loop
    control, and carry no layer tag."""
    out = []
    for comp in prog["bodies"]:
        for op in prog["comps"][comp]:
            name, opcode, _, layer, _, rhs = op
            if opcode in _STRUCTURAL or layer is not None:
                continue
            if 'custom_call_target="tpu_custom_call"' in rhs:
                continue
            out.append(op)
    return out


@pytest.mark.parametrize("cell, kernel", [("perf", "masked_step"),
                                          ("perf-vmem", "multi_step_cm")])
def test_perf_scan_names_its_kernel(programs, cell, kernel):
    """A field past the VMEM budget (the benchmark's) takes masked_step's
    striped sweep, named after it; one that fits takes the kernel
    masked_step delegates to, which keeps its own function's name."""
    assert {op[0].rsplit(".", 1)[0] for op in _kernels(programs(cell))} \
        == {kernel}


def test_hide_step_names_kernels_and_regions(programs):
    """The interior is one whole-shard `masked_step`, which launches
    `multi_step_cm` for a 126² shard (it fits VMEM); the four slabs are
    `fused_step_cm`. The scan driver takes two steps an iteration, so
    each is there twice."""
    kernels = _kernels(programs("hide"))
    assert sorted((op[4], op[0].rsplit(".", 1)[0]) for op in kernels) == \
        [("interior", "multi_step_cm")] * 2 + [("slab", "fused_step_cm")] * 8
    assert all(op[3] == "splice" for op in kernels)
    assert not [op for op in kernels
                if re.match(r"(closed_call|shard_map)\b", op[0])]


def test_perf_loop_body_is_tagged(programs):
    assert _untagged_in_bodies(programs("perf")) == []


@pytest.mark.parametrize("shardy", [False, True], ids=["gspmd", "shardy"])
def test_hide_loop_body_is_tagged(programs, shardy):
    """Every compute op of the hide step names its layer. Compiled as
    the program runs (Shardy), only the data movement XLA's importer
    re-creates may lose the tag; partitioned by GSPMD, which compiles the
    same ops (test below), every op keeps it."""
    prog = programs("hide", shardy=shardy)
    untagged = _untagged_in_bodies(prog)
    if not shardy:
        assert untagged == []
        return
    for name, opcode, *_, rhs in untagged:
        if opcode == "fusion":
            inner = {op[1] for f in re.findall(r"calls=%([\w.\-]+)", rhs)
                     for op in prog["comps"][f]}
            assert inner & _IMPORT_DROPS, (name, inner)
        else:
            assert opcode in _IMPORT_DROPS, (name, opcode)


def _mixed_fusions(prog) -> list:
    """Fusions whose fused ops carry two different layer tags."""
    return sorted(
        (f, tags) for f in prog["fused"]
        for tags in [{op[3] for op in prog["comps"][f] if op[3]}]
        if len(tags) > 1)


@pytest.mark.parametrize("shardy", [False, True], ids=["gspmd", "shardy"])
def test_hide_step_fuses_no_two_layers(programs, shardy):
    """A fusion of two layers' ops would charge one layer with the
    other's time."""
    assert _mixed_fusions(programs("hide", shardy=shardy)) == []


def _ops_signature(prog):
    """The multiset of (opcode, shape) over every computation, and the
    number of fusions."""
    ops = collections.Counter(
        (op[1], op[2]) for comp in prog["comps"].values() for op in comp)
    return ops, len(prog["fused"])


@pytest.mark.parametrize("cell", ["perf", "hide"])
def test_tags_change_no_op(programs, cell):
    """The tags are metadata: the program compiled without them
    (compat.set_xla_metadata a no-op) has the same ops, shapes and
    fusions."""
    plain = programs(cell, tagged=False)
    assert not any(op[3] for comp in plain["comps"].values()
                   for op in comp)
    assert _ops_signature(programs(cell)) == _ops_signature(plain)


def test_partitioners_compile_the_same_hide_step(programs):
    """Shardy (what runs) and GSPMD (which keeps every tag) compile the
    hide step to the same ops, so a GSPMD-compiled trace splits the
    data movement of the program that runs."""
    assert _ops_signature(programs("hide")) == \
        _ops_signature(programs("hide", shardy=False))


def _loop_ops(prog) -> list:
    """(computation, op) of every op in the while bodies and in the
    fusions they call, transitively."""
    seen, todo, out = set(), list(prog["bodies"]), []
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in prog["comps"]:
            continue
        seen.add(comp)
        for op in prog["comps"][comp]:
            out.append((comp, op))
            todo += re.findall(r"\bcalls=%([\w.\-]+)", op[5])
    return out


def _elements(shape: str) -> int:
    dims = re.match(r"\w+\[([\d,]*)\]", shape).group(1)
    return math.prod(int(d) for d in dims.split(",") if d)


def _whole_field_moves(loop, n) -> collections.Counter:
    """Slices, pads, buffer writes of an update, and copies (by layout)
    of half the `n`² field or more among the loop ops `loop`."""
    half = n * n // 2
    shapes = {(comp, op[0]): op[2] for comp, op in loop}
    big = collections.Counter()
    for comp, (name, opcode, shape, *_, rhs) in loop:
        if opcode in ("slice", "pad") and _elements(shape) >= half:
            big[opcode] += 1
        elif opcode == "dynamic-update-slice":
            operands = rhs.split(opcode + "(", 1)[1]
            update = re.findall(r"%([\w.\-]+)", operands)[1]
            if _elements(shapes[(comp, update)]) >= half:
                big["dynamic-update-slice"] += 1
        elif opcode == "copy" and _elements(shape) >= half:
            big["copy " + re.search(r"\{([\d,]+)", shape).group(1)] += 1
    return big


def test_perf_scan_steps_twice_an_iteration_with_no_field_copy(topo):
    """The benchmark's 12288² perf program (q = 10): the scan driver's
    loop takes two `masked_step`s an iteration, so the first writes a
    temporary and the second the carry's own buffer, and the loop holds
    no copy of half the field or more. With one step an iteration XLA
    copies each step's result back into the carry, since the kernel
    reads the field it replaces."""
    with pytest.MonkeyPatch.context() as mp:
        prog = _program(_compiled(topo, mp, "perf", N, dims=(1, 1)))
    per_body = collections.Counter(
        comp for comp in prog["bodies"] for op in prog["comps"][comp]
        if op[0].rsplit(".", 1)[0] == "masked_step")
    assert list(per_body.values()) == [2]
    assert _whole_field_moves(_loop_ops(prog), N) == {}


@pytest.mark.parametrize("n_local", [126, N])
def test_hide_step_splices_into_whole_shard_interior(topo, n_local):
    """The 2x2 hide step's interior is one kernel on the whole unpadded
    shard (region="interior"), two steps to an iteration of the scan
    driver, the exchange is still there, and at the benchmark's 12288²
    shard no whole-shard copy is sliced out for the interior or written
    back: no slice, and no buffer write of an update, is half the shard
    or more. The carry copy is gone: the first step of an iteration
    writes a temporary and the second the carry's own buffer, so
    neither interior kernel writes the field it reads. The slabs get
    their ghosts as received slabs: no pad stages the shard into a
    ghost-ringed buffer, and nothing of the padded shape is in the
    loop. One whole-shard copy stays, put in by the compiler: the field
    in the transposed layout (minor-to-major {0,1}) for the first
    step's column edges (the exchange's sends and the column slabs'
    windows); the second step reads its column edges from the
    row-major field."""
    with pytest.MonkeyPatch.context() as mp:
        prog = _program(_compiled(topo, mp, "hide", n_local))
    assert [op[4] for op in _kernels(prog)].count("interior") == 2
    loop = _loop_ops(prog)
    assert any(op[1] == "collective-permute-start" for _, op in loop)
    if n_local != N:
        return
    assert _whole_field_moves(loop, N) == {"copy 0,1": 1}
    assert not [op for _, op in loop
                if op[2].startswith(f"f32[{N + 2},{N + 2}]")]


def _moves_signature(prog):
    """The number of ops and of fusions, and the data-movement opcodes
    counted, over every computation of a compiled program."""
    ops, fusions = _ops_signature(prog)
    moved = collections.Counter()
    for (opcode, _), k in ops.items():
        if opcode in ("pad", "dynamic-update-slice", "slice",
                      "concatenate", "copy", "collective-permute-start"):
            moved[opcode] += k
    return sum(ops.values()), fusions, dict(moved)


@pytest.fixture(scope="module")
def serve_program(topo):
    """The serving cell's program, 8 lanes of 1024² on one chip: the
    batched advance of variant `shard`."""
    from rocm_mpi_tpu.config import DiffusionConfig
    from rocm_mpi_tpu.models import HeatDiffusion

    n = 1024
    model = HeatDiffusion(
        DiffusionConfig(global_shape=(n, n), dtype="f32", dims=(1, 1)),
        devices=topo.devices[:1],
    )
    advance, bg = model.batched_advance_fn(batch=8, variant="shard",
                                           devices=topo.devices[:1])
    tb = jax.ShapeDtypeStruct((8, n, n), jnp.float32, sharding=bg.sharding)
    cp = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=bg.aux_sharding)
    steps = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=bg.batch_sharding)
    return _program(advance.lower(tb, cp, steps, 8).compile().as_text())


@pytest.mark.parametrize("program,want", [
    ("serve", (213, 17, {"pad": 3, "dynamic-update-slice": 4, "slice": 16,
                         "concatenate": 2, "copy": 8,
                         "collective-permute-start": 4})),
    ("perf-2x2", (228, 12, {"pad": 2, "dynamic-update-slice": 8,
                            "slice": 16, "concatenate": 4, "copy": 10,
                            "collective-permute-start": 8})),
])
def test_padded_exchange_programs_unchanged(topo, serve_program, program,
                                            want):
    """The callers that read the whole padded block keep `place_core` +
    `exchange_into`, and compile to the program they compiled to before
    the overlap step took its ghosts as slabs: the serving cell's
    batched `shard` advance, and the 12288² `perf` scan on the 2x2 mesh
    (its two whole-shard pads are its padded kernel operands)."""
    if program == "serve":
        prog = serve_program
    else:
        with pytest.MonkeyPatch.context() as mp:
            prog = _program(_compiled(topo, mp, "perf", N))
    assert _moves_signature(prog) == want


def test_serve_lane_step_fusion_names_the_lane_step(serve_program):
    """The serving cell's program, 8 lanes of 1024² on one chip: XLA
    fuses each lane's stencil into the per-lane freeze select, and that
    fusion names the lane step, not the loop (layer="driver")."""
    prog = serve_program
    stencils = [
        op for comp in prog["bodies"] for op in prog["comps"][comp]
        if op[1] == "fusion" and {"multiply", "select"} <= {
            inner[1] for f in re.findall(r"calls=%([\w.\-]+)", op[5])
            for inner in prog["comps"][f]}]
    assert stencils
    assert {op[3] for op in stencils} == {"lane_step"}


# ---- shallow water's one-chip perf step ---------------------------------


def _swe_perf_program(topo, mp, n):
    """The compiled scan driver of the swe2d-12288-perf cell at n² on
    one described chip: ShallowWater's perf variant, q = 10."""
    from rocm_mpi_tpu.models.swe import SWEConfig, ShallowWater
    from rocm_mpi_tpu.ops import swe_kernels

    mp.setattr(swe_kernels, "_interpret_default", lambda: False)
    model = ShallowWater(
        SWEConfig(global_shape=(n, n), dtype="f32", dims=(1, 1), nt=100,
                  warmup=10),
        devices=topo.devices[:1],
    )
    advance, _ = model.scan_advance_fn("perf")
    a = _sds((n, n), model.grid.sharding)
    return _program(advance.lower(a, (a, a), (a, a), 100).compile()
                    .as_text())


def test_swe_perf_scan_is_one_striped_kernel_a_step(topo):
    """The benchmark's 12288² SWE program: each step is one
    `masked_swe_step` kernel, two to a scan iteration, and nothing of the
    ghost-ringed 12290² shape is left: no exchange pads the state."""
    with pytest.MonkeyPatch.context() as mp:
        prog = _swe_perf_program(topo, mp, N)
    kernels = _kernels(prog)
    assert {op[0].rsplit(".", 1)[0] for op in kernels} == {"masked_swe_step"}
    per_body = collections.Counter(
        comp for comp in prog["bodies"] for op in prog["comps"][comp]
        if op[0].rsplit(".", 1)[0] == "masked_swe_step")
    assert list(per_body.values()) == [2]
    assert not [op for ops in prog["comps"].values() for op in ops
                if f"[{N + 2},{N + 2}]" in op[2]]


def _kernel_operands(op) -> list:
    """[(name, shape)] of a custom call's operands, in order: the names
    from its argument list, the shapes from its layout constraints."""
    args = op[5].split("custom-call(", 1)[1].split(")", 1)[0]
    names = [re.sub(r"/\*.*?\*/", "", a).strip() for a in args.split(",")]
    rest = op[5].split("operand_layout_constraints=", 1)[1]
    shapes = re.findall(r"(\w+\[[\d,]*\])\{[\d,]*\}",
                        rest[:rest.index("}}") + 2])
    assert len(names) == len(shapes)
    return list(zip(names, shapes))


def test_swe_perf_kernel_reads_one_byte_wall_code(topo):
    """Each `masked_swe_step` of the 12288² SWE scan reads the state's
    three fields and exactly one other operand, a 1-byte 12288² wall
    code: no f32 face mask reaches the kernel."""
    with pytest.MonkeyPatch.context() as mp:
        prog = _swe_perf_program(topo, mp, N)
    kernels = _kernels(prog)
    assert kernels
    for op in kernels:
        operands = _kernel_operands(op)
        assert len(operands) == 8
        assert sorted(dict(operands).values()) == (
            [f"f32[{N},{N}]"] * 3 + [f"s8[{N},{N}]"])


def test_swe_perf_scan_packs_the_walls_once_a_call(topo):
    """The wall code is made by one op of the advance's own computation,
    once a call, outside the while loop; no loop body makes one."""
    with pytest.MonkeyPatch.context() as mp:
        prog = _swe_perf_program(topo, mp, N)
    made = [(comp, op[1]) for comp, ops in prog["comps"].items()
            if comp not in prog["fused"] for op in ops
            if op[2].startswith(f"s8[{N},{N}]")
            and op[1] not in ("get-tuple-element", "parameter", "tuple")]
    assert len(made) == 1
    assert made[0][0] not in prog["bodies"]
    assert made[0][1] == "fusion"


@pytest.mark.parametrize("cell,ops", [("perf", 91), ("hide", 423)])
def test_diffusion_cells_keep_their_op_counts(topo, cell, ops):
    """The two 12288² diffusion programs that share the striped kernels'
    launch code compile to as many ops as before the SWE kernel came."""
    dims = (1, 1) if cell == "perf" else (2, 2)
    with pytest.MonkeyPatch.context() as mp:
        prog = _program(_compiled(topo, mp, cell, N, dims=dims))
    assert _moves_signature(prog)[0] == ops


# ---- shallow water's 2D hide step on the 2x2 mesh -----------------------


def test_swe_hide_2x2_runs_its_two_kernels_at_12288(topo):
    """The swe2d-2x2-12288-hide cell's program, 12288² a chip on the
    2x2 mesh, q = 10, compiles with both kernels in scoped VMEM: each
    step is one whole-shard `masked_swe_step` (region="interior") and
    four `swe_slab_step`s (region="slab"), two steps to a scan
    iteration; no region takes the padded forms, the exchange is there,
    and no loop op is of the ghost-ringed shard's shape."""
    from rocm_mpi_tpu.models.swe import SWEConfig, ShallowWater
    from rocm_mpi_tpu.ops import swe_kernels

    n = 2 * N
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(swe_kernels, "_interpret_default", lambda: False)
        model = ShallowWater(
            SWEConfig(global_shape=(n, n), dtype="f32", dims=(2, 2),
                      nt=100, warmup=10),
            devices=topo.devices,
        )
        advance, _ = model.scan_advance_fn("hide")
        a = _sds((n, n), model.grid.sharding)
        prog = _program(advance.lower(a, (a, a), (a, a), 100).compile()
                        .as_text())
    kernels = collections.Counter(
        (op[4], op[0].rsplit(".", 1)[0]) for op in _kernels(prog))
    assert kernels == {("interior", "masked_swe_step"): 2,
                       ("slab", "swe_slab_step"): 8}
    loop = _loop_ops(prog)
    assert any(op[1] == "collective-permute-start" for _, op in loop)
    assert not [op for _, op in loop
                if op[2].startswith(f"f32[{N + 2},{N + 2}]")]
