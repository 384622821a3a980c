"""Device-side names (docs/TELEMETRY.md "Device-side names"): every
Pallas kernel names itself, the exchange, the splice and the schedule
driver tag the ops they trace (compat.set_xla_metadata), and an enabled
host span shows on the profiler's clock. What the program compiled for
the CPU mesh keeps of the tags is here; what the one compiled for a
described TPU keeps is tests/test_tpu_compile.py's."""

from __future__ import annotations

import ast
import glob
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rocm_mpi_tpu.config import DiffusionConfig
from rocm_mpi_tpu.models import HeatDiffusion
from rocm_mpi_tpu.telemetry import events

REPO = pathlib.Path(__file__).resolve().parent.parent
_ATTR = re.compile(r"(stablehlo\.\w+|\"stablehlo\.\w+\").*"
                   r"mhlo\.frontend_attributes = \{([^}]*)\}")


def _tagged_ops(text: str) -> set[tuple[str, str, str]]:
    """(op, key, value) for every tag on the lowered program's ops."""
    out = set()
    for line in text.splitlines():
        m = _ATTR.search(line)
        if m:
            op = m.group(1).strip('"')
            for k, v in re.findall(r'(\w+) = "(\w+)"', m.group(2)):
                out.add((op, k, v))
    return out


def _hide_model():
    cfg = DiffusionConfig(global_shape=(32, 32), nt=8, warmup=0,
                          dtype="f32", dims=(2, 2), b_width=(4, 4))
    return HeatDiffusion(cfg, devices=jax.devices()[:4])


def _shape(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _expect_hide_tags(tags, writes=True):
    """The hide step's tags; `writes`: its buffer writes lower to
    dynamic_update_slice (under the serving step's vmap they lower to
    scatters, whose tag this line-wise reading does not see). The
    exchange hands the slabs their ghosts as received slabs, assembled
    into each slab's window by concatenates: it writes no buffer."""
    need = [
        ("stablehlo.slice", "layer", "exchange"),
        ("stablehlo.collective_permute", "layer", "exchange"),
        ("stablehlo.concatenate", "layer", "exchange"),
        ("stablehlo.slice", "layer", "splice"),
        ("stablehlo.while", "layer", "driver"),
    ]
    if writes:
        need += [("stablehlo.dynamic_update_slice", "layer", "splice")]
    for n in need:
        assert n in tags, n
    assert ("stablehlo.dynamic_update_slice", "layer", "exchange") \
        not in tags
    regions = {v for _, k, v in tags if k == "region"}
    assert regions == {"interior", "slab"}


def test_hide_step_tags_its_layers():
    """The scan driver's hide step, as the benchmark builds it: the
    exchange, the splice, the regions and the driver each name their
    ops in the lowered program."""
    m = _hide_model()
    advance, _ = m.scan_advance_fn("hide")
    a = _shape((32, 32), m.grid.sharding)
    _expect_hide_tags(_tagged_ops(advance.lower(a, a, 8).as_text()))


def test_batched_hide_step_shares_the_tags():
    """The lane-batched serving step reuses the exchange and the splice,
    so it carries the same tags with no code of its own."""
    m = _hide_model()
    adv, bg = m.batched_advance_fn(batch=2, variant="hide")
    tb = jax.ShapeDtypeStruct((2, 32, 32), jnp.float32,
                              sharding=bg.sharding)
    cp = _shape((32, 32), bg.aux_sharding)
    steps = jax.ShapeDtypeStruct((2,), jnp.int32,
                                 sharding=bg.batch_sharding)
    _expect_hide_tags(_tagged_ops(adv.lower(tb, cp, steps, 8).as_text()),
                      writes=False)


def test_perf_step_tags_the_driver():
    cfg = DiffusionConfig(global_shape=(32, 32), nt=8, warmup=0,
                          dtype="f32", dims=(1, 1))
    m = HeatDiffusion(cfg, devices=jax.devices()[:1])
    advance, _ = m.scan_advance_fn("perf")
    a = _shape((32, 32), m.grid.sharding)
    tags = _tagged_ops(advance.lower(a, a, 8).as_text())
    assert ("stablehlo.divide", "layer", "driver") in tags
    assert not {t for t in tags if t[2] in ("exchange", "splice")}


_OPCODE = re.compile(r"\s*(?:ROOT )?%[\w.\-]+ = \S+ ([\w\-]+)\(")
_DATA_MOVEMENT = {("slice", "exchange"), ("concatenate", "exchange"),
                  ("slice", "splice"), ("dynamic-update-slice", "splice")}


def _compiled_tags(shardy: bool) -> set[tuple[str, str]]:
    """(opcode, layer) of every tagged op of the hide step compiled for
    the CPU mesh, partitioned by Shardy (the default) or GSPMD."""
    m = _hide_model()
    advance, _ = m.scan_advance_fn("hide")
    a = _shape((32, 32), m.grid.sharding)
    saved = jax.config.jax_use_shardy_partitioner
    jax.config.update("jax_use_shardy_partitioner", shardy)
    try:
        text = advance.lower(a, a, 8).compile().as_text()
    finally:
        jax.config.update("jax_use_shardy_partitioner", saved)
    out = set()
    for line in text.splitlines():
        op, tag = _OPCODE.match(line), re.search(r'layer="(\w+)"', line)
        if op and tag:
            out.add((op.group(1), tag.group(1)))
    return out


def test_compiled_hide_step_keeps_tags():
    """Compiled as it runs, the step's collectives and arithmetic keep
    their layer: what the trace readers count on (benchmark/layers.py)."""
    assert {("collective-permute", "exchange"), ("multiply", "splice"),
            ("divide", "driver")} <= _compiled_tags(shardy=True)


def test_gspmd_keeps_data_movement_tags():
    """Partitioned by GSPMD (JAX_USE_SHARDY_PARTITIONER=false, the
    operator's way to split the exchange from the splice in a profile,
    docs/TELEMETRY.md), the data movement keeps its layer too."""
    assert _DATA_MOVEMENT <= _compiled_tags(shardy=False)


@pytest.mark.xfail(strict=False, reason=(
    "jax 0.9: the Shardy pass re-imports the HLO and drops the "
    "frontend_attributes of pad, slice, dynamic-update-slice and other "
    "data movement (docs/TELEMETRY.md \"Device-side names\"); passes "
    "once a toolchain keeps them"))
def test_shardy_keeps_data_movement_tags():
    assert _DATA_MOVEMENT <= _compiled_tags(shardy=True)


def test_swe_one_chip_kernel_keeps_its_name():
    """ShallowWater's one-chip perf step compiles its kernel under the
    name `masked_swe_step` (interpret mode here keeps it in the ops'
    op_name; the described TPU's custom call is pinned in
    tests/test_tpu_compile.py), and the loop keeps its driver tag."""
    from rocm_mpi_tpu.models.swe import SWEConfig, ShallowWater

    m = ShallowWater(SWEConfig(global_shape=(48, 40), nt=8, warmup=0,
                               dtype="f32", dims=(1, 1)),
                     devices=jax.devices()[:1])
    advance, _ = m.scan_advance_fn("perf")
    a = _shape((48, 40), m.grid.sharding)
    lowered = advance.lower(a, (a, a), (a, a), 8)
    text = lowered.compile().as_text()
    assert re.search(r'op_name="[^"]*masked_swe_step', text)
    assert ("stablehlo.while", "layer", "driver") in _tagged_ops(
        lowered.as_text())


def _pallas_calls():
    for path in sorted(glob.glob(str(REPO / "rocm_mpi_tpu/**/*.py"),
                                 recursive=True)):
        tree = ast.parse(pathlib.Path(path).read_text())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and (
                getattr(node.func, "attr", None) == "pallas_call"
            ):
                yield path, node


def test_every_pallas_kernel_is_named():
    """Each pallas_call passes name=: a literal after its public
    function, or the name its private helper was handed. Literal names
    are unique, and none ends in .<digits> (XLA's own suffix)."""
    literal = []
    calls = list(_pallas_calls())
    assert len(calls) >= 15
    for path, call in calls:
        kw = {k.arg: k.value for k in call.keywords}
        assert "name" in kw, f"{path}:{call.lineno} has no name="
        if isinstance(kw["name"], ast.Constant):
            literal.append(kw["name"].value)
    assert len(literal) == len(set(literal)), literal
    assert all(re.fullmatch(r"[a-z][a-z0-9_]*", n) for n in literal)


def test_span_on_profiler_clock(tmp_path, monkeypatch):
    """An enabled span, and a labeled Timer, show by name in the host
    plane of a profile taken while they run."""
    from rocm_mpi_tpu.telemetry import spans
    from rocm_mpi_tpu.utils.metrics import Timer

    monkeypatch.setattr(events, "_ENABLED", True)
    monkeypatch.setattr(events, "_DIR", None)
    x = jnp.ones((64, 64))
    with jax.profiler.trace(str(tmp_path)):
        with spans.span("tagtest.span") as sp:
            sp.sync(x @ x)
        timer = Timer(label="tagtest.timer")
        timer.tic()
        timer.toc(x + 1)
    events.clear()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for p in jax.profiler.ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events}
    assert {"tagtest.span", "tagtest.timer"} <= names


def test_disabled_span_is_the_noop_singleton(monkeypatch):
    from rocm_mpi_tpu.telemetry import spans

    monkeypatch.setattr(events, "_ENABLED", False)
    assert spans.span("tagtest.off") is spans._NOOP


def test_tagged_step_computes_the_same(monkeypatch):
    """Tags are metadata: the tagged hide step and the untagged one give
    the same field, bit for bit."""
    import contextlib

    from rocm_mpi_tpu.utils import compat

    def run():
        m = _hide_model()
        advance, _ = m.scan_advance_fn("hide", nt=4, warmup=0)
        T0, Cp = m.init_state()
        return np.asarray(advance(T0, Cp, 4))

    tagged = run()
    monkeypatch.setattr(compat, "set_xla_metadata",
                        lambda **names: contextlib.nullcontext())
    assert np.array_equal(tagged, run())
