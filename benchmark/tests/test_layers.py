"""The readers of the program's own names (benchmark/layers.py and the
metrics built on it): op text parsing, a synthetic tagged trace, and
the small windows recorded on the chip."""

import glob
import json
import os
import types

import pytest

from benchmark import layers, spec, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRICS = os.path.join(os.path.dirname(DATA), "..", "metrics")
NEW = ("kernel_roofline", "untagged_ms_per_step",
       "interior_kernel_ms_per_step", "slab_kernel_ms_per_step")
CLASSES = ("kernel", "driver", "exchange", "splice", "untagged")


def reader(name):
    return spec._load(os.path.join(METRICS, name + ".py"), "metric").read


def kernel(name, region=None):
    tags = f',layer="splice",region="{region}"' if region else ""
    return (f"%{name} = f32[8,8]{{1,0}} custom-call(f32[8,8] %a), "
            'custom_call_target="tpu_custom_call", frontend_attributes='
            '{kernel_metadata={"shape": {\n "x": [1, 2]}}' + tags + "}")


def tagged(name, opcode, layer=None):
    tags = f', frontend_attributes={{layer="{layer}"}}' if layer else ""
    return f"%{name} = f32[8,8]{{1,0}} {opcode}(f32[8,8] %a){tags}"


def test_attributes():
    text = kernel("fused_step_cm.7", "interior")
    assert layers.attributes(text) == {"layer": "splice",
                                       "region": "interior"}
    assert layers.attributes(tagged("pad.1", "pad", "exchange")) == {
        "layer": "exchange"}
    assert layers.attributes(tagged("copy.5", "copy")) == {}
    assert trace.short_name(text) == \
        "custom-call:tpu_custom_call fused_step_cm"


def ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k.replace("_", " "), events=v)
        for k, v in lines.items()])


def synthetic():
    """One chip, window 0-100 ns: a loop holding, in turn, the interior
    kernel 0-30, a slab kernel 30-40, an exchange pad 40-50, a splice
    update 50-55, a driver add 55-57, an untagged copy 57-77 and a
    permute's done 77-80; 80-100 idle."""
    loop = "%while.1 = (f32[8]{0}) while((f32[8]{0}) %t), body=%b"
    done = "%cp-done.3 = f32[8]{0} collective-permute-done(f32[8]{0} %s)"
    return [plane("/device:TPU:0", XLA_Ops=[
        ev(loop, 0, 80),
        ev(kernel("fused_step_cm.7", "interior"), 0, 30),
        ev(kernel("fused_step_cm.5", "slab"), 30, 10),
        ev(tagged("pad.1", "pad", "exchange"), 40, 10),
        ev(tagged("dynamic-update-slice.9", "dynamic-update-slice",
                  "splice"), 50, 5),
        ev(tagged("add.3", "add", "driver"), 55, 2),
        ev(tagged("copy.5", "copy"), 57, 20),
        ev(done, 77, 3),
        ev(tagged("add.4", "add", "driver"), 99, 1),
    ])]


def run_of(tr, steps, config=None):
    from benchmark.drivers.calls import Window

    return types.SimpleNamespace(
        trace=tr, window=Window(steps_per_call=steps, starts=[0.0],
                                ends=[1.0]),
        config=config or {"global_shape": [4, 4], "dims": [1, 1]},
        peaks={"hbm_bytes_per_s": 1e9})


def test_synthetic_tagged_trace():
    tr = trace.from_planes(synthetic(), [0])
    tr.window = (0, 100)
    run = run_of(tr, 10)
    by = {c: layers.seconds(tr, lambda o, c=c: layers.layer_of(o) == c)
          for c in CLASSES}
    assert by == pytest.approx({"kernel": 40e-9, "driver": 3e-9,
                                "exchange": 10e-9, "splice": 5e-9,
                                "untagged": 20e-9})
    compute = trace.measure(tr.compute(0)) / 1e9
    assert sum(by.values()) == pytest.approx(compute)
    # 10 steps of 3 * 4 B * 16 points at 1 GB/s: 1920 ns over 40 ns of
    # kernels
    assert reader("kernel_roofline")(run) == pytest.approx(100 * 1920 / 40)
    assert reader("untagged_ms_per_step")(run) == pytest.approx(20e-6 / 10)
    assert reader("interior_kernel_ms_per_step")(run) == \
        pytest.approx(30e-6 / 10)
    assert reader("slab_kernel_ms_per_step")(run) == \
        pytest.approx(10e-6 / 10)


def test_untagged_reads_one_chip_only():
    """On a trace of several chips the untagged class holds the data
    movement whose tags the partitioner dropped: nothing to read."""
    two = synthetic() + [plane("/device:TPU:1",
                               XLA_Ops=synthetic()[0].lines[0].events)]
    tr = trace.from_planes(two, [0, 1])
    tr.window = (0, 100)
    assert reader("untagged_ms_per_step")(run_of(tr, 10)) is None
    assert reader("interior_kernel_ms_per_step")(run_of(tr, 10)) == \
        pytest.approx(30e-6 / 10)


def test_by_class_script(capsys):
    """`python3 -m benchmark.layers DIR --steps N` prints each class's ms
    a step, the longest ops by class and region, and their sum over the
    compute time."""
    path = os.path.join(DATA, "hide256_4chip_named.xplane.pb")
    assert layers.main([path, "--steps", "20"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["unit"] == "ms a step" and out["chips"] == [0, 1, 2, 3]
    assert set(out["ms"]) == {"kernel", "driver", "untagged"}
    assert out["sum_over_compute"] == pytest.approx(1.0, rel=5e-3)
    assert {(c, r) for c, r, *_ in out["ops"] if c == "kernel"} == {
        ("kernel", "interior"), ("kernel", "slab")}
    assert sum(row[-1] for row in out["ops"]) == pytest.approx(
        sum(out["ms"].values()))


def test_readers_without_names():
    """A trace of a program that names nothing (the parent of the names)
    gives the kernel roofline and nothing else; no trace, nothing."""
    plain = [plane("/device:TPU:0", XLA_Ops=[
        ev("%closed_call.4 = f32[8]{0} custom-call(f32[8]{0} %a), "
           'custom_call_target="tpu_custom_call", '
           "frontend_attributes={kernel_metadata={}}", 0, 30),
        ev("%copy.5 = f32[8]{0} copy(f32[8]{0} %x)", 30, 20)])]
    run = run_of(trace.from_planes(plain, [0]), 10)
    assert reader("kernel_roofline")(run) == pytest.approx(100 * 1920 / 30)
    for name in NEW[1:]:
        assert reader(name)(run) is None
    run.trace = None
    assert all(reader(n)(run) is None for n in NEW)


RECORDED = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))


@pytest.mark.parametrize("path", RECORDED,
                         ids=[os.path.basename(p) for p in RECORDED])
def test_recorded_classes_add_up(path):
    """In every window recorded on the chip, kernel, driver, exchange,
    splice and untagged time add up to the compute time stencil_roofline
    divides by, within 0.5%."""
    import jax

    meta = json.load(open(path.replace(".xplane.pb", ".json")))
    tr = trace.from_planes(jax.profiler.ProfileData.from_file(path).planes,
                           meta["chips"])
    compute = sum(trace.measure(tr.compute(c)) for c in tr.chips()) / (
        1e9 * len(tr.chips()))
    by = {c: layers.seconds(tr, lambda o, c=c: layers.layer_of(o) == c)
          for c in CLASSES}
    assert sum(by.values()) == pytest.approx(compute, rel=5e-3)
    assert by["kernel"] > 0
    if not meta.get("names"):
        return
    names = {o.kind for ops in tr.ops.values() for o in ops
             if layers.is_kernel(o)}
    assert names == {f"custom-call:tpu_custom_call {n}"
                     for n in meta["names"]["kernels"]}
    assert {layers.layer_of(o) for ops in tr.ops.values()
            for o in ops} >= set(meta["names"]["layers"])
    run = run_of(tr, meta["calls"] * meta["steps_per_call"], meta["config"])
    run.peaks = {"hbm_bytes_per_s": 819e9}
    for name in meta["names"]["metrics"]:
        value = reader(name)(run)
        assert value is not None and value > 0, name
    if len(tr.chips()) > 1:
        assert reader("untagged_ms_per_step")(run) is None
