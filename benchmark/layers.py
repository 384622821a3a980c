"""The program's own names for its device work, read from the trace.

The program names each Pallas kernel after the public function that
launches it (`pallas_call(name=...)`: `masked_step`, `fused_step_cm`,
...) and tags the ops it traces by layer (docs/TELEMETRY.md
"Device-side names"): `layer` is `driver`, `exchange` or `splice`, and
the overlap step's kernels carry `region`, `interior` or `slab`. The
tags are XLA `frontend_attributes`, which the profiler keeps in each
op's text (`Op.name`), as in

    %fused_step_cm.7 = f32[62,118]{...} custom-call(...),
        custom_call_target="tpu_custom_call", ...,
        frontend_attributes={kernel_metadata={...},layer="splice",region="interior"}

Every leaf, non-async, non-collective op in the window falls in one
class: `kernel` (custom-call target `tpu_custom_call`, whatever tag it
carries), else its `layer` tag, else `untagged`. Summed over the
classes, the time is the compute time stencil_roofline divides by.

What a trace can show depends on the toolchain (jax 0.9, libtpu
0.0.34): in a program partitioned over several chips, XLA's Shardy pass
re-imports the HLO and drops the tags of pad, slice, dynamic-slice,
dynamic-update-slice, concatenate, reverse and iota, so there the data
movement of the exchange and of the splice is `untagged`; kernels,
collectives and arithmetic keep theirs. A one-chip program keeps every
tag, and so does a program partitioned by GSPMD
(JAX_USE_SHARDY_PARTITIONER=false), which compiles the same ops.

As a script, it prints the device time of a profile by class:

    python3 -m benchmark.layers TRACE_DIR [--steps N]

TRACE_DIR holds a `jax.profiler` trace (an app's `--profile DIR`); with
`--steps`, times are ms a step, else ms over the whole trace, averaged
over the chips the trace holds.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re

from benchmark import trace

KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
_ATTRS = "frontend_attributes={"
_PAIR = re.compile(r'(\w+)="([^"]*)"')


def attributes(hlo: str) -> dict[str, str]:
    """The string-valued frontend attributes of an op's text; nested
    ones (a kernel's `kernel_metadata={...}`, which may span lines) are
    skipped."""
    i = hlo.find(_ATTRS)
    if i < 0:
        return {}
    i += len(_ATTRS)
    depth, out, start = 0, [], i
    for j in range(i, len(hlo)):
        ch = hlo[j]
        if ch == "{":
            if depth == 0:
                out.append(hlo[start:j])
            depth += 1
        elif ch == "}":
            if depth == 0:
                out.append(hlo[start:j])
                break
            depth -= 1
            if depth == 0:
                start = j + 1
    return dict(_PAIR.findall("".join(out)))


def is_kernel(op: trace.Op) -> bool:
    return KERNEL_TARGET in op.name


def layer_of(op: trace.Op) -> str:
    """`kernel`, the op's `layer` tag, or `untagged`."""
    if is_kernel(op):
        return "kernel"
    return attributes(op.name).get("layer", "untagged")


def is_tagged(tr: trace.Trace) -> bool:
    """True when some op of the trace carries a `layer` tag: a program
    that names its layers (a program without names has nothing for
    these readers)."""
    return any("layer" in attributes(o.name)
               for ops in tr.ops.values() for o in ops)


def compute_ops(tr: trace.Trace, chip):
    """The leaf, non-async, non-collective ops of `chip`: the ops whose
    union is Trace.compute."""
    return [o for o in tr.ops[chip]
            if o.leaf and not o.async_ and not o.collective]


def seconds(tr: trace.Trace, keep) -> float:
    """Device seconds of the compute ops for which keep(op) holds, in
    the window, averaged over the chips (the union of their intervals,
    as Trace.compute takes it)."""
    total = 0.0
    for c in tr.chips():
        total += trace.measure(trace.merge(trace.clip(
            [(o.start, o.end) for o in compute_ops(tr, c) if keep(o)],
            *tr.window)))
    return total / (1e9 * len(tr.chips()))


def ms_per_step(run, keep) -> float | None:
    """seconds(keep) over the window's steps, in ms; None without a
    trace or steps."""
    tr = run.trace
    steps = getattr(run.window, "steps", None)
    if tr is None or not steps:
        return None
    return 1e3 * seconds(tr, keep) / steps


def region_ms_per_step(run, region: str) -> float | None:
    """ms_per_step of the kernels tagged region=`region`; None where no
    kernel carries that tag."""
    def keep(op):
        return is_kernel(op) and attributes(op.name).get("region") == region

    tr = run.trace
    if tr is None or not any(keep(o) for ops in tr.ops.values()
                             for o in ops):
        return None
    return ms_per_step(run, keep)


def by_class(tr: trace.Trace, top: int = 20) -> dict:
    """Device ms of the compute ops, averaged over the chips: by class,
    by (class, region, op) for the `top` longest, and the classes' sum
    over the compute time."""
    per, ops = {}, {}
    for c in tr.chips():
        for o in compute_ops(tr, c):
            s, e = max(o.start, tr.window[0]), min(o.end, tr.window[1])
            if e <= s:
                continue
            key = (layer_of(o), attributes(o.name).get("region", ""),
                   o.kind)
            ops[key] = ops.get(key, 0.0) + (e - s)
    n = 1e6 * len(tr.chips())
    for (cls, _, _), ns in ops.items():
        per[cls] = per.get(cls, 0.0) + ns / n
    compute = sum(trace.measure(tr.compute(c)) for c in tr.chips()) / n
    return {
        "ms": per,
        "ops": sorted(([*k, v / n] for k, v in ops.items()),
                      key=lambda row: -row[-1])[:top],
        "sum_over_compute": sum(per.values()) / compute if compute else None,
    }


def load(path: str) -> trace.Trace:
    """The newest xplane under `path` (or the file itself), every TPU
    chip it holds; the window is its first op to its last."""
    import jax

    files = [path] if path.endswith(".xplane.pb") else glob.glob(
        os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    planes = list(jax.profiler.ProfileData.from_file(
        max(files, key=os.path.getmtime)).planes)
    chips = sorted(int(p.name.rsplit(":", 1)[1]) for p in planes
                   if p.name.startswith("/device:TPU:"))
    return trace.from_planes(planes, chips)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="device time of a profile by "
                                "the program's layer tags")
    p.add_argument("trace_dir")
    p.add_argument("--steps", type=int, default=None)
    args = p.parse_args(argv)
    tr = load(args.trace_dir)
    out = by_class(tr)
    scale = args.steps or 1
    out["ms"] = {k: v / scale for k, v in out["ms"].items()}
    out["ops"] = [[*row[:-1], row[-1] / scale] for row in out["ops"]]
    out["unit"] = "ms a step" if args.steps else "ms"
    out["chips"] = tr.chips()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
