"""The compiled HBM-traffic gate (rocm_mpi_tpu/perf, docs/PERF.md) and
the parity of the reworked traffic-minimal paths.

Two halves, matching the gate's two failure modes:

* the AUDIT must be right: the splice-free halo/overlap/scan/deep paths
  must still produce the physics — pinned against the HostStagedStepper
  transport oracle (diffusion) and the GSPMD ap oracles (wave, SWE);
* the GATE must have teeth: `python -m rocm_mpi_tpu.perf` exits 0 on the
  shipped drivers and demonstrably exits 1 when the pre-rework
  concatenate splice is measured through it (the known-waste fixture).
"""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _run_gate(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # The gate pins its own device count/x64; a test-runner inherited
    # XLA_FLAGS would fight set_cpu_device_count's append.
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-m", "rocm_mpi_tpu.perf", *extra],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600,
    )


class TestGateCLI:
    def test_gate_passes_on_shipped_drivers(self):
        # THE acceptance drill: the traffic gate over the real shard /
        # overlap / deep-k programs on the committed 2-rank CPU geometry.
        proc = _run_gate()
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "shard" in proc.stdout and "overlap" in proc.stdout
        assert "OVER BUDGET" not in proc.stdout

    def test_gate_catches_concatenate_splice_waste(self):
        # Regression-test the gate ITSELF: re-introduce the pre-rework
        # concatenate-based splice (as the built-in fixture) and the gate
        # must exit 1 — proof it detects the staging-copy class, not just
        # that budgets are loose.
        proc = _run_gate("--include-waste-fixture")
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "concat-splice(fixture)" in proc.stdout
        assert "OVER BUDGET" in proc.stdout
        # The shipped drivers still pass inside the same run.
        for line in proc.stdout.splitlines():
            if line.strip().startswith(("shard ", "overlap ", "deep")):
                assert line.rstrip().endswith("ok"), line

    def test_gate_json_rows_parse(self):
        proc = _run_gate("--json")
        assert proc.returncode == 0, proc.stderr
        rows = [json.loads(ln) for ln in proc.stdout.splitlines() if ln]
        assert {r["metric"] for r in rows} >= {"traffic shard",
                                               "traffic overlap"}
        traffic_rows = [r for r in rows
                        if r["metric"].startswith("traffic ")]
        for r in traffic_rows:
            assert r["ok"] is True
            assert r["bytes"] > 0 and r["ideal"] > 0
            assert r["wire"] == r["wire_ideal"]  # exact wire accounting
        # The wire-bytes ladder rows ride the same JSON stream
        # (docs/PERF.md "Wire precision").
        wire_rows = {r["metric"]: r for r in rows
                     if r["metric"].startswith("wire ")}
        assert {"wire f32", "wire bf16", "wire int8",
                "wire int8_delta"} <= set(wire_rows)
        for r in wire_rows.values():
            assert r["ok"] is True
            assert r["bytes"] == r["mode_ideal"]  # exact accounting
        assert wire_rows["wire bf16"]["fraction"] <= 0.55


class TestTrafficModel:
    def test_hlo_bytes_accessed_rules(self):
        from rocm_mpi_tpu.perf.traffic import hlo_bytes_accessed

        text = """
HloModule m
ENTRY %main (p0: f64[4,4]) -> f64[4,4] {
  %p0 = f64[4,4]{1,0} parameter(0)
  %c = f64[] constant(1)
  %b = f64[4,4]{1,0} broadcast(f64[] %c), dimensions={}
  %add = f64[4,4]{1,0} add(f64[4,4]{1,0} %p0, f64[4,4]{1,0} %b)
  %s = f64[1,4]{1,0} slice(f64[4,4]{1,0} %add), slice={[0:1], [0:4]}
  ROOT %dus = f64[4,4]{1,0} dynamic-update-slice(f64[4,4]{1,0} %add, f64[1,4]{1,0} %s, s64[] %c, s64[] %c)
}
"""
        got = hlo_bytes_accessed(text)
        # broadcast: 8 + 128; add: 128+128+128; slice: 2*32; dus: 2*32
        assert got == (8 + 128) + 3 * 128 + 64 + 64

    def test_hlo_wire_bytes_counts_collective_sends(self):
        from rocm_mpi_tpu.perf.traffic import hlo_wire_bytes

        text = """
HloModule m
ENTRY %main (p0: f64[2,8]) -> f64[2,8] {
  %p0 = f64[2,8]{1,0} parameter(0)
  %cp = f64[2,8]{1,0} collective-permute(f64[2,8]{1,0} %p0), channel_id=1, source_target_pairs={{0,1}}
  ROOT %cp2 = f64[2,8]{1,0} collective-permute(f64[2,8]{1,0} %cp), channel_id=2, source_target_pairs={{1,0}}
}
"""
        assert hlo_wire_bytes(text) == 2 * 2 * 8 * 8

    def test_bare_operand_names_resolve_through_definitions(self):
        # jax 0.9.0 prints operands as bare names; both parsers size them
        # from the entry computation's own definitions.
        from rocm_mpi_tpu.perf.traffic import (
            hlo_bytes_accessed,
            hlo_wire_bytes,
        )

        text = """
HloModule m
ENTRY %main (p0: f32[2,8]) -> f32[2,8] {
  %p0 = f32[2,8]{1,0} parameter(0)
  %s = f32[1,8]{1,0} slice(%p0), slice={[0:1], [0:8]}
  %cp = f32[1,8]{1,0} collective-permute(%s), channel_id=1, source_target_pairs={{0,1}}
  ROOT %add = f32[2,8]{1,0} add(%p0, %p0)
}
"""
        assert hlo_wire_bytes(text) == 32
        # slice 2*32, permute 32+32, add 64+64+64
        assert hlo_bytes_accessed(text) == 64 + 64 + 192

    @pytest.mark.parametrize("parser", [
        "hlo_wire_bytes", "hlo_bytes_accessed",
    ])
    def test_unsized_operand_is_an_error_not_zero(self, parser):
        # A parse that reads an unsized operand as 0 bytes would pass
        # every row of the gate without measuring: refuse instead.
        from rocm_mpi_tpu.perf import traffic

        text = """
HloModule m
ENTRY %main (p0: f32[2,8]) -> f32[2,8] {
  %p0 = f32[2,8]{1,0} parameter(0)
  ROOT %cp = f32[2,8]{1,0} collective-permute(%undefined), channel_id=1, source_target_pairs={{0,1}}
}
"""
        with pytest.raises(ValueError, match="format drift"):
            getattr(traffic, parser)(text)

    def test_budgets_file_is_committed_and_sane(self):
        from rocm_mpi_tpu.perf.traffic import load_budgets

        doc = load_budgets()
        assert doc["budgets"].keys() >= {"shard", "overlap", "deep"}
        # The acceptance pin: the fused shard step's committed budget
        # itself sits within 1.5x of the analytic ideal.
        assert doc["budgets"]["shard"] <= 1.5
        geo = doc["geometry"]
        assert geo["dims"] == [2, 1] and geo["local"] >= 16

    @pytest.mark.parametrize("row", ["overlap", "batched-hide2"])
    def test_staged_overlap_fails_the_gate(self, padded_splice, row):
        """The overlap step that staged the shard into a ghost-ringed
        copy for its slabs (`padded_splice`) is over the committed
        budget on the gate geometry; the step as it is, under it."""
        from rocm_mpi_tpu.perf.traffic import audit_batched, audit_variants

        def audited():
            rows = audit_variants() if row == "overlap" else audit_batched()
            (r,) = [r for r in rows if r.variant == row]
            return r

        assert audited().ok
        padded_splice()
        staged = audited()
        assert staged.ratio > staged.budget and not staged.ok

    def test_audit_emits_traffic_annotations(self, tmp_path):
        # step.traffic facts land in the telemetry stream when enabled.
        from rocm_mpi_tpu import telemetry
        from rocm_mpi_tpu.perf.traffic import audit_variants

        telemetry.configure(enabled=True, directory=str(tmp_path), rank=0)
        try:
            rows = audit_variants(local=16, deep_k=4)
            recs = telemetry.records(kind="trace", name="step.traffic")
            assert {r["attrs"]["variant"] for r in recs} >= {
                "shard", "overlap", "deep4"
            }
        finally:
            telemetry.configure(enabled=False)
            telemetry.clear()
        assert all(r.measured_bytes > 0 for r in rows)


class TestReworkedPathParity:
    """The splice-free paths against the transport-free oracles."""

    def _diffusion(self, **kw):
        from rocm_mpi_tpu.config import DiffusionConfig
        from rocm_mpi_tpu.models import HeatDiffusion

        kw.setdefault("global_shape", (32, 32))
        kw.setdefault("lengths", (10.0, 10.0))
        kw.setdefault("nt", 20)
        kw.setdefault("warmup", 4)
        kw.setdefault("dims", (2, 2))
        kw.setdefault("b_width", (4, 4))
        return HeatDiffusion(DiffusionConfig(**kw))

    def test_diffusion_paths_match_host_staged_oracle(self):
        # The IGG_ROCMAWARE_MPI=0 analog as ground truth: the reworked
        # DUS halo, the DUS-spliced overlap (Cm contract, f64 jnp), the
        # scan driver, and a deep sweep must all land on the pure-numpy
        # HostStagedStepper trajectory on a 2x2 mesh.
        oracle = self._diffusion(halo_transport="host").run(variant="shard")
        ref = np.asarray(oracle.T)

        m = self._diffusion()

        def per_step(variant):
            T, Cp = m.init_state()
            return m.advance_fn(variant)(T, Cp, m.config.nt)

        for label, T in (
            ("shard/step", per_step("shard")),
            ("shard/scan", m.run(variant="shard").T),
            ("hide/step", per_step("hide")),
            ("hide/scan", m.run(variant="hide").T),
            ("deep4", m.run_deep(block_steps=4).T),
        ):
            np.testing.assert_allclose(
                np.asarray(T), ref, rtol=1e-12, atol=1e-14,
                err_msg=f"{label} diverged from the host-staged oracle",
            )

    @pytest.mark.parametrize("workload, variant, q", [
        ("diffusion", "shard", 10), ("diffusion", "shard", 5),
        ("diffusion", "perf", 10), ("diffusion", "perf", 5),
        ("diffusion", "hide", 10), ("diffusion", "hide", 5),
        ("wave", "hide", 4), ("wave", "hide", 3),
        ("swe", "hide", 4), ("swe", "hide", 3),
    ], ids=lambda v: str(v))
    def test_scan_driver_bitwise_equals_step_driver(self, workload, variant,
                                                    q):
        # Same step program, same order — the scan driver (two steps an
        # iteration; an odd q ends each chunk with one single step) must
        # equal the per-step loop (advance_fn, q = 1) BITWISE on every
        # workload: it changes scheduling and allocation, never values.
        # Diffusion's perf and hide run the f32 Pallas rungs
        # (masked_step, interpret mode).
        import jax

        from rocm_mpi_tpu.models import (
            AcousticWave,
            ShallowWater,
            SWEConfig,
            WaveConfig,
        )

        windows = dict(nt=2 * q, warmup=q)
        if workload == "diffusion":
            m = self._diffusion(
                dims=(1, 1) if variant == "perf" else (2, 2),
                dtype="f64" if variant == "shard" else "f32", **windows,
            )
        else:
            model, config = {"wave": (AcousticWave, WaveConfig),
                             "swe": (ShallowWater, SWEConfig)}[workload]
            m = model(config(global_shape=(32, 32), lengths=(10.0, 10.0),
                             dims=(2, 2), **windows))
        scan, got_q = m.scan_advance_fn(variant)
        assert got_q == q
        outs = []
        for advance in (scan, m.advance_fn(variant)):
            state, aux = m.state_and_aux()
            outs.append(jax.tree.leaves(advance(*state, aux, 2 * q)))
        for a, b in zip(*outs, strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_wave_masked_hide_bitwise_equals_perf(self):
        # The mask-as-data select (wave_step_padded_masked) is built to be
        # fp-IDENTICAL to perf's expression on updating cells and to hold
        # edge cells bitwise — so hide == perf exactly, sharded.
        import jax.numpy as jnp

        from rocm_mpi_tpu.models import AcousticWave, WaveConfig

        w = AcousticWave(WaveConfig(
            global_shape=(32, 32), lengths=(10.0, 10.0), nt=16, warmup=0,
            dims=(2, 2),
        ))
        U, Uprev, C2 = w.init_state()
        p, _ = w.advance_fn("perf")(jnp.copy(U), jnp.copy(Uprev), C2, 12)
        h, _ = w.advance_fn("hide")(jnp.copy(U), jnp.copy(Uprev), C2, 12)
        np.testing.assert_array_equal(np.asarray(h), np.asarray(p))

    def test_swe_scan_matches_ap_oracle(self):
        from rocm_mpi_tpu.models import ShallowWater, SWEConfig

        s = ShallowWater(SWEConfig(
            global_shape=(32, 32), lengths=(10.0, 10.0), nt=20, warmup=4,
            dims=(2, 2),
        ))
        r_ap = s.run(variant="ap")
        r = s.run(variant="hide")
        np.testing.assert_allclose(
            np.asarray(r.h), np.asarray(r_ap.h), rtol=1e-12, atol=1e-14
        )

    def test_scan_chunk_serves_both_windows(self):
        # q = gcd(warmup, timed): one compiled program, windows exact.
        m = self._diffusion(nt=24, warmup=6)
        advance, q = m.scan_advance_fn("shard", nt=24, warmup=6)
        assert q == 6
        with pytest.raises(ValueError, match=">= 1"):
            m.scan_advance_fn("shard", nt=24, warmup=6, chunk=0)


class TestExchangeInto:
    def test_place_core_and_exchange_into_compose_to_exchange_halo(self):
        import jax
        import jax.numpy as jnp

        from rocm_mpi_tpu.parallel import (
            exchange_halo,
            exchange_into,
            init_global_grid,
            place_core,
        )
        from rocm_mpi_tpu.utils.compat import shard_map

        grid = init_global_grid(8, 8, dims=(2, 2))
        x = jax.device_put(
            jnp.arange(64.0).reshape(8, 8), grid.sharding
        )

        @jax.jit
        def both(x):
            def local(b):
                direct = exchange_halo(b, grid)
                composed = exchange_into(place_core(b), grid)
                return direct, composed

            return shard_map(
                local, mesh=grid.mesh, in_specs=grid.spec,
                out_specs=(grid.spec, grid.spec),
            )(x)

        direct, composed = both(x)
        np.testing.assert_array_equal(
            np.asarray(direct), np.asarray(composed)
        )

    @pytest.mark.parametrize("shape,dims,width,wire", [
        ((8, 8), (2, 2), 1, "f32"),
        ((12, 10), (2, 2), 2, "bf16"),
        ((12, 8), (2, 1), 1, "f32"),
        ((8, 8, 4), (2, 2, 1), 2, "f32"),
    ], ids=["2x2", "2x2-w2-bf16", "2x1", "3d-w2"])
    def test_slabs_rebuild_exchange_halo(self, shape, dims, width, wire):
        """The received slabs carry every ghost of `exchange_halo`,
        corners included: placed by `exchange_into` they give its padded
        block, and `ghosted_window` gives any window of it — the whole
        block, a corner window, a thin slab's window — from the slabs
        and the unpadded block alone."""
        import jax
        import jax.numpy as jnp

        from rocm_mpi_tpu.parallel import (
            exchange_halo,
            exchange_into,
            exchange_slabs,
            ghosted_window,
            init_global_grid,
            place_core,
        )
        from rocm_mpi_tpu.utils.compat import shard_map

        grid = init_global_grid(*shape, dims=dims)
        rng = np.random.default_rng(5)
        x = jax.device_put(jnp.asarray(rng.uniform(-1, 1, shape)),
                           grid.sharding)
        local, w = grid.local_shape, width
        windows = [
            tuple((-w, n + w) for n in local),
            tuple((-w, 2) for n in local),
            tuple((n - 1, n + w) for n in local),
            ((-1, 3),) + tuple((-w, n + 1) for n in local[1:]),
        ]

        @jax.jit
        def same(x):
            def local_check(b):
                padded = exchange_halo(b, grid, width=w, wire_mode=wire)
                slabs = exchange_slabs(b, grid, width=w, wire_mode=wire)
                ok = jnp.array_equal(
                    padded, exchange_into(place_core(b, w), grid, w,
                                          wire_mode=wire))
                for win in windows:
                    idx = tuple(slice(lo + w, hi + w) for lo, hi in win)
                    ok &= jnp.array_equal(
                        padded[idx], ghosted_window(b, slabs, win, w))
                return jnp.full((1,) * b.ndim, ok)

            return shard_map(local_check, mesh=grid.mesh,
                             in_specs=grid.spec, out_specs=grid.spec)(x)

        assert np.asarray(same(x)).all()

    def test_wide_halo_corners_3d(self):
        # Width-2 ghosts on a 3D mesh: every corner/edge region of the
        # ghost ring must carry the right diagonal-neighbor values —
        # checked against a numpy reconstruction of the global array.
        import jax
        import jax.numpy as jnp

        from rocm_mpi_tpu.parallel import exchange_halo, init_global_grid
        from rocm_mpi_tpu.utils.compat import shard_map

        grid = init_global_grid(8, 8, 4, dims=(2, 2, 1))
        g = np.arange(8 * 8 * 4, dtype=np.float64).reshape(8, 8, 4)
        x = jax.device_put(jnp.asarray(g), grid.sharding)
        w = 2

        @jax.jit
        def padded(x):
            return shard_map(
                lambda b: exchange_halo(b, grid, width=w),
                mesh=grid.mesh, in_specs=grid.spec, out_specs=grid.spec,
            )(x)

        out = np.asarray(padded(x))
        local = grid.local_shape
        pl_shape = tuple(n + 2 * w for n in local)
        for ci in range(2):
            for cj in range(2):
                blk = out[
                    ci * pl_shape[0]:(ci + 1) * pl_shape[0],
                    cj * pl_shape[1]:(cj + 1) * pl_shape[1],
                ]
                # Expected: the global window around this shard, zero
                # where it falls off the domain.
                want = np.zeros(pl_shape)
                for i in range(pl_shape[0]):
                    for j in range(pl_shape[1]):
                        for kk in range(pl_shape[2]):
                            gi = ci * local[0] + i - w
                            gj = cj * local[1] + j - w
                            gk = kk - w
                            if (0 <= gi < 8 and 0 <= gj < 8
                                    and 0 <= gk < 4):
                                want[i, j, kk] = g[gi, gj, gk]
                np.testing.assert_array_equal(blk, want)
