"""The autotuner (rocm_mpi_tpu/tuning/, docs/PERF.md "Autotuning").

Coverage map (ISSUE 7 satellites + acceptance drills):
  * key/cache schema round-trip, atomic writes, torn-file tolerance,
    stale jax/backend fingerprint -> miss (never a crash, never deleted);
  * admission-filtered space + the traffic gate's per-family budgets,
    including THE TEETH: a doctored fastest-but-over-budget "winner" is
    rejected by the gate (search skips it; `validate` exits 1 on it —
    the tuning edition of perf's --include-waste-fixture);
  * the resolve chokepoint: hit/miss/stats, unreadable cache degrades;
  * config="auto" bitwise-equal to the default paths on all three
    workloads — on a cold cache (miss fallback) AND steered by a tuned
    cache whose knobs are the bitwise-safe ones;
  * search: persists a gated winner, second run is a pure hit;
  * CLI verbs end-to-end in-process: search/validate/show exit codes,
    warm-run determinism (identical bytes) and compiles.steady_state=0.
"""

import json
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from rocm_mpi_tpu.tuning import cache as tcache
from rocm_mpi_tpu.tuning import gate as tgate
from rocm_mpi_tpu.tuning import keys as tkeys
from rocm_mpi_tpu.tuning import resolve as tresolve
from rocm_mpi_tpu.tuning import space as tspace


@pytest.fixture(autouse=True)
def _isolated_resolve(tmp_path):
    """Every test gets its own cache path and fresh resolve state; the
    process-default path must never leak between tests (resolve memoizes
    its document snapshot by design)."""
    path = tmp_path / "cache.json"
    tresolve.configure(path)
    tresolve.reset_stats()
    yield path
    tresolve.configure(None)
    tresolve.refresh()
    tresolve.reset_stats()


def _entry(config, fp=None):
    return {
        "config": config, "median_us": 1.0, "compile_s": 0.1,
        "gate_ratio": 1.0,
        "fingerprint": fp or tkeys.fingerprint("cpu"),
    }


def _write_cache(path, entries):
    doc = tcache.empty_doc()
    doc["entries"].update(entries)
    tcache.write_doc(path, doc)
    tresolve.refresh()


# ---------------------------------------------------------------------------
# Keys
# ---------------------------------------------------------------------------


def test_key_roundtrip():
    k = tkeys.tuning_key("diffusion.vmem_loop", (252, 252), jnp.float32,
                         topology=(2, 1), backend="tpu")
    s = tkeys.key_str(k)
    assert s == "diffusion.vmem_loop|252x252|f32|2x1|tpu"
    assert tkeys.parse_key(s) == k


def test_key_default_topology_matches_rank():
    k2 = tkeys.tuning_key("diffusion.deep", (64, 64), "f32", backend="cpu")
    k3 = tkeys.tuning_key("diffusion.deep", (32, 32, 32), "f32",
                          backend="cpu")
    assert k2.topology == "1x1" and k3.topology == "1x1x1"


@pytest.mark.parametrize("bad", [
    "nope|32x32|f32|1x1|cpu",       # unknown op
    "diffusion.vmem_loop|32x|f32|1x1|cpu",  # malformed shape
    "diffusion.vmem_loop|32x32|f32|1x1",    # missing field
    "diffusion.vmem_loop|32x32|f32|0x1|cpu",  # degenerate topology
])
def test_parse_key_rejects(bad):
    with pytest.raises(ValueError):
        tkeys.parse_key(bad)


def test_unknown_op_rejected_at_key_build():
    with pytest.raises(ValueError, match="unknown tunable op"):
        tkeys.tuning_key("diffusion.bogus", (32, 32), "f32", backend="cpu")


# ---------------------------------------------------------------------------
# Cache document
# ---------------------------------------------------------------------------


def test_store_load_roundtrip_atomic(tmp_path):
    path = tmp_path / "c.json"
    key = tkeys.tuning_key("wave.vmem_loop", (32, 32), "f32", backend="cpu")
    tcache.store(path, key, _entry({"chunk": 16}))
    assert not (tmp_path / "c.json.tmp").exists()  # atomic rename
    doc = tcache.load(path)
    assert tcache.validate_doc(doc, str(path)) == []
    got = tcache.lookup(doc, key, tkeys.fingerprint("cpu"))
    assert got == {"chunk": 16}
    # A second store of another key keeps the first (read-modify-write).
    key2 = tkeys.tuning_key("swe.vmem_loop", (32, 32), "f32", backend="cpu")
    tcache.store(path, key2, _entry({"chunk": 64}))
    doc = tcache.load(path)
    assert len(doc["entries"]) == 2


def test_torn_file_reads_empty_with_warning(tmp_path):
    path = tmp_path / "torn.json"
    path.write_text('{"v": 1, "kind": "rmt-tuning-cache", "entr')  # torn
    with pytest.warns(UserWarning, match="unreadable"):
        doc = tcache.load(path)
    assert doc == tcache.empty_doc()


def test_alien_document_reads_empty(tmp_path):
    path = tmp_path / "alien.json"
    path.write_text(json.dumps({"metrics": {}}))  # a BENCH record, say
    with pytest.warns(UserWarning, match="not a v1"):
        assert tcache.load(path) == tcache.empty_doc()


def test_missing_file_is_silent_empty(tmp_path):
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # the cold start must not warn
        assert tcache.load(tmp_path / "never.json") == tcache.empty_doc()


def test_stale_fingerprint_is_miss_not_crash():
    key = tkeys.tuning_key("diffusion.vmem_loop", (32, 32), "f32",
                           backend="cpu")
    doc = tcache.empty_doc()
    doc["entries"][tkeys.key_str(key)] = _entry(
        {"chunk": 16}, fp={"jax": "9.9.99", "backend": "cpu"}
    )
    assert tcache.lookup(doc, key, tkeys.fingerprint("cpu")) is None
    # Backend drift in the fingerprint is equally stale, and the stale
    # entry stays in the document (ignored, never deleted).
    doc["entries"][tkeys.key_str(key)] = _entry(
        {"chunk": 16}, fp={"jax": tkeys.fingerprint("cpu")["jax"],
                           "backend": "tpu"}
    )
    assert tcache.lookup(doc, key, tkeys.fingerprint("cpu")) is None
    assert len(doc["entries"]) == 1


def test_validate_doc_flags_drift(tmp_path):
    doc = tcache.empty_doc()
    doc["entries"]["diffusion.vmem_loop|32x32|f32|1x1|cpu"] = {
        "config": {"chunk": 16},  # missing median_us/compile_s/...
        "fingerprint": {"jax": "0.4.37", "backend": "cpu"},
    }
    problems = tcache.validate_doc(doc, "x.json")
    assert any("median_us" in p for p in problems)
    doc2 = tcache.empty_doc()
    doc2["entries"]["not-a-key"] = _entry({"chunk": 16})
    assert any("malformed tuning key" in p
               for p in tcache.validate_doc(doc2, "y.json"))


# ---------------------------------------------------------------------------
# Space admission
# ---------------------------------------------------------------------------


def test_space_vmem_admission_and_pad():
    # Over the VMEM budget (f32 compute width): nothing to enumerate.
    assert tspace.enumerate_space("diffusion.vmem_loop", (1024, 1024),
                                  "f32") == []
    # pow2 shape: no pad candidates (nothing to pad).
    cands = tspace.enumerate_space("diffusion.vmem_loop", (32, 32), "f32")
    assert cands and all(not c["pad_pow2"] for c in cands)
    # Non-pow2: pad candidates appear alongside.
    cands = tspace.enumerate_space("diffusion.vmem_loop", (20, 24), "f32")
    assert any(c["pad_pow2"] for c in cands)
    # All chunks stay >= 4: 1..3 switch the kernel body form (a
    # different fp expression), which would break the bitwise contract.
    assert all(c["chunk"] >= 4 for c in cands)


def test_space_cpu_backend_caps_chunk():
    cands = tspace.enumerate_space("diffusion.vmem_loop", (32, 32), "f32",
                                   backend="cpu")
    assert cands and all(c["chunk"] <= 16 for c in cands)


def test_space_masked_step_only_for_hbm_class():
    assert tspace.enumerate_space("diffusion.masked_step", (64, 64),
                                  "f32") == []  # VMEM loop serves it
    cands = tspace.enumerate_space("diffusion.masked_step", (4096, 4096),
                                   "f32")
    assert cands and all(4096 % c["tm"] == 0 and c["tm"] % 8 == 0
                         for c in cands)


def test_space_deep_clamps_to_shard():
    ks = [c["k"] for c in
          tspace.enumerate_space("diffusion.deep", (16, 16), "f32")]
    assert ks and max(ks) <= 16


# ---------------------------------------------------------------------------
# Traffic gate
# ---------------------------------------------------------------------------


def test_gate_rejects_overbudget_pad():
    g = tgate.validate_config(
        "diffusion.vmem_loop", (140, 140), "f32",
        {"body_form": "eqc", "pad_pow2": True, "chunk": 16},
    )
    assert not g.ok and g.ratio > 3.0 and "rejected" in g.reason
    ok = tgate.validate_config(
        "diffusion.vmem_loop", (252, 252), "f32",
        {"body_form": "conly", "pad_pow2": True, "chunk": 256},
    )
    assert ok.ok and ok.ratio < 1.1  # 252² -> 256² is a 3% pad


def test_gate_masked_step_stripe_budget():
    assert not tgate.validate_config("diffusion.masked_step",
                                     (4096, 4096), "f32", {"tm": 8}).ok
    assert tgate.validate_config("diffusion.masked_step",
                                 (4096, 4096), "f32", {"tm": 64}).ok


def test_gate_validate_entry_from_key_alone():
    key = tkeys.parse_key("diffusion.vmem_loop|140x140|f32|1x1|cpu")
    g = tgate.validate_entry(key, _entry(
        {"body_form": "eqc", "pad_pow2": True, "chunk": 16}
    ))
    assert not g.ok


def test_gate_scan_is_traffic_neutral():
    assert tgate.validate_config("diffusion.scan", (64, 64), "f32",
                                 {"chunk": 64}).ok


def test_gate_rejects_invalid_vmem_knobs():
    """The loud half of malformed-entry defense: the runtime sanitizer
    silently drops knobs that would crash a kernel; `validate` must
    instead FAIL a committed entry carrying them."""
    for bad in (
        {"chunk": -8}, {"chunk": 9}, {"chunk": 2},  # not pow2 >= 4
        {"body_form": "bogus"},
        {"pad_pow2": "yes"},
    ):
        g = tgate.validate_config("diffusion.vmem_loop", (32, 32), "f32",
                                  bad)
        assert not g.ok, bad


# ---------------------------------------------------------------------------
# The resolve chokepoint
# ---------------------------------------------------------------------------


def test_resolve_hit_miss_and_stats(_isolated_resolve):
    key = tkeys.tuning_key("diffusion.vmem_loop", (20, 24), "f32",
                           backend="cpu")
    _write_cache(_isolated_resolve,
                 {tkeys.key_str(key): _entry({"body_form": "conly"})})
    assert tresolve.resolve("diffusion.vmem_loop", (20, 24), "f32") == {
        "body_form": "conly"
    }
    assert tresolve.resolve("diffusion.vmem_loop", (64, 64), "f32") is None
    assert tresolve.stats() == {"hits": 1, "misses": 1}


def test_resolve_unreadable_cache_is_miss(_isolated_resolve):
    _isolated_resolve.write_text("{{{{")
    tresolve.refresh()
    with pytest.warns(UserWarning):
        assert tresolve.resolve("diffusion.vmem_loop", (20, 24),
                                "f32") is None


def test_resolve_deep_k_revalidates_against_grid(_isolated_resolve):
    from rocm_mpi_tpu.parallel.deep_halo import resolve_deep_k
    from rocm_mpi_tpu.parallel.mesh import init_global_grid

    grid = init_global_grid(16, 16, lengths=(10.0, 10.0), dims=(1, 1))
    key = tkeys.tuning_key("diffusion.deep", grid.local_shape, "f32",
                           topology=grid.dims, backend="cpu")
    _write_cache(_isolated_resolve, {tkeys.key_str(key): _entry({"k": 8})})
    assert resolve_deep_k(grid, jnp.float32, "auto") == 8
    assert resolve_deep_k(grid, jnp.float32, None) is None
    # A cached depth deeper than the shard (a reshard shrank it) falls
    # back silently instead of crashing the auto run.
    _write_cache(_isolated_resolve, {tkeys.key_str(key): _entry({"k": 32})})
    assert resolve_deep_k(grid, jnp.float32, "auto") is None


# ---------------------------------------------------------------------------
# config="auto" — bitwise vs the default paths (acceptance)
# ---------------------------------------------------------------------------


def _models(shape=(16, 16), nt=8, warmup=4):
    from rocm_mpi_tpu.config import DiffusionConfig
    from rocm_mpi_tpu.models import (
        AcousticWave,
        HeatDiffusion,
        ShallowWater,
        SWEConfig,
        WaveConfig,
    )

    common = dict(global_shape=shape, lengths=(10.0,) * len(shape),
                  nt=nt, warmup=warmup, dtype="f32",
                  dims=(1,) * len(shape))
    return (
        HeatDiffusion(DiffusionConfig(**common)),
        AcousticWave(WaveConfig(**common)),
        ShallowWater(SWEConfig(**common)),
    )


def test_auto_equals_default_bitwise_on_cold_cache(_isolated_resolve):
    """Empty cache: every config='auto' lookup misses and the fallback
    must be the hand defaults BITWISE, all three workloads."""
    diff, wave, swe = _models()
    d0 = diff.run_vmem_resident().T
    d1 = _models()[0].run_vmem_resident(config="auto").T
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    w0 = wave.run_vmem_resident().U
    w1 = _models()[1].run_vmem_resident(config="auto").U
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(w1))
    s0 = swe.run_vmem_resident().h
    s1 = _models()[2].run_vmem_resident(config="auto").h
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    assert tresolve.stats()["misses"] >= 3
    assert tresolve.stats()["hits"] == 0


def test_auto_equals_default_bitwise_with_tuned_cache(_isolated_resolve):
    """A warm cache steers config='auto' through the RESOLVED knobs —
    and because the vmem-loop space only contains bitwise-safe knobs
    (pad_pow2 is interior-bitwise-pinned, chunks stay in one body-form
    class), the tuned run stays bitwise-equal to the default run."""
    shape = (20, 24)  # non-pow2: the pad knob actually engages
    entries = {}
    for op, config in (
        ("diffusion.vmem_loop",
         {"body_form": "eqc", "pad_pow2": True, "chunk": 4}),
        ("wave.vmem_loop", {"chunk": 4}),
        ("swe.vmem_loop", {"chunk": 4}),
    ):
        key = tkeys.tuning_key(op, shape, "f32", backend="cpu")
        entries[tkeys.key_str(key)] = _entry(config)
    _write_cache(_isolated_resolve, entries)

    d0 = _models(shape)[0].run_vmem_resident().T
    d1 = _models(shape)[0].run_vmem_resident(config="auto").T
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    w0 = _models(shape)[1].run_vmem_resident().U
    w1 = _models(shape)[1].run_vmem_resident(config="auto").U
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(w1))
    s0 = _models(shape)[2].run_vmem_resident().h
    s1 = _models(shape)[2].run_vmem_resident(config="auto").h
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    assert tresolve.stats()["hits"] >= 3  # the cache really steered


def test_auto_scan_driver_bitwise(_isolated_resolve):
    """The scan drivers' auto chunk: tuned q is bitwise (scan==step is
    pinned at any q); a cold cache falls back to the default window."""
    key = tkeys.tuning_key("diffusion.scan", (16, 16), "f32",
                           backend="cpu")
    _write_cache(_isolated_resolve, {tkeys.key_str(key): _entry(
        {"chunk": 2}
    )})
    r0 = _models()[0].run(variant="fused")
    r1 = _models()[0].run(variant="fused", config="auto")
    np.testing.assert_array_equal(np.asarray(r0.T), np.asarray(r1.T))
    assert tresolve.stats()["hits"] >= 1


def test_masked_step_auto_tm_bitwise(monkeypatch, _isolated_resolve):
    """masked_step's tm knob through the auto path: force the HBM-class
    route with a tiny budget, cache tm=16, and pin bitwise equality with
    the automatic height (the striped kernel computes the same
    expression per element at any tm)."""
    import rocm_mpi_tpu.ops.pallas_kernels as pk

    monkeypatch.setattr(pk, "_VMEM_BLOCK_BUDGET_BYTES", 1024)
    shape = (64, 48)
    key = tkeys.tuning_key("diffusion.masked_step", shape, "f32",
                           backend="cpu")
    _write_cache(_isolated_resolve, {tkeys.key_str(key): _entry(
        {"tm": 16}
    )})
    rng = np.random.default_rng(0)
    T = jnp.asarray(rng.random(shape), jnp.float32)
    Cm = jnp.asarray(rng.random(shape) * 1e-4, jnp.float32)
    ref = pk.masked_step(T, Cm, (0.1, 0.1))
    got = pk.masked_step(T, Cm, (0.1, 0.1), config="auto")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert tresolve.stats()["hits"] == 1
    # A cached tm violating the shape's constraints is ignored silently.
    _write_cache(_isolated_resolve, {tkeys.key_str(key): _entry(
        {"tm": 24}  # 64 % 24 != 0
    )})
    got2 = pk.masked_step(T, Cm, (0.1, 0.1), config="auto")
    np.testing.assert_array_equal(np.asarray(got2), np.asarray(ref))


def test_malformed_cache_entry_degrades_not_crashes(_isolated_resolve):
    """A cache entry is untrusted input: knobs that would crash a kernel
    (chunk=-8, body_form='bogus') are dropped at the resolve chokepoint
    and the run degrades to the defaults BITWISE — 'a cache is an
    accelerator, not a dependency'."""
    key = tkeys.tuning_key("diffusion.vmem_loop", (16, 16), "f32",
                           backend="cpu")
    _write_cache(_isolated_resolve, {tkeys.key_str(key): _entry(
        {"chunk": -8, "body_form": "bogus", "pad_pow2": "yes"}
    )})
    d0 = _models()[0].run_vmem_resident().T
    d1 = _models()[0].run_vmem_resident(config="auto").T  # must not raise
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    # Every field was invalid -> the sanitized config is empty -> a miss.
    assert tresolve.stats()["hits"] == 0


def test_last_pad_applied_deprecated_shim():
    import rocm_mpi_tpu.ops.pallas_kernels as pk

    rng = np.random.default_rng(1)
    T = jnp.asarray(rng.random((20, 24)), jnp.float32)
    Cp = jnp.asarray(1.0 + rng.random((20, 24)), jnp.float32)
    pk.fused_multi_step(T, Cp, 1.0, 1e-5, (0.1, 0.1), n_steps=4,
                        chunk=4, pad_pow2=True)
    with pytest.warns(DeprecationWarning, match="plan_vmem_loop"):
        assert pk.last_pad_applied() is True
    # The replacement answers the same question purely, no run needed.
    assert pk.plan_vmem_loop((20, 24), "float32", 4, chunk=4,
                             pad_pow2=True).pad_applied is True


# ---------------------------------------------------------------------------
# Search (+ THE TEETH)
# ---------------------------------------------------------------------------


def test_search_persists_winner_then_pure_hit(tmp_path):
    from rocm_mpi_tpu.tuning import search as tsearch

    path = tmp_path / "s.json"
    cands = [
        {"body_form": "eqc", "pad_pow2": False, "chunk": 4},
        {"body_form": "conly", "pad_pow2": False, "chunk": 4},
    ]
    r1 = tsearch.search_op("diffusion.vmem_loop", (16, 16), "f32",
                           repeats=1, cache_path=path, candidates=cands)
    assert r1["status"] == "tuned"
    assert r1["entry"]["config"] in cands
    assert tcache.validate_doc(tcache.load(path)) == []
    # Second run: fingerprint-valid entry -> NO measurement at all.
    r2 = tsearch.search_op("diffusion.vmem_loop", (16, 16), "f32",
                           repeats=1, cache_path=path, candidates=cands)
    assert r2["status"] == "hit"
    assert r2["entry"]["config"] == r1["entry"]["config"]


def test_search_gate_rejects_doctored_fast_winner(tmp_path, monkeypatch):
    """THE TEETH (the tuning twin of perf's --include-waste-fixture): a
    config that MEASURES fastest but models over the A_eff budget must
    not win — the gate kicks it and the next-fastest in-budget candidate
    is persisted instead. The runner is stubbed so the doctored pad
    config is deterministically 10x 'faster'."""
    from rocm_mpi_tpu.tuning import search as tsearch

    overbudget = {"body_form": "eqc", "pad_pow2": True, "chunk": 4}
    honest = {"body_form": "eqc", "pad_pow2": False, "chunk": 4}

    def fake_runner(op, shape, dtype):
        return lambda config: 1e-6 if config["pad_pow2"] else 1e-5

    monkeypatch.setattr(tsearch, "_make_runner", fake_runner)
    path = tmp_path / "teeth.json"
    # (140,140) pads to (256,256): 3.3x the ideal bytes, over the 1.5
    # vmem_loop budget.
    r = tsearch.search_op("diffusion.vmem_loop", (140, 140), "f32",
                          repeats=1, cache_path=path,
                          candidates=[overbudget, honest])
    assert r["status"] == "tuned"
    assert r["entry"]["config"] == honest
    assert r["rejected"] and r["rejected"][0][0] == overbudget
    assert "rejected" in r["rejected"][0][1]
    # And when EVERY candidate is over budget, nothing is cached.
    r2 = tsearch.search_op("diffusion.vmem_loop", (140, 140), "f32",
                           repeats=1, cache_path=tmp_path / "none.json",
                           candidates=[overbudget])
    assert r2["status"] == "all-rejected" and r2["entry"] is None
    assert not (tmp_path / "none.json").exists()


def test_search_empty_space_is_clean_noop(tmp_path):
    from rocm_mpi_tpu.tuning import search as tsearch

    r = tsearch.search_op("diffusion.masked_step", (16, 16), "f32",
                          repeats=1, cache_path=tmp_path / "e.json")
    assert r["status"] == "empty"


# ---------------------------------------------------------------------------
# CLI (in-process; the acceptance drill's verbs)
# ---------------------------------------------------------------------------


def _cli(argv):
    from rocm_mpi_tpu.tuning.__main__ import main

    return main(argv)


def test_cli_search_validate_show_and_warm_determinism(
    tmp_path, monkeypatch, capsys
):
    """The acceptance drill, in-process: `search` produces a
    schema-valid cache for diffusion + wave; a second run is a pure
    cache hit — byte-identical file, no re-search, and
    compiles.steady_state == 0 with the cache warm; `validate` passes;
    an injected over-budget config makes `validate` exit 1."""
    from rocm_mpi_tpu.telemetry import compiles

    # Tiny candidate chunks: the CLI honors the module space, and the
    # test must not pay chunk-16 interpret traces per candidate.
    monkeypatch.setattr(tspace, "_CHUNKS", (4,))
    path = tmp_path / "cli.json"
    argv = ["search", "--shape", "16x16", "--repeats", "1",
            "--cache", str(path)]
    assert _cli(argv) == 0
    err1 = capsys.readouterr().err
    assert "tuned" in err1
    blob1 = path.read_bytes()

    compiles.reset()  # model the acceptance's fresh second process
    assert _cli(argv) == 0
    err2 = capsys.readouterr().err
    assert "2 hit(s), 0 tuned" in err2
    assert "compiles.steady_state=0" in err2
    assert path.read_bytes() == blob1  # deterministic: a pure hit

    assert _cli(["validate", str(path)]) == 0
    assert _cli(["show", "--cache", str(path)]) == 0
    out = capsys.readouterr().out
    assert "diffusion.vmem_loop|16x16|f32|1x1|cpu" in out

    # Inject an over-budget entry: the gate must fail validate (exit 1).
    doc = json.loads(blob1)
    doc["entries"]["diffusion.vmem_loop|140x140|f32|1x1|cpu"] = _entry(
        {"body_form": "eqc", "pad_pow2": True, "chunk": 4}
    )
    doctored = tmp_path / "doctored.json"
    doctored.write_text(json.dumps(doc))
    assert _cli(["validate", str(doctored)]) == 1
    assert "A_eff ideal" in capsys.readouterr().err


def test_cli_validate_exit_codes(tmp_path, capsys):
    assert _cli(["validate"]) == 2  # no paths
    assert _cli(["validate", str(tmp_path / "missing.json")]) == 2
    torn = tmp_path / "torn.json"
    torn.write_text('{"v": 1, "kin')
    # A torn COMMITTED file fails strictly (unlike the runtime's
    # tolerant read, which degrades to a miss).
    assert _cli(["validate", str(torn)]) == 1


def test_cli_search_usage_errors(capsys):
    assert _cli(["search", "--shape", "banana"]) == 2
    assert _cli(["search", "--repeats", "0"]) == 2
