"""Driver-output contract of bench.py (round-4 VERDICT #1).

The driver captures only the LAST ~4 KB of stdout and parses the final
line; round 3 lost its headline when the full detail blob outgrew that
window (BENCH_r03.json parsed=null). These tests pin the contract:
the compact summary stays well under 2 KB whatever the detail holds,
and the section registry stays consistent with its error-key map.
"""

import importlib.util
import json
import os
import sys

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench.py")


def _load_bench():
    spec = importlib.util.spec_from_file_location("bench_mod", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("bench_mod", mod)
    spec.loader.exec_module(mod)
    return mod


def _fat_result():
    """A result dict with every field populated and a deliberately
    bloated extra_configs blob (the round-3 failure shape)."""
    extras = {
        "dtd_gemm": {"panel_fused_gflops": 124903.9, "panel_fused_n":
                     16384, "compile_s": 150.0, "note": "x" * 500},
        "host_dtd": {"host_runtime_gflops": 985.0, "note": "y" * 500},
        "transformer": {"flash_gflops": 79600.1, "note": "z" * 500},
        "geqrf": {"compiled_gflops": 2430.6},
        "geqrf_fused": {"gflops": 104985.7,
                        "precision_variant": {"gflops": 30000.0}},
        "getrf_fused": {"gflops": 63193.8, "note": "w" * 500},
        "ooc_potrf": {"gflops": 5.5, "hbm_measured": {"spills": 5},
                      "note": "v" * 500},
        "taskrate": {"tasks_per_sec": 9876.5, "n_tasks": 20000,
                     "tasks_per_sec_native": 702199.7,
                     "tasks_per_sec_python": 9000.6,
                     "native_vs_python": 78.02,
                     "overhead_us_per_task": 101.2,
                     "stage_us_per_task": {"insert": 34.4, "select": 1.8,
                                           "dispatch": 13.4,
                                           "release": 8.2},
                     "native_stage_counts": {"inserted": 20000,
                                             "stolen": 11268},
                     "note": "u" * 300},
    }
    return {
        "metric": "tiled_potrf_gflops_per_chip",
        "value": 110000.12, "unit": "GFLOP/s", "vs_baseline": 1.0789,
        "detail": {
            "backend": "tpu", "n": 40960, "tile": 1024,
            "peak_proxy_gemm_gflops": 156912.34,
            "target_gflops_65pct_peak": 101993.02,
            "compile_s": 40.12, "run_s": 0.5432,
            "rel_residual_check": 4.119e-06,
            "precision_variant": {"gflops": 29833.33,
                                  "rel_residual_check": 4.518e-07},
            "latency": {"eager_1k_p50_us": 508.7,
                        "rdv_1M_p50_us": 3521.0,
                        "device_64k_p50_us": 132313.2,
                        "device_64k_link_us": 120000.0,
                        "device_64k_runtime_us": 12313.2,
                        # ISSUE 12 device-plane rows
                        "device_64k_nopipe_p50_us": 232313.2,
                        "host_64k_p50_us": 31000.5,
                        "device_hop_ratio": 4.27,
                        "device_64k_overlap_pct": 38.2,
                        "device_pipeline_ab_ok": True,
                        "ici_64k_p50_us": 787.8,
                        "ici_64k_wire_bytes_per_hop": 148.0},
            "extra_configs": extras,
        },
    }


def test_compact_summary_fits_tail_window():
    bench = _load_bench()
    line = bench._compact_summary(_fat_result())
    assert len(line.encode()) < 2000, len(line)
    parsed = json.loads(line)
    assert parsed["metric"] == "tiled_potrf_gflops_per_chip"
    assert parsed["value"] == 110000.12
    assert parsed["vs_baseline"] == 1.0789
    d = parsed["detail"]
    assert d["gemm_panel_fused_gflops"] == 124903.9
    assert d["host_dtd_gflops"] == 985.0
    assert d["flash_gflops"] == 79600.1
    assert d["getrf_fused_gflops"] == 63193.8
    assert d["geqrf_fused_gflops"] == 104985.7
    assert d["tasks_per_sec"] == 9876.5
    assert d["tasks_per_sec_native"] == 702199.7
    assert d["tasks_per_sec_python"] == 9000.6
    assert d["taskrate_native_ratio"] == 78.02
    assert d["taskrate_stage_us"]["insert"] == 34.4


def test_compact_summary_parses_from_4k_tail():
    """Simulate the driver: full blob line + compact line, tail 4 KB,
    parse the last nonempty line."""
    bench = _load_bench()
    result = _fat_result()
    out = json.dumps(result) + "\n" + bench._compact_summary(result) + "\n"
    tail = out.encode()[-4096:].decode(errors="replace")
    last = [ln for ln in tail.splitlines() if ln.strip()][-1]
    parsed = json.loads(last)
    assert parsed["value"] == 110000.12


def test_compact_summary_survives_error_rows():
    bench = _load_bench()
    result = _fat_result()
    result["detail"]["extra_configs"] = {
        k: {"error": "boom"} for k in result["detail"]["extra_configs"]}
    line = bench._compact_summary(result)
    assert len(line.encode()) < 2000
    parsed = json.loads(line)
    # the headline survives; errored sections' rows are either present
    # as null or shed by the size relief valve (the guards skip
    # missing keys on either side) — never a bogus number
    assert parsed["value"] == 110000.12
    assert parsed["detail"].get("gemm_panel_fused_gflops") is None


def test_section_keys_cover_registry():
    bench = _load_bench()
    assert set(bench._SECTION_KEYS) == set(bench.SECTIONS)


# ---- generic regression guard (round 6: every GFLOPS row guarded, ----
# ---- prior capture parsed as JSON instead of first-regex-hit) --------

def test_compare_captures_flags_gflops_drop():
    bench = _load_bench()
    prior = {"value": 100000.0, "getrf_fused_gflops": 60000.0,
             "flash_gflops": 90000.0}
    cur = {"value": 95000.0,              # -5%: inside the band
           "getrf_fused_gflops": 50000.0,  # -17%: fires
           "flash_gflops": 91000.0}        # improvement: quiet
    out = bench._compare_captures(cur, prior)
    assert "latency_regression" not in out
    reg = out["throughput_regression"]
    assert "getrf_fused_gflops" in reg and "-17%" in reg, reg
    assert "value" not in reg and "flash" not in reg, reg


def test_compare_captures_guards_tasks_per_sec():
    """The taskrate row rides the same >10%-drop guard as the GFLOPS
    rows (higher-is-better, identical direction)."""
    bench = _load_bench()
    prior = {"tasks_per_sec": 10000.0, "host_dtd_gflops": 2000.0}
    out = bench._compare_captures(
        {"tasks_per_sec": 8000.0, "host_dtd_gflops": 2100.0}, prior)
    reg = out["throughput_regression"]
    assert "tasks_per_sec" in reg and "-20%" in reg, reg
    assert "host_dtd" not in reg
    # within-band / improvements stay quiet
    assert bench._compare_captures(
        {"tasks_per_sec": 9500.0, "host_dtd_gflops": 2000.0}, prior) == {}


def test_native_taskrate_keys_registered_and_guarded():
    """ISSUE 10 bench contract: the native-vs-python taskrate A/B keys
    land in the compact summary and BOTH engine rates ride the
    throughput drop-guard; the serving native A/B row is carried too."""
    bench = _load_bench()
    assert "tasks_per_sec_native" in bench._GFLOPS_GUARD_KEYS
    assert "tasks_per_sec_python" in bench._GFLOPS_GUARD_KEYS
    prior = {"tasks_per_sec_native": 700000.0,
             "tasks_per_sec_python": 10000.0}
    out = bench._compare_captures(
        {"tasks_per_sec_native": 100000.0,       # -86%: the native loop
         "tasks_per_sec_python": 9800.0}, prior)  # silently fell back?
    assert "tasks_per_sec_native" in out["throughput_regression"]
    assert "tasks_per_sec_python" not in out["throughput_regression"]
    # serving native A/B: recorded in the compact summary
    result = _fat_result()
    result["detail"]["extra_configs"]["serving"] = {
        "requests_per_sec": 55.7, "native_vs_python": 2.26,
        "p99_ms": 13.7}
    compact = json.loads(bench._compact_summary(result))
    assert compact["detail"]["serving_native_ratio"] == 2.26


def test_device_plane_keys_registered_and_guarded():
    """ISSUE 12 bench contract: the device-plane rows land in the
    compact summary, and the device hop p50, the device/host hop RATIO
    and the ICI hop all ride the latency rise-guard — the device-direct
    win cannot silently regress."""
    bench = _load_bench()
    for key in ("device_64k_p50_us", "device_hop_ratio",
                "ici_64k_p50_us"):
        assert key in bench._LATENCY_GUARD_KEYS, key
    compact = json.loads(bench._compact_summary(_fat_result()))
    d = compact["detail"]
    assert d["device_hop_ratio"] == 4.27
    assert d["device_64k_nopipe_p50_us"] == 232313.2
    assert d["ici_64k_p50_us"] == 787.8
    assert d["ici_64k_wire_bytes_per_hop"] == 148.0
    # full-detail-only rows stay OUT of the size-capped compact line
    assert "device_64k_overlap_pct" not in d
    assert "host_64k_p50_us" not in d
    prior = {"device_64k_p50_us": 10000.0, "device_hop_ratio": 3.0,
             "ici_64k_p50_us": 800.0}
    out = bench._compare_captures(
        {"device_64k_p50_us": 10500.0,       # +5%: inside the band
         "device_hop_ratio": 4.9,            # +63%: ratio fires
         "ici_64k_p50_us": 780.0}, prior)    # improvement: quiet
    reg = out["latency_regression"]
    assert "device_hop_ratio" in reg, reg
    assert "ici_64k_p50_us" not in reg and \
        "device_64k_p50_us" not in reg, reg


def test_compare_captures_flags_latency_rise_only_on_worsening():
    bench = _load_bench()
    prior = {"rdv_1M_p50_us": 3687.0, "eager_1k_p50_us": 512.0}
    out = bench._compare_captures(
        {"rdv_1M_p50_us": 4441.0, "eager_1k_p50_us": 500.0}, prior)
    assert "rdv_1M_p50_us" in out["latency_regression"]
    assert "eager" not in out["latency_regression"]
    # an improvement or a within-band change stays quiet
    assert bench._compare_captures(
        {"rdv_1M_p50_us": 3200.0, "eager_1k_p50_us": 520.0}, prior) == {}


def test_compare_captures_skips_missing_and_error_rows():
    """A failed section (error row / missing key / null) must not read
    as a regression in either direction."""
    bench = _load_bench()
    prior = {"value": 100000.0, "getrf_fused_gflops": None,
             "rdv_1M_p50_us": 3600.0}
    assert bench._compare_captures(
        {"value": None, "getrf_fused_gflops": 10.0}, prior) == {}


def test_parse_capture_file_prefers_parsed_json(tmp_path):
    """ADVICE r5 #3 regression shape: the driver record's stdout tail
    contains the SAME key with a different (stale) value than the
    parsed compact summary — the loader must take the parsed one, not
    the first textual hit."""
    bench = _load_bench()
    rec = {
        "n": 9, "rc": 0,
        "tail": '..."rdv_1M_p50_us": 9999.0, "getrf_fused_gflops": '
                '11111.0 ... stale full-blob fragment',
        "parsed": {"metric": "m", "value": 104769.4,
                   "detail": {"rdv_1M_p50_us": 4440.9,
                              "getrf_fused_gflops": 55460.1}},
    }
    p = tmp_path / "BENCH_r98.json"
    p.write_text(json.dumps(rec))
    base, flat = bench._parse_capture_file(str(p))
    assert base == "BENCH_r98.json"
    assert flat["rdv_1M_p50_us"] == 4440.9
    assert flat["getrf_fused_gflops"] == 55460.1
    assert flat["value"] == 104769.4


def test_throughput_guard_end_to_end(tmp_path, monkeypatch):
    bench = _load_bench()
    rec = {"parsed": {"value": 110000.0,
                      "detail": {"getrf_fused_gflops": 60000.0}}}
    (tmp_path / "BENCH_r07.json").write_text(json.dumps(rec))
    monkeypatch.setattr(bench, "_HERE", str(tmp_path))
    result = _fat_result()
    result["value"] = 90000.0                       # -18% vs prior
    result["detail"]["extra_configs"]["getrf_fused"]["gflops"] = 63193.8
    bench._throughput_regression_guard(result)
    reg = result["detail"]["throughput_regression"]
    assert "value: 110000.0 -> 90000.0" in reg and \
        "vs BENCH_r07.json" in reg, reg
    # ...and the compact summary carries it to the driver tail
    line = bench._compact_summary(result)
    assert "throughput_regression" in json.loads(line)["detail"]


def test_throughput_guard_quiet_without_prior(tmp_path, monkeypatch):
    bench = _load_bench()
    monkeypatch.setattr(bench, "_HERE", str(tmp_path))
    result = _fat_result()
    bench._throughput_regression_guard(result)
    assert "throughput_regression" not in result["detail"]
    assert "throughput_guard_error" not in result["detail"]


def test_trimmed_median():
    bench = _load_bench()
    assert bench._trimmed_median([3.0, 1.0, 2.0]) == 2.0
    # ≥5 samples: extremes dropped before the median
    assert bench._trimmed_median([100.0, 1.0, 2.0, 3.0, 4.0]) == 3.0
    # even counts: true median (mean of the two middles), no
    # upper-middle bias
    assert bench._trimmed_median([500.0, 510.0, 520.0, 900.0]) == 515.0
    assert bench._trimmed_median([1.0, 2.0]) == 1.5


def test_amort_probe_zero_recompile_smoke(tmp_path):
    """Tier-1 CPU-sized smoke of the compile_amortization serving
    claim, through the bench's own probe path: a warm serve (simulated
    fresh process: in-process jit store cleared, persistent store kept)
    pays ZERO XLA compiles and reports full store hits."""
    from parsec_tpu.utils import compile_cache as cc
    from parsec_tpu.utils import mca_param
    import jax

    bench = _load_bench()
    prev = jax.config.jax_compilation_cache_dir
    d = str(tmp_path / "amort")
    try:
        cc.reset_in_process_cache()          # honest cold, any ordering
        cold = bench._amort_probe_run("panel", 192, 64, d)
        assert cold["xla_compiles"] > 0
        assert cold["store_misses"] == cold["n_programs"]
        cc.reset_in_process_cache()          # "second process"
        warm = bench._amort_probe_run("panel", 192, 64, d)
        assert warm["xla_compiles"] == 0, warm
        assert warm["store_hits"] == warm["n_programs"]
        assert warm["store_misses"] == 0
    finally:
        # the probe sets process-global knobs (it normally runs in its
        # own subprocess) — restore them for the rest of the suite
        mca_param.unset("jit.cache_dir")
        mca_param.unset("potrf.trsm_hook")
        cc.disable_compile_cache()
        jax.config.update("jax_compilation_cache_dir", prev)


def test_zero_baseline_latency_rows_fire_absolutely():
    """The compile-count guard keys are 0 in every healthy capture —
    a relative rise can never fire on a zero prior, so any nonzero
    current value must fire absolutely (the 'warm stays at ZERO
    compiles' guard would otherwise be structurally dead)."""
    bench = _load_bench()
    prior = {"amort_panel_warm_compiles": 0.0, "rdv_1M_p50_us": 3600.0}
    out = bench._compare_captures(
        {"amort_panel_warm_compiles": 46.0, "rdv_1M_p50_us": 3600.0},
        prior)
    assert "amort_panel_warm_compiles" in out["latency_regression"]
    assert "zero-baseline" in out["latency_regression"]
    # 0 -> 0 stays quiet
    assert bench._compare_captures(
        {"amort_panel_warm_compiles": 0.0}, prior) == {}


def test_serving_section_registered():
    """--section serving is a first-class section: registry, compact
    summary and both regression guards stay wired together (ISSUE 8
    bench contract: requests/s rides throughput_regression, p99 rides
    the latency rise-guard)."""
    bench = _load_bench()
    assert "serving" in bench.SECTIONS
    assert bench._SECTION_KEYS["serving"] == ("serving",)
    assert "serving_requests_per_sec" in bench._GFLOPS_GUARD_KEYS
    assert "serving_p99_ms" in bench._LATENCY_GUARD_KEYS
    result = _fat_result()
    result["detail"]["extra_configs"]["serving"] = {
        "requests_per_sec": 55.7, "p99_ms": 13.7,
        "p99_ratio_worst": 0.92, "shed_count": 20,
        "quarantine_count": 2, "isolation_check": "OK"}
    compact = json.loads(bench._compact_summary(result))
    d = compact["detail"]
    assert d["serving_requests_per_sec"] == 55.7
    assert d["serving_p99_ms"] == 13.7
    assert d["serving_p99_ratio"] == 0.92
    assert d["serving_shed"] == 20
    assert d["serving_quarantined"] == 2
    assert d["serving_isolation"] == "OK"


def test_serving_guard_rows_fire_in_both_directions():
    bench = _load_bench()
    prior = {"serving_requests_per_sec": 50.0, "serving_p99_ms": 10.0}
    out = bench._compare_captures(
        {"serving_requests_per_sec": 40.0, "serving_p99_ms": 13.0},
        prior)
    assert "serving_requests_per_sec" in out["throughput_regression"]
    assert "serving_p99_ms" in out["latency_regression"]
    # within-band changes stay quiet
    assert bench._compare_captures(
        {"serving_requests_per_sec": 49.0, "serving_p99_ms": 10.5},
        prior) == {}


def test_serving_kv_section_registered():
    """--section serving_kv is a first-class section (ISSUE 15 bench
    contract): registry, error keys, compact summary, and the guards
    stay wired — sustained req/s, the >=3x sharing speedup, the
    prefix-cache hit rate, and prefill-tokens/s ride the throughput
    drop-guard; the share arm's p99 rides the latency rise-guard."""
    bench = _load_bench()
    assert "serving_kv" in bench.SECTIONS
    assert bench._SECTION_KEYS["serving_kv"] == ("serving_kv",)
    for key in ("serving_kv_requests_per_sec", "serving_kv_speedup",
                "kv_hit_rate", "serving_kv_prefill_tokens_per_sec"):
        assert key in bench._GFLOPS_GUARD_KEYS, key
    assert "serving_kv_p99_ms" in bench._LATENCY_GUARD_KEYS
    result = _fat_result()
    result["detail"]["extra_configs"]["serving_kv"] = {
        "requests_per_sec": 61.2, "speedup_vs_nosharing": 3.4,
        "kv_hit_rate": 0.97, "prefill_tokens_per_sec": 42000.1,
        "p99_ms": 650.2, "bitwise": "OK", "spec_accepted_steps": 70,
        "acceptance": "OK"}
    compact = json.loads(bench._compact_summary(result))
    d = compact["detail"]
    assert d["serving_kv_requests_per_sec"] == 61.2
    assert d["serving_kv_speedup"] == 3.4
    assert d["kv_hit_rate"] == 0.97
    assert d["serving_kv_prefill_tokens_per_sec"] == 42000.1
    assert d["serving_kv_p99_ms"] == 650.2
    assert d["serving_kv_bitwise"] == "OK"
    assert d["serving_kv_spec_accepted"] == 70
    assert d["serving_kv_acceptance"] == "OK"


def test_serving_kv_guard_rows_fire_in_both_directions():
    bench = _load_bench()
    prior = {"serving_kv_requests_per_sec": 60.0,
             "serving_kv_speedup": 3.5, "kv_hit_rate": 0.95,
             "serving_kv_prefill_tokens_per_sec": 40000.0,
             "serving_kv_p99_ms": 600.0}
    out = bench._compare_captures(
        {"serving_kv_requests_per_sec": 40.0,     # -33%: regressed
         "serving_kv_speedup": 2.0,               # sharing win gone
         "kv_hit_rate": 0.5,                      # cache stopped hitting
         "serving_kv_prefill_tokens_per_sec": 20000.0,
         "serving_kv_p99_ms": 950.0},             # +58%: p99 blew up
        prior)
    for key in ("serving_kv_requests_per_sec", "serving_kv_speedup",
                "kv_hit_rate", "serving_kv_prefill_tokens_per_sec"):
        assert key in out["throughput_regression"], key
    assert "serving_kv_p99_ms" in out["latency_regression"]
    # within-band changes stay quiet
    assert bench._compare_captures(
        {"serving_kv_requests_per_sec": 58.0,
         "serving_kv_speedup": 3.4, "kv_hit_rate": 0.94,
         "serving_kv_prefill_tokens_per_sec": 39000.0,
         "serving_kv_p99_ms": 640.0}, prior) == {}


def test_amort_section_registered():
    """compile_amortization is a first-class section: registry, error
    keys, and the compact-summary/guard keys stay wired together."""
    bench = _load_bench()
    assert "compile_amortization" in bench.SECTIONS
    assert bench._SECTION_KEYS["compile_amortization"] == (
        "compile_amortization",)
    assert "amort_panel_warm_compiles" in bench._LATENCY_GUARD_KEYS
    assert "amort_panel_new_n_2_compiles" in bench._LATENCY_GUARD_KEYS
    # the summary carries the guarded keys (the guard parses the NEXT
    # round's prior from the summary — an absent key is unguardable)
    result = _fat_result()
    result["detail"]["extra_configs"]["compile_amortization"] = {
        "panel": {"cold": {"xla_compiles": 46,
                           "start_to_first_flop_s": 2.1},
                  "warm": {"xla_compiles": 0,
                           "start_to_first_flop_s": 0.2},
                  "new_n": {"xla_compiles": 28},
                  "new_n_2": {"xla_compiles": 0}},
        "wavefront": {"warm": {"xla_compiles": 7}}}
    compact = json.loads(bench._compact_summary(result))
    assert compact["detail"]["amort_panel_warm_compiles"] == 0
    assert compact["detail"]["amort_panel_new_n_2_compiles"] == 0
    assert compact["detail"]["amort_panel_warm_start_s"] == 0.2
    assert compact["detail"]["amort_wf_warm_compiles"] == 7


def test_elastic_section_registered():
    """--section elastic is a first-class section (ISSUE 11 bench
    contract): registry, error keys, compact summary, and the guards
    stay wired together — ramp tracking rides the throughput
    drop-guard, the migration-pause p99 the latency rise-guard, and
    the bitwise/drain rows land in the summary."""
    bench = _load_bench()
    assert "elastic" in bench.SECTIONS
    assert bench._SECTION_KEYS["elastic"] == ("elastic",)
    assert "elastic_ramp_tracking_pct" in bench._GFLOPS_GUARD_KEYS
    assert "elastic_migration_pause_p99_ms" in bench._LATENCY_GUARD_KEYS
    result = _fat_result()
    result["detail"]["extra_configs"]["elastic"] = {
        "ramp_tracking_pct": 81.8, "migration_pause_p99_ms": 50.9,
        "bitwise": "OK", "peak_world": 4, "final_world": 2,
        "drain_clean": True}
    compact = json.loads(bench._compact_summary(result))
    d = compact["detail"]
    assert d["elastic_ramp_tracking_pct"] == 81.8
    assert d["elastic_migration_pause_p99_ms"] == 50.9
    assert d["elastic_bitwise_ok"] == "OK"
    assert d["elastic_peak_world"] == 4
    assert d["elastic_drain_clean"] is True


def test_elastic_guard_rows_fire_in_both_directions():
    bench = _load_bench()
    prior = {"elastic_ramp_tracking_pct": 85.0,
             "elastic_migration_pause_p99_ms": 50.0}
    out = bench._compare_captures(
        {"elastic_ramp_tracking_pct": 60.0,       # -29%: stopped
         "elastic_migration_pause_p99_ms": 90.0},  # +80%: disruptive
        prior)
    assert "elastic_ramp_tracking_pct" in out["throughput_regression"]
    assert "elastic_migration_pause_p99_ms" in out["latency_regression"]
    # within-band changes stay quiet
    assert bench._compare_captures(
        {"elastic_ramp_tracking_pct": 82.0,
         "elastic_migration_pause_p99_ms": 53.0}, prior) == {}


def test_observability_section_registered():
    """--section observability is a first-class section (ISSUE 9 bench
    contract): registry, error keys, compact summary, and the
    obs_overhead_pct guard stay wired together — the ON rate rides the
    throughput drop-guard, the overhead pct the rise-guard arm. ISSUE
    13 adds the NATIVE arm: obs_native_tasks_per_sec (native engine
    with metrics + tracing live) and obs_native_overhead_pct (cost vs
    native-bare) ride the same two guards."""
    bench = _load_bench()
    assert "observability" in bench.SECTIONS
    assert bench._SECTION_KEYS["observability"] == ("observability",)
    assert "obs_tasks_per_sec" in bench._GFLOPS_GUARD_KEYS
    assert "obs_overhead_pct" in bench._LATENCY_GUARD_KEYS
    assert "obs_native_tasks_per_sec" in bench._GFLOPS_GUARD_KEYS
    assert "obs_native_overhead_pct" in bench._LATENCY_GUARD_KEYS
    result = _fat_result()
    result["detail"]["extra_configs"]["observability"] = {
        "tasks_per_sec_off": 17322.8, "tasks_per_sec_on": 16744.6,
        "obs_overhead_pct": 3.45, "obs_overhead_ok": True,
        "obs_native_tasks_per_sec": 601244.5,
        "native_tasks_per_sec_bare": 668911.2,
        "obs_native_overhead_pct": 10.1, "obs_native_ok": True}
    compact = json.loads(bench._compact_summary(result))
    assert compact["detail"]["obs_overhead_pct"] == 3.45
    assert compact["detail"]["obs_tasks_per_sec"] == 16744.6
    assert compact["detail"]["obs_native_tasks_per_sec"] == 601244.5
    assert compact["detail"]["obs_native_overhead_pct"] == 10.1


def test_obs_overhead_guard_fires_on_rise():
    bench = _load_bench()
    prior = {"obs_overhead_pct": 3.0, "obs_tasks_per_sec": 16000.0}
    out = bench._compare_captures(
        {"obs_overhead_pct": 6.0, "obs_tasks_per_sec": 12000.0}, prior)
    assert "obs_overhead_pct" in out["latency_regression"]
    assert "obs_tasks_per_sec" in out["throughput_regression"]
    # within-band stays quiet
    assert bench._compare_captures(
        {"obs_overhead_pct": 3.2, "obs_tasks_per_sec": 15800.0},
        prior) == {}


def test_obs_native_guard_rows_fire_in_both_directions():
    """ISSUE 13 acceptance guard: a native-rate drop (observation
    evicting the engine again) and a native-observer cost rise both
    fire; within-band changes stay quiet."""
    bench = _load_bench()
    prior = {"obs_native_tasks_per_sec": 600000.0,
             "obs_native_overhead_pct": 8.0}
    out = bench._compare_captures(
        {"obs_native_tasks_per_sec": 15000.0,      # fell to Python-rate
         "obs_native_overhead_pct": 14.0}, prior)   # +75%: cost crept
    assert "obs_native_tasks_per_sec" in out["throughput_regression"]
    assert "obs_native_overhead_pct" in out["latency_regression"]
    assert bench._compare_captures(
        {"obs_native_tasks_per_sec": 590000.0,
         "obs_native_overhead_pct": 8.3}, prior) == {}


def test_sanitize_section_registered():
    """ISSUE 14 bench contract: --section sanitize is a first-class
    section; the native-dfsan taskrate row rides the throughput
    drop-guard and the lane's report count rides the zero-baseline arm
    of the latency guard."""
    bench = _load_bench()
    assert "sanitize" in bench.SECTIONS
    assert bench._SECTION_KEYS["sanitize"] == ("sanitize",)
    assert "tasks_per_sec_native_dfsan" in bench._GFLOPS_GUARD_KEYS
    assert "sanitize_report_count" in bench._LATENCY_GUARD_KEYS
    result = _fat_result()
    result["detail"]["extra_configs"]["taskrate"][
        "tasks_per_sec_native_dfsan"] = 412345.6
    result["detail"]["extra_configs"]["sanitize"] = {
        "report_count": 0, "summary": "asan:0,tsan:0,ubsan:0",
        "ran": ["tsan", "asan", "ubsan"], "skipped": [], "clean": True}
    compact = json.loads(bench._compact_summary(result))
    assert compact["detail"]["tasks_per_sec_native_dfsan"] == 412345.6
    assert compact["detail"]["sanitize_report_count"] == 0


def test_native_dfsan_guard_fires_on_drop_and_any_report():
    """A native-dfsan rate drop (the sanitizer got expensive) and ANY
    sanitizer report against the zero baseline both fail the capture;
    within-band stays quiet."""
    bench = _load_bench()
    prior = {"tasks_per_sec_native_dfsan": 400000.0,
             "sanitize_report_count": 0}
    out = bench._compare_captures(
        {"tasks_per_sec_native_dfsan": 12000.0,   # fell to Python rate
         "sanitize_report_count": 1}, prior)      # a finding appeared
    assert "tasks_per_sec_native_dfsan" in out["throughput_regression"]
    assert "sanitize_report_count" in out["latency_regression"]
    assert "zero-baseline" in out["latency_regression"]
    assert bench._compare_captures(
        {"tasks_per_sec_native_dfsan": 390000.0,
         "sanitize_report_count": 0}, prior) == {}


def test_protocheck_section_registered():
    """--section protocheck is a first-class section (ISSUE 19 bench
    contract): registry, error keys, compact summary, and the guard
    stay wired — states/s rides the throughput drop-guard, and the
    section zeroes the rate when a model violates or a seeded bug goes
    uncaught, so the same guard doubles as the contract alarm."""
    bench = _load_bench()
    assert "protocheck" in bench.SECTIONS
    assert bench._SECTION_KEYS["protocheck"] == ("protocheck",)
    assert "protocheck_states_per_sec" in bench._GFLOPS_GUARD_KEYS
    result = _fat_result()
    result["detail"]["extra_configs"]["protocheck"] = {
        "states_per_sec": 39479.4, "states": 579, "transitions": 1482,
        "seeded_caught": 4, "seeded_total": 4, "clean": True}
    compact = json.loads(bench._compact_summary(result))
    assert compact["detail"]["protocheck_states_per_sec"] == 39479.4
    assert compact["detail"]["protocheck_seeded_caught"] == 4


def test_protocheck_guard_fires_on_rate_drop():
    bench = _load_bench()
    prior = {"protocheck_states_per_sec": 39000.0}
    out = bench._compare_captures(
        {"protocheck_states_per_sec": 0.0}, prior)  # contract broke
    assert "protocheck_states_per_sec" in out["throughput_regression"]
    assert bench._compare_captures(
        {"protocheck_states_per_sec": 38000.0}, prior) == {}


# ---- process model: one process per chip (the launcher holds none) ----

def _canned_section(name):
    """What a healthy child of each section prints, reduced to the
    keys main() reads."""
    rows = {
        "device": {"device": {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1}},
        "flagship": {"flagship": {"gflops": 100000.0, "n": 40960,
                                  "tile": 1024,
                                  "peak_proxy_gemm_gflops": 150000.0,
                                  "precision_variant": {}}},
        "latency": {"latency": {"eager_1k_p50_us": 500.0}},
    }
    return rows.get(name, {name: {}})


def test_error_rows_finds_nested_rows():
    bench = _load_bench()
    assert bench._error_rows({"a": {"gflops": 1.0}}) == []
    assert bench._error_rows(
        {"x": {"error": "boom"}, "t": {"flash_error": "vmem"},
         "ok": {"error": ""}, "l": {"precision_variant": {"error": "e"}}}
    ) == ["x.error", "t.flash_error", "l.precision_variant.error"]


def test_main_prints_then_exits_nonzero_on_error_row(tmp_path, monkeypatch,
                                                     capsys):
    bench = _load_bench()
    monkeypatch.setattr(bench, "_HERE", str(tmp_path))

    def run_section(name):
        if name == "getrf":
            return {"getrf_fused": {"error": "section child rc=1: boom"}}
        return _canned_section(name)

    monkeypatch.setattr(bench, "_run_section", run_section)
    import pytest
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 1
    out = capsys.readouterr()
    last = json.loads(out.out.strip().splitlines()[-1])
    assert last["metric"] == "tiled_potrf_gflops_per_chip"
    assert last["value"] == 100000.0           # the rows still print
    assert "extra_configs.getrf_fused.error" in out.err
    # a clean run exits normally
    monkeypatch.setattr(bench, "_run_section", _canned_section)
    bench.main()
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1])[
        "detail"]["device"]["kind"] == "TPU v5 lite"


def test_main_stops_when_the_device_probe_fails(monkeypatch, capsys):
    bench = _load_bench()
    ran = []

    def run_section(name):
        ran.append(name)
        return {"device": {"error": "needs the tpu platform and JAX "
                                    "found ['cpu']"}}

    monkeypatch.setattr(bench, "_run_section", run_section)
    import pytest
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 1 and ran == ["device"]
    assert "no usable tpu device" in capsys.readouterr().err


def test_launchers_never_import_jax(tmp_path):
    """main() and the compile_amortization section start children that
    need the chip: a parent that touched JAX would hold it. Checked in
    a fresh interpreter (this one has JAX loaded)."""
    import subprocess
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('b', {_BENCH!r})\n"
        "b = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(b)\n"
        f"b._HERE = {str(tmp_path)!r}\n"
        "b._run_section = lambda name: {\n"
        "    'device': {'device': {'platform': 'tpu', 'kind': 'k',\n"
        "                          'count': 1}},\n"
        "    'flagship': {'flagship': {'gflops': 1.0,\n"
        "                 'peak_proxy_gemm_gflops': 2.0}}}.get(\n"
        "        name, {name: {}})\n"
        "b._amort_child = lambda *a: {'xla_compiles': 0}\n"
        "b.main()\n"
        "b._section_compile_amortization()\n"
        "bad = [m for m in ('jax', 'jaxlib', 'parsec_tpu')\n"
        "       if m in sys.modules]\n"
        "sys.exit(f'launcher imported {bad}' if bad else 0)\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_bench_without_a_chip_exits_nonzero():
    """No TPU and no stated PARSEC_BENCH_PLATFORM=cpu: an error, not a
    CPU-sized run under the chip metric's name."""
    import subprocess
    env = {k: v for k, v in os.environ.items()
           if k != "PARSEC_BENCH_PLATFORM"}
    proc = subprocess.run([sys.executable, _BENCH], capture_output=True,
                          text=True, timeout=300, env=env)
    assert proc.returncode != 0
    assert "no usable tpu device" in proc.stderr
    assert "tiled_potrf_gflops" not in proc.stdout
