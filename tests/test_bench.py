"""bench.py orchestration: no accelerator, no headline.

The per-chip metric is a device number. These tests drive
``bench.main()`` with a scripted ``_run_stage`` to prove that the bench
reports it only from a run on an accelerator, exits non-zero when it
finds none or a chip stage fails, and still carries the stages that say
they are the virtual CPU mesh.
"""
import json

import bench


def _popen_raises(*a, **k):
    raise RuntimeError("northstar subprocess disabled in test")


def _scripted(default_probe_results):
    """Build a fake _run_stage. ``default_probe_results`` is the sequence
    of results for probes on the default platform (None env)."""
    calls = []

    def fake_run_stage(args, timeout, env=None):
        on_cpu = bool(env) and env.get("JAX_PLATFORMS") == "cpu"
        calls.append((tuple(args), "cpu" if on_cpu else "default"))
        stage = args[1]
        if stage == "probe":
            n_def = sum(1 for a, e in calls
                        if a[1] == "probe" and e == "default")
            res = default_probe_results[min(n_def - 1,
                                            len(default_probe_results) - 1)]
            return (res, None) if res else (None, "timeout after 240s")
        if stage == "smoke":
            return {"smoke_s": 0.1}, None
        if stage == "bert":
            searched = "--searched" in args
            return {"sps": 950.0 if searched else 900.0, "mfu": 0.31,
                    "flops_per_step": 1.0, "n_chips": 1,
                    "search_time_s": 30.0, "generation": "v5e"}, None
        if stage == "virtual":
            assert env.get("FF_CALIBRATION_V2") == "1"
            assert "xla_force_host_platform_device_count" \
                in env.get("XLA_FLAGS", "")
            return {"n": 8, "virtual_searched_vs_dp": 2.5,
                    "fidelity_spearman": 0.7, "fidelity_rows": 8,
                    "rows": []}, None
        if stage == "long_context":
            assert env.get("FF_CALIBRATION_V2") == "1"
            assert "xla_force_host_platform_device_count" \
                in env.get("XLA_FLAGS", "")
            return {"n": 8, "kernel_impl": "ring",
                    "envelope_binds": True,
                    "envelope_xla_mb": 900.0, "envelope_ring_mb": 300.0,
                    "hbm_gate_mb": 600.0, "verified": True,
                    "step_s_ring": 6.8, "step_s_xla": 16.0,
                    "loss": 1.0, "loss_finite": True,
                    "fidelity_row": {"workload": "long_context",
                                     "ranker": "kernel",
                                     "predicted": 5.5, "measured": 2.4},
                    "ok": True}, None
        if stage == "obs_overhead":
            assert env.get("JAX_PLATFORMS") == "cpu"
            assert "xla_force_host_platform_device_count" \
                in env.get("XLA_FLAGS", "")
            return {"wrapped_step_s": 0.001, "raw_step_s": 0.001,
                    "overhead_pct": 0.1, "ok": True}, None
        if stage == "attribution_overhead":
            assert env.get("JAX_PLATFORMS") == "cpu"
            assert "xla_force_host_platform_device_count" \
                in env.get("XLA_FLAGS", "")
            return {"attrib_on_step_s": 0.00101,
                    "attrib_off_step_s": 0.001,
                    "raw_step_s": 0.001, "overhead_on_pct": 1.0,
                    "overhead_off_pct": 0.0, "harness_s": 1.5,
                    "measured_entries": 7, "ok": True}, None
        if stage == "dispatch_overlap":
            assert env.get("JAX_PLATFORMS") == "cpu"
            # single-device leg: the parent must CLEAR any inherited
            # 8-virtual-device forcing (ci.sh exports it)
            assert "xla_force_host_platform_device_count" \
                not in env.get("XLA_FLAGS", "")
            return {"sync_step_s": 0.002, "deferred_step_s": 0.0018,
                    "deferred_vs_sync": 1.08, "chunk": 16,
                    "rounds": 10, "ok": True}, None
        if stage == "serving_overload":
            assert env.get("JAX_PLATFORMS") == "cpu"
            return {"capacity_rps": 100.0, "offered_x_capacity": 2.0,
                    "deadline_ms": 100.0, "baseline": {},
                    "shedding": {}, "goodput_base_rps": 3.2,
                    "goodput_shed_rps": 52.4, "goodput_ratio": 16.4,
                    "ok": True}, None
        if stage == "reshard":
            assert env.get("JAX_PLATFORMS") == "cpu"
            assert "xla_force_host_platform_device_count" \
                in env.get("XLA_FLAGS", "")
            return {"searched_vs_naive": 1.15, "naive_chunk_s": 0.02,
                    "searched_chunk_s": 0.017, "peak_ok": True,
                    "chunk": 16, "rounds": 6,
                    "time_win": True, "ok": True}, None
        if stage == "comm_overlap":
            assert env.get("JAX_PLATFORMS") == "cpu"
            assert "xla_force_host_platform_device_count" \
                in env.get("XLA_FLAGS", "")
            return {"overlapped_vs_serial": 1.06,
                    "serial_chunk_s": 0.23, "overlap_chunk_s": 0.217,
                    "parity_ok": True, "n_buckets": 4,
                    "model_vs_sim_exposed": 0.73, "agree_ok": True,
                    "chunk": 16, "rounds": 6, "time_win": True,
                    "ok": True}, None
        if stage == "recovery":
            assert env.get("JAX_PLATFORMS") == "cpu"
            assert "xla_force_host_platform_device_count" \
                in env.get("XLA_FLAGS", "")
            return {"baseline_step_s": 0.1, "ckpt_sync_overhead_pct": 2.3,
                    "ckpt_async_overhead_pct": 1.1, "ckpt_every": 10,
                    "time_to_recover_s": 0.5, "ok": True}, None
        if stage == "zero_memory":
            assert env.get("JAX_PLATFORMS") == "cpu"
            assert "xla_force_host_platform_device_count" \
                in env.get("XLA_FLAGS", "")
            return {"opt_bytes_sharded": 835624,
                    "opt_bytes_replicated": 2408528,
                    "mem_ratio": 0.3469, "dp_degree": 4,
                    "n_sharded_params": 2, "step_time_ratio": 1.01,
                    "ok": True}, None
        if stage == "serving_obs_overhead":
            assert env.get("JAX_PLATFORMS") == "cpu"
            return {"bare_rps": 188.4, "disabled_rps": 190.2,
                    "enabled_rps": 189.6, "disabled_over_bare": 1.0096,
                    "enabled_over_bare": 1.0064, "reps": 5,
                    "ok": True}, None
        if stage == "serving_plan":
            assert env.get("JAX_PLATFORMS") == "cpu"
            assert "xla_force_host_platform_device_count" \
                in env.get("XLA_FLAGS", "")
            return {"decode_ratio": 1.12,
                    "per_bucket_ratio": {"1": 1.0, "4": 1.0, "8": 1.12},
                    "predicted_decode_us": {"1": 12.0, "4": 17.0,
                                            "8": 20.0},
                    "floor_guard": {"1": "baseline", "4": "baseline",
                                    "8": "searched"},
                    "bitexact": True, "kv_gate_binds": True,
                    "buckets": [1, 4, 8], "ok": True}, None
        if stage == "fleet":
            assert env.get("JAX_PLATFORMS") == "cpu"
            return {"deadline_ms": 100.0, "capacity_rps": 25.0,
                    "goodput_scaling": 1.9, "fleet_p99_ms": 83.2,
                    "continuous_vs_static": 1.4,
                    "one_replica": {}, "two_replicas": {},
                    "continuous": {}, "static": {}, "ok": True}, None
        if stage == "quantized_sync":
            assert env.get("JAX_PLATFORMS") == "cpu"
            assert "xla_force_host_platform_device_count" \
                in env.get("XLA_FLAGS", "")
            return {"baseline_vs_quantized": 1.21,
                    "rounds": [1.15, 1.21, 1.3],
                    "loss_gap": 2e-05, "bitexact_off": True,
                    "n_quantized": 6, "runtime_on": True,
                    "ok": True}, None
        if stage == "replan":
            assert env.get("JAX_PLATFORMS") == "cpu"
            assert "xla_force_host_platform_device_count" \
                in env.get("XLA_FLAGS", "")
            return {"outcome": "adopted", "trigger": "drift",
                    "gate": "deferred", "predicted_ratio": 3.36,
                    "incumbent_basis": "specs", "rows_remeasured": 54,
                    "degraded_step_s": 0.003, "healed_step_s": 0.0022,
                    "measured_healed_ratio": 1.36,
                    "time_to_adapt_s": 9.1,
                    "replans": 1, "rollbacks": 0, "ok": True}, None
        raise AssertionError(f"unexpected stage {args}")

    return fake_run_stage, calls


def _run_main(monkeypatch, capsys, probe_results, fake=None):
    scripted, calls = _scripted(probe_results)
    monkeypatch.setattr(bench, "_run_stage", fake or scripted)
    monkeypatch.setattr(bench.subprocess, "Popen", _popen_raises)
    monkeypatch.setenv("BENCH_DEADLINE_S", "1200")
    rc = 0
    try:
        bench.main()
    except SystemExit as e:
        rc = e.code
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return out, calls, rc


TPU = {"platform": "tpu", "n": 1, "device_kind": "TPU v5 lite"}
_PER_CHIP = ("metric", "value", "unit", "vs_baseline", "dp_sps",
             "searched_sps", "mfu")


def test_headline_comes_from_the_chip(monkeypatch, capsys):
    out, calls, rc = _run_main(monkeypatch, capsys, [TPU])
    # (the only scripted failure is the disabled northstar subprocess)
    assert rc == 0 and out["error"].startswith("northstar:")
    assert out["platform"] == "tpu"
    assert out["metric"] == bench.METRIC
    assert out["dp_sps"] == 900.0
    assert out["searched_sps"] == 950.0
    assert out["value"] == 950.0
    assert out["vs_baseline"] == round(950.0 / 900.0, 4)
    # one probe, and no chip stage was ever sent to the cpu platform
    assert len([a for a, _ in calls if a[1] == "probe"]) == 1
    assert all(e == "default" for a, e in calls
               if a[1] in ("probe", "smoke", "bert"))


def test_failed_probe_exits_nonzero_without_per_chip_value(monkeypatch,
                                                           capsys):
    out, calls, rc = _run_main(monkeypatch, capsys, [None])
    assert rc not in (0, None)
    assert out["error"].startswith("probe: ")
    assert not any(k in out for k in _PER_CHIP)
    assert not any(a[1] in ("smoke", "bert") for a, _ in calls)


def test_cpu_only_machine_exits_nonzero_without_per_chip_value(
        monkeypatch, capsys):
    cpu = {"platform": "cpu", "n": 1, "device_kind": "cpu"}
    out, calls, rc = _run_main(monkeypatch, capsys, [cpu])
    assert rc not in (0, None)
    assert out["platform"] == "cpu"
    assert "no accelerator" in out["error"]
    assert not any(k in out for k in _PER_CHIP)
    # nothing was timed on the CPU under the flagship's name
    assert not any(a[1] in ("smoke", "bert") for a, _ in calls)


def test_failed_chip_stage_exits_nonzero(monkeypatch, capsys):
    scripted, _ = _scripted([TPU])

    def fake(args, timeout, env=None):
        if args[1] == "bert" and "--searched" in args:
            return None, "rc=1: RESOURCE_EXHAUSTED"
        return scripted(args, timeout, env)

    out, _, rc = _run_main(monkeypatch, capsys, [TPU], fake=fake)
    assert rc not in (0, None)
    assert "bert(searched)" in out["error"]
    assert "value" not in out and "metric" not in out
    assert out["dp_sps"] == 900.0      # measured on the chip: kept


def test_virtual_leg_fields_always_present(monkeypatch, capsys):
    """The 8-virtual-device searched-vs-DP + fidelity leg runs whatever
    the headline platform is, and its fields reach the driver JSON."""
    for probes in ([TPU], [None]):
        out, calls, _ = _run_main(monkeypatch, capsys, probes)
        assert out["virtual_searched_vs_dp"] == 2.5
        assert out["virtual_fidelity_spearman"] == 0.7
        assert out["virtual_fidelity_rows"] == 8
        assert out["virtual_n_devices"] == 8
        assert any(a[1] == "virtual" for a, _ in calls)
        # the telemetry disabled-mode overhead leg rides along and its
        # measured percentage reaches the driver JSON
        assert out["obs_overhead_pct"] == 0.1
        assert any(a[1] == "obs_overhead" for a, _ in calls)
        # and the attribution-mode overhead leg (ISSUE 12)
        assert out["attrib_overhead_on_pct"] == 1.0
        assert out["attrib_overhead_off_pct"] == 0.0
        assert out["attrib_harness_s"] == 1.5
        assert any(a[1] == "attribution_overhead" for a, _ in calls)
        # and the async-dispatch overlap leg
        assert out["dispatch_overlap_ratio"] == 1.08
        assert any(a[1] == "dispatch_overlap" for a, _ in calls)
        # and the searched-resharding leg (ISSUE 6)
        assert out["reshard_searched_vs_naive"] == 1.15
        assert out["reshard_peak_ok"] is True
        assert any(a[1] == "reshard" for a, _ in calls)
        # and the communication-computation overlap leg (ISSUE 13)
        assert out["comm_overlap_ratio"] == 1.06
        assert out["comm_overlap_parity_ok"] is True
        assert out["comm_overlap_model_vs_sim"] == 0.73
        assert any(a[1] == "comm_overlap" for a, _ in calls)
        # so does the checkpoint-overhead + time-to-recover leg
        assert out["ckpt_async_overhead_pct"] == 1.1
        assert out["ckpt_sync_overhead_pct"] == 2.3
        assert out["time_to_recover_s"] == 0.5
        assert any(a[1] == "recovery" for a, _ in calls)
        # and the serving-overload goodput leg (ISSUE 5)
        assert out["serving_goodput_ratio"] == 16.4
        assert out["serving_goodput_shed_rps"] == 52.4
        assert out["serving_goodput_base_rps"] == 3.2
        assert any(a[1] == "serving_overload" for a, _ in calls)
        # and the inference-native serving-plan leg (ISSUE 16)
        assert out["serving_plan_decode_ratio"] == 1.12
        assert out["serving_plan_bitexact"] is True
        assert out["serving_plan_kv_gate"] is True
        assert any(a[1] == "serving_plan" for a, _ in calls)
        # and the serving-observability overhead leg (ISSUE 17)
        assert out["serving_obs_enabled_over_bare"] == 1.0064
        assert out["serving_obs_disabled_over_bare"] == 1.0096
        assert any(a[1] == "serving_obs_overhead" for a, _ in calls)
        # and the serving-fleet leg (ISSUE 18)
        assert out["fleet_goodput_scaling"] == 1.9
        assert out["fleet_p99_ms"] == 83.2
        assert out["fleet_continuous_vs_static"] == 1.4
        assert any(a[1] == "fleet" for a, _ in calls)
        # and the ring-attention long-context leg (ISSUE 19); the
        # scripted virtual leg carries no rows, so its spearman must
        # pass through un-refolded
        assert out["long_context_kernel_impl"] == "ring"
        assert out["long_context_envelope_binds"] is True
        assert out["long_context_verified"] is True
        assert any(a[1] == "long_context" for a, _ in calls)


def test_long_context_row_folds_into_fidelity(monkeypatch, capsys):
    """When the virtual leg carries scored rows, the long-context
    kernel-choice row joins them and the spearman is recomputed over
    the combined set (concordant ranks here -> stays 1.0 at 4 rows)."""
    fake, calls = _scripted([TPU])
    rows = [{"workload": "mlp", "ranker": "tasksim",
             "predicted": 1.2, "measured": 1.1},
            {"workload": "dlrm", "ranker": "tasksim",
             "predicted": 2.5, "measured": 2.2},
            {"workload": "xdl", "ranker": "tasksim",
             "predicted": 1.8, "measured": 1.5}]

    def fake2(args, timeout, env=None):
        if args[1] == "virtual":
            return {"n": 8, "virtual_searched_vs_dp": 2.2,
                    "fidelity_spearman": 1.0, "fidelity_rows": 3,
                    "rows": rows}, None
        return fake(args, timeout, env)

    out, _, _ = _run_main(monkeypatch, capsys, [TPU], fake=fake2)
    assert out["virtual_fidelity_rows"] == 4
    assert out["virtual_fidelity_spearman"] == 1.0
