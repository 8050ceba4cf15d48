#!/bin/bash
# CI entry point — runnable locally and from .github/workflows/ci.yml.
# (The reference runs 7 workflow tiers behind its README badges; here one
# script encodes the same tiers so "which tests run when" is versioned.)
#
#   ./ci.sh fast      fast test tier (every push; ~8 min, 8-dev CPU mesh)
#   ./ci.sh slow      slow tier: example integration tests + HF imports
#   ./ci.sh dryrun    multi-chip compile/execute dryrun (8 virtual devices)
#   ./ci.sh ab        osdi22ae searched-vs-DP A/B sweep (writes JSON)
#   ./ci.sh nightly   slow + dryrun + ab
set -euo pipefail
cd "$(dirname "$0")"

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
if [[ "${XLA_FLAGS:-}" != *xla_force_host_platform_device_count* ]]; then
  export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"
fi

case "${1:-fast}" in
  fast)
    # static analysis gate (docs/static_analysis.md): the framework-
    # invariant linter, the lock-discipline/thread-lifecycle analyzer,
    # and the SPMD-divergence checker must all be clean over the whole
    # package, and every checked-in strategy artifact must pass the
    # static plan verifier — an unsound plan, an invariant regression,
    # a lock race, or a rank-gated collective fails the push before a
    # single test runs. --budget-s asserts the analyzers' combined
    # wall time cannot silently bloat (raised 10s -> 15s with the
    # serving-observability modules: the package-wide pass measures
    # ~10-11s now; a regression past 15s still fails the push).
    python tools/ffcheck.py --lint flexflow_tpu/ --concurrency --spmd \
      --budget-s 15 --verify-strategies
    python -m pytest tests/ -x -q
    # tier-1 smoke under FF_TRACE=1: the default run above exercises the
    # disabled (near-zero-cost) telemetry paths; this pass exercises the
    # ENABLED instrumentation — spans, counters, audit records — on
    # every push so a broken span can't hide behind the off switch
    FF_TRACE=1 python -m pytest tests/test_obs.py tests/test_e2e_mlp.py \
      tests/test_serving_async.py -x -q -m 'not slow'
    # fault-injection smoke: a crash@2 training run must auto-resume
    # from its checkpoints and complete — the resilience subsystem's
    # recovery path exercised on every push, not just in unit tests
    FF_FAULT_PLAN="crash@2" python tools/resilience_smoke.py
    # async-dispatch parity smoke: the same tiny fit with
    # FF_SYNC_EVERY_STEP=1 and with the default deferred loop must
    # reach IDENTICAL final losses — the async path can never silently
    # diverge from the sync-every-step semantics
    python tools/async_parity_smoke.py
    # reshard parity smoke: searched layout-transition plans must stay
    # BIT-IDENTICAL to the FF_NAIVE_RESHARD=1 baseline — both the raw
    # transition matrix and a pipelined model's region boundaries
    python tools/reshard_parity_smoke.py
    # hierarchical-placement smoke: a 2-slice virtual config runs the
    # placement-aware search end-to-end — search -> static plan verify
    # -> one train step — and the gradient sync must lower to a
    # multi-phase reduction tree (docs/topology.md); the heavyweight
    # >= 1.1x gate lives in the multichip dryrun tier
    python tools/placement_smoke.py
    # overlap parity smoke: the bucketed barrier-chained grad-sync
    # schedule (FF_OVERLAP=1, runtime/overlap.py) must produce a loss
    # history BIT-IDENTICAL to the serial update path on the same
    # searched multi-tier plan — overlap is schedule shaping, never
    # math, enforced on every push
    python tools/overlap_parity_smoke.py
    # per-parameter ZeRO parity smoke: a searched optimizer-state
    # sharding assignment must be BIT-IDENTICAL to replicated training
    # (sharding is placement, not math), and a checkpoint saved under
    # it must restore into a shrunken 4-device world at the same loss
    python tools/zero_parity_smoke.py
    # quantized-collectives parity smoke: int8 gradient sync with
    # error feedback (quantized_collectives=auto) must converge
    # bit-comparably with the full-precision baseline on the BERT
    # encoder, the off-mode path must stay bit-exact, and an exported
    # strategy must round-trip its per-tensor/per-phase wire plan
    # through --import verbatim
    python tools/quantized_sync_smoke.py
    # attribution smoke: search -> 3 train steps under FF_ATTRIB=1 ->
    # the strategy audit record must carry a measured per-op side keyed
    # 1:1 to the predicted entries AND a drift report must exist — the
    # prediction-vs-reality loop (docs/observability.md) on every push
    python tools/attribution_smoke.py
    # serving chaos smoke: injected inference failures must open the
    # per-model circuit breaker (fast 503 + Retry-After), the half-open
    # probe after the cooldown must restore service, and drain() must
    # finish in-flight requests before the process exits
    FF_FAULT_PLAN="infer_fail@0;infer_fail@1;infer_fail@2" \
      python tools/serving_chaos_smoke.py
    # serving-plan smoke: the inference-native search produces one
    # verified sub-strategy per batch bucket (KV cache inside the
    # memory envelope), the checked-in gpt2 serving artifact passes the
    # static verifier, the KV envelope gate BINDS (replicated-KV fails
    # typed where sharded-KV fits), and per-bucket instances decode
    # BIT-IDENTICALLY to the training-plan baseline session
    python tools/serving_plan_smoke.py
    # serving-SLO observability smoke (FF_TRACE=1): one generate request
    # must yield one LINKED lifecycle trace (admission -> queue -> batch
    # -> prefill -> per-segment decode -> response, flow-linked in the
    # fftrace merge), /healthz must report live sketch quantiles and a
    # deadline-expired request as an SLO violation, and an injected
    # mis-calibrated serving prediction must produce a drift report
    # attributing exactly its calibration rows — and mark them stale
    python tools/serving_obs_smoke.py
    # distributed resilience smoke: a 2-process CPU world trains under
    # the WorldSupervisor, rank 1 is fault-injected to hard-crash
    # mid-epoch, the world must re-form (relaunch or shrink) and resume
    # from the last committed two-phase checkpoint with a finite,
    # rank-agreeing final loss — cross-process recovery on every push
    python tools/dist_resilience_smoke.py
    # fleet chaos smoke: two gpt2-tiny replica processes behind the
    # FleetRouter, one hard-killed mid-load via FF_FAULT_PLAN=
    # infer_crash@2 — every admitted request must still return 200
    # (failover), the autoscaler must bring a warm replacement up
    # through the shared compile cache (no new cache entries,
    # ff_model_compiles_total a cache-hit witness), fleet /healthz
    # must re-converge at 2 replicas, and the merged multi-endpoint
    # ffstat fleet view must render against the live fleet
    python tools/fleet_smoke.py
    # closed-loop replan smoke: a degrade_link drill fires mid-training
    # on the 2-slice virtual mesh, drift-marked calibration rows are
    # re-measured in place under the active drill, the re-search on the
    # refreshed tables must produce a candidate the predicted-win gate
    # admits (>= 1.1x, recorded gate="deferred" — virtual drills slow
    # the cost model, not real CPU steps), the hot-swap must carry the
    # training state over bit-exactly, and the armed cooldown must hold
    # the loop to exactly one adoption (no flapping)
    python tools/replan_smoke.py
    ;;
  slow)
    python -m pytest tests/ -q -m slow
    ;;
  dryrun)
    python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"
    ;;
  ab)
    # the osdi22ae A/B methodology runs on the CPU-sim mesh: pin the
    # sweep's subprocesses to it whatever the host offers
    JAX_PLATFORMS=cpu python examples/osdi22ae/run_all.py
    ;;
  nightly)
    "$0" slow
    "$0" dryrun
    "$0" ab
    ;;
  *)
    echo "usage: $0 {fast|slow|dryrun|ab|nightly}" >&2
    exit 2
    ;;
esac
