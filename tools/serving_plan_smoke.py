#!/usr/bin/env python
"""Serving-plan smoke (ci.sh fast tier): the inference-native search
end to end on the 8-device CPU mesh —

  - search a per-batch-class serving plan for the small causal LM
    (``build_gpt2`` at (8, 32), ``GPTConfig.tiny``), one sub-strategy
    per batch bucket, ranked by prefill + per-token decode-step
    latency with the KV cache inside the memory envelope;
  - the searched plan must pass ``verify_serving_plan`` and the
    checked-in artifact (``strategies/gpt2_serving_8dev.json``) must
    pass the static verifier (``ffcheck --verify-strategies`` path);
  - the KV envelope gate must BIND: at an artificially small HBM
    budget, a plan whose largest bucket only fits with the KV cache
    sharded verifies, and the replicated-KV analog fails with a typed
    ``PlanVerificationError`` — at compile/verify time, not OOM at
    request time;
  - the checked-in plan's per-bucket instances must serve decode
    requests BIT-IDENTICALLY to the training-plan (pure-DP) baseline
    session at every bucket, segmented lock holds included.

Regenerate the artifact with ``--regen`` (same seed/budget — commit the
diff). Decode-step latency under the plan is not measured anywhere yet
(no benchmark cell serves); this smoke keeps the fast tier honest in
~60 s.
"""
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")

ARTIFACT = os.path.join(REPO, "strategies", "gpt2_serving_8dev.json")
BUCKETS = (1, 4, 8)


def _compile_gpt2(mutate=None):
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.models.nlp import GPTConfig, build_gpt2
    cfg = FFConfig()
    cfg.only_data_parallel = True
    if mutate is not None:
        mutate(cfg)
    ff = FFModel(cfg)
    out = build_gpt2(ff, 8, 32, GPTConfig.tiny())
    ff.compile(SGDOptimizer(0.0), "identity", [], output_tensor=out)
    return ff


def main() -> int:
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    n = len(jax.devices())
    if n < 8:
        print(f"serving-plan smoke: need 8 virtual devices, have {n}",
              file=sys.stderr)
        return 1

    # -- 1. search: one plan per bucket, verified inside ---------------
    from flexflow_tpu.search.serving_plan import (optimize_serving_strategy,
                                                  save_serving_plan)
    ff = _compile_gpt2(lambda c: (setattr(c, "only_data_parallel", False),
                                  setattr(c, "search_budget", 120)))
    plan = optimize_serving_strategy(ff, buckets=BUCKETS, budget=120)
    assert sorted(plan.buckets) == sorted(BUCKETS), plan.buckets
    axis_sizes = dict(ff.dmesh.axis_sizes)

    def _dim0_degree(spec):
        if spec is None or not len(spec):
            return 1
        entry = spec[0]
        if entry is None:
            return 1
        names = entry if isinstance(entry, (tuple, list)) else (entry,)
        d = 1
        for a in names:
            d *= axis_sizes.get(a, 1)
        return d

    for b, p in plan.buckets.items():
        assert np.isfinite(p.cost.decode_step) and p.cost.decode_step > 0
        # batch-dim (sample) degrees must divide the bucket — the
        # constraint that makes small buckets lean TP, large DP
        for name, op in p.strategy.ops.items():
            for sp in op.outputs:
                d = _dim0_degree(sp)
                assert b % max(d, 1) == 0, (b, name, sp)
    print(f"serving smoke: searched {len(plan.buckets)} bucket plans; "
          f"decode-step predictions "
          f"{ {b: round(p.cost.decode_step * 1e6, 1) for b, p in sorted(plan.buckets.items())} } us")

    if "--regen" in sys.argv:
        save_serving_plan(ARTIFACT, plan)
        print(f"serving smoke: regenerated {ARTIFACT}")

    # -- 2. the checked-in artifact passes the static verifier --------
    from flexflow_tpu.analysis.plan_verifier import verify_strategy_file
    with open(ARTIFACT) as f:
        doc = json.load(f)
    report = verify_strategy_file(ARTIFACT, doc=doc)
    assert report.ok(), [f_.format() for f_ in report.errors]
    assert sorted(int(k) for k in doc["serving"]["buckets"]) \
        == sorted(BUCKETS), doc["serving"]["buckets"]
    print("serving smoke: checked-in artifact verifies "
          f"({len(report.findings)} finding(s))")

    # -- 3. the KV envelope gate binds ---------------------------------
    # At an HBM budget sized between the sharded and replicated KV
    # footprints, the sharded-KV plan verifies and the replicated one
    # fails TYPED — the gate is enforced statically, before serving.
    from flexflow_tpu.analysis.plan_verifier import (PlanVerificationError,
                                                     verify_serving_plan)
    import copy
    big = max(plan.buckets)
    block = plan.to_block()
    sub = block["buckets"][str(big)]
    assert sub["kv"], "no causal attention layers in the gpt2 graph"

    def kv_variant(shard_degree):
        v = copy.deepcopy(sub)
        for kv in v["kv"].values():
            kv["shard_degree"] = shard_degree
            kv["bytes"] = (2 * big * block["max_seq"]
                           * kv["num_kv_heads"] * kv["head_dim"]
                           * 4) // shard_degree
        return v

    shard, repl = kv_variant(2), kv_variant(1)
    # pin the HBM budget BETWEEN the two variants' envelopes, using the
    # verifier's own arithmetic so the gate decision is never off by a
    # rounding term
    from flexflow_tpu.analysis.plan_verifier import serving_envelope
    by_name = {l.name: l for l in ff.layers}
    axes = dict(ff.dmesh.axis_sizes)
    env_shard = serving_envelope(shard, big, by_name, axes)
    env_repl = serving_envelope(repl, big, by_name, axes)
    assert env_shard["envelope_bytes"] < env_repl["envelope_bytes"]
    hbm = (env_shard["envelope_bytes"] + env_repl["envelope_bytes"]) / 2.0

    def envelope_check(variant):
        from flexflow_tpu.analysis.plan_verifier import (PlanReport,
                                                         _check_serving)
        rep = PlanReport()
        _check_serving(rep, {"version": 1, "max_seq": block["max_seq"],
                             "decode_tokens": block["decode_tokens"],
                             "buckets": {str(big): variant}},
                       by_name, axes, ff.dmesh.spec, hbm)
        return rep

    rep_ok = envelope_check(shard)
    assert rep_ok.ok(), [f_.format() for f_ in rep_ok.errors]
    rep_bad = envelope_check(repl)
    assert not rep_bad.ok(), "replicated-KV plan verified under a " \
                             "budget it cannot fit"
    assert any(f_.seam == "serving-memory" for f_ in rep_bad.errors), \
        [f_.format() for f_ in rep_bad.errors]
    # and the typed path: verify_serving_plan raises, not OOMs
    try:
        verify_serving_plan(
            {"version": 1, "max_seq": block["max_seq"],
             "decode_tokens": block["decode_tokens"],
             "buckets": {str(big): repl}},
            ff.layers, ff.dmesh, hbm_bytes=hbm, context="smoke-gate")
    except PlanVerificationError as e:
        print(f"serving smoke: KV envelope gate binds "
              f"({len(e.findings)} typed finding(s))")
    else:
        print("serving smoke: FAIL — replicated-KV plan passed the "
              "envelope gate", file=sys.stderr)
        return 1

    # -- 4. serve the checked-in plan; decode bit-exact vs baseline ---
    from flexflow_tpu.search.serving_plan import bucket_strategy_doc
    from flexflow_tpu.serving.session import (InferenceSession,
                                              ServingPlanSession)
    import tempfile
    per_bucket = {}
    for b in BUCKETS:
        sub_doc = bucket_strategy_doc(doc, b)
        fd, p = tempfile.mkstemp(suffix=f".bucket{b}.json")
        with os.fdopen(fd, "w") as f:
            json.dump(sub_doc, f)
        try:
            fb = _compile_gpt2(
                lambda c, p=p: (setattr(c, "only_data_parallel", False),
                                setattr(c, "import_strategy_file", p)))
        finally:
            os.unlink(p)
        per_bucket[b] = InferenceSession(fb, [b], decode_segment=4)
    serving = ServingPlanSession(per_bucket)
    baseline = InferenceSession(_compile_gpt2(), BUCKETS,
                                decode_segment=0)

    rng = np.random.default_rng(0)
    checks = 0
    for n_rows, plen, eos in [(1, 6, None), (3, 5, 7), (4, 4, None),
                              (8, 7, 3)]:
        ids = np.zeros((n_rows, 32), np.int32)
        ids[:, :plen] = rng.integers(1, 500, (n_rows, plen))
        got = serving.generate(ids, plen, 12, temperature=0.0,
                               eos_token_id=eos)
        want = baseline.generate(ids, plen, 12, temperature=0.0,
                                 eos_token_id=eos)
        assert np.array_equal(got, want), \
            f"decode mismatch at n={n_rows} eos={eos}"
        checks += 1
    # ragged prompts through the router too
    pl = np.array([6, 2, 5], np.int32)
    ids = np.zeros((3, 32), np.int32)
    for r, p_ in enumerate(pl):
        ids[r, :p_] = rng.integers(1, 500, p_)
    got = serving.generate(ids, pl, 10, temperature=0.0, eos_token_id=7)
    want = baseline.generate(ids, pl, 10, temperature=0.0,
                             eos_token_id=7)
    assert np.array_equal(got, want), "ragged decode mismatch"
    checks += 1
    print(f"serving smoke: {checks} decode request shapes bit-exact vs "
          f"the training-plan baseline")
    print("serving smoke: PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
