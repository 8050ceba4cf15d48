#!/usr/bin/env python
"""Kernel-tier smoke (ci.sh fast tier): on the 2-slice virtual CPU mesh
with a seq=4 sequence axis, run the searched kernel tier end to end —
calibrated search → adopted strategy carries a NON-DEFAULT kernel
choice → static plan verification → one real train step — and assert
the serialization contract:

  - the adopted ``kernel_impls`` block exports with the strategy and
    ``--import`` honors it verbatim (imported model trains to a
    BIT-IDENTICAL first-step loss — the plan fully determines the
    lowering);
  - the audit-visible kernel record prices the searched choice against
    the forced-XLA baseline (searched-vs-forced-XLA delta);
  - a forced ``attention:xla`` control on the same mesh agrees
    numerically (the kernels are implementations, not different math).

See docs/kernels.md. The long-context memory-envelope gate lives in
``tests/test_kernel_tier.py``; this smoke keeps the fast tier honest.
"""
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")
# searched (non-forced) kernel planning requires calibration evidence
os.environ["FF_CALIBRATION_V2"] = "1"

# out of the measured calibration payload range on the CPU sim, so the
# analytic tier prices the choice — the geometry where ring wins
BATCH, SEQ, EMBED, HEADS = 4, 2048, 512, 8


def _build(mutate=None, export=None, imp=None):
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.parallel.machine import MachineSpec

    spec = MachineSpec.detect()
    spec.num_devices = 8
    spec.num_slices = 2
    spec.num_hosts = 2
    spec.dcn_bandwidth_gbps = 1.0
    spec.dcn_latency_us = 20.0

    cfg = FFConfig()
    cfg.batch_size = BATCH
    cfg.seq_parallel_degree = 4
    cfg.search_budget = 8
    cfg.search_floor_guard = "false"
    if export:
        cfg.export_strategy_file = export
    if imp:
        cfg.import_strategy_file = imp
    if mutate is not None:
        mutate(cfg)
    ff = FFModel(cfg)
    q = ff.create_tensor((BATCH, SEQ, EMBED), name="q")
    ff.multihead_attention(q, q, q, embed_dim=EMBED, num_heads=HEADS)
    ff.compile(SGDOptimizer(0.01), "mean_squared_error", [],
               machine_spec=spec)
    return ff


def _step_loss(ff):
    import numpy as np
    rng = np.random.default_rng(0)
    batch = {"q": rng.normal(size=(BATCH, SEQ, EMBED))
             .astype(np.float32),
             "label": rng.normal(size=(BATCH, SEQ, EMBED))
             .astype(np.float32)}
    bm = ff._run_train_step(ff.executor.make_train_step(), batch)
    return float(np.asarray(bm["loss"]))


def main() -> int:
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    if len(jax.devices()) < 8:
        print("kernel tier smoke: need 8 virtual devices",
              file=sys.stderr)
        return 1

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "strategy.json")

        # -- searched: the tier must adopt a non-default attention impl
        ff = _build(export=path)
        assert ff.dmesh.seq_degree == 4, ff.dmesh.axis_sizes
        attn = [l.name for l in ff.layers
                if l.op_type.name == "OP_MULTIHEAD_ATTENTION"][0]
        impls = dict(getattr(ff.strategy, "kernel_impls", {}) or {})
        chosen = impls.get(attn)
        assert chosen and chosen != "xla", \
            f"searched tier kept the default impl: {impls}"

        # -- audit: calibration-priced searched-vs-forced-XLA delta
        rec = getattr(ff, "_kernel_record", None)
        assert rec and rec["n_nondefault"] >= 1, rec
        op = next(o for o in rec["ops"] if o["name"] == attn)
        assert op["impl"] == chosen and not op["forced"], op
        assert op["forced_xla_s"] >= op["predicted_s"] > 0, op
        delta = op["forced_xla_s"] - op["predicted_s"]

        # -- exported artifact carries the block; verifier accepts it
        import json
        with open(path) as f:
            doc = json.load(f)
        assert doc.get("kernel_impls", {}).get(attn) == chosen, \
            doc.get("kernel_impls")
        from flexflow_tpu.analysis.plan_verifier import \
            verify_strategy_file
        report = verify_strategy_file(path)
        assert report.ok(), [f.format() for f in report.errors]

        loss = _step_loss(ff)
        assert np.isfinite(loss), loss

        # -- import honors the block verbatim, bit-exact replay
        ff_imp = _build(imp=path)
        assert dict(ff_imp.strategy.kernel_impls) == impls, \
            ff_imp.strategy.kernel_impls
        assert ff_imp.executor._kernel_impls.get(attn) == chosen
        loss_imp = _step_loss(ff_imp)
        assert loss_imp == loss, \
            f"import round-trip not bit-exact: {loss_imp} != {loss}"

        # -- forced-xla control on the SAME mesh: same math, different
        #    kernel — numerics agree within kernel tolerance
        def force_xla(cfg):
            cfg.kernel_impls = "attention:xla"
        ff_xla = _build(mutate=force_xla)
        assert ff_xla.strategy.kernel_impls.get(attn) == "xla"
        loss_xla = _step_loss(ff_xla)
        assert np.isfinite(loss_xla)
        assert abs(loss_xla - loss) <= 3e-2 * max(abs(loss_xla), 1.0), \
            (loss, loss_xla)

    print(f"kernel tier smoke OK: searched impl {attn}={chosen} "
          f"(vs forced-xla delta {delta:.3e}s predicted), verified, "
          f"import bit-exact (loss={loss:.6f}), xla control "
          f"loss={loss_xla:.6f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
