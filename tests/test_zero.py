"""ZeRO-1 optimizer-state sharding (runtime/zero.py): placement,
per-device memory, and numerics vs the replicated-state baseline.

Beyond-reference capability: the reference allocates full V/M per
replica (``src/runtime/optimizer_kernel.cu``)."""
import jax
import numpy as np
import pytest

from flexflow_tpu import AdamOptimizer, FFConfig, FFModel
from flexflow_tpu.models import build_mlp


def _train(zero: bool, steps: int = 5):
    cfg = FFConfig()
    cfg.batch_size = 16
    cfg.only_data_parallel = True
    cfg.shard_optimizer_states = zero
    ff = FFModel(cfg)
    out = build_mlp(ff, 16, in_dim=32, hidden=(64, 64), num_classes=8)
    ff.compile(AdamOptimizer(0.01), "sparse_categorical_crossentropy", [],
               output_tensor=out)
    rng = np.random.default_rng(0)
    b = {"input": rng.normal(size=(16, 32)).astype(np.float32),
         "label": rng.integers(0, 8, size=(16, 1)).astype(np.int32)}
    step = ff.executor.make_train_step()
    losses = []
    for _ in range(steps):
        bm = ff._run_train_step(step, b)
        losses.append(float(np.asarray(bm["loss"])))
    return ff, losses


def test_zero_shards_moments_and_matches_numerics():
    ff_z, losses_z = _train(zero=True)
    ff_r, losses_r = _train(zero=False)

    # every shardable Adam moment is sharded: its addressable shard is
    # smaller than the logical array
    m = ff_z.opt_state["m"]
    sharded = 0
    for lname, ws in m.items():
        for wname, leaf in ws.items():
            shard = leaf.addressable_shards[0].data
            if shard.size < leaf.size:
                sharded += 1
                assert leaf.size % shard.size == 0
    assert sharded >= 3, f"expected sharded moments, got {sharded}"

    # the replicated baseline keeps full-size shards
    m_r = ff_r.opt_state["m"]
    for lname, ws in m_r.items():
        for wname, leaf in ws.items():
            assert leaf.addressable_shards[0].data.size == leaf.size

    # numerics identical (sharding is placement, not math)
    np.testing.assert_allclose(losses_z, losses_r, rtol=1e-5, atol=1e-6)


def test_zero_state_stays_sharded_across_steps():
    ff, _ = _train(zero=True, steps=3)
    for ws in ff.opt_state["v"].values():
        for leaf in ws.values():
            if leaf.size >= 64:        # every big moment stays sharded
                assert leaf.addressable_shards[0].data.size < leaf.size


def test_zero_flag_spelling():
    cfg = FFConfig.parse_args(["--zero"])
    assert cfg.shard_optimizer_states
    cfg = FFConfig.parse_args(["--shard-optimizer-states"])
    assert cfg.shard_optimizer_states


def test_zero_checkpoint_roundtrip(tmp_path):
    """Checkpoint save/restore preserves ZeRO moment shardings and the
    training trajectory (restore re-places onto the live pytree's
    shardings)."""
    from flexflow_tpu.runtime.checkpoint import (restore_model_checkpoint,
                                                 save_model_checkpoint)
    ff, _ = _train(zero=True, steps=3)
    save_model_checkpoint(ff, str(tmp_path))
    # fresh model, same config/build: restore into it
    ff2, _ = _train(zero=True, steps=1)
    step = restore_model_checkpoint(ff2, str(tmp_path))
    assert step == ff._step
    for lname, ws in ff2.opt_state["m"].items():
        for wname, leaf in ws.items():
            ref = ff.opt_state["m"][lname][wname]
            # placement preserved (still ZeRO-sharded) and values equal
            assert (leaf.addressable_shards[0].data.size
                    == ref.addressable_shards[0].data.size)
            np.testing.assert_allclose(np.asarray(leaf), np.asarray(ref),
                                       rtol=1e-6)
    # training continues identically from the restored state
    rng = np.random.default_rng(0)
    b = {"input": rng.normal(size=(16, 32)).astype(np.float32),
         "label": rng.integers(0, 8, size=(16, 1)).astype(np.int32)}
    l1 = float(np.asarray(ff._run_train_step(
        ff.executor.make_train_step(), b)["loss"]))
    l2 = float(np.asarray(ff2._run_train_step(
        ff2.executor.make_train_step(), b)["loss"]))
    np.testing.assert_allclose(l2, l1, rtol=1e-5)


# ===========================================================================
# zero_spec edge cases (shape-level core shared with the planner/verifier)
# ===========================================================================

def test_zero_spec_edge_cases():
    from flexflow_tpu.runtime.zero import zero_spec
    axes = {"x0": 2, "x1": 4}
    # scalar / 0-dim leaves never shard
    assert zero_spec((), None, axes) is None
    # no free axis divides any dim
    assert zero_spec((7, 5), None, axes) is None
    # no free axes at all (weight consumes the whole mesh)
    assert zero_spec((8, 8), ("x0", "x1"), axes) is None
    # multi-axis absorption: dim 1 soaks BOTH axes (degree 8), beating
    # dim 0's single-axis 4
    sp = zero_spec((12, 8), None, {"a": 4, "b": 2})
    assert sp is not None
    assert sp[1] == ("a", "b") and sp[0] is None, sp
    # equal-degree tie on equal dims keeps the first dim
    sp = zero_spec((8, 8), None, {"a": 2})
    assert sp[0] == "a" and (len(sp) < 2 or sp[1] is None), sp
    # equal-degree tie prefers the LARGER dim
    sp = zero_spec((4, 8), None, {"a": 2})
    assert sp[1] == "a" and sp[0] is None, sp
    # the weight's own axes are skipped, free ones absorbed
    sp = zero_spec((8, 8), (None, "x1"), axes)
    assert sp[0] == "x0" and sp[1] == "x1", sp


def test_zero_spec_never_collides_with_weight_axes():
    """Property: the ZeRO spec follows the weight's own placement on
    the weight's sharded dims, shards exactly ONE extra dim over axes
    the weight left free (never re-using a weight axis on a new dim),
    and that dim divides its absorbed degree."""
    import random

    from flexflow_tpu.runtime.zero import zero_spec
    rng = random.Random(7)
    axis_sizes = {"x0": 2, "x1": 2, "x2": 3}
    names = list(axis_sizes)
    for _ in range(200):
        rank = rng.randint(0, 3)
        shape = tuple(rng.choice((1, 2, 3, 4, 6, 7, 12))
                      for _ in range(rank))
        wspec = []
        free = list(names)
        for d in range(rank):
            if free and rng.random() < 0.4:
                a = free.pop(rng.randrange(len(free)))
                wspec.append(a)
            else:
                wspec.append(None)
        sp = zero_spec(shape, tuple(wspec), axis_sizes)
        if sp is None:
            continue
        used = {a for a in wspec if a is not None}
        entries = list(tuple(sp)) + [None] * (rank - len(tuple(sp)))
        new_dims = []
        for d in range(rank):
            e = entries[d]
            w = wspec[d]
            if w is not None:
                # weight-sharded dims pass through untouched
                assert e == w, (shape, wspec, sp)
                continue
            if e is None:
                continue
            new_axes = e if isinstance(e, tuple) else (e,)
            # the extra axes never collide with the weight's own
            assert not set(new_axes) & used, (shape, wspec, sp)
            deg = 1
            for a in new_axes:
                deg *= axis_sizes[a]
            assert deg > 1 and shape[d] % deg == 0, (shape, wspec, sp)
            new_dims.append(d)
        # exactly one dim absorbs the free axes
        assert len(new_dims) == 1, (shape, wspec, sp)


def test_zero_assignment_roundtrip_and_uniform_equivalence():
    """ZeroAssignment JSON round-trip, and the 'all' assignment applied
    to a live state reproduces the uniform --zero flag's placements
    leaf for leaf (the pinned legacy behavior as an assignment)."""
    from flexflow_tpu.runtime.zero import (ZeroAssignment,
                                           shard_optimizer_state)
    ff_u, _ = _train(zero=True, steps=1)
    # a fresh replicated model on the same graph/mesh
    ff_r, _ = _train(zero=False, steps=1)
    params_meta = {
        lname: {w: tuple(leaf.shape) for w, leaf in ws.items()}
        for lname, ws in ff_r.params.items()}
    assignment = ZeroAssignment.uniform(
        params_meta, ff_r.strategy, dict(ff_r.dmesh.axis_sizes))
    doc = assignment.to_json()
    back = ZeroAssignment.from_json(doc)
    assert back.sharded_params() == assignment.sharded_params()
    state = shard_optimizer_state(ff_r.opt_state, ff_r.dmesh, back)
    for slot in ("m", "v"):
        for lname, ws in ff_u.opt_state[slot].items():
            for wname, leaf_u in ws.items():
                leaf_a = state[slot][lname][wname]
                assert (leaf_a.addressable_shards[0].data.shape
                        == leaf_u.addressable_shards[0].data.shape), \
                    (slot, lname, wname)


# ===========================================================================
# searched per-parameter assignment (ISSUE 10 tentpole)
# ===========================================================================

def _train_big(policy: str, steps: int = 3, mem_mb: int = 0,
               hidden=(512, 512)):
    cfg = FFConfig()
    cfg.batch_size = 16
    cfg.only_data_parallel = True
    cfg.zero_policy = policy
    cfg.device_mem_mb = mem_mb
    ff = FFModel(cfg)
    out = build_mlp(ff, 16, in_dim=32, hidden=hidden, num_classes=8)
    ff.compile(AdamOptimizer(0.01), "sparse_categorical_crossentropy", [],
               output_tensor=out)
    rng = np.random.default_rng(0)
    b = {"input": rng.normal(size=(16, 32)).astype(np.float32),
         "label": rng.integers(0, 8, size=(16, 1)).astype(np.int32)}
    step = ff.executor.make_train_step()
    losses = []
    for _ in range(steps):
        bm = ff._run_train_step(step, b)
        losses.append(float(np.asarray(bm["loss"])))
    return ff, losses, b


def test_zero_auto_assignment_non_uniform_and_bit_exact():
    """'auto' shards the big matrices (overhead within the slack) and
    leaves the tiny biases replicated — a genuinely NON-uniform
    per-parameter assignment — and training numerics are bit-identical
    to the replicated baseline (sharding is placement, not math)."""
    ff_z, losses_z, _ = _train_big("auto")
    ff_r, losses_r, _ = _train_big("off")
    assert losses_z == losses_r, (losses_z, losses_r)
    za = ff_z.strategy.zero
    assert za is not None
    s = za.summary()
    assert 0 < s["n_sharded"] < s["n_params"]
    assert not s["uniform"]
    # the big kernel is sharded on device...
    m = ff_z.opt_state["m"]
    big = m["op_linear_1"]["kernel"]
    assert big.addressable_shards[0].data.size < big.size
    # ...the biases are not
    assert (m["op_linear_1"]["bias"].addressable_shards[0].data.size
            == m["op_linear_1"]["bias"].size)
    # and the baseline keeps everything replicated
    for ws in ff_r.opt_state["m"].values():
        for leaf in ws.values():
            assert leaf.addressable_shards[0].data.size == leaf.size
    # the audit record carries per-param choice + scores
    rec = ff_z._zero_record
    assert rec["n_sharded"] == s["n_sharded"] and not rec["uniform"]
    sharded = [p for p in rec["per_param"] if p["sharded"]]
    assert sharded and all(p["bytes_saved"] > 0 for p in sharded)
    assert all("overhead_s" in p and "replicated_s" in p
               for p in rec["per_param"])


def test_zero_state_bytes_per_device_track_one_over_dp():
    """At dp = 4 the searched assignment's optimizer state on a device
    is well under the replicated one's: Adam on an MLP whose matrices
    dominate, so the ratio is near 1/4 and must not pass 0.6."""
    from flexflow_tpu.parallel.machine import MachineSpec

    def state_bytes_on_device_0(policy):
        cfg = FFConfig()
        cfg.batch_size = 64
        cfg.only_data_parallel = True
        cfg.zero_policy = policy
        ff = FFModel(cfg)
        out = build_mlp(ff, 64, in_dim=64, hidden=(512, 512),
                        num_classes=10)
        ff.compile(AdamOptimizer(0.01),
                   "sparse_categorical_crossentropy", [],
                   output_tensor=out,
                   machine_spec=MachineSpec(num_devices=4,
                                            generation="cpu-sim"))
        assert ff.dmesh.num_devices == 4
        n_sharded = len(ff.strategy.zero.sharded_params()) \
            if ff.strategy.zero else 0
        # one shard a leaf: a replicated leaf's shard is the whole leaf
        return n_sharded, sum(
            leaf.addressable_shards[0].data.nbytes
            for leaf in jax.tree.leaves(ff.opt_state))

    n_sharded, sharded = state_bytes_on_device_0("auto")
    _, replicated = state_bytes_on_device_0("off")
    assert n_sharded > 0
    assert sharded <= 0.6 * replicated, (sharded, replicated)


def test_zero_memory_pressure_only_fits_with_assignment():
    """A model sized to FAIL the replicated memory envelope: compile
    raises a typed PlanVerificationError replicated, and compiles +
    verifies + trains with a searched per-parameter assignment — the
    'models that don't fit replicated are a supported scenario'
    acceptance."""
    import pytest

    from flexflow_tpu.analysis.plan_verifier import PlanVerificationError
    with pytest.raises(PlanVerificationError, match="memory-env|envelope"):
        _train_big("off", steps=0, mem_mb=4)
    ff, losses, _ = _train_big("memory", steps=2, mem_mb=4)
    assert all(np.isfinite(l) for l in losses)
    assert ff.strategy.zero is not None and ff.strategy.zero
    assert ff._plan_verify_report.ok()
    mem = ff._plan_verify_report.memory
    assert mem["zero_sharded_params"] >= 1
    assert mem["envelope_bytes"] <= mem["hbm_bytes"]


def test_zero_checkpoint_meta_and_shrunken_world_restore(tmp_path):
    """Save under a per-parameter assignment -> the checkpoint meta
    records the assignment and per-leaf opt shardings; restore into a
    SHRUNKEN world (8 -> 4 devices, a different assignment) reaches the
    same loss — the elastic device-loss re-plan's round-trip."""
    import jax

    from flexflow_tpu.parallel.machine import MachineSpec
    from flexflow_tpu.runtime.checkpoint import (CheckpointManager,
                                                 restore_model_checkpoint,
                                                 save_model_checkpoint)
    ff, _, b = _train_big("auto", steps=3)
    save_model_checkpoint(ff, str(tmp_path))
    # meta records the assignment + per-leaf shardings
    mgr = CheckpointManager(str(tmp_path))
    _, meta = mgr.restore()
    assert meta["zero"]["decisions"]
    shardings = meta["opt_shardings"]
    assert shardings
    assert any(sp for sp in shardings.values() if sp), shardings
    # the ORIGINAL world's next-step loss is the reference
    l_ref = float(np.asarray(ff._run_train_step(
        ff.executor.make_train_step(), b)["loss"]))
    # a 4-device world (elastic.shrunken_spec shape) restores the same
    # files: host state re-places onto ITS assignment via place_host
    spec4 = MachineSpec(num_devices=4, generation="cpu-sim")
    cfg = FFConfig()
    cfg.batch_size = 16
    cfg.only_data_parallel = True
    cfg.zero_policy = "auto"
    ff4 = FFModel(cfg)
    out = build_mlp(ff4, 16, in_dim=32, hidden=(512, 512), num_classes=8)
    ff4.compile(AdamOptimizer(0.01), "sparse_categorical_crossentropy",
                [], output_tensor=out, machine_spec=spec4)
    assert ff4.dmesh.num_devices == 4
    step = restore_model_checkpoint(ff4, str(tmp_path))
    assert step == ff._step - 1
    l4 = float(np.asarray(ff4._run_train_step(
        ff4.executor.make_train_step(), b)["loss"]))
    np.testing.assert_allclose(l4, l_ref, rtol=1e-5)


def test_zero_elastic_replan_roundtrip(tmp_path):
    """Device loss under a ZeRO assignment: replan_on_device_loss
    re-searches on the shrunken mesh (a fresh assignment), and the
    checkpoint restore reshards the partially-sharded state onto it —
    training continues at the pre-loss loss."""
    from flexflow_tpu.resilience.elastic import replan_on_device_loss
    from flexflow_tpu.runtime.checkpoint import (restore_model_checkpoint,
                                                 save_model_checkpoint)
    ff, _, b = _train_big("auto", steps=3)
    save_model_checkpoint(ff, str(tmp_path))
    l_ref = float(np.asarray(ff._run_train_step(
        ff.executor.make_train_step(), b)["loss"]))
    n = replan_on_device_loss(ff, n_lost=4)
    assert n == 4
    assert ff.dmesh.num_devices == 4
    restore_model_checkpoint(ff, str(tmp_path))
    l_new = float(np.asarray(ff._run_train_step(
        ff.executor.make_train_step(), b)["loss"]))
    np.testing.assert_allclose(l_new, l_ref, rtol=1e-5)


def test_zero_strategy_export_import_roundtrip(tmp_path, monkeypatch):
    """The searched assignment serializes with the strategy and an
    --import honors it verbatim (no re-planning). The search prices on
    the machine model's own link constants (``cpu-sim``: 5 GB/s, 1 us a
    hop): whether ``auto`` shards anything must not hang on a timed
    all-reduce of this host's 8 virtual devices."""
    import json

    from flexflow_tpu.search.costmodel import OpCostModel
    monkeypatch.setattr(OpCostModel, "calibrate_collectives",
                        lambda self, dmesh: None)
    path = str(tmp_path / "strategy.json")

    def build(cfg):
        ff = FFModel(cfg)
        out = build_mlp(ff, 16, in_dim=32, hidden=(512, 512),
                        num_classes=8)
        ff.compile(AdamOptimizer(0.01),
                   "sparse_categorical_crossentropy", [],
                   output_tensor=out)
        return ff

    cfg = FFConfig()
    cfg.batch_size = 16
    cfg.search_algo = "mcmc"
    cfg.search_budget = 10
    cfg.zero_policy = "auto"
    cfg.export_strategy_file = path
    ff = build(cfg)
    assert ff.strategy.zero is not None
    doc = json.load(open(path))
    assert doc.get("zero", {}).get("decisions")
    cfg2 = FFConfig()
    cfg2.batch_size = 16
    cfg2.import_strategy_file = path
    # import path plans nothing itself: the file's assignment is adopted
    cfg2.zero_policy = "off"
    ff2 = build(cfg2)
    assert ff2.strategy.zero is not None
    assert (ff2.strategy.zero.sharded_params()
            == ff.strategy.zero.sharded_params())
    # and the state actually shards per the imported assignment
    for lname, wname in ff2.strategy.zero.sharded_params():
        leaf = ff2.opt_state["m"][lname][wname]
        assert leaf.addressable_shards[0].data.size < leaf.size


def test_zero_policy_flag_spelling():
    cfg = FFConfig.parse_args(["--zero-search"])
    assert cfg.zero_policy == "auto"
    cfg = FFConfig.parse_args(["--zero-policy", "memory"])
    assert cfg.zero_policy == "memory"
    cfg = FFConfig.parse_args(["--zero-overhead-frac", "0.1"])
    assert cfg.zero_overhead_frac == 0.1
