"""Native C++ runtime library tests: builds libffruntime.so, checks the
C++ engines against the pure-Python reference implementations, and runs
the task-graph evaluator end-to-end on a searched PCG."""
import numpy as np
import pytest

from flexflow_tpu import native


@pytest.fixture(scope="module")
def lib():
    if not native.ensure_built():
        pytest.skip("no C++ toolchain available")
    assert native.available()
    return native.get_lib()


def test_ensure_built_rebuilds_when_source_is_newer(lib):
    """A binary older than its source is stale: it must not silently
    outrank ``src/ffruntime.cc`` (it is git-ignored, so nothing else
    would ever refresh it)."""
    import os
    so_before = os.path.getmtime(native._SO)
    src_stat = os.stat(native._SRC)
    try:
        os.utime(native._SRC, (so_before + 10, so_before + 10))
        assert native._stale()
        assert native.ensure_built()
        assert os.path.getmtime(native._SO) > so_before
    finally:
        os.utime(native._SRC, (src_stat.st_atime, src_stat.st_mtime))
    assert not native._stale()


def test_failed_build_raises_with_a_compiler_present(lib, monkeypatch,
                                                     tmp_path):
    bad = tmp_path / "broken.cc"
    bad.write_text("this is not C++\n")
    monkeypatch.setattr(native, "_SRC", str(bad))
    monkeypatch.setattr(native, "_SO", str(tmp_path / "libbroken.so"))
    with pytest.raises(RuntimeError, match="g\\+\\+ exit"):
        native.ensure_built()
    assert list(tmp_path.iterdir()) == [bad]   # no half-written binary


def _random_dag(rng, n, extra_edges):
    """Random DAG: edges only from lower to higher ids."""
    edges = [(i, i + 1) for i in range(n - 1) if rng.random() < 0.7]
    for _ in range(extra_edges):
        a, b = sorted(rng.choice(n, size=2, replace=False))
        if a != b:
            edges.append((int(a), int(b)))
    return list(set(edges))


def test_simulate_matches_python(lib):
    rng = np.random.default_rng(0)
    for trial in range(20):
        n = int(rng.integers(2, 60))
        proc = rng.integers(0, 4, size=n).tolist()
        dur = rng.random(n).tolist()
        edges = _random_dag(rng, n, int(rng.integers(0, 40)))
        ms_c = native.simulate(proc, dur, edges, 4)
        ms_py = native.simulate_py(proc, dur, edges, 4)
        assert abs(ms_c - ms_py) < 1e-9, (trial, ms_c, ms_py)


def test_simulate_queueing_semantics(lib):
    # two independent unit tasks on one processor must serialize
    assert native.simulate([0, 0], [1.0, 1.0], [], 1) == pytest.approx(2.0)
    # on two processors they run concurrently
    assert native.simulate([0, 1], [1.0, 1.0], [], 2) == pytest.approx(1.0)
    # chain respects dependencies across processors
    ms = native.simulate([0, 1, 0], [1.0, 2.0, 1.0],
                         [(0, 1), (1, 2)], 2)
    assert ms == pytest.approx(4.0)


def test_simulate_detects_cycle(lib):
    with pytest.raises(ValueError):
        native.simulate([0, 0], [1.0, 1.0], [(0, 1), (1, 0)], 1)


def test_critical_path(lib):
    # diamond: 1 + max(2, 3) + 1
    dur = [1.0, 2.0, 3.0, 1.0]
    edges = [(0, 1), (0, 2), (1, 3), (2, 3)]
    assert native.critical_path(dur, edges) == pytest.approx(5.0)
    # simulation on 1 proc >= critical path
    assert native.simulate([0] * 4, dur, edges, 1) >= 5.0


def test_gather_batch(lib):
    rng = np.random.default_rng(1)
    src = rng.standard_normal((100, 17, 3)).astype(np.float32)
    idx = rng.integers(0, 100, size=32)
    out = native.gather_batch(src, idx)
    np.testing.assert_array_equal(out, src[idx])
    # threaded path (batch >= 64)
    idx2 = rng.integers(0, 100, size=256)
    out2 = native.gather_batch(src, idx2, n_threads=4)
    np.testing.assert_array_equal(out2, src[idx2])


def test_transitive_closure(lib):
    n = 5
    edges = [(0, 1), (1, 2), (3, 4)]
    reach = native.transitive_closure(n, edges)
    assert reach[2, 0] and reach[2, 1] and reach[1, 0]
    assert reach[4, 3]
    assert not reach[0, 1] and not reach[4, 0] and not reach[2, 3]


def test_task_graph_evaluator_on_searched_graph():
    """TaskGraphEvaluator scores a real PCG; TP strategies must show
    overlap benefit vs the naive additive sum."""
    from flexflow_tpu.core.tensor import Tensor
    from flexflow_tpu.model import FFModel
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.parallel.machine import DeviceMesh, MachineSpec
    from flexflow_tpu.pcg.graph import Graph
    from flexflow_tpu.search.costmodel import OpCostModel
    from flexflow_tpu.search.tasksim import TaskGraphBuilder
    from flexflow_tpu.search.unity import GraphCostEvaluator

    cfg = FFConfig()
    cfg.only_data_parallel = True
    ff = FFModel(cfg)
    x = ff.create_tensor((32, 64), name="x")
    h = ff.dense(x, 128, activation="relu")
    h = ff.dense(h, 128, activation="relu")
    out = ff.dense(h, 10)
    graph = Graph.from_layers(ff.layers, [x], [out])

    spec = MachineSpec(num_devices=8, generation="v5e")
    dmesh = DeviceMesh(spec)
    cost = OpCostModel(spec)
    builder = TaskGraphBuilder(cost, 8)
    makespan, mem = builder.build(graph)
    assert makespan > 0 and mem > 0
    # simulated makespan can't beat the single-chain critical path by more
    # than numerical noise, and must be <= the additive total
    add = GraphCostEvaluator(cost, dmesh).graph_cost(graph)
    assert makespan <= add.total + 1e-9


def test_machine_model_v1_search_runs():
    """--machine-model-version 1 routes search scoring through the native
    simulator end-to-end."""
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer

    cfg = FFConfig()
    cfg.machine_model_version = 1
    cfg.search_budget = 4
    ff = FFModel(cfg)
    x = ff.create_tensor((16, 32), name="x")
    h = ff.dense(x, 64, activation="relu")
    out = ff.dense(h, 8)
    sm = ff.softmax(out)
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy", [])
    label = np.random.default_rng(0).integers(0, 8, size=(16, 1))
    batch = {"x": np.random.default_rng(1).normal(size=(16, 32))
             .astype(np.float32),
             "label": label.astype(np.int32)}
    step = ff.executor.make_train_step()
    bm = ff._run_train_step(step, batch)
    assert np.isfinite(float(np.asarray(bm["loss"])))
