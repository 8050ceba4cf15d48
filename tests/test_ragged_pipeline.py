"""Ragged GPipe engine: unequal per-stage block counts + prologue
(embedding) and epilogue (head) inside the pipelined region
(parallel/pipeline.py::gpipe_ragged). Reference: finishes the capability
zwang86/FlexFlow only reserved (``ffconst.h:159`` OP_PIPELINE)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flexflow_tpu.parallel.pipeline import gpipe_ragged
from jax import shard_map

S = 4           # stages
COUNTS = (2, 2, 1, 1)   # ragged: 6 blocks over 4 stages
CMAX = 2
M = 8           # microbatches
MB = 2          # microbatch size
H, V = 8, 16    # hidden, vocab


def _mesh():
    if len(jax.devices()) < S:
        pytest.skip("needs >= 4 devices")
    devs = np.array(jax.devices()[:S]).reshape(S)
    return Mesh(devs, ("pp",))


def _params(rng):
    table = rng.normal(size=(V, H)).astype(np.float32)
    Ws = rng.normal(size=(sum(COUNTS), H, H)).astype(np.float32) * 0.3
    head = rng.normal(size=(H, V)).astype(np.float32)
    return table, Ws, head


def _stacked_padded(Ws):
    """(6, H, H) -> (S, CMAX, H, H), stage s owns its COUNTS[s] blocks,
    padded slots zeroed."""
    out = np.zeros((S, CMAX, H, H), np.float32)
    i = 0
    for s, c in enumerate(COUNTS):
        for k in range(c):
            out[s, k] = Ws[i]
            i += 1
    return jnp.asarray(out)


def _sequential(table, Ws, head, ids):
    x = table[ids]                       # (B, H)
    for W in Ws:
        x = jnp.tanh(x @ W)
    return x @ head                      # (B, V)


def _pipelined(table, stacked, head, ids, mesh):
    def block_fn(p, x, t):
        return jnp.tanh(x @ p)

    def prologue_fn(p, raw, t):
        return p[raw]

    def epilogue_fn(p, y, t):
        return y @ p

    engine = gpipe_ragged(block_fn, "pp", M, COUNTS,
                          prologue_fn=prologue_fn,
                          epilogue_fn=epilogue_fn)
    raw_xs = ids.reshape(M, MB)
    hidden_ex = jnp.zeros((MB, H), jnp.float32)
    out_ex = jnp.zeros((MB, V), jnp.float32)

    fn = shard_map(
        engine, mesh=mesh,
        in_specs=(P("pp"), P(), P(), P(), P(), P()),
        out_specs=P(),
        check_vma=False)
    ys = fn(stacked, table, head, raw_xs, hidden_ex, out_ex)
    return ys.reshape(M * MB, V)


def test_ragged_forward_matches_sequential():
    mesh = _mesh()
    rng = np.random.default_rng(0)
    table, Ws, head = _params(rng)
    ids = jnp.asarray(rng.integers(0, V, size=(M * MB,)), jnp.int32)
    want = _sequential(jnp.asarray(table), jnp.asarray(Ws),
                       jnp.asarray(head), ids)
    got = _pipelined(jnp.asarray(table), _stacked_padded(Ws),
                     jnp.asarray(head), ids, mesh)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_gpt2_ragged_end_to_end():
    """GPT-2 with 6 blocks over 4 stages through the PRODUCT path:
    uniform finder fails (6 % 4 != 0), auto-ragged absorbs the
    embedding prologue and the LN+lm_head epilogue into the edge
    stages. Forward matches a sequential re-emission with the SAME
    (unstacked) weights exactly."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    import numpy as np
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.models import GPTConfig, build_gpt2

    batch, seq = 8, 16
    cfg = FFConfig()
    cfg.batch_size = batch
    cfg.pipeline_stages = 4
    cfg.pipeline_microbatches = 4
    ff = FFModel(cfg)
    g = GPTConfig(vocab_size=64, hidden_size=32, num_layers=6,
                  num_heads=4, max_position=seq, dropout=0.0)
    out = build_gpt2(ff, batch, seq, g)
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy", [],
               output_tensor=out)
    pipe = ff.executor.pipe
    assert pipe is not None and pipe.is_ragged, pipe
    assert sum(pipe.counts) == 6 and len(pipe.counts) == 4, pipe.counts
    assert pipe.prologue, "embedding prologue should be absorbed"
    assert pipe.epilogue, "LN+lm_head epilogue should be absorbed"
    # softmax stays outside for the CE-on-logits fusion
    assert all(l.op_type.name != "OP_SOFTMAX" for l in pipe.epilogue)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, g.vocab_size, size=(batch, seq)).astype(np.int32)
    b = {"input_ids": ids,
         "position_ids": np.tile(np.arange(seq, dtype=np.int32),
                                 (batch, 1))}

    fwd = ff.executor.make_forward()
    got = np.asarray(fwd(ff.params, ff.state, b))

    # oracle: flatten the stacked block params back to per-layer dicts
    # and emit the ORIGINAL program sequentially
    flat = {k: v for k, v in ff.params.items()
            if not k.startswith("pp::")}
    slot_of = ff.executor._ragged_slot_of()
    for lj, tl in enumerate(pipe.template):
        stacked = ff.params.get(pipe.param_name(tl))
        if stacked is None:
            continue        # weight-less template layer (add etc.)
        for bidx, names in enumerate(pipe.stage_layer_names):
            s, k = slot_of[bidx]
            flat[names[lj]] = {w: a[s, k] for w, a in stacked.items()}
    from flexflow_tpu.ops import EmitCtx
    ctx = EmitCtx(training=False, rngs={}, state=ff.state,
                  config=ff.config)
    want = np.asarray(ff.executor.program.emit(flat, b, ctx)[0])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    # and a train step decreases loss
    lab = ids
    bt = dict(b, label=lab)
    step = ff.executor.make_train_step()
    l0 = float(np.asarray(ff._run_train_step(step, bt)["loss"]))
    for _ in range(4):
        li = float(np.asarray(ff._run_train_step(step, bt)["loss"]))
    assert np.isfinite(l0) and np.isfinite(li)
    assert li < l0, (l0, li)


def test_ragged_grads_match_sequential():
    mesh = _mesh()
    rng = np.random.default_rng(1)
    table, Ws, head = _params(rng)
    ids = jnp.asarray(rng.integers(0, V, size=(M * MB,)), jnp.int32)

    def loss_seq(table, Ws, head):
        return jnp.sum(_sequential(table, Ws, head, ids) ** 2)

    def loss_pipe(table, stacked, head):
        return jnp.sum(_pipelined(table, stacked, head, ids, mesh) ** 2)

    g_seq = jax.grad(loss_seq, argnums=(0, 1, 2))(
        jnp.asarray(table), jnp.asarray(Ws), jnp.asarray(head))
    g_pipe = jax.grad(loss_pipe, argnums=(0, 1, 2))(
        jnp.asarray(table), _stacked_padded(Ws), jnp.asarray(head))
    # prologue (embedding) grad
    np.testing.assert_allclose(np.asarray(g_pipe[0]),
                               np.asarray(g_seq[0]), rtol=1e-4,
                               atol=1e-5)
    # epilogue (head) grad
    np.testing.assert_allclose(np.asarray(g_pipe[2]),
                               np.asarray(g_seq[2]), rtol=1e-4,
                               atol=1e-5)
    # block grads: unpack the padded stacking; padded slots get zero
    i = 0
    gp = np.asarray(g_pipe[1])
    for s, c in enumerate(COUNTS):
        for k in range(CMAX):
            if k < c:
                np.testing.assert_allclose(gp[s, k],
                                           np.asarray(g_seq[1][i]),
                                           rtol=1e-4, atol=1e-5)
                i += 1
            else:
                np.testing.assert_allclose(gp[s, k], 0.0, atol=1e-7)
