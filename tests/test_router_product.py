"""The router of ``ops/moe_ops.py::RoutedExpertsOp``: its float32
product, which stays XLA's ``Precision.HIGHEST`` one (six bf16 passes
forward and in both cotangents: timed on the chip it runs at the matrix
unit's rate, ``examples/tpu_time_router_product.py``), and what follows
the product under ``moe.route``, where the chosen experts' own scores
and the held groups' sizes are a compare and a sum instead of a gather
and a scatter-add of single numbers (``own_scores``, ``group_sizes``):
held here, bit for bit, to ``jnp.take_along_axis`` and ``jnp.bincount``
and their transposes, at the benchmark's nine cells' expert counts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import FFConfig
from flexflow_tpu.ops import moe_ops
from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
from flexflow_tpu.ops.registry import EmitCtx

#: the nine expert cells' (published experts, top_k, experts held)
CELLS = {
    "cell3_joyai": (256, 8, 16),
    "cell4_lfm2": (64, 4, 8),
    "cell5_kimi": (256, 8, 8),
    "cell6_xing": (64, 4, 8),
    "cell7_keye": (128, 8, 16),
    "cell8_trinity": (128, 8, 16),
    "cell10_qwen3next": (512, 10, 32),
    "cell12_sdar": (128, 8, 16),
    "cell13_nemotron": (512, 22, 8),
}
TOKENS = 96


def drawn(n, k, seed=0):
    rng = np.random.default_rng(seed)
    scores = jnp.asarray(rng.standard_normal((TOKENS, n)), jnp.float32)
    ct = jnp.asarray(rng.standard_normal((TOKENS, k)), jnp.float32)
    return scores, jax.lax.top_k(scores, k)[1], ct


def same(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and np.array_equal(a, b)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_own_scores_is_the_gather(cell):
    n, k, _ = CELLS[cell]
    scores, idx, _ = drawn(n, k)
    assert same(moe_ops.own_scores(scores, idx),
                jnp.take_along_axis(scores, idx, axis=-1))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_its_transpose_is_the_gathers(cell):
    """A token's choices are distinct, so each expert's cotangent is one
    number or none: the scatter-add's result to the bit."""
    n, k, _ = CELLS[cell]
    scores, idx, ct = drawn(n, k, seed=1)

    def grad(fn):
        return jax.grad(lambda s: jnp.sum(fn(s) * ct))(scores)
    assert same(grad(lambda s: moe_ops.own_scores(s, idx)),
                grad(lambda s: jnp.take_along_axis(s, idx, axis=-1)))


def test_a_choice_named_twice_sums_its_cotangents():
    scores, _, ct = drawn(32, 4, seed=2)
    idx = jnp.asarray(np.random.default_rng(3).integers(0, 4, (TOKENS, 4)),
                      jnp.int32)
    got = jax.grad(lambda s: jnp.sum(moe_ops.own_scores(s, idx) * ct))(
        scores)
    want = jax.grad(lambda s: jnp.sum(
        jnp.take_along_axis(s, idx, axis=-1) * ct))(scores)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    assert float(jnp.max(jnp.abs(got[:, 4:]))) == 0


def test_own_scores_keeps_the_choice_alone_for_its_transpose():
    """The ``custom_vjp``'s residuals: ``idx`` and an EMPTY array that
    carries the experts' count; no (tokens, top_k, experts) array."""
    scores, idx, ct = drawn(128, 8)
    _, pull = jax.vjp(lambda s: moe_ops.own_scores(s, idx), scores)
    kept = [np.shape(v) for v in jax.tree.leaves(pull)]
    assert sorted(kept) == [(0, 128), (TOKENS, 8)]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_group_sizes_is_the_bincount(cell):
    """Of a layer's ``tokens x top_k`` assignments, those past the held
    experts among them (the trailing group, which is not counted)."""
    n, k, held = CELLS[cell]
    idx = drawn(n, k, seed=4)[1].reshape(-1)
    group = jnp.where(idx < held, idx, held)
    got = moe_ops.group_sizes(group, held)
    assert got.dtype == jnp.int32
    assert same(got, jnp.bincount(group, length=held + 1)[:held].astype(
        jnp.int32))
    assert int(jnp.sum(got)) == int(jnp.sum(idx < held))


def parents_route(logits, bias, top_k, scale, scoring):
    """``route()`` as it stood before PR 67."""
    if scoring == "softmax":
        biased = scores = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
        biased = scores + jax.lax.stop_gradient(bias)
    least, idx = jax.lax.top_k(biased, top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    gates = scale * chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    return idx, gates, jnp.sum(biased >= least[:, -1:], axis=0)


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
@pytest.mark.parametrize("cell", ["cell3_joyai", "cell10_qwen3next",
                                  "cell13_nemotron"])
def test_route_is_the_parents(cell, scoring):
    """Choice, gates and loads to the bit, and the gates' gradient
    through the scores."""
    n, k, _ = CELLS[cell]
    logits, _, ct = drawn(n, k, seed=5)
    bias = jnp.asarray(np.random.default_rng(6).normal(0, 0.02, n),
                       jnp.float32)
    got = moe_ops.route(logits, bias, k, 2.5, scoring, loads=True)
    want = parents_route(logits, bias, k, 2.5, scoring)
    assert all(same(g, w) for g, w in zip(got, want))

    def grad(fn):
        return jax.grad(lambda l: jnp.sum(fn(l)[1] * ct))(logits)
    np.testing.assert_allclose(
        grad(lambda l: moe_ops.route(l, bias, k, 2.5, scoring)),
        grad(lambda l: parents_route(l, bias, k, 2.5, scoring)),
        rtol=1e-6, atol=1e-9)


# ----------------------------------------------------------------------
# the layer
# ----------------------------------------------------------------------
E, F, N_EXPERTS, HELD, TOP_K = 128, 64, 128, 8, 4
PARAMS = dict(num_experts=N_EXPERTS, top_k=TOP_K, expert_dim=F, shared_dim=F,
              experts_held=HELD, first_held=0, scale=2.5, bias_std=0.02)


def layer_operands(dtype=jnp.float32, seed=13):
    ks = iter(jax.random.split(jax.random.key(seed), 9))

    def draw(*shape, scale):
        return scale * jax.random.normal(next(ks), shape)
    w = {"wg": draw(E, N_EXPERTS, scale=0.3),
         "bias": draw(N_EXPERTS, scale=0.05),
         "w_gate": draw(HELD, E, F, scale=0.2),
         "w_up": draw(HELD, E, F, scale=0.2),
         "w_down": draw(HELD, F, E, scale=0.2),
         "ws_gate": draw(E, F, scale=0.2), "ws_up": draw(E, F, scale=0.2),
         "ws_down": draw(F, E, scale=0.2)}
    return draw(2, 128, E, scale=1.0).astype(dtype), w


def loss(x, w):
    cfg = FFConfig()
    ctx = EmitCtx(training=True, config=cfg)
    (y,) = RoutedExpertsOp().emit(PARAMS, [x], w, ctx, "experts")
    return jnp.sum(jnp.sin(y.astype(jnp.float32)))


def routers(jaxpr, inside=False):
    """The equations under ``moe.route`` of a jaxpr and of the jaxprs
    inside it (whose own name stacks start anew at their call)."""
    for eqn in jaxpr.eqns:
        mine = inside or "moe.route" in str(eqn.source_info.name_stack)
        if mine:
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from routers(inner, mine)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_routers_three_products_are_float32_at_the_highest(dtype):
    """Forward and both cotangents: float32 operands, float32 result,
    ``Precision.HIGHEST`` on both sides (six bf16 passes on the chip),
    whatever the stream's type."""
    x, w = layer_operands(dtype)
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, w).jaxpr
    shapes = {(256, N_EXPERTS), (256, E), (E, N_EXPERTS), (N_EXPERTS, E)}
    mine = [e for e in routers(jaxpr) if e.primitive.name == "dot_general"
            and tuple(e.outvars[0].aval.shape) in shapes]
    assert len(mine) == 3
    highest = jax.lax.Precision.HIGHEST
    for e in mine:
        assert all(v.aval.dtype == jnp.float32 for v in e.invars + e.outvars)
        assert tuple(e.params["precision"]) == (highest, highest)


def test_no_gather_and_no_scatter_under_the_routers_scope():
    """What is left under ``moe.route`` forward and backward: the
    product, elementwise passes, the top-k and the two sorts."""
    x, w = layer_operands()
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, w).jaxpr
    names = {e.primitive.name for e in routers(jaxpr)}
    assert {"dot_general", "top_k", "sort"} <= names
    assert not {n for n in names if "gather" in n or "scatter" in n}
