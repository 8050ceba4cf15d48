"""The routed experts' way back to tokens by the Pallas kernel
(``kernels/moe_token_sum.py``, interpret mode on the CPU) against the
plain sum of ``ops/moe_ops.py::_of_each_choice``, at widths of 128 and
256 (the kernel takes ``hidden`` in whole lanes) and a few hundred
tokens.

The plain path is the one ``tests/test_latent_moe.py`` and its three
sister files hold to the references; here the two paths of one sum, and
of one layer, are held to each other: sums to 1e-6 and the layer's
gradients to 1e-5 of the largest entry.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import FFConfig
from flexflow_tpu.kernels import moe_token_sum as kernel
from flexflow_tpu.obs import events
from flexflow_tpu.ops import moe_ops
from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
from flexflow_tpu.ops.registry import EmitCtx


def close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape and np.all(np.isfinite(got))
    scale = max(float(np.max(np.abs(want))), 1e-6)
    err = float(np.max(np.abs(got - want))) / scale
    assert err <= tol, f"relative error {err:.3e} > {tol}"


def sort_of(tokens, k, held, n, budget, chunk=0, seed=0):
    """``(mine, at, inside)`` as ``RoutedExpertsOp`` makes them for
    chunk ``chunk`` of the budget: every token's ``k`` distinct choices
    among ``n`` experts, the first ``held`` of them held, a stable sort
    by held expert padded to whole chunks; ``inside`` the held groups'
    rows in the chunk."""
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.permutation(n)[:k] for _ in range(tokens)])
    group = np.where(idx.reshape(-1) < held, idx.reshape(-1), held)
    order = np.argsort(group, kind="stable").astype(np.int32)
    inverse = np.argsort(order).astype(np.int32)
    order = np.pad(order, (0, -len(order) % budget))
    lo = chunk * budget
    ends = np.cumsum(np.bincount(group, minlength=held + 1)[:held])
    inside = np.diff(np.clip(ends, lo, lo + budget), prepend=lo)
    return order[lo:lo + budget], inverse - lo, inside.astype(np.int32)


def rows_of(budget, hidden, live, dtype, seed=1, past=0.0):
    rows = np.random.default_rng(seed).standard_normal(
        (budget, hidden)).astype(np.float32)
    rows[live:] = past
    return jnp.asarray(rows, dtype)


def plain_sum(src, at, w):
    """``_combine``'s and ``_rows_for_bwd``'s own lines."""
    return sum(w[:, j:j + 1] * rows.astype(jnp.float32) for j, rows in
               enumerate(moe_ops._of_each_choice(src, jnp.asarray(at),
                                                 w.shape[1])))


def gates_of(tokens, k, seed=2):
    return jnp.asarray(np.random.default_rng(seed).random((tokens, k)),
                       jnp.float32)


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["gates", "ones"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("k", [4, 8])
def test_the_kernel_is_the_plain_sum(k, dtype, weighted):
    """Both uses' operands: float32 rows times gates (``combine``) and
    bf16 rows under ones (the row gather's transpose); the products and
    the sum float32 on both paths, so they differ by the order of at
    most ``k`` terms."""
    tokens, hidden, budget = 192, 128, 512
    mine, at, inside = sort_of(tokens, k, 4, 16, budget)
    live = int(inside.sum())
    assert 0 < live < budget
    src = rows_of(budget, hidden, live, dtype)
    w = gates_of(tokens, k) if weighted else None
    got = kernel.token_sum(src, jnp.asarray(mine), jnp.asarray(inside),
                           tokens, k, w)
    assert got.dtype == jnp.float32 and got.shape == (tokens, hidden)
    close(got, plain_sum(src, at, jnp.ones((tokens, k)) if w is None
                         else w), 1e-6)


@pytest.mark.parametrize("chunk", [0, 1, 2])
def test_assignments_outside_the_rows_read_nothing(chunk):
    """``at`` below 0 (an earlier chunk's), at ``len(src)`` and beyond
    (a later chunk's, and the absent experts') add nothing to the plain
    sum, and the kernel, which walks the chunk's own live rows, is that
    sum in every chunk of an overflowing sort."""
    tokens, k, hidden, budget = 96, 4, 128, 128
    mine, at, inside = sort_of(tokens, k, 12, 16, budget, chunk)
    live = int(inside.sum())
    assert np.any(at < 0) == (chunk > 0) and live > 0
    assert np.any(at == budget) == np.any(at > budget) == (chunk < 2)
    src = rows_of(budget, hidden, live, jnp.float32, seed=chunk)
    w = gates_of(tokens, k)
    got = kernel.token_sum(src, jnp.asarray(mine), jnp.asarray(inside),
                           tokens, k, w)
    close(got, plain_sum(src, at, w), 1e-6)


def test_no_live_choice_is_zeros_and_three_are_their_sum():
    """Three groups of 2, 0 and 2 rows: token 5 has three live choices
    (two experts' rows, one group apart), token 2 one, the others
    none."""
    tokens, k, hidden, budget = 8, 4, 128, 16
    order = np.zeros(budget, np.int32)               # padding names 0
    for row, (t, j) in enumerate(((2, 1), (5, 0), (5, 2), (5, 3))):
        order[row] = t * k + j
    src = rows_of(budget, hidden, budget, jnp.float32)
    w = gates_of(tokens, k)
    got = np.asarray(kernel.token_sum(
        src, jnp.asarray(order), jnp.asarray([2, 0, 2], jnp.int32), tokens,
        k, w))
    s, w = np.asarray(src), np.asarray(w)
    want = np.zeros((tokens, hidden), np.float32)
    want[2] = w[2, 1] * s[0]
    want[5] = w[5, 0] * s[1] + w[5, 2] * s[2] + w[5, 3] * s[3]
    assert not np.any(got[[0, 1, 3, 4, 6, 7]])
    close(got, want, 1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
def test_rows_past_the_live_ones_are_not_read(dtype):
    """The last chunk of a padded sort: the padding "sorts last and
    reads token 0", the absent experts' rows stand before it, and the
    grouped products leave all of them unwritten on the TPU. The kernel
    reads none of them: NaNs there reach nothing, and token 0 gets its
    own rows only."""
    tokens, k, hidden, budget = 90, 4, 128, 128
    mine, at, inside = sort_of(tokens, k, 4, 16, budget, chunk=0)
    live = int(inside.sum())
    assert live < budget - 16
    last, _, none = sort_of(tokens, k, 4, 16, budget, chunk=2)
    assert not np.any(last[-24:])            # 360 rows in 3 chunks
    w = gates_of(tokens, k)
    want = plain_sum(rows_of(budget, hidden, live, dtype), at, w)
    got = kernel.token_sum(
        rows_of(budget, hidden, live, dtype, past=np.nan),
        jnp.asarray(mine), jnp.asarray(inside), tokens, k, w)
    close(got, want, 1e-6)
    # a chunk of nothing but absent rows and padding: no live row, zeros
    assert not np.any(none)
    got = kernel.token_sum(rows_of(budget, hidden, 0, dtype, past=np.nan),
                           jnp.asarray(last), jnp.asarray(none), tokens, k,
                           w)
    assert not np.any(np.asarray(got))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("tile", [None, 8, 32, 40],
                         ids=["own", "8", "32", "40"])
def test_a_token_count_that_is_no_multiple_of_the_tile(tile, dtype):
    """100 tokens: one tile of 128 by the shapes' own rule, and tiles of
    8, 32 and 40 with a last one that reaches past the tokens; runs
    that start and end anywhere in the blocks of 8 (16) rows they are
    copied in, more blocks a tile than there are slots."""
    tokens, k, hidden, budget = 100, 8, 256, 304
    mine, at, inside = sort_of(tokens, k, 8, 32, budget)
    live = int(inside.sum())
    assert kernel.SLOTS * 16 < live < budget
    assert kernel.tile_tokens(tokens, hidden, dtype) == 128
    src = rows_of(budget, hidden, live, dtype, past=np.nan)
    w = gates_of(tokens, k)
    got = kernel.token_sum(src, jnp.asarray(mine), jnp.asarray(inside),
                           tokens, k, w, tile=tile)
    assert got.shape == (tokens, hidden)
    close(got, plain_sum(rows_of(budget, hidden, live, dtype), at, w), 1e-6)


def test_the_run_starts_are_the_groups_rows_by_tile():
    """``starts[i, g]`` against a count by hand: rows of group ``g``
    whose token is below ``i * tile``, from the group's first row."""
    tokens, k, budget, tile = 100, 8, 304, 32
    mine, _, inside = sort_of(tokens, k, 8, 32, budget)
    got = np.asarray(kernel.run_starts(jnp.asarray(mine),
                                       jnp.asarray(inside), tokens, k, tile))
    ends = np.cumsum(inside)
    for g in range(8):
        tok = mine[ends[g] - inside[g]:ends[g]] // k
        assert np.all(np.diff(tok) > 0)
        for i in range(5):
            assert got[i, g] == ends[g] - inside[g] + np.sum(tok < i * tile)
    assert np.array_equal(got[4], ends)


# ---------------------------------------------------------------------------
# the layer: 256 tokens x top 4 of 32 experts, 4 held, 1,024 sorted rows
# against a budget of 512
# ---------------------------------------------------------------------------
E, F, N_EXPERTS, HELD, FIRST, TOP_K = 128, 64, 32, 4, 8, 4
PARAMS = dict(num_experts=N_EXPERTS, top_k=TOP_K, expert_dim=F,
              shared_dim=F, experts_held=HELD, first_held=FIRST, scale=2.5)


def layer_operands(overflow, seed=13):
    """``overflow`` adds 2 to the held experts' bias: every choice of
    every token is theirs, 1,024 live rows, two chunks."""
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
    if overflow:
        w["bias"] = w["bias"].at[FIRST:FIRST + HELD].add(2.0)
    return draw(2, 128, E, scale=1.0), w


def layer(x, w, bf16=False):
    cfg = FFConfig()
    cfg.use_bf16_compute = bf16
    ctx = EmitCtx(training=True, config=cfg)
    (y,) = RoutedExpertsOp().emit(PARAMS, [x], w, ctx, "experts")
    return y, ctx.counters


def step():
    """Made anew for each path: ``jax.jit`` keeps its traces by
    function, and the path is chosen at trace time."""
    def loss(x, w):
        y, counters = layer(x, w)
        return jnp.sum(jnp.sin(y)), (y, counters)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))


def paths_of(events_seen):
    return [e["attrs"]["token_sum"] for e in events_seen
            if e["name"] == "moe.route"]


@pytest.mark.parametrize("overflow", [False, True],
                         ids=["inside_the_budget", "over_it"])
def test_the_layer_through_the_kernel_is_the_plain_layer(overflow,
                                                         monkeypatch):
    """Output and the gradients of the input, the router (the gates'
    path through ``wg``) and the three stacked weights, once inside the
    budget and once with the further chunks' loop running the kernel
    (forward, and rematerialised in the backward)."""
    x, w = layer_operands(overflow)
    assert RoutedExpertsOp.rows_multiplied(256, PARAMS) == 512
    assert kernel.takes_kernel(256, E, TOP_K, 512, HELD, jnp.float32)
    events.enable()
    events.clear()
    try:
        (_, (y, counters)), got = step()(x, w)
        monkeypatch.setattr(kernel, "takes_kernel", lambda *a: False)
        (_, (want_y, _)), want = step()(x, w)
        assert paths_of(events.events()) == ["kernel", "plain"]
    finally:
        events.disable()
        events.clear()
    assert float(counters["moe.overflow"]) == overflow
    assert float(counters["moe.dropped"]) == 0
    close(y, want_y, 1e-5)
    close(got[0], want[0], 1e-5)
    for key in ("wg", "w_gate", "w_up", "w_down"):
        assert float(jnp.max(jnp.abs(want[1][key]))) > 0
        close(got[1][key], want[1][key], 1e-5)


def test_the_layer_at_bf16_operands_sums_the_cotangent_in_float32(
        monkeypatch):
    """The row gather's transpose is handed bf16 rows where the cell
    computes in bf16: both paths sum them in float32 and round once."""
    x, w = layer_operands(False)

    def grad():
        return jax.jit(jax.grad(
            lambda x, w: jnp.sum(jnp.sin(layer(x, w, True)[0]))))
    events.enable()
    events.clear()
    try:
        got = grad()(x, w)
        monkeypatch.setattr(kernel, "takes_kernel", lambda *a: False)
        want = grad()(x, w)
        assert paths_of(events.events()) == ["kernel", "plain"]
    finally:
        events.disable()
        events.clear()
    close(got, want, 1e-5)


@pytest.mark.parametrize("shape, takes", [
    ((4096, 2048, 8, 4096, 16, jnp.float32), True),      # cell 3
    ((8192, 2048, 4, 8192, 8, jnp.bfloat16), True),      # cell 4
    ((4096, 2304, 8, 4096, 8, jnp.bfloat16), True),      # cell 5
    ((4096, 3584, 4, 4096, 8, jnp.float32), True),       # cell 6
    ((256, 128, 4, 512, 4, jnp.float32), True),
    ((256, 64, 4, 512, 4, jnp.float32), False),      # half a lane row
    ((256, 192, 4, 512, 4, jnp.float32), False),     # a row and a half
    ((256, 128, 4, 512, 4, jnp.float16), False),
    ((256, 128, 4, 504, 4, jnp.float32), True),      # whole blocks of 8
    ((256, 128, 4, 504, 4, jnp.bfloat16), False),    # not of 16
    ((256, 128, 4, 1024, 4, jnp.float32), False),    # every row is live
    ((256, 128, 2, 512, 4, jnp.float32), False),     # rows + tokens > k t
    ((65536, 2048, 8, 65536, 16, jnp.float32), False),   # SMEM's room
    ((4096, 2 ** 20, 8, 512, 16, jnp.float32), False),   # VMEM's
], ids=lambda v: "-".join(str(getattr(i, "__name__", i)) for i in v)
    if isinstance(v, tuple) else str(v))
def test_takes_kernel_is_read_from_the_shapes(shape, takes):
    assert kernel.takes_kernel(*shape) is takes


@pytest.mark.parametrize("width, path", [(128, "kernel"), (64, "plain")])
def test_the_route_instant_says_which_path_ran(width, path):
    events.enable()
    events.clear()
    try:
        x, w = layer_operands(False)
        if width != E:
            x = x[..., :width]
            w = {k: v[:width] if k in ("wg", "ws_gate", "ws_up") else
                 v[:, :width] if k in ("w_gate", "w_up") else
                 v[..., :width] if k in ("w_down", "ws_down") else v
                 for k, v in w.items()}
        jax.jit(jax.grad(lambda x: jnp.sum(layer(x, w)[0])))(x)
        (route,) = [e["attrs"] for e in events.events()
                    if e["name"] == "moe.route"]
        assert route["token_sum"] == path and route["rows_budget"] == 512
        calls = [e["attrs"] for e in events.events()
                 if e["name"] == "moe.kernel"]
        if path == "plain":
            assert not calls
        else:
            assert [c["use"] for c in calls] == ["combine", "rows_for_bwd"]
            assert all(c["layer"] == "experts" and c["rows"] == 512
                       and c["tile"] == 256
                       and c["vmem_bytes"] == kernel.vmem_bytes(
                           256, width, jnp.float32) for c in calls)
    finally:
        events.disable()
        events.clear()


def test_under_a_mesh_of_several_devices_the_plain_path(monkeypatch):
    """The sort is over the global batch there; no cell runs it."""
    import types
    events.enable()
    events.clear()
    try:
        x, w = layer_operands(False)
        cfg = FFConfig()
        cfg.use_bf16_compute = False
        ctx = EmitCtx(training=True, config=cfg)
        ctx.mesh = types.SimpleNamespace(size=4)
        RoutedExpertsOp().emit(PARAMS, [x], w, ctx, "experts")
        (route,) = [e["attrs"] for e in events.events()
                    if e["name"] == "moe.route"]
        assert route["token_sum"] == "plain"
    finally:
        events.disable()
        events.clear()
