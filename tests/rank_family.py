"""What the rank families' test files share (``tests/README.md``): one
tolerance rule, one way to build a family's tiny model and its batch,
the program's step and the reference's loss as jitted functions of the
weights, and the lowered train step that ``tests/test_lowered_steps.py``
pins. A family's file binds its own data to these (``functools.partial``:
which configuration class, which builder, which module of
``benchmarks/reference/``, which weights its ``spread`` moves) and owns
only its equations' tests.

Nothing heavy is called eagerly: an eager call of a function that holds
a ``jax.checkpoint``, a ``lax.scan`` or a ``custom_vjp`` compiles its
pieces one by one (five to seven times the jitted call's time on the
tiny models, ISSUE 59), so ``program``, ``reference_loss`` and
``reference_call`` jit what they run, and a file wraps an op's ``emit``
in ``jax.jit`` itself.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import cells
from flexflow_tpu import AdamOptimizer, FFConfig, FFModel
from flexflow_tpu.ops.registry import EmitCtx
from flexflow_tpu.parallel.machine import MachineSpec
from flexflow_tpu.runtime.metrics import COUNTER_PREFIX

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
B = 2


def reference(name):
    """The module ``benchmarks/reference/<name>.py``."""
    return cells.load_module(os.path.join(ROOT, "benchmarks"), "reference",
                             name)


def _relative_error(got, want, floor):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.max(np.abs(want))), floor)
    return float(np.max(np.abs(got - want))) / scale


def close(got, want, tol=TOL, floor=1e-6):
    """The largest difference, relative to ``want``'s largest entry (or
    to ``floor``, for a ``want`` that is zero but for rounding)."""
    err = _relative_error(got, want, floor)
    assert err <= tol, f"relative error {err:.3e} > {tol}"


def apart(got, want, tol=50 * TOL):
    assert _relative_error(got, want, 1e-6) > tol


def f32_ctx(training=True, impl=None):
    """An ``EmitCtx`` that computes in float32; ``impl`` forces the
    attention path (``"xla"``, ``"flash"``) as an adopted plan would."""
    cfg = FFConfig()
    cfg.use_bf16_compute = False
    ctx = EmitCtx(training=training, config=cfg)
    ctx.kernel_impls = {"attention": impl} if impl else None
    return ctx


def sizes_of(mc):
    """The configuration as the references read it: the config.json keys,
    with ``num_experts_published`` filled in where a share leaves it."""
    sizes = dataclasses.asdict(mc)
    if sizes.get("num_experts_published", 0) is None:
        sizes["num_experts_published"] = mc.num_experts
    return sizes


def build(config, builder, remat="none", model_cfg=None, batch=B, seq=32,
          attention=None, devices=None):
    """The compiled model of ``model_cfg`` (``config.tiny()`` unless
    given) by ``builder``: float32, no search, ``remat`` as asked;
    ``attention`` forces every attention layer's path and ``devices``
    cuts the mesh to the first few of the 8 CPU devices (a forced kernel
    runs on one, as the benchmark's chip is: on more the masked kernels
    have no ``shard_map`` wrap)."""
    cfg = FFConfig()
    cfg.batch_size = batch
    cfg.only_data_parallel = True        # no search: 0.3 s a compile
    cfg.use_bf16_compute = False
    cfg.remat = remat
    if attention:
        cfg.kernel_impls = f"attention:{attention}"
    ff = FFModel(cfg)
    mc = model_cfg or config.tiny()
    out = builder(ff, batch, seq, mc)
    some = {"machine_spec": MachineSpec.detect(jax.devices()[:devices])} \
        if devices else {}
    ff.compile(AdamOptimizer(1e-3), "sparse_categorical_crossentropy", [],
               output_tensor=out, **some)
    return ff, mc


def data(mc, seq=32, seed=1, batch=B):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, mc.vocab_size, (batch, seq)).astype(np.int32)
    pos = np.tile(np.arange(seq, dtype=np.int32), (batch, 1))
    return {"input_ids": jnp.asarray(ids), "position_ids": jnp.asarray(pos),
            "label": jnp.asarray(np.roll(ids, -1, 1)[..., None])}


def spread(params, rule, seed=3):
    """The seed's weights with some pushed off their initial value, so
    that a lost norm, gate or scale shows: ``rule(layer, key, w, rng)``
    gives the moved weight, or None for one that stays. Which weights
    move is the family's knowledge; the walk over them is this."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, ws in params.items():
        out[name] = {}
        for k, w in ws.items():
            moved = rule(name, k, w, rng)
            out[name][k] = w if moved is None else moved
    return on_one_device(out)


def scaled(w, rng):
    """``w`` times a uniform draw in (0.5, 1.5) an entry."""
    return w * jnp.asarray(rng.uniform(0.5, 1.5, w.shape), w.dtype)


def shifted(w, rng):
    """``w`` plus a uniform draw in (-0.5, 0.5) an entry."""
    return w + jnp.asarray(rng.uniform(-0.5, 0.5, w.shape), w.dtype)


def on_one_device(params):
    """The weights as arrays of the first device. A compiled model's
    live replicated on the mesh of the 8 CPU devices, and a jitted
    function of them follows them there: with nothing in it sharded
    (these models are built without a search, their batch of 2 whole on
    every device) it does the same arithmetic eight times over, on the
    cores that five other workers share."""
    return jax.tree.map(jnp.asarray, jax.device_get(params))


def _of_the_weights(fn):
    fn = jax.jit(fn)
    return lambda params: fn(on_one_device(params))


def named(ff, params):
    return [(l.name, params[l.name]) for l in ff.layers
            if l.name in params]


def forward(ff, params, batch, training=True):
    """The program's step as the executor runs it, traced where it is
    called: ``(loss, metrics, outputs, auxiliary losses, captured)``."""
    ex = ff.executor
    outs, _, aux, capture = ex._forward(
        params, ff.state, batch, training, jnp.int32(0))
    loss, bm = ex._loss_and_metrics(outs, capture, batch["label"], aux)
    return loss, bm, outs, aux, capture


def program(ff, params, batch, training=True):
    """``(loss, metrics, probabilities)`` of the program's step."""
    def step(params, batch):
        loss, bm, outs, _, _ = forward(ff, params, batch, training)
        return loss, bm, outs[0]
    return jax.jit(step)(on_one_device(params), batch)


def stepper(ff, batch):
    """The program's training step as one jitted function of the
    weights, ``((loss, metrics), gradients)``: for a test that runs it
    at several sets of weights."""
    def step(params):
        loss, bm, _, _, _ = forward(ff, params, batch)
        return loss, bm
    return _of_the_weights(jax.value_and_grad(step, has_aux=True))


def step_and_gradients(ff, params, batch):
    return stepper(ff, batch)(params)


def same_step(got, want, loss_tol=1e-6, grad_tol=1e-5):
    """Two results of ``step_and_gradients`` are one step: the loss,
    every counter among the metrics and every weight's gradient."""
    (loss, bm), grads = got
    (want_loss, want_bm), want_grads = want
    close(loss, want_loss, loss_tol)
    for key in want_bm:
        if key.startswith(COUNTER_PREFIX):
            close(bm[key], want_bm[key], loss_tol)
    for name, ws in want_grads.items():
        for k in ws:
            close(grads[name][k], ws[k], grad_tol)


def fixtures(build, data, spread=None):
    """The two module-scoped fixtures every family's file has: ``tiny``,
    the family's tiny model, its configuration, its batch and (where the
    family has a ``spread`` rule) its spread weights; ``tiny_step``, that
    model's ``step_and_gradients`` at those weights, which the gradient
    test, the remat test and the counters test all read."""
    @pytest.fixture(scope="module", name="tiny")
    def tiny():
        ff, mc = build()
        return (ff, mc, data(mc)) + ((spread(ff.params),) if spread else ())

    @pytest.fixture(scope="module", name="tiny_step")
    def tiny_step(tiny):
        ff, _, batch = tiny[:3]
        return step_and_gradients(ff, tiny[-1] if spread else ff.params,
                                  batch)

    return tiny, tiny_step


def reference_call(fn, ff, mc, params, batch):
    """``fn`` of a reference module (its decoder, its heads, ...) on the
    program's weights by layer name, the sizes, the ids and positions."""
    return _of_the_weights(lambda p: fn(
        named(ff, p), sizes_of(mc), batch["input_ids"],
        batch["position_ids"]))(params)


def _reference_loss(ref, ff, mc, batch):
    return lambda p: ref.loss(
        named(ff, p), sizes_of(mc), batch["input_ids"],
        batch["position_ids"], batch["label"][..., 0])


def reference_loss(ref, ff, mc, params, batch):
    return _of_the_weights(_reference_loss(ref, ff, mc, batch))(params)


def reference_grader(ref, ff, mc, batch):
    """The gradient of the reference's loss, jitted, by the weights."""
    return _of_the_weights(jax.grad(_reference_loss(ref, ff, mc, batch)))


def reference_gradients(ref, ff, mc, params, batch):
    return reference_grader(ref, ff, mc, batch)(params)


def refuses(ref, fn, match, ff, sizes, batch):
    """``fn`` of a reference module raises its ``ReferenceMismatch`` for
    ``sizes`` while it walks the layers: no value is computed."""
    with pytest.raises(ref.ReferenceMismatch, match=match):
        jax.eval_shape(lambda p: fn(named(ff, p), sizes, batch["input_ids"],
                                    batch["position_ids"]), ff.params)


def lowered_text(fn, *args):
    """The StableHLO text ``fn`` lowers to for ``args``, from cold caches.

    The text is JAX's, and JAX shares a jitted ``jnp`` function
    (``_where``, ``floor_divide``, ``clip``...) between its call sites as
    ONE private function only where both sites' traces came out of the
    same cache entry. After enough other tests in the process (PR 55
    found it with five files ahead of the pin, none of which does it
    alone) some of those entries have been evicted while the experts'
    inline-jitted loops still hold jaxprs traced from them: the same
    step then lowers with 82 private functions where a fresh process
    emits 78. Whoever compares or pins a text owns what it reads."""
    jax.clear_caches()
    return jax.jit(fn).lower(*args).as_text()


def lowered_step(mc, builder, remat, batch=2, seq=32):
    """The lowered train step of ``builder``'s graph for ``mc``: the
    default ``FFConfig`` (bfloat16 compute) but no search, ``batch`` x
    ``seq`` ids drawn from seed 0, caches cold before the model is built
    (``lowered_text`` says why)."""
    jax.clear_caches()
    cfg = FFConfig()
    cfg.batch_size = batch
    cfg.only_data_parallel = True
    cfg.remat = remat
    ff = FFModel(cfg)
    out = builder(ff, batch, seq, mc)
    ff.compile(AdamOptimizer(1e-3), "sparse_categorical_crossentropy", [],
               output_tensor=out)
    return ff.executor.make_train_step().lower(
        ff.params, ff.opt_state, ff.state, jnp.int32(0),
        data(mc, seq, seed=0, batch=batch)).as_text()
