"""Compile-only check of the Pallas kernels against a TPU v5e topology.

No chip is needed: ``libtpu`` describes a v5e 2x2 host, and lowering with
``ShapeDtypeStruct`` operands placed on its devices runs the real XLA:TPU
and Mosaic compilers. This is what CPU tests cannot see — interpret mode
lowers a kernel to plain XLA ops, so a kernel that GSPMD refuses to
partition, or one that overflows VMEM, still passes there.

It proves compilation only. Whether the kernels compute the right values
on the chip is ``chip_smoke.py``'s job.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flexflow_tpu.kernels import flash_attention


@pytest.fixture(scope="module")
def v5e_devices():
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any libtpu failure = no topology
        pytest.skip(f"libtpu cannot describe a v5e:2x2 topology: {e!r}")
    return topo.devices


@pytest.fixture
def chip_locations():
    """MLIR locations as ``enable_compilation_cache`` sets them on an
    accelerator (it leaves them alone on the CPU platform)."""
    from flexflow_tpu.utils.compilation_cache import one_frame_locations
    prev = jax.config.jax_traceback_in_locations_limit
    one_frame_locations()
    yield
    jax.config.update("jax_traceback_in_locations_limit", prev)


MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _flash_loss(mesh, spec, q, k, v):
    o = flash_attention(q, k, v, causal=True, interpret=False, mesh=mesh,
                        spec=spec)
    return jnp.sum(o.astype(jnp.float32))


def _kernel_names(txt):
    """The Mosaic calls' instruction names; outside any ``named_scope``
    XLA wraps them as ``jvp_<name>_`` / ``transpose_jvp_<name>__``."""
    names = (l.split(" = ")[0].split("%")[-1].rsplit(".", 1)[0]
             for l in txt.splitlines() if MOSAIC_CALL in l)
    return sorted(re.sub(r"^(transpose_)?(jvp_)?|_+$", "", n)
                  for n in names)


FLASH_NAMES = ["flash_attention_bwd_dkv", "flash_attention_bwd_dq",
               "flash_attention_fwd"]


# forward + backward at the backward tiles derived from the shapes
# (flash_attention.bwd_tiles): cell 2 of the benchmark, a longer
# sequence, a wider head
@pytest.mark.parametrize("shape", [(2, 12, 1024, 64), (1, 12, 2048, 64),
                                   (1, 8, 1024, 128)])
def test_flash_fwd_bwd_compiles_on_one_device(v5e_devices, chip_locations,
                                              shape):
    mesh = Mesh(np.array(v5e_devices[:1]), ("x0",))
    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                               sharding=NamedSharding(mesh, P()))
    txt = _compile_text(
        jax.grad(functools.partial(_flash_loss, None, None),
                 argnums=(0, 1, 2)), qkv, qkv, qkv)
    assert _kernel_names(txt) == FLASH_NAMES


# the forward alone at the blocks derived from the shapes
# (flash_attention.fwd_tiles): (batch, heads, sq, sk, d, dv, dtype, causal,
# dropout) -> (block_q, block_k). The benchmark's three cells, a length
# whose keys do not fit one block, one walked in one piece; then float32
# with dropout, not causal, over 2,048 keys and more, which Mosaic refused
# while the pieces of a k block were unrolled inline (PR 32's review), and
# the one causal case a sweep of 488 found refused after that
FWD_COMPILE_CASES = {
    "cell1": ((8, 16, 512, 512, 64, 64, "bfloat16", False, 0.1), (512, 512)),
    "cell2": ((12, 12, 1024, 1024, 64, 64, "bfloat16", True, 0.0),
              (1024, 1024)),
    "cell3": ((1, 32, 4096, 4096, 192, 128, "bfloat16", True, 0.0),
              (1024, 4096)),
    "s8192": ((1, 4, 8192, 8192, 128, 128, "bfloat16", True, 0.0),
              (1024, 4096)),
    "s768": ((1, 4, 768, 768, 64, 64, "bfloat16", True, 0.0), (768, 768)),
    "f32_dropout_2048_d128": (
        (1, 4, 2048, 2048, 128, 128, "float32", False, 0.1), None),
    "f32_dropout_4096_d128": (
        (1, 4, 4096, 4096, 128, 128, "float32", False, 0.1), None),
    "f32_dropout_1024x2048_d128": (
        (1, 4, 1024, 2048, 128, 128, "float32", False, 0.1), None),
    "f32_dropout_2048_d256": (
        (1, 2, 2048, 2048, 256, 256, "float32", False, 0.1), None),
    "f32_dropout_4096_d256": (
        (1, 2, 4096, 4096, 256, 256, "float32", False, 0.1), None),
    "bf16_dropout_4096_d256": (
        (1, 2, 4096, 4096, 256, 256, "bfloat16", False, 0.1), None),
    "f32_dropout_1024_d256_causal": (
        (1, 2, 1024, 1024, 256, 256, "float32", True, 0.1), None),
}


@pytest.mark.parametrize("case", sorted(FWD_COMPILE_CASES))
def test_flash_forward_compiles_at_the_derived_blocks(
        v5e_devices, chip_locations, case):
    from flexflow_tpu.kernels.flash_attention import fwd_tiles
    (b, h, sq, sk, d, dv, dtype, causal, rate), want = FWD_COMPILE_CASES[case]
    dtype = jnp.dtype(dtype)
    if want is not None:
        assert fwd_tiles(sq, sk, d, dtype, rate > 0, dv) == want
    mesh = Mesh(np.array(v5e_devices[:1]), ("x0",))
    sh = NamedSharding(mesh, P())
    q = jax.ShapeDtypeStruct((b, h, sq, d), dtype, sharding=sh)
    k = jax.ShapeDtypeStruct((b, h, sk, d), dtype, sharding=sh)
    v = jax.ShapeDtypeStruct((b, h, sk, dv), dtype, sharding=sh)
    txt = _compile_text(
        lambda q, k, v: flash_attention(
            q, k, v, causal=causal, interpret=False, dropout_rate=rate,
            dropout_seed=jnp.int32(3)), q, k, v)
    assert _kernel_names(txt) == ["flash_attention_fwd"]
    # the log-sum-exp leaves the kernel one float32 a row (PR 39), not
    # replicated over 128 lanes
    assert f"f32[{b * h},1,{sq}]" in txt
    assert dtype == jnp.float32 or f"f32[{b * h},{sq},128]" not in txt


@pytest.mark.parametrize("shape", [(4, 12, 1024, 64), (2, 8, 4096, 128)])
def test_flash_forward_under_shard_map_compiles_on_2x2(
        v5e_devices, chip_locations, shape):
    mesh = Mesh(np.array(v5e_devices).reshape(2, 2), ("x0", "x1"))
    spec = P("x0", "x1")          # batch over x0, heads over x1
    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                               sharding=NamedSharding(mesh, spec))
    txt = _compile_text(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False, mesh=mesh,
                                        spec=spec), qkv, qkv, qkv)
    assert _kernel_names(txt) == ["flash_attention_fwd"]
    assert "all-gather" not in txt    # operands stay where they are


def test_flash_with_unequal_head_sizes_compiles_at_the_latent_shape(
        v5e_devices, chip_locations):
    """The three kernels at the shape of ``joyai_llm_flash.train.1chip``:
    32 heads, 4096 positions, q.k over 192 and p.v over 128, bf16,
    causal, at the backward tiles ``bwd_tiles`` hands out for them."""
    from flexflow_tpu.kernels.flash_attention import bwd_tiles
    mesh = Mesh(np.array(v5e_devices[:1]), ("x0",))
    sh = NamedSharding(mesh, P())
    qk = jax.ShapeDtypeStruct((1, 32, 4096, 192), jnp.bfloat16, sharding=sh)
    v = jax.ShapeDtypeStruct((1, 32, 4096, 128), jnp.bfloat16, sharding=sh)
    # (since PR 39 counts one float32 a row for the statistics, 192 / 192
    # fits the same tiles; before, v's 128 lanes bought dkv its width)
    assert bwd_tiles(4096, 4096, 192, jnp.bfloat16, False, 128) == (
        (1024, 1024), (1024, 1024))
    txt = _compile_text(
        jax.grad(functools.partial(_flash_loss, None, None),
                 argnums=(0, 1, 2)), qk, qk, v)
    assert _kernel_names(txt) == FLASH_NAMES
    # no operand was padded to a common head size
    assert "bf16[32,4096,256]" not in txt


# what `auto` moved onto the kernels in PR 30: not causal, dropout inside
# the kernels; cell 1 of the benchmark (BERT-large, 8 x 512), and the two
# head sizes at the shortest length the rule moves
@pytest.mark.parametrize("shape", [(8, 16, 512, 64), (32, 16, 256, 64),
                                   (32, 8, 256, 128)])
def test_flash_with_dropout_compiles_at_the_shapes_auto_moved(
        v5e_devices, chip_locations, shape):
    from flexflow_tpu.ops.nn_ops import MultiHeadAttentionOp as mha
    s, d = shape[2:]
    assert mha.auto_takes_flash(s, s, d, d, 0.1)
    mesh = Mesh(np.array(v5e_devices[:1]), ("x0",))
    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                               sharding=NamedSharding(mesh, P()))

    def loss(q, k, v):
        o = flash_attention(q, k, v, interpret=False, dropout_rate=0.1,
                            dropout_seed=jnp.int32(3))
        return jnp.sum(o.astype(jnp.float32))

    txt = _compile_text(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
    assert _kernel_names(txt) == FLASH_NAMES


@pytest.mark.parametrize("dropout", [False, True])
@pytest.mark.parametrize("dtype,d", [("bfloat16", 256), ("float32", 64),
                                     ("float32", 128), ("float32", 256)])
def test_flash_bwd_compiles_where_the_tile_rule_has_to_step_down(
        v5e_devices, chip_locations, dtype, d, dropout):
    """Mosaic refuses 1024 x 1024 backward tiles for wide heads, f32
    operands and dropout (over its 16 MiB of scoped VMEM): what the rule
    hands it instead has to compile."""
    from flexflow_tpu.kernels.flash_attention import bwd_tiles
    mesh = Mesh(np.array(v5e_devices[:1]), ("x0",))
    qkv = jax.ShapeDtypeStruct((1, 2, 2048, d), jnp.dtype(dtype),
                               sharding=NamedSharding(mesh, P()))
    if dropout or (d == 256 and dtype == "float32"):
        assert bwd_tiles(2048, 2048, d, qkv.dtype, dropout) != (
            (1024, 1024), (1024, 1024))

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, interpret=False,
                            dropout_rate=0.1 if dropout else 0.0,
                            dropout_seed=3)
        return jnp.sum(o.astype(jnp.float32))

    txt = _compile_text(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
    assert _kernel_names(txt) == FLASH_NAMES


# the backward as PR 39 left it (one float32 a row for the statistics,
# dkv's tile keys-major, dq's statistics by lanes from scratch) at the
# shapes of the benchmark's five cells: (batch, heads, s, d, dv, causal,
# dropout); cell 5 runs cell 3's shape
CELL_SHAPES = {
    "cell1_bert_large": (8, 16, 512, 64, 64, False, 0.1),
    "cell2_gpt2_124m": (12, 12, 1024, 64, 64, True, 0.0),
    "cell3_joyai_cell5_kimi": (1, 32, 4096, 192, 128, True, 0.0),
    "cell4_lfm2": (1, 32, 8192, 64, 64, True, 0.0),
}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_flash_backward_compiles_at_the_cells_shapes(v5e_devices,
                                                     chip_locations, cell):
    b, h, s, d, dv, causal, rate = CELL_SHAPES[cell]
    mesh = Mesh(np.array(v5e_devices[:1]), ("x0",))
    sh = NamedSharding(mesh, P())
    qk = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16, sharding=sh)
    v = jax.ShapeDtypeStruct((b, h, s, dv), jnp.bfloat16, sharding=sh)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=causal, interpret=False,
                            dropout_rate=rate,
                            dropout_seed=jnp.int32(3) if rate else None)
        return jnp.sum(o.astype(jnp.float32))

    txt = _compile_text(jax.grad(loss, argnums=(0, 1, 2)), qk, qk, v)
    assert _kernel_names(txt) == FLASH_NAMES
    # no row statistic replicated over 128 lanes, in or around the calls
    # (at dv 128 that is also the shape of ``do * o`` in float32)
    assert dv == 128 or f"f32[{b * h},{s},128]" not in txt
    assert f"f32[{b * h},1,{s}]" in txt


def test_flash_kernels_are_named_in_the_compiled_step(v5e_devices,
                                                      chip_locations):
    """What the benchmark's readers find a kernel by: the Pallas call's
    ``name`` is its HLO instruction's name in the compiled text (the
    profiler's trace names a device op by its instruction), and a
    ``named_scope`` around the call is in its ``op_name`` — under the
    locations the program sets on the chip (with
    ``jax_include_full_tracebacks_in_locations`` off, as PR 21 had it,
    both are lost: ``tpu_custom_call.N`` and a bare ``pallas_call``)."""
    mesh = Mesh(np.array(v5e_devices[:1]), ("x0",))
    qkv = jax.ShapeDtypeStruct((2, 12, 1024, 64), jnp.bfloat16,
                               sharding=NamedSharding(mesh, P()))

    def loss(q, k, v):
        with jax.named_scope("ff.forward"), jax.named_scope("attn_3"):
            return _flash_loss(None, None, q, k, v)

    txt = _compile_text(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
    calls = [l for l in txt.splitlines() if MOSAIC_CALL in l]
    assert _kernel_names(txt) == FLASH_NAMES
    by_name = {n: l for n in FLASH_NAMES for l in calls if f"%{n}." in l}
    assert 'jvp(ff.forward)/attn_3/flash_attention_fwd/pallas_call"' \
        in by_name["flash_attention_fwd"]
    for n in ("flash_attention_bwd_dq", "flash_attention_bwd_dkv"):
        assert f'transpose(jvp(ff.forward))/attn_3/{n}/pallas_call"' \
            in by_name[n]


def test_kernel_bytes_do_not_depend_on_who_traced_first(v5e_devices,
                                                        chip_locations):
    """The serialized Mosaic kernel sits inside the HLO that keys the
    persistent compile cache. With JAX's default full-traceback
    locations it embedded the Python call chain of its first tracer, and
    on the chip every program holding a kernel missed the cache on a
    second start. Under the setting ``enable_compilation_cache`` applies
    (``one_frame_locations``) the lowered text is the same from any call
    depth."""
    mesh = Mesh(np.array(v5e_devices[:1]), ("x0",))
    qkv = jax.ShapeDtypeStruct((1, 2, 256, 64), jnp.bfloat16,
                               sharding=NamedSharding(mesh, P()))
    loss = functools.partial(_flash_loss, None, None)

    def lowered():
        jax.clear_caches()        # forget the previous tracer's kernel
        return jax.jit(loss).lower(qkv, qkv, qkv).as_text()

    def from_deeper(n):
        return lowered() if n == 0 else from_deeper(n - 1)

    assert lowered() == from_deeper(3)


@pytest.mark.parametrize("shape", [(4, 12, 1024, 64), (2, 12, 2048, 64),
                                   (2, 8, 1024, 128)])
def test_flash_under_shard_map_compiles_on_2x2(v5e_devices, chip_locations,
                                               shape):
    mesh = Mesh(np.array(v5e_devices).reshape(2, 2), ("x0", "x1"))
    spec = P("x0", "x1")          # batch over x0, heads over x1
    qkv = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                               sharding=NamedSharding(mesh, spec))
    txt = _compile_text(
        jax.grad(functools.partial(_flash_loss, mesh, spec),
                 argnums=(0, 1, 2)), qkv, qkv, qkv)
    assert _kernel_names(txt) == FLASH_NAMES
    assert "all-gather" not in txt    # operands stay where they are


def test_flash_without_shard_map_is_refused_on_2x2(v5e_devices):
    """The failure the wrap exists for: keep it visible."""
    mesh = Mesh(np.array(v5e_devices).reshape(2, 2), ("x0", "x1"))
    qkv = jax.ShapeDtypeStruct(
        (4, 12, 1024, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(("x0", "x1"))))
    with pytest.raises(NotImplementedError, match="shard_map"):
        _compile_text(functools.partial(_flash_loss, None, None),
                      qkv, qkv, qkv)


# ----------------------------------------------------------------------
# the gated delta rule's in-chunk terms (kernels/gated_delta_rule.py)
# ----------------------------------------------------------------------
KDA_NAMES = ["gated_delta_rule_bwd", "gated_delta_rule_fwd",
             "gated_delta_rule_scan_bwd", "gated_delta_rule_scan_fwd"]


@pytest.fixture
def compiled_kda(monkeypatch):
    """The op asks the platform whether to interpret, and the platform
    here is the CPU: answer for the described chip."""
    for module in ("gated_delta_rule", "delta_mix"):
        monkeypatch.setattr(
            f"flexflow_tpu.kernels.{module}.pallas_interpret", lambda: False)


def _kda_loss(mesh, spec, q, k, v, g, beta, chunk=64):
    from flexflow_tpu.ops.recurrent_ops import gated_delta_rule
    with jax.named_scope("ff.forward"), jax.named_scope("kda_2"), \
            jax.named_scope("kda.scan"):
        out, _ = gated_delta_rule(q, k, v, g, beta, chunk, jnp.bfloat16,
                                  mesh=mesh, spec=spec)
    return jnp.sum(out)


def _kda_operands(mesh, spec, b, h, t, d):
    def arr(*shape):
        return jax.ShapeDtypeStruct(
            shape, jnp.float32, sharding=NamedSharding(
                mesh, P(*(tuple(spec) + (None,) * len(shape))[:len(shape)])))
    return [arr(b, h, t, d)] * 4 + [arr(b, h, t)]


# (batch, heads, tokens, head size, chunk): cell 5 of the benchmark, the
# sequence ISSUE 35 asked for, a padded tail over two grid steps, and the
# shortest and a longer chunk the shape rule takes
KDA_SHAPES = [(1, 32, 4096, 128, 64), (1, 32, 8192, 128, 64),
              (2, 4, 64 * 9 + 5, 128, 64), (1, 4, 256, 128, 16),
              (1, 4, 512, 256, 128)]


@pytest.mark.parametrize("b,h,t,d,chunk", KDA_SHAPES)
def test_the_linear_attention_kernels_compile(v5e_devices, compiled_kda,
                                              b, h, t, d, chunk):
    """Forward and backward for a described v5e: no triangular solve
    (XLA's is a custom call), no other custom call than the four
    kernels' (the terms' pair and the scan's) and XLA's own buffers,
    and no ``while`` is left in the recurrence."""
    mesh = Mesh(np.array(v5e_devices[:1]), ("x0",))
    txt = _compile_text(
        jax.grad(functools.partial(_kda_loss, None, None, chunk=chunk),
                 argnums=range(5)), *_kda_operands(mesh, (), b, h, t, d))
    assert _kernel_names(txt) == KDA_NAMES
    # (ConcatBitcast: XLA's own, the decays sliced into fast memory)
    assert set(re.findall(r'custom_call_target="([^"]+)"', txt)) <= {
        "tpu_custom_call", "AllocateBuffer", "ConcatBitcast"}
    assert " while(" not in txt


@pytest.mark.parametrize("spec", [("x0", None), (None, "x0")],
                         ids=["batch", "heads"])
def test_the_linear_attention_kernels_compile_under_a_mesh(
        v5e_devices, compiled_kda, spec):
    """Four chips by batch or by heads: each runs the kernels on its own
    (batch, head) rows under ``shard_map``; GSPMD could not partition
    the Mosaic calls."""
    mesh = Mesh(np.array(v5e_devices), ("x0",))
    txt = _compile_text(
        jax.grad(functools.partial(_kda_loss, mesh, P(*spec)),
                 argnums=range(5)),
        *_kda_operands(mesh, spec, 4, 8, 512, 128))
    assert _kernel_names(txt) == KDA_NAMES


def test_the_linear_attention_kernels_keep_their_scope(
        v5e_devices, compiled_kda, chip_locations):
    """All four calls carry the layer's name and ``kda.scan`` in their
    ``op_name``, the backward's inside the ``transpose(``: the
    benchmark's ``kda_time_share.train`` and
    ``kda_scan_time_share.train`` find them by those parts."""
    mesh = Mesh(np.array(v5e_devices[:1]), ("x0",))
    txt = _compile_text(
        jax.grad(functools.partial(_kda_loss, None, None),
                 argnums=range(5)), *_kda_operands(mesh, (), 1, 4, 512, 128))
    by_name = {n: l for n in KDA_NAMES for l in txt.splitlines()
               if MOSAIC_CALL in l and f"{n}." in l.split(" = ")[0]}
    assert 'jvp(ff.forward)/kda_2/kda.scan/gated_delta_rule_fwd/' \
        'pallas_call"' in by_name["gated_delta_rule_fwd"]
    assert 'transpose(jvp(ff.forward))/kda_2/kda.scan/' \
        'gated_delta_rule_bwd/pallas_call"' in by_name["gated_delta_rule_bwd"]
    assert 'jvp(ff.forward)/kda_2/kda.scan/gated_delta_rule_scan_fwd/' \
        'pallas_call"' in by_name["gated_delta_rule_scan_fwd"]
    assert 'transpose(jvp(ff.forward))/kda_2/kda.scan/' \
        'gated_delta_rule_scan_bwd/pallas_call"' \
        in by_name["gated_delta_rule_scan_bwd"]


def test_a_rematerialised_block_calls_the_forward_kernel_twice_a_layer(
        v5e_devices, compiled_kda, chip_locations):
    """The benchmark's layout at two heads of 128 over 512 positions,
    ``remat = "blocks"``, the train step compiled for a described v5e:
    every linear-attention layer's forward kernels (the terms' and the
    scan's) are called twice (the forward pass and the layer's own
    second run for its backward), the two inside the rematerialised
    blocks (``kda_1``, ``kda_2``) too, where the block's second run
    called them a third time before the block kept the layer's marked
    output; one backward call of each a layer, and no ``while`` under
    ``kda.scan``."""
    import dataclasses

    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel
    from flexflow_tpu.models.nlp import (KimiLinearRankConfig,
                                         build_latent_moe)
    from flexflow_tpu.parallel.machine import MachineSpec
    tiny = KimiLinearRankConfig.tiny()
    mc = dataclasses.replace(
        tiny, hidden_size=128, num_experts=4, num_experts_published=16,
        linear_attn_config=dict(tiny.linear_attn_config, num_heads=2,
                                head_dim=128))
    cfg = FFConfig()
    cfg.batch_size = 1
    cfg.only_data_parallel = True
    cfg.kernel_impls = "attention:xla"
    cfg.remat = "blocks"
    ff = FFModel(cfg)
    out = build_latent_moe(ff, 1, 512, mc)
    # a mesh of one device, as the described chip is
    ff.compile(AdamOptimizer(1e-3), "sparse_categorical_crossentropy", [],
               output_tensor=out,
               machine_spec=MachineSpec.detect(jax.devices()[:1]))
    assert ff.executor._remat[:3] == (12, 6, 2)
    ids = np.zeros((1, 512), np.int32)
    batch = next(iter(ff._combined_loader(
        [ids, ids], np.zeros((1, 512, 1), np.int32), shuffle=False)))
    one = jax.sharding.SingleDeviceSharding(v5e_devices[0])
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=one),
        (ff.params, ff.opt_state, ff.state, jnp.int32(0), batch))
    txt = ff.executor.make_train_step().lower(*shapes).compile().as_text()
    calls = {}
    for line in txt.splitlines():
        if MOSAIC_CALL in line and "gated_delta_rule" in line:
            op_name = line.split('op_name="')[1].split('"')[0]
            (layer,) = {p for p in op_name.split("/")
                        if p.startswith("kda_")}
            (kernel,) = re.findall(r"gated_delta_rule_(\w+)/", op_name)
            calls[layer, kernel] = calls.get((layer, kernel), 0) + 1
    assert calls == {(f"kda_{i}", k): n for i in (0, 1, 2, 4)
                     for k, n in (("fwd", 2), ("bwd", 1), ("scan_fwd", 2),
                                  ("scan_bwd", 1))}
    assert not [l for l in txt.splitlines()
                if " while(" in l and "kda.scan" in l]
    # q, k and v through ``kernels/delta_mix.py`` (PR 63), every call
    # under ``kda.mix`` and none under ``kda.scan``: a branch's forward
    # runs with the step's forward and the layer's second run; its third
    # run (the ``kda.branch`` checkpoint's) needs the product alone, so
    # the forward call is dropped there: 2 x 3 forward calls a layer and
    # one backward a branch (3 x 3 and 3 if it were kept)
    mixes = {}
    for line in txt.splitlines():
        if MOSAIC_CALL in line and "delta_mix" in line:
            op_name = line.split('op_name="')[1].split('"')[0]
            assert "kda.mix" in op_name and "kda.scan" not in op_name
            (layer,) = {p for p in op_name.split("/")
                        if p.startswith("kda_")}
            (kernel,) = re.findall(r"delta_mix_(\w+)/", op_name)
            mixes[layer, kernel] = mixes.get((layer, kernel), 0) + 1
    found = {n for (_, k), n in mixes.items() if k == "fwd"}
    assert found in ({6}, {9}), mixes
    print(f"delta_mix_fwd calls a layer: {found}")
    assert mixes == {(f"kda_{i}", k): n for i in (0, 1, 2, 4)
                     for k, n in (("fwd", 6), ("bwd", 3))}


# the residual streams' mixes (kernels/hyper_connection.py)
# ----------------------------------------------------------------------
MHC_NAMES = ["hyper_connection_post_bwd", "hyper_connection_post_fwd",
             "hyper_connection_pre_bwd", "hyper_connection_pre_fwd"]


@pytest.fixture
def compiled_mhc(monkeypatch):
    monkeypatch.setattr(
        "flexflow_tpu.kernels.hyper_connection.pallas_interpret",
        lambda: False)


def _mhc_loss(mesh, spec, x, phi_t, gate, y, maps):
    """Both nodes' calls as a sub-layer has them: ``post`` takes the
    streams the ``pre`` call hands on."""
    from flexflow_tpu.kernels import hyper_connection as hck
    with jax.named_scope("ff.forward"):
        with jax.named_scope("attn_res_2_pre"), jax.named_scope("mhc.mix"):
            u, stats, xs = hck.read_streams(x, phi_t, gate, 1e-6,
                                            mesh=mesh, spec=spec)
        with jax.named_scope("attn_res_2"), jax.named_scope("mhc.mix"):
            out = hck.write_streams(xs, y + u, maps, mesh=mesh, spec=spec)
    return jnp.sum(out ** 2) + jnp.sum(stats ** 2)


def _mhc_operands(mesh, spec, b, s, n, c):
    from flexflow_tpu.kernels.hyper_connection import stats_width

    def arr(*shape, sharded=True):
        axes = (tuple(spec) + (None,) * len(shape))[:len(shape)]
        return jax.ShapeDtypeStruct(
            shape, jnp.float32, sharding=NamedSharding(
                mesh, P(*axes) if sharded else P()))
    kp = stats_width(n)
    return [arr(b, s, n, c), arr(kp, n * c, sharded=False),
            arr(2, kp, sharded=False), arr(b, s, c), arr(b, s, n + n * n)]


# (batch, positions, streams, channels): cell 6 of the benchmark, a
# padded tail (1,000 tokens in tiles of 256 and 128), two streams of one
# lane tile, and channels so wide that the backward tiles step down to 32
MHC_SHAPES = [(1, 4096, 4, 3584), (2, 500, 4, 3584), (1, 64, 2, 128),
              (1, 512, 4, 8192)]


@pytest.mark.parametrize("b,s,n,c", MHC_SHAPES)
def test_the_hyper_connection_kernels_compile(v5e_devices, compiled_mhc,
                                              b, s, n, c):
    """Forward and backward for a described v5e, at the tiles the shapes
    give: Mosaic takes the working set ``vmem_bytes`` counts inside the
    budget, the three products at ``HIGHEST`` and ``dphi``'s block
    summed over a sequential grid."""
    mesh = Mesh(np.array(v5e_devices[:1]), ("x0",))
    txt = _compile_text(
        jax.grad(functools.partial(_mhc_loss, None, None),
                 argnums=range(5)), *_mhc_operands(mesh, (), b, s, n, c))
    assert _kernel_names(txt) == MHC_NAMES


@pytest.mark.parametrize("spec", [("x0", None), (None, "x0")],
                         ids=["batch", "sequence"])
def test_the_hyper_connection_kernels_compile_under_a_mesh(
        v5e_devices, compiled_mhc, spec):
    """Four chips by batch or by sequence: each runs the kernels on its
    own tokens under ``shard_map``, and ``dphi`` is all-reduced."""
    mesh = Mesh(np.array(v5e_devices), ("x0",))
    txt = _compile_text(
        jax.grad(functools.partial(_mhc_loss, mesh, P(*spec)),
                 argnums=range(5)),
        *_mhc_operands(mesh, spec, 4, 512, 4, 512))
    assert _kernel_names(txt) == MHC_NAMES
    assert "all-reduce" in txt


def test_the_hyper_connection_kernels_keep_their_scope(
        v5e_devices, compiled_mhc, chip_locations):
    """All four calls carry their node's name and ``mhc.mix`` in their
    ``op_name``, the backward's inside the ``transpose(``: the
    benchmark's ``xing_mhc_time_share.train`` finds them by the node's
    name."""
    mesh = Mesh(np.array(v5e_devices[:1]), ("x0",))
    txt = _compile_text(
        jax.grad(functools.partial(_mhc_loss, None, None),
                 argnums=range(5)),
        *_mhc_operands(mesh, (), 1, 512, 4, 512))
    by_name = {n: l for n in MHC_NAMES for l in txt.splitlines()
               if MOSAIC_CALL in l and f"{n}." in l.split(" = ")[0]}
    for name, node in (("pre", "attn_res_2_pre"), ("post", "attn_res_2")):
        assert f'jvp(ff.forward)/{node}/mhc.mix/hyper_connection_' \
            f'{name}_fwd/pallas_call"' in by_name[
                f"hyper_connection_{name}_fwd"]
        assert f'transpose(jvp(ff.forward))/{node}/mhc.mix/' \
            f'hyper_connection_{name}_bwd/pallas_call"' in by_name[
                f"hyper_connection_{name}_bwd"]


# the routed experts' way back to tokens (kernels/moe_token_sum.py)
# ----------------------------------------------------------------------
@pytest.fixture
def compiled_token_sum(monkeypatch):
    monkeypatch.setattr(
        "flexflow_tpu.kernels.moe_token_sum.pallas_interpret",
        lambda: False)


def _experts_loss(params, x, w):
    from flexflow_tpu import FFConfig
    from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
    from flexflow_tpu.ops.registry import EmitCtx
    ctx = EmitCtx(training=True, config=FFConfig())
    with jax.named_scope("ff.forward"), jax.named_scope("experts_1"):
        (y,) = RoutedExpertsOp().emit(params, [x], w, ctx, "experts_1")
    return jnp.sum(y ** 2)


def _experts_operands(device, tokens, hidden, params):
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
    one = jax.sharding.SingleDeviceSharding(device)
    specs = RoutedExpertsOp().weights(params, [(1, tokens, hidden)],
                                      [DataType.DT_FLOAT])
    return (jax.ShapeDtypeStruct((1, tokens, hidden), jnp.float32,
                                 sharding=one),
            {s.name: jax.ShapeDtypeStruct(s.shape, jnp.float32,
                                          sharding=one) for s in specs})


# (tokens, hidden, published, held, top_k, expert width, rows_factor):
# cells 3 and 4 of the benchmark (4,096 and 8,192 rows of 32,768, tiles
# of 512 tokens), and a layer whose every row is live (no loop; the
# shapes leave it to the plain path)
EXPERT_SHAPES = [(4096, 2048, 256, 16, 8, 768, 2, 4),
                 (8192, 2048, 64, 8, 4, 1536, 2, 4),
                 (512, 256, 8, 8, 2, 128, 2, 0)]


@pytest.mark.parametrize("tokens,hidden,n,held,k,f,factor,calls",
                         EXPERT_SHAPES)
def test_the_experts_layer_compiles_through_the_token_sum_kernel(
        v5e_devices, compiled_token_sum, chip_locations, tokens, hidden, n,
        held, k, f, factor, calls):
    """Forward and backward of one routed-experts layer for a described
    v5e: the first chunk calls the kernel once forward (``_combine``)
    and once backward (the row gather's transpose), and so does the
    further chunks' loop (of the backward's two loops the activations'
    alone wants the transpose, and neither the sum it rematerialises);
    every call keeps the layer's scope, which is how the benchmark's
    ``moe_time_share.train`` finds it."""
    from flexflow_tpu.kernels import moe_token_sum as mts
    from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
    params = dict(num_experts=n, top_k=k, expert_dim=f, shared_dim=0,
                  experts_held=held, first_held=0, rows_factor=factor)
    budget = RoutedExpertsOp.rows_multiplied(tokens, params)
    assert mts.takes_kernel(tokens, hidden, k, budget, held,
                            jnp.bfloat16) == bool(calls)
    txt = _compile_text(
        jax.grad(functools.partial(_experts_loss, params), argnums=(0, 1)),
        *_experts_operands(v5e_devices[0], tokens, hidden, params))
    mine = [l for l in txt.splitlines()
            if MOSAIC_CALL in l and "moe_token_sum" in l.split(" = ")[0]]
    assert len(mine) == calls
    assert all('/experts_1/' in l.split('op_name="')[1].split('"')[0]
               for l in mine)


# ----------------------------------------------------------------------
# the flash kernels' mask operand and the head-mean kernel (PR 49)
# ----------------------------------------------------------------------
def test_the_masked_kernels_compile_at_cell_7s_shapes(v5e_devices,
                                                       chip_locations):
    """``keye_vl2_30b_a3b.train.1chip``: one sequence of 8,192, 32 heads
    of 128, bf16, causal, an int8 mask a batch row: the three kernels at
    the blocks derived with the mask's tile counted, then the heads'
    mean probability, which writes ONE (b, s, s) float32 array."""
    import importlib
    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
    b, h, s, d = 1, 32, 8192, 128
    one = jax.sharding.SingleDeviceSharding(v5e_devices[0])
    qkv = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16, sharding=one)
    mask = jax.ShapeDtypeStruct((b, s, s), jnp.int8, sharding=one)
    lse = jax.ShapeDtypeStruct((b, h, s), jnp.float32, sharding=one)
    assert fa.fwd_tiles(s, s, d, jnp.bfloat16, False, None, True) \
        != fa.fwd_tiles(s, s, d, jnp.bfloat16, False)

    def loss(q, k, v, m):
        o = flash_attention(q, k, v, causal=True, mask=m, interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    txt = _compile_text(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv,
                        mask)
    assert _kernel_names(txt) == FLASH_NAMES
    # every call reads an int8 tile of the one mask (dkv its transpose)
    calls = [l for l in txt.splitlines() if MOSAIC_CALL in l]
    assert all(f"s8[{b},{s},{s}]" in l for l in calls)
    # and nothing with a head axis over (queries, keys) exists
    assert not re.search(rf"\[({h}|{b},{h}|{b * h}),{s},{s}\]", txt)

    txt = _compile_text(
        lambda q, k, l, m: fa.flash_attention_head_mean(
            q, k, l, m, causal=True, interpret=False), qkv, qkv, lse, mask)
    assert _kernel_names(txt) == ["flash_attention_head_mean"]
    assert f"f32[{b},{s},{s}]" in txt
    assert not re.search(rf"\[({h}|{b},{h}|{b * h}),{s},{s}\]", txt)


def _without_debug_info(lowered: str) -> str:
    """A lowered module's text with each Mosaic call's serialized body
    replaced by the body's assembly WITHOUT locations (which carry this
    checkout's path and the kernels' line numbers)."""
    import base64
    import json

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def body(match):
        cfg = match.group(1).replace("\\22", '"').replace("\\5C", "\\")
        ctx = jax_mlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(
                json.loads(cfg)["custom_call_config"]["body"]))
            return module.operation.get_asm(enable_debug_info=False)

    return re.sub(r'backend_config = "(.*?)"(?=[,}\s])', body, lowered,
                  flags=re.S)


# sha256 of the three calls' lowered text (forward + backward of a sum,
# bf16, for a described v5e, locations stripped) at the shapes cells 1
# to 6 call the kernels with, taken from the parent of PR 49 (``git
# archive 385e301``) by these same lines: with ``mask=None`` the kernels
# lower to what they were, operand for operand and tile for tile. A PR
# that means to change the unmasked kernels replaces the hashes.
UNMASKED_SHA256 = {
    "cell1_bert_large":
        "d79e1659cd00bba44104e458da1d69e14e2dd4225a4485d0cbb9cae678031d94",
    "cell2_gpt2_124m":
        "deceda128c3c6cf885ec0c971352e2e78e918a35c6d14ae8115b789b7d267af9",
    "cell3_joyai_cell5_kimi":
        "d2f353e9636ffe5607b90e41665c8fefbbc4d8805e19ed22587a527aa995bae0",
    "cell4_lfm2":
        "e4d977a36d40ad0554e6e0c9a674bd084d4a53970153ac384880b090a95eea3a",
}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_without_a_mask_the_kernels_lower_to_the_parents_text(
        v5e_devices, cell):
    import hashlib
    b, h, s, d, dv, causal, rate = CELL_SHAPES[cell]
    one = jax.sharding.SingleDeviceSharding(v5e_devices[0])
    qk = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((b, h, s, dv), jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=causal, interpret=False,
                            dropout_rate=rate,
                            dropout_seed=jnp.int32(3) if rate else None)
        return jnp.sum(o.astype(jnp.float32))

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qk, qk, v).as_text()
    assert lowered.count("tpu_custom_call") == 3
    text = _without_debug_info(lowered)
    assert "stable_mosaic" in text and "loc(" not in text
    assert hashlib.sha256(text.encode()).hexdigest() \
        == UNMASKED_SHA256[cell]


# ----------------------------------------------------------------------
# q/k norm, rotary embedding, cast and the turn to heads-first (PR 50)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("heads,repeat", [(32, 1), (4, 8)], ids=["q", "k"])
def test_the_norm_rope_kernels_compile_at_cell_7s_shapes(
        v5e_devices, chip_locations, heads, repeat):
    """``keye_vl2_30b_a3b.train.1chip``: 8,192 positions, heads of 128,
    bf16, a row's own positions; q's 32 heads and k's 4, whose backward
    reads the 32 query heads' cotangents. Forward and backward at the
    derived tiles; the output is the flash kernels' operand and ``dx``
    the projection's own bytes."""
    from flexflow_tpu.kernels import qk_norm_rope as nrk
    b, s, d = 1, 8192, 128
    one = jax.sharding.SingleDeviceSharding(v5e_devices[0])
    assert nrk.takes_kernel(s, heads, d, repeat, jnp.bfloat16)

    def both(x, scale, pos, ct):
        tables = nrk.rope_tables(pos, d, 1e7)
        y, pull = jax.vjp(lambda x, scale: nrk.qk_norm_rope(
            x, scale, tables, eps=1e-6, dtype=jnp.bfloat16, repeat=repeat,
            interpret=False), x, scale)
        return y, pull(ct)

    txt = _compile_text(
        both, jax.ShapeDtypeStruct((b, s, heads, d), jnp.float32,
                                   sharding=one),
        jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one),
        jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((b, heads * repeat, s, d), jnp.bfloat16,
                             sharding=one))
    assert _kernel_names(txt) == ["qk_norm_rope_bwd", "qk_norm_rope_fwd"]
    calls = [l for l in txt.splitlines() if MOSAIC_CALL in l]
    # in: the projection's (b, s, heads * d) float32; out: heads-first
    assert all(f"f32[{b},{s},{heads * d}]" in l for l in calls)
    assert any(f"bf16[{b},{heads},{s},{d}]" in l.split(" custom-call(")[0]
               for l in calls)


# one attention layer, forward and backward of a sum through
# ``MultiHeadAttentionOp.emit`` with the flash kernels forced, lowered
# for a described v5e: (batch, positions, hidden, heads, key/value
# heads, head size, the layer's further parameters)
LAYER_SHAPES = {
    "cell2_gpt2_124m": (12, 1024, 768, 12, 12, 64, {"bias": True}),
    "cell4_lfm2": (1, 8192, 2048, 32, 8, 64,
                   {"bias": False, "rope": True, "rope_theta": 1e6,
                    "qk_norm": True, "qk_norm_eps": 1e-5}),
    "cell7_keye_without_indexer": (1, 8192, 2048, 32, 4, 128, {
        "bias": False, "rope": True, "rope_theta": 1e7, "qk_norm": True,
        "qk_norm_eps": 1e-6}),
    "cell7_keye": (1, 8192, 2048, 32, 4, 128, {
        "bias": False, "rope": True, "rope_theta": 1e7, "qk_norm": True,
        "qk_norm_eps": 1e-6, "indexer_heads": 16, "indexer_head_dim": 64,
        "indexer_topk": 2048, "indexer_q_chunk": 512}),
    "cell8_trinity_window": (1, 8192, 2048, 32, 4, 128, {
        "bias": False, "rope": True, "rope_theta": 1e4, "qk_norm": True,
        "qk_norm_eps": 1e-5, "output_gate": True, "sliding_window": 2048}),
    "cell8_trinity_full": (1, 8192, 2048, 32, 4, 128, {
        "bias": False, "qk_norm": True, "qk_norm_eps": 1e-5,
        "output_gate": True}),
}

# sha256 of the layer's lowered text (locations stripped) taken from the
# parent of PR 50 (``git archive ad1f078``) by these same lines: where
# ``_takes_norm_rope_kernel`` says no, the layer lowers to what it was,
# equation for equation. A PR that means to change those layers'
# emission replaces the hashes. Cell 4's is PR 52's: since then the flash
# kernels read its 8 key/value heads in place and the layer repeats
# nothing, so it no longer matches PR 51's commit ``f028476`` (whose
# text hashed b202c6f9...10f7, as ``ad1f078``'s did).
LAYER_SHA256 = {
    "cell2_gpt2_124m":
        "28bb6501df1dd541cd0152f0d29fac5bc6b5de4ff9505c5b1e3ebe019ff4f550",
    "cell4_lfm2":
        "788c1a9db0ae3fb44e640ed56a913f567ea7f4bb4943d50d96c5322a4fb3ab73",
}


def _lowered_layer(device, monkeypatch, cell):
    from flexflow_tpu import FFConfig
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.ops.nn_ops import MultiHeadAttentionOp
    from flexflow_tpu.ops.registry import EmitCtx
    import importlib
    monkeypatch.setattr(
        importlib.import_module("flexflow_tpu.kernels.flash_attention"),
        "pallas_interpret", lambda: False)
    b, s, e, h, kv, d, more = LAYER_SHAPES[cell]
    params = dict({"embed_dim": e, "num_heads": h, "num_kv_heads": kv,
                   "kdim": h * d, "vdim": h * d, "causal": True}, **more)
    op = MultiHeadAttentionOp()
    one = jax.sharding.SingleDeviceSharding(device)
    x = jax.ShapeDtypeStruct((b, s, e), jnp.float32, sharding=one)
    pos = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one)
    weights = {w.name: jax.ShapeDtypeStruct(w.shape, jnp.float32,
                                            sharding=one)
               for w in op.weights(params, [(b, s, e)] * 3,
                                   [DataType.DT_FLOAT] * 3)}
    counted = {}

    def loss(x, weights, pos):
        ctx = EmitCtx(training=True, config=FFConfig())
        ctx.kernel_impls = {"attention": "flash"}
        (y,) = op.emit(params, [x, x, x, pos], weights, ctx, "attn")
        counted.update(ctx.counters)
        return jnp.sum(y.astype(jnp.float32))

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        x, weights, pos).as_text()
    return lowered, counted


@pytest.mark.parametrize("cell", sorted(LAYER_SHA256))
def test_where_the_predicate_says_no_the_layer_lowers_to_the_parents_text(
        v5e_devices, monkeypatch, cell):
    """Cell 2 (no rotary embedding, no q/k norm) and cell 4 (both, on
    heads of 64): the three flash calls and nothing of PR 50's. Cell 2's
    equal head counts count no grouped layer either."""
    import hashlib
    lowered, counted = _lowered_layer(v5e_devices[0], monkeypatch, cell)
    assert lowered.count("tpu_custom_call") == 3
    assert "qk_norm_rope" not in lowered
    assert set(counted) == ({"attn.grouped_kv_layers"}
                            if cell == "cell4_lfm2" else set())
    text = _without_debug_info(lowered)
    assert hashlib.sha256(text.encode()).hexdigest() == LAYER_SHA256[cell]


def test_where_it_says_yes_the_layer_calls_the_kernels(v5e_devices,
                                                       monkeypatch):
    """Cell 7's layer shape without its indexer (``emit``'s own flash
    call): q and k forward, the three flash kernels, q and k backward,
    and no rotate-half or repeat of a float32 array around them."""
    monkeypatch.setattr("flexflow_tpu.kernels.qk_norm_rope."
                        "pallas_interpret", lambda: False)
    lowered, counted = _lowered_layer(v5e_devices[0], monkeypatch,
                                      "cell7_keye_without_indexer")
    assert lowered.count("tpu_custom_call") == 7
    assert lowered.count("qk_norm_rope_fwd") >= 2 \
        and lowered.count("qk_norm_rope_bwd") >= 2
    assert set(counted) == {"attn.norm_rope_kernel_layers",
                            "attn.grouped_kv_layers"}


# ----------------------------------------------------------------------
# the window inside the flash kernels (PR 51)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("window", [2048, 1000])
def test_the_windowed_kernels_compile_at_cell_8s_shapes(v5e_devices,
                                                        chip_locations,
                                                        window):
    """``trinity_mini.train.1chip``: 32 heads of 128 over 8,192
    positions, bf16, causal, a window of 2,048 (and one that is no
    multiple of a tile or of the forward's piece): the three kernels
    with the band's index maps and liveness tests compile under Mosaic,
    and the call has no operand beyond the causal call's (a window is
    never a mask)."""
    b, h, s, d = 1, 32, 8192, 128
    one = jax.sharding.SingleDeviceSharding(v5e_devices[0])
    qkv = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16, sharding=one)

    def loss(q, k, v, window):
        o = flash_attention(q, k, v, causal=True, interpret=False,
                            window=window)
        return jnp.sum(o.astype(jnp.float32))

    def text(window):
        return _compile_text(jax.grad(functools.partial(
            loss, window=window), argnums=(0, 1, 2)), qkv, qkv, qkv)

    banded, causal = text(window), text(0)
    assert _kernel_names(banded) == FLASH_NAMES

    def operands(txt):
        return sorted(l.split(" custom-call(")[1].count("%")
                      for l in txt.splitlines() if MOSAIC_CALL in l)

    assert operands(banded) == operands(causal)
    assert "s8[" not in banded


# ----------------------------------------------------------------------
# grouped-query attention's key/value heads read in place (PR 52)
# ----------------------------------------------------------------------
# one sequence of 8,192, 32 query heads, bf16, causal: (key/value heads,
# head size, window, masked)
GROUPED_CELLS = {
    "cell4_lfm2": (8, 64, 0, False),
    "cell7_keye_masked": (4, 128, 0, True),
    "cell8_trinity_window": (4, 128, 2048, False),
    "cell8_trinity_full": (4, 128, 0, False),
}


@pytest.mark.parametrize("cell", sorted(GROUPED_CELLS))
def test_the_grouped_kernels_compile_at_the_cells_shapes(v5e_devices,
                                                         chip_locations,
                                                         cell):
    """The three calls with k and v at the model's own head count: every
    call reads them as ``bf16[kvh,8192,d]``, ``bwd_dkv`` writes ``dk``
    and ``dv`` in that shape, and nothing of 32 heads stands for them
    (q, ``do``, the output and ``dq`` are all there is at 32)."""
    import importlib
    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
    kvh, d, window, masked = GROUPED_CELLS[cell]
    b, h, s = 1, 32, 8192
    one = jax.sharding.SingleDeviceSharding(v5e_devices[0])
    q = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((b, kvh, s, d), jnp.bfloat16, sharding=one)
    mask = [jax.ShapeDtypeStruct((b, s, s), jnp.int8, sharding=one)] \
        if masked else []

    def loss(q, k, v, *m):
        o = flash_attention(q, k, v, causal=True, interpret=False,
                            window=window, mask=m[0] if m else None)
        return jnp.sum(o.astype(jnp.float32))

    txt = _compile_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv, *mask)
    assert _kernel_names(txt) == FLASH_NAMES
    calls = {re.sub(r"^(transpose_)?(jvp_)?|_+$", "", l.split(" = ")[0]
                    .split("%")[-1].rsplit(".", 1)[0]): l
             for l in txt.splitlines() if MOSAIC_CALL in l}
    narrow, wide = f"bf16[{b * kvh},{s},{d}]", f"bf16[{b * h},{s},{d}]"
    for name, line in calls.items():
        result, operands = line.split(" custom-call(")
        assert operands.count(narrow) == 2, name            # k and v
        assert operands.count(wide) == (1 if name.endswith("fwd") else 2)
    dkv = calls["flash_attention_bwd_dkv"].split(" custom-call(")[0]
    assert dkv.count(narrow) == 2 and wide not in dkv       # dk and dv
    if masked:
        lse = jax.ShapeDtypeStruct((b, h, s), jnp.float32, sharding=one)
        txt = _compile_text(
            lambda q, k, l, m: fa.flash_attention_head_mean(
                q, k, l, m, causal=True, interpret=False), q, kv, lse, *mask)
        (line,) = [l for l in txt.splitlines() if MOSAIC_CALL in l]
        assert _kernel_names(txt) == ["flash_attention_head_mean"]
        assert line.split(" custom-call(")[1].count(narrow) == 1


@pytest.mark.parametrize("cell,calls,counted", [
    ("cell2_gpt2_124m", 3, set()),
    ("cell4_lfm2", 3, {"attn.grouped_kv_layers"}),
    ("cell7_keye", 9, {"attn.grouped_kv_layers",
                       "attn.norm_rope_kernel_layers"}),
    ("cell8_trinity_window", 7, {"attn.grouped_kv_layers",
                                 "attn.norm_rope_kernel_layers"}),
    ("cell8_trinity_full", 3, {"attn.grouped_kv_layers"})])
def test_a_layer_whose_kernels_read_grouped_heads_counts_itself(
        v5e_devices, monkeypatch, cell, calls, counted):
    """``attn.grouped_kv_layers`` is counted by a layer of cells 4, 7
    and 8 (the indexer's path and the norm-and-rotary kernel's
    included) and not by cell 2's equal head counts, and nothing is
    repeated to 32 heads on the way, in float32 or heads-first."""
    monkeypatch.setattr("flexflow_tpu.kernels.qk_norm_rope."
                        "pallas_interpret", lambda: False)
    lowered, got = _lowered_layer(v5e_devices[0], monkeypatch, cell)
    assert lowered.count("tpu_custom_call") == calls
    assert {k for k in got if not k.startswith(
        ("dsa.", "attn.window_pairs", "attn.causal_pairs", "attn.gate_"))} \
        == counted
    # ``jnp.repeat`` on the heads' axis goes through (.., kvh, group, ..)
    b, s, _, h, kv, d, _ = LAYER_SHAPES[cell]
    assert f"tensor<{b}x{s}x{kv}x{h // kv}x{d}x" not in lowered
    assert f"tensor<{b}x{kv}x{h // kv}x{s}x{d}x" not in lowered


# ----------------------------------------------------------------------
# the index scores and their pull-back (PR 54)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("rows,keys,causal", [
    (8192, 8192, True), (4096, 4096, True), (512, 8192, False)],
    ids=["cell7", "validation", "one_chunk"])
def test_the_index_score_kernels_compile_at_cell_7s_shapes(
        v5e_devices, chip_locations, rows, keys, causal):
    """``keye_vl2_30b_a3b.train.1chip``: 16 heads of 64 against one key
    head, bf16 operands, the whole sequence in one causal call at the
    derived 512 x 512 tiles (and a chunk of it against every key): the
    scores again, then ``dqi``, ``dki`` and ``dwi`` from their
    cotangent. Nothing with a head axis over (queries, keys) exists, and
    each call tells XLA's scheduler what it costs."""
    from flexflow_tpu.kernels import index_scores as isk
    b, j, c = 1, 16, 64
    assert isk.takes_kernel(rows, keys, j, c, jnp.bfloat16)
    one = jax.sharding.SingleDeviceSharding(v5e_devices[0])

    def both(qi, ki, wi, d):
        return (isk.index_scores_fwd(qi, ki, wi, jnp.bfloat16,
                                     causal=causal, interpret=False),
                isk.index_scores_bwd(qi, ki, wi, d, jnp.bfloat16,
                                     causal=causal, interpret=False))

    lowered = jax.jit(both).lower(*(
        jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)
        for shape in ((b, rows, j, c), (b, keys, c), (b, rows, j),
                      (b, rows, keys))))
    txt = lowered.compile().as_text()
    assert _kernel_names(txt) == ["index_scores_bwd", "index_scores_fwd"]
    assert not re.search(rf"\[({j}|{b},{j}),{rows},{keys}\]", txt)
    assert lowered.as_text().count("cost_estimate") == 2


# ----------------------------------------------------------------------
# the state-space / attention hybrid (PR 55)
# ----------------------------------------------------------------------
def test_the_grouped_kernels_compile_at_cell_9s_shapes_with_its_scale(
        v5e_devices, chip_locations):
    """``granite_4_0_h_micro.train.1chip``: 32 query heads on 8 key/value
    heads of 64 over 4,096 positions, bf16, causal, the scores times
    1/64: the three kernels compile, K and V read at their own 8 heads,
    and the scale is a constant of the kernels (no operand more than the
    default's)."""
    b, h, kvh, s, d = 1, 32, 8, 4096, 64
    one = jax.sharding.SingleDeviceSharding(v5e_devices[0])
    q = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((b, kvh, s, d), jnp.bfloat16, sharding=one)

    def text(**scale):
        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=True, interpret=False,
                                **scale)
            return jnp.sum(o.astype(jnp.float32))
        return _compile_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)

    scaled, default = text(sm_scale=0.015625), text()
    assert _kernel_names(scaled) == FLASH_NAMES

    def operands(txt):
        return sorted(l.split(" custom-call(")[1].count("%")
                      for l in txt.splitlines() if MOSAIC_CALL in l)

    assert operands(scaled) == operands(default)
    narrow = f"bf16[{b * kvh},{s},{d}]"
    assert all(l.split(" custom-call(")[1].count(narrow) == 2
               for l in scaled.splitlines() if MOSAIC_CALL in l)


def test_the_state_space_mixer_compiles_at_the_published_width(
        v5e_devices, monkeypatch):
    """One mixer's forward and backward at 2048 -> 64 heads of 64 x 128
    over 4,096 positions in 16 chunks of 256, bf16 operands, compiled
    for a described v5e: the recurrence is two Mosaic calls (the forward
    in the layer's second run, the backward; this loss reads no value of
    the first run), both under ``ssm.scan``, each with a cost estimate;
    no ``(256, 256)`` array a head is in the compiled text, forward or
    backward (``L`` for all chunks was 256 MiB in float32), no ``while``
    carries the chunk states and nothing the size of ``x`` is copied or
    turned on its way into or out of the calls; the layer's temporaries
    are the projection and a few arrays the size of ``x``."""
    from flexflow_tpu import FFConfig
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.kernels import state_space
    from flexflow_tpu.ops.recurrent_ops import StateSpaceMixerOp
    from flexflow_tpu.ops.registry import EmitCtx
    monkeypatch.setattr(state_space, "pallas_interpret", lambda: False)
    params = {"num_heads": 64, "head_dim": 64, "state": 128, "taps": 4,
              "chunk": 256, "eps": 1e-5}
    op = StateSpaceMixerOp()
    one = jax.sharding.SingleDeviceSharding(v5e_devices[0])
    x = jax.ShapeDtypeStruct((1, 4096, 2048), jnp.float32, sharding=one)
    w = {s.name: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=one)
         for s in op.weights(params, [(1, 4096, 2048)],
                             [DataType.DT_FLOAT])}

    def loss(x, w):
        (y,) = op.emit(params, [x], w,
                       EmitCtx(training=True, config=FFConfig()), "mamba_0")
        return jnp.sum(y)

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w)
    assert lowered.as_text().count("cost_estimate") == 2
    compiled = lowered.compile()
    txt = compiled.as_text()
    calls = [l for l in txt.splitlines() if MOSAIC_CALL in l]
    assert _kernel_names(txt) == ["state_space_bwd", "state_space_fwd"]
    assert all("ssm.scan" in l for l in calls)
    assert "remat.ssm.chunk" not in txt and "remat.ssm.layer" in txt \
        and " while(" not in txt
    # a (chunk, chunk) matrix a head: more than the 16 chunks' one C B^T
    square = re.findall(r"\b(?:f32|bf16)\[([0-9,]*),256,256\]", txt)
    assert not [s for s in square
                if np.prod([int(v) for v in s.split(",")]) > 16]
    # x lies tokens last as the projection writes it, and the calls read
    # it so: no copy or transpose of 4,096 x 4,096 float32
    assert not re.search(
        r"= f32\[1,4096,4096\]\S* (copy|transpose)\(", txt)
    # the compile reads 0.479 GiB (the plain path's was held under 3)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6 * 2 ** 30


@pytest.mark.parametrize("mdt", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_the_state_space_kernels_compile_under_a_default_of_highest(
        v5e_devices, mdt):
    """Both kernels alone at cell 9's shape with
    ``jax_default_matmul_precision`` at ``highest`` around the call, as
    a validation that compares against float32 sets it: the kernels name
    their products' precision themselves (Mosaic refused a bf16 operand
    at ``highest``, ``Bad lhs type``, when they left it to the default)."""
    from flexflow_tpu.kernels import state_space
    b, h, p, t, n, c = 1, 64, 64, 4096, 128, 256
    one = jax.sharding.SingleDeviceSharding(v5e_devices[0])
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)
            for s in ((b, h, p, t), (b, h, t), (b, h, t), (b, t, n),
                      (b, t, n))]

    def loss(*a):
        y, _ = state_space.scan_chunks(*a, c, mdt, interpret=False)
        return jnp.sum(y * y)

    with jax.default_matmul_precision("highest"):
        txt = _compile_text(jax.grad(loss, argnums=range(5)), *args)
    assert _kernel_names(txt) == ["state_space_bwd", "state_space_fwd"]


def test_the_grouped_kernels_compile_at_cell_10s_shapes(v5e_devices,
                                                        chip_locations):
    """``qwen3_next_80b_a3b.train.1chip``: 16 query heads on 2 key/value
    heads of 256 over 8,192 positions, bf16, causal (an 8-fold group at
    the largest head size a cell runs): the three kernels compile and
    read K and V at their own 2 heads."""
    b, h, kvh, s, d = 1, 16, 2, 8192, 256
    one = jax.sharding.SingleDeviceSharding(v5e_devices[0])
    q = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((b, kvh, s, d), jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    txt = _compile_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert _kernel_names(txt) == FLASH_NAMES
    narrow = f"bf16[{b * kvh},{s},{d}]"
    assert all(l.split(" custom-call(")[1].count(narrow) == 2
               for l in txt.splitlines() if MOSAIC_CALL in l)


def _turned(txt, size):
    """The float32 ``copy`` / ``transpose`` instructions of a compiled
    layer whose result has ``size`` entries or more: an operand of the
    delta rule's kernels turned or copied on its way."""
    return [l for l in txt.splitlines()
            if re.search(r"= f32\[[0-9,]*\]\S* (copy|transpose)\(", l)
            and np.prod([int(n) for n in re.search(
                r"= f32\[([0-9,]*)\]", l).group(1).split(",")]) >= size]


def test_the_head_decay_delta_rule_compiles_at_the_published_width(
        v5e_devices, compiled_kda):
    """One linear layer's forward and backward at 2048 -> 16 q/k heads
    under 32 value heads of 128 over 8,192 positions in 128 chunks of
    64, bf16 operands, compiled for a described v5e: the chunks' terms by
    the head form of the kernels and the state by the scan kernels,
    three Mosaic calls of each (the layer's run, its recomputation and
    the backward) under ``gdn.scan``, q and k read at their own 16
    heads, no ``while`` over the chunk states; no
    triangular solve, no ``remat.gdn.terms``, no float32 ``(64, 64)``
    matrix a head-chunk (64 MiB a layer) and no ``(64, 64, 128)`` tensor
    of channel differences is in the text, and the layer's temporaries
    stay under 3 GiB."""
    from flexflow_tpu import FFConfig
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.ops.recurrent_ops import GatedDeltaRuleOp
    from flexflow_tpu.ops.registry import EmitCtx
    params = {"num_heads": 32, "num_key_heads": 16, "head_dim": 128,
              "taps": 4, "eps": 1e-6, "decay": "head"}
    op = GatedDeltaRuleOp()
    one = jax.sharding.SingleDeviceSharding(v5e_devices[0])
    x = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.float32, sharding=one)
    w = {s.name: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=one)
         for s in op.weights(params, [(1, 8192, 2048)],
                             [DataType.DT_FLOAT])}

    def loss(x, w):
        (y,) = op.emit(params, [x], w,
                       EmitCtx(training=True, config=FFConfig()),
                       "linear_attn_0")
        return jnp.sum(y * y)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))
                       ).lower(x, w).compile()
    txt = compiled.as_text()
    calls = [l for l in txt.splitlines() if MOSAIC_CALL in l]
    assert _kernel_names(txt) == ["delta_mix_bwd"] * 3 \
        + ["delta_mix_fwd"] * 6 + ["gated_delta_rule_head_bwd"] \
        + ["gated_delta_rule_head_fwd"] * 2 \
        + ["gated_delta_rule_scan_bwd"] \
        + ["gated_delta_rule_scan_fwd"] * 2
    assert all(("gdn.mix" if "delta_mix" in l else "gdn.scan") in l
               for l in calls)
    # q's and k's projections reach the mix kernels tokens-first at
    # their own 16 heads, v's at 32, and leave heads-first
    assert sorted(re.search(r"= \(?(f32\[[0-9,]*\])", l).group(1)
                  for l in calls if "delta_mix_fwd" in l) \
        == ["f32[1,16,8192,128]"] * 4 + ["f32[1,32,8192,128]"] * 2
    assert not _turned(txt, 16 * 8192 * 128)
    assert all("f32[16,8192,128]" in l for l in calls if "_head_" in l)
    # the scan's rows leave as (B H, T, dv) and its states stay (N, B H,
    # dv, dk): no turn of the stacked outputs after a loop
    assert all("f32[32,8192,128]" in l and "f32[128,32,128,128]" in l
               for l in calls if "_scan_" in l)
    assert "triangular" not in txt and "remat.gdn.terms" not in txt \
        and " while(" not in txt and "kda.scan" not in txt
    assert not re.search(r"\bf32\[[0-9,]*,64,64\]", txt)
    assert not re.search(r"\b(?:f32|bf16)\[[0-9,]*,64,64,128\]", txt)
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2 ** 30


GDN_NAMES = ["gated_delta_rule_head_bwd", "gated_delta_rule_head_fwd",
             "gated_delta_rule_scan_bwd", "gated_delta_rule_scan_fwd"]


def _gdn_loss(mesh, spec, q, k, v, g, beta):
    from flexflow_tpu.ops.recurrent_ops import gated_delta_rule
    with jax.named_scope("ff.forward"), jax.named_scope("linear_attn_1"), \
            jax.named_scope("gdn.scan"):
        out, _ = gated_delta_rule(q, k, v, g, beta, 64, jnp.bfloat16,
                                  mesh=mesh, spec=spec)
    return jnp.sum(out)


def _gdn_operands(mesh, spec, b, hk, h, t, d):
    def arr(*shape):
        return jax.ShapeDtypeStruct(
            shape, jnp.float32, sharding=NamedSharding(
                mesh, P(*(tuple(spec) + (None,) * len(shape))[:len(shape)])))
    ops = [arr(b, h, t, d)] + [arr(b, h, t)] * 2
    if len(spec) > 1 and spec[1] and hk % mesh.shape[spec[1]]:
        spec = ()               # q/k heads the axis cannot split: whole
    return [arr(b, hk, t, d)] * 2 + ops


@pytest.mark.parametrize("spec,hk,names", [
    (("x0", None), 2, GDN_NAMES), ((None, "x0"), 4, GDN_NAMES),
    ((None, "x0"), 2, [])], ids=["batch", "heads", "split_groups"])
def test_the_head_decay_kernels_compile_under_a_mesh(
        v5e_devices, compiled_kda, spec, hk, names):
    """Four chips by batch or by heads: each runs the head form's
    kernels on its own q/k heads and the value heads they serve under
    ``shard_map``; two q/k heads over four devices would split a group,
    and that layer keeps the plain terms."""
    mesh = Mesh(np.array(v5e_devices), ("x0",))
    txt = _compile_text(
        jax.grad(functools.partial(_gdn_loss, mesh, P(*spec)),
                 argnums=range(5)),
        *_gdn_operands(mesh, spec, 4, hk, 2 * hk, 512, 128))
    assert _kernel_names(txt) == names


def test_the_head_decay_kernels_keep_their_scope(v5e_devices, compiled_kda,
                                                 chip_locations):
    """All four calls carry the layer's name and ``gdn.scan`` in their
    ``op_name``, the backward's inside the ``transpose(``: the
    benchmark's ``qwen3next_gdn_scan_time_share.train`` finds them by
    those parts."""
    mesh = Mesh(np.array(v5e_devices[:1]), ("x0",))
    txt = _compile_text(
        jax.grad(functools.partial(_gdn_loss, None, None),
                 argnums=range(5)), *_gdn_operands(mesh, (), 1, 2, 4, 512,
                                                   128))
    by_name = {n: l for n in GDN_NAMES for l in txt.splitlines()
               if MOSAIC_CALL in l and f"{n}." in l.split(" = ")[0]}
    assert 'jvp(ff.forward)/linear_attn_1/gdn.scan/' \
        'gated_delta_rule_head_fwd/pallas_call"' \
        in by_name["gated_delta_rule_head_fwd"]
    assert 'transpose(jvp(ff.forward))/linear_attn_1/gdn.scan/' \
        'gated_delta_rule_head_bwd/pallas_call"' \
        in by_name["gated_delta_rule_head_bwd"]
    assert 'jvp(ff.forward)/linear_attn_1/gdn.scan/' \
        'gated_delta_rule_scan_fwd/pallas_call"' \
        in by_name["gated_delta_rule_scan_fwd"]
    assert 'transpose(jvp(ff.forward))/linear_attn_1/gdn.scan/' \
        'gated_delta_rule_scan_bwd/pallas_call"' \
        in by_name["gated_delta_rule_scan_bwd"]


# ----------------------------------------------------------------------
# the delta rule's scan kernel pair (the state from chunk to chunk in
# VMEM), alone at the two cells' shapes and inside cell 5's layer
# ----------------------------------------------------------------------
SCAN_NAMES = ["gated_delta_rule_scan_bwd", "gated_delta_rule_scan_fwd"]


@pytest.mark.parametrize("chunks,lanes", [(64, 128), (128, 1)],
                         ids=["cell_5_a_channel", "cell_10_a_head"])
def test_the_scan_kernels_compile_at_the_cells_shapes(v5e_devices, chunks,
                                                      lanes):
    """The pair alone on the terms as the terms kernels leave them, bf16,
    32 (batch x head) rows of 128 x 128 in chunks of 64: cell 5's 64
    chunks with a decay a channel of k, cell 10's 128 with a decay a
    head (the same operand with a row of 1, spread inside). Both calls
    compile for a described v5e, take the terms chunk leading as they
    lie and hand the rows out as ``(B H, T, dv)``."""
    from flexflow_tpu.kernels.gated_delta_rule import scan_chunks
    one = jax.sharding.SingleDeviceSharding(v5e_devices[0])

    def arr(dtype, *last):
        return jax.ShapeDtypeStruct((chunks, 1, 32) + last, dtype,
                                    sharding=one)
    bf16, f32 = jnp.bfloat16, jnp.float32
    terms = [arr(bf16, 64, 128), arr(f32, 64, 128), arr(bf16, 64, 64),
             arr(bf16, 64, 128), arr(bf16, 64, 128), arr(f32, lanes)]
    txt = _compile_text(jax.grad(
        lambda *t: jnp.sum(scan_chunks(*t, interpret=False)[0] ** 2),
        argnums=range(6)), *terms)
    calls = [l for l in txt.splitlines() if MOSAIC_CALL in l]
    assert _kernel_names(txt) == SCAN_NAMES and " while(" not in txt
    rows = f"f32[32,{chunks * 64},128]"
    assert all(rows in l and f"bf16[{chunks},32,64,128]" in l
               and f"f32[{chunks},32,128,128]" in l for l in calls)


def test_the_channel_decay_delta_rule_compiles_at_the_published_width(
        v5e_devices, compiled_kda):
    """One linear layer's forward and backward at cell 5's shape (2304 ->
    32 heads of 128 over 4,096 positions in 64 chunks of 64, bf16
    operands) compiled for a described v5e: three Mosaic calls of the
    terms' kernels and three of the scan's (the layer's run, its
    recomputation and the backward), every one under ``kda.scan``; no
    ``while`` is left under that scope (nor anywhere in the layer), no
    triangular solve, and no array the size of the stacked outputs is
    turned or copied around the scan's calls."""
    from flexflow_tpu import FFConfig
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.ops.recurrent_ops import GatedDeltaRuleOp
    from flexflow_tpu.ops.registry import EmitCtx
    params = {"num_heads": 32, "head_dim": 128, "taps": 4, "eps": 1e-5}
    op = GatedDeltaRuleOp()
    one = jax.sharding.SingleDeviceSharding(v5e_devices[0])
    x = jax.ShapeDtypeStruct((1, 4096, 2304), jnp.float32, sharding=one)
    w = {s.name: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=one)
         for s in op.weights(params, [(1, 4096, 2304)],
                             [DataType.DT_FLOAT])}

    def loss(x, w):
        (y,) = op.emit(params, [x], w,
                       EmitCtx(training=True, config=FFConfig()), "kda_1")
        return jnp.sum(y * y)

    txt = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))
                  ).lower(x, w).compile().as_text()
    calls = [l for l in txt.splitlines() if MOSAIC_CALL in l]
    assert _kernel_names(txt) == ["delta_mix_bwd"] * 3 \
        + ["delta_mix_fwd"] * 6 + ["gated_delta_rule_bwd"] \
        + ["gated_delta_rule_fwd"] * 2 + ["gated_delta_rule_scan_bwd"] \
        + ["gated_delta_rule_scan_fwd"] * 2
    assert all(("kda.mix" if "delta_mix" in l else "kda.scan") in l
               for l in calls)
    assert " while(" not in txt and "triangular" not in txt
    # nor around the mix kernels': the projection's product is written
    # tokens-first as they read it, ``dp`` goes to the products as it is
    assert not _turned(txt, 32 * 4096 * 128)


# ----------------------------------------------------------------------
# q, k and v from the projection's product to the recurrence's operand
# (kernels/delta_mix.py, PR 63)
# ----------------------------------------------------------------------
MIX_NAMES = ["delta_mix_bwd", "delta_mix_fwd"]
# (batch, tokens, heads, unit): cell 5's q, k and v, cell 10's q and k,
# cell 10's v
MIX_SHAPES = {"cell5_q": (1, 4096, 32, True), "cell5_v": (1, 4096, 32, False),
              "cell10_q": (1, 8192, 16, True),
              "cell10_v": (1, 8192, 32, False)}


def _mix_both(mesh, spec, unit, p, taps, ct):
    from flexflow_tpu.kernels.delta_mix import delta_mix
    b, t, width = p.shape
    with jax.named_scope("ff.forward"), jax.named_scope("kda_2"), \
            jax.named_scope("kda.mix"):
        y, pull = jax.vjp(lambda p, taps: delta_mix(
            p.reshape(b, t, -1, 128), taps, unit=unit, scale=128 ** -0.5,
            eps=1e-6, interpret=False, mesh=mesh, spec=spec), p, taps)
    return y, pull(ct)


def _mix_operands(mesh, spec, b, t, heads):
    bi, hi = (tuple(spec) + (None, None))[:2]

    def arr(shape, *entries):
        return jax.ShapeDtypeStruct(shape, jnp.float32,
                                    sharding=NamedSharding(mesh, P(*entries)))
    return (arr((b, t, heads * 128), bi, None, hi),
            arr((heads, 128, 4), hi), arr((b, heads, t, 128), bi, hi))


@pytest.mark.parametrize("case", sorted(MIX_SHAPES))
def test_the_mix_kernels_compile_at_the_cells_shapes(v5e_devices,
                                                     chip_locations, case):
    """Forward and backward at the derived tiles for a described v5e:
    the projection's ``(b, t, heads * d)`` float32 in, heads-first out,
    ``dp`` the projection's own bytes, and nothing of an operand's size
    copied or turned beside the two calls."""
    from flexflow_tpu.kernels import delta_mix as dmk
    b, t, heads, unit = MIX_SHAPES[case]
    assert dmk.takes_kernel(128, 4, t, jnp.float32)
    mesh = Mesh(np.array(v5e_devices[:1]), ("x0",))
    txt = _compile_text(functools.partial(_mix_both, None, None, unit),
                        *_mix_operands(mesh, (), b, t, heads))
    assert _kernel_names(txt) == MIX_NAMES
    bwd, fwd = (l.split(" custom-call(") for l in sorted(
        (l for l in txt.splitlines() if MOSAIC_CALL in l),
        key=lambda l: "delta_mix_fwd" in l.split(" = ")[0]))
    wide, first = f"f32[{b},{t},{heads * 128}]", f"f32[{b},{heads},{t},128]"
    # (the operands' shapes stand in the call's layout constraints)
    assert first in fwd[0] and fwd[1].count(wide) == 2
    assert wide in bwd[0] and bwd[1].count(wide) == 3 \
        and bwd[1].count(first) == 2
    # both carry the scope the benchmark's layer shares find them by
    assert "ff.forward/kda_2/kda.mix/" in fwd[1] \
        and "transpose(ff.forward)/kda_2/kda.mix/" in bwd[1]
    assert not _turned(txt, b * t * heads * 128)


@pytest.mark.parametrize("spec", [("x0", None), (None, "x0")],
                         ids=["batch", "heads"])
def test_the_mix_kernels_compile_under_a_mesh(v5e_devices, spec):
    """Four chips by batch or by heads: each runs the kernels on its own
    rows and whole heads under ``shard_map``; by batch the taps'
    gradient is summed over the chips."""
    mesh = Mesh(np.array(v5e_devices), ("x0",))
    txt = _compile_text(functools.partial(_mix_both, mesh, P(*spec), True),
                        *_mix_operands(mesh, spec, 4, 512, 8))
    assert _kernel_names(txt) == MIX_NAMES
    assert ("all-reduce" in txt) is (spec[0] is not None)


# sha256 of one linear layer's lowered text (value and gradients of a
# sum, float32 operands, for a described v5e) taken from the parent of
# PR 63 (``git archive b5347bf``) by these same lines: where ``mix_impl``
# says no, q, k and v are ``short_conv``, ``silu`` and ``_unit`` line for
# line. A PR that means to change those lines replaces the hashes.
LINEAR_LAYERS = {
    "channel_decay_heads_of_64": (
        {"num_heads": 4, "head_dim": 64, "taps": 4, "eps": 1e-5}, False,
        "50d6cd5a597398ec3434102b4633b5ac6e91484d873efc588b99e83a467a9469"),
    "channel_decay_stubbed": (
        {"num_heads": 2, "head_dim": 128, "taps": 4, "eps": 1e-5}, True,
        "aabae65529ac31637d7cf6967177bb52dfcb16c826fd87cf81fec27663b04ae9"),
    "head_decay_stubbed": (
        {"num_heads": 4, "num_key_heads": 2, "head_dim": 128, "taps": 4,
         "eps": 1e-6, "decay": "head"}, True,
        "f398e961dba98198c1c72d0ef42ecd80bc14205e93dd25eda8fd00d4deaaaec9"),
}


@pytest.mark.parametrize("case", sorted(LINEAR_LAYERS))
def test_where_the_mix_predicate_says_no_the_layer_lowers_to_the_parents_text(
        v5e_devices, monkeypatch, case):
    """Heads of 64 (the shapes say no) and both forms of the decay at
    heads of 128 with the predicate stubbed to no: the layer's lowered
    text is the parent's, and names no mix kernel."""
    import hashlib

    from flexflow_tpu import FFConfig
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.ops.recurrent_ops import GatedDeltaRuleOp
    from flexflow_tpu.ops.registry import EmitCtx
    params, stubbed, sha = LINEAR_LAYERS[case]
    if stubbed:
        monkeypatch.setattr("flexflow_tpu.kernels.delta_mix.takes_kernel",
                            lambda *a: False)
    op = GatedDeltaRuleOp()
    one = jax.sharding.SingleDeviceSharding(v5e_devices[0])
    shape = (1, 512, 256)
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)
    w = {s.name: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=one)
         for s in op.weights(params, [shape], [DataType.DT_FLOAT])}

    def loss(x, w):
        (y,) = op.emit(params, [x], w,
                       EmitCtx(training=True, config=FFConfig()), "linear_0")
        return jnp.sum(y * y)

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w).as_text()
    assert "delta_mix" not in lowered
    assert hashlib.sha256(lowered.encode()).hexdigest() == sha


# ----------------------------------------------------------------------
# the SambaY cell (PR 61): flash at 64 / 128 on paired heads, the
# selective scan at the published width
# ----------------------------------------------------------------------
@pytest.mark.parametrize("window", [0, 512], ids=["whole", "window_512"])
def test_the_grouped_kernels_compile_at_cell_11s_shapes(v5e_devices,
                                                        chip_locations,
                                                        window):
    """``phi4_mini_flash_reasoning.train.1chip``: one of a differential
    layer's two calls, 20 query pairs on 10 key pairs over 8,192
    positions, q.k over 64 and p.v over 128 (no cell before it ran 64 /
    128), bf16, causal, whole and in the 512 band: the three kernels
    compile and read K and V at their own 10 heads."""
    b, h, kvh, s, d, dv = 1, 20, 10, 8192, 64, 128
    one = jax.sharding.SingleDeviceSharding(v5e_devices[0])
    q = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16, sharding=one)
    k = jax.ShapeDtypeStruct((b, kvh, s, d), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((b, kvh, s, dv), jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, interpret=False,
                            window=window)
        return jnp.sum(o.astype(jnp.float32))

    txt = _compile_text(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert _kernel_names(txt) == FLASH_NAMES
    for narrow in (f"bf16[{b * kvh},{s},{d}]", f"bf16[{b * kvh},{s},{dv}]"):
        assert all(narrow in l.split(" custom-call(")[1]
                   for l in txt.splitlines() if MOSAIC_CALL in l)


def test_the_selective_scan_mixer_compiles_at_the_published_width(
        v5e_devices, monkeypatch):
    """One mixer's forward and backward at 2560 -> 5120 channels of 16
    state entries over 8,192 positions in 128 chunks of 64, bf16
    operands, compiled for a described v5e: the recurrence is two Mosaic
    calls (``kernels/selective_scan.py``: the forward that keeps the
    chunks' starting states, the backward), both under ``ssm1.scan``,
    each with a cost estimate; no ``while`` walks the chunks and no
    ``remat.ssm1.chunk`` is left; NO array of a chunk's states, let
    alone the sequence's (``(64, 16, 5120)`` float32 was 20 MiB a chunk
    on the plain path, ``(8192, 16, 5120)`` would be 2.5 GiB): what
    carries ``(.., 16, ..)`` registers is the 128 chunks' starting
    states, 40 MiB. ``x``, ``dt`` and ``y`` reach the calls as ``(1024,
    320, 128)``, the bytes of the tiled ``(8192, 5120)`` arrays as they
    lie: nothing that size is copied, turned or reshaped beside the
    calls (the views are bitcasts inside the neighbouring fusions). The
    layer's temporaries read 1.72 GiB here where the plain path's read
    1.58 (its interior held: it is not rematerialised whole, a block
    around it is): the calls' ``dx`` and ``d dt`` are buffers of their
    own, 160 MiB each, where XLA fused the plain path's into their
    consumers; the whole step's ``step_hbm_gib`` is LOWER (12.077 for
    12.124: PERF.md section 6, PR 62)."""
    from flexflow_tpu import FFConfig
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.kernels import selective_scan
    from flexflow_tpu.ops.recurrent_ops import SelectiveScanMixerOp
    from flexflow_tpu.ops.registry import EmitCtx
    monkeypatch.setattr(selective_scan, "pallas_interpret", lambda: False)
    params = {"inner": 5120, "state": 16, "dt_rank": 160, "taps": 4,
              "chunk": 64, "memory_out": True}
    op = SelectiveScanMixerOp()
    one = jax.sharding.SingleDeviceSharding(v5e_devices[0])
    x = jax.ShapeDtypeStruct((1, 8192, 2560), jnp.float32, sharding=one)
    w = {s.name: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=one)
         for s in op.weights(params, [(1, 8192, 2560)],
                             [DataType.DT_FLOAT])}
    assert sum(int(np.prod(v.shape)) for v in w.values()) == 41241600

    def loss(x, w):
        y, m = op.emit(params, [x], w,
                       EmitCtx(training=True, config=FFConfig()), "ssm_0")
        return jnp.sum(y) + jnp.sum(m)

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w)
    assert lowered.as_text().count("cost_estimate") == 2
    compiled = lowered.compile()
    txt = compiled.as_text()
    calls = [l for l in txt.splitlines() if MOSAIC_CALL in l]
    assert _kernel_names(txt) == ["selective_scan_bwd", "selective_scan_fwd"]
    assert all("ssm1.scan" in l for l in calls)
    # in the text's order: the forward first
    assert ["selective_scan_fwd" in l.split(" = ")[0] for l in calls] \
        == [True, False]
    assert "remat.ssm1.chunk" not in txt and " while(" not in txt
    # a chunk's states or the sequence's, in any view
    assert not re.search(r"f32\[(8192|128,64|64),1,16,5120\]", txt)
    assert re.search(r"f32\[1,128,5,16,8,128\]", txt)     # the 128 starts
    # nothing the size of x beside the calls but fusions' own results
    whole = 8192 * 5120
    moved = [l for l in txt.splitlines()
             for m in [re.search(
                 r"= f32\[([0-9,]+)\]\S* (copy|transpose|reshape)\(", l)]
             if m and np.prod([int(v) for v in m.group(1).split(",")])
             >= whole]
    assert not moved, moved
    assert compiled.memory_analysis().temp_size_in_bytes < 1.75 * 2 ** 30


# ----------------------------------------------------------------------
# the block-diffusion mask inside the flash kernels (PR 64)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("length,block,dtype", [
    (4096, 4, "bfloat16"), (1536, 24, "float32")],
    ids=["cell_12s", "a_block_of_24_float32"])
def test_the_block_diffusion_kernels_compile(v5e_devices, chip_locations,
                                             length, block, dtype):
    """``sdar_30b_a3b.train.1chip``: 32 query heads on 4 key/value heads
    of 128 over 2 x 4,096 positions, bf16, blocks of 4 (and a block that
    is no power of two, whose first place is a remainder): the three
    kernels with the mask's index maps and liveness tests compile under
    Mosaic, and the call has no operand beyond the plain call's (the
    mask is never one)."""
    one = jax.sharding.SingleDeviceSharding(v5e_devices[0])
    q = jax.ShapeDtypeStruct((1, 32, 2 * length, 128), jnp.dtype(dtype),
                             sharding=one)
    kv = jax.ShapeDtypeStruct((1, 4, 2 * length, 128), jnp.dtype(dtype),
                              sharding=one)

    def loss(q, k, v, bd):
        o = flash_attention(q, k, v, interpret=False, block_diffusion=bd)
        return jnp.sum(o.astype(jnp.float32))

    def text(bd):
        return _compile_text(jax.grad(functools.partial(loss, bd=bd),
                                      argnums=(0, 1, 2)), q, kv, kv)

    masked, plain = text((length, block)), text(())
    assert _kernel_names(masked) == FLASH_NAMES

    def operands(txt):
        return sorted(l.split(" custom-call(")[1].count("%")
                      for l in txt.splitlines() if MOSAIC_CALL in l)

    assert operands(masked) == operands(plain)
    assert "s8[" not in masked


@pytest.mark.parametrize("blocks", [
    dict(block_q=512, block_k=2048, bwd_block_q=512, bwd_block_k=1024),
    dict(block_q=1024, block_k=512, bwd_block_q=1024, bwd_block_k=512),
    dict(block_q=256, block_k=1024, bwd_block_q=256, bwd_block_k=256)],
    ids=["a_wider_k_side", "a_wider_q_side", "pieces_wider_than_the_q_block"])
def test_the_walked_diagonal_compiles_where_a_side_is_wider(
        v5e_devices, chip_locations, blocks):
    """PR 65: a live noised x noised tile is walked in the sub-blocks of
    its diagonal. At cell 12's tiles every place is static; where one
    side of a tile is wider, the narrower side's place in it comes from
    the program ids: dynamic sublane offsets into the k-side blocks and
    scratch, and in ``bwd_dkv`` under a wider q side a dynamic LANE
    offset into the row statistics. Mosaic takes each."""
    from flexflow_tpu.kernels.flash_attention import BD_SUB
    from flexflow_tpu.obs import events
    one = jax.sharding.SingleDeviceSharding(v5e_devices[0])
    q = jax.ShapeDtypeStruct((1, 8, 4096, 128), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((1, 2, 4096, 128), jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        o = flash_attention(q, k, v, interpret=False,
                            block_diffusion=(2048, 4), **blocks)
        return jnp.sum(o.astype(jnp.float32))

    events.enable()
    events.clear()
    try:
        txt = _compile_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
        subs = [e["attrs"]["bd_sub"] for e in events.events()
                if e["name"] == "flash.grid"]
    finally:
        events.disable()
        events.clear()
    assert _kernel_names(txt) == FLASH_NAMES
    assert subs == [BD_SUB] * 3



# ----------------------------------------------------------------------
# grouped state-space mixers, experts in a latent (PR 66)
# ----------------------------------------------------------------------
def test_the_grouped_mixer_compiles_at_cell_13s_shape(v5e_devices,
                                                      monkeypatch):
    """``nemotron3_super_120b_a12b.train.1chip``: one mixer's forward
    and backward at 4096 -> 32 heads of 64 x 128 in 2 groups of B and C
    over 4,096 positions in 32 chunks of 128, bf16 operands, compiled
    for a described v5e: the recurrence is the two Mosaic calls, both
    under ``ssm.scan``, reading B and C as ``(1, 4096, 256)`` (two
    groups' columns side by side, picked by the block index: no copy a
    group), and no ``while`` carries the chunk states."""
    from flexflow_tpu import FFConfig
    from flexflow_tpu.ffconst import DataType
    from flexflow_tpu.kernels import state_space
    from flexflow_tpu.ops.recurrent_ops import StateSpaceMixerOp
    from flexflow_tpu.ops.registry import EmitCtx
    monkeypatch.setattr(state_space, "pallas_interpret", lambda: False)
    params = {"num_heads": 32, "head_dim": 64, "state": 128, "taps": 4,
              "chunk": 128, "eps": 1e-5, "groups": 2}
    assert state_space.takes_kernel(128, 32, 64, 128, 2) \
        and state_space.heads_per_block(32, 64, 2) == 8
    op = StateSpaceMixerOp()
    one = jax.sharding.SingleDeviceSharding(v5e_devices[0])
    x = jax.ShapeDtypeStruct((1, 4096, 4096), jnp.float32, sharding=one)
    w = {s.name: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=one)
         for s in op.weights(params, [(1, 4096, 4096)],
                             [DataType.DT_FLOAT])}
    assert w["in_proj"].shape == (4096, 4640) \
        and w["conv_w"].shape == (2560, 4)

    def loss(x, w):
        (y,) = op.emit(params, [x], w,
                       EmitCtx(training=True, config=FFConfig()), "mamba_1")
        return jnp.sum(y)

    txt = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w).compile() \
        .as_text()
    calls = [l for l in txt.splitlines() if MOSAIC_CALL in l]
    assert _kernel_names(txt) == ["state_space_bwd", "state_space_fwd"]
    assert all("ssm.scan" in l and "f32[1,4096,256]" in l for l in calls)
    assert " while(" not in txt


def test_the_latent_experts_compile_through_the_token_sum_kernel(
        v5e_devices, compiled_token_sum, chip_locations):
    """One LatentMoE layer's forward and backward at cell 13's shape
    (4,096 tokens of 4,096, 22 of 512 sigmoid-routed experts with 8
    held, ReLU-squared experts of 2,688 in a latent of 1,024, a shared
    expert of 5,376 on the stream, eight shares of rows) for a described
    v5e: the way back to the tokens is the kernel over rows as wide as
    the LATENT, two grouped products a pass (no gate matrix), and the
    three scopes are on the compiled ops, the BACKWARD's kernel calls
    among them: ``_rows_for``'s and ``_combine``'s transposes are
    ``custom_vjp`` backwards and come out under the scope their forward
    call was made in, which is where
    ``nemotron_moe_latent_time_share.train`` looks for them."""
    from flexflow_tpu.kernels import moe_token_sum as mts
    from flexflow_tpu.ops.moe_ops import RoutedExpertsOp
    params = dict(num_experts=512, top_k=22, expert_dim=2688,
                  shared_dim=5376, experts_held=8, first_held=0, scale=5.0,
                  bias_std=0.02, rows_factor=8, latent=1024,
                  activation="relu2")
    budget = RoutedExpertsOp.rows_multiplied(4096, params)
    assert budget == 11264
    assert mts.takes_kernel(4096, 1024, 22, budget, 8, jnp.bfloat16)
    x, w = _experts_operands(v5e_devices[0], 4096, 4096, params)
    assert sorted(w) == ["bias", "w_down", "w_latent_in", "w_latent_out",
                         "w_up", "wg", "ws_down", "ws_up"]
    txt = _compile_text(
        jax.grad(functools.partial(_experts_loss, params), argnums=(0, 1)),
        x, w)
    mine = [l for l in txt.splitlines()
            if MOSAIC_CALL in l and "moe_token_sum" in l.split(" = ")[0]]
    assert len(mine) == 4 and all("moe.latent" in l for l in mine)
    assert sum("transpose(" in l for l in mine) >= 2
    assert all(f"[{budget},1024]" in l for l in mine)
    for scope in ("experts_1/moe.route", "experts_1/moe.latent",
                  "experts_1/moe.shared"):
        assert scope in txt, scope


# ----------------------------------------------------------------------
# the router: XLA's float32 product, no gather and no scatter (PR 67)
# ----------------------------------------------------------------------
#: (tokens, hidden, published experts) and the layer's parameters
ROUTER_CELLS = {
    "cell13_nemotron": ((4096, 4096, 512), dict(
        num_experts=512, top_k=22, expert_dim=2688, shared_dim=5376,
        experts_held=8, first_held=0, scale=5.0, bias_std=0.02,
        rows_factor=8, latent=1024, activation="relu2")),
    "cell10_qwen3next": ((8192, 2048, 512), dict(
        num_experts=512, top_k=10, expert_dim=512, shared_dim=512,
        experts_held=32, first_held=0, scoring="softmax",
        choice_bias=False, shared_gate=True, rows_factor=6)),
}


def _highest_products(txt):
    """The result shapes (leading ones dropped) of the compiled text's
    matrix products whose operands are both at the highest precision:
    six bf16 passes each."""
    found = set()
    for l in txt.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* convolution\(", l)
        if m and "operand_precision={highest,highest}" in l:
            dims = [int(d) for d in m.group(1).split(",")]
            found.add(tuple(d for d in dims if d != 1))
    return found


@pytest.mark.parametrize("cell", sorted(ROUTER_CELLS))
def test_the_router_keeps_xlas_product_and_walks_no_single_numbers(
        v5e_devices, compiled_token_sum, chip_locations, cell):
    """One routed-experts layer's forward and backward at the cell's
    shape for a described v5e. The router's products are XLA's own at
    the highest precision, forward and cotangent (on the chip they run
    at the matrix unit's rate: ``examples/tpu_time_router_product.py``;
    all three are held in the jaxpr by ``tests/test_router_product.py``:
    here ``x^T dlogits`` has ``logits``' shape wherever tokens equal
    hidden). Under ``moe.route`` nothing is left that the chip walks a
    number at a time: no gather (the chosen experts' own scores were one
    of 90,112 single numbers a layer in cell 13) and no scatter (the
    groups' sizes), forward or backward; what stays are the product, the
    top-k's sort, the two sorts of the assignments and elementwise
    passes."""
    (t, e, n), params = ROUTER_CELLS[cell]
    x, w = _experts_operands(v5e_devices[0], t, e, params)
    txt = _compile_text(
        jax.grad(functools.partial(_experts_loss, params), argnums=(0, 1)),
        x, w)
    assert {(t, n), (t, e)} <= _highest_products(txt)
    mine = [l for l in txt.splitlines()
            if "experts_1/moe.route" in l and " = " in l]
    assert len([l for l in mine if " sort(" in l]) == 3
    walked = [l.split(" = ")[0].strip() for l in mine
              if re.search(r" (gather|scatter)\(", l)
              or "kind=kCustom" in l or "GatherScatter" in l]
    assert not walked
