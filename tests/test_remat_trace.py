"""Rematerialisation seen from inside the program (PR 53): every
``jax.checkpoint`` the package makes goes through ``ops/registry.py::
checkpointed``, which names the call on the device (``remat.<site>``)
and records one ``remat.wrap`` instant a trace with what the wrap holds
for its backward; ``executor.init_params`` and ``compile.opt_state``
carry one device's ``device_bytes``. CPU, tiny sizes; the model is the
linear-attention configuration's, whose layers rematerialise themselves
inside and outside a rematerialised block.
"""
import io
import os
import re
import tokenize

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flexflow_tpu import AdamOptimizer, FFConfig, FFModel
from flexflow_tpu.executor import device_bytes
from flexflow_tpu.models.nlp import (KeyeRankConfig, KimiLinearRankConfig,
                                     XingRankConfig, build_hybrid_conv_moe,
                                     build_latent_moe)
from flexflow_tpu.obs import events
from flexflow_tpu.ops import registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(ROOT, "flexflow_tpu")
B, S = 8, 40              # a batch the 8 virtual devices divide
SITES = {"block": "executor.py", "kda.layer": "ops/recurrent_ops.py",
         "kda.branch": "ops/recurrent_ops.py",
         "kda.terms": "ops/recurrent_ops.py",
         "kda.step": "ops/recurrent_ops.py",
         "gdn.terms": "ops/recurrent_ops.py",
         "ssm.layer": "ops/recurrent_ops.py",
         "ssm.chunk": "ops/recurrent_ops.py",
         "ssm1.chunk": "ops/recurrent_ops.py", "mhc.maps": "ops/hyper_ops.py",
         "mhc.plain": "ops/hyper_ops.py",
         "dsa.chunk": "ops/sparse_attention.py"}


# ----------------------------------------------------------------------
# one wrap, ten sites: the sources
# ----------------------------------------------------------------------
def _code_tokens(path):
    """The file's tokens without comments and strings (a docstring may
    still name ``jax.checkpoint``)."""
    with open(path) as f:
        text = f.read()
    return [t.string for t in tokenize.generate_tokens(
        io.StringIO(text).readline)
        if t.type not in (tokenize.COMMENT, tokenize.STRING, tokenize.NL,
                          tokenize.NEWLINE, tokenize.INDENT, tokenize.DEDENT)]


def _checkpoint_calls(path) -> int:
    toks = _code_tokens(path)
    return sum(1 for a, b, c in zip(toks, toks[1:], toks[2:])
               if (a, b) == ("jax", ".") and c in ("checkpoint", "remat"))


def test_the_package_calls_jax_checkpoint_once_inside_the_wrap():
    found = {}
    for folder, _, files in os.walk(PACKAGE):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                n = _checkpoint_calls(path)
                if n:
                    found[os.path.relpath(path, PACKAGE)] = n
    assert found == {os.path.join("ops", "registry.py"): 1}


def test_the_search_for_calls_finds_one_where_there_is_one(tmp_path):
    path = tmp_path / "m.py"
    path.write_text('"""names ``jax.checkpoint`` in words."""\n'
                    "import jax\n# jax.checkpoint(f)\n"
                    "KEEP = jax.checkpoint_policies.everything_saveable\n"
                    "@jax.checkpoint\ndef f(x):\n    return x\n"
                    "g = jax.remat(f)\n")
    assert _checkpoint_calls(str(path)) == 2


@pytest.mark.parametrize("site,file", sorted(SITES.items()))
def test_each_site_goes_through_the_wrap_under_its_name(site, file):
    with open(os.path.join(PACKAGE, file)) as f:
        text = f.read()
    assert text.count(f'site="{site}"') == 1
    calls = text.count("checkpointed(")
    assert calls == sum(1 for f in SITES.values() if f == file)


# ----------------------------------------------------------------------
# the model: linear attention inside and outside a block
# ----------------------------------------------------------------------
def _build(builder, mc, remat="blocks", batch=B, seq=S):
    cfg = FFConfig()
    cfg.batch_size = batch
    cfg.only_data_parallel = True
    cfg.use_bf16_compute = False
    cfg.kernel_impls = "attention:xla"
    cfg.remat = remat
    ff = FFModel(cfg)
    out = builder(ff, batch, seq, mc)
    ff.compile(AdamOptimizer(1e-3), "sparse_categorical_crossentropy", [],
               output_tensor=out)
    return ff


def _lowered(ff, vocab, batch=B, seq=S):
    ids = np.zeros((batch, seq), np.int32) % vocab
    pos = np.tile(np.arange(seq, dtype=np.int32), (batch, 1))
    fed = next(iter(ff._combined_loader(
        [ids, pos], np.zeros((batch, seq, 1), np.int32), shuffle=False)))
    return ff.executor.make_train_step().lower(
        ff.params, ff.opt_state, ff.state, jnp.int32(0), fed)


def _wraps():
    return [e["attrs"] for e in events.events() if e["name"] == "remat.wrap"]


@pytest.fixture(scope="module")
def kimi():
    """The tiny linear-attention model's train step traced with the
    recorder on (its instants, its spans' attributes, its compiled
    ``op_name`` s) and again with it off (its lowered text)."""
    mc = KimiLinearRankConfig.tiny()
    events.enable()
    events.clear()
    try:
        ff = _build(build_latent_moe, mc)
        spans = {e["name"]: e["attrs"] for e in events.events()
                 if e["name"] in ("executor.init_params",
                                  "compile.opt_state")}
        events.clear()
        lowered = _lowered(ff, mc.vocab_size)
        wraps, kept = _wraps(), [e["attrs"] for e in events.events()
                                 if e["name"] == "remat.kept"]
    finally:
        events.disable()
        events.clear()
    on = lowered.as_text()
    names = set(re.findall(r'op_name="([^"]*)"',
                           lowered.compile().as_text()))
    off = _lowered(ff, mc.vocab_size).as_text()
    silent = _wraps()
    return dict(ff=ff, mc=mc, spans=spans, wraps=wraps, kept=kept, on=on,
                off=off, names=names, silent=silent)


def test_recorder_off_no_instant_and_the_same_lowered_step(kimi):
    assert kimi["silent"] == []
    assert kimi["on"] == kimi["off"]


def _one(wraps, **want):
    got = [a for a in wraps
           if all(a.get(k) == v for k, v in want.items())]
    assert len(got) == 1, (want, got)
    return got[0]


def test_the_blocks_record_what_they_hold(kimi):
    """A block holds its entry (float32 (batch, positions, hidden), one
    device's eighth under the data-parallel mesh) and the marked output
    of its linear-attention layer, the same size; ``remat.kept`` says
    the whole array's, as it did."""
    ff, mc = kimi["ff"], kimi["mc"]
    assert dict(ff.dmesh.axis_sizes) == {"x0": 2, "x1": 2, "x2": 2}
    entry = 4 * B * S * mc.hidden_size // 8
    blocks = [a for a in kimi["wraps"] if a["site"] == "block"]
    assert [a["block"] for a in blocks] == [0, 1]
    for b, a in enumerate(blocks):
        assert a["depth"] == 0 and a["policy"] == "keep_marked"
        assert a["entry_bytes"] == entry and a["kept_bytes"] == entry
        assert f"kda_{b + 1}" in a["layers"] and "layer" not in a
        weights = sum(int(w.size) * w.dtype.itemsize
                      for l in a["layers"]
                      for w in ff.params.get(l, {}).values())
        assert a["weights_bytes"] == weights     # replicated: whole
    assert [k["bytes"] for k in kimi["kept"]] == [8 * entry] * 2


@pytest.mark.parametrize("layer,block,depth", [
    ("kda_0", None, 0), ("kda_1", 0, 1), ("kda_2", 1, 1), ("kda_4", None, 0)])
def test_a_layers_own_wrap_records_its_depth_and_its_entry(
        kimi, layer, block, depth):
    mc = kimi["mc"]
    a = _one(kimi["wraps"], site="kda.layer", layer=layer)
    assert a.get("block") == block and a["depth"] == depth
    assert a["policy"] == "none" and a["kept_bytes"] == 0
    assert a["entry_bytes"] == 4 * B * S * mc.hidden_size // 8
    assert a["weights_bytes"] == sum(
        int(w.size) * w.dtype.itemsize
        for w in kimi["ff"].params[layer].values())


@pytest.mark.parametrize("part", ["wq", "wk", "wv", "wf_a", "wg_a"])
def test_each_branch_is_a_wrap_one_deeper_than_its_layer(kimi, part):
    inside = _one(kimi["wraps"], site="kda.branch", layer="kda_2", part=part)
    outside = _one(kimi["wraps"], site="kda.branch", layer="kda_0",
                   part=part)
    assert (inside["depth"], inside["block"]) == (2, 1)
    assert outside["depth"] == 1 and "block" not in outside
    assert inside["entry_bytes"] == outside["entry_bytes"] \
        == 4 * B * S * kimi["mc"].hidden_size // 8
    assert inside["weights_bytes"] > 0 and inside["policy"] == "none"


@pytest.mark.parametrize("site", ["kda.terms", "kda.step"])
def test_the_recurrences_wraps_are_recorded_once_a_layer(kimi, site):
    for layer in ("kda_0", "kda_1", "kda_2", "kda_4"):
        a = _one(kimi["wraps"], site=site, layer=layer)
        assert a["weights_bytes"] == 0 and a["entry_bytes"] > 0
        assert a["depth"] == (2 if layer in ("kda_1", "kda_2") else 1)


def test_the_set_up_spans_carry_one_devices_bytes(kimi):
    ff = kimi["ff"]
    whole = sum(int(a.nbytes) for a in jax.tree.leaves(ff.params))
    init = kimi["spans"]["executor.init_params"]
    assert init["bytes"] == whole
    # data parallel: every device holds every weight; Adam two moments
    assert init["device_bytes"] == whole + sum(
        int(a.nbytes) for a in jax.tree.leaves(ff.state))
    assert kimi["spans"]["compile.opt_state"]["device_bytes"] == sum(
        int(a.nbytes) for a in jax.tree.leaves(ff.opt_state))
    assert kimi["spans"]["compile.opt_state"]["device_bytes"] >= 2 * whole


def _parts(name):
    return name.split(";")[0].split("/")


def _after(parts, first, then):
    """``then`` stands after ``first`` in the path."""
    return first in parts and then in parts[parts.index(first):]


@pytest.mark.parametrize("what,holds", [
    ("a block's second run of its experts",
     lambda p: _after(p, "remat.block", "rematted_computation")
     and _after(p, "rematted_computation", "experts_2")),
    ("a layer's own second run inside a block",
     lambda p: _after(p, "remat.block", "kda_2")
     and _after(p, "kda_2", "remat.kda.layer")
     and _after(p, "remat.kda.layer", "rematted_computation")),
    ("a layer's own second run outside a block",
     lambda p: "remat.block" not in p
     and _after(p, "kda_0", "remat.kda.layer")
     and _after(p, "remat.kda.layer", "rematted_computation")),
    ("a branch run again by its layer's second run",
     lambda p: _after(p, "remat.kda.layer", "rematted_computation")
     and _after(p, "rematted_computation", "remat.kda.branch")),
    ("a branch's own second run",
     lambda p: _after(p, "remat.kda.layer", "remat.kda.branch")
     and _after(p, "remat.kda.branch", "rematted_computation"))])
def test_the_compiled_steps_op_names_carry_their_owner(kimi, what, holds):
    assert any(holds(_parts(n)) for n in kimi["names"]), what


def test_a_block_that_keeps_a_layers_output_does_not_run_it_again(kimi):
    """``kda_2``'s block keeps its output: no op of the block's own
    recomputation lies in the layer."""
    for n in kimi["names"]:
        p = _parts(n)
        if "rematted_computation" in p and "kda_2" in p:
            assert p.index("remat.kda.layer") \
                < p.index("rematted_computation"), n


def test_names_are_exact_parts():
    """``remat.kda.layer`` is not ``kda.scan``: the readers of scopes
    compare whole parts."""
    parts = "a/remat.kda.layer/checkpoint/kda.scan/mul".split("/")
    assert "kda.scan" in parts and "remat.kda.layer" in parts
    assert "kda.layer" not in parts and "remat.kda" not in parts


# ----------------------------------------------------------------------
# the other ops' sites
# ----------------------------------------------------------------------
def _traced_wraps(builder, mc, remat, seq=S):
    events.enable()
    events.clear()
    try:
        ff = _build(builder, mc, remat=remat, seq=seq)
        events.clear()
        _lowered(ff, mc.vocab_size, seq=seq)
        return ff, _wraps()
    finally:
        events.disable()
        events.clear()


def test_the_hyper_connections_maps_are_wraps_of_their_layers():
    mc = XingRankConfig.tiny()
    ff, wraps = _traced_wraps(build_latent_moe, mc, "blocks")
    maps = [a for a in wraps if a["site"] == "mhc.plain"]
    assert maps and {a["policy"] for a in maps} == {"none"}
    names = {l.name for l in ff.layers}
    assert {a["layer"] for a in maps} <= names
    streams = 4 * B * S * mc.hc_mult * mc.hidden_size // 8
    assert {a["entry_bytes"] for a in maps} == {streams}
    assert {a["depth"] for a in maps} <= {0, 1}
    inside = [a for a in maps if a["depth"] == 1]
    assert inside and all("block" in a for a in inside)
    # a block holds the streams at its entry, and keeps nothing marked
    blocks = [a for a in wraps if a["site"] == "block"]
    assert blocks and all(a["policy"] == "none" and a["kept_bytes"] == 0
                          and a["entry_bytes"] == streams for a in blocks)


def test_the_sparse_attentions_chunks_are_wraps_told_apart_by_row():
    mc = KeyeRankConfig.tiny()
    seq = 48
    ff, wraps = _traced_wraps(build_hybrid_conv_moe, mc, "blocks", seq=seq)
    chunks = [a for a in wraps if a["site"] == "dsa.chunk"]
    layers = {a["layer"] for a in chunks}
    assert layers and layers <= {l.name for l in ff.layers}
    for layer in layers:
        rows = [a["part"] for a in chunks if a["layer"] == layer]
        assert rows == sorted(set(rows)) and rows[0] == 0 and len(rows) > 1
    assert {a["policy"] for a in chunks} == {"none"}
    # the block keeps the attention's output, marked in the layer
    blocks = [a for a in wraps if a["site"] == "block"]
    assert blocks and all(a["policy"] == "keep_marked"
                          and a["kept_bytes"] > 0 for a in blocks)


def test_without_remat_only_the_ops_own_wraps_are_recorded():
    mc = KimiLinearRankConfig.tiny()
    _, wraps = _traced_wraps(build_latent_moe, mc, "none")
    assert {a["site"] for a in wraps} == {
        "kda.layer", "kda.branch", "kda.terms", "kda.step"}
    assert all("block" not in a for a in wraps)
    assert {a["depth"] for a in wraps if a["site"] == "kda.layer"} == {0}


# ----------------------------------------------------------------------
# the wrap alone
# ----------------------------------------------------------------------
def test_the_wrap_is_jax_checkpoint_with_the_policy_it_is_given():
    policy = jax.checkpoint_policies.save_only_these_names(
        registry.KEPT_BY_BLOCK)

    def fn(x, w):
        return jnp.sum(registry.kept_by_block(jnp.sin(x @ w)) ** 2)

    x, w = jnp.ones((4, 8)), jnp.full((8, 8), 0.1)
    want = jax.grad(jax.checkpoint(fn, policy=policy), 1)(x, w)
    got = jax.grad(registry.checkpointed(
        fn, site="block", block=0, policy=policy, weights=(1,)), 1)(x, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # recorder off: nothing recorded, no wrap left open
    assert _wraps() == [] and not getattr(registry._WRAPS, "open", None)


def test_the_wrap_counts_entry_weights_and_kept_bytes_apart():
    policy = jax.checkpoint_policies.save_only_these_names(
        registry.KEPT_BY_BLOCK)

    def inner(x):
        return jnp.tanh(x)

    def fn(x, w):
        y = registry.checkpointed(inner, site="kda.layer", layer="l")(x @ w)
        return jnp.sum(registry.kept_by_block(y))

    events.enable()
    events.clear()
    try:
        jax.make_jaxpr(registry.checkpointed(
            fn, site="block", block=3, policy=policy, weights=(1,),
            layers=["l"]))(jnp.ones((4, 8), jnp.bfloat16),
                           jnp.ones((8, 16), jnp.float32))
        own, block = _wraps()
    finally:
        events.disable()
        events.clear()
    assert own == {"site": "kda.layer", "layer": "l", "block": 3, "depth": 1,
                   "policy": "none", "entry_bytes": 4 * 16 * 4,
                   "weights_bytes": 0, "kept_bytes": 0}
    assert block == {"site": "block", "block": 3, "depth": 0,
                     "policy": "keep_marked", "entry_bytes": 4 * 8 * 2,
                     "weights_bytes": 8 * 16 * 4, "kept_bytes": 4 * 16 * 4,
                     "layers": ["l"]}


@pytest.mark.parametrize("spec,shards", [
    (None, 1), (P(), 1), (P("a"), 2), (P(("a", "b")), 8),
    (P(None, "b"), 4), (P("a", "b"), 8)])
def test_bytes_are_one_devices_under_a_spec(spec, shards):
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("a", "b"))
    x = jax.ShapeDtypeStruct((16, 32), jnp.float32)
    assert registry._shard_bytes(x, spec, mesh) == 16 * 32 * 4 // shards
    # a dict of specs by the argument's keys; a key it lacks is whole
    tree = {"l": {"w": x, "b": x}}
    assert registry._shard_bytes(tree, {"l": {"w": spec}}, mesh) \
        == 16 * 32 * 4 // shards + 16 * 32 * 4


def test_device_bytes_is_the_fullest_devices_share():
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("a", "b"))
    whole = jax.device_put(np.zeros((16, 32), np.float32),
                           NamedSharding(mesh, P()))
    split = jax.device_put(np.zeros((16, 32), np.float32),
                           NamedSharding(mesh, P("a", "b")))
    assert device_bytes({"w": whole}) == 16 * 32 * 4
    assert device_bytes([whole, split]) == 16 * 32 * 4 + 16 * 32 * 4 // 8
    assert device_bytes({}) == 0
