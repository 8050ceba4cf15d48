"""Every rank configuration's train step lowers to the text it lowered
to at the parent commit.

The thirteen classes are the eleven that a cell's config file names and
the two base classes they extend; each is built at its ``tiny()`` size by
its builder (``rank_family.lowered_step``: the default ``FFConfig`` but
no search, 2 x 32 ids drawn from seed 0, cold caches), with and without
rematerialised blocks, and the sha256 of the lowered StableHLO is held
against ``LOWERED``. On the CPU this is the one instrument for "this
refactor changed no cell's program": a PR that touches a builder, an op
or the executor and leaves the twenty as they were compiles, in every
cell, the graph the parent compiled (kernel bodies and shapes at the
published sizes aside: ``tests/test_tpu_aot_compile.py`` holds those).
The tiny steps take the XLA attention path here (32 positions, interpret
mode), so the table also holds one attention layer's value and gradients
down the flash kernels and down XLA, which
``tests/test_ssm_hybrid.py::test_without_a_scale_the_layer_lowers_as_at_the_parent``
reads: the flash path is the one every cell runs on the chip.

The hashes were written at commit bc99f21 (PR 59's parent; PR 59
changes no program file, so parent and change agree). A PR that MEANS
to change a step replaces the hash it changes and says in ``CHANGES.md``
which cell's step that is; a failure prints the class, the mode and the
new hash. This file's cases clear JAX's caches, so they stay a file of
their own: ``--dist loadfile`` gives them a worker's turn and nobody
else's warm cache is emptied.
"""
import hashlib

import pytest

import rank_family as rf
from flexflow_tpu.models import nlp

BUILDER = {          # class name: its builder and the cell that names it
    "LatentMoEConfig": nlp.build_latent_moe,            # (the base class)
    "JoyAIFlashRankConfig": nlp.build_latent_moe,       # cell 3
    "KimiLinearRankConfig": nlp.build_latent_moe,       # cell 5
    "XingRankConfig": nlp.build_latent_moe,             # cell 6
    "HybridConvMoEConfig": nlp.build_hybrid_conv_moe,   # (the base class)
    "LFM2RankConfig": nlp.build_hybrid_conv_moe,        # cell 4
    "KeyeRankConfig": nlp.build_hybrid_conv_moe,        # cell 7
    "TrinityRankConfig": nlp.build_hybrid_conv_moe,     # cell 8
    "GraniteHybridRankConfig": nlp.build_hybrid_conv_moe,   # cell 9
    "Qwen3NextRankConfig": nlp.build_hybrid_conv_moe,   # cell 10
    "Phi4FlashRankConfig": nlp.build_hybrid_conv_moe,   # cell 11
    "SDARRankConfig": nlp.build_hybrid_conv_moe,        # cell 12
    "NemotronHRankConfig": nlp.build_hybrid_conv_moe,   # cell 13
}

LOWERED = {
    # ``tests/test_ssm_hybrid.py::lowered_attention``: one grouped causal
    # attention layer without a scale, value and gradients, down each path
    ("attention layer", "flash"):
        "1f67df5a42a5ece58e9e596581abd5711f2707c9a791e5996e8aaacd1a16c7ea",
    ("attention layer", "xla"):
        "42694f5f039ec7b5be8f3bf3fb0ac4be16fa42807be7a53dd667a65aa9b407bb",
    ("GraniteHybridRankConfig", "none"):
        "d80aee0caff6db589728d4799a3ac93558c95a5ac19df9ddae0da01133a6d091",
    ("GraniteHybridRankConfig", "blocks"):
        "9dbe347ac12162413603559c0ec60f99a0aa4a3e54d05fe0ff80a8c96b1e96e9",
    ("HybridConvMoEConfig", "none"):
        "dfa0f7e24297d78957d8644dc8903deed572524d5efa93c3fb55d3801480e9e2",
    ("HybridConvMoEConfig", "blocks"):
        "1a59f59459b09a86bf6a264c81d21c9e94dbb10dc30121ee98ade9478e4d5412",
    ("JoyAIFlashRankConfig", "none"):
        "cdb4f55ef86e6572cc9f607644a0e6ca659664c999bb223ba5717ff0473404c7",
    ("JoyAIFlashRankConfig", "blocks"):
        "b60cef900927287072a1daacb2c7b189d6816245e4ceaf133ac55cf47e341049",
    ("KeyeRankConfig", "none"):
        "44a48df71f1629c5f5151615ce7cec20dc2b887d471db0501ed2daead1839016",
    ("KeyeRankConfig", "blocks"):
        "0d20f419bb84cdfb8951da276ea2479f1c690fabe605a2d669523c9d7fc86e65",
    ("KimiLinearRankConfig", "none"):
        "6dbfe7a9c289c7a9fb9be4120772e88d2073527aa1d0e64ae350d844e6968bb1",
    ("KimiLinearRankConfig", "blocks"):
        "cba9000de8746dfce1a2cca52294f8b4f08c9417ac725d4ba2c6d8f6dd608d03",
    ("LFM2RankConfig", "none"):
        "8f8128eed419125269ed485da3d88775a0ccb11bd8354bd7f5239e17fbd5c65a",
    ("LFM2RankConfig", "blocks"):
        "e39329f73030ad79e44711e72529ab8beb79297855b13b4672e1d90e91bc44cc",
    ("LatentMoEConfig", "none"):
        "1ea7f47cd760026f9b0bf390fc61c4f929c761cb23873c57e4fd383c92e9ab38",
    ("LatentMoEConfig", "blocks"):
        "ebe5b056861101bf03d389f0a0a87b9ba94ff3ab8c13135eb2be6bb91922e3d1",
    ("NemotronHRankConfig", "none"):
        "1049d9d563572cad10ee7aec0362a6e53b00f49cc31749e94109d4495d1f21cc",
    ("NemotronHRankConfig", "blocks"):
        "3cde0d0463900cf91b1a28a52ff3966a4637200659746d0e4eb79b66817cd35f",
    ("Phi4FlashRankConfig", "none"):
        "8a6c7e2c5faf315891702659868813abcc0088093e0307ad59c12f4275cf8f65",
    ("Phi4FlashRankConfig", "blocks"):
        "f399127f3e6867ae5b2e09f0aa78d7b74ce9c6cbf323391b3d2725a239bb0452",
    ("Qwen3NextRankConfig", "none"):
        "6ae1f7bc1a602372feec80a028ef319c4313df79ef0dc76c116b5e2ddbeba336",
    ("Qwen3NextRankConfig", "blocks"):
        "75213f2e760c85ba401dc4d7b94af0ca803596dd8603717014c37d19e5087721",
    ("SDARRankConfig", "none"):
        "8a4e467e6eb009c46b51c360a745350850688561966f6b2c1e9bd944ae881047",
    ("SDARRankConfig", "blocks"):
        "559ef78378bc594fc9af5b72d7df47717e2ce99d0e2b44fe3e14440581731f1b",
    ("TrinityRankConfig", "none"):
        "708cf492ac0352eff099c0761b8f98dcc72db3ac033f5d898799227c50ac6696",
    ("TrinityRankConfig", "blocks"):
        "5a64243ea76f334805bbe7f542884c20a3b5d4465e302e224d2f2934d7f96b58",
    ("XingRankConfig", "none"):
        "5bcfba02c974b900704518f59fbe730bf5f3598ccd3de49a98e798df659d8488",
    ("XingRankConfig", "blocks"):
        "c0feb6265391f7b9f074e1784e0224456d34f4cef09cdab6c300c76bc0f0ea7a",
}


@pytest.mark.parametrize("remat", ["none", "blocks"])
@pytest.mark.parametrize("name", sorted(BUILDER))
def test_the_step_lowers_as_at_the_parent(name, remat):
    mc = getattr(nlp, name).tiny()
    text = rf.lowered_step(mc, BUILDER[name], remat)
    got = hashlib.sha256(text.encode()).hexdigest()
    assert got == LOWERED.get((name, remat)), \
        f'\n    ("{name}", "{remat}"):\n        "{got}",'
