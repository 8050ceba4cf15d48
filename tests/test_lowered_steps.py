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
new hash (PR 67 replaced the twenty-two with a routed-experts layer:
``route()``'s gather and the sizes' scatter-add became a compare and a
sum). This file's cases clear JAX's caches, so they stay a file of
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
        "a9ad53f00df6a1a7741a400ff45a941468977dff354632fd96975c1be5200416",
    ("HybridConvMoEConfig", "blocks"):
        "7a60dea107852e01d172fb40934f17424639e1c35f29d5d649cfb2e7c77540e9",
    ("JoyAIFlashRankConfig", "none"):
        "e9ecbfdaf13f9f8ca25f16947ea0728c8fda6250c6b28ddf3d67b4f7c62c433f",
    ("JoyAIFlashRankConfig", "blocks"):
        "fd6048f80b86f8aa15244a00b40f7562a888480f0618a2d99f383a322baf2855",
    ("KeyeRankConfig", "none"):
        "f66b1a7bd8f40e244e98696c7b6a9fbc9a6856e000ebc256602926162c9abb80",
    ("KeyeRankConfig", "blocks"):
        "d4fafc03534ebba625538b3ac782f6337230c48a5ba7044b9d94b2605905ba45",
    ("KimiLinearRankConfig", "none"):
        "7fa66593f9ac8500b9eaf6c65a858025e2f4e602e5de9304f94a74eace1ca9fa",
    ("KimiLinearRankConfig", "blocks"):
        "9342c3407dc551152fdcf8a403c40b39076242336c33634615456368c4ead52c",
    ("LFM2RankConfig", "none"):
        "f9fbadd1d2b91c3433b1e031a231f7220377192ddae0a209ef1a3eab4d4e7c6e",
    ("LFM2RankConfig", "blocks"):
        "4603f5e794dc85199e86c65229f0fef0fdc00c5d9dc7f9c6706d92c89cfadc51",
    ("LatentMoEConfig", "none"):
        "aea1c063b9beb3ed6c9766a441337d4f85bfa01a83209d945baebbc070c64438",
    ("LatentMoEConfig", "blocks"):
        "6c8d1eb9c627d9bf8ae109596db830c4ea512c4653bbcae86270e895a071c851",
    ("NemotronHRankConfig", "none"):
        "beb1c044b840ffd2965c0a31ebbccd75129747b8e18a043f9ae7a84fd08d6b13",
    ("NemotronHRankConfig", "blocks"):
        "6ff6ac38c000ab4f79dd11f08901954c84101c397ca1918b4c01bb41342a9fa0",
    ("Phi4FlashRankConfig", "none"):
        "8a6c7e2c5faf315891702659868813abcc0088093e0307ad59c12f4275cf8f65",
    ("Phi4FlashRankConfig", "blocks"):
        "f399127f3e6867ae5b2e09f0aa78d7b74ce9c6cbf323391b3d2725a239bb0452",
    ("Qwen3NextRankConfig", "none"):
        "9ddcbfa8406ec65170b673e5561a8c6b31cbc0f2950217c8a847ca2881e23309",
    ("Qwen3NextRankConfig", "blocks"):
        "74e14437d90e1ad2cfeaaa8984083118678b12839e703bd39ee8276566735958",
    ("SDARRankConfig", "none"):
        "9c0d79516b760d797ee8442e8948e8142ff7f4049964883dc403e15f6e7a4b28",
    ("SDARRankConfig", "blocks"):
        "6854672cea79c054f82f1b5cd64d9c90611651c2dd967bd374cc654ba2fc4621",
    ("TrinityRankConfig", "none"):
        "b25c75a7714285166e64a4be21fa874136bbe71b2cc43ac71bbd366d7d5c4d57",
    ("TrinityRankConfig", "blocks"):
        "dcf665e7a77a4c22337fe6255a70bb368e62841d4952edefefa9aaa201866cdf",
    ("XingRankConfig", "none"):
        "8f36ad5009291ff5ba542c9ec68b0a2861a055e40a9baadcd631d7705bdcf9be",
    ("XingRankConfig", "blocks"):
        "6a70761031559ddb0a99ce9c3ef8740c4bfca6a437701eb4f8796f692a4abefa",
}


@pytest.mark.parametrize("remat", ["none", "blocks"])
@pytest.mark.parametrize("name", sorted(BUILDER))
def test_the_step_lowers_as_at_the_parent(name, remat):
    mc = getattr(nlp, name).tiny()
    text = rf.lowered_step(mc, BUILDER[name], remat)
    got = hashlib.sha256(text.encode()).hexdigest()
    assert got == LOWERED.get((name, remat)), \
        f'\n    ("{name}", "{remat}"):\n        "{got}",'
