"""chip_smoke.py's legs at tiny widths on the CPU mesh.

The smoke itself only means something on a TPU, and chip time is too
scarce to debug its plumbing there: these tests run the same functions
(compile with the search on, fit, the placement and program checks,
KV-cache generation against re-forward, fused Adam against the plain
update) on the 8-virtual-device CPU mesh, where the chip-only checks are
off and the Pallas kernels run in interpret mode.
"""
import dataclasses
import os
import subprocess
import sys

import pytest

import chip_smoke
from flexflow_tpu.models.nlp import (BertConfig, GPTConfig,
                                     GraniteHybridRankConfig,
                                     HybridConvMoEConfig, KeyeRankConfig,
                                     KimiLinearRankConfig, LatentMoEConfig,
                                     NemotronHRankConfig,
                                     Phi4FlashRankConfig,
                                     Qwen3NextRankConfig, SDARRankConfig,
                                     TrinityRankConfig, XingRankConfig)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _small_run(monkeypatch):
    monkeypatch.setattr(chip_smoke, "SEARCH_BUDGET", 2)
    monkeypatch.setattr(chip_smoke, "TRAIN_STEPS", 2)
    monkeypatch.setattr(chip_smoke, "PROMPT_LEN", 8)
    monkeypatch.setattr(chip_smoke, "NEW_TOKENS", 4)


def _one_layer(cfg):
    """Tier-1 pays for compiles, not for depth."""
    return dataclasses.replace(cfg, num_layers=1)


def test_leg_a_bert_tiny_on_the_cpu_mesh(capsys):
    # (BERT-large's step size moves a 64-wide model nowhere in 3 steps)
    chip_smoke.leg_bert_train(_one_layer(BertConfig.tiny()), seq=16,
                              per_chip_batch=1, alpha=1e-3)
    out = capsys.readouterr().out
    assert "A/bert: mesh {'x0': 2, 'x1': 2, 'x2': 2}" in out
    assert "floor guard {'skipped': " in out       # cpu: says so
    assert "collectives ['all-reduce'" in out
    # cpu: the rule keeps `auto` on XLA, and the leg holds the step to it
    assert "A/bert: resolved attention impl ['xla']" in out
    assert "the rule alone gives xla" in out


def test_leg_b_gpt2_tiny_on_the_cpu_mesh(capsys):
    n = chip_smoke.leg_gpt2_kernels(_one_layer(GPTConfig.tiny()), seq=16,
                                    per_chip_batch=1)
    out = capsys.readouterr().out
    assert n == 0                                   # cpu: interpret mode
    assert "B/gpt2: resolved attention impl ['xla']" in out   # cpu: auto
    assert "B/generate: 4 tokens after a 8-token prompt" in out
    assert "kernel plan none" in out                # nothing forced


def test_leg_c_latent_moe_tiny_on_the_cpu_mesh(capsys):
    """A share of 4 of 16 experts on the 8-device mesh: the two expert
    layers are the rematerialised run, each announces its routing."""
    cfg = dataclasses.replace(LatentMoEConfig.tiny(), n_routed_experts=4,
                              n_routed_experts_published=16)
    chip_smoke.leg_latent_moe(cfg, seq=16, per_chip_batch=1,
                              label="C/small", alpha=1e-3)
    out = capsys.readouterr().out
    assert "rematerialised run (12, 6, 2) keeps 0 outputs" in out
    assert "resolved attention impls ['xla'] in 4 layers" in out  # cpu
    for layer in ("experts_1", "experts_2", "experts_mtp"):
        assert (f"moe.route {layer}: 4 of 16 experts held from 0, top 4, "
                f"128 tokens, 512 rows of the 512 sorted") in out
    assert "moe.dropped 0.0, moe.overflow 0.0" in out
    assert "rows_budget [512] a layer" in out
    # what this leg cannot see is named, and the named script exists
    assert f"python3 {chip_smoke.VALIDATION}" in out
    assert os.path.isfile(os.path.join(
        os.path.dirname(chip_smoke.__file__), chip_smoke.VALIDATION))


def test_leg_d_hybrid_conv_moe_tiny_on_the_cpu_mesh(capsys):
    """A share of 4 of 16 experts on the 8-device mesh: the layers
    announce themselves by kind in ``layer_types``' order, the run of
    [expert feed-forward, next convolution] blocks is rematerialised."""
    cfg = dataclasses.replace(HybridConvMoEConfig.tiny(), num_experts=4,
                              num_experts_published=16)
    chip_smoke.leg_hybrid_conv_moe(cfg, seq=16, per_chip_batch=1,
                                   label="D/small", alpha=1e-3)
    out = capsys.readouterr().out
    assert "rematerialised run (15, 6, 3) keeps 0 outputs" in out
    assert ("conv.short ['conv_0', 'conv_2', 'conv_3', 'conv_4']; "
            "attn.qk_norm ['attn_1']; moe.route ['experts_1', 'experts_2',"
            " 'experts_3', 'experts_4']") in out
    assert "resolved attention impls ['xla'] in 1 layers" in out  # cpu
    assert "moe.dropped 0.0, moe.overflow 0.0" in out
    assert "rows_budget [512] a layer" in out
    assert f"python3 {chip_smoke.VALIDATION_HYBRID}" in out
    assert os.path.isfile(os.path.join(
        os.path.dirname(chip_smoke.__file__), chip_smoke.VALIDATION_HYBRID))


def test_leg_e_linear_latent_moe_tiny_on_the_cpu_mesh(capsys):
    """A share of 4 of 16 experts on the 8-device mesh: the layers
    announce themselves by kind as the two lists say, the scans are
    counted, the two whole layers that repeat are rematerialised."""
    cfg = dataclasses.replace(KimiLinearRankConfig.tiny(), num_experts=4,
                              num_experts_published=16)
    chip_smoke.leg_linear_latent_moe(cfg, seq=16, per_chip_batch=1,
                                     label="E/small", alpha=1e-3)
    out = capsys.readouterr().out
    # float32 (8, 16, 64): the two linear-attention layers' outputs
    assert ("rematerialised run (12, 6, 2) keeps 2 outputs, 0.1 MB"
            in out)
    assert ("kda.scan ['kda_0', 'kda_1', 'kda_2', 'kda_4']; attn.latent "
            "['attn_3']; moe.route ['experts_1', 'experts_2', 'experts_3',"
            " 'experts_4']") in out
    assert ("kda.scan 4 heads of 8 behind 4 taps, 128 tokens in 1 chunks "
            "of 64") in out
    assert "counters kda.scans 12.0, kda.log_decay_min -" in out
    assert "resolved attention impls ['xla'] in 1 layers" in out  # cpu
    assert "moe.dropped 0.0, moe.overflow 0.0" in out
    assert f"python3 {chip_smoke.VALIDATION_LINEAR}" in out
    assert os.path.isfile(os.path.join(
        os.path.dirname(chip_smoke.__file__), chip_smoke.VALIDATION_LINEAR))


@pytest.mark.parametrize("hidden,impl", [(64, "plain"), (128, "kernel")])
def test_leg_f_mhc_latent_moe_tiny_on_the_cpu_mesh(capsys, hidden, impl):
    """A share of 4 of 16 experts on the 8-device mesh: every sub-layer
    announces its maps and the path its mixes took (the plain functions
    at 64 channels; at 128 the four kernels, interpreted here and under
    ``shard_map`` over the mesh's batch axis), the two whole layers that
    repeat are entered by the stream tensor and rematerialised, every
    sub-layer is counted in every step."""
    cfg = dataclasses.replace(XingRankConfig.tiny(), n_routed_experts=4,
                              n_routed_experts_published=16,
                              hidden_size=hidden)
    chip_smoke.leg_mhc_latent_moe(cfg, seq=16, per_chip_batch=1,
                                  label="F/small", alpha=1e-3)
    out = capsys.readouterr().out
    assert "rematerialised run (16, 8, 2) keeps 0 outputs" in out
    assert (f"mhc.maps in 8 sub-layers: 4 streams of {hidden}, 20 "
            f"iterations, 128 tokens") in out
    assert f"the streams' mixes by ['{impl}'] (the shapes say {impl})" in out
    for kernel in ("pre_fwd", "post_fwd", "post_bwd", "pre_bwd"):
        assert (f"mhc.kernel {kernel}: 1 grid steps of 16 tokens" in out) \
            == (impl == "kernel")
    # (a kernel is a Mosaic call in a step compiled for the chip only)
    assert "0 of the step's Mosaic calls are the hyper-connection" in out
    assert f"a rematerialised block is entered by (8, 16, 4, {hidden})" \
        in out
    assert f"counters mhc.sublayers {8.0 * (1 + chip_smoke.TRAIN_STEPS)}, " \
           f"mhc.clamped 0.0, mhc.sum_err " in out
    assert "resolved attention impls ['xla'] in 4 layers" in out  # cpu
    assert "moe.dropped 0.0, moe.overflow 0.0" in out
    assert f"python3 {chip_smoke.VALIDATION_MHC}" in out
    assert os.path.isfile(os.path.join(
        os.path.dirname(chip_smoke.__file__), chip_smoke.VALIDATION_MHC))


def test_leg_g_sparse_index_moe_tiny_on_the_cpu_mesh(capsys):
    """A share of 4 of 16 experts on the 8-device mesh, 32 positions
    with 24 keys a query in chunks of 16: the four equal layers are the
    rematerialised run, each block keeps its attention layer, whose
    alignment loss leaves the block as an output."""
    cfg = dataclasses.replace(KeyeRankConfig.tiny(), num_experts=4,
                              num_local_experts=4,
                              num_experts_published=16)
    chip_smoke.leg_sparse_index_moe(cfg, seq=32, per_chip_batch=1,
                                    label="G/small", alpha=1e-3)
    out = capsys.readouterr().out
    layers = "['attn_0', 'attn_1', 'attn_2', 'attn_3']"
    assert (f"in {layers}; resolved ['xla']; the rematerialised run "
            f"(1, 6, 4) keeps {layers}") in out
    assert "'topk': 24, 'q_chunk': 16, 'chunks': 2, 'selecting': True" in out
    want = sum(min(t + 1, 24) for t in range(32)) / (32 * 33 / 2)
    assert f"kept {want:.6f} of the causal pairs" in out
    assert "moe.dropped 0.0, moe.overflow 0.0" in out
    assert f"python3 {chip_smoke.VALIDATION_SPARSE}" in out
    assert os.path.isfile(os.path.join(
        os.path.dirname(chip_smoke.__file__), chip_smoke.VALIDATION_SPARSE))


def test_leg_h_window_gated_moe_tiny_on_the_cpu_mesh(capsys):
    """A share of 4 of 16 experts on the 8-device mesh, 32 positions
    under a window of 24: the four expert layers, whose attention layers
    differ (window, full, window, window), are the rematerialised run;
    the counters give the band's share and the gate's mean."""
    cfg = dataclasses.replace(TrinityRankConfig.tiny(), num_experts=4,
                              num_experts_published=16)
    chip_smoke.leg_window_gated_moe(cfg, seq=32, per_chip_batch=1,
                                    label="H/small", alpha=1e-3)
    out = capsys.readouterr().out
    layers = "['attn_0', 'attn_1', 'attn_2', 'attn_3', 'attn_4']"
    assert "rematerialised run (15, 8, 4) keeps 0 outputs" in out
    assert (f"attn.qk_norm {layers}; moe.route ['experts_1', 'experts_2', "
            f"'experts_3', 'experts_4']; resolved ['xla'] in 5 layers") in out
    want = sum(min(t + 1, 24) for t in range(32)) / (32 * 33 / 2)
    assert f"keeps {want:.6f} of the causal pairs ({want:.6f} by count)" \
        in out
    assert "moe.dropped 0.0, moe.overflow 0.0" in out
    assert f"python3 {chip_smoke.VALIDATION_WINDOW}" in out
    assert os.path.isfile(os.path.join(
        os.path.dirname(chip_smoke.__file__), chip_smoke.VALIDATION_WINDOW))


def test_leg_i_ssm_hybrid_tiny_on_the_cpu_mesh(capsys):
    """32 positions in two chunks of 16 on the 8-device mesh: the six
    layers (mamba x3, attention, mamba x2) are six rematerialised
    blocks, the five mixers' outputs kept, every mixer and the scaled
    attention layer announced."""
    chip_smoke.leg_ssm_hybrid(GraniteHybridRankConfig.tiny(), seq=32,
                              per_chip_batch=1, label="I/small", alpha=1e-3)
    out = capsys.readouterr().out
    assert "rematerialised run (2, 13, 6) keeps 5 outputs" in out
    assert ("ssm.layer ['mamba_0', 'mamba_1', 'mamba_2', 'mamba_4', "
            "'mamba_5']; attn.sm_scale ['attn_3']; resolved ['xla'] in 1 "
            "layers") in out
    assert "2 chunks of 16; a layer's most negative whole-chunk log-decay -" \
        in out
    assert f"python3 {chip_smoke.VALIDATION_SSM}" in out
    assert os.path.isfile(os.path.join(
        os.path.dirname(chip_smoke.__file__), chip_smoke.VALIDATION_SSM))


def test_leg_j_gdn_gated_moe_tiny_on_the_cpu_mesh(capsys):
    """32 positions on the 8-device mesh: the four layers (linear x3,
    full) are four rematerialised blocks, the three linear layers'
    outputs kept, every linear layer, the partial turn and the gated
    shared experts announced."""
    chip_smoke.leg_gdn_gated_moe(Qwen3NextRankConfig.tiny(), seq=32,
                                 per_chip_batch=1, label="J/small",
                                 alpha=1e-3)
    out = capsys.readouterr().out
    assert "rematerialised run (1, 6, 4) keeps 3 outputs" in out
    assert ("gdn.scan ['linear_attn_0', 'linear_attn_1', 'linear_attn_2']; "
            "attn.qk_norm ['attn_3']; moe.route ['experts_0', 'experts_1', "
            "'experts_2', 'experts_3']; resolved ['xla'] in 1 layers") in out
    assert "J/small: the chunks' terms by ['plain'] (the shapes say plain)" \
        in out and "gdn.kernel" not in out
    assert "a layer's most negative in-chunk log-decay -" in out
    assert "moe.dropped 0.0, moe.overflow 0.0" in out
    assert f"python3 {chip_smoke.VALIDATION_GDN}" in out
    assert os.path.isfile(os.path.join(
        os.path.dirname(chip_smoke.__file__), chip_smoke.VALIDATION_GDN))


def test_leg_k_sambay_tiny_on_the_cpu_mesh(capsys):
    """32 positions on the 8-device mesh: the six layers of six kinds
    are six rematerialised blocks of their own lengths, the scan's
    output and the keys and values handed across their edges, every
    scan and differential layer announced."""
    chip_smoke.leg_sambay(Phi4FlashRankConfig.tiny(), seq=32,
                          per_chip_batch=1, label="K/small", alpha=1e-3)
    out = capsys.readouterr().out
    assert ("rematerialised run (1, (11, 11, 11, 11, 15, 11), 6) hands on "
            "2 layers' outputs") in out
    assert ("ssm1.scan ['ssm_0', 'ssm_2']; attn.diff ['attn_1', 'attn_3', "
            "'attn_5']; resolved ['xla'] in 3 layers") in out
    assert "a scan's most negative dt A -" in out and "lambda 0.7" in out
    assert f"python3 {chip_smoke.VALIDATION_SAMBAY}" in out
    assert os.path.isfile(os.path.join(
        os.path.dirname(chip_smoke.__file__), chip_smoke.VALIDATION_SAMBAY))


def test_leg_l_block_diffusion_tiny_on_the_cpu_mesh(capsys):
    """32 tokens (64 positions) on the 8-device mesh: three
    rematerialised blocks, the noising op drawing from the step's key in
    training and the configuration's in eval, the mask in every layer
    (off the kernels here: every pair computed), the loss weighted."""
    chip_smoke.leg_block_diffusion(SDARRankConfig.tiny(), seq=32,
                                   per_chip_batch=1, label="L/small",
                                   alpha=1e-3)
    out = capsys.readouterr().out
    assert "rematerialised run (5, 6, 3)" in out
    assert ("the noising op drew from ['eval', 'step']; the mask in 3 "
            "layers by ['xla']; the loss weighted over [256] rows") in out
    assert "the kernels' grids visit 1.0000 of the square" in out
    assert f"python3 {chip_smoke.VALIDATION_BLOCK_DIFFUSION}" in out
    assert os.path.isfile(os.path.join(
        os.path.dirname(chip_smoke.__file__),
        chip_smoke.VALIDATION_BLOCK_DIFFUSION))


def test_leg_m_latent_experts_hybrid_tiny_on_the_cpu_mesh(capsys):
    """32 positions on the 8-device mesh: ``EMEM*`` is two
    rematerialised [moe, mamba] blocks (the attention layer held whole),
    both mixers' outputs kept, every mixer and every expert layer
    announced, nothing dropped."""
    chip_smoke.leg_latent_experts_hybrid(
        NemotronHRankConfig.tiny(), seq=32, per_chip_batch=1,
        label="M/small", alpha=1e-3)
    out = capsys.readouterr().out
    assert "rematerialised run (1, 6, 2) keeps 2 outputs" in out
    assert ("ssm.layer ['mamba_1', 'mamba_3']; moe.route ['experts_0', "
            "'experts_2']; scan ['plain'], token sum ['plain']") in out
    assert "moe.dropped 0.0, moe.overflow 0.0" in out
    assert f"python3 {chip_smoke.VALIDATION_NEMOTRON_H}" in out
    assert os.path.isfile(os.path.join(
        os.path.dirname(chip_smoke.__file__),
        chip_smoke.VALIDATION_NEMOTRON_H))


@pytest.mark.parametrize("seq", [128, 512, 1024, 2048, 4096])
def test_leg_l_bound_on_the_visited_share_is_the_grids_count(seq):
    """What leg L allows the kernels' grids on a chip is what
    ``grid_steps`` counts at the tiles the shapes derive (bf16, heads of
    128): the diagonal tiles as their sub-blocks."""
    import jax.numpy as jnp

    from flexflow_tpu.kernels.flash_attention import (
        BD_SUB, block_diffusion_visited)
    visited = block_diffusion_visited(8, seq, 4, 128, jnp.bfloat16, 2)
    want = sum(visited.values()) / (3 * 8 * (2 * seq) ** 2)
    assert chip_smoke._bd_walked_share(seq) == want
    assert want <= 0.75 and (seq > BD_SUB) == (want < 0.75)
    if seq == 4096:             # the cell's
        assert want == (20 + 4 * BD_SUB / 1024) / 64


def test_a_loss_that_does_not_fall_fails_the_smoke(monkeypatch):
    class Stuck:
        def fit(self, **kw):
            return [{"loss": 0.7, "epoch_time_s": 0.0}] * 3

    with pytest.raises(chip_smoke.SmokeFailure, match="did not fall"):
        chip_smoke._fit(Stuck(), None, None, "t")


def test_main_refuses_to_run_without_a_tpu():
    """On the CPU platform it exits non-zero before building a model and
    prints no result line."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode not in (0, None)
    assert "no TPU" in r.stderr
    assert r.stdout.strip() == ""
