"""NLP model zoo: Transformer encoder, BERT, GPT-2.

Reference parity: ``examples/cpp/Transformer/transformer.cc`` (encoder
stack); BERT/GPT come through the torch.fx frontend in the reference —
here they're also available natively, configured to the standard published
sizes (BERT-large: 24 layers, hidden 1024, heads 16; GPT-2 sizes per
https://openai.com 124M/355M/774M/1.5B).
"""
from __future__ import annotations

import dataclasses
import math

from ..ffconst import ActiMode, AggrMode, DataType
from ..model import FFModel


@dataclasses.dataclass
class TransformerConfig:
    """Reference ``transformer.cc`` TransformerConfig defaults."""
    hidden_size: int = 512
    embedding_size: int = 512
    num_heads: int = 8
    num_layers: int = 6
    sequence_length: int = 512


def create_attention_encoder(ff: FFModel, input, hidden_dim: int,
                             num_heads: int, kdim: int, vdim: int):
    """One encoder layer exactly as reference ``transformer.cc:33-45``:
    MHA followed by two dense layers, no residual/LN (the reference
    example omits them)."""
    t = ff.multihead_attention(input, input, input, hidden_dim, num_heads,
                               kdim, vdim)
    return ff.dense(ff.dense(t, hidden_dim, ActiMode.AC_MODE_RELU,
                             use_bias=False),
                    hidden_dim, ActiMode.AC_MODE_NONE, use_bias=False)


def build_transformer(ff: FFModel, batch_size: int,
                      cfg: TransformerConfig | None = None):
    """Reference Transformer benchmark model (``transformer.cc:135-158``):
    encoder stack on (B, L, H) input, final dense(1), MSE loss."""
    cfg = cfg or TransformerConfig()
    x = ff.create_tensor((batch_size, cfg.sequence_length, cfg.hidden_size),
                         name="input")
    t = x
    for _ in range(cfg.num_layers):
        t = create_attention_encoder(ff, t, cfg.hidden_size, cfg.num_heads,
                                     cfg.hidden_size // cfg.num_heads,
                                     cfg.hidden_size // cfg.num_heads)
    return ff.dense(t, 1, ActiMode.AC_MODE_NONE, use_bias=False)


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 1024        # BERT-large
    num_layers: int = 24
    num_heads: int = 16
    intermediate_size: int = 4096
    max_position: int = 512
    type_vocab_size: int = 2
    dropout: float = 0.1
    num_labels: int = 2

    @classmethod
    def base(cls):
        return cls(hidden_size=768, num_layers=12, num_heads=12,
                   intermediate_size=3072)

    @classmethod
    def tiny(cls):
        """For tests/compile checks."""
        return cls(vocab_size=1024, hidden_size=64, num_layers=2,
                   num_heads=4, intermediate_size=128, max_position=64)


def _bert_layer(ff: FFModel, t, cfg: BertConfig, causal: bool = False):
    attn = ff.multihead_attention(t, t, t, cfg.hidden_size, cfg.num_heads,
                                  dropout=cfg.dropout, causal=causal)
    t = ff.layer_norm(ff.add(t, ff.dropout(attn, cfg.dropout)),
                      [-1])
    ffn = ff.dense(t, cfg.intermediate_size, ActiMode.AC_MODE_GELU)
    ffn = ff.dense(ffn, cfg.hidden_size)
    return ff.layer_norm(ff.add(t, ff.dropout(ffn, cfg.dropout)), [-1])


def build_bert(ff: FFModel, batch_size: int, seq_len: int,
               cfg: BertConfig | None = None, classifier: bool = True):
    """BERT encoder (token ids → pooled classification logits).

    Post-LN encoder per the original architecture; embeddings = word +
    position (+ segment omitted when ids not given).
    """
    cfg = cfg or BertConfig()
    ids = ff.create_tensor((batch_size, seq_len), DataType.DT_INT32,
                           name="input_ids")
    pos = ff.create_tensor((batch_size, seq_len), DataType.DT_INT32,
                           name="position_ids")
    tok = ff.embedding(ids, cfg.vocab_size, cfg.hidden_size,
                       AggrMode.AGGR_MODE_NONE, name="word_embeddings")
    pe = ff.embedding(pos, cfg.max_position, cfg.hidden_size,
                      AggrMode.AGGR_MODE_NONE, name="position_embeddings")
    t = ff.layer_norm(ff.add(tok, pe), [-1])
    t = ff.dropout(t, cfg.dropout)
    for _ in range(cfg.num_layers):
        t = _bert_layer(ff, t, cfg)
    if not classifier:
        return t
    # pooler: first-token representation → dense tanh → classifier
    cls_tok = ff.reshape(ff.slice_tensor(t, starts=[0], ends=[1], axes=[1]),
                         (batch_size, cfg.hidden_size))
    pooled = ff.dense(cls_tok, cfg.hidden_size, ActiMode.AC_MODE_TANH)
    logits = ff.dense(pooled, cfg.num_labels)
    return ff.softmax(logits)


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_position: int = 1024
    dropout: float = 0.0

    @classmethod
    def gpt2_xl(cls):
        return cls(hidden_size=1600, num_layers=48, num_heads=25)

    @classmethod
    def gpt2_medium(cls):
        return cls(hidden_size=1024, num_layers=24, num_heads=16)

    @classmethod
    def tiny(cls):
        return cls(vocab_size=512, hidden_size=64, num_layers=2,
                   num_heads=4, max_position=128)


def build_gpt2(ff: FFModel, batch_size: int, seq_len: int,
               cfg: GPTConfig | None = None):
    """GPT-2 decoder-only LM: pre-LN blocks, causal attention, tied-untied
    LM head (untied dense here), softmax over vocab."""
    cfg = cfg or GPTConfig()
    ids = ff.create_tensor((batch_size, seq_len), DataType.DT_INT32,
                           name="input_ids")
    pos = ff.create_tensor((batch_size, seq_len), DataType.DT_INT32,
                           name="position_ids")
    tok = ff.embedding(ids, cfg.vocab_size, cfg.hidden_size,
                       name="wte")
    pe = ff.embedding(pos, cfg.max_position, cfg.hidden_size, name="wpe")
    t = ff.dropout(ff.add(tok, pe), cfg.dropout)
    for _ in range(cfg.num_layers):
        h = ff.layer_norm(t, [-1])
        attn = ff.multihead_attention(h, h, h, cfg.hidden_size,
                                      cfg.num_heads, dropout=cfg.dropout,
                                      causal=True)
        t = ff.add(t, attn)
        h = ff.layer_norm(t, [-1])
        ffn = ff.dense(h, 4 * cfg.hidden_size, ActiMode.AC_MODE_GELU)
        ffn = ff.dense(ffn, cfg.hidden_size)
        t = ff.add(t, ffn)
    t = ff.layer_norm(t, [-1])
    logits = ff.dense(t, cfg.vocab_size, use_bias=False, name="lm_head")
    return ff.softmax(logits)


@dataclasses.dataclass
class NMTConfig:
    """LSTM seq2seq with attention (reference legacy ``nmt/`` app:
    embed -> stacked LSTM encoder/decoder -> attention -> softmax,
    ``nmt/nmt.cc``/``lstm.cu``)."""
    src_vocab: int = 32000
    tgt_vocab: int = 32000
    embed_dim: int = 512
    hidden_size: int = 512
    num_layers: int = 2
    num_heads: int = 1           # attention over encoder states


def build_nmt(ff: FFModel, batch_size: int, src_len: int, tgt_len: int,
              cfg: NMTConfig | None = None):
    """Teacher-forcing NMT: encoder LSTM over the source, decoder LSTM
    over the (shifted) target, decoder attends to encoder states, dense
    projects to the target vocabulary. Returns (b, tgt_len, tgt_vocab)
    logits; train with sparse CE against the gold target."""
    cfg = cfg or NMTConfig()
    src = ff.create_tensor((batch_size, src_len), dtype=DataType.DT_INT32,
                           name="src_ids")
    tgt = ff.create_tensor((batch_size, tgt_len), dtype=DataType.DT_INT32,
                           name="tgt_ids")
    enc = ff.embedding(src, cfg.src_vocab, cfg.embed_dim,
                       AggrMode.AGGR_MODE_NONE, name="src_embed")
    enc = ff.lstm(enc, cfg.hidden_size, cfg.num_layers, name="encoder")
    dec = ff.embedding(tgt, cfg.tgt_vocab, cfg.embed_dim,
                       AggrMode.AGGR_MODE_NONE, name="tgt_embed")
    dec = ff.lstm(dec, cfg.hidden_size, cfg.num_layers, name="decoder")
    # attention readout over encoder states (the nmt app's per-step
    # attention, batched over all decoder positions)
    ctx = ff.multihead_attention(dec, enc, enc, cfg.hidden_size,
                                 cfg.num_heads, name="attention")
    h = ff.add(dec, ctx, name="attn_residual")
    return ff.dense(h, cfg.tgt_vocab, ActiMode.AC_MODE_NONE,
                    name="vocab_proj")


@dataclasses.dataclass
class LlamaConfig:
    """LLaMA-family decoder (RMSNorm, SwiGLU, rotary embeddings) — built
    from framework primitives (rms_norm / dense / batch_matmul / rotate
    via slice+concat), no special attention op. TPU-native addition:
    the reference predates this family."""
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    # grouped-query attention (LLaMA-2-70B/LLaMA-3 family); 0 = MHA.
    # Only the fused_attention build consumes this (the primitive form
    # predates GQA, like the reference).
    num_kv_heads: int = 0
    # Mistral-family sliding-window attention; 0 = full causal.
    # fused_attention only.
    sliding_window: int = 0
    # Qwen2-family q/k/v projection biases (o_proj stays bias-free in
    # those checkpoints; the fused op's bo is simply zero).
    # fused_attention only.
    attention_bias: bool = False
    max_position: int = 2048
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6

    @classmethod
    def tiny(cls):
        return cls(vocab_size=96, hidden_size=32, intermediate_size=64,
                   num_layers=2, num_heads=4, max_position=64)


def _rope_tables(seq_len: int, head_dim: int, theta: float):
    import numpy as np
    inv = 1.0 / theta ** (np.arange(0, head_dim, 2) / head_dim)
    freqs = np.outer(np.arange(seq_len), inv)          # (s, d/2)
    emb = np.concatenate([freqs, freqs], axis=-1)      # (s, d) half-split
    shape = (1, 1, seq_len, head_dim)
    return (np.cos(emb).reshape(shape).astype(np.float32),
            np.sin(emb).reshape(shape).astype(np.float32))


def build_llama(ff: FFModel, batch_size: int, seq_len: int,
                cfg: LlamaConfig | None = None, lm_head: bool = True,
                fused_attention: bool = False):
    """Causal LM: (b, s) token ids -> (b, s, vocab) logits (or final
    hidden states when ``lm_head=False``). HF weight layout compatible
    (q/k/v/o + gate/up/down per layer, half-split rotate RoPE).

    ``fused_attention=True`` builds each attention block as ONE
    OP_MULTIHEAD_ATTENTION with in-op RoPE instead of the primitive
    dense/batch_matmul/softmax form — same math, but eligible for the
    Pallas flash kernel and KV-cache incremental decode (the primitive
    form carries seq-length-baked mask/rope constants a length-1 decode
    trace cannot satisfy). Convert primitive-layout weights with
    ``llama_fuse_params``."""
    import math
    import numpy as np
    cfg = cfg or LlamaConfig()
    b, s = batch_size, seq_len
    nh = cfg.num_heads
    hd = cfg.hidden_size // nh

    ids = ff.create_tensor((b, s), DataType.DT_INT32, name="input_ids")
    h = ff.embedding(ids, cfg.vocab_size, cfg.hidden_size,
                     AggrMode.AGGR_MODE_NONE, name="embed_tokens")

    def mlp_block(h, i):
        """SwiGLU MLP + residual — shared by both attention forms (the
        layer names are llama_fuse_params' pass-through contract)."""
        x2 = ff.rms_norm(h, eps=cfg.rms_eps, name=f"post_norm_{i}")
        gate = ff.dense(x2, cfg.intermediate_size, use_bias=False,
                        name=f"gate_proj_{i}")
        up = ff.dense(x2, cfg.intermediate_size, use_bias=False,
                      name=f"up_proj_{i}")
        silu = ff.multiply(gate, ff.sigmoid(gate), name=f"silu_{i}")
        down = ff.dense(ff.multiply(silu, up), cfg.hidden_size,
                        use_bias=False, name=f"down_proj_{i}")
        return ff.add(h, down, name=f"mlp_res_{i}")

    def head(h):
        h = ff.rms_norm(h, eps=cfg.rms_eps, name="final_norm")
        if not lm_head:
            return h
        # final softmax so the executor fuses CE-on-logits (the stable
        # loss path engages on OP_SOFTMAX outputs, executor.py; same
        # convention as build_gpt2/build_bert)
        return ff.softmax(ff.dense(h, cfg.vocab_size, use_bias=False,
                                   name="lm_head"))

    if fused_attention:
        for i in range(cfg.num_layers):
            x = ff.rms_norm(h, eps=cfg.rms_eps, name=f"input_norm_{i}")
            attn_out = ff.multihead_attention(
                x, x, x, cfg.hidden_size, nh,
                bias=cfg.attention_bias, causal=True,
                rope=True, rope_theta=cfg.rope_theta,
                num_kv_heads=cfg.num_kv_heads,
                sliding_window=cfg.sliding_window, name=f"attn_{i}")
            h = ff.add(h, attn_out, name=f"attn_res_{i}")
            h = mlp_block(h, i)
        return head(h)

    if cfg.sliding_window or cfg.attention_bias \
            or cfg.num_kv_heads not in (0, nh):
        raise ValueError(
            "sliding_window/GQA/attention_bias need "
            "fused_attention=True — the primitive build predates them "
            "and would silently compute plain full MHA")
    cos_np, sin_np = _rope_tables(s, hd, cfg.rope_theta)
    cos_t = ff.create_tensor(cos_np.shape, create_grad=False,
                             name="rope_cos")
    cos_t.set_tensor(cos_np)
    sin_t = ff.create_tensor(sin_np.shape, create_grad=False,
                             name="rope_sin")
    sin_t.set_tensor(sin_np)
    mask_np = np.triu(np.full((1, 1, s, s), -1e9, np.float32), 1)
    mask_t = ff.create_tensor(mask_np.shape, create_grad=False,
                              name="causal_mask")
    mask_t.set_tensor(mask_np)

    def heads(x, name):
        # (b, s, H) -> (b, nh, s, hd)
        return ff.transpose(ff.reshape(x, (b, s, nh, hd),
                                       name=f"{name}_split"),
                            (0, 2, 1, 3), name=f"{name}_t")

    def rope(x, name):
        x1 = ff.slice_tensor(x, [0], [hd // 2], [3], name=f"{name}_lo")
        x2 = ff.slice_tensor(x, [hd // 2], [hd], [3], name=f"{name}_hi")
        rot = ff.concat([ff.scalar_multiply(x2, -1.0), x1], axis=-1,
                        name=f"{name}_rot")
        return ff.add(ff.multiply(x, cos_t), ff.multiply(rot, sin_t),
                      name=f"{name}_rope")

    for i in range(cfg.num_layers):
        x = ff.rms_norm(h, eps=cfg.rms_eps, name=f"input_norm_{i}")
        q = rope(heads(ff.dense(x, cfg.hidden_size, use_bias=False,
                                name=f"q_proj_{i}"), f"q{i}"), f"q{i}")
        k = rope(heads(ff.dense(x, cfg.hidden_size, use_bias=False,
                                name=f"k_proj_{i}"), f"k{i}"), f"k{i}")
        v = heads(ff.dense(x, cfg.hidden_size, use_bias=False,
                           name=f"v_proj_{i}"), f"v{i}")
        kt = ff.transpose(k, (0, 1, 3, 2), name=f"kT_{i}")
        scores = ff.scalar_multiply(
            ff.batch_matmul(q, kt, name=f"qk_{i}"), 1.0 / math.sqrt(hd))
        probs = ff.softmax(ff.add(scores, mask_t), axis=-1,
                           name=f"probs_{i}")
        ctx = ff.batch_matmul(probs, v, name=f"ctx_{i}")
        merged = ff.reshape(ff.transpose(ctx, (0, 2, 1, 3)),
                            (b, s, cfg.hidden_size), name=f"merge_{i}")
        attn_out = ff.dense(merged, cfg.hidden_size, use_bias=False,
                            name=f"o_proj_{i}")
        h = ff.add(h, attn_out, name=f"attn_res_{i}")
        h = mlp_block(h, i)

    return head(h)


def _fuse_qkvo(q, k, v, o, e, nh, kvh):
    """Shared (in, out)-kernel -> fused-attention reshapes: wq/wk/wv
    (e, heads, hd), wo (nh, hd, e). The single reshape convention for
    both llama_fuse_params and the HF state-dict loader."""
    hd = e // nh
    return {"wq": q.reshape(e, nh, hd),
            "wk": k.reshape(e, kvh, hd),
            "wv": v.reshape(e, kvh, hd),
            "wo": o.reshape(nh, hd, e)}


def llama_fuse_params(params, cfg: LlamaConfig):
    """Convert primitive-layout LLaMA params (``build_llama`` default:
    ``q_proj_{i}``/``k_proj_{i}``/``v_proj_{i}``/``o_proj_{i}`` dense
    kernels, the HF import layout) into the fused-attention layout
    (``attn_{i}``: wq/wk/wv (e, h, d), wo (h, d, e)). Non-attention
    entries (norms, FFN, embeddings, lm_head) share names and pass
    through unchanged — so HF-imported weights can serve through the
    flash/KV-decode path."""
    import numpy as np
    if cfg.num_kv_heads not in (0, cfg.num_heads):
        raise ValueError(
            "llama_fuse_params converts the MHA primitive layout; a "
            "GQA target (num_kv_heads < num_heads) has no primitive "
            "source — load GQA checkpoints into the fused layout "
            "directly")
    nh = cfg.num_heads
    e = cfg.hidden_size
    hd = e // nh
    out = {}
    fused = {}
    for i in range(cfg.num_layers):
        fused[f"attn_{i}"] = _fuse_qkvo(
            np.asarray(params[f"q_proj_{i}"]["kernel"]),
            np.asarray(params[f"k_proj_{i}"]["kernel"]),
            np.asarray(params[f"v_proj_{i}"]["kernel"]),
            np.asarray(params[f"o_proj_{i}"]["kernel"]), e, nh, nh)
    skip = {f"{p}_proj_{i}" for i in range(cfg.num_layers)
            for p in ("q", "k", "v", "o")}
    for name, leaf in params.items():
        if name not in skip:
            out[name] = leaf
    out.update(fused)
    return out


def llama_load_hf_state_dict(state_dict, cfg: LlamaConfig,
                             fused: bool = False):
    """Map a HuggingFace ``LlamaForCausalLM`` state dict onto
    ``build_llama``'s parameter layout (primitive by default; ``fused``
    produces the fused-attention layout, required for GQA checkpoints
    where num_kv_heads < num_heads). HF stores Linear weights as
    (out, in); dense kernels here are (in, out). RoPE carries no
    weights in either convention, so the mapping is purely structural.

    Values may be torch tensors (CPU) or arrays. Returns the params
    dict for ``FFModel.params`` (numpy leaves; device placement happens
    on first use)."""
    import numpy as np

    def _np(v):
        try:
            return np.asarray(v)
        except Exception:
            # bf16 torch tensors have no numpy dtype — upcast (params
            # here are fp32 masters anyway)
            return v.detach().cpu().float().numpy()

    nh = cfg.num_heads
    e = cfg.hidden_size
    hd = e // nh
    kvh = cfg.num_kv_heads or nh
    if (kvh != nh or cfg.attention_bias) and not fused:
        raise ValueError("GQA / attention-bias checkpoints need "
                         "fused=True (the primitive build is plain "
                         "bias-free MHA)")
    sd = {k: _np(v) for k, v in state_dict.items()}
    consumed = set()

    def take(key):
        consumed.add(key)
        return sd[key]

    # tie_word_embeddings checkpoints (Llama-3.2-1B/3B class) omit
    # lm_head.weight — the head shares the embedding matrix
    if "lm_head.weight" in sd:
        lm_w = take("lm_head.weight")
    else:
        lm_w = sd["model.embed_tokens.weight"]
    params = {
        "embed_tokens": {"kernel": take("model.embed_tokens.weight")},
        "final_norm": {"scale": take("model.norm.weight")},
        "lm_head": {"kernel": lm_w.T},
    }
    if params["embed_tokens"]["kernel"].shape[1] != e:
        raise ValueError(
            f"embed_tokens kernel shape "
            f"{params['embed_tokens']['kernel'].shape} does not match "
            f"hidden_size {e}")
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        params[f"input_norm_{i}"] = {
            "scale": take(p + "input_layernorm.weight")}
        params[f"post_norm_{i}"] = {
            "scale": take(p + "post_attention_layernorm.weight")}
        for proj in ("gate", "up", "down"):
            params[f"{proj}_proj_{i}"] = {
                "kernel": take(p + f"mlp.{proj}_proj.weight").T}
        q = take(p + "self_attn.q_proj.weight").T      # (e, nh*hd)
        k = take(p + "self_attn.k_proj.weight").T      # (e, kvh*hd)
        v = take(p + "self_attn.v_proj.weight").T
        o = take(p + "self_attn.o_proj.weight").T      # (nh*hd, e)
        if q.shape != (e, nh * hd) or k.shape != (e, kvh * hd):
            raise ValueError(
                f"checkpoint/config head mismatch: q {q.shape} "
                f"k {k.shape} vs (e={e}, nh={nh}, kvh={kvh}, hd={hd})")
        if fused:
            attn = _fuse_qkvo(q, k, v, o, e, nh, kvh)
            if cfg.attention_bias:
                # Qwen2 family: q/k/v carry biases, o_proj does not —
                # the fused op's bo is present but zero
                attn["bq"] = take(
                    p + "self_attn.q_proj.bias").reshape(nh, hd)
                attn["bk"] = take(
                    p + "self_attn.k_proj.bias").reshape(kvh, hd)
                attn["bv"] = take(
                    p + "self_attn.v_proj.bias").reshape(kvh, hd)
                attn["bo"] = np.zeros((e,), attn["wq"].dtype)
            params[f"attn_{i}"] = attn
        else:
            params[f"q_proj_{i}"] = {"kernel": q}
            params[f"k_proj_{i}"] = {"kernel": k}
            params[f"v_proj_{i}"] = {"kernel": v}
            params[f"o_proj_{i}"] = {"kernel": o}
    # every checkpoint tensor must have been mapped (buffers like the
    # legacy rotary inv_freq are recomputed in-op and safely skipped);
    # silently dropping weights (attention biases, extra layers) would
    # produce wrong numerics with no signal
    leftover = [k_ for k_ in sd
                if k_ not in consumed and "rotary_emb" not in k_]
    if leftover:
        raise ValueError(
            f"unmapped checkpoint tensors {sorted(leftover)[:8]}"
            f"{'...' if len(leftover) > 8 else ''} — config/architecture "
            f"mismatch (attention_bias / num_layers / tied embeddings?)")
    return params


@dataclasses.dataclass
class MixtralConfig:
    """Mixtral-family sparse-MoE decoder (Mistral backbone: fused
    attention + GQA + RoPE, FFN replaced by a top-k mixture of SwiGLU
    experts). Beyond-reference: the reference's MoE (moe.cc) is the
    2017 classification MoE, not an LM block."""
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    num_experts: int = 8
    experts_per_tok: int = 2
    max_position: int = 2048
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    # Mistral-backbone sliding window (HF MixtralConfig defaults 4096);
    # 0 = full causal
    sliding_window: int = 0

    @classmethod
    def tiny(cls):
        return cls(vocab_size=96, hidden_size=32, intermediate_size=64,
                   num_layers=2, num_heads=4, num_kv_heads=2,
                   num_experts=4, experts_per_tok=2, max_position=64)


def build_mixtral(ff: FFModel, batch_size: int, seq_len: int,
                  cfg: MixtralConfig | None = None,
                  lm_head: bool = True):
    """Mixtral decoder as a dense mixture: every expert computes, each
    token weights the top-k experts by its renormalized router probs
    (HF MixtralSparseMoeBlock semantics exactly — parity-tested against
    transformers). For sparse dispatch at scale use the MoE op family
    (group_by/aggregate) with expert_parallel_strategy; the dense form
    is exact, serving-friendly, and KV-decode eligible."""
    cfg = cfg or MixtralConfig()
    b, s = batch_size, seq_len
    E, k = cfg.num_experts, cfg.experts_per_tok

    ids = ff.create_tensor((b, s), DataType.DT_INT32, name="input_ids")
    h = ff.embedding(ids, cfg.vocab_size, cfg.hidden_size,
                     AggrMode.AGGR_MODE_NONE, name="embed_tokens")
    # one constant id per expert, shared by every layer's routing mask
    expert_sel = [ff.create_constant((1,), float(e_i), DataType.DT_INT32)
                  for e_i in range(E)]

    for i in range(cfg.num_layers):
        x = ff.rms_norm(h, eps=cfg.rms_eps, name=f"input_norm_{i}")
        attn_out = ff.multihead_attention(
            x, x, x, cfg.hidden_size, cfg.num_heads, bias=False,
            causal=True, rope=True, rope_theta=cfg.rope_theta,
            num_kv_heads=cfg.num_kv_heads,
            sliding_window=cfg.sliding_window, name=f"attn_{i}")
        h = ff.add(h, attn_out, name=f"attn_res_{i}")

        x2 = ff.rms_norm(h, eps=cfg.rms_eps, name=f"post_norm_{i}")
        router = ff.dense(x2, E, use_bias=False, name=f"moe_gate_{i}")
        probs = ff.softmax(router, axis=-1, name=f"moe_probs_{i}")
        vals, idx = ff.top_k(probs, k, True, name=f"moe_topk_{i}")
        denom = ff.reduce_sum(vals, [-1], keepdims=True,
                              name=f"moe_denom_{i}")
        moe_out = None
        for e_i in range(E):
            m = ff.cast(ff.equal(idx, expert_sel[e_i],
                                 name=f"moe_eq_{i}_{e_i}"),
                        DataType.DT_FLOAT, name=f"moe_m_{i}_{e_i}")
            w = ff.divide(
                ff.reduce_sum(ff.multiply(vals, m), [-1], keepdims=True,
                              name=f"moe_w_{i}_{e_i}"),
                denom, name=f"moe_wn_{i}_{e_i}")
            gate = ff.dense(x2, cfg.intermediate_size, use_bias=False,
                            name=f"e{e_i}_w1_{i}")
            up = ff.dense(x2, cfg.intermediate_size, use_bias=False,
                          name=f"e{e_i}_w3_{i}")
            act = ff.multiply(ff.multiply(gate, ff.sigmoid(gate)), up,
                              name=f"moe_act_{i}_{e_i}")
            down = ff.dense(act, cfg.hidden_size, use_bias=False,
                            name=f"e{e_i}_w2_{i}")
            contrib = ff.multiply(down, w, name=f"moe_c_{i}_{e_i}")
            moe_out = contrib if moe_out is None else \
                ff.add(moe_out, contrib, name=f"moe_sum_{i}_{e_i}")
        h = ff.add(h, moe_out, name=f"mlp_res_{i}")

    h = ff.rms_norm(h, eps=cfg.rms_eps, name="final_norm")
    if not lm_head:
        return h
    return ff.softmax(ff.dense(h, cfg.vocab_size, use_bias=False,
                               name="lm_head"))


def mixtral_load_hf_state_dict(state_dict, cfg: MixtralConfig):
    """Map a HuggingFace ``MixtralForCausalLM`` state dict onto
    ``build_mixtral``'s layout (attention via the shared fused
    reshapes; experts w1/w2/w3 -> e{e}_w1/w2/w3 kernels)."""
    import numpy as np

    def _np(v):
        try:
            return np.asarray(v)
        except Exception:
            return v.detach().cpu().float().numpy()

    nh, e = cfg.num_heads, cfg.hidden_size
    hd = e // nh
    kvh = cfg.num_kv_heads or nh
    sd = {k_: _np(v) for k_, v in state_dict.items()}
    consumed = set()

    def take(key):
        consumed.add(key)
        return sd[key]

    if "lm_head.weight" in sd:
        lm_w = take("lm_head.weight")
    else:
        lm_w = sd["model.embed_tokens.weight"]
    params = {
        "embed_tokens": {"kernel": take("model.embed_tokens.weight")},
        "final_norm": {"scale": take("model.norm.weight")},
        "lm_head": {"kernel": lm_w.T},
    }
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        params[f"input_norm_{i}"] = {
            "scale": take(p + "input_layernorm.weight")}
        params[f"post_norm_{i}"] = {
            "scale": take(p + "post_attention_layernorm.weight")}
        q = take(p + "self_attn.q_proj.weight").T
        k = take(p + "self_attn.k_proj.weight").T
        if q.shape != (e, nh * hd) or k.shape != (e, kvh * hd):
            raise ValueError(
                f"checkpoint/config head mismatch: q {q.shape} "
                f"k {k.shape} vs (e={e}, nh={nh}, kvh={kvh}, hd={hd})")
        params[f"attn_{i}"] = _fuse_qkvo(
            q, k,
            take(p + "self_attn.v_proj.weight").T,
            take(p + "self_attn.o_proj.weight").T, e, nh, kvh)
        params[f"moe_gate_{i}"] = {
            "kernel": take(p + "block_sparse_moe.gate.weight").T}
        for x in range(cfg.num_experts):
            ep = p + f"block_sparse_moe.experts.{x}."
            params[f"e{x}_w1_{i}"] = {"kernel": take(ep + "w1.weight").T}
            params[f"e{x}_w2_{i}"] = {"kernel": take(ep + "w2.weight").T}
            params[f"e{x}_w3_{i}"] = {"kernel": take(ep + "w3.weight").T}
    leftover = [k_ for k_ in sd
                if k_ not in consumed and "rotary_emb" not in k_]
    if leftover:
        raise ValueError(f"unmapped checkpoint tensors "
                         f"{sorted(leftover)[:8]} — config mismatch")
    return params


@dataclasses.dataclass
class LatentMoEConfig:
    """DeepSeek-V3-shaped decoder: latent attention in every layer,
    ``first_k_dense_replace`` leading SwiGLU layers and then sparse
    routed experts with a shared one, ``num_nextn_predict_layers``
    (0 or 1) multi-token-prediction modules. The fields carry the names
    of the published ``config.json`` keys; the defaults are
    JoyAI-LLM-Flash's (``model_type: joyai_llm_flash``).

    ``n_routed_experts`` counts the experts whose weights are HELD here,
    ``first_held_expert`` onwards; the router, the top-k and the gates'
    normalisation run over ``n_routed_experts_published`` (None: the
    same, every expert is held). A device of an expert-parallel layer
    holds its share and computes its own experts' part of the result.

    ``q_lora_rank`` None gives latent attention one full query
    projection. :func:`build_latent_moe` reads three fields more where a
    subclass has them (:class:`KimiLinearRankConfig`), two of them keys
    of ``model_type: kimi_linear``. ``linear_attn_config`` (absent or
    None: every layer's operator is latent attention) names, counting
    layers from 1, the layers whose operator is a gated delta-rule
    linear-attention layer (``kda_layers``, of ``num_heads`` heads of
    ``head_dim`` behind convolutions of ``short_conv_kernel_size`` taps)
    and those that keep latent attention (``full_attn_layers``);
    ``mla_use_nope`` (absent: False) leaves the rotary embedding out of
    latent attention; ``expert_rows_factor`` (absent: 2) is an expert
    layer's row budget in uniform shares.

    Six more where a subclass has them (:class:`XingRankConfig`), keys
    of ``model_type: xing4_0``. ``rope_scaling`` (absent or None: plain
    frequencies) is a ``type: "yarn"`` group for latent attention's
    rotary embedding. ``hc_mult`` (absent or 1: a layer's output is
    ADDED to one residual stream) is the number of residual streams of
    manifold-constrained hyper-connections: every attention and
    feed-forward then reads ``Hpre X`` and is written back as ``Hres X +
    Hpost^T F`` (``ops.hyper_ops``), with ``hc_sinkhorn_iters``,
    ``hc_eps``, ``mhc_h_res_clamp_min`` and ``mhc_h_res_clamp_max``."""
    vocab_size: int = 129280
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    q_lora_rank: int | None = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32000000.0
    rms_norm_eps: float = 1e-6
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    n_routed_experts: int = 256
    n_routed_experts_published: int | None = None
    first_held_expert: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    num_nextn_predict_layers: int = 1
    # not in config.json: DeepSeek-V3's weight of the MTP loss late in
    # training (arXiv:2412.19437 section 4.2), and the spread of the
    # routers' correction bias, which is drawn once and never trained
    mtp_loss_weight: float = 0.3
    router_bias_std: float = 0.02

    @classmethod
    def tiny(cls):
        """3 + 1 layers, 4 heads of 24 / 16, 16 experts top-4: tests."""
        return cls(vocab_size=96, hidden_size=64, num_hidden_layers=3,
                   num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
                   qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                   rope_theta=10000.0, intermediate_size=160,
                   moe_intermediate_size=32, n_routed_experts=16,
                   num_experts_per_tok=4, router_bias_std=0.05)


@dataclasses.dataclass
class JoyAIFlashRankConfig(LatentMoEConfig):
    """What ONE chip holds of JoyAI-LLM-Flash where 16 chips share each
    layer (the benchmark's ``joyai_llm_flash``): experts 0 to 15 of the
    256, one of eight slices of the vocabulary, and the leading dense
    layer with four of the 39 expert layers (the rest lie on further
    chips as pipeline stages); every width as published."""
    vocab_size: int = 16160
    num_hidden_layers: int = 5
    n_routed_experts: int = 16
    n_routed_experts_published: int | None = 256


@dataclasses.dataclass
class KimiLinearRankConfig(LatentMoEConfig):
    """What ONE chip holds of Kimi-Linear-48B-A3B where 32 chips share
    each layer (the benchmark's ``kimi_linear_48b_a3b``): experts 0 to 7
    of the 256, one of eight slices of the vocabulary, and published
    layer 1 (the dense one) with layers 2 to 5 (one whole period: three
    linear-attention layers around one latent-attention layer; the rest
    lie on further chips as pipeline stages); every width as published.

    ``model_type: kimi_linear`` names four of the parent's fields
    otherwise; they are taken under its names and copied over."""
    vocab_size: int = 20480
    hidden_size: int = 2304
    num_hidden_layers: int = 5
    q_lora_rank: int | None = None
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    routed_scaling_factor: float = 2.446
    num_nextn_predict_layers: int = 0
    # the two keys the parent class does not have (its docstring)
    linear_attn_config: dict | None = dataclasses.field(
        default_factory=lambda: {
            "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4],
            "num_heads": 32, "head_dim": 128, "short_conv_kernel_size": 4})
    mla_use_nope: bool = True
    num_experts: int = 8
    num_experts_published: int | None = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    # not in config.json: the row budget of an expert layer, in uniform
    # shares of the held experts (``RoutedExpertsOp.rows_multiplied``);
    # a 32nd of the experts is sent up to 3.4 times its share
    expert_rows_factor: int = 4

    def __post_init__(self):
        self.n_routed_experts = self.num_experts
        self.n_routed_experts_published = self.num_experts_published
        self.num_experts_per_tok = self.num_experts_per_token
        self.n_shared_experts = self.num_shared_experts

    @classmethod
    def tiny(cls):
        """The benchmark's layout at a small size: 4 linear-attention
        heads of 8 behind 4 taps, 4 latent heads of 16 + 8 / 16, 16
        experts top-4 and a shared one: tests."""
        return cls(vocab_size=96, hidden_size=64, num_attention_heads=4,
                   kv_lora_rank=32, qk_nope_head_dim=16,
                   qk_rope_head_dim=8, v_head_dim=16, intermediate_size=160,
                   moe_intermediate_size=32, router_bias_std=0.05,
                   linear_attn_config={
                       "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4],
                       "num_heads": 4, "head_dim": 8,
                       "short_conv_kernel_size": 4},
                   num_experts=16, num_experts_published=None,
                   num_experts_per_token=4)


def _xing_rope_scaling():
    return {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
            "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
            "type": "yarn"}


@dataclasses.dataclass
class XingRankConfig(LatentMoEConfig):
    """What ONE chip holds of Xing4.0-29B-A4B where 8 chips share each
    layer as one tensor- and expert-parallel group working on the same
    micro-batch (the benchmark's ``xing4_29b_a4b``): heads 0 to 3 of the
    32 (``wq_b``, ``wkv_b`` and ``wo`` by head; this chip's heads' part
    of the output projection is what goes on), experts 0 to 7 of the 64,
    one of eight slices of the vocabulary, and published layer 0 (a
    dense one) with layers 2 to 5 (the rest lie on further chips as
    pipeline stages); every width as published. The down-projections to
    the latents, the routers, the shared expert, the dense feed-forward
    and the hyper-connection maps are computed whole on every chip."""
    vocab_size: int = 16384
    hidden_size: int = 3584
    num_hidden_layers: int = 5
    first_k_dense_replace: int = 1
    num_attention_heads: int = 4
    q_lora_rank: int | None = 768
    rope_theta: float = 10000.0
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    n_routed_experts: int = 8
    n_routed_experts_published: int | None = 64
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    # the six keys the parent class does not have (its docstring)
    rope_scaling: dict | None = dataclasses.field(
        default_factory=_xing_rope_scaling)
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30
    mhc_h_res_clamp_max: float = 30

    @classmethod
    def tiny(cls):
        """The benchmark's layout at a small size: 3 + 1 layers of 4
        streams, 4 heads of 16 + 8 / 16 under YaRN with a factor of 4
        over 16 original positions, 16 experts top-4: tests."""
        return cls(vocab_size=96, hidden_size=64, num_hidden_layers=3,
                   q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                   qk_rope_head_dim=8, v_head_dim=16, intermediate_size=160,
                   moe_intermediate_size=32, n_routed_experts=16,
                   n_routed_experts_published=None, router_bias_std=0.05,
                   rope_scaling=dict(_xing_rope_scaling(), factor=4,
                                     original_max_position_embeddings=16,
                                     beta_fast=4))


def build_latent_moe(ff: FFModel, batch_size: int, seq_len: int,
                     cfg: LatentMoEConfig | None = None):
    """Causal LM of :class:`LatentMoEConfig`: inputs ``[ids, pos]``,
    output the softmax over the head (the executor's CE-on-logits path),
    as :func:`build_gpt2`; ``pos`` is what every layer's rotary
    embedding turns by. A layer's operator is latent attention, or,
    for the layers ``linear_attn_config`` names, a gated delta-rule
    linear-attention layer; its feed-forward does not depend on which.

    The residual rule is chosen once from ``hc_mult``. Absent or 1: a
    sub-layer's output is added to the one stream. ``n`` > 1: the
    embedding is copied to ``n`` streams (batch, seq, n, hidden), every
    sub-layer reads and writes them through its own maps
    (``FFModel.hyper_connection_pre`` / ``_post``), and the streams are
    summed before the final norm.

    The multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437
    section 2.2) predicts token ``t + 2`` from the trunk's last hidden
    state at ``t`` and the embedding of token ``t + 1`` through one more
    decoder layer, and adds ``mtp_loss_weight`` times its cross-entropy
    to the loss. It shares the trunk's embedding and output head: the
    graph has no weight shared by two layers, so the module reads the
    trunk's embedding output shifted by one position, and its final
    hidden state goes through the ONE head layer beside the trunk's
    (joined along the sequence, split again after). Under several
    streams it reads the SUMMED trunk state, copies ``h'`` to streams of
    its own and sums them after its layer."""
    cfg = cfg or LatentMoEConfig()
    if cfg.num_nextn_predict_layers not in (0, 1):
        raise ValueError("0 or 1 multi-token-prediction module")
    lin = getattr(cfg, "linear_attn_config", None)
    linear = set(lin["kda_layers"]) if lin else set()
    if lin:                             # layers are numbered from 1 there
        full = set(lin["full_attn_layers"])
        if linear & full or linear | full != set(
                range(1, cfg.num_hidden_layers + 1)):
            raise ValueError(
                f"kda_layers {sorted(linear)} and full_attn_layers "
                f"{sorted(full)} must name each of the layers 1 to "
                f"{cfg.num_hidden_layers} once")
    b, s, hid = batch_size, seq_len, cfg.hidden_size
    published = cfg.n_routed_experts_published or cfg.n_routed_experts
    ids = ff.create_tensor((b, s), DataType.DT_INT32, name="input_ids")
    pos = ff.create_tensor((b, s), DataType.DT_INT32, name="position_ids")
    emb = ff.embedding(ids, cfg.vocab_size, hid, name="embed_tokens")

    def norm(x, name):
        return ff.rms_norm(x, eps=cfg.rms_norm_eps, name=name)

    streams = getattr(cfg, "hc_mult", None) or 1

    def spread(x, name):                # a copy of x in each stream
        if streams == 1:
            return x
        return ff.concat([ff.unsqueeze(x, [2])] * streams, axis=2,
                         name=name)

    def gathered(x, name):
        return x if streams == 1 else ff.reduce_sum(x, [2], name=name)

    def residual(h, sublayer, name):
        """``h`` after one sub-layer: ``sublayer`` maps what it reads to
        what it adds."""
        if streams == 1:
            return ff.add(h, sublayer(h), name=name)
        u, maps, h = ff.hyper_connection_pre(
            h, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.rms_norm_eps,
            (cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max),
            name=name + "_pre")
        return ff.hyper_connection_post(h, sublayer(u), maps, name=name)

    def decoder_layer(h, tag, experts: bool, linear: bool = False):
        h = residual(h, lambda u: operator(u, tag, linear),
                     f"attn_res_{tag}")
        return residual(h, lambda u: feed_forward(u, tag, experts),
                        f"mlp_res_{tag}")

    def operator(h, tag, linear: bool):
        x = norm(h, f"input_norm_{tag}")
        if linear:
            attn = ff.gated_delta_rule(
                x, lin["num_heads"], lin["head_dim"],
                lin["short_conv_kernel_size"], eps=cfg.rms_norm_eps,
                name=f"kda_{tag}")
        else:
            attn = ff.latent_attention(
                x, pos, cfg.num_attention_heads,
                cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                cfg.qk_rope_head_dim, cfg.v_head_dim,
                rope_theta=cfg.rope_theta, eps=cfg.rms_norm_eps,
                rope=not getattr(cfg, "mla_use_nope", False),
                rope_scaling=getattr(cfg, "rope_scaling", None),
                name=f"attn_{tag}")
        return attn

    def feed_forward(h, tag, experts: bool):
        x = norm(h, f"post_norm_{tag}")
        if experts:
            y = ff.routed_experts(
                x, published, cfg.num_experts_per_tok,
                cfg.moe_intermediate_size,
                shared_dim=cfg.n_shared_experts * cfg.moe_intermediate_size,
                experts_held=cfg.n_routed_experts,
                first_held=cfg.first_held_expert,
                scale=cfg.routed_scaling_factor,
                bias_std=cfg.router_bias_std,
                rows_factor=getattr(cfg, "expert_rows_factor", 2),
                name=f"experts_{tag}")
        else:
            gate = ff.dense(x, cfg.intermediate_size, use_bias=False,
                            name=f"gate_proj_{tag}")
            up = ff.dense(x, cfg.intermediate_size, use_bias=False,
                          name=f"up_proj_{tag}")
            silu = ff.multiply(gate, ff.sigmoid(gate), name=f"silu_{tag}")
            y = ff.dense(ff.multiply(silu, up), hid, use_bias=False,
                         name=f"down_proj_{tag}")
        return y

    h = spread(emb, "streams")
    for i in range(cfg.num_hidden_layers):
        h = decoder_layer(h, str(i), i >= cfg.first_k_dense_replace,
                          i + 1 in linear)
    h = gathered(h, "streams_sum")
    out = norm(h, "final_norm")
    if not cfg.num_nextn_predict_layers:
        return ff.softmax(ff.dense(out, cfg.vocab_size, use_bias=False,
                                   name="lm_head"))

    # Emb(x_{t+1}): the trunk's embeddings one position on; the last
    # position has no next token (zeros there, and no target either)
    nxt = ff.concat([ff.slice_tensor(emb, [1], [s], [1]),
                     ff.create_constant((b, 1, hid), 0.0)], axis=1,
                    name="mtp_next_emb")
    joined = ff.concat([norm(nxt, "mtp_enorm"), norm(h, "mtp_hnorm")],
                       axis=-1, name="mtp_concat")
    hm = ff.dense(joined, hid, use_bias=False, name="mtp_eh_proj")
    hm = gathered(decoder_layer(spread(hm, "mtp_streams"), "mtp", True),
                  "mtp_streams_sum")
    hm = norm(hm, "mtp_final_norm")
    logits = ff.dense(ff.concat([out, hm], axis=1, name="head_in"),
                      cfg.vocab_size, use_bias=False, name="lm_head")
    ff.next_token_loss(ff.slice_tensor(logits, [s], [2 * s], [1]), ids,
                       offset=2, weight=cfg.mtp_loss_weight,
                       name="mtp_loss")
    return ff.softmax(ff.slice_tensor(logits, [0], [s], [1],
                                      name="lm_logits"))


_SAMBAY_KINDS = frozenset({
    "mamba1", "mamba1_memory", "diff_sliding_attention",
    "diff_attention_kv", "gated_memory", "diff_cross_attention"})


def _lfm2_layer_types():
    """LFM2-24B-A2B's 40 operators: conv, conv, then [full_attention,
    conv, conv, conv] ten times less the last two (30 conv, 10
    attention)."""
    return (["conv", "conv"]
            + ["full_attention", "conv", "conv", "conv"] * 10)[:40]


@dataclasses.dataclass
class HybridConvMoEConfig:
    """Hybrid convolution/attention decoder with sparse experts
    (``model_type: lfm2_moe``): every layer is an operator, a gated short
    convolution or grouped-query attention as ``layer_types`` says, and a
    feed-forward, SwiGLU in the first ``num_dense_layers`` and then
    sigmoid-routed experts with no shared one. The fields carry the names
    of the published ``config.json`` keys; the defaults are
    LFM2-24B-A2B's.

    ``num_experts`` counts the experts whose weights are HELD here,
    ``first_held_expert`` onwards; the router, the top-k and the gates'
    normalisation run over ``num_experts_published`` (None: the same),
    as in :class:`LatentMoEConfig`."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    layer_types: list = dataclasses.field(default_factory=_lfm2_layer_types)
    num_dense_layers: int = 2
    conv_L_cache: int = 3
    conv_bias: bool = False
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int | None = None          # not published: hidden / heads
    rope_parameters: dict = dataclasses.field(
        default_factory=lambda: {"rope_theta": 1000000.0,
                                 "rope_type": "default"})
    norm_eps: float = 1e-5
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_experts: int = 64
    num_experts_published: int | None = None
    first_held_expert: int = 0
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    # not in config.json: the spread of the routers' bias, which is
    # drawn once and never trained
    router_bias_std: float = 0.02

    @classmethod
    def tiny(cls):
        """5 layers laid out as the benchmark's cut (a dense conv layer,
        then one period of expert layers), 4 heads on 2 kv heads of 16,
        16 experts top-4: tests."""
        return cls(vocab_size=96, hidden_size=64, num_hidden_layers=5,
                   layer_types=["conv", "full_attention", "conv", "conv",
                                "conv"],
                   num_dense_layers=1, num_attention_heads=4,
                   num_key_value_heads=2,
                   rope_parameters={"rope_theta": 10000.0,
                                    "rope_type": "default"},
                   intermediate_size=160, moe_intermediate_size=32,
                   num_experts=16, num_experts_per_tok=4,
                   router_bias_std=0.05)


@dataclasses.dataclass
class LFM2RankConfig(HybridConvMoEConfig):
    """What ONE chip holds of LFM2-24B-A2B where 8 chips share each layer
    (the benchmark's ``lfm2_24b_a2b``): experts 0 to 7 of the 64, one of
    eight slices of the vocabulary, and published layer 0 with layers 2
    to 5 (one dense layer, then one whole period of expert layers; the
    rest lie on further chips as pipeline stages); every width as
    published."""
    vocab_size: int = 8192
    num_hidden_layers: int = 5
    layer_types: list = dataclasses.field(
        default_factory=lambda: ["conv", "full_attention", "conv", "conv",
                                 "conv"])
    num_dense_layers: int = 1
    head_dim: int | None = 64            # assumed: hidden / heads
    num_experts: int = 8
    num_experts_published: int | None = 64


@dataclasses.dataclass
class KeyeRankConfig(HybridConvMoEConfig):
    """What ONE chip holds of Keye-VL-2.0-30B-A3B's language model
    (``model_type: KeyeVL2``) where 8 chips share each layer (the
    benchmark's ``keye_vl2_30b_a3b``): every layer is grouped-query
    attention (32 query heads on 4 key/value heads of 128, q/k norms,
    rotary embedding) over the ``sa_config["topk"]`` keys that a learned
    indexer selects for each query (``ops/sparse_attention``), then 128
    softmax-routed experts of 768, 8 a token, no shared expert and no
    choice bias. Here: experts 0 to 15, one of eight slices of the
    vocabulary and published layers 0 to 3 (the rest lie on further
    chips as pipeline stages); every width as published.

    ``config.json`` names three of the parent's fields otherwise
    (``rms_norm_eps``, ``rope_theta``, the expert count, which it gives
    twice); they are taken under its names and copied over. The step is
    text-only: the three position streams of ``rope_scaling``'s
    ``mrope_section`` are equal there and the rotary embedding is the
    ordinary one."""
    vocab_size: int = 18992
    num_hidden_layers: int = 4
    layer_types: list = dataclasses.field(
        default_factory=lambda: ["sparse_attention"] * 4)
    num_dense_layers: int = 0
    num_key_value_heads: int = 4
    head_dim: int | None = 128
    intermediate_size: int = 6144        # published; no dense layer reads it
    moe_intermediate_size: int = 768
    num_experts: int = 16
    num_experts_published: int | None = 128
    num_experts_per_tok: int = 8
    use_expert_bias: bool = False
    # the keys the parent class does not have
    num_local_experts: int = 16
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000000.0
    scoring_func: str = "softmax"        # not in config.json: the family's
    sa_config: dict = dataclasses.field(
        default_factory=lambda: {
            "indexer_head_dim": 64, "indexer_num_heads": 16,
            "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
            "q_chunk_size": 512, "topk": 2048})

    def __post_init__(self):
        if self.num_local_experts != self.num_experts \
                or self.sa_config["indexer_num_kv_heads"] != 1:
            raise ValueError(
                "num_local_experts repeats num_experts and the indexer "
                "has one key head")
        self.norm_eps = self.rms_norm_eps
        self.rope_parameters = {"rope_theta": self.rope_theta,
                                "rope_type": "default"}

    @classmethod
    def tiny(cls):
        """4 equal layers, 4 heads on 2 kv heads of 16, an indexer of 2
        heads of 8 that keeps 24 keys a query in chunks of 16 queries,
        16 experts top-4, all held: tests."""
        return cls(vocab_size=96, hidden_size=64, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=16, rope_theta=10000.0,
                   moe_intermediate_size=32, num_experts=16,
                   num_local_experts=16, num_experts_published=None,
                   num_experts_per_tok=4,
                   sa_config={"indexer_head_dim": 8, "indexer_num_heads": 2,
                              "indexer_num_kv_heads": 1,
                              "kv_chunk_size": 16, "q_chunk_size": 16,
                              "topk": 24})


@dataclasses.dataclass
class TrinityRankConfig(HybridConvMoEConfig):
    """What ONE chip holds of Trinity-Mini (``model_type: afmoe``, 26B
    parameters, 3B a token) where 8 chips share each layer (the
    benchmark's ``trinity_mini``): grouped-query attention (32 query
    heads on 4 key/value heads of 128, q/k norms) with a sigmoid output
    gate on every layer, three ``"sliding_attention"`` layers (the
    ``sliding_window`` keys that end with the query's own, rotary
    embedding) to one ``"full_attention"`` layer (every causal key, NO
    rotary embedding); four norms a layer, the two after the sub-layers
    inside the residual branch; the embedding scaled by sqrt(hidden);
    128 sigmoid-routed experts of 1024, 8 a token, beside a shared one.
    Here: experts 0 to 15, one of eight slices of the vocabulary, and
    published layer 0 (dense, window) with layers 2 to 5 (window, full,
    window, window: one whole period of expert layers); every width as
    published.

    ``config.json`` names six of the parent's fields otherwise
    (``rms_norm_eps``, ``rope_theta``, ``route_scale``, ``route_norm``,
    ``score_func``, the shared expert as a count); they are taken under
    its names and copied over. The three fields after them are forms of
    the model's published code that ``config.json`` has no key for."""
    vocab_size: int = 25024
    num_hidden_layers: int = 5
    layer_types: list = dataclasses.field(
        default_factory=lambda: ["sliding_attention", "sliding_attention",
                                 "full_attention", "sliding_attention",
                                 "sliding_attention"])
    num_dense_layers: int = 1
    num_key_value_heads: int = 4
    head_dim: int | None = 128
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_experts: int = 16
    num_experts_published: int | None = 128
    num_experts_per_tok: int = 8
    # the keys the parent class does not have
    sliding_window: int = 2048
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    route_scale: float = 2.826
    route_norm: bool = True
    score_func: str = "sigmoid"
    num_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    mup_enabled: bool = True             # the embedding times sqrt(hidden)
    # not in config.json: modeling_afmoe.py's forms
    attention_output_gate: bool = True   # o * sigmoid(x Wg), before Wo
    sandwich_norms: bool = True          # a norm after each sub-layer too
    full_attention_rope: bool = False    # NoPE on the full layers

    def __post_init__(self):
        if self.n_group != 1 or self.topk_group != 1 \
                or self.num_shared_experts not in (0, 1):
            raise ValueError("grouped routing and several shared experts "
                             "are not built")
        self.norm_eps = self.rms_norm_eps
        self.rope_parameters = {"rope_theta": self.rope_theta,
                                "rope_type": "default"}
        self.routed_scaling_factor = self.route_scale
        self.norm_topk_prob = self.route_norm

    @classmethod
    def tiny(cls):
        """The benchmark's layout (a dense window layer, then window,
        full, window, window with experts), 4 heads on 2 kv heads of 16,
        a window of 24, 16 experts top-4, all held: tests."""
        return cls(vocab_size=96, hidden_size=64, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=16, sliding_window=24,
                   intermediate_size=160, moe_intermediate_size=32,
                   num_experts=16, num_experts_published=None,
                   num_experts_per_tok=4, router_bias_std=0.05)


def _granite_layer_types():
    # granite-4.0-h-micro's first period: published layers 0 to 9
    return ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


@dataclasses.dataclass
class GraniteHybridRankConfig(HybridConvMoEConfig):
    """What ONE chip holds of Granite-4.0-H-Micro (``model_type:
    granitemoehybrid``, 3B parameters, dense) as stage 0 of a four-stage
    pipeline with the vocabulary in eight slices (the benchmark's
    ``granite_4_0_h_micro``): by ``layer_types`` nine ``"mamba"`` layers
    (a state-space mixer: ``mamba_n_heads`` heads of ``mamba_d_head``
    channels, a state of ``mamba_d_state``, one group, a convolution of
    ``mamba_d_conv`` taps with a bias, chunks of ``mamba_chunk_size``)
    to one ``"attention"`` layer (32 query heads on 8 key/value heads of
    64, NO rotary embedding, no q/k norm, scores times
    ``attention_multiplier`` and not ``1 / sqrt(64)``), a dense SwiGLU of
    ``shared_intermediate_size`` in EVERY layer and no routed expert;
    the embedding times ``embedding_multiplier``, each sub-layer's output
    times ``residual_multiplier`` before it is added, the logits divided
    by ``logits_scaling``. Here: published layers 0 to 9 (one whole
    period) and one of eight slices of the vocabulary; every width as
    published.

    The fields after the parent's carry ``config.json``'s keys by their
    names; ``rms_norm_eps`` and the MLP's width are copied over the
    parent's names for them, and the parent's expert fields read
    ``num_local_experts`` (0: every layer is dense)."""
    vocab_size: int = 12544
    num_hidden_layers: int = 10
    layer_types: list = dataclasses.field(
        default_factory=_granite_layer_types)
    num_dense_layers: int = 10
    head_dim: int | None = 64            # not published: hidden / heads
    intermediate_size: int = 8192
    moe_intermediate_size: int = 0       # no expert layer reads it
    num_experts: int = 0
    num_experts_per_tok: int = 0
    use_expert_bias: bool = False
    # the keys the parent class does not have
    num_local_experts: int = 0
    shared_intermediate_size: int = 8192
    rms_norm_eps: float = 1e-5
    position_embedding_type: str = "nope"
    attention_bias: bool = False
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_expand: int = 2
    mamba_d_conv: int = 4
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_chunk_size: int = 256

    def __post_init__(self):
        if self.num_local_experts or self.num_experts_per_tok \
                or self.position_embedding_type != "nope" \
                or self.attention_bias or self.mamba_proj_bias \
                or not self.mamba_conv_bias:
            raise ValueError(
                "routed experts, a rotary embedding, a bias in a "
                "projection and a convolution without one are not built "
                "for this family")
        if self.mamba_n_heads * self.mamba_d_head \
                != self.mamba_expand * self.hidden_size \
                or self.intermediate_size != self.shared_intermediate_size:
            raise ValueError(
                "mamba_n_heads x mamba_d_head is mamba_expand x "
                "hidden_size, and intermediate_size repeats "
                "shared_intermediate_size")
        self.norm_eps = self.rms_norm_eps
        self.num_experts = self.num_local_experts
        self.num_dense_layers = self.num_hidden_layers

    @classmethod
    def tiny(cls):
        """6 layers of both kinds (mamba x3, attention, mamba x2), 4
        state-space heads of 16 with a state of 8 in chunks of 16 (two
        chunks of a 32-token sequence), 4 attention heads on 2 kv heads
        of 16: tests."""
        return cls(vocab_size=96, hidden_size=32, num_hidden_layers=6,
                   layer_types=["mamba"] * 3 + ["attention"]
                   + ["mamba"] * 2,
                   num_attention_heads=4, num_key_value_heads=2,
                   head_dim=8, intermediate_size=64,
                   shared_intermediate_size=64, attention_multiplier=0.25,
                   mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8,
                   mamba_chunk_size=16)


@dataclasses.dataclass
class Qwen3NextRankConfig(HybridConvMoEConfig):
    """What ONE chip holds of Qwen3-Next-80B-A3B (``model_type:
    qwen3_next``, 80B parameters, 3B a token) where 16 chips share each
    layer (the benchmark's ``qwen3_next_80b_a3b``): layer ``i`` is
    ``"full_attention"`` where ``(i + 1) % full_attention_interval ==
    0`` and ``"linear_attention"`` otherwise. A linear layer is a gated
    delta rule with a decay a HEAD (Gated DeltaNet): ``linear_num_key_
    heads`` heads of q and k serve ``linear_num_value_heads`` heads of v,
    each through a convolution of ``linear_conv_kernel_dim`` taps, a
    full-rank SiLU gate on the normed output. A full layer is
    grouped-query attention (16 query heads on 2 key/value heads of 256)
    with zero-centred q/k norms, a rotary embedding over the first
    ``partial_rotary_factor`` of each head and a sigmoid output gate.
    Every layer has 512 softmax-routed experts of 512, 10 a token, gates
    normalised, beside a shared expert of ``shared_expert_intermediate_
    size`` times ``sigmoid(x . w_s)``. Every norm but the linear layer's
    gated one multiplies by ``1 + w``. Here: experts 0 to 31, one of
    eight slices of the vocabulary, and published layers 0 to 3 (one
    whole period); every width as published.

    The fields after the parent's carry ``config.json``'s keys by their
    names; ``rms_norm_eps`` and ``rope_theta`` are copied over the
    parent's names for them and ``layer_types`` is laid out from
    ``full_attention_interval``. The last three are forms of the
    model's published code that ``config.json`` has no key for."""
    vocab_size: int = 18992
    num_hidden_layers: int = 4
    layer_types: list | None = None      # from full_attention_interval
    num_dense_layers: int = 0
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int | None = 256
    intermediate_size: int = 5120        # published; no dense layer reads it
    moe_intermediate_size: int = 512
    num_experts: int = 32
    num_experts_published: int | None = 512
    num_experts_per_tok: int = 10
    use_expert_bias: bool = False
    # the keys the parent class does not have
    full_attention_interval: int = 4
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    partial_rotary_factor: float = 0.25
    shared_expert_intermediate_size: int = 512
    decoder_sparse_step: int = 1
    mlp_only_layers: list = dataclasses.field(default_factory=list)
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000000.0
    scoring_func: str = "softmax"        # not in config.json: the family's
    # not in config.json: modeling_qwen3_next.py's forms
    attention_output_gate: bool = True   # o * sigmoid(x Wg), before Wo
    zero_centered_norms: bool = True     # x / rms(x) * (1 + w), w from 0
    shared_expert_gate: bool = True      # E_shared(x) * sigmoid(x . w_s)
    # rows the experts' products are handed, in uniform shares of the
    # held experts: in training the routers learn within tens of steps to
    # prefer the experts whose output is not left out, and a layer's held
    # share of the assignments triples in 90 steps (PERF.md section 6, PR
    # 57); 2 shares, enough at a seed's weights, overflow from step 63
    expert_rows_factor: int = 6

    def __post_init__(self):
        if self.decoder_sparse_step != 1 or self.mlp_only_layers \
                or self.linear_key_head_dim != self.linear_value_head_dim:
            raise ValueError(
                "a layer without experts and linear-attention key heads of "
                "another size than the value heads' are not built for this "
                "family")
        kinds = ["full_attention" if (i + 1) % self.full_attention_interval
                 == 0 else "linear_attention"
                 for i in range(self.num_hidden_layers)]
        if self.layer_types is None:
            self.layer_types = kinds
        elif list(self.layer_types) != kinds:
            raise ValueError(f"layer_types {self.layer_types} are not "
                             f"full_attention_interval's {kinds}")
        self.norm_eps = self.rms_norm_eps
        self.rope_parameters = {"rope_theta": self.rope_theta,
                                "rope_type": "default"}

    @classmethod
    def tiny(cls):
        """One period (linear x 3, full): 2 key heads under 4 value
        heads of 16, 4 attention heads on 2 kv heads of 16 of which 4
        entries turn, 16 experts top-4 of width 32, all held: tests."""
        return cls(vocab_size=96, hidden_size=64, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=16, rope_theta=10000.0,
                   moe_intermediate_size=32,
                   shared_expert_intermediate_size=32, num_experts=16,
                   num_experts_published=None, num_experts_per_tok=4,
                   linear_num_key_heads=2, linear_num_value_heads=4,
                   linear_key_head_dim=16, linear_value_head_dim=16)


@dataclasses.dataclass
class SDARRankConfig(HybridConvMoEConfig):
    """What ONE chip holds of SDAR-30B-A3B-Chat (``model_type:
    sdar_moe``, 30B parameters, 3B a token) where 8 chips share each
    layer (the benchmark's ``sdar_30b_a3b``), on the TRAINING path of a
    block-diffusion model (SDAR, arXiv:2510.06303): the decoder is the
    Qwen3-MoE one (grouped-query attention, 32 query heads on 4 key/value
    heads of 128, q/k norms before the rotary embedding; 128
    softmax-routed experts of 768, 8 a token, gates normalised, no
    shared expert, no choice bias, every layer an expert layer), and a
    step runs it over ``2 L`` positions: the batch's ``L`` tokens with
    some replaced by ``mask_token_id`` (a block of ``block_length``
    tokens draws ``t = t_min + (1 - t_min) u`` and masks each of its
    tokens with probability ``t``), then the clean tokens, both halves
    at positions ``0 .. L - 1``, under the block-diffusion mask
    (``FFModel.multihead_attention``'s ``block_diffusion_block``). The
    head reads the noised half and the loss is ``(1 / L) sum_i w_i
    nll_i`` with ``w_i = masked_i / t``. Here: experts 0 to 15, one of
    eight slices of the vocabulary and published layers 0 to 5 (the rest
    lie on further chips as pipeline stages); every width as published.

    The fields after the parent's carry ``config.json``'s keys by their
    names (``rms_norm_eps`` and ``rope_theta`` are copied over the
    parent's names for them); the last four are the training recipe's,
    which ``config.json`` has no key for."""
    vocab_size: int = 18992
    num_hidden_layers: int = 6
    layer_types: list | None = None      # every layer the one kind
    num_dense_layers: int = 0
    num_key_value_heads: int = 4
    head_dim: int | None = 128
    intermediate_size: int = 6144        # published; no dense layer reads it
    moe_intermediate_size: int = 768
    num_experts: int = 16
    num_experts_published: int | None = 128
    num_experts_per_tok: int = 8
    use_expert_bias: bool = False
    # the keys the parent class does not have
    decoder_sparse_step: int = 1
    mlp_only_layers: list = dataclasses.field(default_factory=list)
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    scoring_func: str = "softmax"        # not in config.json: the family's
    # not in config.json: the block-diffusion training recipe
    block_length: int = 4
    mask_token_id: int = 18991           # the slice's last id
    t_min: float = 1e-3
    eval_noise_seed: int = 23            # sum(w) / L = 0.947 at 1 x 4096
    # how the routers are DRAWN: the columns of one share of the experts,
    # repeated for each share (``FFModel.routed_experts``), so that the
    # rows this share is sent do not hang on the seed's draw of which
    # experts the mask id's ~L/2 alike positions choose; 1: a plain draw
    router_repeats: int = 8

    def __post_init__(self):
        if self.decoder_sparse_step != 1 or self.mlp_only_layers:
            raise ValueError("a layer without experts is not built for "
                             "this family")
        kinds = ["block_diffusion_attention"] * self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = kinds
        elif list(self.layer_types) != kinds:
            raise ValueError(f"layer_types {self.layer_types}: every "
                             f"layer is 'block_diffusion_attention'")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask_token_id {self.mask_token_id} is no id "
                             f"of the {self.vocab_size} held")
        self.norm_eps = self.rms_norm_eps
        self.rope_parameters = {"rope_theta": self.rope_theta,
                                "rope_type": "default"}

    @classmethod
    def tiny(cls):
        """3 equal layers, 4 heads on 2 kv heads of 16, 16 experts top-4
        of width 32, all held, blocks of 4 tokens: tests."""
        return cls(vocab_size=96, hidden_size=64, num_hidden_layers=3,
                   num_attention_heads=4, num_key_value_heads=2,
                   head_dim=16, rope_theta=10000.0,
                   moe_intermediate_size=32, num_experts=16,
                   num_experts_published=None, num_experts_per_tok=4,
                   mask_token_id=95, router_repeats=1)


@dataclasses.dataclass
class Phi4FlashRankConfig(HybridConvMoEConfig):
    """What ONE chip holds of Phi-4-mini-flash-reasoning (``model_type:
    phi4flash``, 3.8B parameters, dense; the SambaY decoder-hybrid-decoder
    of arXiv:2507.06607 with differential attention) as one stage of a
    pipeline with the vocabulary in eight slices (the benchmark's
    ``phi4_mini_flash_reasoning``). Published layer ``i`` of
    ``num_hidden_layers_published`` = 32 is a state-space position where
    ``i % mb_per_layer == 0`` and an attention position otherwise; the
    first half is the self-decoder, the second the cross-decoder:

      i < 16, even   ``"mamba1"``: a selective-scan (Mamba-1) mixer
      i < 16, odd    ``"diff_sliding_attention"``: differential
                     attention in a window of ``sliding_window``
      i == 16        ``"mamba1_memory"``: a mixer that also hands on its
                     scan's output ``m`` (before the gate)
      i == 17        ``"diff_attention_kv"``: whole differential
                     attention that also hands on its keys and values
      i > 17, even   ``"gated_memory"``: ``(m * silu(u W1)) W2``
      i > 17, odd    ``"diff_cross_attention"``: differential attention
                     of this layer's queries over layer 17's keys and
                     values

    Every layer ends in a dense SwiGLU of ``intermediate_size``; every
    norm is a LayerNorm with a bias; there is no positional embedding.
    Here: published layers ``first_layer_index`` = 14 to 19 (one of each
    kind, and two of the two that stand on both sides of the middle)
    and one of eight slices of the vocabulary; every width as published.

    The fields after the parent's carry ``config.json``'s keys by their
    names; ``layer_norm_eps`` is copied over the parent's ``norm_eps``
    and ``layer_types`` is laid out from the indices. The ``mamba_*``
    sizes are Mamba-1's defaults, which ``config.json`` leaves to the
    model's configuration class (the benchmark file lists them as
    assumed); ``mamba_chunk_size`` is the program's, not the model's."""
    vocab_size: int = 25008
    hidden_size: int = 2560
    num_hidden_layers: int = 6
    layer_types: list | None = None      # from the published indices
    num_dense_layers: int = 6
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    head_dim: int | None = 64            # not published: hidden / heads
    intermediate_size: int = 10240
    moe_intermediate_size: int = 0       # no expert layer reads it
    num_experts: int = 0
    num_experts_per_tok: int = 0
    use_expert_bias: bool = False
    # the keys the parent class does not have
    mb_per_layer: int = 2
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    mlp_bias: bool = False
    lm_head_bias: bool = False
    tie_word_embeddings: bool = True     # published; untied here (R11)
    embd_pdrop: float = 0.0
    resid_pdrop: float = 0.0
    hidden_act: str = "silu"
    max_position_embeddings: int = 262144
    # not in config.json: where this share stands, and Mamba-1's sizes
    first_layer_index: int = 14
    num_hidden_layers_published: int = 32
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160             # ceil(hidden / 16)
    mamba_chunk_size: int = 64

    def kind_of(self, i: int) -> str:
        """The kind of PUBLISHED layer ``i``."""
        half = self.num_hidden_layers_published // 2
        if i % self.mb_per_layer == 0:
            return "mamba1" if i < half else \
                "mamba1_memory" if i == half else "gated_memory"
        return "diff_sliding_attention" if i < half else \
            "diff_attention_kv" if i == half + 1 else "diff_cross_attention"

    def __post_init__(self):
        if self.mlp_bias or self.lm_head_bias or self.embd_pdrop \
                or self.resid_pdrop or self.hidden_act != "silu" \
                or self.mb_per_layer != 2:
            raise ValueError(
                "a bias in the MLP or the head, dropout, another "
                "activation than silu and another layout than one "
                "attention position after each state-space position are "
                "not built for this family")
        last = self.first_layer_index + self.num_hidden_layers
        if last > self.num_hidden_layers_published:
            raise ValueError(
                f"layers {self.first_layer_index} to {last - 1} of "
                f"{self.num_hidden_layers_published}")
        kinds = [self.kind_of(i)
                 for i in range(self.first_layer_index, last)]
        if self.layer_types is None:
            self.layer_types = kinds
        elif list(self.layer_types) != kinds:
            raise ValueError(f"layer_types {self.layer_types} are not the "
                             f"published indices' {kinds}")
        self.norm_eps = self.layer_norm_eps
        self.num_dense_layers = self.num_hidden_layers

    @classmethod
    def tiny(cls):
        """The benchmark's six kinds in its order: 4 heads on 2 kv heads
        of 8 (two query pairs on one key pair), a window of 8, 64
        channels of 4 state entries, a step size of rank 2, chunks of 16
        (two of a 32-token sequence): tests."""
        return cls(vocab_size=96, hidden_size=32, num_attention_heads=4,
                   num_key_value_heads=2, head_dim=8, intermediate_size=64,
                   sliding_window=8, mamba_d_state=4, mamba_dt_rank=2,
                   mamba_chunk_size=16)


_NEMOTRON_KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


@dataclasses.dataclass
class NemotronHRankConfig(HybridConvMoEConfig):
    """What ONE chip holds of NVIDIA-Nemotron-3-Super-120B-A12B
    (``model_type: nemotron_h``, 120B parameters, 12B a token) as rank 0
    of a 4-chip tensor-parallel group, 16 such groups sharing each
    layer's experts (the benchmark's ``nemotron3_super_120b_a12b``). A
    block of this family is ONE sub-layer, ``h += Mix(norm(h))``, its
    kind a letter of ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer
    (``mamba_num_heads`` heads of ``mamba_head_dim`` with a state of
    ``ssm_state_size`` in ``n_groups`` groups of B and C, ``conv_kernel``
    taps with a bias, chunks of ``chunk_size``, the gated norm's mean
    square over a group's channels); ``*`` grouped-query attention with
    NO positional embedding, no q/k norm, scores times ``head_dim **
    -0.5``; ``E`` a LatentMoE feed-forward: a sigmoid router over
    ``n_routed_experts`` published experts (the top
    ``num_experts_per_tok`` of score + bias, gates normalised and times
    ``routed_scaling_factor``) and a shared expert of
    ``moe_shared_expert_intermediate_size`` both read the stream, the
    routed experts work in a latent of ``moe_latent_size`` between two
    projections, and every expert is ``W2 relu(W1 .)^2``
    (``mlp_hidden_act: relu2``: no gate matrix). Here: published layers
    26 to 36 (``EMEMEMEMEM*``, the first whole period of 11), Mamba heads
    0 to 31 of 128 with groups 0 and 1 of 8, query heads 0 to 7 of 32 on
    key/value head 0 of 2, experts 0 to 7 of 512, one of eight slices of
    the vocabulary; every width as published. The head shares' part of
    each output projection goes on as it is: the group's all-reduce is
    not run.

    The fields after the parent's carry ``config.json``'s keys by their
    names; ``n_routed_experts`` counts the experts HELD (it is copied
    over the parent's ``num_experts``) and ``*_published`` give the
    whole model's counts. The properties at the end hand the builder the
    sizes under the names :class:`GraniteHybridRankConfig` gave them.
    The multi-token-prediction module (``mtp_hybrid_override_pattern``)
    is not built."""
    vocab_size: int = 16384
    hidden_size: int = 4096
    num_hidden_layers: int = 11
    layer_types: list | None = None      # from hybrid_override_pattern
    num_dense_layers: int = 0
    num_attention_heads: int = 8
    num_key_value_heads: int = 1
    head_dim: int | None = 128
    intermediate_size: int = 2688        # published; no dense layer reads it
    moe_intermediate_size: int = 2688
    num_experts: int = 8
    num_experts_published: int | None = 512
    num_experts_per_tok: int = 22
    routed_scaling_factor: float = 5.0
    # the choice's bias starts where the published modelling code starts
    # it, at zero (``e_score_correction_bias``, a buffer of zeros), and
    # follows the family's balancing rule from there
    # (``router_bias_update_rate``, below)
    router_bias_std: float = 0.0
    # the keys the parent class does not have
    hybrid_override_pattern: str = "EMEMEMEMEM*"
    mamba_num_heads: int = 32
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 2
    conv_kernel: int = 4
    chunk_size: int = 128
    expand: int = 2
    use_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_hidden_act: str = "silu"
    attention_bias: bool = False
    mlp_bias: bool = False
    use_bias: bool = False
    mlp_hidden_act: str = "relu2"
    n_routed_experts: int = 8
    n_shared_experts: int = 1
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_group: int = 1                     # of experts: no group limit
    topk_group: int = 1
    layer_norm_epsilon: float = 1e-5
    tie_word_embeddings: bool = False
    # not in config.json: the whole model's counts beside the share's
    mamba_num_heads_published: int = 128
    n_groups_published: int = 8
    num_attention_heads_published: int = 32
    num_key_value_heads_published: int = 2
    # not in config.json: the row budget of an expert layer, in uniform
    # shares of the held experts (``FFModel.routed_experts``)
    expert_rows_factor: int = 8
    # not in config.json, the training recipe's: what the balancing rule
    # moves an expert's choice bias by a step, down where the step sent
    # it more than the uniform share of the assignments and up where
    # less (``FFModel.routed_experts``'s ``bias_step``; DeepSeek-V3's
    # published 0.001)
    router_bias_update_rate: float = 1e-3
    #: ``build_hybrid_conv_moe``: a layer is its one sub-layer
    sublayers_per_block = 1

    def __post_init__(self):
        if self.use_bias or self.mlp_bias or self.attention_bias \
                or self.mamba_proj_bias or not self.use_conv_bias \
                or self.mlp_hidden_act != "relu2" \
                or self.mamba_hidden_act != "silu" \
                or self.n_group != 1 or self.topk_group != 1 \
                or self.tie_word_embeddings \
                or self.layer_norm_epsilon != self.norm_eps:
            raise ValueError(
                "a bias in a projection, a convolution without one, "
                "experts that are not ReLU-squared, a group-limited "
                "choice of experts, a tied head and two norm epsilons "
                "are not built for this family")
        if set(self.hybrid_override_pattern) - set(_NEMOTRON_KINDS):
            raise ValueError(
                f"hybrid_override_pattern {self.hybrid_override_pattern!r}"
                f": its letters are {sorted(_NEMOTRON_KINDS)} (a dense "
                f"MLP block, '-', is not built)")
        kinds = [_NEMOTRON_KINDS[c] for c in self.hybrid_override_pattern]
        if self.layer_types is None:
            self.layer_types = kinds
        elif list(self.layer_types) != kinds:
            raise ValueError(f"layer_types {self.layer_types} is not "
                             f"{self.hybrid_override_pattern!r}")
        # query heads a key/value head: here, and in the whole model
        reads, reads_published = (
            self.num_attention_heads // self.num_key_value_heads,
            self.num_attention_heads_published
            // self.num_key_value_heads_published)
        if self.mamba_num_heads_published * self.mamba_head_dim \
                != self.expand * self.hidden_size \
                or self.mamba_num_heads * self.n_groups_published \
                != self.n_groups * self.mamba_num_heads_published \
                or reads_published % reads \
                or (self.num_key_value_heads > 1
                    and reads != reads_published):
            raise ValueError(
                "the whole mixer's heads x mamba_head_dim is expand x "
                "hidden_size, and a head share holds whole groups of B "
                "and C, and whole key/value heads with the query heads "
                "that read them or some of ONE key/value head's")
        self.num_experts = self.n_routed_experts
        self.num_dense_layers = 0

    # the builder's names for the mixer's and the attention layer's sizes
    mamba_n_heads = property(lambda self: self.mamba_num_heads)
    mamba_d_head = property(lambda self: self.mamba_head_dim)
    mamba_d_state = property(lambda self: self.ssm_state_size)
    mamba_n_groups = property(lambda self: self.n_groups)
    mamba_d_conv = property(lambda self: self.conv_kernel)
    mamba_chunk_size = property(lambda self: self.chunk_size)
    attention_multiplier = property(lambda self: self.head_dim ** -0.5)

    @classmethod
    def tiny(cls):
        """``EMEM*``: 4 state-space heads of 16 in 2 groups with a state
        of 8 in chunks of 16, 4 attention heads on 2 kv heads of 16, 16
        ReLU-squared experts of 48 top-3 in a latent of 32 under a
        stream of 64, all held, a shared expert of 96: tests."""
        return cls(vocab_size=96, hidden_size=64, num_hidden_layers=5,
                   hybrid_override_pattern="EMEM*",
                   num_attention_heads=4, num_key_value_heads=2,
                   head_dim=16, num_attention_heads_published=4,
                   num_key_value_heads_published=2,
                   mamba_num_heads=8, mamba_head_dim=16, ssm_state_size=8,
                   n_groups=2, mamba_num_heads_published=8,
                   n_groups_published=2, chunk_size=16,
                   moe_intermediate_size=48, moe_latent_size=32,
                   moe_shared_expert_intermediate_size=96,
                   n_routed_experts=16, num_experts_published=None,
                   num_experts_per_tok=3, router_bias_std=0.05,
                   expert_rows_factor=2)


def _rolled_by_one(ff: FFModel, x, rows: int, name: str):
    """Rows ``1 .. rows - 1`` and then row 0 of ``x``'s first ``rows``
    along axis 1: ``out[i] = x[(i + 1) % rows]``."""
    return ff.concat([ff.slice_tensor(x, [1], [rows], [1]),
                      ff.slice_tensor(x, [0], [1], [1])], axis=1, name=name)


def build_hybrid_conv_moe(ff: FFModel, batch_size: int, seq_len: int,
                          cfg: HybridConvMoEConfig | None = None):
    """Causal LM of :class:`HybridConvMoEConfig`: inputs ``[ids, pos]``,
    output the softmax over the head, as :func:`build_latent_moe`. The
    layers are laid out from ``layer_types`` and ``num_dense_layers``:
    ``h += Op(norm(h))`` then ``h += FF(norm(h))``. A
    ``"sparse_attention"`` layer (:class:`KeyeRankConfig`) is a
    ``"full_attention"`` one whose queries attend the keys its indexer
    selects; its alignment loss joins the step's. A
    ``"sliding_attention"`` layer (:class:`TrinityRankConfig`) is one
    whose queries see the ``sliding_window`` keys that end with their
    own; that class also turns the full layers' rotary embedding off,
    gates every attention layer's output, norms each sub-layer's output
    before the residual add, scales the embedding and adds a shared
    expert. A ``"mamba"`` layer (:class:`GraniteHybridRankConfig`) is a
    state-space mixer and that class's ``"attention"`` layer grouped-query
    attention with no rotary embedding, no q/k norm and the scores'
    multiplier the configuration gives; the class also multiplies the
    embedding, each sub-layer's output before its add and the logits by
    scalars of its own. A ``"linear_attention"`` layer
    (:class:`Qwen3NextRankConfig`) is a gated delta rule with a decay a
    head; that class also turns only part of each head on its full
    layers, gates their output and the shared expert, and multiplies
    every norm by ``1 + w``. The six kinds of
    :class:`Phi4FlashRankConfig` (``"mamba1"``, ``"mamba1_memory"``,
    ``"diff_sliding_attention"``, ``"diff_attention_kv"``,
    ``"gated_memory"``, ``"diff_cross_attention"``) are a selective-scan
    mixer, differential attention and a gated memory unit of plain ops;
    two of them read what an earlier layer handed on, and that class's
    norms are LayerNorms with a bias. A ``"block_diffusion_attention"``
    layer (:class:`SDARRankConfig`) attends under the block-diffusion
    mask; that class's graph opens with the noising op, runs every layer
    over ``2 seq_len`` positions (the noised copy, then the clean one),
    reads the head off the noised half and weighs the loss's rows. A
    class whose ``sublayers_per_block`` is 1
    (:class:`NemotronHRankConfig`) lays every layer out as ONE
    sub-layer, ``h += Op(norm(h))`` OR ``h += FF(norm(h))``: its
    ``"mamba"`` and ``"attention"`` layers end at the operator's add, a
    ``"moe"`` layer is the feed-forward alone, and that class's mixers
    read B and C in groups, its experts are ReLU-squared and routed in a
    latent between two projections of the layer's own.
    ``pos`` is what the
    attention layers' rotary embedding turns by (a layout in which no
    layer turns by it still declares it, and ``fit`` drops its array).

    The published model ties the head to the embedding; this graph has
    no weight read by two layers, so the head is its own matrix (as
    :func:`build_gpt2`'s)."""
    cfg = cfg or HybridConvMoEConfig()
    kinds = list(cfg.layer_types)
    if len(kinds) != cfg.num_hidden_layers \
            or set(kinds) - {"conv", "full_attention", "sparse_attention",
                             "sliding_attention", "mamba", "attention",
                             "linear_attention",
                             "block_diffusion_attention", "moe"} \
            - _SAMBAY_KINDS:
        raise ValueError(
            f"layer_types must name {cfg.num_hidden_layers} layers, each "
            f"'conv', 'full_attention', 'sparse_attention', "
            f"'sliding_attention', 'mamba', 'attention', "
            f"'linear_attention', 'block_diffusion_attention', 'moe' or "
            f"one of {sorted(_SAMBAY_KINDS)}; got "
            f"{len(kinds)}: {sorted(set(kinds))}")
    # blocks of ONE sub-layer (``NemotronHRankConfig``): a layer is its
    # operator or, where its kind is "moe", its feed-forward
    single = getattr(cfg, "sublayers_per_block", 2) == 1
    if ("moe" in kinds) != single or (single and cfg.num_dense_layers):
        raise ValueError("a 'moe' layer is a block of one sub-layer: it "
                         "needs a configuration whose sublayers_per_block "
                         "is 1, which has no dense feed-forward")
    if cfg.conv_bias or not cfg.norm_topk_prob:
        raise ValueError("conv_bias and gates that are not normalised "
                         "over the chosen experts are not built")
    b, s, hid = batch_size, seq_len, cfg.hidden_size
    heads = cfg.num_attention_heads
    head_dim = cfg.head_dim or hid // heads
    published = cfg.num_experts_published or cfg.num_experts
    # fields of a subclass (``KeyeRankConfig``): absent, the graph is
    # the one it was
    sa = getattr(cfg, "sa_config", None)
    if "sparse_attention" in kinds and sa is None:
        raise ValueError("a 'sparse_attention' layer needs the "
                         "configuration's sa_config")
    indexer = {} if sa is None else {"indexer": {
        "heads": sa["indexer_num_heads"], "head_dim": sa["indexer_head_dim"],
        "topk": sa["topk"], "q_chunk": sa["q_chunk_size"]}}
    scoring = {"scoring": cfg.scoring_func} \
        if hasattr(cfg, "scoring_func") else {}
    if getattr(cfg, "score_func", "sigmoid") != "sigmoid":
        scoring = {"scoring": cfg.score_func}
    window = getattr(cfg, "sliding_window", 0)
    if "sliding_attention" in kinds and not window:
        raise ValueError("a 'sliding_attention' layer needs the "
                         "configuration's sliding_window")
    gated = {"output_gate": True} \
        if getattr(cfg, "attention_output_gate", False) else {}
    sandwich = getattr(cfg, "sandwich_norms", False)
    shared_dim = cfg.moe_intermediate_size \
        * getattr(cfg, "num_shared_experts", 0)
    if "linear_attention" in kinds \
            and not hasattr(cfg, "linear_num_value_heads"):
        raise ValueError("a 'linear_attention' layer needs the "
                         "configuration's linear_* sizes")
    # the forms of a subclass (``Qwen3NextRankConfig``): absent, the
    # graph is the one it was
    centred = {"zero_centered": True} \
        if getattr(cfg, "zero_centered_norms", False) else {}
    if hasattr(cfg, "partial_rotary_factor"):
        gated["rotary_dim"] = int(head_dim * cfg.partial_rotary_factor)
    if centred:
        gated["qk_norm_zero_centered"] = True
    experts = dict(scoring)
    if hasattr(cfg, "shared_expert_intermediate_size"):
        shared_dim = cfg.shared_expert_intermediate_size
        experts.update(shared_gate=cfg.shared_expert_gate,
                       choice_bias=cfg.use_expert_bias,
                       rows_factor=cfg.expert_rows_factor)
    if hasattr(cfg, "moe_latent_size"):
        shared_dim = cfg.moe_shared_expert_intermediate_size \
            * cfg.n_shared_experts
        experts.update(latent=cfg.moe_latent_size,
                       activation=cfg.mlp_hidden_act,
                       rows_factor=cfg.expert_rows_factor,
                       bias_step=cfg.router_bias_update_rate)
    if _SAMBAY_KINDS & set(kinds) and not hasattr(cfg, "mamba_dt_rank"):
        raise ValueError("a selective-scan, differential or gated-memory "
                         "layer needs the configuration's mamba_* sizes "
                         "and first_layer_index")
    if "mamba" in kinds and not hasattr(cfg, "mamba_n_heads"):
        raise ValueError("a 'mamba' layer needs the configuration's "
                         "mamba_* sizes")
    if "attention" in kinds and not hasattr(cfg, "attention_multiplier"):
        raise ValueError("an 'attention' layer needs the configuration's "
                         "attention_multiplier")
    # the scalars of a subclass (``GraniteHybridRankConfig``): absent,
    # the graph has no node for them
    residual_scale = getattr(cfg, "residual_multiplier", None)
    diffusion = "block_diffusion_attention" in kinds
    if diffusion and (set(kinds) != {"block_diffusion_attention"}
                      or not hasattr(cfg, "block_length")):
        raise ValueError("'block_diffusion_attention' layers need the "
                         "configuration's block_length, mask_token_id, "
                         "t_min and eval_noise_seed, and stand alone")
    if diffusion:           # softmax scores read no choice bias: no weight
        experts.update(choice_bias=cfg.use_expert_bias,
                       router_repeats=cfg.router_repeats)
    ids = ff.create_tensor((b, s), DataType.DT_INT32, name="input_ids")
    pos = ff.create_tensor((b, s), DataType.DT_INT32, name="position_ids",
                           may_be_unread=not set(kinds) - {"mamba",
                                                           "attention",
                                                           "moe"}
                           - _SAMBAY_KINDS)
    read_ids = ids
    if diffusion:
        # the decoder reads [noised ; clean], 2 s positions; the runner's
        # labels are the NEXT token's, so the loss is handed the head's
        # rows and their weights rolled by one: out[i] = P[(i + 1) % s]
        read_ids, loss_weights = ff.block_diffusion_noise(
            ids, cfg.block_length, cfg.mask_token_id, cfg.t_min,
            cfg.eval_noise_seed, name="noise")
        ff.set_loss_weights(_rolled_by_one(ff, loss_weights, s,
                                           "loss_weights"))
    h = ff.embedding(read_ids, cfg.vocab_size, hid, name="embed_tokens")
    if getattr(cfg, "mup_enabled", False):
        h = ff.scalar_multiply(h, math.sqrt(hid), name="embed_scale")
    if hasattr(cfg, "embedding_multiplier"):
        h = ff.scalar_multiply(h, cfg.embedding_multiplier,
                               name="embedding_multiplier")

    def norm(x, name):
        if hasattr(cfg, "layer_norm_eps"):      # with a scale and a bias
            return ff.layer_norm(x, [2], eps=cfg.layer_norm_eps, name=name)
        return ff.rms_norm(x, eps=cfg.norm_eps, name=name, **centred)

    def differential(x, i, **more):
        # lambda starts from the layer's PUBLISHED index
        depth = cfg.first_layer_index + i
        return ff.multihead_attention(
            x, more.pop("key", x), more.pop("value", x), hid, heads,
            kdim=heads * head_dim, vdim=heads * head_dim, bias=True,
            causal=True, num_kv_heads=cfg.num_key_value_heads,
            differential={"lambda_init": 0.8 - 0.6 * math.exp(-0.3 * depth),
                          "eps": cfg.layer_norm_eps},
            name=f"attn_{i}", **more)

    memory = handed_kv = None       # what layers 16 and 17 hand on

    def scaled(x, name):
        return x if residual_scale is None \
            else ff.scalar_multiply(x, residual_scale, name=name)

    for i, kind in enumerate(kinds):
        x = None if kind == "moe" else norm(h, f"operator_norm_{i}")
        if kind == "moe":
            op = None                   # the block is its feed-forward
        elif kind == "conv":
            op = ff.gated_short_conv(x, cfg.conv_L_cache, name=f"conv_{i}")
        elif kind in ("mamba1", "mamba1_memory"):
            op = ff.selective_scan_mixer(
                x, cfg.mamba_expand * hid, cfg.mamba_d_state,
                cfg.mamba_dt_rank, cfg.mamba_d_conv, cfg.mamba_chunk_size,
                memory_out=kind == "mamba1_memory", name=f"ssm_{i}")
            if kind == "mamba1_memory":
                op, memory = op
        elif kind == "diff_sliding_attention":
            op = differential(x, i, sliding_window=window)
        elif kind == "diff_attention_kv":
            op, *handed_kv = differential(x, i, kv_out=True)
        elif kind == "diff_cross_attention":
            if handed_kv is None:
                raise ValueError(f"layer {i}: no earlier "
                                 f"'diff_attention_kv' layer's keys and "
                                 f"values to attend over")
            op = differential(x, i, key=handed_kv[0], value=handed_kv[1],
                              kv_projected=True)
        elif kind == "gated_memory":
            # (m * silu(u W1)) W2 from ops the graph has
            if memory is None:
                raise ValueError(f"layer {i}: no earlier 'mamba1_memory' "
                                 f"layer's scan output to gate")
            pre = ff.dense(x, memory.shape[-1], use_bias=False,
                           name=f"gmu_in_{i}")
            act = ff.multiply(pre, ff.sigmoid(pre, name=f"gmu_sigmoid_{i}"),
                              name=f"gmu_silu_{i}")
            op = ff.dense(ff.multiply(memory, act, name=f"gmu_gate_{i}"),
                          hid, use_bias=False, name=f"gmu_out_{i}")
        elif kind == "mamba":
            op = ff.state_space_mixer(
                x, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
                cfg.mamba_d_conv, cfg.mamba_chunk_size,
                groups=cfg.mamba_n_groups, eps=cfg.norm_eps,
                name=f"mamba_{i}")
        elif kind == "linear_attention":
            op = ff.gated_delta_rule(
                x, cfg.linear_num_value_heads, cfg.linear_value_head_dim,
                cfg.linear_conv_kernel_dim, eps=cfg.norm_eps,
                num_key_heads=cfg.linear_num_key_heads, decay="head",
                name=f"linear_attn_{i}")
        elif kind == "block_diffusion_attention":
            # both halves turn by the one ``pos``; not causal
            op = ff.multihead_attention(
                x, x, x, hid, heads, kdim=heads * head_dim,
                vdim=heads * head_dim, bias=False, rope=True,
                rope_theta=cfg.rope_parameters["rope_theta"],
                num_kv_heads=cfg.num_key_value_heads, qk_norm=True,
                qk_norm_eps=cfg.norm_eps, positions=pos,
                block_diffusion_block=cfg.block_length, name=f"attn_{i}")
        elif kind == "attention":
            # no rotary embedding, no q/k norm, the model's own scale
            op = ff.multihead_attention(
                x, x, x, hid, heads, kdim=heads * head_dim,
                vdim=heads * head_dim, bias=False, causal=True,
                num_kv_heads=cfg.num_key_value_heads,
                sm_scale=cfg.attention_multiplier, name=f"attn_{i}")
        else:
            # a full layer turns by the positions unless the class says
            # it has no rotary embedding; a window layer always does
            turns = kind != "full_attention" \
                or getattr(cfg, "full_attention_rope", True)
            op = ff.multihead_attention(
                x, x, x, hid, heads, kdim=heads * head_dim,
                vdim=heads * head_dim, bias=False, causal=True, rope=turns,
                rope_theta=cfg.rope_parameters["rope_theta"],
                num_kv_heads=cfg.num_key_value_heads, qk_norm=True,
                qk_norm_eps=cfg.norm_eps,
                positions=pos if turns else None, name=f"attn_{i}",
                **(indexer if kind == "sparse_attention" else {}),
                **({"sliding_window": window}
                   if kind == "sliding_attention" else {}), **gated)
        if op is not None:
            if sandwich:
                op = norm(op, f"post_operator_norm_{i}")
            h = ff.add(h, scaled(op, f"operator_scale_{i}"),
                       name=f"operator_res_{i}")
            if single:
                continue
        x = norm(h, f"ffn_norm_{i}")
        if i < cfg.num_dense_layers:
            gate = ff.dense(x, cfg.intermediate_size, use_bias=False,
                            name=f"gate_proj_{i}")
            up = ff.dense(x, cfg.intermediate_size, use_bias=False,
                          name=f"up_proj_{i}")
            silu = ff.multiply(gate, ff.sigmoid(gate), name=f"silu_{i}")
            y = ff.dense(ff.multiply(silu, up), hid, use_bias=False,
                         name=f"down_proj_{i}")
        else:
            y = ff.routed_experts(
                x, published, cfg.num_experts_per_tok,
                cfg.moe_intermediate_size, shared_dim=shared_dim,
                experts_held=cfg.num_experts,
                first_held=cfg.first_held_expert,
                scale=cfg.routed_scaling_factor,
                bias_std=cfg.router_bias_std if cfg.use_expert_bias
                else 0.0, name=f"experts_{i}", **experts)
        if sandwich:
            y = norm(y, f"post_ffn_norm_{i}")
        h = ff.add(h, scaled(y, f"ffn_scale_{i}"), name=f"ffn_res_{i}")
    if diffusion:
        # the head reads the noised half, rolled as the weights are
        h = _rolled_by_one(ff, h, s, "noised_rows")
    logits = ff.dense(norm(h, "final_norm"), cfg.vocab_size,
                      use_bias=False, name="lm_head")
    if hasattr(cfg, "logits_scaling"):
        logits = ff.scalar_true_divide(logits, cfg.logits_scaling,
                                       name="logits_scaling")
    return ff.softmax(logits)
