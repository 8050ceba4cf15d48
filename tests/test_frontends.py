"""Frontend tests: torch.fx import (numeric alignment with torch),
Keras-style API end-to-end. (ONNX handlers are exercised only when the
onnx package is present.)"""
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer

torch = pytest.importorskip("torch")
import torch.nn as nn  # noqa: E402


def _cfg(bs=8):
    c = FFConfig()
    c.batch_size = bs
    c.only_data_parallel = True
    return c


class SmallNet(nn.Module):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(16, 32)
        self.fc2 = nn.Linear(32, 4)

    def forward(self, x):
        x = torch.relu(self.fc1(x))
        return torch.softmax(self.fc2(x), dim=-1)


class SmallCNN(nn.Module):
    def __init__(self):
        super().__init__()
        self.conv = nn.Conv2d(3, 8, 3, padding=1)
        self.pool = nn.MaxPool2d(2)
        self.flat = nn.Flatten()
        self.fc = nn.Linear(8 * 8 * 8, 10)

    def forward(self, x):
        x = torch.relu(self.conv(x))
        x = self.pool(x)
        return self.fc(self.flat(x))


def test_torch_fx_mlp_alignment():
    """Imported torch model + copied weights == torch forward (alignment
    test, reference tests/align analog)."""
    from flexflow_tpu.frontends.torch_fx import PyTorchModel
    net = SmallNet()
    ff = FFModel(_cfg(8))
    x = ff.create_tensor((8, 16), name="x")
    m = PyTorchModel(net)
    outs = m.torch_to_ff(ff, [x])
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy", [],
               output_tensor=outs[0])
    m.copy_weights(ff)
    xs = np.random.default_rng(0).normal(size=(8, 16)).astype(np.float32)
    with torch.no_grad():
        ref = net(torch.from_numpy(xs)).numpy()
    fwd = ff.executor.make_forward()
    got = np.asarray(fwd(ff.params, ff.state, {"x": xs}))
    np.testing.assert_allclose(ref, got, rtol=2e-2, atol=2e-3)


def test_torch_fx_cnn_alignment():
    from flexflow_tpu.frontends.torch_fx import PyTorchModel
    net = SmallCNN()
    ff = FFModel(_cfg(4))
    x = ff.create_tensor((4, 3, 16, 16), name="x")
    m = PyTorchModel(net)
    outs = m.torch_to_ff(ff, [x])
    ff.compile(SGDOptimizer(0.01), "sparse_categorical_crossentropy", [],
               output_tensor=outs[0])
    m.copy_weights(ff)
    xs = np.random.default_rng(0).normal(size=(4, 3, 16, 16))\
        .astype(np.float32)
    with torch.no_grad():
        ref = net(torch.from_numpy(xs)).numpy()
    fwd = ff.executor.make_forward()
    got = np.asarray(fwd(ff.params, ff.state, {"x": xs}))
    np.testing.assert_allclose(ref, got, rtol=5e-2, atol=5e-3)


def test_keras_sequential_trains():
    from flexflow_tpu.frontends import keras
    from flexflow_tpu.frontends.keras.callbacks import VerifyMetrics
    model = keras.Sequential([
        keras.Input((20,), name="x"),
        keras.Dense(64, activation="relu"),
        keras.Dense(4),
        keras.Softmax(),
    ])
    cfg = FFConfig()
    cfg.only_data_parallel = True
    model.compile("sgd", "sparse_categorical_crossentropy", ["accuracy"],
                  config=cfg, batch_size=64)
    rng = np.random.default_rng(0)
    centers = rng.normal(size=(4, 20)) * 3
    ys = rng.integers(0, 4, 512).astype(np.int32)
    xs = (centers[ys] + rng.normal(size=(512, 20))).astype(np.float32)
    model.ffmodel.optimizer.lr = 0.1
    model.fit(xs, ys, epochs=4, verbose=False,
              callbacks=[VerifyMetrics("accuracy", 0.8)])
    rep = model.evaluate(xs, ys)
    assert rep["accuracy"] > 0.8


def test_keras_functional_multi_input():
    from flexflow_tpu.frontends import keras
    a = keras.Input((8,), name="a")
    b = keras.Input((8,), name="b")
    da = keras.Dense(16, activation="relu")(a.tensor)
    db = keras.Dense(16, activation="relu")(b.tensor)
    merged = keras.Concatenate()([da, db])
    out = keras.Softmax()(keras.Dense(2)(merged))
    model = keras.Model(inputs=[a, b], outputs=out)
    cfg = FFConfig()
    cfg.only_data_parallel = True
    model.compile("adam", "sparse_categorical_crossentropy", ["accuracy"],
                  config=cfg, batch_size=32)
    rng = np.random.default_rng(0)
    xa = rng.normal(size=(128, 8)).astype(np.float32)
    xb = rng.normal(size=(128, 8)).astype(np.float32)
    ys = (xa.sum(-1) > xb.sum(-1)).astype(np.int32)
    hist = model.fit([xa, xb], ys, epochs=3, verbose=False)
    assert hist[-1]["loss"] < hist[0]["loss"]


class MHANet(nn.Module):
    def __init__(self):
        super().__init__()
        self.attn = nn.MultiheadAttention(16, 4, batch_first=True)
        self.fc = nn.Linear(16, 2)

    def forward(self, x):
        a, _ = self.attn(x, x, x)
        return self.fc(a[:, -1])


def test_torch_fx_mha_and_negative_index():
    """nn.MultiheadAttention tuple output + x[:, -1] lowering."""
    from flexflow_tpu.frontends.torch_fx import PyTorchModel
    ff = FFModel(_cfg(4))
    x = ff.create_tensor((4, 6, 16), name="x")
    m = PyTorchModel(MHANet())
    outs = m.torch_to_ff(ff, [x])
    assert outs[0].shape == (4, 2), outs[0].shape


class SeqNet(nn.Module):
    def __init__(self):
        super().__init__()
        self.seq = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                                 nn.Linear(16, 2))

    def forward(self, x):
        return self.seq(x)


def test_torch_fx_sequential_weight_copy():
    from flexflow_tpu.frontends.torch_fx import PyTorchModel
    torch.manual_seed(0)     # unseeded, one draw in some hundreds fails
    net = SeqNet()
    ff = FFModel(_cfg(4))
    x = ff.create_tensor((4, 8), name="x")
    m = PyTorchModel(net)
    outs = m.torch_to_ff(ff, [x])
    ff.compile(SGDOptimizer(0.01), "identity", [], output_tensor=outs[0])
    m.copy_weights(ff)
    xs = np.random.default_rng(0).normal(size=(4, 8)).astype(np.float32)
    with torch.no_grad():
        ref = net(torch.from_numpy(xs)).numpy()
    got = np.asarray(ff.executor.make_forward()(
        ff.params, ff.state, {"x": xs}))
    np.testing.assert_allclose(ref, got, rtol=2e-2, atol=2e-3)


def test_early_stopping_halts_fit():
    from flexflow_tpu.frontends import keras
    from flexflow_tpu.frontends.keras.callbacks import EarlyStopping
    model = keras.Sequential([
        keras.Input((8,), name="x"),
        keras.Dense(4),
        keras.Softmax(),
    ])
    cfg = FFConfig()
    cfg.only_data_parallel = True
    model.compile("sgd", "sparse_categorical_crossentropy", [],
                  config=cfg, batch_size=16)
    model.ffmodel.optimizer.lr = 0.0  # loss plateaus immediately
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(64, 8)).astype(np.float32)
    ys = rng.integers(0, 4, 64).astype(np.int32)
    hist = model.fit(xs, ys, epochs=10, verbose=False,
                     callbacks=[EarlyStopping(patience=2)])
    assert len(hist) < 10, len(hist)


def test_lr_scheduler_takes_effect():
    from flexflow_tpu.frontends import keras
    from flexflow_tpu.frontends.keras.callbacks import LearningRateScheduler
    model = keras.Sequential([
        keras.Input((8,), name="x"),
        keras.Dense(4),
        keras.Softmax(),
    ])
    cfg = FFConfig()
    cfg.only_data_parallel = True
    model.compile("sgd", "sparse_categorical_crossentropy", [],
                  config=cfg, batch_size=16)
    model.ffmodel.optimizer.lr = 0.5
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(64, 8)).astype(np.float32)
    ys = rng.integers(0, 4, 64).astype(np.int32)
    w0 = model.ffmodel.get_weights(model.ffmodel.layers[0].name).copy()
    # lr -> 0 after first epoch: weights must stop changing
    model.fit(xs, ys, epochs=1, verbose=False)
    w1 = model.ffmodel.get_weights(model.ffmodel.layers[0].name).copy()
    assert not np.allclose(w0, w1)
    model.fit(xs, ys, epochs=2, verbose=False,
              callbacks=[LearningRateScheduler(lambda e: 0.0)])
    # epoch 1 ran at 0.5 (schedule applies at epoch end), epoch 2 at 0.0
    w2 = model.ffmodel.get_weights(model.ffmodel.layers[0].name).copy()
    model.fit(xs, ys, epochs=1, verbose=False)  # lr now 0 via scheduler
    w3 = model.ffmodel.get_weights(model.ffmodel.layers[0].name).copy()
    assert np.allclose(w2, w3)


def test_torch_fx_huggingface_bert():
    """Import a real HF BertModel through fx (reference
    ``python/flexflow/torch/model.py`` HF path), copy weights, and match
    torch numerics — exercises const folding of the mask/position-id
    machinery and the SDPA lowering."""
    import numpy as np
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from transformers import BertConfig as HFBertConfig, BertModel
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.frontends.torch_fx import PyTorchModel

    tcfg = HFBertConfig(vocab_size=128, hidden_size=32,
                        num_hidden_layers=2, num_attention_heads=4,
                        intermediate_size=64, max_position_embeddings=64)
    torch.manual_seed(0)
    m = BertModel(tcfg)
    pm = PyTorchModel(m, is_hf_model=True, batch_size=2)
    cfg = FFConfig()
    cfg.only_data_parallel = True
    ff = FFModel(cfg)
    ids = ff.create_tensor((2, 16), dtype="int32", name="input_ids")
    outs = pm.torch_to_ff(ff, [ids])
    assert outs[0].shape == (2, 16, 32)
    ff.compile(SGDOptimizer(0.01), "identity", [], output_tensor=outs[0])
    pm.copy_weights(ff)
    x = np.random.default_rng(0).integers(0, 128, size=(2, 16)) \
        .astype(np.int32)
    y = np.asarray(ff.executor.make_forward()(ff.params, ff.state,
                                              {"input_ids": x}))
    with torch.no_grad():
        ref = m(input_ids=torch.from_numpy(x.astype(np.int64))) \
            .last_hidden_state.numpy()
    np.testing.assert_allclose(y, ref, atol=5e-3, rtol=5e-3)


def test_torch_fx_huggingface_gpt2():
    """Import a real HF GPT2Model (Conv1D modules, causal masking,
    NewGELU) through fx, copy weights, and match torch numerics
    (reference HF path, ``python/flexflow/torch/model.py``)."""
    import numpy as np
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    from transformers import GPT2Config as HFGPT2Config, GPT2Model
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.frontends.torch_fx import PyTorchModel

    tcfg = HFGPT2Config(vocab_size=96, n_embd=32, n_layer=2, n_head=4,
                        n_positions=32, resid_pdrop=0.0, embd_pdrop=0.0,
                        attn_pdrop=0.0)
    torch.manual_seed(0)
    m = GPT2Model(tcfg)
    pm = PyTorchModel(m, is_hf_model=True, batch_size=2)
    cfg = FFConfig()
    cfg.only_data_parallel = True
    ff = FFModel(cfg)
    ids = ff.create_tensor((2, 16), dtype="int32", name="input_ids")
    outs = pm.torch_to_ff(ff, [ids])
    assert outs[0].shape == (2, 16, 32)
    ff.compile(SGDOptimizer(0.01), "identity", [], output_tensor=outs[0])
    pm.copy_weights(ff)
    x = np.random.default_rng(1).integers(0, 96, size=(2, 16)) \
        .astype(np.int32)
    y = np.asarray(ff.executor.make_forward()(ff.params, ff.state,
                                              {"input_ids": x}))
    with torch.no_grad():
        ref = m(input_ids=torch.from_numpy(x.astype(np.int64))) \
            .last_hidden_state.numpy()
    np.testing.assert_allclose(y, ref, atol=5e-3, rtol=5e-3)


def test_torch_fx_t5_rmsnorm_fusion():
    """T5LayerNorm modules fuse to OP_RMSNORM (reference T5 handling)."""
    import numpy as np
    torch = pytest.importorskip("torch")
    import torch.nn as nn
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.ffconst import OperatorType
    from flexflow_tpu.frontends.torch_fx import PyTorchModel

    class T5LayerNorm(nn.Module):  # HF-identical semantics
        def __init__(self, d, eps=1e-6):
            super().__init__()
            self.weight = nn.Parameter(torch.ones(d))
            self.variance_epsilon = eps

        def forward(self, x):
            var = x.pow(2).mean(-1, keepdim=True)
            return self.weight * x * torch.rsqrt(
                var + self.variance_epsilon)

    class Block(nn.Module):
        def __init__(self):
            super().__init__()
            self.norm = T5LayerNorm(16)
            self.fc = nn.Linear(16, 16)

        def forward(self, x):
            return self.fc(self.norm(x))

    m = Block().eval()
    with torch.no_grad():
        m.norm.weight.mul_(1.5)
    pm = PyTorchModel(m)
    cfg = FFConfig()
    cfg.only_data_parallel = True
    cfg.use_bf16_compute = False  # f32 matmul for tight numeric check
    ff = FFModel(cfg)
    x_t = ff.create_tensor((4, 16), name="x")
    outs = pm.torch_to_ff(ff, [x_t])
    assert any(l.op_type == OperatorType.OP_RMSNORM for l in ff.layers)
    ff.compile(SGDOptimizer(0.01), "identity", [], output_tensor=outs[0])
    pm.copy_weights(ff)
    x = np.random.default_rng(2).normal(size=(4, 16)).astype(np.float32)
    y = np.asarray(ff.executor.make_forward()(ff.params, ff.state,
                                              {"x": x}))
    with torch.no_grad():
        ref = m(torch.from_numpy(x)).numpy()
    np.testing.assert_allclose(y, ref, atol=2e-3, rtol=2e-3)


def test_torch_fx_batchnorm_running_stats():
    """BatchNorm2d import carries eps + running stats (eval-mode
    numerics match a torch model with non-trivial running stats)."""
    import numpy as np
    torch = pytest.importorskip("torch")
    import torch.nn as nn
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.frontends.torch_fx import PyTorchModel

    m = nn.Sequential(nn.Conv2d(3, 4, 3, padding=1),
                      nn.BatchNorm2d(4, eps=1e-3), nn.ReLU()).eval()
    with torch.no_grad():  # non-default running stats
        m[1].running_mean.copy_(torch.tensor([0.1, -0.2, 0.3, 0.0]))
        m[1].running_var.copy_(torch.tensor([1.5, 0.5, 2.0, 1.0]))
        m[1].weight.copy_(torch.tensor([1.1, 0.9, 1.2, 1.0]))
        m[1].bias.copy_(torch.tensor([0.0, 0.1, -0.1, 0.2]))
    pm = PyTorchModel(m)
    cfg = FFConfig()
    cfg.only_data_parallel = True
    cfg.use_bf16_compute = False  # f32 conv for tight numeric check
    ff = FFModel(cfg)
    x_t = ff.create_tensor((2, 3, 8, 8), name="x")
    outs = pm.torch_to_ff(ff, [x_t])
    ff.compile(SGDOptimizer(0.01), "identity", [], output_tensor=outs[0])
    pm.copy_weights(ff)
    x = np.random.default_rng(3).normal(size=(2, 3, 8, 8)) \
        .astype(np.float32)
    fwd = ff.executor.make_eval_forward() \
        if hasattr(ff.executor, "make_eval_forward") \
        else ff.executor.make_forward()
    y = np.asarray(fwd(ff.params, ff.state, {"x": x}))
    with torch.no_grad():
        ref = m(torch.from_numpy(x)).numpy()
    np.testing.assert_allclose(y, ref, atol=2e-3, rtol=2e-3)


def test_torch_fx_file_roundtrip(tmp_path):
    """torch_to_file -> file_to_ff round-trip (reference
    ``torch_to_file``/``file_to_ff``, model.py:2408-2604): the rebuilt
    graph trains and matches the direct import's forward numerics."""
    import numpy as np
    torch = pytest.importorskip("torch")
    import torch.nn as nn
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.frontends.torch_fx import PyTorchModel

    m = nn.Sequential(nn.Linear(12, 24), nn.ReLU(),
                      nn.Linear(24, 5)).eval()
    pm = PyTorchModel(m)
    path = str(tmp_path / "graph.json")

    cfg = FFConfig()
    cfg.only_data_parallel = True
    ff1 = FFModel(cfg)
    x1 = ff1.create_tensor((4, 12), name="x")
    outs1 = pm.torch_to_file(ff1, [x1], path)
    ff1.compile(SGDOptimizer(0.01), "identity", [], output_tensor=outs1[0])
    pm.copy_weights(ff1)

    # rebuild WITHOUT touching torch / the traced module
    ff2 = FFModel(FFConfig())
    ff2.config.only_data_parallel = True
    x2 = ff2.create_tensor((4, 12), name="x")
    outs2 = PyTorchModel.file_to_ff(path, ff2, [x2])
    assert [l.op_type for l in ff2.layers] == \
        [l.op_type for l in ff1.layers]
    ff2.compile(SGDOptimizer(0.01), "identity", [], output_tensor=outs2[0])
    for lname, lp in ff1.params.items():
        for wname, w in lp.items():
            ff2.set_weights(lname, wname, np.asarray(w))
    x = np.random.default_rng(4).normal(size=(4, 12)).astype(np.float32)
    y1 = np.asarray(ff1.executor.make_forward()(ff1.params, ff1.state,
                                                {"x": x}))
    y2 = np.asarray(ff2.executor.make_forward()(ff2.params, ff2.state,
                                                {"x": x}))
    np.testing.assert_allclose(y1, y2, atol=1e-6)


def test_keras_maximum_minimum():
    """Keras merge-layer parity: Maximum/Minimum complete the reference's
    layer set (``python/flexflow/keras/layers/merge.py``)."""
    from flexflow_tpu.frontends import keras
    a = keras.Input((8,), name="a")
    b = keras.Input((8,), name="b")
    mx = keras.Maximum()([a.tensor, b.tensor])
    mn = keras.Minimum()([a.tensor, b.tensor])
    merged = keras.Concatenate()([mx, mn])
    out = keras.Softmax()(keras.Dense(2)(merged))
    model = keras.Model(inputs=[a, b], outputs=out)
    cfg = FFConfig()
    cfg.only_data_parallel = True
    model.compile("sgd", "sparse_categorical_crossentropy", [],
                  config=cfg, batch_size=16)
    rng = np.random.default_rng(0)
    xa = rng.normal(size=(32, 8)).astype(np.float32)
    xb = rng.normal(size=(32, 8)).astype(np.float32)
    ys = rng.integers(0, 2, 32).astype(np.int32)
    hist = model.fit([xa, xb], ys, epochs=2, verbose=False)
    assert np.isfinite(hist[-1]["loss"])


@pytest.mark.slow
def test_torch_fx_huggingface_mt5():
    """Import a real HF MT5Model (encoder-decoder: T5LayerNorm fusion,
    relative position bias, cross attention) and match torch numerics
    (reference HF mT5 path, ``python/flexflow/torch/model.py:2408``)."""
    pytest.importorskip("transformers")
    from transformers import MT5Config, MT5Model
    from flexflow_tpu.ffconst import OperatorType
    from flexflow_tpu.frontends.torch_fx import PyTorchModel

    tcfg = MT5Config(vocab_size=96, d_model=32, d_kv=8, d_ff=64,
                     num_layers=2, num_heads=4, dropout_rate=0.0)
    torch.manual_seed(0)
    m = MT5Model(tcfg).eval()
    pm = PyTorchModel(m, is_hf_model=True, batch_size=2)
    cfg = FFConfig()
    cfg.only_data_parallel = True
    cfg.use_bf16_compute = False
    ff = FFModel(cfg)
    ids = ff.create_tensor((2, 16), dtype="int32", name="input_ids")
    dids = ff.create_tensor((2, 8), dtype="int32",
                            name="decoder_input_ids")
    outs = pm.torch_to_ff(ff, [ids, dids])
    assert outs[0].shape == (2, 8, 32)
    assert any(l.op_type == OperatorType.OP_RMSNORM for l in ff.layers)
    ff.compile(SGDOptimizer(0.01), "identity", [], output_tensor=outs[0])
    pm.copy_weights(ff)
    x = np.random.default_rng(0).integers(0, 96, size=(2, 16)) \
        .astype(np.int32)
    dx = np.random.default_rng(1).integers(0, 96, size=(2, 8)) \
        .astype(np.int32)
    y = np.asarray(ff.executor.make_forward()(
        ff.params, ff.state, {"input_ids": x, "decoder_input_ids": dx}))
    with torch.no_grad():
        ref = m(input_ids=torch.from_numpy(x.astype(np.int64)),
                decoder_input_ids=torch.from_numpy(dx.astype(np.int64))) \
            .last_hidden_state.numpy()
    np.testing.assert_allclose(y, ref, atol=5e-3, rtol=5e-3)
