"""API-surface tests: builder methods, dataloader parity path, name
collisions, weights round-trip, flag parsing."""
import functools
import os
import re

import numpy as np
import pytest

from flexflow_tpu import (ActiMode, AggrMode, DataType, FFConfig, FFModel,
                          SGDOptimizer)


def test_create_data_loader_path():
    """Reference-parity flow: explicit label tensor + create_data_loader."""
    cfg = FFConfig()
    cfg.batch_size = 32
    cfg.only_data_parallel = True
    ff = FFModel(cfg)
    x = ff.create_tensor((32, 10), name="x")
    label = ff.create_tensor((32, 1), DataType.DT_INT32, name="label")
    out = ff.softmax(ff.dense(x, 4))
    ff.compile(SGDOptimizer(0.1), "sparse_categorical_crossentropy",
               ["accuracy"])
    assert ff.label_tensor is label
    rng = np.random.default_rng(0)
    xs = rng.normal(size=(128, 10)).astype(np.float32)
    ys = rng.integers(0, 4, size=(128, 1)).astype(np.int32)
    ff.create_data_loader(x, xs)
    ff.create_data_loader(label, ys)
    hist = ff.fit(epochs=1, verbose=False)
    assert "loss" in hist[0]


def test_duplicate_layer_names_uniquified():
    ff = FFModel(FFConfig())
    x = ff.create_tensor((8, 4))
    ff.dense(x, 4, name="fc")
    l2 = ff._add_layer.__self__  # noqa - just build another
    t2 = ff.dense(x, 8, name="fc")
    names = [l.name for l in ff.layers]
    assert len(names) == len(set(names)), names


def test_weights_roundtrip():
    cfg = FFConfig()
    cfg.only_data_parallel = True
    ff = FFModel(cfg)
    x = ff.create_tensor((16, 8), name="x")
    out = ff.softmax(ff.dense(x, 4, name="fc"))
    ff.compile(SGDOptimizer(0.1), "sparse_categorical_crossentropy", [])
    w = ff.get_weights("fc", "kernel")
    assert w.shape == (8, 4)
    w2 = np.ones_like(w)
    ff.set_weights("fc", "kernel", w2)
    assert np.allclose(ff.get_weights("fc", "kernel"), 1.0)


def test_parse_args_reference_flags(caplog):
    """The reference's spellings parse. Those that select nothing here
    (``-ll:gpu``, ``--fusion``, ...) are taken with their values, leave
    the flags around them alone and are named in one debug line."""
    import logging
    with caplog.at_level(logging.DEBUG, logger="flexflow_tpu"):
        cfg = FFConfig.parse_args(
            ["-e", "3", "-b", "128", "--lr", "0.02", "-d", "/data/x",
             "--budget", "30", "--only-data-parallel", "-ll:gpu", "4",
             "-ll:fsize", "14000", "--fusion", "--compgraph", "g.dot",
             "--enable-parameter-parallel", "--seed", "7"])
    assert cfg.epochs == 3
    assert cfg.batch_size == 128
    assert cfg.learning_rate == 0.02
    assert cfg.search_budget == 30
    assert cfg.only_data_parallel
    assert cfg.device_mem_mb == 14000
    assert cfg.seed == 7
    (line,) = [r.getMessage() for r in caplog.records
               if "ignored" in r.getMessage()]
    assert line.endswith("-d /data/x, -ll:gpu 4, --fusion, "
                         "--compgraph g.dot, --enable-parameter-parallel")
    for gone in ("workers_per_node", "perform_fusion", "dataset_path",
                 "enable_parameter_parallel"):
        assert not hasattr(cfg, gone)


def test_kdim_vdim_attention():
    """kdim != embed_dim must work (qProjSize == kdim, ref attention.cc)."""
    cfg = FFConfig()
    cfg.only_data_parallel = True
    ff = FFModel(cfg)
    q = ff.create_tensor((4, 6, 64), name="q")
    out = ff.multihead_attention(q, q, q, embed_dim=64, num_heads=4,
                                 kdim=32, vdim=32)
    red = ff.mean(out, [1, 2])
    ff.compile(SGDOptimizer(0.01), "identity", [])
    fwd = ff.executor.make_forward()
    batch = {"q": np.random.default_rng(0).normal(size=(4, 6, 64))
             .astype(np.float32)}
    y = fwd(ff.params, ff.state, batch)
    assert y.shape == (4,)


# ---------------------------------------------------------------------------
# the documents name only paths that exist
# ---------------------------------------------------------------------------
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DOCUMENTS = ["README.md"] + sorted(
    "docs/" + f for f in os.listdir(os.path.join(_REPO, "docs"))
    if f.endswith(".md"))
_PATH_SUFFIXES = (".py", ".md", ".json", ".jsonl", ".sh", ".toml", ".cc",
                  ".yml", ".ipynb", "/")
# what a run leaves behind (``.gitignore``) is named as a place, not a file
_GENERATED = (".ffcache/", ".jax_cache/", ".bench_trace/", "chiprun_out/",
              "bench_results/", "_clean_tree/", "_parent/", "_scratch/")
# the files of a checkpoint directory, and one of the reference's sources
_NOT_OURS = {"manifest.json", "meta.json", "model.cc"}


def _named_paths(text):
    """Backticked spans and link targets that read as a path of this
    repo: one token with a file suffix (or a trailing slash), no
    placeholder, no URL, nothing outside the checkout."""
    spans = re.findall(r"`([^`\n]+)`", text)
    spans += re.findall(r"\]\(([^)\s]+)\)", text)
    for span in spans:
        tok = span.strip().split("::")[0].split("#")[0]
        tok = re.sub(r":\d+(-\d+)?$", "", tok)         # file.py:12-30
        if (not tok or re.search(r"[\s*<>{}$|=,()\[\]]", tok)
                or "://" in tok or tok.startswith(("/", "~", "-", "."))
                or not tok.endswith(_PATH_SUFFIXES)
                or tok.startswith(_GENERATED) or tok in _NOT_OURS):
            continue
        yield tok


@functools.lru_cache(maxsize=None)
def _tree():
    """Every file and directory of the checkout, as ``/``-led paths."""
    found = set()
    for root, dirs, files in os.walk(_REPO):
        dirs[:] = [d for d in dirs if d != ".git" and d != "__pycache__"
                   and not (d + "/").startswith(_GENERATED)]
        rel = os.path.relpath(root, _REPO)
        lead = "/" if rel == "." else f"/{rel}/"
        found.update(lead + f for f in files)
        found.update(lead + d + "/" for d in dirs)
    return found


@pytest.mark.parametrize("document", _DOCUMENTS)
def test_documents_name_only_paths_that_exist(document):
    """Every repo path the README or a page of ``docs/`` names is the
    tail of a path in the tree (the pages write ``ops/nn_ops.py`` for
    ``flexflow_tpu/ops/nn_ops.py`` and ``fleet/router.py`` inside the
    serving section)."""
    with open(os.path.join(_REPO, document)) as f:
        text = f.read()
    tree = _tree()
    missing = sorted({tok for tok in _named_paths(text)
                      if not any(p.endswith("/" + tok) for p in tree)})
    assert not missing, f"{document} names paths that do not exist: " \
                        f"{missing}"
