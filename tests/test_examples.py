"""Run the example suite (subprocess, CPU-8) — the reference treats its
examples AS the integration suite (``tests/multi_gpu_tests.sh``)."""
import os
import subprocess
import sys

import pytest

EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples")

FAST = [
    ("mnist_mlp.py", ["-b", "16", "--only-data-parallel"]),
    ("alexnet_cifar10.py", ["-b", "8", "--only-data-parallel"]),
    ("dlrm.py", ["-b", "16", "--only-data-parallel"]),
    ("xdl.py", ["-b", "16", "--only-data-parallel"]),
    ("mixture_of_experts.py", ["-b", "16", "--only-data-parallel"]),
    ("candle_uno.py", ["-b", "8", "--only-data-parallel"]),
    ("transformer.py", ["-b", "4", "--only-data-parallel"]),
    ("nmt.py", ["-b", "8", "--only-data-parallel"]),
    ("llama.py", ["-b", "8", "--only-data-parallel"]),
    ("generate_lm.py", ["--steps", "40", "--serve"]),
]

SLOW = [
    ("bert.py", ["-b", "2", "--only-data-parallel"]),
    ("gpt2.py", ["-b", "2", "--only-data-parallel"]),
    ("resnext50.py", ["-b", "2", "--only-data-parallel"]),
    ("inception.py", ["-b", "2", "--only-data-parallel"]),
    # searched strategy end-to-end (the osdi22ae A/B shape, single run)
    ("mnist_mlp.py", ["-b", "16", "--budget", "4"]),
]

# examples with their own success marker instead of a samples/s line
SLOW_MARKED = [
    ("llama_serve_hf.py", ["--beams", "2", "--serve", "--oneshot"],
     "matches local decode"),
    ("decode_bench.py", ["--seq", "96", "--hidden", "64", "--layers", "2"],
     "incremental ms/token"),
]


def _run(script, args, expect="samples/s"):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    r = subprocess.run(
        [sys.executable, script] + args, cwd=EXAMPLES, env=env,
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"{script}: {r.stdout}\n{r.stderr}"
    assert expect in r.stdout, r.stdout


@pytest.mark.parametrize("script,args", FAST,
                         ids=[s for s, _ in FAST])
def test_example_fast(script, args):
    _run(script, args)


@pytest.mark.slow
@pytest.mark.parametrize("script,args", SLOW,
                         ids=[f"{s}-{i}" for i, (s, _) in enumerate(SLOW)])
def test_example_slow(script, args):
    _run(script, args)


@pytest.mark.slow
@pytest.mark.parametrize("script,args,expect", SLOW_MARKED,
                         ids=[s for s, _, _ in SLOW_MARKED])
def test_example_slow_marked(script, args, expect):
    _run(script, args, expect)
