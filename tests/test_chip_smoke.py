"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and
its phases, driven at a tiny size in interpret mode, pass their own
checks (the full-size run needs the chip)."""
import dataclasses
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402

#: the registry algebras at bounds the interpreter runs in seconds, each
#: with a dim that is not a block multiple
TINY_CELLS = {
    "gemm": dict(m=40, n=256, k=136),
    "batched_gemv": dict(m=8, k=80, n=256),
    "conv2d": dict(k=16, c=16, y=8, x=8),
    "depthwise_conv": dict(k=16, y=8, x=8),
    "mttkrp": dict(i=32, j=32, k=16, l=16),
    "ttmc": dict(i=8, j=8, k=8, l=8, m=8),
}


def tiny_model():
    from repro.configs.registry import get_config

    return dataclasses.replace(get_config(cs.SERVE_ARCH).reduced(),
                               dtype="bfloat16")


TINY_SERVE = dict(capacity=4, max_context=64, page_size=8,
                  prompt_lens=(4, 9, 20, 30, 4, 9, 20, 30), new_tokens=6,
                  checked=(1, 5), seed=0)


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                          capture_output=True, text=True, timeout=300,
                          cwd=ROOT, env=env)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU found" in proc.stderr


def test_generator_phase_tiny():
    # XLA:CPU cannot run some bf16 x bf16 -> f32 dots; fp32 here
    cs.generator_phase(TINY_CELLS, interpret=True, seed=0, dtype="float32")


def test_graph_phase_tiny():
    from repro.graph import from_model

    cs.graph_phase([("tiny layer",
                     from_model.transformer_layer_graph(l=32, d=32, f=64),
                     True)], interpret=True, seed=0)


def test_serve_phase_tiny():
    cs.serve_phase(tiny_model(), **TINY_SERVE)


def test_fp32_check_catches_wrong_tokens():
    import numpy as np

    cfg = tiny_model()
    params = cs.random_params(cfg, 0)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, (s,), dtype=np.int32)
               for s in (9, 20)]
    _, outs = cs.serve(cfg, params, prompts, capacity=4, max_context=64,
                       page_size=8, new_tokens=6, seed=0)
    shifted = [np.roll(o, 1) for o in outs]
    with pytest.raises(AssertionError, match="fp32 logit"):
        cs.check_against_fp32(cfg, params, prompts, shifted, (0, 1),
                              max_context=64, label="shifted")


_MESH_SCRIPT = """
import dataclasses, sys
sys.path.insert(0, {root!r})
import chip_smoke as cs
from repro.configs.registry import get_config
from repro.dist.engine import square_submesh
mesh = square_submesh(2)
cs.sharded_generator_phase({cells!r}, mesh, interpret=True, seed=0,
                           dtype="float32")
cfg = dataclasses.replace(get_config(cs.SERVE_ARCH).reduced(),
                          dtype="bfloat16")
cs.sharded_serve_phase(cfg, mesh, **{serve!r})
print("MESH OK")
"""


def test_mesh_phases_tiny_on_four_cpu_devices():
    # the --chips 4 path on fake CPU devices (a fresh interpreter: the
    # device count is fixed at jax import): sharded outputs span the
    # mesh, and the sharded decode step compiles once
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    script = _MESH_SCRIPT.format(root=ROOT, cells=TINY_CELLS,
                                 serve=TINY_SERVE)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=600,
                          cwd=ROOT, env=env)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    assert "devices=4" in proc.stdout and "MESH OK" in proc.stdout
