"""Explicit (STT-scheduled shard_map) collectives vs GSPMD-auto parity.

Runs in a subprocess with 8 fake devices (pytest's jax already holds 1).
Covers: forward logits, gradients (incl. mlp_manual/qkv_manual transposes),
and the MoE manual path (logits exact; aux is per-shard by design).
"""
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "@SRC@")
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models import init_params, split, forward
from repro.models import attention
from repro.train import trainer

attention.FULL_SCORES_MAX_LEN = 16   # force the chunked/manual path
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)

def grads_for(cfg, params, batch):
    with jax.sharding.set_mesh(mesh):
        return jax.jit(lambda p, b: jax.grad(
            lambda pp: trainer.loss_fn(pp, b, cfg)[0])(p))(params, batch)

def flat(tree):
    return np.concatenate([np.asarray(x).ravel() for x in jax.tree.leaves(tree)])

# --- dense (granite): forward + grads, incl. qkv/mlp_manual ---------------
base = dataclasses.replace(get_config("granite-8b").reduced(),
                           sequence_parallel=True, dtype="float32", d_ff=128)
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, base.vocab)
batch = {"tokens": toks, "targets": jnp.roll(toks, -1, 1)}
outs = {}
for flag in (False, True):
    cfg = dataclasses.replace(base, explicit_collectives=flag)
    params, _ = split(init_params(jax.random.PRNGKey(0), cfg))
    with jax.sharding.set_mesh(mesh):
        logits, _, _ = jax.jit(lambda p, t: forward(p, t, cfg))(params, toks)
    outs[flag] = (np.asarray(logits), flat(grads_for(cfg, params, batch)))
lerr = np.abs(outs[True][0] - outs[False][0]).max()
gerr = np.abs(outs[True][1] - outs[False][1]).max() / (
    np.abs(outs[False][1]).max() + 1e-12)
assert lerr < 2e-3, ("dense logits", lerr)
assert gerr < 1e-3, ("dense grads", gerr)
print("dense parity OK", lerr, gerr)

# --- moe (mixtral): logits exact; aux per-shard (documented) ---------------
base = dataclasses.replace(get_config("mixtral-8x22b").reduced(),
                           sequence_parallel=True, dtype="float32",
                           capacity_factor=8.0)
outs = {}
for flag in (False, True):
    cfg = dataclasses.replace(base, explicit_collectives=flag)
    params, _ = split(init_params(jax.random.PRNGKey(0), cfg))
    with jax.sharding.set_mesh(mesh):
        logits, aux, _ = jax.jit(lambda p, t: forward(p, t, cfg))(params, toks)
    outs[flag] = np.asarray(logits)
lerr = np.abs(outs[True] - outs[False]).max()
assert lerr < 2e-3, ("moe logits", lerr)
print("moe parity OK", lerr)
print("EXPLICIT_TP_PARITY_OK")
"""


def test_explicit_collectives_parity():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, "-c", SCRIPT.replace("@SRC@", src)],
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "EXPLICIT_TP_PARITY_OK" in proc.stdout
