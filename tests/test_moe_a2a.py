"""all-to-all expert-parallel MoE vs dense-dispatch baseline (4-dev mesh)."""
import json
import os
import subprocess
import sys

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
import jax
import jax.numpy as jnp
import dataclasses
from repro.configs import get_smoke
from repro.models import moe as dense_moe
from repro.models import moe_a2a
from repro.models.common import ParamCollector, make_rules

mesh = jax.make_mesh((2, 2), ("data", "model"))
cfg = get_smoke("moonshot-v1-16b-a3b")
# huge capacity → no drops on either path → outputs must match
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
    cfg.moe, capacity_factor=8.0))
col = ParamCollector(key=jax.random.key(0))
dense_moe.init_moe(col, cfg, 1)
p = jax.tree.map(lambda a: a[0], col.params)
rng = np.random.default_rng(0)
B, S, d = 4, 8, cfg.d_model
x = jnp.asarray(rng.normal(0, 0.5, (B, S, d))).astype(jnp.bfloat16)
rules = make_rules(sizes=dict(mesh.shape))
rules = dataclasses.replace(rules, mesh=mesh)

with mesh:
    y_ref, aux_ref = jax.jit(
        lambda p, x: dense_moe.apply_moe(p, x, rules, cfg))(p, x)
    y_a2a, aux_a2a = jax.jit(
        lambda p, x: moe_a2a.apply_moe_a2a(p, x, rules, cfg))(p, x)
    y_i8, _ = jax.jit(
        lambda p, x: moe_a2a.apply_moe_a2a(p, x, rules, cfg,
                                           int8_dispatch=True))(p, x)
err = float(jnp.max(jnp.abs(y_a2a.astype(jnp.float32)
                            - y_ref.astype(jnp.float32))))
scale = float(jnp.max(jnp.abs(y_ref.astype(jnp.float32)))) + 1e-9
# gradient path through the a2a island
with mesh:
    g = jax.jit(jax.grad(lambda p: jnp.sum(
        moe_a2a.apply_moe_a2a(p, x, rules, cfg)[0].astype(jnp.float32) ** 2)))(p)
gn = float(sum(jnp.sum(jnp.abs(l.astype(jnp.float32)))
               for l in jax.tree.leaves(g)))
err_i8 = float(jnp.max(jnp.abs(y_i8.astype(jnp.float32)
                               - y_ref.astype(jnp.float32))))
print("RESULT:" + json.dumps({
    "rel_err": err / scale,
    "rel_err_int8": err_i8 / scale,
    "aux_rel": abs(float(aux_a2a) - float(aux_ref)) / (abs(float(aux_ref)) + 1e-9),
    "grad_finite": bool(np.isfinite(gn)) and gn > 0}))
"""


def test_a2a_matches_dense_dispatch():
    proc = subprocess.run([sys.executable, "-c", _SCRIPT],
                          capture_output=True, text=True, timeout=600,
                          env=dict(os.environ, PYTHONPATH="src"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    out = json.loads(line[0][len("RESULT:"):])
    assert out["rel_err"] < 0.05, out
    assert out["rel_err_int8"] < 0.10, out  # int8 dispatch quantization
    assert out["aux_rel"] < 0.05, out
    assert out["grad_finite"], out
