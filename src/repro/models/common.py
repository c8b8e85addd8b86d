"""Model substrate: logical-axis sharding, norms, projections, rotary.

Sharding follows the MaxText/t5x pattern: every parameter and key
activation carries *logical* axis names; a rules table maps logical →
mesh axes per deployment. Parameters are plain pytrees (dict of arrays);
a parallel tree of logical-axes tuples is produced by the same init
functions, so `jax.eval_shape` of init + the axes tree gives allocation-
free shardings for the dry run.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import compat

# --------------------------------------------------------------- rules
@dataclasses.dataclass(frozen=True)
class Rules:
    """logical axis → mesh axis, separately for activations and params.

    Params: "embed"/state axes shard over the FSDP axis ("data"), head/
    mlp/vocab/expert axes over "model" (TP/EP); the same logical name can
    therefore map differently for a [V, d] weight (d → data) and a
    [B, S, d] activation (d → replicated). `sizes` carries the mesh axis
    sizes so constraints silently drop on non-divisible dims (e.g. 8 KV
    heads on a 16-wide model axis) instead of forcing SPMD full-remat.
    """
    act: dict
    param: dict
    sizes: dict
    mesh: Any = None  # set when shard_map islands (moe_a2a) are in play


PROD_SIZES = {"pod": 2, "data": 16, "model": 16}


def make_rules(multi_pod: bool = False, long_context: bool = False,
               fsdp: bool = True, sizes: dict | None = None,
               decode: bool = False, mesh=None, ep2d: bool = False,
               dp_only: bool = False) -> Rules:
    if dp_only:
        # small models on a big mesh: 16-way TP costs ~4 activation
        # all-reduces per layer for ~no memory benefit. Pure DP over the
        # whole mesh + 2D-FSDP params eliminates them (§Perf cell A).
        allax = ("pod", "data", "model") if multi_pod else ("data", "model")
        act = {"batch": allax, "seq": None, "kv_seq": None, "embed": None,
               "heads": None, "kv_heads": None, "mlp": None, "vocab": None,
               "experts": None}
        param = {"embed": ("data", "model") if fsdp else None,
                 "heads": None, "kv_heads": None, "mlp": None,
                 "vocab": None, "experts": None, "layers": None,
                 "batch": allax, "kv_seq": None}
        return Rules(act=act, param=param, sizes=sizes or dict(PROD_SIZES),
                     mesh=mesh)
    dp = ("pod", "data") if multi_pod else "data"
    act = {
        "batch": dp, "seq": None, "kv_seq": None, "embed": None,
        "heads": "model", "kv_heads": "model", "mlp": "model",
        "vocab": "model", "experts": "model",
    }
    if decode:  # batch shards "data"; KV sequence takes the model axis
        act.update(kv_seq="model")
    if long_context:  # batch=1 decode: shard the KV sequence instead
        act.update(batch=None, kv_seq=dp)
    param = {
        "embed": "data" if fsdp else None,
        "heads": "model", "kv_heads": "model", "mlp": "model",
        "vocab": "model", "layers": None,
        # a2a expert parallelism: experts shard over the whole EP mesh so
        # every device owns whole experts and no gather/reshard happens at
        # the shard_map boundary (the _dedupe pass drops the now-redundant
        # embed/mlp mappings on expert weights automatically)
        "experts": ("data", "model") if ep2d else "model",
        # decode caches reuse the param table for their specs:
        "batch": act["batch"], "kv_seq": act["kv_seq"],
    }
    return Rules(act=act, param=param, sizes=sizes or dict(PROD_SIZES),
                 mesh=mesh)


def spec_for(axes: tuple, table: dict) -> P:
    return P(*[table.get(a) if a is not None else None for a in axes])


def _divisible(dim: int, mapped, sizes: dict) -> bool:
    if mapped is None:
        return True
    axes = mapped if isinstance(mapped, tuple) else (mapped,)
    total = 1
    for a in axes:
        total *= sizes.get(a, 1)
    return dim % total == 0


def _dedupe(mapped: list) -> list:
    """A mesh axis may appear once per spec; keep the first occurrence."""
    used: set = set()
    out = []
    for m in mapped:
        axes = m if isinstance(m, tuple) else (m,) if m else ()
        if any(a in used for a in axes):
            out.append(None)
            continue
        used.update(axes)
        out.append(m)
    return out


def constrain(x: jnp.ndarray, axes: tuple, rules: "Rules | None") -> jnp.ndarray:
    """Logical with_sharding_constraint (no-op when rules is None).

    Drops the constraint on any dim the mesh cannot divide evenly —
    forcing it would make GSPMD fall back to full rematerialization.
    """
    if rules is None:
        return x
    mapped = [rules.act.get(a) if a is not None else None for a in axes]
    mapped = [m if _divisible(x.shape[i], m, rules.sizes) else None
              for i, m in enumerate(mapped)]
    spec = P(*_dedupe(mapped))
    if rules.mesh is not None and rules.mesh.explicit_axes:
        # these models are laid out by GSPMD: constrain over the mesh's
        # devices with Auto axes (see compat.auto_mesh)
        spec = NamedSharding(compat.auto_mesh(rules.mesh), spec)
    return jax.lax.with_sharding_constraint(x, spec)


def tree_specs(axes_tree: Any, table: dict) -> Any:
    """Map a tree of logical-axes tuples → PartitionSpecs (param table)."""
    return jax.tree.map(lambda a: spec_for(a, table), axes_tree,
                        is_leaf=lambda a: isinstance(a, tuple))


def tree_specs_for_shapes(shapes_tree: Any, axes_tree: Any, table: dict,
                          sizes: dict) -> Any:
    """Like tree_specs but drops non-divisible dims (shape-aware)."""
    flat_s, treedef = jax.tree.flatten(shapes_tree)
    flat_a = treedef.flatten_up_to(axes_tree)

    def one(sds, axes):
        mapped = [table.get(a) if a is not None else None for a in axes]
        mapped = [m if _divisible(sds.shape[i], m, sizes) else None
                  for i, m in enumerate(mapped)]
        return P(*_dedupe(mapped))

    return jax.tree.unflatten(treedef, [one(s, a)
                                        for s, a in zip(flat_s, flat_a)])


# --------------------------------------------------------------- params
@dataclasses.dataclass
class ParamCollector:
    """Accumulates params + logical axes during init. One per model."""
    params: dict = dataclasses.field(default_factory=dict)
    axes: dict = dataclasses.field(default_factory=dict)
    key: jax.Array | None = None

    def sub(self, name: str) -> "ParamCollector":
        p, a = {}, {}
        self.params[name] = p
        self.axes[name] = a
        c = ParamCollector(p, a, None)
        c._parent = self  # noqa: SLF001 — key threading
        return c

    def next_key(self) -> jax.Array:
        root = self
        while getattr(root, "_parent", None) is not None:
            root = root._parent
        root.key, k = jax.random.split(root.key)
        return k

    def param(self, name: str, shape: tuple, axes: tuple, *, scale: float | None = None,
              dtype=jnp.bfloat16, init: str = "normal") -> jnp.ndarray:
        assert len(shape) == len(axes), (name, shape, axes)
        if init == "zeros":
            v = jnp.zeros(shape, dtype)
        elif init == "ones":
            v = jnp.ones(shape, dtype)
        else:
            fan_in = shape[0] if len(shape) > 1 else shape[-1]
            s = scale if scale is not None else fan_in ** -0.5
            v = (jax.random.normal(self.next_key(), shape, jnp.float32) * s).astype(dtype)
        self.params[name] = v
        self.axes[name] = axes
        return v


# --------------------------------------------------------------- layers
def rms_norm(x: jnp.ndarray, gamma: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * gamma


def init_rms(col: ParamCollector, name: str, dim: int):
    return col.param(name, (dim,), ("embed",), init="ones", dtype=jnp.bfloat16)


def dense(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """x [..., in] @ w [in, out] in bf16 with fp32 accumulation."""
    return jax.lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32).astype(x.dtype)


ACTIVATIONS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),  # nemotron squared-ReLU
}


def rotary(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0,
           rope_dim: int | None = None) -> jnp.ndarray:
    """RoPE over the last dim of x [..., S, H, dh] with positions [..., S]."""
    dh = x.shape[-1]
    rd = rope_dim or dh
    half = rd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # [..., S, half]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:rd]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([rot.astype(x.dtype), x[..., rd:]], axis=-1)
