"""Parameter metadata + logical-axis sharding resolution.

Models in ``repro.models`` build parameter pytrees whose leaves are
:class:`Param` — a value (concrete array or ``ShapeDtypeStruct``) tagged
with *logical* axis names ("embed", "heads", "vocab", ...).  A per-family
rules table maps logical names onto physical mesh axes; :func:`resolve_spec`
turns the tag into a ``PartitionSpec`` and *downgrades* any entry whose
mesh-axis product does not divide the corresponding dimension (recording
the downgrade so callers can report it).  This is the same logical-axis
approach MaxText/T5X use, kept dependency-free.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisName = str | None
LogicalAxes = tuple[AxisName, ...]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Param:
    """A parameter value tagged with logical axis names.

    ``axes`` must have one entry per value dimension; ``None`` marks a
    dimension that is never sharded (e.g. small biases, norm scales).
    """

    value: Any
    axes: LogicalAxes = ()

    def tree_flatten(self):
        return (self.value,), self.axes

    @classmethod
    def tree_unflatten(cls, axes, children):
        return cls(children[0], axes)

    @property
    def shape(self):
        return self.value.shape

    @property
    def dtype(self):
        return self.value.dtype


def is_param(x) -> bool:
    return isinstance(x, Param)


def unzip(tree):
    """Split a Param tree into (value_tree, axes_tree) with identical structure.
    Non-Param leaves pass through (their axes default to all-None)."""
    values = jax.tree.map(lambda p: p.value if is_param(p) else p, tree,
                          is_leaf=is_param)
    axes = jax.tree.map(
        lambda p: p.axes if is_param(p)
        else (None,) * getattr(p, "ndim", 0), tree, is_leaf=is_param)
    return values, axes


def zip_params(values, axes):
    return jax.tree.map(Param, values, axes,
                        is_leaf=lambda x: not isinstance(x, (dict, list, tuple)))


# ---------------------------------------------------------------------------
# Logical-axis rules
# ---------------------------------------------------------------------------

# A rules table maps logical axis name -> mesh axis name(s).  Values may be
# None (replicate), a str, or a tuple of str (sharded over several mesh axes).
Rules = Mapping[str, Any]

# Default rules for a (pod?, data, model) mesh.  "fsdp" entries shard the
# weight-stationary dimension over the data axes (ZeRO-3 style); they are
# enabled by `with_fsdp`.
LM_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "seq": None,
    "seq_shard": ("pod", "data"),   # sequence-sharded activations / KV (long ctx)
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "moe_mlp": "model",             # per-expert hidden dim (TP-in-expert)
    "experts": "model",             # expert-parallel stacking dim
    "vocab": "model",
    "latent": None,                 # MLA latent dims stay replicated
    "classes": None,
    "channels": "model",            # conv output channels
    "in_channels": None,
    "spatial": None,
    "patch": None,
}

VISION_RULES = dict(LM_RULES)
DIFFUSION_RULES = dict(LM_RULES)


def with_fsdp(rules: Rules, axes=("pod", "data")) -> dict[str, Any]:
    """Return rules where weight 'embed'/'in_channels' dims are data-sharded
    (fully-sharded data parallel for the parameter/optimizer state)."""
    out = dict(rules)
    out["embed"] = axes
    out["in_channels"] = axes
    return out


# Pure-FSDP rules: the model axis is repurposed as extra data parallelism
# (ZeRO-3).  No tensor parallelism => no per-layer activation all-reduces;
# weights are all-gathered per use instead.  The right regime for models
# whose layers are small relative to the batch (tinyllama — §Perf).
FSDP_DP_RULES: dict[str, Any] = {
    "batch": ("pod", "data", "model"),
    "seq": None,
    "seq_shard": ("pod", "data", "model"),
    "embed": ("pod", "data", "model"),
    "heads": None, "kv_heads": None, "head_dim": None,
    "mlp": None, "moe_mlp": None, "experts": None,
    "vocab": None, "latent": None, "classes": None,
    "channels": None, "in_channels": ("pod", "data", "model"),
    "spatial": None, "patch": None,
}


@dataclasses.dataclass
class Downgrade:
    path: str
    dim: int
    logical: str
    wanted: Any
    reason: str


def _mesh_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        return math.prod(mesh.shape[a] for a in axis)
    return mesh.shape[axis]


def resolve_spec(shape: Sequence[int], axes: LogicalAxes, rules: Rules,
                 mesh: Mesh, path: str = "",
                 downgrades: list[Downgrade] | None = None,
                 used_axes: set | None = None) -> P:
    """Map logical axes -> PartitionSpec, dropping non-divisible entries.

    For tuple mesh axes we try the longest divisible prefix, e.g. a batch
    of 4 on (("pod","data")) with pod=2, data=16 resolves to ("pod",).
    A mesh axis may appear at most once in a spec; duplicates replicate.
    """
    if downgrades is None:
        downgrades = []
    used = set() if used_axes is None else used_axes
    entries: list[Any] = []
    if len(axes) != len(shape):
        raise ValueError(f"{path}: axes {axes} rank != shape {shape}")
    for d, (dim, name) in enumerate(zip(shape, axes)):
        if name is None or name not in rules or rules[name] is None:
            entries.append(None)
            continue
        want = rules[name]
        cand = tuple(want) if isinstance(want, (tuple, list)) else (want,)
        # Drop mesh axes absent from this mesh or already used by another
        # dim of this param.
        cand = tuple(a for a in cand if a in mesh.shape and a not in used)
        # Longest prefix of the candidate tuple that divides dim.
        chosen: tuple[str, ...] = ()
        for k in range(len(cand), 0, -1):
            prefix = cand[:k]
            if dim % _mesh_size(mesh, prefix) == 0:
                chosen = prefix
                break
        if chosen != (tuple(want) if isinstance(want, (tuple, list)) else (want,)):
            downgrades.append(Downgrade(path, d, name, want,
                                        f"dim {dim} not divisible / axis reuse"))
        if not chosen:
            entries.append(None)
        elif len(chosen) == 1:
            entries.append(chosen[0])
            used.add(chosen[0])
        else:
            entries.append(chosen)
            used.update(chosen)
    # Trim trailing Nones (canonical PartitionSpec form).
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def tree_specs(axes_tree, shapes_tree, rules: Rules, mesh: Mesh,
               collect_downgrades: list[Downgrade] | None = None):
    """Build a PartitionSpec tree matching the param tree."""
    paths_axes = jax.tree.flatten_with_path(
        axes_tree, is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))
    flat_axes, treedef = paths_axes
    flat_shapes = [tuple(v.shape) for v in jax.tree.leaves(shapes_tree)]
    if len(flat_axes) != len(flat_shapes):
        raise ValueError(f"axes/shapes leaf mismatch: {len(flat_axes)} vs "
                         f"{len(flat_shapes)}")
    specs = []
    for (path, axes), shape in zip(flat_axes, flat_shapes):
        pstr = jax.tree_util.keystr(path)
        specs.append(resolve_spec(shape, axes, rules, mesh, pstr,
                                  collect_downgrades))
    return jax.tree.unflatten(treedef, specs)


def tree_shardings(axes_tree, shapes_tree, rules: Rules, mesh: Mesh,
                   collect_downgrades: list[Downgrade] | None = None):
    specs = tree_specs(axes_tree, shapes_tree, rules, mesh, collect_downgrades)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def named_sharding(mesh: Mesh, *spec_entries) -> NamedSharding:
    return NamedSharding(mesh, P(*spec_entries))


def batch_spec(mesh: Mesh, batch: int, rank: int, rules: Rules = LM_RULES) -> P:
    """PartitionSpec for a batched activation: shard dim 0 over data axes
    (with divisibility auto-downgrade), replicate the rest."""
    return resolve_spec((batch,) + (1,) * (rank - 1),
                        ("batch",) + (None,) * (rank - 1), rules, mesh)


# ---------------------------------------------------------------------------
# Abstract init (no allocation) — used by the dry-run.
# ---------------------------------------------------------------------------

def abstract_init(init_fn: Callable, *args, **kwargs):
    """Run an init function under eval_shape: Param leaves keep their logical
    axes (aux data) while values become ShapeDtypeStructs."""
    return jax.eval_shape(lambda: init_fn(*args, **kwargs))


def param_count(values_tree) -> int:
    return sum(int(math.prod(v.shape)) for v in jax.tree.leaves(values_tree))


def param_bytes(values_tree) -> int:
    return sum(int(math.prod(v.shape)) * jnp.dtype(v.dtype).itemsize
               for v in jax.tree.leaves(values_tree))
