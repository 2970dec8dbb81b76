"""Logical-axis -> mesh-axis sharding rules (MaxText-style).

Rules:
  embed (d_model)        -> 'data'   (FSDP/ZeRO: params+opt reduce over data)
  vocab / heads / kv_heads / mlp / experts / ssm_inner -> 'model' (TP/EP)
  batch                  -> ('pod','data')
  decode KV cache        -> batch axes; long-context (B==1) -> sequence over
                            'data' (sequence parallelism / flash-decoding)
A dimension falls back to replication when not divisible by its mesh axis
(e.g. gemma3's 4 heads on a 16-way model axis — see the roofline tables
in docs/REPRODUCTION.md).
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from repro.models.params import ParamSpec, is_spec

LOGICAL_RULES: dict[str | None, str | None] = {
    "embed": "data",
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "experts": "model",
    "ssm_inner": "model",
    None: None,
}


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name]


def spec_pspec(mesh: Mesh, spec: ParamSpec) -> P:
    out: list = []
    used: set[str] = set()   # a mesh axis may shard at most one dim;
    for dim, logical in zip(spec.shape, spec.axes):  # first dim wins (EP
        mesh_ax = LOGICAL_RULES.get(logical)         # beats TP on experts)
        if mesh_ax is not None and mesh_ax in mesh.axis_names \
                and mesh_ax not in used \
                and dim % _axis_size(mesh, mesh_ax) == 0:
            out.append(mesh_ax)
            used.add(mesh_ax)
        else:
            out.append(None)
    return P(*out)


def param_shardings(mesh: Mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, spec_pspec(mesh, s)),
        spec_tree, is_leaf=is_spec)


def constrain_like_params(tree, spec_tree):
    """Constrain a param-shaped tree (e.g. grads) to the params' sharding.

    Keeping per-microbatch grads and the accumulation buffer SHARDED is what
    turns the naive full-size-all-reduce-then-slice gradient path into
    sharded accumulation (reduce-scatter-like); see docs/REPRODUCTION.md.
    No-op outside a mesh context.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return tree
    flat, treedef = jax.tree_util.tree_flatten(tree)
    specs = jax.tree_util.tree_leaves(spec_tree, is_leaf=is_spec)
    out = [jax.lax.with_sharding_constraint(g, spec_pspec(mesh, s))
           for g, s in zip(flat, specs)]
    return jax.tree_util.tree_unflatten(treedef, out)


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_pspec(mesh: Mesh, ndim: int, *, batch_dim: int = 0) -> P:
    parts: list = [None] * ndim
    parts[batch_dim] = batch_axes(mesh)
    return P(*parts)


def data_sharding(mesh: Mesh, ndim: int, *, batch_dim: int = 0):
    return NamedSharding(mesh, data_pspec(mesh, ndim, batch_dim=batch_dim))


def cache_shardings(mesh: Mesh, cfg, cache_tree, *, seq_shard: bool):
    """Decode-cache shardings. seq_shard=True (long-context, batch==1):
    shard the KV sequence dim over 'data' (sequence parallelism); otherwise
    shard batch. kv heads / ssm heads go to 'model' when divisible."""
    bax = batch_axes(mesh)

    def one(path, sds):
        # rightmost-anchored so stacked layouts (+leading n_rep dim) work
        name = jax.tree_util.keystr(path)
        shape = sds.shape
        n = len(shape)
        if "'length'" in name or n < 3:
            return NamedSharding(mesh, P())
        parts: list = [None] * n
        if "'k'" in name or "'v'" in name:
            # (..., B, cap, hkv, hd)
            if seq_shard and "data" in mesh.axis_names \
                    and shape[-3] % _axis_size(mesh, "data") == 0:
                parts[-3] = "data"
            elif bax and shape[-4] % _mesh_prod(mesh, bax) == 0:
                parts[-4] = bax
            if shape[-2] % _axis_size(mesh, "model") == 0:
                parts[-2] = "model"
        elif "'ssm'" in name:
            # (..., B, H, N, P)
            if bax and shape[-4] % _mesh_prod(mesh, bax) == 0:
                parts[-4] = bax
            if shape[-3] % _axis_size(mesh, "model") == 0:
                parts[-3] = "model"
        elif "'conv'" in name:
            # (..., B, K-1, conv_dim)
            if bax and shape[-3] % _mesh_prod(mesh, bax) == 0:
                parts[-3] = bax
            if shape[-1] % _axis_size(mesh, "model") == 0:
                parts[-1] = "model"
        return NamedSharding(mesh, P(*parts))

    return jax.tree_util.tree_map_with_path(one, cache_tree)


def _mesh_prod(mesh: Mesh, axes: tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= _axis_size(mesh, a)
    return n


def opt_state_shardings(mesh: Mesh, spec_tree, opt_state_shapes):
    """Optimizer state inherits the param sharding where shapes match;
    factored Adafactor rows/cols inherit the matching prefix; scalars
    replicate."""
    param_shards = {}
    for path, s in jax.tree_util.tree_leaves_with_path(
            spec_tree, is_leaf=is_spec):
        param_shards[jax.tree_util.keystr(path)] = (s.shape,
                                                    spec_pspec(mesh, s))

    def one(path, sds):
        name = jax.tree_util.keystr(path)
        shape = sds.shape
        for pname, (pshape, pspec) in param_shards.items():
            if pname in name:
                if shape == pshape:
                    return NamedSharding(mesh, pspec)
                if shape == pshape[:-1]:   # adafactor row stats
                    return NamedSharding(mesh, P(*pspec[:-1]))
                if len(pshape) >= 2 and shape == pshape[:-2] + pshape[-1:]:
                    return NamedSharding(mesh, P(*(tuple(pspec[:-2])
                                                   + (pspec[-1],))))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(one, opt_state_shapes)
