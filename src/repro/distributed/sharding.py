"""Parameter / optimizer / cache partition specs.

2-D sharding: tensor-parallel over the "model" axis (heads / ffn / experts /
vocab) x fully-sharded (ZeRO-3 style) over the "data" axis on the
complementary dimension. Pods replicate parameters (pure DP across the "pod"
axis); the batch shards over ("pod", "data").

Every proposed spec passes through a divisibility guard so reduced smoke
configs and odd dimensions (granite's 40 experts on a 16-way model axis,
whisper's d_model=384) degrade to replication on the offending axis instead of
failing to lower.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ArchConfig


def shard_map(f, *, mesh, in_specs, out_specs, check_replication: bool = True):
    """`jax.shard_map` with the replication check spelled `check_replication`.

    Everything in this repo (and its spawned-subprocess test snippets) routes
    through this wrapper instead of calling `jax.shard_map` directly.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_replication)


def _guard(mesh: Mesh, shape: tuple, spec: P) -> P:
    """Drop mesh axes that do not divide the corresponding dim."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    fixed = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            fixed.append(None)
            continue
        axs = ax if isinstance(ax, tuple) else (ax,)
        need = int(np.prod([sizes[a] for a in axs]))
        fixed.append(ax if dim % need == 0 else None)
    return P(*fixed)


#: parameter-name -> (spec builder). Specs are written for the *unstacked*
#: leaf; the scan-unit axis is prepended automatically for block params.
_COL = {"wq", "wk", "wv", "up", "gate", "in_proj"}          # (D, out*) -> TP out
_ROW = {"wo", "down", "out_proj", "dt_proj"}                # (in*, D) -> TP in
_VEC_TP = {"bq", "bk", "bv", "conv_b", "d_skip", "dt_bias"}


def _leaf_spec(path: tuple[str, ...], shape: tuple, cfg: ArchConfig) -> P:
    name = path[-1]
    in_moe = "moe" in path
    if in_moe:
        mode = cfg.moe.shard_mode
        if name == "router":
            return P("data", None)
        if name in ("up", "gate"):                           # (E, D, F)
            return P("model", "data", None) if mode == "ep" \
                else P(None, "data", "model")
        if name == "down":                                   # (E, F, D)
            return P("model", None, "data") if mode == "ep" \
                else P(None, "model", "data")
    if name in ("embed", "lm_head"):                         # (V, D)
        return P("model", "data")
    if name == "pos_emb":
        return P(None, "data")
    if name in ("scale", "bias"):
        return P(None)
    if name in ("q_norm", "k_norm"):
        return P(None)
    if name == "conv_w":                                     # (k, d_in)
        return P(None, "model")
    if name == "a_log":                                      # (d_in, N)
        return P("model", None)
    if name == "x_proj":                                     # (d_in, dt+2N)
        return P("model", "data")
    if name in _COL:
        return P("data", "model")
    if name in _ROW:
        return P("model", "data")
    if name in _VEC_TP:
        return P("model")
    return P()                                               # replicate


def _path_names(path) -> tuple[str, ...]:
    names = []
    for part in path:
        if hasattr(part, "key"):
            names.append(str(part.key))
        elif hasattr(part, "name"):
            names.append(str(part.name))
        else:
            names.append(str(part))
    return tuple(names)


def param_specs(params_shape: Any, cfg: ArchConfig, mesh: Mesh) -> Any:
    """Pytree of PartitionSpec matching a (possibly abstract) param tree."""
    def one(path, leaf):
        names = _path_names(path)
        shape = tuple(leaf.shape)
        spec = _leaf_spec(names, shape, cfg)
        stacked = "blocks" in names or (
            "encoder" in names and "layers" in names)
        if stacked and len(spec) < len(shape):
            spec = P(None, *spec)                            # scan-unit axis
        return _guard(mesh, shape, spec)

    return jax.tree_util.tree_map_with_path(one, params_shape)


def param_shardings(params_shape: Any, cfg: ArchConfig, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params_shape, cfg, mesh))


# ---------------------------------------------------------------------------
# Batch / cache specs
# ---------------------------------------------------------------------------

def _batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def batch_specs(batch_shape: Any, mesh: Mesh) -> Any:
    """tokens/labels (B, S): batch over data axes; frames (B, T, D) same."""
    ba = _batch_axes(mesh)

    def one(leaf):
        spec = P(ba, *([None] * (len(leaf.shape) - 1)))
        return _guard(mesh, tuple(leaf.shape), spec)

    return jax.tree.map(one, batch_shape)


def cache_specs(cache_shape: Any, cfg: ArchConfig, mesh: Mesh) -> Any:
    """Decode caches. Leading axis is n_units. KV caches (U, B, L, H, hd):
    batch over data axes, heads over model; if the batch cannot shard
    (long_500k has B=1) the sequence axis takes the data axes instead.
    Mamba caches (U, B, d_in, N)/(U, B, k-1, d_in): d_in over model."""
    ba = _batch_axes(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_data = int(np.prod([sizes[a] for a in ba]))

    n_model = sizes.get("model", 1)

    def one(leaf):
        shape = tuple(leaf.shape)
        if len(shape) == 5:                                  # KV cache
            # TP the KV heads if they divide the model axis, else the head
            # dim (GQA kv=8 on a 16-way model axis); batch over data axes,
            # falling back to the sequence axis when B = 1 (long_500k).
            hax = "model" if shape[3] % n_model == 0 else None
            dax = "model" if hax is None and shape[4] % n_model == 0 else None
            if shape[1] % n_data == 0:
                spec = P(None, ba, None, hax, dax)
            else:
                spec = P(None, None, ba, hax, dax)
        elif len(shape) == 4:                                # conv or ssm state
            # (U, B, k-1, d_in) or (U, B, d_in, N): shard widest trailing dim.
            if shape[2] >= shape[3]:
                spec = P(None, ba, "model", None)
            else:
                spec = P(None, ba, None, "model")
        else:
            spec = P()
        return _guard(mesh, shape, spec)

    return jax.tree.map(one, cache_shape)


def sharding_tree(spec_tree: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree)


# ---------------------------------------------------------------------------
# Conv-network partition primitives (sharded NetworkPlan execution)
# ---------------------------------------------------------------------------
# NHWC activations partitioned over a 1-D ("data",) axis, either on the batch
# dim (data parallel) or on H (spatial halo partitioning). These run *inside*
# a shard_map body, so each sees the device-local shard.

def data_axis_name(mesh: Mesh) -> str:
    """The batch/spatial partition axis: "data" if present, else axis 0."""
    return "data" if "data" in mesh.axis_names else mesh.axis_names[0]


def halo_exchange(x: jax.Array, axis_name: str, num_shards: int,
                  halo: int) -> jax.Array:
    """Exchange `halo` boundary rows (axis 1, NHWC H) with mesh neighbors.

    Returns the local shard grown by `halo` rows on each side. Edge shards
    receive zeros (ppermute with no inbound edge), which is exactly SAME
    zero padding -- so a VALID conv over the exchanged tensor reproduces the
    unsharded SAME conv's rows owned by this shard.
    """
    if halo == 0:
        return x
    up = jax.lax.ppermute(x[:, -halo:], axis_name,
                          [(i, i + 1) for i in range(num_shards - 1)])
    dn = jax.lax.ppermute(x[:, :halo], axis_name,
                          [(i + 1, i) for i in range(num_shards - 1)])
    return jnp.concatenate([up, x, dn], axis=1)


def gather_rows(x: jax.Array, axis_name: str) -> jax.Array:
    """Reassemble the full H from row shards (all shards get the full copy)."""
    return jax.lax.all_gather(x, axis_name, axis=1, tiled=True)


def scatter_rows(full: jax.Array, axis_name: str,
                 num_shards: int) -> jax.Array:
    """Take back this shard's contiguous H rows from a replicated tensor."""
    local = full.shape[1] // num_shards
    i = jax.lax.axis_index(axis_name)
    return jax.lax.dynamic_slice_in_dim(full, i * local, local, axis=1)
