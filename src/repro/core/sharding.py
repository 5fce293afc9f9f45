"""Logical-axis sharding rules (divisibility-aware).

Every tensor in the framework is annotated with a *logical spec*: a tuple of
logical axis names (or ``None``) per dimension, e.g. an attention projection
``(embed, heads, head_dim)``.  A :class:`ShardingRules` table maps logical
axes to mesh axes.  ``spec_for`` resolves a logical spec against a concrete
shape and mesh, dropping mesh axes that do not divide the dimension — this is
what lets a single rule table serve e.g. smollm's 9 attention heads (not
divisible by ``model=16`` → replicated) and granite's 32 heads (sharded).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LogicalSpec = tuple  # tuple[str | None, ...]


def _as_tuple(x) -> tuple:
    if x is None:
        return ()
    if isinstance(x, str):
        return (x,)
    return tuple(x)


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Maps logical axis names -> mesh axis name(s)."""

    rules: Mapping[str, tuple]

    def replace(self, **updates) -> "ShardingRules":
        new = dict(self.rules)
        for k, v in updates.items():
            new[k] = _as_tuple(v)
        return ShardingRules(new)

    def mesh_axes_for(self, logical_axis: str | None) -> tuple:
        if logical_axis is None:
            return ()
        return _as_tuple(self.rules.get(logical_axis))

    def spec_for(self, logical: Sequence, shape: Sequence[int], mesh: Mesh) -> P:
        """Resolve a logical spec to a PartitionSpec for ``shape`` on ``mesh``.

        Mesh axes that are missing from the mesh, already used by another
        dimension, or that do not evenly divide the dimension size are
        dropped (replication fallback).
        """
        if len(logical) != len(shape):
            raise ValueError(
                f"logical spec {logical} does not match shape {shape}")
        used: set = set()
        out = []
        for name, dim in zip(logical, shape):
            axes = []
            remaining = dim
            for ax in self.mesh_axes_for(name):
                if ax in used or ax not in mesh.shape:
                    continue
                size = mesh.shape[ax]
                if remaining % size != 0:
                    continue
                axes.append(ax)
                used.add(ax)
                remaining //= size
            if not axes:
                out.append(None)
            elif len(axes) == 1:
                out.append(axes[0])
            else:
                out.append(tuple(axes))
        # PartitionSpec trailing Nones are harmless; keep full rank for clarity.
        return P(*out)

    def sharding_for(self, logical: Sequence, shape: Sequence[int],
                     mesh: Mesh) -> NamedSharding:
        return NamedSharding(mesh, self.spec_for(logical, shape, mesh))


# ---------------------------------------------------------------------------
# Rule tables.
#
# "tp_dp" is the paper-faithful baseline: a job owns a set of data-parallel
# slices (the malleable resource) and each slice does tensor parallelism over
# the fixed "model" axis — mirroring the paper's fixed cores-per-node,
# variable node-count resource model.
# ---------------------------------------------------------------------------

TP_DP_RULES = ShardingRules({
    # activations
    "batch": ("pod", "data"),
    "seq": (),
    "kv_seq": (),            # decode-time KV cache sequence axis
    "embed": (),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": (),
    "mlp": ("model",),
    "experts": ("model",),
    "expert_mlp": (),
    "vocab": ("model",),
    "state": (),             # SSM / RG-LRU recurrent state width
    "layers": (),            # stacked scan dimension — never sharded
    "frontend": (),
    "table_embed": (),       # embedding-table model dim (never FSDP)
    "zero1": ("pod", "data"),   # ZeRO-1 optimizer-moment sharding
})

# FSDP-style variant: weights additionally sharded over the data axis
# (all-gathered at use).
FSDP_RULES = TP_DP_RULES.replace(embed=("data",))

# Long-context decode (batch too small to shard): shard the KV cache /
# sequence dimension over the data axis; distributed softmax via GSPMD.
LONG_CONTEXT_RULES = TP_DP_RULES.replace(
    batch=(), kv_seq=("pod", "data"), seq=("pod", "data"))


# -- the mesh a step is traced for -------------------------------------------
#
# The trainer traces its step under the mesh it compiles for: kernels read it
# (``context_mesh``) to pick their platform and to map themselves over the
# devices.  Activations are left to GSPMD's propagation.

_MESH_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "tracing_mesh", default=None)


@contextlib.contextmanager
def tracing_mesh(mesh: Mesh):
    """Trace the enclosed code for ``mesh`` (see :func:`context_mesh`)."""
    tok = _MESH_CTX.set(mesh)
    try:
        yield
    finally:
        _MESH_CTX.reset(tok)


def context_mesh() -> "Mesh | None":
    """The mesh the code is being traced for, or None outside a context."""
    return _MESH_CTX.get()


def logical_to_sharding(tree_logical, tree_shapes, mesh: Mesh,
                        rules: ShardingRules):
    """Map a pytree of logical specs + matching shapes -> NamedShardings."""
    return jax.tree.map(
        lambda logical, shape: rules.sharding_for(logical, shape, mesh),
        tree_logical, tree_shapes,
        is_leaf=lambda x: isinstance(x, tuple),
    )
