"""Logical-axis → mesh-axis sharding rules.

Role of reference fsdp_engine.py:167-263 (DTensor TP module plan) +
apply_fsdp2 — replaced by declarative rules in the t5x/MaxText style: each
param carries logical axis names (models.transformer.param_logical_axes);
one rules table maps them onto mesh axes; pjit does the rest.

Default rules:
- "embed"  → "fsdp"    (ZeRO-3-style param sharding on the model dim)
- "heads"  → "tensor"  (megatron-style column/row parallel attention)
- "mlp"    → "tensor"  (column/row parallel FFN)
- "vocab"  → None      (replicated; vocab-parallel loss is a later opt)
- "layer"  → None      (scanned axis, never sharded)
- "channel" → "fsdp"   (a state-space mixer's channels, where a leaf has no
  model dimension to split: its convolution, rates, skip and the two small
  products around its scan, so that every leaf of a dense stack is sharded)
- "expert", "exp_embed" (a sparse layer's three expert stacks: their
  expert dimension and their model width) → ("expert", "fsdp"), None on a
  mesh with chips to split the experts among (``mesh_rules``): a chip holds
  WHOLE experts, an equal share of them, so no collective carries an
  expert's weights or gradient over those axes
  (``ops/moe.make_sharded_moe_ffn`` brings the tokens to them)

Activations: batch → ("data", "fsdp"), sequence → "seq" (Ulysses-style SP
handled inside attention via all-to-alls XLA derives from shardings).
"""

from typing import Any, Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# a logical axis -> the mesh axis, or axes, that split it (None: none)
Rules = Dict[str, Union[str, Tuple[str, ...], None]]

# The table of a mesh with nothing to split a sparse layer's experts among
# (``expert`` x ``fsdp`` = 1: one chip; data, sequence or tensor parallelism
# alone). Nothing is split there whatever the spelling, and a one-chip
# program's text (its compile-cache key) spells its arguments' shardings by
# it: the expert stacks keep the spelling they have had
DEFAULT_RULES: Rules = {
    "embed": "fsdp",
    "heads": "tensor",
    "mlp": "tensor",
    "expert": "expert",  # MoE expert dim (EP)
    "exp_embed": "fsdp",
    "vocab": None,
    "layer": None,
    "channel": "fsdp",
}
_EXPERT_AXES = ("expert", "fsdp")


def split_axes(mesh: Mesh, names) -> Tuple[str, ...]:
    """One ``PartitionSpec`` entry's mesh axes of more than one device."""
    names = (names,) if isinstance(names, str) else tuple(names or ())
    return tuple(a for a in names if mesh.shape[a] > 1)


def experts_split(mesh: Mesh) -> Tuple[str, ...]:
    """The axes of ``mesh`` that split a sparse layer's expert stacks among
    its chips, whole experts each: () where every chip holds them all."""
    return split_axes(mesh, _EXPERT_AXES)


def mesh_rules(mesh: Mesh) -> Rules:
    """The rules table of ``mesh``: where it has chips to split the experts
    among, a chip holds WHOLE experts (the stacks' expert dimension over
    ``expert`` and ``fsdp``, their model width unsplit)."""
    if not experts_split(mesh):
        return DEFAULT_RULES
    return dict(DEFAULT_RULES, expert=_EXPERT_AXES, exp_embed=None)


def logical_to_spec(
    axes: Tuple[Optional[str], ...],
    rules: Optional[Rules] = None,
    mesh: Optional[Mesh] = None,
) -> P:
    """``rules``, else ``mesh``'s table, else the default one."""
    rules = rules or (DEFAULT_RULES if mesh is None else mesh_rules(mesh))
    return P(*(rules.get(a) if a is not None else None for a in axes))


def tree_shardings(
    mesh: Mesh,
    logical_tree: Any,
    rules: Optional[Rules] = None,
):
    """Map a tree of logical-axis tuples to a tree of NamedShardings."""
    return jax.tree_util.tree_map(
        lambda axes: NamedSharding(mesh, logical_to_spec(axes, rules, mesh)),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def batch_spec() -> P:
    """Packed batch arrays [B, T]: rows over (data, fsdp), tokens over seq."""
    return P(("data", "fsdp"), "seq")


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, batch_spec())


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_params(mesh: Mesh, params: Any, logical_tree: Any, rules=None):
    """Device-put a host pytree onto the mesh under the rules table."""
    shardings = tree_shardings(mesh, logical_tree, rules)
    return jax.device_put(params, shardings)
