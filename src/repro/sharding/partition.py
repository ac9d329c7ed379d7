"""Logical-axis sharding rules (DESIGN §5).

Every parameter carries a tuple of *logical axis names*; activations are
constrained at block boundaries. Rules map logical names to mesh axes;
``logical_to_spec`` drops any assignment whose dimension is not divisible
by the mesh-axis size (e.g. whisper-tiny's 6 heads on a 16-way ``model``
axis fall back to replication) so one rule set serves all 10 assigned
architectures on every mesh.

Parallelism mapping (train):
  * DP/FSDP — ``batch`` over ("pod","data"); params' ``fsdp`` (largest
    non-TP dim) over "data" (ZeRO-3 gather on use);
  * TP — ``heads``/``kv``/``ff``/``vocab`` over "model";
  * EP — ``experts`` over "model";
  * SP — activation ``act_seq`` over "model" between blocks (norm/residual
    segments), re-gathered by XLA inside attention.
Serving: KV-cache ``cache_seq`` over "model" (long-context decode), batch
over ("pod","data").
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

Axes = Tuple[Optional[Union[str, Tuple[str, ...]]], ...]

# parameter logical axes
PARAM_RULES: Dict[str, Optional[Union[str, Tuple[str, ...]]]] = {
    "vocab": "model",
    "heads": "model",      # fused heads*head_dim output dims
    "kv": "model",
    "ff": "model",
    "experts": "model",
    "fsdp": "data",        # ZeRO-3 shard of the non-TP major dim
    "embed": None,
    "layers": None,        # stacked scan axis (pipeline axis at >4k chips)
    "conv": None,
    "state": None,
    "lora": None,
    None: None,
}

# activation logical axes
ACT_RULES: Dict[str, Optional[Union[str, Tuple[str, ...]]]] = {
    "act_batch": ("pod", "data"),
    "act_batch_nopod": "data",
    "act_seq": "model",     # sequence parallelism between blocks
    "act_embed": None,
    "act_heads": "model",
    "cache_seq": "model",   # KV cache length dim for decode
    "act_experts": "model",
    None: None,
}


def _filter_assignment(mesh, assignment):
    """Drop mesh axes absent from this mesh (e.g. 'pod' on single-pod);
    returns (normalized assignment or None, product of axis sizes)."""
    if assignment is None:
        return None, 1
    names = mesh.axis_names
    axes = (assignment,) if isinstance(assignment, str) else tuple(assignment)
    present = tuple(a for a in axes if a in names)
    if not present:
        return None, 1
    size = 1
    for a in present:
        size *= mesh.shape[a]
    return (present[0] if len(present) == 1 else present), size


def logical_to_spec(shape: Sequence[int], axes: Axes, mesh: Mesh,
                    rules: Dict) -> P:
    """PartitionSpec from logical axes, with divisibility fallback."""
    assert len(shape) == len(axes), (shape, axes)
    parts = []
    for dim, ax in zip(shape, axes):
        assignment, size = _filter_assignment(mesh, rules.get(ax, None))
        if assignment is None or size == 1 or dim % size != 0:
            parts.append(None)
        else:
            parts.append(assignment)
    return P(*parts)


def logical_to_sharding(shape: Sequence[int], axes: Axes, mesh: Mesh,
                        rules: Optional[Dict] = None) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(
        shape, axes, mesh, rules or PARAM_RULES))


def constrain(x: jax.Array, axes: Axes, rules: Optional[Dict] = None
              ) -> jax.Array:
    """with_sharding_constraint under the ambient mesh (no-op when no mesh
    is set — smoke tests and benches run unconstrained on 1 device)."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    spec = logical_to_spec(x.shape, axes, mesh, rules or ACT_RULES)
    return jax.lax.with_sharding_constraint(x, spec)


def tree_shardings(tree, axes_tree, mesh: Mesh, rules: Optional[Dict] = None):
    """Map a pytree of arrays/ShapeDtypeStructs + matching logical-axes tree
    to NamedShardings."""
    return jax.tree.map(
        lambda leaf, ax: logical_to_sharding(leaf.shape, ax, mesh,
                                             rules or PARAM_RULES),
        tree, axes_tree,
        is_leaf=lambda l: hasattr(l, "shape"))
