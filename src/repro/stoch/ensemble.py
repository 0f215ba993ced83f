"""Vmapped ensemble forward + uncertainty statistics + mesh placement.

``ensemble_forward`` runs one model function over every replica of a
:class:`~repro.stoch.replicas.ReplicaSet` in a single ``jax.vmap`` — the
replica axis maps over the *stacked* stochastic leaves only, while the
shared base leaves are closed over and broadcast, so XLA never materializes
K copies of embeddings / norms / dense fallthroughs. Backend dispatch is
type-keyed (``repro.engine.registry``), and the serving leaf classes carry
their static aux data through ``vmap`` untouched, so the packed / xnor /
packed_conv datapaths all vmap as-is.

``ensemble_stats`` condenses the (K, ..., V) replica logits into the
user-visible uncertainty signal: ensemble-mean logits, mean per-logit
across-replica variance, and vote agreement (the fraction of replicas whose
argmax matches the ensemble argmax).

``place_replicas`` puts a ReplicaSet on a mesh: base leaves follow the
plan's recorded sharding column exactly as single-sample serving does, and
each stacked leaf gets the plan's ``replica_axis`` ("data" / "model" /
None) prepended to its row's column — replicas shard over the chosen mesh
axis while every inner dim keeps its single-replica placement.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.stoch.replicas import ReplicaSet, _substitute


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EnsembleStats:
    """Per-input ensemble uncertainty summary (all f32).

    ``mean_logits``  (..., V)  ensemble-mean logits (what gets decoded)
    ``variance``     (...,)    across-replica logit variance, meaned over V
    ``agreement``    (...,)    fraction of replicas voting with the ensemble
    """

    mean_logits: jax.Array
    variance: jax.Array
    agreement: jax.Array

    def tree_flatten(self):
        return (self.mean_logits, self.variance, self.agreement), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def ensemble_stats(rep_logits: jax.Array) -> EnsembleStats:
    """Condense (K, ..., V) per-replica logits into :class:`EnsembleStats`.

    Agreement compares each replica's argmax against the argmax of the
    ensemble *mean* — a unanimous ensemble scores 1.0 regardless of K."""
    x = rep_logits.astype(jnp.float32)
    mean = jnp.mean(x, axis=0)                               # (..., V)
    variance = jnp.mean(jnp.var(x, axis=0), axis=-1)         # (...,)
    votes = jnp.argmax(x, axis=-1)                           # (K, ...)
    winner = jnp.argmax(mean, axis=-1)                       # (...,)
    agreement = jnp.mean((votes == winner[None]).astype(jnp.float32), axis=0)
    return EnsembleStats(mean, variance, agreement)


def ensemble_forward(rs: ReplicaSet, fn: Callable[[Any], jax.Array],
                     *, stats: bool = True):
    """Run ``fn(serving_tree) -> logits`` once per replica via ``vmap``.

    Returns :class:`EnsembleStats` (default) or the raw (K, ..., V)
    replica logits (``stats=False``). ``fn`` must be traceable (it is
    called under ``vmap``); jit the *caller* for a single fused ensemble
    step. For k = 1 the vmap is skipped entirely — the call lowers to
    exactly the single-sample program (bit-identity with the non-ensemble
    path, asserted in tests)."""
    if rs.k == 1:
        logits = fn(rs.base)[None]
    else:
        def one(stacked_slice):
            return fn(_substitute(rs.base, stacked_slice))

        logits = jax.vmap(one, in_axes=0, axis_size=rs.k)(rs.stacked)
    return ensemble_stats(logits) if stats else logits


def prepend_replica_axis(rax: Optional[str], spec):
    """``PartitionSpec(rax, *spec)`` with ``rax`` deduplicated from the
    inner entries first (a mesh-axis name may appear at most once in a
    spec; the replica axis wins the collision). ``rax=None`` prepends a
    replicated leading dim."""
    from jax.sharding import PartitionSpec as P

    entries = []
    for e in spec:
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a != rax)
            entries.append(kept if kept else None)
        else:
            entries.append(None if e == rax else e)
    return P(rax, *entries)


def replica_specs(rs: ReplicaSet, *, mesh=None) -> dict[str, Any]:
    """PartitionSpec pytree for the *stacked* nodes of ``rs``: the plan's
    ``replica_axis`` on the leading (K,) dim, the row's recorded sharding
    column (rank-adapted per stored array) on the inner dims. The replica
    axis wins a name collision — a column entry naming the same mesh axis
    is dropped, since a name may appear at most once in a spec."""
    from repro.distributed.sharding import (_adapt_spec, sanitize_spec,
                                            serving_leaf_pspec)

    rax = rs.plan.replica_axis
    out: dict[str, Any] = {}
    for path, node in rs.stacked.items():
        row = rs.plan[path]
        spec = row.pspec
        if spec is None:                      # v1-manifest row: re-derive
            spec = serving_leaf_pspec(path, node)

        def spec_for(a, spec=spec):
            full = prepend_replica_axis(rax, _adapt_spec(spec, a.ndim - 1))
            return (sanitize_spec(mesh, full, a.shape)
                    if mesh is not None else full)

        out[path] = jax.tree.map(spec_for, node)
    return out


def place_replicas(mesh, rs: ReplicaSet,
                   plan: Optional[Any] = None) -> ReplicaSet:
    """Place a ReplicaSet on ``mesh``: base leaves via the ordinary
    plan-column placement (``place_packed_params``), stacked leaves with
    the plan's ``replica_axis`` prepended (:func:`replica_specs`). A
    ``replica_axis`` of None (or a K not divisible by the axis size)
    replicates the stack."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.sharding import (place_packed_params,
                                            sanitize_spec, with_model_split)

    plan = plan if plan is not None else rs.plan
    base = place_packed_params(mesh, rs.base, plan)
    specs = replica_specs(rs, mesh=mesh)
    stacked = {}
    for path, node in rs.stacked.items():
        placed = jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            node, specs[path])
        # the per-replica node keeps its row's split (replica axis aside)
        row = getattr(node, "master_shape", ())[1:]
        col = sanitize_spec(mesh, rs.plan[path].pspec or P(), row)
        stacked[path] = with_model_split(placed, col)
    return ReplicaSet(base=base, stacked=stacked, k=rs.k, paths=rs.paths,
                      plan=rs.plan)
