"""Distributed reduction layer: the §7 radix-4 tree at mesh scale.

- plan:        ReductionPlan — ONE tree shape + carry budget shared by the
               in-register, in-VMEM (Pallas) and cross-device tiers
- collectives: factor_radix4 / make_tree_mesh / tree_psum /
               tree_reduce_scatter_gather

Only ``plan`` (no direct jax dependency) is imported eagerly;
``collectives`` — which builds jax machinery at import — loads on
its first ``from repro.dist import ...``.
"""
from repro.dist import plan  # noqa: F401
