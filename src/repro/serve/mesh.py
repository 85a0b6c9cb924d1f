"""Mesh-sharded serving plan: bind an EngineConfig to a device mesh.

The serve tier's paged engine keeps ONE pooled decode-state tree, one
slot batch, and one page table.  :class:`MeshPlan` is the layout contract
that splits all three across a ``jax.sharding.Mesh`` with a single
``"slots"`` data axis so one engine serves a slot batch no single device
could hold:

* **slots** — the decode batch axis shards into ``shards`` equal groups
  of ``slots_per_shard`` lanes; slot ``s`` lives on device
  ``s // slots_per_shard``.  Tokens, per-slot positions, sampling lanes
  and page-table rows all shard the same way, so a decode step is
  embarrassingly parallel: every device advances only its own lanes.
* **page pool** — each pooled leaf's ``phys_page`` axis shards into
  ``shards`` contiguous blocks of ``block`` pages; block ``s`` is device
  ``s``'s local slice.  ``repro.serve.cache.PagePool`` keeps one free
  list per block (process-local allocation — admission never does a
  cross-device allocator round-trip), and the *first page of every
  block* is that shard's scratch page.  Page ids are global on the host;
  a dispatch converts a table row to shard-local offsets with one
  vectorized ``% block`` (:meth:`local_pages`) — the unallocated
  sentinel 0 maps to every shard's local scratch 0 by construction.
* **weights** — replicated (every device holds the full params), placed
  once at engine build; an optional model axis for sharded weights can
  compose later without changing this plan's data axis.

Decode dispatches run under ``jax.shard_map`` with logits and sampled
tokens kept ``P("slots")``-sharded, so a decode step moves **zero
cross-device traffic**: only admission/retire touch the host.

Correctness note: per-slot decode math is batch-independent (each lane
attends only through its own page-table row), so a sharded engine's
greedy tokens are bit-exact vs the single-device engine serving the same
requests — the property ``benchmarks/bench_serve.py`` asserts.
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["MeshPlan"]


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Frozen layout contract for one mesh-sharded engine.

    Built by :meth:`build` from a *resolved*
    :class:`~repro.serve.config.EngineConfig`; the engine keeps it as
    ``self.mesh_plan`` and every sharded code path (dispatch wrapping,
    table localization, shard-of queries) goes through it.
    """

    #: devices along the ``slots`` axis
    shards: int
    #: decode lanes per shard (``max_slots // shards``)
    slots_per_shard: int
    #: pool pages per shard block, including the block's scratch page
    block: int
    #: the bound ``jax.sharding.Mesh`` with axis ``("slots",)``
    mesh: object

    @classmethod
    def build(cls, config) -> "MeshPlan":
        """Bind a resolved ``EngineConfig`` to the first ``mesh_shards``
        visible devices as a 1-D ``("slots",)`` mesh.

        Raises ``RuntimeError`` with the ``XLA_FLAGS`` recipe when fewer
        devices are visible than the config shards across (the flag must
        be set before the first jax device query — the backend
        initializes once), and ``ValueError`` when the config was not
        resolved to a paged engine (the pool is what shards)."""
        import jax

        shards = config.mesh_shards
        if shards < 2:
            raise ValueError(
                f"MeshPlan needs mesh_shards >= 2, got {shards} "
                f"(a single-device engine has no mesh to plan)")
        if not config.paged_kv or not config.pool_pages:
            raise ValueError(
                "MeshPlan.build needs a RESOLVED paged config "
                "(config.resolve(model_cfg) with paged_kv on) — the "
                "physical page pool is what shards across the mesh")
        devices = jax.devices()
        if len(devices) < shards:
            raise RuntimeError(
                f"mesh_shards={shards} needs {shards} devices but only "
                f"{len(devices)} are visible; on CPU set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{shards} in the environment BEFORE the first jax call "
                f"(the backend initializes once per process)")
        mesh = jax.sharding.Mesh(
            np.array(devices[:shards]), ("slots",))
        return cls(shards=shards,
                   slots_per_shard=config.max_slots // shards,
                   block=config.pool_pages // shards + 1,
                   mesh=mesh)

    # --------------------------------------------------------- shard maps
    def shard_of_slot(self, slot: int) -> int:
        """The shard (device index along ``slots``) holding ``slot``."""
        return int(slot) // self.slots_per_shard

    def shard_of_page(self, page: int) -> int:
        """The shard whose pool block holds global physical ``page``."""
        return int(page) // self.block

    def local_pages(self, table: np.ndarray) -> np.ndarray:
        """Convert a host page table of *global* page ids to the
        shard-local offsets a sharded dispatch indexes with — one
        vectorized ``% block``.

        Sound because the engine's allocator invariant guarantees every
        non-zero entry of a slot's row lives in that slot's own shard
        block (global id ``shard * block + local``), and the unallocated
        sentinel 0 maps to local 0 — which is *every* shard's scratch
        page, exactly where an unallocated/idle lane must aim."""
        return np.asarray(table, np.int32) % np.int32(self.block)

    # ------------------------------------------------------ sharding specs
    def lane_spec(self):
        """``PartitionSpec("slots")`` — per-slot lanes, tables, tokens."""
        from jax.sharding import PartitionSpec as P
        return P("slots")

    def replicated_spec(self):
        """``PartitionSpec()`` — params and broadcast scalars."""
        from jax.sharding import PartitionSpec as P
        return P()

    def state_specs(self, pspecs):
        """Per-leaf ``PartitionSpec`` tree for the pooled state: the
        ``"slots"`` mesh axis on each leaf's ``phys_page`` axis (read off
        the ``pspecs`` ParamSpec axis names — the pool axis position
        varies by leaf), every other axis replicated."""
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.models.common import ParamSpec

        def spec_of(s):
            ax = s.axes.index("phys_page")
            return P(*([None] * ax + ["slots"]))

        return jax.tree.map(spec_of, pspecs,
                            is_leaf=lambda x: isinstance(x, ParamSpec))
