"""SPMD shard context: mesh + per-input PartitionSpecs for planning.

The paper's production claim (thousands of devices) assumes fusion
plans that are legal under data/tensor parallelism.  ``ShardCtx`` is
the one object that carries a ``jax.sharding.Mesh`` plus the
``PartitionSpec`` of every flat graph input/output through the whole
pipeline:

* **tracer** -- ``local_args`` turns global example arguments into
  per-shard ``ShapeDtypeStruct``s, so the traced graph *is* the
  per-shard program: row counts, VMEM pressure, interface-HBM bytes
  and every stitch/partition/anchor gain are priced on per-shard
  shapes with zero changes to the cost formulas themselves.
  ``axis_env`` lets ``jax.make_jaxpr`` trace the collectives
  (``psum``/``all_gather``/``reduce_scatter``) the per-shard function
  contains.
* **codegen/stitch** -- ``wrap`` puts the compiled fusion schedule
  (and the XLA reference baseline) inside ``jax.shard_map``, so ONE
  emitted megakernel plan replays on every shard and the guard ladder
  / shadow verification work per-shard.
* **plan cache** -- ``signature_items`` folds mesh shape + axis names
  + specs into ``graph_signature`` so 1-device and 8-device plans can
  never collide; mesh-free graphs hash nothing extra.

Two flavors:

* **explicit** (``in_specs`` given): the wrapped function is the
  *per-shard* body, written shard_map-style with explicit collectives.
  Planning runs on local shapes and dispatch goes through
  ``shard_map``.
* **ambient** (``in_specs`` None, mesh discovered from
  ``repro.dist.partitioning.use_mesh``): the function stays
  global-view (GSPMD places the collectives); the mesh is folded into
  the plan signature and compile keys only, so serving under
  ``use_mesh`` never collides its plans with single-device ones.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax

from repro.runtime.guard import GuardError


class ShardSpecError(GuardError):
    """A PartitionSpec does not divide the shape it is applied to."""


def _spec_axes(entry) -> tuple:
    """The mesh axis names one PartitionSpec entry references."""
    if entry is None:
        return ()
    if isinstance(entry, (tuple, list)):
        return tuple(entry)
    return (entry,)


@dataclass(frozen=True)
class ShardCtx:
    """Mesh + flat input/output PartitionSpecs (None specs: ambient)."""

    mesh: Any
    in_specs: tuple | None = None
    out_specs: tuple | None = None

    # -- basic mesh queries --------------------------------------------------
    @property
    def explicit(self) -> bool:
        """True when per-input specs are known: plan per-shard and
        dispatch through ``shard_map``.  False (ambient): mesh keys the
        signature only."""
        return self.in_specs is not None

    @property
    def axis_sizes(self) -> dict:
        return dict(self.mesh.shape)

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.mesh.shape.values():
            n *= int(s)
        return n

    def axis_env(self) -> list[tuple[str, int]]:
        """(name, size) pairs for ``jax.make_jaxpr``: lets the tracer
        bind the collectives of the per-shard function."""
        return [(str(a), int(s)) for a, s in self.mesh.shape.items()]

    def mesh_key(self) -> tuple:
        """Hashable mesh identity (shape + axis order) for compile-cache
        and dispatch-table keys."""
        return tuple((str(a), int(s)) for a, s in self.mesh.shape.items())

    # -- per-shard shapes ----------------------------------------------------
    def shard_factor(self, spec) -> tuple[int, ...] | None:
        """Per-dim divisor tuple of ``spec`` (None: unknown spec)."""
        if spec is None:
            return None
        sizes = self.axis_sizes
        out = []
        for entry in tuple(spec):
            f = 1
            for a in _spec_axes(entry):
                f *= int(sizes[a])
            out.append(f)
        return tuple(out)

    def local_shape(self, shape: tuple[int, ...], spec) -> tuple[int, ...]:
        """The per-shard shape of a global ``shape`` under ``spec``.

        Raises :class:`ShardSpecError` on a non-divisible assignment --
        the bad-spec seam the ``shard_spec_fail`` fault point simulates
        at emission time.
        """
        factors = self.shard_factor(spec)
        if factors is None:
            return tuple(shape)
        out = list(shape)
        for i, f in enumerate(factors):
            if f == 1:
                continue
            if i >= len(out) or out[i] % f != 0:
                raise ShardSpecError(
                    f"PartitionSpec {spec} does not divide shape "
                    f"{tuple(shape)} (dim {i} by {f})")
            out[i] //= f
        return tuple(out)

    def local_args(self, flat_args) -> list:
        """Per-shard ``ShapeDtypeStruct``s for the flat global args."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        if self.in_specs is None:
            raise ValueError("ambient ShardCtx has no input specs")
        if len(self.in_specs) != len(flat_args):
            raise ValueError(
                f"{len(self.in_specs)} in_specs for {len(flat_args)} "
                "flat arguments")
        return [jax.ShapeDtypeStruct(
                    self.local_shape(tuple(np.shape(a)), spec),
                    jnp.result_type(a))
                for a, spec in zip(flat_args, self.in_specs)]

    # -- dispatch ------------------------------------------------------------
    def wrap(self, fn):
        """``shard_map`` ``fn`` (a flat per-shard callable) over the
        mesh.  ``check_vma=False``: the fusion schedule replays pallas
        calls and per-node binds whose replication the checker cannot
        see through."""
        if not self.explicit:
            raise ValueError("ambient ShardCtx cannot wrap a dispatch")
        return jax.shard_map(fn, mesh=self.mesh,
                             in_specs=tuple(self.in_specs),
                             out_specs=tuple(self.out_specs),
                             check_vma=False)

    # -- cache signature -----------------------------------------------------
    def signature_items(self) -> tuple:
        """What ``plan_cache.graph_signature`` hashes for this mesh."""
        return (self.mesh_key(),
                tuple(repr(s) for s in self.in_specs or ()),
                tuple(repr(s) for s in self.out_specs or ()),
                self.explicit)

    def mesh_record(self) -> dict:
        """The ``mesh`` section a sharded plan-cache entry stores."""
        return {"shape": [int(s) for s in self.mesh.shape.values()],
                "axes": [str(a) for a in self.mesh.shape.keys()]}

    # -- construction --------------------------------------------------------
    @classmethod
    def build(cls, mesh, in_specs, out_specs) -> "ShardCtx":
        """Normalize an explicit (mesh, in_specs, out_specs) triple."""
        from jax.sharding import PartitionSpec as P

        def norm(specs):
            if specs is None:
                return None
            if isinstance(specs, P):     # single-arg/-output shorthand
                specs = (specs,)
            return tuple(P() if s is None else s for s in specs)

        return cls(mesh=mesh, in_specs=norm(in_specs),
                   out_specs=norm(out_specs))

    @classmethod
    def ambient(cls) -> "ShardCtx | None":
        """The mesh installed by ``repro.dist.partitioning.use_mesh``,
        as a signature-only context (>1 device meshes only)."""
        from repro.dist.partitioning import current_ctx

        mctx = current_ctx()
        if mctx is None or getattr(mctx, "mesh", None) is None:
            return None
        ctx = cls(mesh=mctx.mesh)
        return ctx if ctx.n_devices > 1 else None


def ambient_mesh_key() -> tuple | None:
    """Dispatch-table key fragment for the active ``use_mesh`` context
    (None outside one): the serving layer keys its jitted pairs on this
    so a sharded serve never reuses a single-device compile."""
    ctx = ShardCtx.ambient()
    return ctx.mesh_key() if ctx is not None else None


def input_specs_from_names(mesh, names_and_shapes, **mesh_ctx_kwargs):
    """Derive flat input ``PartitionSpec``s from ``dist/partitioning``
    activation names.

    ``names_and_shapes`` is a sequence of ``(name, shape)`` pairs, one
    per flat input; a falsy name (or an unknown one) replicates.  Specs
    are divisibility-repaired with ``move=False`` exactly like
    ``constrain`` does, so the planner and the runtime agree on the
    layout.
    """
    from jax.sharding import PartitionSpec as P

    from repro.dist.partitioning import _MeshCtx, _fit_spec, _named_spec

    mctx = _MeshCtx(mesh, **mesh_ctx_kwargs)
    specs = []
    for name, shape in names_and_shapes:
        spec = _named_spec(name, tuple(shape), mctx) if name else None
        if spec is None:
            specs.append(P())
        else:
            specs.append(_fit_spec(spec, tuple(shape), mesh, move=False))
    return tuple(specs)
