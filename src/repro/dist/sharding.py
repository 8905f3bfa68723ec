"""Logical-axis sharding layer: ParamSpec trees + ShardingCtx.

Every parameter / cache / optimizer-slot leaf is declared once as a
:class:`ParamSpec` — shape, *logical* axis names, init and dtype. The same
declaration materializes

* real arrays              (``tree_init`` — smoke tests, single host),
* ``ShapeDtypeStruct``s    (``tree_abstract`` — the dry-run path, no
  allocation),
* ``PartitionSpec``s       (``tree_pspecs`` — mesh lowering), and
* ``NamedSharding``s       (``tree_shardings``).

Logical → mesh axes go through a *rules* dict (``DEFAULT_RULES``); callers
override entries per profile (e.g. the dry-run switches ``"expert"`` to the
run's dispatch axes and clears ``"embed"`` for serving — no per-step FSDP
all-gathers at decode). Rule application is defensive: a mesh axis is used
only if it exists in the mesh, is not already taken by an earlier dim of the
same spec, and divides the dim size — otherwise that dim is replicated. This
is what lets one model definition lower on any mesh shape.

``NO_SHARDING`` is the single-device context (mesh=None): ``constrain`` is
the identity and every spec is fully replicated.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Logical axis name → tuple of mesh axis names (applied left to right).
# "embed" over the data axis = FSDP; tensor-parallel dims over "model";
# "batch" over every data-parallel axis present ("pod" first on multi-pod).
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "embed": ("data",),          # FSDP (cleared for serving profiles)
    "ff": ("model",),
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": (),
    "d_inner": ("model",),
    "ssm_heads": ("model",),
    "expert": ("model",),        # dry-run overrides with the run's slot axes
    "seq": ("model",),           # active only when sequence_parallel
    "kv_seq": ("model",),        # distributed-LSE decode fallback
    "layers": (),
}

# Default leaf dtype when a spec leaves dtype=None: bf16, matching the
# byte accounting in core/regions.py (2 bytes per unspecified leaf) and the
# training setup (bf16 weights, f32 optimizer slots declared explicitly).
_DEFAULT_DTYPE = jnp.bfloat16


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """One leaf: shape + logical axes (+ init/dtype/scale)."""
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"            # "normal" | "zeros" | "ones"
    dtype: Any = None               # None → bfloat16 (_DEFAULT_DTYPE)
    scale: float | None = None      # normal() stddev; None → 0.02

    def __post_init__(self):
        object.__setattr__(self, "shape", tuple(self.shape))
        object.__setattr__(self, "axes", tuple(self.axes))
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


_IS_SPEC = lambda x: isinstance(x, ParamSpec)  # noqa: E731


@dataclasses.dataclass(frozen=True)
class ShardingCtx:
    """Mesh + logical-axis rules; mesh=None = single device (NO_SHARDING)."""
    mesh: Any = None
    rules: dict[str, tuple[str, ...]] | None = None
    sequence_parallel: bool = True
    unroll: bool | int = False

    # -- rule resolution ------------------------------------------------
    def _rule(self, name: str | None) -> tuple[str, ...]:
        if name is None or self.mesh is None:
            return ()
        rules = self.rules if self.rules is not None else DEFAULT_RULES
        if name == "seq" and not self.sequence_parallel:
            return ()
        return tuple(rules.get(name, ()))

    def axis_size(self, mesh_axis: str) -> int:
        if self.mesh is None:
            return 1
        return int(dict(self.mesh.shape).get(mesh_axis, 1))

    def divides(self, name: str | None, size: int) -> bool:
        """Whether `size` splits evenly over the mesh axes mapped to the
        logical axis `name` (True means sharding that dim loses nothing)."""
        axes = [a for a in self._rule(name) if a in dict(self.mesh.shape)] \
            if self.mesh is not None else []
        prod = math.prod(self.axis_size(a) for a in axes) if axes else 1
        return prod > 1 and size % prod == 0

    def spec(self, axes: tuple[str | None, ...],
             shape: tuple[int, ...]) -> P:
        """PartitionSpec for logical `axes` of an array of `shape`, applying
        the rules defensively (missing / non-dividing / already-used mesh
        axes fall back to replication for that dim)."""
        if self.mesh is None:
            return P()
        mesh_shape = dict(self.mesh.shape)
        used: set[str] = set()
        entries: list[Any] = []
        for dim, name in zip(shape, axes):
            picked: list[str] = []
            prod = 1
            for a in self._rule(name):
                if a not in mesh_shape or a in used:
                    continue
                nxt = prod * mesh_shape[a]
                if dim % nxt != 0:
                    continue
                picked.append(a)
                prod = nxt
            used.update(picked)
            entries.append(tuple(picked) if len(picked) > 1
                           else (picked[0] if picked else None))
        while entries and entries[-1] is None:
            entries.pop()
        return P(*entries)

    def constrain(self, x: jax.Array, *axes: str | None) -> jax.Array:
        """with_sharding_constraint through the logical rules (identity when
        there is no mesh)."""
        if self.mesh is None:
            return x
        spec = self.spec(tuple(axes), x.shape)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))


NO_SHARDING = ShardingCtx(mesh=None)


# ----------------------------------------------------------------------
# Seed-batch device sharding (chaos sweeps)
# ----------------------------------------------------------------------
def local_shard_count(requested: int | str | None) -> int:
    """Resolve a device-shard request against the local device count.
    ``None`` → 1 (no sharding), ``"auto"`` → all local devices, an int is
    clamped to the available devices."""
    n_local = jax.local_device_count()
    if requested is None:
        return 1
    if requested == "auto":
        return n_local
    return max(1, min(int(requested), n_local))


def _seed_mesh(n_shards: int) -> Mesh:
    return Mesh(np.array(jax.local_devices()[:n_shards]), ("seeds",))


def sharded_seed_fn(run, *, xs_axes, n_shards: int, donate_state=True):
    """Device-sharded twin of ``jit(vmap(run))`` over a seed batch.

    ``run(pa, state, xs)`` is the per-seed scan; the returned callable
    takes a FLAT seed batch (leading axis ``S``, a multiple of
    ``n_shards``) and splits it across local devices with
    `jax.shard_map`. ``pa`` is replicated; ``state`` leaves and the
    seed-indexed ``xs`` leaves (axis 0 in `xs_axes`) carry the seed axis.
    The per-seed scan is embarrassingly parallel, so the split needs no
    collective. The state argument is donated — each call's arena state
    buffers are consumed in place instead of being copied."""
    inner = jax.vmap(run, in_axes=(None, 0, xs_axes))
    seeded = lambda a: P("seeds") if a == 0 else P()  # noqa: E731
    fn = jax.shard_map(
        inner, mesh=_seed_mesh(n_shards),
        in_specs=(P(), P("seeds"),
                  {k: seeded(a) for k, a in xs_axes.items()}),
        out_specs=P("seeds"))
    return jax.jit(fn, donate_argnums=(1,) if donate_state else ())


def sharded_grid_fn(run, *, pa_axes, xs_axes, cfg_xs_axes, seed_axes,
                    n_shards: int):
    """Device-sharded twin of the doubly-vmapped ``(C, S)`` config-grid
    run (`jax_engine.get_cached_config_fn`), split over the SEED axis.

    ``run(pa, state, xs)`` is the per-seed scan. The inner function
    vmaps seeds (``xs_axes``) then configs (``pa_axes`` over the traced
    resiliency leaves, ``cfg_xs_axes`` over the per-config xs leaves);
    `jax.shard_map` splits the flat seed axis — ``state`` leaves on axis
    0, each xs leaf on ``seed_axes[k]`` (None = replicated: the tick
    times, and the per-config ckpt schedules which carry no seed axis)
    — across local devices. Each (config, seed) chain is embarrassingly
    parallel, so outputs merge back to ``(C, S, ...)`` bit-for-bit with
    the single-device grid. State is NOT donated: grid outputs carry an
    extra config axis, so the per-shard input buffers are never
    reusable."""
    inner = jax.vmap(jax.vmap(run, in_axes=(None, 0, xs_axes)),
                     in_axes=(pa_axes, None, cfg_xs_axes))

    def spec_of(ax):
        if ax is None:
            return P()
        return P(*((None,) * ax + ("seeds",)))

    fn = jax.shard_map(
        inner, mesh=_seed_mesh(n_shards),
        in_specs=(P(), P("seeds"),
                  {k: spec_of(a) for k, a in seed_axes.items()}),
        out_specs=P(None, "seeds"))
    return jax.jit(fn)


def batch_axes_for(mesh, batch: int) -> tuple[str, ...]:
    """Data-parallel mesh axes whose product divides `batch` (longest
    prefix of ("pod", "data") present in the mesh)."""
    out: tuple[str, ...] = ()
    prod = 1
    shape = dict(mesh.shape)
    for a in ("pod", "data"):
        if a in shape and batch % (prod * shape[a]) == 0:
            out += (a,)
            prod *= shape[a]
    return out


# ----------------------------------------------------------------------
# Tree materializers
# ----------------------------------------------------------------------
def _leaf_dtype(s: ParamSpec):
    return s.dtype if s.dtype is not None else _DEFAULT_DTYPE


def _init_leaf(rng: jax.Array, s: ParamSpec) -> jax.Array:
    dt = _leaf_dtype(s)
    if s.init == "zeros":
        return jnp.zeros(s.shape, dt)
    if s.init == "ones":
        return jnp.ones(s.shape, dt)
    std = s.scale if s.scale is not None else 0.02
    return (jax.random.normal(rng, s.shape, jnp.float32) * std).astype(dt)


def tree_init(rng: jax.Array, spec_tree) -> Any:
    """Materialize real arrays for every ParamSpec leaf (split rng per leaf)."""
    leaves, treedef = jax.tree.flatten(spec_tree, is_leaf=_IS_SPEC)
    rngs = jax.random.split(rng, max(len(leaves), 1))
    return jax.tree.unflatten(
        treedef, [_init_leaf(r, s) for r, s in zip(rngs, leaves)])


def tree_abstract(spec_tree) -> Any:
    """ShapeDtypeStruct stand-ins (no allocation — the dry-run currency)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, _leaf_dtype(s)),
        spec_tree, is_leaf=_IS_SPEC)


def tree_pspecs(spec_tree, ctx: ShardingCtx) -> Any:
    """PartitionSpec per leaf via the ctx rules."""
    return jax.tree.map(lambda s: ctx.spec(s.axes, s.shape),
                        spec_tree, is_leaf=_IS_SPEC)


def tree_shardings(spec_tree, ctx: ShardingCtx) -> Any:
    """NamedSharding per leaf (None leaves when ctx has no mesh)."""
    if ctx.mesh is None:
        return jax.tree.map(lambda s: None, spec_tree, is_leaf=_IS_SPEC)
    return jax.tree.map(
        lambda s: NamedSharding(ctx.mesh, ctx.spec(s.axes, s.shape)),
        spec_tree, is_leaf=_IS_SPEC)
