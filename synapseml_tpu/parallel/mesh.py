"""Mesh construction and sharding policy — the heart of the TPU runtime.

Replaces the reference's three per-engine comm stacks (LightGBM socket ring
``NetworkManager.scala``, VW spanning-tree ``VowpalWabbitClusterUtil.scala:15-42``,
horovod ring-allreduce ``dl/utils.py:31-46``) with ONE backend: a named
`jax.sharding.Mesh` whose axes express every parallelism the framework uses:

  axis      meaning                                   reference analog
  ----      -------                                   ----------------
  'data'    data parallelism (batch sharding)         Spark partitions / horovod DP
  'fsdp'    parameter sharding inside the DP group    (none — net new)
  'tensor'  tensor (model) parallelism                (none — net new)
  'seq'     sequence/context parallelism              (none — net new, ring attention)
  'expert'  expert parallelism for MoE                (none — net new)
  'pipe'    pipeline (stage) parallelism              (none — net new, GPipe schedule)

Collectives ride ICI within a slice, DCN across slices; XLA inserts them from
sharding annotations (GSPMD), we only name axes and place constraints.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import logging
import math
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["MeshConfig", "MeshContext", "create_mesh", "current_mesh",
           "batch_sharding", "replicated",
           "logical_axis_rules", "shard_params", "shard_inference_params", "P"]

AXES = ("data", "fsdp", "tensor", "seq", "expert", "pipe")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Axis sizes; -1 on `data` means 'absorb all remaining devices'."""

    data: int = -1
    fsdp: int = 1
    tensor: int = 1
    seq: int = 1
    expert: int = 1
    pipe: int = 1

    def resolve(self, n_devices: int) -> dict[str, int]:
        sizes = dataclasses.asdict(self)
        fixed = math.prod(v for v in sizes.values() if v > 0)
        free = [k for k, v in sizes.items() if v <= 0]
        if len(free) > 1:
            raise ValueError(f"at most one axis may be -1, got {free}")
        if free:
            if n_devices % fixed:
                raise ValueError(f"{n_devices} devices not divisible by fixed axes {fixed}")
            sizes[free[0]] = n_devices // fixed
        if math.prod(sizes.values()) != n_devices:
            raise ValueError(f"mesh {sizes} does not cover {n_devices} devices")
        return sizes


# the MeshContext whose scope() is active in this thread (see current_mesh)
_SCOPE: contextvars.ContextVar = contextvars.ContextVar(
    "synapseml_tpu_mesh", default=None)


class MeshContext:
    """A constructed mesh plus sharding helpers; the framework-wide handle that
    estimators receive instead of a horovod backend / NetworkManager."""

    def __init__(self, mesh: Mesh, config: MeshConfig):
        self.mesh = mesh
        self.config = config

    @property
    def axis_sizes(self) -> dict[str, int]:
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def batch_sharding(self) -> NamedSharding:
        """Shard leading (batch) dim over every data-like axis."""
        return self.sharding(("data", "fsdp"))

    def stacked_batch_sharding(self) -> NamedSharding:
        """[K, batch, ...] step-stacked arrays: K replicated (the scan axis),
        the batch dim sharded as ``batch_sharding`` shards it."""
        return self.sharding(None, ("data", "fsdp"))

    def replicated(self) -> NamedSharding:
        return self.sharding()

    def data_parallel_size(self) -> int:
        s = self.axis_sizes
        return s.get("data", 1) * s.get("fsdp", 1)

    def shard_batch(self, batch: Any) -> Any:
        """Place a host pytree of arrays onto the mesh, batch-dim sharded.
        Cross-process meshes build from local slices (``place_leaf``) —
        every process supplies the same global batch."""
        from .partition import place_leaf

        sh = self.batch_sharding()
        return jax.tree.map(lambda x: place_leaf(x, sh), batch)

    def shard_stacked_batch(self, batch: Any) -> Any:
        """Place [K, batch, ...] step-stacked arrays: K replicated (scan axis),
        batch dim sharded over the data axes."""
        from .partition import place_leaf

        sh = self.stacked_batch_sharding()
        return jax.tree.map(lambda x: place_leaf(x, sh), batch)

    @contextlib.contextmanager
    def scope(self):
        """Put this mesh in scope for everything traced inside:
        :func:`current_mesh` returns it, which is how sequence-parallel
        attention finds the mesh to ``shard_map`` over.

        Framework-owned on purpose. ``jax.set_mesh`` would also switch on
        flax's own param constraints, and flax 0.12.3 cannot trace those:
        ``DenseGeneral`` makes its kernel at a flattened rank that the
        kernel's 3-name partition spec cannot annotate, so every
        ``module.apply`` under a set mesh raises. The legacy ``with mesh:``
        is visible only through the deprecated ``pxla.thread_resources``."""
        token = _SCOPE.set(self)
        try:
            yield self
        finally:
            _SCOPE.reset(token)


def current_mesh() -> "MeshContext | None":
    """The :class:`MeshContext` whose :meth:`~MeshContext.scope` is active in
    this thread, or None."""
    return _SCOPE.get()


def create_mesh(config: MeshConfig | None = None, devices: Sequence[Any] | None = None,
                allow_fewer: bool = True) -> MeshContext:
    """Build the framework mesh over the available devices.

    Device order: `jax.devices()` already orders TPU devices so that adjacent
    ids are ICI neighbors within a host; we lay the fastest-varying mesh axes
    (tensor/seq) innermost so their collectives stay on-host/ICI and `data`
    outermost so DP gradient reduction crosses DCN only when unavoidable —
    the TPU equivalent of the reference's "sort machine list by min partition id"
    determinism (``NetworkManager.scala:354-425``).
    """
    config = config or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    try:
        sizes = config.resolve(n)
    except ValueError as e:
        if not allow_fewer:
            raise
        # degrade to pure data-parallel on smaller device counts (e.g.
        # 1-chip CI) — never silently: the caller asked for another layout
        sizes = {k: 1 for k in AXES}
        sizes["data"] = n
        logging.getLogger(__name__).warning(
            "mesh %s does not fit %d device(s) (%s); degrading to pure data "
            "parallel %s — pass allow_fewer=False to make this an error",
            config, n, e, {"data": n})
    shape = tuple(sizes[a] for a in AXES)
    arr = np.asarray(devices).reshape(shape)
    mesh = Mesh(arr, AXES)
    return MeshContext(mesh, config)


def batch_sharding(mesh_ctx: MeshContext) -> NamedSharding:
    return mesh_ctx.batch_sharding()


def replicated(mesh_ctx: MeshContext) -> NamedSharding:
    return mesh_ctx.replicated()


# ---- logical axis rules: Flax `nn.with_partitioning` names -> mesh axes ----

DEFAULT_RULES: tuple[tuple[str, Any], ...] = (
    ("batch", ("data", "fsdp")),
    ("embed", "fsdp"),
    ("mlp", "tensor"),
    ("heads", "tensor"),
    ("kv", None),
    ("vocab", "tensor"),
    ("seq", "seq"),
    ("expert", "expert"),
    ("pipe", "pipe"),
)


def logical_axis_rules(extra: Sequence[tuple[str, Any]] = ()) -> list[tuple[str, Any]]:
    return list(DEFAULT_RULES) + list(extra)


def shard_params(params: Any, mesh_ctx: MeshContext, rules: Sequence[tuple[str, Any]] | None = None) -> Any:
    """Apply logical->physical sharding to a Flax param pytree with
    `nn.Partitioned` metadata; plain arrays replicate."""
    import flax.linen as nn
    from flax.core import meta

    rules = rules or logical_axis_rules()

    def to_sharding(x):
        if isinstance(x, meta.Partitioned):
            spec = nn.logical_to_mesh_axes(x.names, rules=rules)
            return jax.device_put(x.value, NamedSharding(mesh_ctx.mesh, spec))
        return jax.device_put(x, mesh_ctx.replicated())

    return jax.tree.map(to_sharding, params,
                        is_leaf=lambda x: isinstance(x, meta.Partitioned))


def shard_inference_params(module, example_inputs: dict, params, mesh_ctx,
                           rules: Sequence[tuple[str, Any]] | None = None):
    """Place a PLAIN param pytree (e.g. from models.convert_hf) onto the mesh
    with the module's logical shardings — the inference-side analog of the
    trainer's init-time sharding (Llama-2-7B sharded batch inference,
    BASELINE.md). The module is abstractly initialized (eval_shape: no
    compute, no memory) just to recover each param's ``nn.Partitioned`` axis
    names; values then device_put with those shardings.
    """
    import jax

    from flax.core import meta

    abstract = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), **example_inputs))
    boxes = abstract["params"]
    flat_boxes = {tuple(str(getattr(k, "key", k)) for k in path): leaf
                  for path, leaf in jax.tree_util.tree_flatten_with_path(
                      boxes, is_leaf=lambda x: isinstance(x, meta.Partitioned))[0]}

    # re-box the plain values with the module's metadata, then delegate to
    # shard_params so train and inference placement share one code path
    def rebox(path, v):
        key = tuple(str(getattr(k, "key", k)) for k in path)
        box = flat_boxes.get(key)
        if isinstance(box, meta.Partitioned):
            return box.replace_boxed(v)
        return v

    boxed = jax.tree_util.tree_map_with_path(rebox, params)
    return shard_params(boxed, mesh_ctx, rules)
