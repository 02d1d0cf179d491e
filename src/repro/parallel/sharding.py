"""Logical-axis sharding: DP / FSDP / TP / EP / SP on one mesh.

Mapping (defaults; per-arch overrides via ``ArchConfig``):

  batch   -> ("pod", "data")      data parallel (+ cross-pod DP)
  embed   -> ("data",)            FSDP / ZeRO-3 shard of weight d_model dims
             ("pod","data")       for the 123B/340B class (fsdp_over_pod)
  mlp     -> ("model",)           tensor parallel (ffn hidden)
  heads   -> ("model",)           tensor parallel (attention heads)
  kv      -> ("model",)           kv heads (usually < mesh => auto-dropped)
  vocab   -> ("model",)           embedding/lm-head vocab dim
  expert  -> ("model",)           expert parallel (MoE)
  kv_seq  -> ("model",)           sequence-parallel KV cache at decode
  layers  -> ()                   scan-stacked layer dim, never sharded

Divisibility degradation: if a tensor dim is not divisible by the mapped
mesh-axis product, the mapping *degrades* to the longest divisible prefix
(possibly replicated).  This is deliberate — the paper's theme is best-effort
programmability, and it makes every (arch x shape x mesh) cell lower without
hand-tuning 15-head / 8-kv-head edge cases.  The dry-run report records the
degradations so none of them are silent.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class Sharder:
    def __init__(self, mesh: Mesh, rules: dict):
        self.mesh = mesh
        self.rules = dict(rules)
        self.mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.degradations: list = []

    # -- spec construction ---------------------------------------------------
    def _axes_for(self, logical: Optional[str], dim: int, used: set):
        if logical is None:
            return ()
        mapped = self.rules.get(logical, ())
        picked = []
        size = 1
        for ax in mapped:
            if ax not in self.mesh_sizes or ax in used:
                continue
            nxt = size * self.mesh_sizes[ax]
            if dim % nxt != 0:
                break
            picked.append(ax)
            size = nxt
        if mapped and len(picked) < len([a for a in mapped
                                         if a in self.mesh_sizes]):
            self.degradations.append((logical, dim, tuple(mapped),
                                      tuple(picked)))
        return tuple(picked)

    def spec(self, logical_axes: tuple, shape: tuple) -> P:
        used: set = set()
        out = []
        for logical, dim in zip(logical_axes, shape):
            axes = self._axes_for(logical, dim, used)
            used.update(axes)
            if len(axes) == 0:
                out.append(None)
            elif len(axes) == 1:
                out.append(axes[0])
            else:
                out.append(tuple(axes))
        return P(*out)

    def named(self, logical_axes: tuple, shape: tuple) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical_axes, shape))

    def constrain(self, x, *logical_axes):
        if len(logical_axes) != x.ndim:
            raise ValueError(
                f"constrain: {len(logical_axes)} axes for rank-{x.ndim}"
            )
        return jax.lax.with_sharding_constraint(
            x, self.named(tuple(logical_axes), x.shape)
        )

    # -- whole-pytree helpers -------------------------------------------------
    def tree_shardings(self, axes_tree, shape_tree):
        """NamedSharding tree for params: axes_tree from ``param_axes``,
        shape_tree of arrays or ShapeDtypeStructs with matching structure."""
        return jax.tree.map(
            lambda ax, arr: self.named(tuple(ax), arr.shape),
            axes_tree, shape_tree,
            is_leaf=lambda a: isinstance(a, tuple),
        )


def make_rules(mesh: Mesh, *, fsdp_over_pod: bool = False) -> dict:
    has_pod = "pod" in mesh.axis_names
    batch = ("pod", "data") if has_pod else ("data",)
    fsdp = batch if (fsdp_over_pod and has_pod) else ("data",)
    return {
        "batch": batch,
        "embed": fsdp,
        "mlp": ("model",),
        "heads": ("model",),
        "kv": ("model",),
        "vocab": ("model",),
        "expert": ("model",),
        "kv_seq": ("model",),
        "q_seq": ("model",),
        "expert_cap": ("data",),
        "state": ("model",),
        "layers": (),
    }


# --------------------------------------------------------------------------
# PlacementPlan: the device-placement half of the serving engine's
# layout x placement product.  Cache LAYOUT (contiguous vs paged KV
# blocks, ``repro.serving.layout``) and device PLACEMENT (replicated vs
# PE-sharded) are orthogonal refinement axes — the paper applies PE
# duplication and scratchpad reorganization *together*, and AutoDSE-style
# search needs the knob space to stay a product — so the plan is its own
# object instead of a fork inside the engine.
# --------------------------------------------------------------------------


class PlacementPlan:
    """Where the serving engine's arrays live: one data-parallel mesh
    axis (or none).

    ``mesh is None`` is the replicated plan — every helper degrades to a
    no-op, so single-device engines pay nothing and callers never branch.
    With a mesh, the helpers hand out the three sharding families the
    decode step needs: ``replicated`` (params, block tables),
    :meth:`axis` (one array axis over ``"data"`` — the batch axis of a
    contiguous cache, the BLOCK axis of a paged pool), and the
    per-tick token/position shardings.
    """

    def __init__(self, mesh: Optional[Mesh] = None):
        self.mesh = mesh

    @property
    def sharded(self) -> bool:
        return self.mesh is not None

    @property
    def n_devices(self) -> int:
        return 1 if self.mesh is None else int(self.mesh.devices.size)

    # -- sharding constructors (None when unsharded) -------------------------
    @property
    def replicated(self) -> Optional[NamedSharding]:
        return None if self.mesh is None else NamedSharding(self.mesh, P())

    def axis(self, ax: int) -> Optional[NamedSharding]:
        """Shard one array axis over the data mesh axis."""
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P(*([None] * ax + ["data"])))

    def token_shardings(self):
        """(tokens (B, 1), positions/seeds (B,)) shardings for the step."""
        if self.mesh is None:
            return None, None
        return (NamedSharding(self.mesh, P("data", None)),
                NamedSharding(self.mesh, P("data")))

    def cache_shardings(self, model, batch_size: int, max_seq: int):
        """Batch-axis shardings for a CONTIGUOUS per-slot cache tree
        (every leaf sharded on its logical ``batch`` axis)."""
        if self.mesh is None:
            return None
        sharder = Sharder(self.mesh, {"batch": ("data",)})
        return sharder.tree_shardings(model.cache_axes(),
                                      model.cache_spec(batch_size, max_seq))

    # -- placement application ----------------------------------------------
    def put_replicated(self, tree):
        """Replicate a pytree across the plan's devices (identity when
        unsharded)."""
        if self.mesh is None:
            return tree
        return jax.device_put(tree, self.replicated)

    def constrain_axis(self, leaf, ax: int):
        """In-graph re-shard of ``leaf`` on axis ``ax`` (identity when
        unsharded) — how the paged step turns its gathered dense view
        into a batch-sharded one so the model runs PE-duplicated."""
        if self.mesh is None:
            return leaf
        return jax.lax.with_sharding_constraint(leaf, self.axis(ax))

    def tracing(self):
        """Context to trace a step of this plan in: JAX's abstract mesh
        is the plan's, so a Mosaic kernel inside the step runs on every
        device over replicated operands (``kernels/paged_attention/ops``)
        instead of failing to partition.  No-op when unsharded."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.sharding.use_abstract_mesh(self.mesh.abstract_mesh)

    def constrain_replicated(self, leaf):
        """In-graph re-shard of ``leaf`` to fully replicated (identity
        when unsharded) — how the gather-free paged KERNEL step keeps a
        BLOCK-axis-sharded pool working: the Pallas kernel is a
        single-device program, so the step replicates the pool for the
        kernel call and its ``out_shardings`` re-shard the written pool
        back onto the block axis.  Correctness everywhere, measured
        profitability decides (the best-effort contract)."""
        if self.mesh is None:
            return leaf
        return jax.lax.with_sharding_constraint(leaf, self.replicated)


def plan_pe_placement(config, batch_size: int,
                      devices=None) -> PlacementPlan:
    """Build the engine's :class:`PlacementPlan` from its config.

    PE duplication degrades, never fails (the repo-wide best-effort
    contract): ``pe`` is clipped to the visible devices, then reduced
    until the batch divides it; anything that lands at 1 returns the
    replicated plan.  The same plan serves both cache layouts — the
    layout object decides WHICH axis each array shards on.
    """
    pe = config.effective_pe
    if pe <= 1:
        return PlacementPlan()
    devs = list(devices) if devices is not None else jax.devices()
    n = min(pe, len(devs))
    while n > 1 and batch_size % n:
        n -= 1
    if n <= 1:
        return PlacementPlan()
    return PlacementPlan(Mesh(np.asarray(devs[:n]), ("data",)))


# --------------------------------------------------------------------------
# Ambient sharder: models call ``constrain(...)`` unconditionally; outside a
# mesh context it is the identity, so CPU smoke tests need no mesh plumbing.
# --------------------------------------------------------------------------

_local = threading.local()


def set_sharder(s: Optional[Sharder]):
    _local.sharder = s


def get_sharder() -> Optional[Sharder]:
    return getattr(_local, "sharder", None)


class use_sharder:
    def __init__(self, s: Sharder):
        self.s = s

    def __enter__(self):
        self.prev = get_sharder()
        set_sharder(self.s)
        return self.s

    def __exit__(self, *exc):
        set_sharder(self.prev)


def constrain(x, *logical_axes):
    s = get_sharder()
    if s is None:
        return x
    return s.constrain(x, *logical_axes)
