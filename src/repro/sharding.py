"""Logical-axis sharding: one rule table maps every tensor in the framework
onto the production meshes.

Meshes (launch/mesh.py):
    single-pod: (16, 16)    axes ("data", "model")
    multi-pod : (2, 16, 16) axes ("pod", "data", "model")

Logical axes:
    batch    -> (pod,) data      (DP; batch dim of activations)
    embed    -> data if fsdp else None   (FSDP / ZeRO-3 on the d_model dim)
    vocab    -> model            (TP of embedding + LM head)
    heads    -> model            (TP of attention heads)
    kv_heads -> model            (TP of KV heads; may be uneven -> GSPMD pads)
    mlp      -> model            (TP of the FFN hidden dim)
    expert   -> model            (EP of MoE experts)
    seq/layers/state/... -> None

Models never name mesh axes directly — they call `logical_spec(...)` /
`constrain(x, ...)` so the same code runs on a laptop (no mesh), one pod, or
many pods.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

def shard_map(f, mesh: Mesh, in_specs, out_specs):
    """`jax.shard_map` with the replication check off: every shard_map in
    this repo wraps bodies the checker cannot analyze (Pallas calls,
    ppermute cascades)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_rules(mesh: Optional[Mesh], fsdp: bool = True) -> dict:
    if mesh is None:
        return {}
    axes = set(mesh.axis_names)
    batch = tuple(a for a in ("pod", "data") if a in axes)
    rules = {
        "batch": batch if batch else None,
        "vocab": "model" if "model" in axes else None,
        "heads": "model" if "model" in axes else None,
        "kv_heads": "model" if "model" in axes else None,
        "mlp": "model" if "model" in axes else None,
        "expert": "model" if "model" in axes else None,
        # full expert parallelism: expert banks sharded over data x model
        # jointly (deepseek: 256 experts / 256 chips = 1 per chip) — no
        # per-layer weight all-gather; tokens all-to-all to expert owners.
        "expert_full": (("data", "model") if ("data" in axes and
                                              "model" in axes)
                        else ("model" if "model" in axes else None)),
        "embed": ("data" if (fsdp and "data" in axes) else None),
        # activation feature dim: NOT FSDP-sharded (that's params-only);
        # hillclimb experiments may remap this to "model" (sequence/TP out)
        "act_embed": None,
        # Megatron-style sequence parallelism: the residual stream's token
        # dim is sharded over the TP axis between blocks (pointwise ops and
        # the MLP run sequence-sharded; GSPMD all-gathers only where
        # attention genuinely needs the full sequence, and reduce-scatters
        # back).  16× less residual memory + converts TP all-reduces into
        # RS+AG pairs.  Shape-aware fallback replicates when S % 16 != 0
        # (e.g. decode S=1).
        "act_seq": "model" if "model" in axes else None,
        # 8-bit optimizer-state blocks: flat layout, sharded over EVERYTHING
        # (ZeRO for quantized moments); shape-aware fallback leaves small
        # tensors replicated.
        "qblocks": batch + ("model",) if "model" in axes else batch or None,
    }
    return rules


class _Ctx(threading.local):
    mesh: Optional[Mesh] = None
    rules: dict = {}


_CTX = _Ctx()


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], fsdp: bool = True, rules: Optional[dict] = None):
    """Activate a mesh + logical rules for model code in this thread."""
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh = mesh
    _CTX.rules = rules if rules is not None else make_rules(mesh, fsdp)
    try:
        if mesh is not None:
            with mesh:
                yield
        else:
            yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def _axis_size(mesh: Mesh, r) -> int:
    if r is None:
        return 1
    if isinstance(r, (tuple, list)):
        n = 1
        for a in r:
            n *= mesh.shape[a]
        return n
    return mesh.shape[r]


def logical_spec(logical_axes: Sequence[Optional[str]],
                 shape: Optional[Sequence[int]] = None) -> P:
    """Translate logical axis names to a PartitionSpec under current rules.

    If `shape` is given, any mapping whose mesh-axis size does not evenly
    divide the dimension is dropped (replicated) — e.g. 8 KV heads on a
    16-way model axis.  This "best-effort" fallback keeps every config
    lowerable; padding heads instead is a per-arch config choice.
    """
    rules = _CTX.rules
    mesh = _CTX.mesh
    parts = []
    used = set()
    for i, ax in enumerate(logical_axes):
        r = rules.get(ax) if ax else None
        if r is not None and shape is not None and mesh is not None:
            if shape[i] % _axis_size(mesh, r) != 0:
                r = None
        # a mesh axis may appear once per spec: first logical axis wins
        # (e.g. KV caches: act_seq and kv_heads both -> "model")
        if r is not None:
            names = r if isinstance(r, (tuple, list)) else (r,)
            if any(n in used for n in names):
                r = None
            else:
                used.update(names)
        parts.append(r)
    return P(*parts)


def named_sharding(logical_axes: Sequence[Optional[str]],
                   shape: Optional[Sequence[int]] = None
                   ) -> Optional[NamedSharding]:
    if _CTX.mesh is None:
        return None
    return NamedSharding(_CTX.mesh, logical_spec(logical_axes, shape))


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint in logical axes; no-op without a mesh."""
    if _CTX.mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_CTX.mesh, logical_spec(logical_axes, x.shape)))


def spec_tree(axes_tree):
    """Map a pytree of logical-axes tuples to PartitionSpecs."""
    return jax.tree.map(
        lambda axes: logical_spec(axes),
        axes_tree,
        is_leaf=lambda t: isinstance(t, tuple) and all(
            a is None or isinstance(a, str) for a in t),
    )


def sharding_tree(axes_tree):
    """Map a pytree of logical-axes tuples to NamedShardings (or None)."""
    mesh = _CTX.mesh
    if mesh is None:
        return jax.tree.map(lambda _: None, axes_tree,
                            is_leaf=lambda t: isinstance(t, tuple))
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, logical_spec(axes)),
        axes_tree,
        is_leaf=lambda t: isinstance(t, tuple) and all(
            a is None or isinstance(a, str) for a in t),
    )


def pad_to_multiple(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult
