"""Logical-axis -> mesh-axis rule table (MaxText-style).

Every parameter/cache leaf declares logical axes in its schema; these rules
map them onto the production mesh. Rules fall back to replication when a
dim is not divisible by the mesh axis (specs_from_schema), so a single
rule table serves all ten architectures — the per-arch hillclimb
overrides live in ParallelConfig. Each distinct fall-back emits a
one-time ``RuntimeWarning`` naming the axis and sizes, so lost
parallelism is visible instead of silent.
"""
from __future__ import annotations

import warnings
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ParallelConfig

# replication fall-backs already reported, keyed by (where, axis, dim,
# size) — falling back is the designed behavior (one rule table serves
# every architecture), but doing it SILENTLY hides lost parallelism, so
# each distinct fall-back warns exactly once per process
_REPLICATION_WARNED = set()


def _warn_replicated(where: str, axis, dim: int, size: int):
    # the obs counter bumps on EVERY fall-back (that's what a counter
    # is for); the Python warning below stays once-per-process
    from repro import obs
    obs.event("warn.replication_fallback", where=where, axis=str(axis))
    key = (where, str(axis), int(dim), int(size))
    if key in _REPLICATION_WARNED:
        return
    _REPLICATION_WARNED.add(key)
    warnings.warn(
        f"{where}: dim {dim} is not divisible by mesh axis {axis!r} "
        f"(size {size}); falling back to replication — this dimension "
        f"gets NO parallelism. Pad the dim to a multiple of {size} or "
        f"shrink the mesh axis to recover it.",
        RuntimeWarning, stacklevel=3)


def resolve_mesh_axis(devices, dim: int, where: str,
                      axis: str = "restart") -> Optional[int]:
    """Validate a user-facing ``devices=`` request and return the mesh
    size to build, or ``None`` to run unsharded.

    ``None``/1 asks for no mesh; non-positive or more-devices-than-
    visible raise (the latter naming the ``XLA_FLAGS`` host-device trick,
    same message family as ``simulate_grid``); a ``dim`` that doesn't
    divide the mesh falls back to the unsharded path with the same
    warn-once replication ``RuntimeWarning`` the rule table emits — lost
    parallelism is visible, never silent, and results are identical
    either way (the sharded kernels are bit-identical by construction).
    """
    if devices is None or devices == 1:
        return None
    devices = int(devices)
    if devices <= 0:
        raise ValueError(f"devices must be a positive mesh size, "
                         f"got {devices}")
    if devices > jax.device_count():
        raise ValueError(
            f"devices={devices} but only {jax.device_count()} "
            f"JAX device(s) are visible; on CPU export "
            f"XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{devices} before the first jax import")
    if dim % devices != 0:
        _warn_replicated(where, axis, dim, devices)
        return None
    return devices


def mesh_shape_dict(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def build_rules(parallel: ParallelConfig, mesh: Mesh) -> Dict[str, Optional[str]]:
    axes = set(mesh.axis_names)

    def ax(a):
        return a if a in axes else None

    batch = tuple(a for a in parallel.batch_axes if a in axes)
    seq_ax = parallel.seq_axis
    if isinstance(seq_ax, tuple):
        seq_ax = tuple(a for a in seq_ax if a in axes) or None
    else:
        seq_ax = ax(seq_ax)
    return {
        "vocab": ax(parallel.tp_axis),
        "embed": ax(parallel.fsdp_axis),
        "mlp": ax(parallel.tp_axis),
        "heads": ax(parallel.tp_axis),
        "kv": ax(parallel.tp_axis),
        "kv_heads": ax(parallel.tp_axis),
        "expert": ax(parallel.expert_axis),
        "batch": batch if batch else None,
        "cache_seq": seq_ax if parallel.shard_cache_seq else None,
        # activation-only logical axes (constrain() checks divisibility)
        "heads_act": ax(parallel.tp_axis),
        "kv_heads_act": ax(parallel.tp_axis),
        "vocab_act": ax(parallel.tp_axis),
        "mlp_act": ax(parallel.tp_axis),
        "expert_act": ax(parallel.expert_axis),
        "seq_act": None,   # sequence parallelism (hillclimb override)
        # small/replicated dims
        "rank": None, "state": None, "conv": None, "norm": None,
        "layers": None, "groups": None,
    }


def batch_partition(parallel: ParallelConfig, mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in parallel.batch_axes if a in set(mesh.axis_names))


# ---------------------------------------------------------------------------
# Activation sharding constraints
#
# GSPMD's propagation is a global solve: without anchors it may pick
# different activation layouts for near-identical programs (observed:
# 1-group vs 2-group probes sharding attention differently). Model code
# calls ``constrain(x, ...logical axes)``; the step factories install the
# mesh + rules here before tracing. No-op when nothing is installed, so
# model code stays mesh-free.
# ---------------------------------------------------------------------------

_ACT = {"mesh": None, "rules": None}


def set_activation_mesh(mesh: Optional[Mesh], rules: Optional[Dict] = None):
    _ACT["mesh"] = mesh
    _ACT["rules"] = rules


def constrain(x, *logical_axes):
    """with_sharding_constraint by logical axis names (None = replicated).
    Drops any axis whose dim is not divisible by the mesh axis size."""
    mesh, rules = _ACT["mesh"], _ACT["rules"]
    if mesh is None or rules is None:
        return x
    import jax
    shape_d = mesh_shape_dict(mesh)
    spec, used = [], set()
    for dim, ax in zip(x.shape, logical_axes):
        mesh_ax = rules.get(ax) if ax is not None else None
        if mesh_ax is None:
            spec.append(None)
            continue
        axes_t = mesh_ax if isinstance(mesh_ax, tuple) else (mesh_ax,)
        if any(a in used for a in axes_t):
            spec.append(None)
            continue
        size = 1
        for a in axes_t:
            size *= shape_d.get(a, 1)
        if size > 1 and dim % size == 0:
            spec.append(mesh_ax)
            used.update(axes_t)
        else:
            if size > 1:
                _warn_replicated(f"constrain(logical axis {ax!r})",
                                 mesh_ax, dim, size)
            spec.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def input_batch_specs(batch_abstract: Dict, parallel: ParallelConfig,
                      mesh: Mesh) -> Dict:
    """PartitionSpecs for a model input batch: shard dim 0 (batch) over the
    dp axes when divisible; positions [3, b, s] shard dim 1."""
    dp = batch_partition(parallel, mesh)
    size = 1
    for a in dp:
        size *= mesh_shape_dict(mesh)[a]
    out = {}
    for k, v in batch_abstract.items():
        if k == "positions" and len(v.shape) == 3:
            if v.shape[1] % size == 0:
                out[k] = P(None, dp, None)
            else:
                if size > 1:
                    _warn_replicated(f"input_batch_specs({k!r})", dp,
                                     v.shape[1], size)
                out[k] = P()
        elif v.ndim >= 1 and v.shape[0] % size == 0 and size > 1:
            out[k] = P(dp, *([None] * (v.ndim - 1)))
        else:
            if size > 1 and v.ndim >= 1:
                _warn_replicated(f"input_batch_specs({k!r})", dp,
                                 v.shape[0], size)
            out[k] = P()
    return out
