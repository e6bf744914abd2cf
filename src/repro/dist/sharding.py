"""Logical-axis sharding: model code annotates arrays with *logical* names
("batch", "mlp", "kv_seq", ...) and this module resolves them against
whatever mesh is active — production (pod, data, model), host test meshes,
or none at all (annotations become no-ops on a single device).

Resolution is rule-driven and shape-aware: a logical name maps to an
ordered tuple of mesh axes; axes missing from the mesh fold away, axes
already consumed by an earlier dimension are skipped (first dim wins), and
``shape_aware_spec`` additionally drops axes whose combined size does not
divide the dimension (e.g. 8 kv heads on a 16-way model axis replicate
instead of erroring)."""
from __future__ import annotations

import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax import shard_map  # noqa: F401  (re-exported for core.capture)

P = jax.sharding.PartitionSpec

# logical name -> ordered mesh axes (leftmost first; missing axes fold away)
DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "fsdp": ("pod", "data"),
    "embed": ("data",),
    "seq": ("model",),
    "kv_seq": ("model",),
    "kv_seq_model": ("model",),
    "mlp": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "ssm_inner": ("model",),
    "conv": (),
    "layer_stack": (),
    # leading axis of stacked same-shape compression-group batches
    # (core.compress device path): spread whole groups over the data
    # axes; replicates when the bucket doesn't divide (shape_aware_spec)
    "group_batch": ("pod", "data"),
    # streaming-calibration accumulators (core.capture mesh path):
    # "calib_shard" is the per-shard stacking axis of streaming-whitening
    # QR factors (one (d, d) factor per data shard, tree-reduced at
    # finalize); "gram_rows" is the row dimension of sharded (D, D) Gram
    # accumulators — each device holds a (D/n_shards, D) block and folds
    # its rows of XᵀX from all-gathered activations (DESIGN.md §1.6)
    "calib_shard": ("pod", "data"),
    "gram_rows": ("pod", "data"),
}

_CTX = threading.local()


def _rules() -> Dict[str, Tuple[str, ...]]:
    return getattr(_CTX, "rules", DEFAULT_RULES)


def current_mesh():
    return getattr(_CTX, "mesh", None)


class use_rules:
    """Context manager: overlay `rules` on the defaults and (optionally)
    pin the mesh that ``constrain`` resolves against."""

    def __init__(self, rules: Optional[Dict] = None, mesh=None):
        self._rules = dict(DEFAULT_RULES)
        self._rules.update(rules or {})
        self._mesh = mesh

    def __enter__(self):
        self._prev = (getattr(_CTX, "rules", None),
                      getattr(_CTX, "mesh", None))
        _CTX.rules = self._rules
        _CTX.mesh = self._mesh
        return self

    def __exit__(self, *exc):
        _CTX.rules, _CTX.mesh = self._prev
        return False


def _mesh_axes(mesh) -> Dict[str, int]:
    return dict(mesh.shape)


def _resolve(name: Optional[str], mesh_shape: Dict[str, int],
             used: set) -> Tuple[str, ...]:
    if name is None:
        return ()
    want = _rules().get(name, ())
    return tuple(a for a in want if a in mesh_shape and a not in used)


def _entry(axes: Tuple[str, ...]):
    if not axes:
        return None
    if len(axes) == 1:
        return axes[0]
    return axes


def logical_spec(axes: Sequence[Optional[str]], mesh) -> P:
    """Resolve logical names to a PartitionSpec (no shape checks)."""
    mesh_shape = _mesh_axes(mesh)
    used: set = set()
    entries = []
    for name in axes:
        got = _resolve(name, mesh_shape, used)
        used.update(got)
        entries.append(_entry(got))
    return P(*entries)


def shape_aware_spec(shape: Sequence[int], axes: Sequence[Optional[str]],
                     mesh) -> P:
    """Like ``logical_spec`` but drops (from the right) mesh axes whose
    combined size does not evenly divide the array dimension, so awkward
    shapes replicate instead of failing to lower."""
    assert len(shape) == len(axes), (shape, axes)
    mesh_shape = _mesh_axes(mesh)
    used: set = set()
    entries = []
    for dim, name in zip(shape, axes):
        resolved = _resolve(name, mesh_shape, used)
        got = resolved
        while got:
            total = 1
            for a in got:
                total *= mesh_shape[a]
            if dim % total == 0:
                break
            got = got[:-1]
        used.update(got)
        # a divisibility-reduced composite keeps its tuple form (partial
        # sharding of a folded axis group); plain resolutions unwrap
        entries.append(got if got and got != resolved else _entry(got))
    return P(*entries)


def constrain(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Annotate `x` with logical axes. No-op unless a mesh is active
    (``use_rules(..., mesh=...)``)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = shape_aware_spec(x.shape, axes, mesh)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, spec))


def axis_group_size(mesh, axes: Sequence[str]) -> int:
    """Total number of shards along a folded mesh-axis group."""
    size = 1
    for a in axes:
        size *= dict(mesh.shape)[a]
    return size


def combined_axis_index(axes: Sequence[str], mesh) -> jax.Array:
    """Row-major linear shard index along a folded axis group — the
    ``shard_map``-body counterpart of folding several mesh axes into one
    PartitionSpec entry (e.g. the sharded-Gram row blocks: the block a
    device owns is ``combined_axis_index * block_rows``)."""
    mesh_shape = dict(mesh.shape)
    idx = None
    for a in axes:
        i = jax.lax.axis_index(a)
        idx = i if idx is None else idx * mesh_shape[a] + i
    return idx if idx is not None else 0


def shardings_for_tree(params, specs, mesh):
    """NamedSharding tree for a (params, axis-name specs) tree pair. Works
    on concrete arrays or ShapeDtypeStructs (abstract dry-runs)."""
    return jax.tree.map(
        lambda v, s: jax.sharding.NamedSharding(
            mesh, shape_aware_spec(v.shape, s, mesh)),
        params, specs)
