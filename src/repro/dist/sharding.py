"""Logical-axis -> mesh-axis sharding rules.

Models annotate parameters and activations with *logical* axis names
("vocab", "heads", "batch", ...; see ``models.base``).  This module owns
the single mapping from those names to physical mesh axes, so switching
strategies (TP vs FSDP+TP, sequence parallelism on/off) is a rule change,
not a model change.

Every lookup is divisibility-checked against the actual dim size and each
physical axis is used at most once per tensor — an unshardable dim simply
stays replicated, which is what makes all of this single-device safe.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

# logical axis -> physical mesh axis (None = replicated).  "batch" is
# special-cased: it shards over the data-parallel axes (pod, data).
_RULES: dict[str, Optional[str]] = {
    "vocab": "model",
    "heads": "model",
    "kv": "model",
    "mlp": "model",
    "expert": "model",
    "kvseq": "model",   # decode KV-cache sequence dim (flash-decode split)
    "embed": None,      # fsdp strategies override to "data" per-param
    "layers": None,
    "seq": None,        # sequence parallelism: configure_rules(seq="model")
}


def configure_rules(**kwargs) -> dict:
    """Update rules; returns the previous values of the touched keys so
    callers can restore with ``configure_rules(**prev)``."""
    prev = {k: _RULES.get(k) for k in kwargs}
    _RULES.update(kwargs)
    return prev


def current_mesh():
    """The (abstract) mesh set by ``jax.set_mesh``, or None.  Abstract, so
    it reads the same inside and outside ``jax.jit``: axis names and sizes
    are all the cache keys and sharding constraints need.  Called on the
    op dispatch hot path (cache keys), so it must stay allocation-free."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def _axes_size(mesh, axes: Sequence[str]) -> int:
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def logical_to_pspec(axes: Sequence[Optional[str]], mesh,
                     shape: Optional[tuple] = None) -> tuple:
    """Map logical axis names to a PartitionSpec tuple for ``mesh``.

    Guards: a physical axis is used at most once per tensor (first logical
    axis wins, later ones stay replicated), and when ``shape`` is given a
    dim is only sharded if its size divides evenly."""
    used: set[str] = set()
    spec: list = []
    for i, ax in enumerate(axes):
        entry = None
        if ax == "batch":
            data_axes = [a for a in ("pod", "data")
                         if a in mesh.axis_names and a not in used]
            if shape is not None:
                while data_axes and shape[i] % _axes_size(mesh, data_axes) != 0:
                    data_axes.pop(0)   # drop pod first, then data
            if len(data_axes) == 1:
                entry = data_axes[0]
            elif data_axes:
                entry = tuple(data_axes)
        elif ax is not None:
            phys = _RULES.get(ax)
            if (phys and phys in mesh.axis_names and phys not in used
                    and (shape is None or shape[i] % mesh.shape[phys] == 0)):
                entry = phys
        if entry is not None:
            used.update(entry if isinstance(entry, tuple) else (entry,))
        spec.append(entry)
    return tuple(spec)


def batch_pspec(mesh, ndim: int = 2, batch_size: Optional[int] = None) -> P:
    """PartitionSpec for a batch-leading tensor: dim 0 over every data axis
    whose product divides ``batch_size`` (pod dropped first), dim 1 over
    the sequence-parallel axis when ``configure_rules(seq=...)`` is on."""
    data_axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    if batch_size is not None:
        while data_axes and batch_size % _axes_size(mesh, data_axes) != 0:
            data_axes.pop(0)
    if not data_axes:
        first = None
    elif len(data_axes) == 1:
        first = data_axes[0]
    else:
        first = tuple(data_axes)
    spec: list = [first] + [None] * (max(ndim, 1) - 1)
    seq_ax = _RULES.get("seq")
    if ndim >= 2 and seq_ax and seq_ax in mesh.axis_names:
        in_first = first == seq_ax or (isinstance(first, tuple) and seq_ax in first)
        if not in_first:
            spec[1] = seq_ax
    return P(*spec)


def param_shardings(axes_tree, sds_tree, mesh, strategy: str = "fsdp_tp"):
    """NamedSharding tree for parameters.

    ``strategy="tp"``: tensor-parallel axes only (heads/kv/mlp/vocab/expert
    -> model).  ``strategy="fsdp_tp"``: additionally shard the "embed"
    (d_model) axis over the data axis — FSDP-style parameter sharding."""
    fsdp = "fsdp" in strategy

    def one(axes, sds):
        used: set[str] = set()
        spec: list = []
        for i, ax in enumerate(axes):
            entry = None
            if ax is not None and ax != "batch":
                phys = _RULES.get(ax)
                if fsdp and ax == "embed":
                    phys = "data"
                if (phys and phys in mesh.axis_names and phys not in used
                        and sds.shape[i] % mesh.shape[phys] == 0):
                    entry = phys
                    used.add(phys)
            spec.append(entry)
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map(one, axes_tree, sds_tree,
                                  is_leaf=lambda x: isinstance(x, tuple))
