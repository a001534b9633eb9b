"""Distribution layer: logical-axis sharding rules, activation sharding
constraints, and the fault-tolerant training loop.

Everything here is **single-device safe**: with no mesh active (or a
one-device mesh) every function degrades to the identity, so smoke tests
and the CPU container run the exact same model code as a TPU pod.

``shard_act(x, *logical_axes)`` is the model-side entry point: it attaches
a sharding constraint mapping logical axis names ("batch", "heads", ...)
to mesh axes via the rules in :mod:`repro.dist.sharding`.  Inside an open
``tapir`` region the constraint is captured as a ``sharding`` annotation
on the producing IR node (``tapir.annotate_sharding``): every pass sees
it, and lowering replays it as ``jax.lax.with_sharding_constraint`` under
the ambient mesh — regions and GSPMD compose instead of the tracer
silently dropping constraints.
"""
from __future__ import annotations

from .fault import (Fault, FaultInjector, FaultTolerantLoop,
                    ScriptedFaultInjector, StragglerWatchdog)
from .sharding import (batch_pspec, configure_rules, current_mesh,
                       logical_to_pspec, param_shardings)


def shard_act(x, *logical_axes):
    """Constrain activation ``x``'s sharding by logical axis names.

    No-op when no mesh is active or the mesh is a single device.  On a
    lazy region handle (TracedTensor) the resolved spec is recorded as a
    ``sharding`` annotation on the producing node and replayed at
    lowering; on a concrete array it applies immediately."""
    from repro.core.tapir import annotate_sharding, is_traced
    mesh = current_mesh()
    if mesh is None or mesh.size <= 1:
        return x
    spec = logical_to_pspec(logical_axes, mesh, shape=tuple(x.shape))
    if is_traced(x):
        return annotate_sharding(x, spec)
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    try:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*spec)))
    except ValueError:
        # outside a jit trace on some jax versions; constraint is advisory
        return x
