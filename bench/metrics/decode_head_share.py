"""Share of the device's busy time inside the traced decode steps that the
LM head's program takes, %: device operations of the ``tapir_slot_head``
region program (module ``jit_tapir_slot_head``) inside the ``bench.decode``
host spans, over all device operations inside them.  Each span holds one
``decode_step_slots`` call up to its logits; the engine's argmax after it
(a few microseconds) is left out.  A trace whose programs carry no region
names finds nothing to read."""
from bench.harness import xplane

MODULE = "jit_tapir_slot_head"


def _head_ops(ops: list, modules: list) -> list:
    """The operations of one device plane that start inside a head module
    (both sorted; a plane runs one module at a time)."""
    heads = [(s, e) for s, e, n in modules if n.split("(")[0] == MODULE]
    out, j = [], 0
    for s, e, _ in ops:
        while j < len(heads) and heads[j][1] <= s:
            j += 1
        if j < len(heads) and heads[j][0] <= s:
            out.append((s, e))
    return out


def read(ctx):
    if ctx["kind"] != "serve" or ctx["trace"] is None \
            or not ctx["trace"].ops:
        return None
    tr = ctx["trace"]
    lo, hi = xplane.window(tr)
    spans = [s for s in xplane.spans_named(tr, "bench.decode")
             if s[0] >= lo and s[1] <= hi]
    busy = xplane.busy_ns(tr, spans)
    head = [xplane.covered(xplane.merge(_head_ops(
                ops, tr.modules.get(plane, []))), spans)
            for plane, ops in tr.ops.items()]
    if not spans or busy <= 0 or not any(head):
        return None
    return 100.0 * (sum(head) / len(head)) / busy
