"""Adversarial correctness harness for the two-tier compiled-program cache.

Four attack surfaces:

1. **Key stability** (hypothesis): the canonical graph signature must be
   invariant under node-id renumbering and insertion order — two processes
   that trace the same program land on the same L2 entry — while staying
   sensitive to everything that changes the compiled artifact
   (``Schedule.impl``, sharding, mesh fingerprint, ``force_impl``).
2. **Corruption / version skew**: truncated payloads, flipped bits, and a
   jaxlib upgrade must produce a clean recompile (quarantine-and-recompile,
   never a crash, never a wrong answer) with bitwise identical outputs.
3. **Concurrency / process lifecycle**: racing writers must leave a
   consistent store with one durable winner; a warm process must compile
   zero XLA programs; an entry compiled under an 8-device mesh must MISS
   on a shrunk mesh.
4. **L1/L2 coherence**: ``clear_cache`` (L1) must not purge L2;
   ``invalidate_mesh`` must purge BOTH so a dead mesh's programs cannot
   resurrect from disk.
"""
import functools
import itertools
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import Mesh

from repro.cache import ProgramDiskCache, stable_digest
from repro.core import tapir
from repro.core.tapir import TapirConfig, _cfg_key, clear_cache, use

from test_graph_properties import _random_graph


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _graph_with_offset(seed: int, n_ops: int, offset: int = 0,
                       dead_every: int = 0):
    """Rebuild the same random graph with perturbed node ids: ``offset``
    shifts the whole id space, ``dead_every`` interleaves dead nodes (then
    prunes them) so surviving ids are renumbered AND non-contiguous."""
    rng = np.random.default_rng(seed)
    g, m, k, weights = _random_graph(rng, n_ops)
    g.prune()    # normalize: drop dead chain arms so every variant (the
    #              perturbed ones must prune their interleaved dead nodes)
    #              agrees on the declared-input list
    if offset == 0 and dead_every == 0:
        return g
    g2 = tapir.TaskGraph("prop")
    g2._counter = itertools.count(offset)
    rng2 = np.random.default_rng(seed)
    remap = {}
    order = sorted(g.nodes)
    for i, nid in enumerate(order):
        n = g.nodes[nid]
        if dead_every and i % dead_every == 0 and n.op != "input":
            src = remap[n.inputs[0]]
            g2.add("ew", (src,), g.nodes[n.inputs[0]].ttype,
                   pdims=g.nodes[n.inputs[0]].pdims, fn="relu")
        if n.op == "input":
            remap[nid] = g2.add_input(n.attrs["name"], n.ttype)
        else:
            remap[nid] = g2.add(n.op, tuple(remap[i] for i in n.inputs),
                                n.ttype, pdims=n.pdims, rdims=n.rdims,
                                **n.attrs)
    g2.set_outputs([remap[o] for o in g.outputs])
    g2.prune()
    # rng2 kept only to mirror _random_graph's stream, not used further
    del rng2
    return g2


def _region_program(cache_dir: str, mode: str = "readwrite"):
    """One tiny region program under an L2-backed config; returns (output
    ndarray, cache_stats snapshot)."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32))
    w1 = jnp.asarray(rng.normal(size=(16, 32)).astype(np.float32))
    w2 = jnp.asarray(rng.normal(size=(32, 8)).astype(np.float32))
    cfg = TapirConfig(mode="tapir", program_cache_dir=cache_dir,
                      cache_mode=mode)
    with use(cfg):
        with tapir.region("adv"):
            h = tapir.linear(x, w1, activation="silu")
            out = tapir.linear(h, w2)
        o = np.asarray(out.jax())
    return o, dict(tapir.cache_stats())


def _only_entry(cache_dir: str) -> tuple[str, str]:
    """(bin_path, json_path) of the single committed entry."""
    l2 = ProgramDiskCache(cache_dir, "read")
    entries = l2.entries()
    assert len(entries) == 1, f"expected 1 entry, got {len(entries)}"
    return l2.entry_paths(entries[0][0])


# ---------------------------------------------------------------------------
# 1. key stability (hypothesis)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), n_ops=st.integers(1, 8),
       offset=st.integers(1, 500))
def test_signature_invariant_under_renumbering(seed, n_ops, offset):
    base = _graph_with_offset(seed, n_ops).signature()
    shifted = _graph_with_offset(seed, n_ops, offset=offset).signature()
    assert base == shifted
    assert stable_digest(base) == stable_digest(shifted)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), n_ops=st.integers(2, 8),
       dead_every=st.integers(1, 3))
def test_signature_invariant_under_insertion_order(seed, n_ops, dead_every):
    """Interleaving (then pruning) dead nodes renumbers every surviving
    node and leaves id gaps — the signature must not notice."""
    base = _graph_with_offset(seed, n_ops).signature()
    perturbed = _graph_with_offset(seed, n_ops,
                                   dead_every=dead_every).signature()
    assert base == perturbed


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), n_ops=st.integers(1, 6))
def test_signature_sensitive_to_impl_and_sharding(seed, n_ops):
    g = _graph_with_offset(seed, n_ops)
    base = g.signature()
    nid = g.outputs[0]
    g.nodes[nid].schedule.impl = "pallas_flash"
    assert g.signature() != base, "Schedule.impl must be part of the key"
    g.nodes[nid].schedule.impl = ""
    assert g.signature() == base
    g.nodes[nid].sharding = ("model", None)
    assert g.signature() != base, "sharding must be part of the key"


def test_cfg_key_sensitive_to_mesh_and_force_impl():
    cfg = TapirConfig(mode="tapir")
    base = _cfg_key(cfg, "cpu")
    forced = _cfg_key(TapirConfig(mode="tapir",
                                  force_impl=(("matmul", "opaque"),)), "cpu")
    assert forced != base, "force_impl must be part of the key"
    mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
    with jax.set_mesh(mesh):
        meshed = _cfg_key(cfg, "cpu")
    assert meshed != base, "mesh fingerprint must be part of the key"
    assert meshed[-1] == (("model", 1),)


def test_stable_digest_canonicalization():
    # dict insertion order must not leak into the digest
    assert (stable_digest({"a": 1, "b": 2})
            == stable_digest({"b": 2, "a": 1}))
    assert stable_digest({"a": 1}) != stable_digest({"a": 2})
    # type tags: equal-looking values of different types must differ
    assert stable_digest(1) != stable_digest(1.0)
    assert stable_digest("1") != stable_digest(1)
    assert stable_digest((1, 2)) == stable_digest([1, 2])  # tuple==list: json round-trip safe
    # ndarray: content-addressed
    a = np.arange(6, dtype=np.float32).reshape(2, 3)
    assert stable_digest(a) == stable_digest(a.copy())
    assert stable_digest(a) != stable_digest(a.T)
    # callables digest by qualname + bytecode, not by object identity
    def f(v):
        return v + 1

    def g(v):
        return v + 1
    assert stable_digest(f) == stable_digest(f)
    assert stable_digest(f) != stable_digest(g)  # different qualname


def test_callable_digest_covers_full_code_identity():
    """Regression (review): hashing only ``co_code`` missed constant edits
    — flipping ``x*0.5`` to ``x*0.25`` changes ``co_consts`` but not the
    bytecode, so a stale AOT executable replayed as a false hit.  The
    digest must cover consts, referenced names, defaults, closure cells,
    and nested code objects."""
    # same qualname ("<lambda>"), identical bytecode, different co_consts
    assert (stable_digest(eval("lambda v: v * 0.5"))
            != stable_digest(eval("lambda v: v * 0.25")))
    assert (stable_digest(eval("lambda v: v * 0.5"))
            == stable_digest(eval("lambda v: v * 0.5")))

    # identical code object, different captured closure-cell value
    def make(c):
        def scaled(v):
            return v * c
        return scaled
    assert stable_digest(make(0.5)) != stable_digest(make(0.25))
    assert stable_digest(make(0.5)) == stable_digest(make(0.5))

    # identical bytecode, different referenced global names
    assert (stable_digest(eval("lambda v: np.sin(v)", {"np": np}))
            != stable_digest(eval("lambda v: np.cos(v)", {"np": np})))

    # default argument values live outside co_consts
    assert (stable_digest(eval("lambda v, s=0.5: v * s"))
            != stable_digest(eval("lambda v, s=0.25: v * s")))

    # nested code objects (inline lambda edited)
    assert (stable_digest(eval("lambda v: (lambda u: u + 1)(v)"))
            != stable_digest(eval("lambda v: (lambda u: u + 2)(v)")))

    # functools.partial: bound arguments are part of the program
    base = eval("lambda v, s: v * s")
    assert (stable_digest(functools.partial(base, s=0.5))
            != stable_digest(functools.partial(base, s=0.25)))


def test_opaque_callable_digest_never_crosses_processes():
    """A callable with no introspectable code (C extension, builtin)
    cannot be behavior-fingerprinted, so its digest is salted per process:
    stable inside one process, a guaranteed MISS from any other — never a
    false hit on a changed binary."""
    assert stable_digest(np.tanh) == stable_digest(np.tanh)
    from repro.testing import SRC_DIR
    code = ("import numpy as np\n"
            "from repro.cache import stable_digest\n"
            "print(stable_digest(np.tanh))\n")
    env = dict(os.environ, PYTHONPATH=SRC_DIR)
    out = subprocess.check_output([sys.executable, "-c", code], env=env,
                                  text=True)
    assert out.strip() != stable_digest(np.tanh)


# ---------------------------------------------------------------------------
# 2. corruption / version skew -> quarantine-and-recompile
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("attack", ["truncate", "bitflip", "jaxlib-skew"])
def test_corrupt_entry_recompiles_cleanly(tmp_path, attack):
    d = str(tmp_path / "store")
    clear_cache()
    out_cold, st_cold = _region_program(d)
    assert st_cold["compiled_programs"] == 1 and st_cold["l2_writes"] == 1

    bin_path, json_path = _only_entry(d)
    if attack == "truncate":
        raw = open(bin_path, "rb").read()
        with open(bin_path, "wb") as f:
            f.write(raw[: len(raw) // 2])      # torn write mid-payload
    elif attack == "bitflip":
        raw = bytearray(open(bin_path, "rb").read())
        raw[len(raw) // 3] ^= 0x40             # single flipped bit
        with open(bin_path, "wb") as f:
            f.write(raw)
    else:
        meta = json.load(open(json_path))
        meta["jaxlib"] = "99.99.99"            # runtime upgraded under us
        with open(json_path, "w") as f:
            json.dump(meta, f)

    clear_cache()
    out_warm, st_warm = _region_program(d)
    assert st_warm["l2_quarantined"] >= 1, "bad entry must quarantine"
    assert st_warm["l2_hits"] == 0
    assert st_warm["compiled_programs"] == 1, "must recompile cleanly"
    assert out_warm.tobytes() == out_cold.tobytes(), \
        "recompiled output must be bitwise identical"
    # the bad entry moved aside, the recompile republished a good one
    q = os.path.join(d, "quarantine")
    assert os.path.isdir(q) and len(os.listdir(q)) >= 1
    assert st_warm["l2_writes"] == 1


def test_quarantined_entries_never_probed_again(tmp_path):
    d = str(tmp_path / "store")
    clear_cache()
    _region_program(d)
    bin_path, _ = _only_entry(d)
    with open(bin_path, "wb") as f:
        f.write(b"garbage")
    clear_cache()
    _region_program(d)                          # quarantines + republishes
    q = os.path.join(d, "quarantine")
    before = sorted(os.listdir(q))
    mtimes = {n: os.path.getmtime(os.path.join(q, n)) for n in before}
    clear_cache()
    _, st3 = _region_program(d)                 # must hit the fresh entry
    assert st3["l2_hits"] == 1 and st3["l2_quarantined"] == 0
    assert sorted(os.listdir(q)) == before, "quarantine must be untouched"
    for n in before:
        assert os.path.getmtime(os.path.join(q, n)) == mtimes[n]


def test_read_mode_never_publishes(tmp_path):
    d = str(tmp_path / "store")
    clear_cache()
    _, st1 = _region_program(d, mode="read")
    assert st1["compiled_programs"] == 1 and st1["l2_writes"] == 0
    assert ProgramDiskCache(d, "read").entries() == []


def test_read_mode_never_quarantines_shared_store(tmp_path):
    """Regression (review): a read-mode replica (e.g. version-skewed mid
    rolling-upgrade) used to ``os.replace`` every failing entry into
    ``quarantine/`` — one probe-only instance could evict the fleet's
    entire warm cache.  A read-mode verification failure must report a
    miss and leave the store byte-for-byte untouched."""
    d = str(tmp_path / "store")
    clear_cache()
    out_cold, _ = _region_program(d)            # populate via readwrite
    bin_path, json_path = _only_entry(d)
    raw = bytearray(open(bin_path, "rb").read())
    raw[len(raw) // 2] ^= 0x01
    with open(bin_path, "wb") as f:
        f.write(raw)
    clear_cache()
    out, st = _region_program(d, mode="read")   # corrupt probe, read-only
    assert st["compiled_programs"] == 1 and st["l2_hits"] == 0
    assert st["l2_quarantined"] == 0 and st["l2_writes"] == 0
    assert out.tobytes() == out_cold.tobytes()
    assert os.path.exists(bin_path) and os.path.exists(json_path), \
        "probe-only instance must leave even a corrupt entry in place"
    assert not os.path.isdir(os.path.join(d, "quarantine"))
    # version skew (the rolling-upgrade scenario): same rule
    meta = json.load(open(json_path))
    meta["jaxlib"] = "99.99.99"
    with open(json_path, "w") as f:
        json.dump(meta, f)
    ro = ProgramDiskCache(d, "read")
    digest = ro.entries()[0][0]
    assert ro.get(digest) is None
    assert ro.stats["quarantined"] == 0
    assert os.path.exists(bin_path) and os.path.exists(json_path)


def test_payload_container_is_not_pickle(tmp_path):
    """The on-disk payload container must never unpickle (a crafted entry
    in a shared cache dir would otherwise execute code in every replica
    that probes it): the codec round-trips (blob, in_tree, out_tree)
    through framed JSON, and a pickle bomb fails closed as a decode error
    — quarantined in readwrite, ignored in read mode."""
    import pickle

    from repro.cache.disk import (decode_program_payload,
                                  encode_program_payload)
    in_tree = jax.tree_util.tree_structure(((0, 0, 0), {}))
    out_tree = jax.tree_util.tree_structure({"a": 0, "b": (0, [0, None])})
    raw = encode_program_payload(b"\x00XLA-BLOB\xff", in_tree, out_tree)
    blob, it, ot = decode_program_payload(raw)
    assert blob == b"\x00XLA-BLOB\xff"
    assert it == in_tree and ot == out_tree

    class Boom:
        def __reduce__(self):
            return (os.system, ("false",))

    bomb = pickle.dumps(Boom())
    with pytest.raises(ValueError):
        decode_program_payload(bomb)

    # end-to-end: a pickle payload planted in the store degrades to a
    # clean recompile, never an unpickle
    d = str(tmp_path / "store")
    clear_cache()
    out_cold, _ = _region_program(d)
    bin_path, json_path = _only_entry(d)
    with open(bin_path, "wb") as f:
        f.write(bomb)
    meta = json.load(open(json_path))
    meta["payload_sha256"] = __import__("hashlib").sha256(bomb).hexdigest()
    meta["payload_bytes"] = len(bomb)
    with open(json_path, "w") as f:
        json.dump(meta, f)
    clear_cache()
    out_warm, st = _region_program(d)
    assert st["l2_hits"] == 0 and st["compiled_programs"] == 1
    assert st["l2_quarantined"] >= 1
    assert out_warm.tobytes() == out_cold.tobytes()


# ---------------------------------------------------------------------------
# 3. concurrency + process lifecycle (subprocess)
# ---------------------------------------------------------------------------

_SUBPROC_BODY = """
import numpy as np, jax.numpy as jnp
import repro.core.tapir as tapir
from repro.core.tapir import TapirConfig, use
rng = np.random.default_rng(7)
x = jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32))
w1 = jnp.asarray(rng.normal(size=(16, 32)).astype(np.float32))
w2 = jnp.asarray(rng.normal(size=(32, 8)).astype(np.float32))
cfg = TapirConfig(mode="tapir", program_cache_dir={d!r},
                  cache_mode="readwrite")
with use(cfg):
    with tapir.region("adv"):
        h = tapir.linear(x, w1, activation="silu")
        out = tapir.linear(h, w2)
    o = np.asarray(out.jax())
s = tapir.cache_stats()
print("STATS::" + repr((s["compiled_programs"], s["l2_hits"],
                        s["l2_writes"], float(o.sum()))))
"""


def _spawn(d: str) -> subprocess.Popen:
    from repro.testing import SRC_DIR
    script = _SUBPROC_BODY.format(d=d)
    env = dict(os.environ, PYTHONPATH=SRC_DIR)
    return subprocess.Popen([sys.executable, "-c", script], env=env,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)


def _stats_of(p: subprocess.Popen) -> tuple:
    out, err = p.communicate(timeout=560)
    assert p.returncode == 0, f"stderr:\n{err[-2000:]}"
    for line in out.splitlines():
        if line.startswith("STATS::"):
            return eval(line[len("STATS::"):])  # noqa: S307 - our own output
    raise AssertionError(f"no STATS:: in\n{out[-1000:]}")


def test_concurrent_writers_one_durable_winner(tmp_path):
    """Two processes race to compile + publish the same program.  Both must
    succeed, agree on the answer, and leave exactly one committed entry
    that a third (warm) process can hit."""
    d = str(tmp_path / "store")
    p1, p2 = _spawn(d), _spawn(d)
    (c1, h1, w1, s1), (c2, h2, w2, s2) = _stats_of(p1), _stats_of(p2)
    assert s1 == s2, "racing processes must agree on the answer"
    assert c1 + c2 >= 1          # at least one compiled; maybe both raced
    l2 = ProgramDiskCache(d, "read")
    entries = l2.entries()
    assert len(entries) == 1, "same key => one durable entry"
    assert l2.get(entries[0][0]) is not None, "winner must verify"
    c3, h3, w3, s3 = _stats_of(_spawn(d))
    assert c3 == 0 and h3 == 1 and s3 == s1, "warm process: zero compiles"


def test_warm_process_compiles_zero_programs(tmp_path):
    d = str(tmp_path / "store")
    c1, h1, w1, s1 = _stats_of(_spawn(d))
    assert c1 == 1 and w1 == 1
    c2, h2, w2, s2 = _stats_of(_spawn(d))
    assert c2 == 0, "warm start must compile zero XLA programs"
    assert h2 == 1 and w2 == 0
    assert s2 == s1


def test_mesh_shrink_misses_eight_device_entry(tmp_path):
    """A program compiled under an 8-device mesh must MISS when the mesh
    shrinks to 4 — the fingerprint is part of the key, so the shrunk run
    compiles fresh and publishes its own entry."""
    from repro.testing import run_mesh_subprocess
    d = str(tmp_path / "store")
    body = """
    import repro.dist
    from jax.sharding import Mesh
    import repro.core.tapir as tapir
    from repro.core.tapir import TapirConfig, use
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
    mesh = Mesh(np.array(jax.devices()).reshape(-1), ("model",))
    cfg = TapirConfig(mode="tapir", program_cache_dir={d!r},
                      cache_mode="readwrite")
    with jax.set_mesh(mesh), use(cfg):
        with tapir.region("meshed"):
            out = tapir.linear(x, w)
        out.jax()
    s = tapir.cache_stats()
    result.update(compiled=s["compiled_programs"], l2_hits=s["l2_hits"],
                  l2_writes=s["l2_writes"])
    """.format(d=d)
    r8 = run_mesh_subprocess(body, devices=8)
    assert r8["compiled"] == 1 and r8["l2_writes"] == 1
    r8b = run_mesh_subprocess(body, devices=8)
    assert r8b["compiled"] == 0 and r8b["l2_hits"] == 1, \
        "same mesh shape must hit"
    r4 = run_mesh_subprocess(body, devices=4)
    assert r4["l2_hits"] == 0, "shrunk mesh must not replay 8-device code"
    assert r4["compiled"] == 1 and r4["l2_writes"] == 1
    assert len(ProgramDiskCache(d, "read").entries()) == 2


# ---------------------------------------------------------------------------
# 4. L1/L2 coherence: clear vs invalidate
# ---------------------------------------------------------------------------

def test_clear_cache_is_l1_only(tmp_path):
    d = str(tmp_path / "store")
    clear_cache()
    _region_program(d)
    clear_cache()                # L1 gone...
    assert tapir.cache_stats()["size"] == 0
    l2 = ProgramDiskCache(d, "read")
    assert len(l2.entries()) == 1, "...but L2 must survive clear_cache"
    _, st = _region_program(d)   # and still serve the warm start
    assert st["compiled_programs"] == 0 and st["l2_hits"] == 1


def test_program_cache_clear_empties_store(tmp_path):
    d = str(tmp_path / "store")
    clear_cache()
    cfg = TapirConfig(mode="tapir", program_cache_dir=d,
                      cache_mode="readwrite")
    _region_program(d)
    l2 = tapir.program_cache(cfg)
    assert len(l2.entries()) == 1
    assert l2.clear() == 1
    assert l2.entries() == []
    clear_cache()
    _, st = _region_program(d)
    assert st["compiled_programs"] == 1, "cleared store must recompile"


def test_invalidated_mesh_cannot_resurrect_from_disk(tmp_path):
    """Regression for the L1/L2 coherence hole: ``invalidate_mesh`` used to
    purge only the in-memory caches, so a purged mesh's program would
    silently resurrect from disk in the next process.  It must purge the
    attached L2 stores too."""
    d = str(tmp_path / "store")
    clear_cache()
    mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
    cfg = TapirConfig(mode="tapir", program_cache_dir=d,
                      cache_mode="readwrite")
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(4, 16)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))

    def run():
        with jax.set_mesh(mesh), use(cfg):
            with tapir.region("meshed"):
                out = tapir.linear(x, w)
            out.jax()
        return dict(tapir.cache_stats())

    st1 = run()
    assert st1["l2_writes"] == 1
    fp = (("model", 1),)
    n = tapir.invalidate_mesh(fp)
    assert n >= 2, "must evict from memory AND disk"
    assert tapir.program_cache(cfg).entries() == [], \
        "disk entries for the dead mesh must be gone"
    clear_cache()
    st2 = run()
    assert st2["l2_hits"] == 0, "purged mesh must not resurrect from disk"
    assert st2["compiled_programs"] == 1
    # entries for OTHER meshes survive invalidation
    clear_cache()
    tapir.invalidate_mesh((("model", 64),))
    assert len(tapir.program_cache(cfg).entries()) == 1


def test_pre_bump_pipeline_entry_misses_cleanly(tmp_path, monkeypatch):
    """Regression for the PIPELINE_VERSION bump: an L2 entry persisted by
    the previous pipeline (different lowering semantics for the same graph
    signature) must MISS cleanly — recompile, never replay.  Two layers:
    the salt is part of the key digest (old entries are unreachable, not
    even probed → no quarantine) AND part of the sidecar metadata (a
    forged same-digest entry skew-misses)."""
    import repro.cache
    import repro.cache.disk as disk_mod
    d = str(tmp_path / "store")
    old = "repro-pipeline-8"
    assert repro.cache.PIPELINE_VERSION != old, \
        "bump test assumes the salt moved past pipeline-8"

    # populate the store as the PREVIOUS pipeline would have
    clear_cache()
    monkeypatch.setattr(repro.cache, "PIPELINE_VERSION", old)
    monkeypatch.setattr(disk_mod, "PIPELINE_VERSION", old)
    out_old, st_old = _region_program(d)
    assert st_old["l2_writes"] == 1
    monkeypatch.undo()

    # current pipeline: clean miss + recompile, old entry left in place
    clear_cache()
    out_new, st_new = _region_program(d)
    assert st_new["l2_hits"] == 0, "pre-bump entry must not replay"
    assert st_new["compiled_programs"] == 1
    assert st_new["l2_quarantined"] == 0, \
        "key-level miss: the stale entry is unreachable, not corrupt"
    assert len(ProgramDiskCache(d, "read").entries()) == 2
    assert out_new.tobytes() == out_old.tobytes()

    # metadata layer: a same-digest entry claiming the old pipeline salt
    # (e.g. a hand-copied store) skew-misses instead of replaying
    l2 = ProgramDiskCache(d, "readwrite")
    for digest, _ in l2.entries():
        _, json_path = l2.entry_paths(digest)
        meta = json.load(open(json_path))
        meta["pipeline"] = old
        with open(json_path, "w") as f:
            json.dump(meta, f)
    clear_cache()
    _, st3 = _region_program(d)
    assert st3["l2_hits"] == 0 and st3["compiled_programs"] == 1
    assert st3["l2_quarantined"] >= 1, "metadata skew must quarantine"
