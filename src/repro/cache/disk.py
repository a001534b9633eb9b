"""On-disk L2 tier of the compiled-program cache.

Layout (one pair of files per program, content-addressed by key digest)::

    <root>/v1/<dd>/<digest>.bin     # framed (blob, in_tree, out_tree)
    <root>/v1/<dd>/<digest>.json    # sidecar: provenance + integrity
    <root>/quarantine/              # entries that failed verification

``<dd>`` is the first two hex chars of the digest (fan-out so a fleet-sized
cache never puts 10k files in one directory).

Write protocol (same discipline as ``checkpoint/ckpt.py``: stage + atomic
rename, readers never observe a torn entry):

1. payload staged to ``<digest>.bin.tmp-<pid>-<nonce>`` then
   ``os.replace``d to final — rename is atomic on POSIX, so two replicas
   racing to publish the same key both succeed and the last rename wins;
   both wrote byte-identical content (same key => same program), so there
   is exactly one durable winner and no torn state.
2. sidecar staged + renamed AFTER the payload.  A reader requires the
   sidecar, so a visible sidecar implies a visible payload.

Read protocol (**quarantine-and-recompile**: a cache problem may cost a
compile, never correctness):

* sidecar missing                       -> miss (in-progress write)
* sidecar unparsable                    -> quarantine, miss
* format / jax / jaxlib / pipeline-salt
  mismatch                              -> version skew: quarantine, miss
* payload missing, short, or sha256
  mismatch vs the sidecar               -> corruption: quarantine, miss
* payload decode fails                  -> corruption: quarantine, miss

A failed verification is retried ONCE before quarantining: payload and
sidecar are replaced independently, so a reader racing two same-key
writers can observe writer A's payload next to writer B's sidecar — the
pair has settled by the re-read, which separates that transient torn
*observation* from durable corruption.  Quarantine itself only runs in
``readwrite`` mode: a ``read``-mode instance (probe-only replica over a
fleet-shared store) reports a miss without ever mutating the store, so
one version-skewed replica cannot evict the warm cache for everyone.
Quarantined entries are RENAMED into ``quarantine/`` (never deleted — a
fleet operator can post-mortem them) and are never probed again: ``get``
only looks under ``v1/``.

Trust model: the payload container is a framed JSON + raw-bytes encoding
— NO pickle, so a crafted ``.bin`` cannot execute code at decode time.
The XLA blob inside it is still handed to the runtime's native executable
deserializer, and the sha256 sidecar is an *integrity* check (bit rot,
torn writes), not *authentication* — so ``program_cache_dir`` must only
be writable by principals you would let publish code into the process.
Directories this module creates are made mode 0o700; a shared fleet
cache that intentionally widens access (e.g. group-writable) is the
operator's trust decision to make.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import os
import shutil
import threading
import uuid
from typing import Any, Optional

#: 2: payload container moved from pickle to the framed no-pickle
#: encoding (``encode_program_payload``) — v1 entries skew-miss.
FORMAT_VERSION = 2

#: Pipeline semantics salt.  Part of every L2 key: any PR that changes what
#: the pass pipeline / lowering emits for the same graph signature MUST
#: bump this, or old entries would replay stale programs.  (The jax/jaxlib
#: versions are keyed separately — this covers *our* compiler.)
#: 9: pyfunc nodes lower through a jit boundary (transpose-unit association
#: for gradients) and the autodiff/gradient-program machinery landed —
#: programs emitted by pipeline-8 for the same signature are stale.
#: 10: kernel impls lower through their custom-VJP wrappers with the
#: interpret mode taken from the program's backend, no kernel binds under
#: a mesh, and the gated MLP's hidden carries a replication constraint.
#: 11: each region program's module is named for its region
#: (``jit_tapir_<region>``); an older entry would put the old name back
#: into a device trace.
#: 12: paged decode attention is a library op (``paged_attention``) whose
#: impl the registry binds: the Pallas kernel reading pages in place, or
#: the gathered-view composite that was a ``pyfunc`` node before.
#: 13: the one-position SSM state update is a library op
#: (``ssm_state_step``, two output nodes lowered once) whose impl the
#: registry binds: the in-place Pallas kernel or the jnp composite.
#: 14: a matmul bound to the fused kernel takes its row tile from the rows
#: the kernel sees (every leading dim of the activation), so a decode GEMM
#: runs as one row block: the same signature lowers to another program.
PIPELINE_VERSION = "repro-pipeline-14"


def _versions() -> dict:
    import jax
    import jaxlib
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "pipeline": PIPELINE_VERSION, "format": FORMAT_VERSION}


#: jax's persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: a fixed directory at the checkout root (gitignored).  Fixed, not
#: temporary, because the path is part of what a later process must find.
DEFAULT_XLA_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

_XLA_CACHE_ENABLED = False


def xla_cache_dir() -> str:
    """Where jax's persistent compilation cache lives: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``DEFAULT_XLA_CACHE_DIR``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_XLA_CACHE_DIR


def enable_xla_disk_cache() -> None:
    """Turn on jax's own persistent compilation cache at ``xla_cache_dir()``.

    The L2 store covers region programs (the big AOT executables), but a
    cold process also pays dozens of small XLA compiles our tier never
    sees: eager primitive dispatches (zeros-init, indexing, argmax) and
    outer-jit wrappers whose inputs are tracers.  jax persists those keyed
    on its own HLO fingerprint + jaxlib version.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and nothing
    is set here."""
    global _XLA_CACHE_ENABLED
    if _XLA_CACHE_ENABLED or os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    from jax._src import compilation_cache
    jax.config.update("jax_compilation_cache_dir", DEFAULT_XLA_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # the cache-used probe is sticky: once any compile ran (backend init,
    # param setup) the "no cache dir" verdict is latched — reset so the
    # next compile re-reads the config and opens the directory
    compilation_cache.reset_cache()
    _XLA_CACHE_ENABLED = True


#: ``jax_enable_compilation_cache`` is process-global state: the suspend
#: window below flips it off and back on, so every compile that uses the
#: guard must be serialized through this lock or a concurrent region
#: compile could land inside another thread's window and be served from
#: the XLA cache — the exact poisoning the guard exists to prevent.
_XLA_SUSPEND_LOCK = threading.RLock()


@contextlib.contextmanager
def suspend_xla_disk_cache():
    """Run a compile OUTSIDE jax's persistent compilation cache.

    Region programs are AOT-compiled and published to the L2 program
    store, so letting jax's own cache also serve that compile is not just
    redundant — it poisons L2: an executable *loaded from* the XLA cache
    re-``serialize``s on CPU to a blob whose jitted fusion symbols are
    gone ("Symbols not found: [ divide_multiply_fusion ]" at the next
    ``deserialize_and_load``).  The cache-used verdict is latched, so
    disabling means flipping the flag AND resetting the latch on both
    edges; the on-disk entries are untouched, only the verdict re-reads
    the config.

    Holds ``_XLA_SUSPEND_LOCK`` for the whole window: concurrent region
    AOT compiles serialize instead of racing the global flag.  Compiles
    issued by other threads that do NOT take this guard can still observe
    the flag down mid-window (jax config is process-global); within repro
    every region compile funnels through here, and the publish-time
    load-back check in ``_l2_publish`` backstops anything that slips."""
    import jax
    from jax._src import compilation_cache
    with _XLA_SUSPEND_LOCK:
        active = (jax.config.jax_compilation_cache_dir
                  and jax.config.jax_enable_compilation_cache)
        if not active:
            yield
            return
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()


def atomic_write_bytes(path: str, data: bytes) -> None:
    """Stage-and-rename write: concurrent readers see the old file or the
    new file, never a prefix."""
    tmp = f"{path}.tmp-{os.getpid()}-{uuid.uuid4().hex[:8]}"
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def _json_default(o: Any):
    """numpy scalars/arrays serialize as NUMBERS, not their str() — a
    checkpoint meta carrying an np.int64 must round-trip as an int, or
    restore reads a string where the scheduler expects a count."""
    import numpy as np
    if isinstance(o, np.integer):
        return int(o)
    if isinstance(o, np.floating):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    return str(o)


def atomic_write_json(path: str, obj: Any) -> None:
    atomic_write_bytes(path, json.dumps(obj, indent=1, sort_keys=True,
                                        default=_json_default).encode())


def _makedirs_private(path: str) -> None:
    """``mkdir -p`` that chmods every component THIS process creates to
    0o700 (chmod, not mode=, so the umask can't widen it).  Pre-existing
    directories are left alone — a deliberately group-shared fleet cache
    is the operator's trust decision (see module docstring)."""
    created = []
    p = os.path.abspath(path)
    while p and not os.path.isdir(p):
        created.append(p)
        parent = os.path.dirname(p)
        if parent == p:
            break
        p = parent
    os.makedirs(path, exist_ok=True)
    for q in created:
        try:
            os.chmod(q, 0o700)
        except OSError:
            pass


# -- payload container codec (deliberately NOT pickle: see trust model) -----
#
# A program payload is ``(blob, in_tree, out_tree)``: an opaque bytes blob
# from ``jax.experimental.serialize_executable.serialize`` plus two
# PyTreeDefs.  The treedefs of region programs are built from standard
# containers only (the positional-jit calling convention is
# ``((arg0..argN), {})``; outputs are tuples/lists/dicts of arrays), so
# they round-trip through a tagged JSON skeleton — no arbitrary object
# construction on decode.  Frame::
#
#     b"RPC2" | u32 header length | header JSON | raw blob
#
# ``encode`` raises ValueError on a treedef containing non-standard nodes
# (publish is skipped — degrade to uncached, never to pickle); ``decode``
# raises ValueError on any malformed frame (caller quarantines).

_PAYLOAD_MAGIC = b"RPC2"


def _skeleton_to_obj(x: Any, leaf: Any) -> Any:
    if x is leaf:
        return {"t": "leaf"}
    if x is None:
        return {"t": "none"}
    if isinstance(x, (tuple, list)):
        tag = "tuple" if isinstance(x, tuple) else "list"
        return {"t": tag, "v": [_skeleton_to_obj(v, leaf) for v in x]}
    if isinstance(x, dict):
        items = []
        for k in sorted(x, key=repr):
            if not isinstance(k, (str, int, bool)) or isinstance(k, bool):
                raise ValueError(f"unsupported treedef dict key {k!r}")
            items.append([k, _skeleton_to_obj(x[k], leaf)])
        return {"t": "dict", "v": items}
    raise ValueError(f"unsupported treedef node {type(x).__name__}")


def _obj_to_skeleton(o: Any, leaf: Any) -> Any:
    tag = o.get("t") if isinstance(o, dict) else None
    if tag == "leaf":
        return leaf
    if tag == "none":
        return None
    if tag in ("tuple", "list"):
        seq = [_obj_to_skeleton(v, leaf) for v in o["v"]]
        return tuple(seq) if tag == "tuple" else seq
    if tag == "dict":
        out = {}
        for k, v in o["v"]:
            if not isinstance(k, (str, int)) or isinstance(k, bool):
                raise ValueError(f"unsupported treedef dict key {k!r}")
            out[k] = _obj_to_skeleton(v, leaf)
        return out
    raise ValueError(f"unsupported treedef node tag {tag!r}")


def encode_program_payload(blob: bytes, in_tree, out_tree) -> bytes:
    import jax
    leaf = object()

    def tree_obj(td):
        skel = jax.tree_util.tree_unflatten(td, [leaf] * td.num_leaves)
        return _skeleton_to_obj(skel, leaf)

    header = json.dumps({"in_tree": tree_obj(in_tree),
                         "out_tree": tree_obj(out_tree)},
                        sort_keys=True).encode()
    return (_PAYLOAD_MAGIC + len(header).to_bytes(4, "big")
            + header + bytes(blob))


def decode_program_payload(raw: bytes):
    import jax
    if raw[:4] != _PAYLOAD_MAGIC:
        raise ValueError("bad payload magic")
    n = int.from_bytes(raw[4:8], "big")
    if len(raw) < 8 + n:
        raise ValueError("truncated payload header")
    header = json.loads(raw[8:8 + n].decode())
    leaf = object()

    def tree_def(o):
        return jax.tree_util.tree_structure(
            _obj_to_skeleton(o, leaf), is_leaf=lambda x: x is leaf)

    return (raw[8 + n:], tree_def(header["in_tree"]),
            tree_def(header["out_tree"]))


class ProgramDiskCache:
    """Content-addressed store for serialized AOT executables.

    ``mode``: ``"off"`` (every call a no-op), ``"read"`` (probe but never
    publish NOR quarantine — the store is immutable to this instance),
    ``"readwrite"``.  In readwrite mode verification failures increment
    ``stats["quarantined"]`` and move the entry aside; ``get`` then reports
    a miss so the caller recompiles.
    """

    def __init__(self, root: str, mode: str = "readwrite"):
        if mode not in ("off", "read", "readwrite"):
            raise ValueError(f"cache_mode must be off|read|readwrite, "
                             f"got {mode!r}")
        self.root = root
        self.mode = mode
        self.stats = {"hits": 0, "misses": 0, "quarantined": 0, "writes": 0}

    # -- paths ------------------------------------------------------------
    @property
    def store_dir(self) -> str:
        return os.path.join(self.root, "v1")

    @property
    def quarantine_dir(self) -> str:
        return os.path.join(self.root, "quarantine")

    def entry_paths(self, digest: str) -> tuple[str, str]:
        d = os.path.join(self.store_dir, digest[:2])
        return (os.path.join(d, f"{digest}.bin"),
                os.path.join(d, f"{digest}.json"))

    # -- quarantine -------------------------------------------------------
    def quarantine(self, digest: str, reason: str) -> None:
        """Move a bad entry aside (never deleted, never re-read).

        No-op outside ``readwrite``: a probe-only (``read``) instance must
        never mutate the shared store — one version-skewed read replica
        (e.g. older jaxlib mid rolling-upgrade) would otherwise quarantine
        every entry it probes and evict the fleet's warm cache."""
        if self.mode != "readwrite":
            return
        _makedirs_private(self.quarantine_dir)
        nonce = uuid.uuid4().hex[:8]
        for path in self.entry_paths(digest):
            if os.path.exists(path):
                dst = os.path.join(
                    self.quarantine_dir,
                    f"{os.path.basename(path)}.{reason}.{nonce}")
                try:
                    os.replace(path, dst)
                except OSError:
                    pass
        self.stats["quarantined"] += 1

    # -- read -------------------------------------------------------------
    def _read_verified(self, digest: str):
        """One verification attempt: ``((payload, meta), None)`` on success
        or ``(None, reason)`` — reason ``"absent"`` is a plain miss, any
        other reason is a verification failure."""
        bin_path, json_path = self.entry_paths(digest)
        if not os.path.exists(json_path):
            return None, "absent"
        try:
            with open(json_path, "rb") as f:
                meta = json.loads(f.read().decode())
        except (OSError, ValueError, UnicodeDecodeError):
            return None, "sidecar-unreadable"
        want = _versions()
        got = {k: meta.get(k) for k in want}
        if got != want or meta.get("key_digest") != digest:
            return None, "version-skew"
        try:
            with open(bin_path, "rb") as f:
                raw = f.read()
        except OSError:
            return None, "payload-missing"
        if (len(raw) != meta.get("payload_bytes")
                or hashlib.sha256(raw).hexdigest()
                != meta.get("payload_sha256")):
            return None, "payload-corrupt"
        try:
            payload = decode_program_payload(raw)
        except Exception:
            return None, "payload-decode-failed"
        return (payload, meta), None

    def get(self, digest: str) -> Optional[tuple[Any, dict]]:
        """Verified read: ``((blob, in_tree, out_tree), sidecar meta)`` or
        None.  Any integrity or version failure is retried once (racing
        same-key writers replace payload and sidecar independently, so a
        reader can transiently observe writer A's payload next to writer
        B's sidecar — settled by the re-read), then quarantines the entry
        (readwrite mode only) and returns None: the caller's fallback is a
        clean recompile, which in readwrite mode republishes and heals the
        slot."""
        if self.mode == "off":
            return None
        got, reason = self._read_verified(digest)
        if got is None and reason != "absent":
            got, reason = self._read_verified(digest)
        if got is not None:
            self.stats["hits"] += 1
            return got
        if reason != "absent":
            self.quarantine(digest, reason)
        self.stats["misses"] += 1
        return None

    # -- write ------------------------------------------------------------
    def put(self, digest: str, payload_obj: tuple,
            meta: Optional[dict] = None) -> bool:
        """Transactional publish of a ``(blob, in_tree, out_tree)`` program
        payload; returns False in read/off modes, and False (publish
        skipped, process serves uncached) if the treedefs contain
        non-standard pytree nodes the safe codec refuses."""
        if self.mode != "readwrite":
            return False
        try:
            raw = encode_program_payload(*payload_obj)
        except Exception:
            return False
        bin_path, json_path = self.entry_paths(digest)
        _makedirs_private(os.path.dirname(bin_path))
        sidecar = dict(meta or {})
        sidecar.update(_versions(), key_digest=digest,
                       payload_sha256=hashlib.sha256(raw).hexdigest(),
                       payload_bytes=len(raw))
        atomic_write_bytes(bin_path, raw)        # payload first,
        atomic_write_json(json_path, sidecar)    # sidecar commits the entry
        self.stats["writes"] += 1
        return True

    # -- maintenance ------------------------------------------------------
    def entries(self) -> list[tuple[str, dict]]:
        """(digest, sidecar meta) for every committed entry."""
        out = []
        if not os.path.isdir(self.store_dir):
            return out
        for dd in sorted(os.listdir(self.store_dir)):
            d = os.path.join(self.store_dir, dd)
            if not os.path.isdir(d):
                continue
            for name in sorted(os.listdir(d)):
                if not name.endswith(".json"):
                    continue
                try:
                    with open(os.path.join(d, name)) as f:
                        meta = json.load(f)
                except (OSError, ValueError):
                    continue
                out.append((name[:-len(".json")], meta))
        return out

    def invalidate(self, fingerprint: tuple) -> int:
        """Purge every entry compiled under mesh ``fingerprint`` (recorded
        in the sidecar).  A purged fingerprint cannot be resurrected: both
        files are removed, not quarantined — this is an intentional
        invalidation, not a fault."""
        fp = [list(p) for p in fingerprint]     # JSON round-trip form
        n = 0
        for digest, meta in self.entries():
            if meta.get("mesh_fingerprint") == fp:
                for path in self.entry_paths(digest):
                    try:
                        os.remove(path)
                    except OSError:
                        pass
                n += 1
        return n

    def clear(self) -> int:
        """Drop every committed entry (quarantine is kept for post-mortem).
        Returns the number of entries removed."""
        n = len(self.entries())
        shutil.rmtree(self.store_dir, ignore_errors=True)
        return n
