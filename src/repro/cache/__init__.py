"""Two-tier compiled-program cache.

L1 is ``core.tapir``'s in-memory ``_CACHE``/``_PROGRAMS`` (dies with the
process); this package provides the content-addressed on-disk L2 tier
(``ProgramDiskCache``) plus the cross-process key digest
(``stable_digest``) and the pipeline-semantics salt (``PIPELINE_VERSION``)
every L2 key includes.  Wiring lives in ``core.tapir._compile``: L1 miss
-> L2 probe -> compile + publish.
"""
from .digest import stable_digest
from .disk import (FORMAT_VERSION, PIPELINE_VERSION, ProgramDiskCache,
                   atomic_write_bytes, atomic_write_json,
                   enable_xla_disk_cache, suspend_xla_disk_cache,
                   xla_cache_dir)

__all__ = [
    "FORMAT_VERSION", "PIPELINE_VERSION", "ProgramDiskCache",
    "atomic_write_bytes", "atomic_write_json", "enable_xla_disk_cache",
    "stable_digest", "suspend_xla_disk_cache", "xla_cache_dir",
]
