"""Persistent kernel-compile cache: serialized launch artifacts on disk.

CuPBoP ships kernels as cubin/fatbinary files that ``cudaModuleLoad`` maps
into a process without recompiling (Fig. 3's driver-library replacement).
The JAX analogue of a compiled module is a :func:`jax.export` artifact: the
traced+lowered StableHLO for one launch specialization.  This module stores
those artifacts on disk so a *new process* skips the expensive Python
trace+lower of the kernel pipeline and goes straight to XLA.

Layout: one ``<key>.bin`` per launch specialization under the cache
directory.  The key is a sha256 over (cache-format version, jax version,
kernel fingerprint, backend, grid/block ``Dim3``, grain, dyn_shared,
interpret, arg treedef, arg shapes/dtypes) - editing a kernel body, moving
to a new jax, or changing any launch geometry produces a different key, so
stale artifacts are never loaded (they are simply orphaned; ``prune()``
deletes everything).

The directory comes from ``CUPBOP_CACHE_DIR`` (set to ``off``/``0``/empty
to disable) or :func:`repro.core.api.enable_disk_cache`; there is no
default directory so test/CI runs never write outside their sandbox unless
asked to.  Serialization is best-effort: a kernel whose lowering cannot be
exported (or a corrupt/unwritable cache file) degrades to in-memory-only
caching, never to an error.

This tier stores traced programs, not XLA executables.  Those live in
JAX's own persistent compilation cache, which :func:`use_jax_cache` turns
on; entry points (``chip_smoke.py``, ``python -m repro.launch.serve``,
the ``benchmarks/`` scripts) call it, imports never do.
"""
from __future__ import annotations

import hashlib
import os
import tempfile
from typing import Callable

import jax
from jax import export as _jax_export   # submodule: not reachable as jax.export

CACHE_FORMAT_VERSION = 1

#: JAX's compilation cache when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed path, because the path is part of what a later run must find
JAX_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_jax_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX; otherwise the
    cache goes to :data:`JAX_CACHE_DIR`.  Suite kernels compile in well
    under JAX's default one-second persistence threshold, so every compile
    is kept.  Call before the first compile: JAX reads these settings once.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = JAX_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def artifact_key(fingerprint: str, backend: str, grid, block, grain,
                 dyn_shared, interpret, treedef, shapes, *,
                 devices=None, shard_axis: str = "blocks",
                 donate_idx: tuple[int, ...] = ()) -> str:
    """Stable cross-process hash of one launch specialization.

    Includes the lowering platform: ``jax.export`` artifacts are
    platform-specific, so a cache directory shared between e.g. a CPU and
    a TPU machine must not serve either one the other's modules.  The
    process device count (plus the requested ``devices``/``shard_axis``)
    joins the key for the same reason: a multi-device backend's artifact
    bakes in its mesh, so a run under
    ``--xla_force_host_platform_device_count=8`` must not serve a
    single-device process (or vice versa).
    """
    payload = repr((CACHE_FORMAT_VERSION, jax.__version__,
                    jax.default_backend(), jax.device_count(), fingerprint,
                    backend, tuple(grid), tuple(block), grain, dyn_shared,
                    interpret, devices, shard_axis, tuple(donate_idx),
                    str(treedef), shapes))
    return hashlib.sha256(payload.encode()).hexdigest()


class DiskCache:
    """A directory of serialized launch artifacts (best-effort, atomic)."""

    def __init__(self, path: str):
        self.path = os.path.abspath(os.path.expanduser(path))

    def _file(self, key: str) -> str:
        return os.path.join(self.path, f"{key}.bin")

    def load(self, key: str) -> Callable | None:
        """Deserialize the artifact for ``key`` -> callable, or None.

        The returned callable has the same leaves->pytree signature the
        traced function had; wrap it in ``jax.jit`` for dispatch caching.
        """
        try:
            with open(self._file(key), "rb") as f:
                blob = f.read()
            return _jax_export.deserialize(blob).call
        except FileNotFoundError:
            return None
        except Exception:            # corrupt blob / incompatible artifact
            try:
                os.unlink(self._file(key))
            except OSError:
                pass
            return None

    def store(self, key: str, fn: Callable, leaves: tuple) -> bool:
        """Export ``fn`` specialized to ``leaves`` and persist it.

        Returns True on success.  Export re-traces ``fn`` abstractly; any
        failure (non-exportable primitive, read-only dir) is swallowed -
        the in-memory cache still holds the entry.
        """
        try:
            blob = _jax_export.export(jax.jit(fn))(*leaves).serialize()
            os.makedirs(self.path, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=self.path, suffix=".tmp")
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, self._file(key))   # atomic vs concurrent readers
            return True
        except Exception:
            return False

    def prune(self) -> int:
        """Delete every artifact; returns the number removed."""
        n = 0
        try:
            names = os.listdir(self.path)
        except OSError:
            return 0
        for name in names:
            if name.endswith((".bin", ".tmp")):
                try:
                    os.unlink(os.path.join(self.path, name))
                    n += 1
                except OSError:
                    pass
        return n


def from_env() -> "DiskCache | None":
    """Build the process-default DiskCache from ``CUPBOP_CACHE_DIR``."""
    path = os.environ.get("CUPBOP_CACHE_DIR", "")
    if not path or path.lower() in ("off", "0", "none"):
        return None
    return DiskCache(path)
