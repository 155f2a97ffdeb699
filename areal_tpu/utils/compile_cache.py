"""Persistent XLA compilation cache: where it lives, and turning it on.

The serving engine's compiled-program surface is a LADDER — prefill wave
shapes × kv page buckets × decode row buckets × sampling modes — and a
cold engine pays for all of it at warmup; the trainer adds its grad and
update programs. The programs are deterministic functions of (jaxlib,
flags, HLO), so a persistent on-disk cache replays them on every process
after the first.

Where the cache lives is ONE rule, here (``resolve_cache_dir``):

1. ``JAX_COMPILATION_CACHE_DIR``, when the process inherited it. jax reads
   the variable itself; this module then sets no directory in code, so
   whoever runs the program can place the cache from outside.
2. else the directory a config names (``JaxGenConfig.compilation_cache_dir``
   / the server's ``--compilation-cache-dir``);
3. else ``<checkout>/.jax_compile_cache`` for the entry points that always
   cache (``examples/gsm8k_grpo.py``, ``chip_smoke.py``).

The directory is part of the cache key's world: it is never a temporary
name, a pid or a time. The local launcher exports the config's value to
server subprocesses only when nothing was inherited.
"""

import os
import threading
from typing import Optional

from areal_tpu.utils import logging as logging_util

logger = logging_util.getLogger("CompileCache")

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

_lock = threading.Lock()
_enabled_dir: Optional[str] = None


def default_cache_dir() -> str:
    """``<checkout>/.jax_compile_cache`` (git ignores it)."""
    checkout = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(checkout, ".jax_compile_cache")


def resolve_cache_dir(configured: str = "") -> str:
    """The one placement rule (module docstring): inherited environment,
    else the configured directory, else the checkout's default."""
    return os.environ.get(CACHE_DIR_ENV) or configured or default_cache_dir()


def enable_compilation_cache(cache_dir: str) -> bool:
    """Turn jax's persistent compilation cache on.

    With ``JAX_COMPILATION_CACHE_DIR`` inherited, that directory is used
    and no directory is set in code; otherwise jax is pointed at
    ``cache_dir`` (empty = nothing asked for: returns False, cache stays
    off). Thresholds are dropped to zero either way: the decode bucket
    ladder is many SMALL programs (default jax only persists compiles
    > 1 s), and the warmup cost is their sum, not any single entry.

    Returns True once the cache is active. A cache that was asked for and
    cannot be enabled (unwritable directory, a variable exported after jax
    was imported) RAISES: a run that silently recompiles everything is not
    the run that was asked for. Idempotent per directory."""
    global _enabled_dir
    inherited = os.environ.get(CACHE_DIR_ENV, "")
    if not (inherited or cache_dir):
        return False
    target = os.path.abspath(os.path.expanduser(inherited or cache_dir))
    with _lock:
        if _enabled_dir == target:
            return True
        os.makedirs(target, exist_ok=True)
        import jax
        from jax._src import compilation_cache as _cc

        if inherited:
            seen = jax.config.jax_compilation_cache_dir or ""
            if os.path.abspath(os.path.expanduser(seen)) != target:
                raise RuntimeError(
                    f"{CACHE_DIR_ENV}={inherited!r} but jax holds "
                    f"{seen!r}: the variable must be in the environment "
                    "before jax is imported"
                )
        else:
            jax.config.update("jax_compilation_cache_dir", target)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # jax initializes its cache object ONCE, at the first compile — a
        # process that compiled anything before this call (param init,
        # another engine) would silently keep the cache off forever.
        # reset_cache() forces re-initialization against the directory
        # just configured.
        _cc.reset_cache()
        _enabled_dir = target
        logger.info(f"persistent compilation cache at {target}")
        return True


def enabled_dir() -> Optional[str]:
    """The directory the cache is currently pointed at (None = off)."""
    return _enabled_dir


def disable_compilation_cache() -> None:
    """Turn the persistent cache back off (tests; a process that
    enabled it for one engine must be able to restore the default).
    The enable is process-global jax config — on the CPU backend some
    TRAINER-side programs (donation-heavy sharded train steps) have been
    observed to misbehave with the cache enabled, so test suites that
    exercise both planes in one process must scope the enable to the
    serving tests."""
    global _enabled_dir
    with _lock:
        import jax
        from jax._src import compilation_cache as _cc

        jax.config.update("jax_compilation_cache_dir", None)
        _cc.reset_cache()
        _enabled_dir = None


def pack_seed(cache_dir: str, artifact_path: str) -> int:
    """Pack a warmed compilation-cache directory into one seed artifact
    (gzip tarball) a launcher ships to spawned servers. Returns the
    number of cache entries packed. The artifact is written atomically
    (tmp + rename) so a concurrent reader never sees a torn tar."""
    import tarfile

    cache_dir = os.path.abspath(os.path.expanduser(cache_dir))
    entries = sorted(
        f
        for f in os.listdir(cache_dir)
        if os.path.isfile(os.path.join(cache_dir, f))
    )
    tmp = artifact_path + ".tmp"
    with tarfile.open(tmp, "w:gz") as tar:
        for f in entries:
            tar.add(os.path.join(cache_dir, f), arcname=f)
    os.replace(tmp, artifact_path)
    logger.info(
        f"packed {len(entries)} cache entries → {artifact_path}"
    )
    return len(entries)


def ensure_seeded(cache_dir: str, artifact_path: str) -> int:
    """Unpack a seed artifact into ``cache_dir`` (skipping entries that
    already exist — a live cache is never clobbered). Returns entries
    extracted; missing/corrupt artifacts degrade to 0 with a warning
    (the seed is an optimization, never a launch dependency)."""
    import tarfile

    cache_dir = os.path.abspath(os.path.expanduser(cache_dir))
    os.makedirs(cache_dir, exist_ok=True)
    try:
        n = 0
        with tarfile.open(artifact_path, "r:gz") as tar:
            for member in tar.getmembers():
                # flat cache layout only — refuse path traversal
                name = os.path.basename(member.name)
                if not member.isfile() or not name:
                    continue
                dest = os.path.join(cache_dir, name)
                if os.path.exists(dest):
                    continue
                src = tar.extractfile(member)
                if src is None:
                    continue
                tmp = dest + ".seedtmp"
                with open(tmp, "wb") as out:
                    out.write(src.read())
                os.replace(tmp, dest)
                n += 1
        logger.info(
            f"seeded compile cache {cache_dir} with {n} entries from "
            f"{artifact_path}"
        )
        return n
    except (OSError, tarfile.TarError) as e:
        logger.warning(f"seed artifact {artifact_path} unusable: {e}")
        return 0
