"""Shared persistent-compile-cache convention + AOT program store.

ONE home for the cache path and thresholds: train.py, python -m
tpunet.serve, bench.py, the scripts, tests/conftest.py and
tests/_mp_worker.py all call this, and nothing else in the repo
assigns ``jax_compilation_cache_dir`` — the directory is part of the
cache key, so a cache that moves never hits. The home is
``JAX_COMPILATION_CACHE_DIR`` where the operator set it, else
``<checkout>/.jax_cache`` (git-ignored): a fixed path next to the
code that compiled into it, never one built from the temp directory,
the user name, a pid or the time.

The AOT store (``AotProgramStore``) is the stronger form the serving
tier needs: the persistent compilation cache still pays tracing +
lowering + a cache probe per program at every boot, but a replica's
program set is CLOSED (one decode step + one program per prefill
bucket), so the whole ``jax.jit(...).lower().compile()`` result can be
serialized once (``jax.experimental.serialize_executable``) and
deserialized at boot — no tracing, no lowering, no XLA invocation.
That is what turns replica cold-start from compile-bound minutes into
seconds and makes the router tier's scale-up decisions actionable
(docs/serving.md "AOT warm-start"). Entries are keyed by a caller-
supplied config digest + program shape + jax version + backend, so a
changed model config or runtime can never load a stale executable.
"""

from __future__ import annotations

import contextlib
import hashlib
import os
import pickle

from tpunet.utils import fsatomic

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir() -> str:
    """The compile-cache directory: JAX's own env var where set, else
    ``<checkout>/.jax_cache`` — also what subprocess launchers export
    as JAX_COMPILATION_CACHE_DIR."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def enable_persistent_compile_cache() -> None:
    """Point JAX's compiled-program cache at ``cache_dir()``.

    Thresholds are lowered so every Trainer program is cached, not
    just multi-second compiles. Call AFTER jax is importable, BEFORE
    the first compile.
    """
    import jax

    jax.config.update("jax_compilation_cache_dir", cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _count_compiles()


# What this process compiled, from jax's own monitoring events: every
# backend compile request (a persistent-cache hit included — then the
# seconds are the retrieval) and every persistent-cache hit.
_COMPILES = {"programs": 0, "seconds": 0.0, "cache_hits": 0}
_counting = False


def _count_compiles() -> None:
    global _counting
    if _counting:
        return
    _counting = True
    import jax

    def on_duration(event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILES["programs"] += 1
            _COMPILES["seconds"] += secs

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            _COMPILES["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def compile_stats_line() -> str:
    """One line for an entry point's log: programs compiled since
    ``enable_persistent_compile_cache()``, their seconds, and how many
    were served from the persistent cache."""
    return (f"Compile: {_COMPILES['programs']} programs, "
            f"{_COMPILES['seconds']:.1f}s, "
            f"{_COMPILES['cache_hits']} from cache ({cache_dir()})")


def reset_compilation_cache_latch() -> None:
    """Drop jax's once-per-process cache-usage latch.

    ``compile_or_get_cached`` gates on ``is_cache_used()``, which
    checks ``jax_enable_compilation_cache`` ONCE and latches the
    answer for the life of the process — after any compile has run
    with the cache enabled, flipping the flag off is silently ignored
    for both reads and writes. ``reset_cache()`` clears the latch (and
    the lazily-held cache handle) so the next compile re-evaluates the
    flag."""
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


@contextlib.contextmanager
def serializable_compile():
    """Compile with the persistent compilation cache OFF.

    An executable whose compile was SERVED from XLA's persistent cache
    serializes without error into a blob that fails
    ``deserialize_and_load`` at the next boot ("Symbols not found:
    [..._fusion ...]"), silently poisoning the AOT store. Wrap the
    ``.lower().compile()`` of any program destined for ``save`` in
    this so the executable is built fresh and self-contained; the
    cache setting is restored on exit.

    The flag flip alone is NOT enough: jax latches is-the-cache-used
    at the process's first compile, so a boot that compiled anything
    before this point would keep reading (and writing) the cache with
    the flag down — the latch is reset on entry and again on exit so
    both sides see their own flag honestly.
    """
    import jax

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    reset_compilation_cache_latch()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        reset_compilation_cache_latch()


class AotProgramStore:
    """Serialize/deserialize fully-compiled jax executables on disk.

    One store = one directory of ``<key>.aotx`` files, each a pickled
    ``(serialized_executable, in_tree, out_tree)`` triple from
    ``jax.experimental.serialize_executable.serialize``. The key folds
    in the caller's config digest (model architecture + pool shape),
    the program name and shape tag, the jax version, and the kind and
    number of the devices the program EXECUTES on — any mismatch is a
    clean MISS, never a wrong program.

    ``devices`` are those execution devices (default: the process's
    first local device, where an un-meshed engine's programs run).
    They are handed to ``deserialize_and_load``, which otherwise loads
    for EVERY local device: a one-device program in an 8-device (CPU
    tests) or 4-chip process would then refuse its first call.

    ``load`` returns the loaded executable or None; ``save`` is
    best-effort (a read-only disk degrades to the persistent
    compilation cache, not to a crash). Both are torn-write-safe
    (tmp + rename) like every other artifact writer in the repo.
    """

    SUFFIX = ".aotx"

    def __init__(self, directory: str, config_digest: str,
                 devices=None):
        self.directory = directory
        self.config_digest = config_digest
        if devices is None:
            import jax
            devices = jax.local_devices()[:1]
        self.devices = list(devices)

    def _deserialize(self, blob, in_tree, out_tree):
        from jax.experimental import serialize_executable
        return serialize_executable.deserialize_and_load(
            blob, in_tree, out_tree, execution_devices=self.devices)

    @staticmethod
    def digest(parts: object) -> str:
        """Stable 16-hex digest of a JSON-able description (the model/
        pool config fields that select a program)."""
        import json
        blob = json.dumps(parts, sort_keys=True, default=str)
        return hashlib.sha256(blob.encode()).hexdigest()[:16]

    def _path(self, name: str, shape_tag: str) -> str:
        import jax
        runtime = self.digest({
            "jax": jax.__version__,
            "device_kind": self.devices[0].device_kind,
            "n_devices": len(self.devices),
        })
        key = f"{name}-{shape_tag}-{self.config_digest}-{runtime}"
        return os.path.join(self.directory, key + self.SUFFIX)

    def load(self, name: str, shape_tag: str):
        """The deserialized executable, or None on miss/corruption
        (a corrupt entry is removed so the next save rewrites it)."""
        path = self._path(name, shape_tag)
        if not os.path.exists(path):
            return None
        try:
            with open(path, "rb") as f:
                blob, in_tree, out_tree = pickle.load(f)
            return self._deserialize(blob, in_tree, out_tree)
        except Exception:  # noqa: BLE001 — a stale/corrupt entry must
            # degrade to a recompile, never kill the boot.
            try:
                os.unlink(path)
            except OSError:
                pass
            return None

    def save(self, name: str, shape_tag: str, compiled) -> bool:
        """Serialize one compiled executable; best-effort (False on
        any failure — the persistent compilation cache still covers
        the next boot).

        Shared-filesystem safe: a multi-host fleet pointing N
        replicas at ONE ``--aot-cache`` dir all computes the same
        entry key, so the commit is deduplicated — the payload is
        staged under its CONTENT digest (two hosts serializing
        concurrently never collide on the tmp name) and committed
        under an ``flock``-guarded check: whichever host wins writes
        once, every later writer sees the committed entry and returns
        without touching the file. Still torn-write-safe (tmp +
        rename) like every other artifact writer in the repo."""
        from jax.experimental import serialize_executable
        try:
            blob, in_tree, out_tree = serialize_executable.serialize(
                compiled)
            # Prove the roundtrip NOW: a cache-served executable (see
            # serializable_compile) serializes without error into a
            # blob that cannot be loaded back — a boot must never
            # trust an entry that was not load-verified at save time.
            self._deserialize(blob, in_tree, out_tree)
            payload = pickle.dumps((blob, in_tree, out_tree))
            # First-writer-wins dedup + content-digest staging lives in
            # fsatomic — the prefix KV spill store shares the identical
            # commit discipline.
            return fsatomic.publish_bytes(
                self._path(name, shape_tag), payload)
        except Exception:  # noqa: BLE001
            return False
