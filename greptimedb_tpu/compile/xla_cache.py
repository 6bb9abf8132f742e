"""Where JAX's own persistent compilation cache lives.

One rule for every entry point that starts a serving or measuring
process (cli standalone/datanode, chip_smoke.py, benchmark/run.py):
``JAX_COMPILATION_CACHE_DIR`` wins when the environment sets it — JAX
reads it itself and nothing is set in code — otherwise the cache is
``<checkout>/.jax_cache``.  The directory is part of the cache key, so
it is a fixed path: never under a data home, a temp name or a machine
tag.  This is XLA's cache of every jitted program; the AOT
``ArtifactStore`` under the data home (store.py) is a different thing.

On the CPU backend the cache stays off unless the environment asks for
it.  With jaxlib 0.9.0 an XLA:CPU executable that was read back from
this cache does not survive ``serialize_executable``: the AOT store
re-serializes it, and the first call after the store's next load fails
with ``Function bitcast_dot_fusion.1 not found`` (seen at TSBS size, a
fresh data home over a warm cache).  CPU compiles take well under a
second each, so nothing is lost there.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
_counts = dict.fromkeys(_EVENTS.values(), 0)
_dir: str | None = None
_listening = False


def _on_event(event: str, **_kw) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        _counts[name] += 1


def configure_xla_cache() -> str | None:
    """Place the cache (see module docstring) and start counting its
    hits and misses; returns the directory in use, None when off."""
    global _dir, _listening
    import jax

    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    _dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or None
    if _dir is None and jax.default_backend() != "cpu":
        _dir = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", _dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return _dir


def xla_cache_stats() -> dict:
    """The directory, how many entries it holds, and the compiles that
    hit or missed it since ``configure_xla_cache``."""
    try:
        entries = len(os.listdir(_dir)) if _dir else 0
    except OSError:
        entries = 0
    return {"dir": _dir, "entries": entries, **_counts}
