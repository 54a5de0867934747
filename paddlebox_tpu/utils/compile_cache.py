"""Where JAX's persistent compilation cache lives, decided in one place.

One binned-push train step costs the TPU compiler tens of seconds, and a
cold chip call pays that for every program, so the entry points
(``chip_smoke.py``, ``benchmark/run.py``, ``examples/train_ctr.py``) turn
the persistent cache on first thing. The directory is part of the cache key's
environment, so it must not move between runs: when
``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and nothing is
set in code; otherwise it is ``.jax_cache`` at the root of this checkout
(listed in ``.gitignore``) — never a temp name, a pid or a time. The key
holds each program's metadata too (``enable_compile_cache``), so an entry
serves only the tree it was compiled from. Tests do not call this: they
compile small programs and leave the cache off.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class CompileMeter:
    """Seconds and counts of this process's backend compilations, from
    JAX's own monitoring events: ``seconds`` covers every program handed
    to the backend (a persistent-cache hit costs its retrieval time),
    ``hits`` the ones the persistent cache answered."""

    def __init__(self):
        self.seconds = 0.0
        self.compilations = 0
        self.hits = 0
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compilations += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> dict:
        return {"compile_seconds": round(self.seconds, 3),
                "compilations": self.compilations,
                "cache_hits": self.hits}

    def since(self, earlier: dict) -> dict:
        """What was compiled after the `earlier` snapshot."""
        now = self.snapshot()
        return {"compile_seconds": round(now["compile_seconds"]
                                         - earlier["compile_seconds"], 3),
                "compilations": now["compilations"]
                - earlier["compilations"],
                "cache_hits": now["cache_hits"] - earlier["cache_hits"]}


def enable_compile_cache() -> dict:
    """Turn the persistent compilation cache on (see module docstring).
    Returns ``{"dir", "from", "warm"}``: the directory in use, whether
    the environment or this checkout named it, and whether it already
    held entries when this process started."""
    import jax
    # An executable's metadata is read since PR 38 (monitor/device_scopes.py
    # takes each instruction's stage from its op_name), and JAX leaves
    # metadata out of the cache's key by default: a tree that differs from
    # the one that filled the cache in scopes alone would run that tree's
    # executables and read its names. With metadata in the key an entry
    # answers only the source it was compiled from (file, line and scope
    # of every operation), at the price of a miss where lines moved.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        cache_dir, source = env_dir, "env"
    else:
        cache_dir, source = os.path.join(_CHECKOUT, ".jax_cache"), "checkout"
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    try:
        warm = any(os.scandir(cache_dir))
    except OSError:
        warm = False
    return {"dir": cache_dir, "from": source, "warm": warm}
