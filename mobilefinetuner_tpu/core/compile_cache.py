"""Where JAX's persistent compilation cache lives — one rule, one place.

Every entry point (the CLIs, bench.py, tools/serve_bench.py, the router's
replicas, chip_smoke.py) calls `enable_compile_cache()` first thing:

  - `JAX_COMPILATION_CACHE_DIR` set: JAX reads the variable itself; this
    module sets nothing, so whoever launched the process (a driver, a
    chip tool) owns the placement;
  - unset: ONE fixed directory inside the checkout (`<repo>/.jax_cache`,
    git-ignored). The directory is part of the cache key's context, so
    it is never built from a temporary name, a pid or the time — a
    directory that moves never hits.

The module imports no JAX at import time: a parent process that must
stay off the accelerator (chip_smoke.py, `serve_bench --router`) can ask
`cache_dir()` where its children will cache without touching a backend.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "writes",
}
_counts = {"requests": 0, "hits": 0, "writes": 0}
_listening = False


def cache_dir() -> str:
    """The directory compiled programs are cached in (no JAX import)."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def _on_event(event: str, **_kw) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        _counts[key] += 1


def enable_compile_cache() -> str:
    """Place the persistent cache by the rule above and start counting
    its traffic (`cache_counts`). Idempotent. Returns the directory."""
    global _listening
    import jax
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return cache_dir()


def cache_counts() -> dict:
    """Persistent-cache traffic of this process since enable: compile
    `requests` that consulted the cache, `hits` served from it, and
    `writes` of new entries (programs under JAX's size/compile-time
    floors are compiled without either)."""
    return dict(_counts)
