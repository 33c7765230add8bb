"""JAX process environment: where the persistent compile cache lives, and
the environment of child processes that must stay off the accelerator.

A chip belongs to one process at a time. Once the serving (or bench)
process has touched JAX it holds the chip, so every child it starts —
spawned load generators, spawn read workers, virtual-mesh legs — runs
with ``JAX_PLATFORMS=cpu`` set explicitly; otherwise a child importing
the package on a TPU host would try to grab the chip and fail or hang.
"""

from __future__ import annotations

import contextlib
import os

#: fixed default cache location: JAX keys cache entries by path among
#: other things, so a directory that moves between runs never hits
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax-cache",
)


def cpu_env(n_devices: int = 0) -> dict:
    """Environment for a child interpreter pinned to the CPU backend; with
    ``n_devices`` > 0 it also gets an n-device virtual CPU mesh (the
    device-count flag is spliced into any operator-set XLA_FLAGS, never
    overwriting them)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if n_devices > 0:
        flags = [
            f
            for f in env.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")
        ]
        flags.append(f"--xla_force_host_platform_device_count={n_devices}")
        env["XLA_FLAGS"] = " ".join(flags)
    return env


@contextlib.contextmanager
def cpu_children():
    """Processes started inside this block (multiprocessing spawn/forkserver
    children inherit the C environment at start) see JAX_PLATFORMS=cpu.
    This process's backend is already initialized, so it is unaffected."""
    old = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = old


def enable_compile_cache(configured: str = "") -> str:
    """Turn on JAX's persistent compilation cache before the first jit and
    return the directory in use.

    ``JAX_COMPILATION_CACHE_DIR`` set in the environment wins, and then no
    directory is set in code (JAX reads the variable itself). Otherwise the
    ``configured`` directory (``engine.compile_cache_dir``) is used, else
    the fixed ``<repo>/.jax-cache``. The size and compile-time floors are
    dropped to zero: this service's kernel set is small and every entry is
    worth keeping."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not cache_dir:
        cache_dir = configured or REPO_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
