"""What the process runs on, decided in one place.

Two rules the kernels, the utilisation tables and every jitting entry
point share:

- **The backend is asked for, never inferred.**  Pallas kernels lower for
  the TPU.  They run in interpret mode only when a test's flag or argument
  says so, or when the process was started with `JAX_PLATFORMS=cpu` in so
  many words (the CPU suite and the CI smoke jobs are).  Any other non-TPU
  backend is a machine that was meant to have a chip and does not: that
  raises instead of quietly running something slower.
- **The compile cache is placed from outside.**  `JAX_COMPILATION_CACHE_DIR`
  wins and nothing is set in code; without it the cache lives at one fixed
  path inside the checkout (the path is part of the cache key, so a
  directory named after a pid, a time or a temp name would never hit).

Importing this module does not import JAX: the launcher uses it too.
"""

import os

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def cpu_requested() -> bool:
    """The process was started with `JAX_PLATFORMS=cpu`, nothing else."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def kernel_backend(interpret_flag: bool = False) -> str:
    """"tpu" (lower the kernel), "interpret" (a test's flag), or "cpu" (an
    explicit CPU run: callers interpret or take their reference path).  A
    non-TPU backend nobody asked for raises."""
    import jax

    if interpret_flag:
        return "interpret"
    backend = jax.default_backend()
    if backend == "tpu":
        return "tpu"
    if cpu_requested():
        return "cpu"
    raise RuntimeError(
        f"JAX came up on {backend!r} but the process did not ask for it: "
        "the Pallas kernels need a TPU.  Start a CPU run with "
        "JAX_PLATFORMS=cpu in the environment."
    )


def compile_cache_dir() -> str:
    """Where the persistent compile cache is: the environment's directory,
    else `<checkout>/.jax_cache`."""
    return os.environ.get(COMPILE_CACHE_ENV) or os.path.join(
        REPO_ROOT, ".jax_cache"
    )


def enable_compile_cache() -> str:
    """Called once by every entry point that jits.  Returns the directory
    in use.  With the variable set JAX has already read it, and this sets
    nothing."""
    if not os.environ.get(COMPILE_CACHE_ENV):
        import jax

        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    return compile_cache_dir()


def device_report() -> dict:
    """The device as JAX reports it, and its peak memory so far: what a
    process that holds the chip tells whoever started it."""
    import jax

    devices = jax.devices()
    # the CPU backend reports no memory statistics
    peaks = [
        s["peak_bytes_in_use"]
        for s in (d.memory_stats() for d in devices)
        if s and "peak_bytes_in_use" in s
    ]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "peak_bytes_in_use": max(peaks) if peaks else None,
    }
