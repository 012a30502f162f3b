"""The device: which one a run may use, what it can do at best, what it
compiled and how much memory it took."""

import os
import sys

# Published peaks of one chip, keyed by the exact `device_kind` JAX reports.
# Source: Google Cloud TPU documentation, "TPU v5e" system architecture:
# 197 TFLOP/s bf16, 819 GB/s HBM2e bandwidth, 16 GB HBM per chip.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (system architecture table)",
    },
}

# jax.monitoring duration events: a program lowered (compiled or fetched
# from the persistent cache) and a program compiled by the backend
LOWERED_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def peaks_for(kind):
    if kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {kind!r}; add a row to "
            "benchmarks/lib/device.py PEAKS with its source"
        )
    return PEAKS[kind]


def fail(msg):
    """No result line, exit code other than 0."""
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    sys.exit(3)


def open_device(chips, rehearsal):
    """Import JAX, refuse the wrong device, place the compile cache.
    -> ({"platform", "kind", "count"} as JAX reports them, cache directory)."""
    if rehearsal and os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        fail("--cpu-rehearsal needs JAX_PLATFORMS=cpu in the environment")
    import jax

    from areal_tpu.utils.runtime import enable_compile_cache

    devices = jax.devices()
    dev = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if rehearsal:
        if dev["platform"] != "cpu":
            fail(f"--cpu-rehearsal runs on the CPU, JAX found {dev['platform']!r}")
    else:
        if dev["platform"] != "tpu":
            fail(f"a measured run needs a TPU, JAX found {dev['platform']!r}; "
                 "no result is printed from another device")
        if dev["count"] < chips:
            fail(f"the cell asks for {chips} chips, JAX found {dev['count']}")
        peaks_for(dev["kind"])
    # the program's own placement: JAX_COMPILATION_CACHE_DIR if set, else
    # <checkout>/.jax_cache (fixed path: the path is part of the key)
    cache_dir = enable_compile_cache()
    # every program goes to the cache, the small ones too: a later run of
    # the cell has to find all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return dev, cache_dir


class CompileCounter:
    """Counts programs lowered and programs compiled, by `jax.monitoring`
    duration events; `mark()` starts a new count (the measured window)."""

    def __init__(self):
        import logging

        import jax
        import jax.monitoring

        self.lowered = 0
        self.compiled = 0
        self.compile_s = 0.0
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        # what was compiled, for the diagnostics line: JAX logs each
        # program it compiles by name and shapes
        jax.config.update("jax_log_compiles", True)
        counter = self

        class _Names(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if msg.startswith("Compiling ") and len(counter.names) < 12:
                    counter.names.append(msg[:300])

        for name in ("jax._src.interpreters.pxla", "jax._src.dispatch"):
            lg = logging.getLogger(name)
            lg.addHandler(_Names())
            lg.propagate = False

    def _on(self, event, seconds, **kw):
        if event == LOWERED_EVENT:
            self.lowered += 1
        elif event == BACKEND_COMPILE_EVENT:
            self.compiled += 1
            self.compile_s += seconds

    def mark(self):
        self.lowered = self.compiled = 0
        self.compile_s = 0.0
        self.names = []

    def snapshot(self):
        return {"lowered": self.lowered, "compiled": self.compiled,
                "compile_s": self.compile_s, "names": list(self.names)}


def memory_peak_bytes():
    """Peak bytes in use on the fullest chip, None where the backend does
    not say (the CPU)."""
    import jax

    peaks = [
        s["peak_bytes_in_use"]
        for s in (d.memory_stats() for d in jax.devices())
        if s and "peak_bytes_in_use" in s
    ]
    return max(peaks) if peaks else None


def jax_seed(seed):
    """A `jax.random` key from any whole number (the driver's seeds pass
    2**31): the low 31 bits make the key, the rest is folded in."""
    import jax

    key = jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)
    return jax.random.fold_in(key, int(seed) >> 31)
