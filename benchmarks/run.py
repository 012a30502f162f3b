#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads `workloads/<cell>.json`, its configuration `configs/<config>.json`
and every `layer_metrics/*.json` that lists the cell; runs the cell's kind
(`kinds/<kind>.py`) on the chips the cell asks for; prints diagnostics on
earlier lines and, as the LAST line of standard output, one JSON object:
`correct`, `attempted`, `failed`, `metrics`, `device` (and `breakdown` with
`--trace 1`), then `compared`: each number `correct` was decided on beside
its limit, which are the last lines of standard error too.  With
`--trace 0` the metrics are the cell's end-to-end metrics, with `--trace 1`
its per-layer metrics from a short traced window.

A run that finds no TPU, or fewer chips than the cell asks for, prints no
result and exits with code 3.  `--cpu-rehearsal` (with `JAX_PLATFORMS=cpu`)
runs the same control flow at a toy size; its metric names carry the prefix
`rehearsal.` and its device says `cpu`, so it cannot pass for a chip result.
"""

_T_PROCESS = __import__("time").perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)


def diag(**record):
    """An earlier line of output: anything but the result; `t` is the
    seconds since the process started (what `setup_s` counts from)."""
    record["t"] = round(time.perf_counter() - _T_PROCESS, 3)
    print(json.dumps({"diag": record}, default=str), flush=True)


class Bench:
    """What a kind gets from the harness: spans, the compile counter, the
    window's clock and the profiler."""

    def __init__(self, args, dev, peaks):
        from benchmarks.lib.device import CompileCounter
        from benchmarks.lib.spans import Spans

        self.args = args
        self.dev = dev
        self.peaks = peaks
        self.rehearsal = args.cpu_rehearsal
        self.spans = Spans()
        self.compiles = CompileCounter()
        self.diag = diag
        self.setup_s = None
        self.window_s = None
        self._trace_dir = None
        self._t_open = None
        self._window_span = None

    def window_seconds(self, cell):
        """How long this run measures: `--seconds`, or the cell's shorter
        `trace_seconds` in a traced run (traces are large)."""
        s = float(self.args.seconds)
        if self.args.trace:
            s = min(s, float(cell.get("trace_seconds", 5)))
        return s

    def open_window(self):
        """Set-up ends here.  Call with the device drained."""
        import jax.profiler

        self.spans.mark()
        self.compiles.mark()
        if self.args.trace:
            self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self._window_span = self.spans.span("window")
        self._window_span.__enter__()
        self._t_open = time.perf_counter()
        self.setup_s = self._t_open - _T_PROCESS
        return self._t_open

    def close_window(self, t_end=None):
        """Call with the device drained; `t_end` is when the last whole unit
        of work ended (defaults to now)."""
        import jax.profiler

        now = time.perf_counter()
        self._window_span.__exit__(None, None, None)
        self.window_s = (t_end or now) - self._t_open
        self.window_compiles = self.compiles.snapshot()
        if self.args.trace:
            jax.profiler.stop_trace()
        # what a run costs after its window (PERF.md section 5) starts
        # here: the profiler's export, then the kind's comparisons with the
        # reference (up to `phase: kind_done`), then `phase: trace_read`
        self.diag(phase="window_closed", window_s=self.window_s,
                  stop_trace_s=time.perf_counter() - now)
        return self.window_s

    def read_trace(self):
        from benchmarks.lib import trace_reduce

        if not self._trace_dir:
            return None
        try:
            return trace_reduce.read_xplane(self._trace_dir)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)


# toy sizes for the CPU rehearsal: the published keys, shrunk
REHEARSAL_HF = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "max_position_embeddings": 4096,
}


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--cpu-rehearsal", action="store_true",
                   help="toy sizes on the CPU; needs JAX_PLATFORMS=cpu; the "
                        "result cannot pass for a chip result")
    p.add_argument("--bench-root", default=HERE,
                   help="benchmark directory to read data files from")
    args = p.parse_args()

    from benchmarks.lib import device, loader

    try:
        import areal_tpu  # noqa: F401
    except ImportError as e:
        device.fail(f"the system under test is not in this checkout: {e}")

    cell = loader.load_cell(args.workload, args.bench_root)
    hf = loader.load_config(cell["config"], args.bench_root)
    layer_metrics = loader.load_layer_metrics(args.workload, args.bench_root)
    run_kind = loader.load_kind(cell["kind"], args.bench_root)
    if args.cpu_rehearsal:
        hf = {**hf, **REHEARSAL_HF}
        cell["traffic"] = {**cell["traffic"], **cell.get("rehearsal", {})}
        cell["rehearsal_run"] = True

    diag(phase="files", cell=cell["name"])
    dev, cache_dir = device.open_device(cell["chips"], args.cpu_rehearsal)
    peaks = None if args.cpu_rehearsal else device.peaks_for(dev["kind"])
    diag(phase="device", device=dev, compile_cache=cache_dir, cell=cell["name"],
         config=cell["config"], seed=args.seed, trace=args.trace)

    bench = Bench(args, dev, peaks)
    res = run_kind(cell, hf, bench)
    # res: correct, attempted, failed, metrics {name: (value, unit)},
    #      counts, counters, work, checks, compared {name: {value, limit}}
    diag(phase="kind_done")
    t_read = time.perf_counter()
    trace = bench.read_trace()
    read_s = time.perf_counter() - t_read
    out_metrics = {}
    line = {
        "correct": bool(res["correct"]),
        "attempted": int(res["attempted"]),
        "failed": int(res["failed"]),
    }
    device_block = {**dev, "memory_peak_bytes": device.memory_peak_bytes()}
    if args.trace:
        from benchmarks.lib import trace_reduce

        ctx = {
            "trace": trace, "window_s": bench.window_s,
            "counts": res.get("counts", {}), "spans": bench.spans,
            "counters": res.get("counters", {}), "work": res.get("work", {}),
            "peaks": peaks, "compiles": bench.window_compiles,
        }
        for spec in layer_metrics:
            v = spec["read"](ctx, spec)
            if v is not None:
                out_metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
        if trace is not None:
            busy = trace_reduce.busy_s(trace)
            if busy is not None:
                device_block["busy_s"] = busy
            line["breakdown"] = {
                "device_ops": trace_reduce.top_device_ops(trace),
                "idle_gaps": trace_reduce.idle_gaps(trace),
            }
            diag(phase="trace", lines=trace.lines_seen,
                 matched={m["name"]: trace_reduce.matched_ops(trace, m["ops"])
                          for m in layer_metrics if "ops" in m},
                 alignment_ms=trace_reduce.alignment_ms(trace),
                 host_spans=len(trace.host_spans),
                 program_spans={k: len(v)
                                for k, v in trace.program_spans.items()},
                 read_s=read_s,
                 readers_s=time.perf_counter() - t_read - read_s)
        device_block["window_s"] = bench.window_s
    else:
        wanted = loader.end_to_end_metrics(cell["name"], args.bench_root)
        for name, (value, unit) in res["metrics"].items():
            if wanted is None or name in wanted:
                out_metrics[name] = {"value": value, "unit": unit}
        out_metrics["setup_s"] = {"value": bench.setup_s, "unit": "s"}
    diag(phase="window", window_s=bench.window_s, setup_s=bench.setup_s,
         compiles_in_window=bench.window_compiles,
         checks=res.get("checks"), counts=res.get("counts"),
         all_metrics=res["metrics"], spans=bench.spans.total_s)
    if args.cpu_rehearsal:
        out_metrics = {f"rehearsal.{k}": v for k, v in out_metrics.items()}
        line["rehearsal"] = True
    line["metrics"] = out_metrics
    line["device"] = device_block
    # what was compared, each number beside its limit: the line's last key,
    # and the last lines of standard error
    line["compared"] = {**res.get("compared", {}),
                        "failed": {"value": int(res["failed"]), "limit": 0}}
    for name, c in line["compared"].items():
        print(f"compared {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    # the kind has stopped what it started; a daemon thread of the program
    # under test must not keep the process from ending
    os._exit(0)


if __name__ == "__main__":
    main()
