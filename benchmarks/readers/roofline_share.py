"""Share (%) of the roofline: the least time the chip could take for the
work named by `work` (operations over peak FLOP/s when `bound` is "flops",
bytes over peak bytes/s when "bytes") over the device time of the
operations matching `ops`."""
from benchmarks.lib import trace_reduce


def read(ctx, spec):
    if ctx["trace"] is None or ctx["peaks"] is None:
        return None
    took = trace_reduce.time_of_ops_matching(ctx["trace"], spec["ops"])
    need = ctx["work"].get(spec["work"])
    if not took or not need:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s" if spec["bound"] == "flops"
                        else "hbm_bytes_per_s"]
    return 100.0 * (need / peak) / took
