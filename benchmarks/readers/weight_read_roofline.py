"""Share (%) of the weight-read roofline of the decode path: the least time
the chip could take to read the model's weights once per forward pass
(`counters[counter]` passes x the bytes of one parameter in the
configuration's `bench.dtype` x `lib.flops.dense_param_count(config)` over
the peak HBM bytes/s) over the device time of the programs matching
`modules`.

The K/V bytes a pass reads are left out on purpose, and so are activations:
the share then errs low, never over 100%.  Bytes-bound: at these batch
sizes a decode pass is 2 FLOPs per weight byte per slot, far under the
chip's ridge.  None without a chip's peaks, without the counter (an earlier
commit of the program has none), or when no such program ran.
"""

import jax.numpy as jnp

from benchmarks.lib import flops, loader
from benchmarks.readers.device_time_of_modules_matching import modules_s


def read(ctx, spec):
    if ctx["peaks"] is None:
        return None
    passes = (ctx["counters"] or {}).get(spec["counter"])
    took, _ = modules_s(ctx["trace"], spec["modules"])
    if not passes or not took:
        return None
    config = loader.load_config(spec["config"])
    param_bytes = jnp.dtype(config["bench"]["dtype"]).itemsize
    need = passes * param_bytes * flops.dense_param_count(config)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / took
