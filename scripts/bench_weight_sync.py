"""Measure trainer→server weight-sync latency: transfer vs disk path.

Transfer = binary octet-stream chunks into server memory
(gen/server.py /update_weights_chunk); disk = HF safetensors snapshot +
/update_weights_from_disk.  On a single-core host the two ends of the
transfer serialize, so transfer_vs_disk > 1 here does NOT mean the wire
path lost — see docs/perf.md "Weight-sync latency" for the decomposition
and regime analysis.  Host/network-bound, so it runs anywhere:

    JAX_PLATFORMS=cpu python scripts/bench_weight_sync.py

Prints one JSON line; the numbers live in docs/perf.md.
"""

import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax

    import asyncio
    import threading

    import numpy as np
    from aiohttp import web

    from areal_tpu.gen.engine import GenEngine
    from areal_tpu.gen.server import GenServer
    from areal_tpu.models import init_params
    from areal_tpu.models.hf import save_hf_checkpoint
    from areal_tpu.models.model_config import qwen25_1p5b
    from areal_tpu.utils.http import request_with_retry_sync

    cfg = qwen25_1p5b().replace(dtype="bfloat16", param_dtype="bfloat16")
    params = init_params(cfg, jax.random.PRNGKey(0))
    n_bytes = sum(int(np.prod(p.shape)) * 2 for p in jax.tree_util.tree_leaves(params))

    engine = GenEngine(cfg, params=params, n_slots=1, max_seq_len=128,
                       prompt_bucket=16)
    server = GenServer(engine)
    server.start()
    holder, started = {}, threading.Event()

    def _run():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)

        async def go():
            runner = web.AppRunner(server.app())
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            holder["addr"] = f"127.0.0.1:{runner.addresses[0][1]}"
            started.set()

        loop.run_until_complete(go())
        loop.run_forever()

    threading.Thread(target=_run, daemon=True).start()
    assert started.wait(30)
    addr = holder["addr"]

    # --- transfer path: trainer-side push through the engine hook -------
    from areal_tpu.api.config import TrainEngineConfig
    from areal_tpu.api.io_struct import WeightUpdateMeta
    from areal_tpu.engine.jax_train import JaxTrainEngine

    trainer = JaxTrainEngine(
        TrainEngineConfig(
            experiment_name="wsync", trial_name="t",
            init_from_scratch=True, dtype="bfloat16",
            param_dtype="bfloat16", optimizer=None,
        ),
        model_config=cfg,
    )
    trainer.initialize(ft_spec=None)
    # settle async param initialisation: measuring from here would charge
    # jit-init wait time to the transfer path
    jax.block_until_ready(trainer.params)
    os.environ["AREAL_LLM_SERVER_ADDRS"] = addr
    # abort-commit path pinned: the bench measures the stream+commit
    # choreography the non-live fleet default used through r4
    meta = WeightUpdateMeta.from_transfer("wsync", "t", live_commit=False)
    t0 = time.perf_counter()
    trainer._update_weights_transfer(meta)
    transfer_s = time.perf_counter() - t0

    # --- disk path: HF snapshot + server-side load ----------------------
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "v1")
        t0 = time.perf_counter()
        host = trainer._export_params()
        save_hf_checkpoint(host, cfg, path, save_dtype="bfloat16")
        save_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        request_with_retry_sync(
            addr=addr, endpoint="/update_weights_from_disk",
            payload={"path": path, "version": 2}, timeout=600,
        )
        load_s = time.perf_counter() - t0

    print(json.dumps({
        "model": "qwen25_1p5b",
        "model_bytes_bf16": n_bytes,
        "transfer_path_seconds": round(transfer_s, 2),
        "disk_path_seconds": round(save_s + load_s, 2),
        "disk_save_seconds": round(save_s, 2),
        "disk_load_seconds": round(load_s, 2),
        "transfer_vs_disk": round(transfer_s / max(save_s + load_s, 1e-9), 3),
    }))


if __name__ == "__main__":
    main()
