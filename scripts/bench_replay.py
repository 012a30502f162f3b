"""Trace-driven load replay: latency-vs-throughput curves for the fleet.

ROADMAP item 2's measurement rig: fleet claims must be p50/p99
latency-vs-throughput curves under realistic mixed traffic, not
single-run tok/s means.  This harness drives a live router+gen fleet
with a recorded or synthetic arrival process at several rate
multipliers and emits one curve JSON:

- **workload**: either ``--trace events.jsonl`` (replays a recorded
  run's ``rollout_submit`` arrival clock, prompt lengths, and decode
  budgets — see `areal_tpu/obs/workload.py`) or ``--workload mixed``
  (seeded synthetic mix: chat bursts, GRPO groups with shared prompts,
  long-context stragglers);
- **fleet**: self-hosted by default — N in-process GenServers on the
  tiny test model behind the real Router, the same
  in-process-aiohttp pattern bench_e2e_grpo uses — or an external
  fleet via ``--addr host:port`` (nothing is booted, client-side
  metrics only);
- **rates**: each ``--rates`` multiplier compresses the arrival clock
  (16 = same work arriving 16x faster) and replays the full workload,
  measuring per-request e2e latency, achieved throughput, and errors.

The driver emits client-side lifecycle events (rollout_submit /
gen_done / rollout_lost) into the shared telemetry ring, so a
self-hosted run's ``--telemetry-dir`` dump contains full spans
(admission, prefill, decode chunks included) and ``--slo-report``
turns it straight into an SLO_REPORT JSON for `scripts/check_slo.py`.

Example (CPU smoke, the slo-smoke CI job):

  python scripts/bench_replay.py --model tiny --servers 1 --router \\
      --workload mixed --duration 8 --base-rps 2 --rates 1,4,16 \\
      --n-slots 8 --max-seq-len 256 --max-new-tokens 16 \\
      --telemetry-dir /tmp/replay --slo-report /tmp/replay/SLO_REPORT.json \\
      --out /tmp/replay/curves.json
"""

import argparse
import asyncio
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from areal_tpu.obs import slo as slo_mod  # noqa: E402
from areal_tpu.obs import workload as wl  # noqa: E402
from areal_tpu.obs.trace import dist_summary  # noqa: E402
from areal_tpu.utils import telemetry  # noqa: E402

SCHEMA = "areal-replay-curves/v1"


# ---------------------------------------------------------------------------
# fleet boot (self-hosted mode)
# ---------------------------------------------------------------------------


def _tiny_model():
    """Config and random weights of the self-hosted fleet."""
    import jax

    from areal_tpu.models import init_params
    from areal_tpu.models.model_config import tiny_config

    cfg = tiny_config(vocab_size=512, qkv_bias=True,
                      hf_architecture="Qwen2ForCausalLM", eos_token_id=None)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _boot_server(cfg, params, args, role: str = "both",
                 host_offload: Optional[bool] = None):
    """One GenServer on its own aiohttp thread (the bench_e2e pattern:
    two OS processes cannot share a chip, so the fleet slice lives in
    threads).  Returns (addr, stop)."""
    import threading

    from aiohttp import web

    from areal_tpu.gen.engine import GenEngine
    from areal_tpu.gen.server import GenServer
    from areal_tpu.utils import network

    engine = GenEngine(
        cfg,
        params=params,
        n_slots=args.n_slots,
        max_seq_len=args.max_seq_len,
        prompt_bucket=64,
        decode_chunk=8,
        share_prefix=True,
        host_offload=(args.host_offload
                      if host_offload is None else host_offload),
        host_cache_mb=args.host_cache_mb,
    )
    engine.host_min_tokens = args.host_min_tokens
    server = GenServer(engine, role=role)
    server.start()
    port = network.find_free_port()
    loop = asyncio.new_event_loop()

    def run():
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(server.app())
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", port)
        loop.run_until_complete(site.start())
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    _wait_health(f"127.0.0.1:{port}")

    def stop():
        server.shutdown.set()
        server.worker.join(timeout=10)
        loop.call_soon_threadsafe(loop.stop)

    return f"127.0.0.1:{port}", stop


def _boot_router(addrs: List[str], disagg: bool = False):
    """The real Router over the booted servers, same thread pattern."""
    import threading

    from aiohttp import web

    from areal_tpu.gen.router import Router, RouterConfig

    router = Router(RouterConfig(disagg=disagg), addresses=list(addrs))
    state: Dict[str, Any] = {}
    started = threading.Event()

    def run():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)

        async def _serve():
            runner = web.AppRunner(router.app())
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            state["loop"] = loop
            state["runner"] = runner
            state["port"] = runner.addresses[0][1]
            started.set()

        loop.run_until_complete(_serve())
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    if not started.wait(timeout=30):
        raise RuntimeError("replay Router did not come up")

    def stop():
        async def _cleanup():
            await state["runner"].cleanup()

        asyncio.run_coroutine_threadsafe(
            _cleanup(), state["loop"]).result(timeout=10)
        state["loop"].call_soon_threadsafe(state["loop"].stop)

    return f"127.0.0.1:{state['port']}", stop


def _wait_health(addr: str, timeout: float = 60.0) -> None:
    import urllib.request

    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        try:
            urllib.request.urlopen(f"http://{addr}/health", timeout=1)
            return
        except Exception:
            time.sleep(0.1)
    raise RuntimeError(f"replay backend {addr} did not come up")


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


async def _drive(addr: str, arrivals: List[wl.Arrival], *, rate: float,
                 vocab: int, seed: int, timeout: float,
                 max_seq_len: int, pin_streams: bool = False,
                 record: bool = False,
                 retries: int = 0) -> List[Dict[str, Any]]:
    """Replay one rate multiplier: fire every arrival at its scheduled
    time (absolute offsets from the run start, so client-side queueing
    delay shows up as latency, exactly like an open-loop load test) and
    measure per-request wall latency.

    ``pin_streams`` assigns a deterministic sampler stream id per
    trace_id (the cross-fleet bit-identity contract: same-seed engines
    share ``_decode_key``, so a client-pinned stream makes the token
    stream a pure function of the request, not of which server — or
    fleet topology — served it).  ``record`` keeps trace_id + token +
    logprob streams on each result for A/B comparison.  ``retries``
    emulates the RemoteInfEngine failover contract: on transport error
    resubmit up to N times (counter-keyed sampling makes the resubmit
    continue the identical stream), and only exhausted retries count as
    lost trajectories."""
    import aiohttp
    import zlib

    scaled = wl.scale(arrivals, rate)
    results: List[Dict[str, Any]] = []
    loop = asyncio.get_running_loop()
    t0 = loop.time()
    conn = aiohttp.TCPConnector(limit=0)
    client_timeout = aiohttp.ClientTimeout(total=timeout)
    async with aiohttp.ClientSession(
            connector=conn, timeout=client_timeout) as session:

        async def one(i: int, a: wl.Arrival) -> None:
            await asyncio.sleep(max(0.0, a.t - (loop.time() - t0)))
            # keep prompt + budget inside the fleet's sequence budget
            budget = max(1, min(a.max_new_tokens, max_seq_len - 4))
            plen = max(1, min(a.prompt_len, max_seq_len - budget - 4))
            ids = wl.prompt_ids(a, vocab=vocab, seed=seed)[:plen]
            trace_id = f"replay-x{rate:g}-{i:05d}"
            payload = {
                "rid": trace_id,
                "trace_id": trace_id,
                "group_id": f"x{rate:g}-{a.group_id}" if a.group_id else "",
                "group_n": a.group_n if a.group_id else 0,
                "input_ids": ids,
                "sampling_params": {
                    "max_new_tokens": budget,
                    "temperature": 1.0,
                },
            }
            if pin_streams:
                payload["stream_id"] = (
                    (zlib.crc32(trace_id.encode()) & 0x0FFFFFFF) + 1)
            telemetry.emit("rollout_submit", trace_id=trace_id,
                           rid=trace_id, group_id=payload["group_id"],
                           input_len=len(ids), server=addr)
            start = time.perf_counter()
            rec: Dict[str, Any] = {"kind": a.kind, "rate": rate}
            attempts = 0
            while True:
                attempts += 1
                try:
                    async with session.post(
                            f"http://{addr}/generate", json=payload) as resp:
                        body = await resp.json()
                        if resp.status != 200:
                            raise RuntimeError(f"HTTP {resp.status}")
                    lat = time.perf_counter() - start
                    out_len = len(body.get("output_tokens", []))
                    telemetry.emit(
                        "gen_done", trace_id=trace_id,
                        stop_reason=body.get("stop_reason", "stop"),
                        output_len=out_len, attempts=attempts, latency_s=lat)
                    rec.update(ok=True, latency_s=lat, output_len=out_len,
                               stop_reason=body.get("stop_reason", "stop"))
                    if record:
                        rec.update(
                            trace_id=trace_id,
                            tokens=list(body.get("output_tokens", [])),
                            logprobs=list(body.get("output_logprobs", [])
                                          or []))
                    break
                except Exception as e:  # noqa: BLE001 — errors are data here
                    if attempts <= retries:
                        telemetry.emit("resubmit", trace_id=trace_id,
                                       attempt=attempts)
                        await asyncio.sleep(0.2)
                        continue
                    lat = time.perf_counter() - start
                    telemetry.emit("rollout_lost", trace_id=trace_id)
                    rec.update(ok=False, latency_s=lat, output_len=0,
                               error=str(e)[:120])
                    break
            results.append(rec)

        await asyncio.gather(*(one(i, a) for i, a in enumerate(scaled)))
    return results


async def _warmup(addrs: List[str], *, vocab: int,
                  max_seq_len: int) -> None:
    """Trigger JIT compilation before measuring: one request per prompt
    bucket count the workload can reach, against EVERY server directly
    (through the router a balancer could leave a replica cold, and its
    compile stall would poison the first measured rate).  Runs with
    telemetry still disabled so compile time never lands in the SLO log
    or the curves."""
    import aiohttp

    lens = sorted({8, min(100, max(9, max_seq_len - 12))})
    async with aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=300)) as session:
        for a, addr in enumerate(addrs):
            for i, plen in enumerate(lens):
                payload = {
                    "rid": f"warmup-{a}-{i}",
                    "trace_id": f"warmup-{a}-{i}",
                    "input_ids": [3 + (j % max(1, vocab - 4))
                                  for j in range(plen)],
                    "sampling_params": {"max_new_tokens": 8,
                                        "temperature": 1.0},
                }
                async with session.post(
                        f"http://{addr}/generate", json=payload) as resp:
                    await resp.json()


def _scrape_prefix_stats(addrs: List[str]) -> Dict[str, int]:
    """Sum the radix/paged prefix-cache counters over the fleet's
    /metrics JSON surfaces (works identically for self-hosted and
    external backends)."""
    import urllib.request

    keys = ("prefix_cache_hits", "prefix_cache_misses",
            "prefix_cache_evictions", "prefix_cache_host_swaps")
    total = dict.fromkeys(keys, 0)
    for addr in addrs:
        try:
            with urllib.request.urlopen(
                    f"http://{addr}/metrics", timeout=5) as r:
                m = json.loads(r.read())
        except Exception:  # noqa: BLE001 — external fleets may not expose it
            continue
        for k in keys:
            total[k] += int(m.get(k, 0))
    return total


def _prefix_cache_delta(before: Dict[str, int],
                        after: Dict[str, int]) -> Dict[str, Any]:
    d = {k: after[k] - before[k] for k in before}
    lookups = d["prefix_cache_hits"] + d["prefix_cache_misses"]
    return {
        "hits": d["prefix_cache_hits"],
        "misses": d["prefix_cache_misses"],
        "evictions": d["prefix_cache_evictions"],
        "host_swaps": d["prefix_cache_host_swaps"],
        "hit_rate": (d["prefix_cache_hits"] / lookups) if lookups else None,
    }


def _rate_summary(rate: float, arrivals: List[wl.Arrival],
                  results: List[Dict[str, Any]],
                  wall_s: float) -> Dict[str, Any]:
    ok = [r for r in results if r["ok"]]
    out_tokens = sum(r["output_len"] for r in ok)
    offered_span = (arrivals[-1].t / rate) if arrivals else 0.0
    return {
        "rate": rate,
        "n": len(results),
        "ok": len(ok),
        "errors": len(results) - len(ok),
        "offered_rps": (len(arrivals) / offered_span)
        if offered_span > 0 else None,
        "achieved_rps": (len(ok) / wall_s) if wall_s > 0 else None,
        "output_tokens": out_tokens,
        "output_tokens_per_s": (out_tokens / wall_s) if wall_s > 0 else None,
        "wall_s": round(wall_s, 3),
        "latency_s": dist_summary(r["latency_s"] for r in ok),
        "latency_by_kind": {
            kind: dist_summary(r["latency_s"] for r in ok
                               if r["kind"] == kind)
            for kind in sorted({r["kind"] for r in ok})
        },
    }


# ---------------------------------------------------------------------------
# disaggregated A/B (ISSUE 17)
# ---------------------------------------------------------------------------


async def _warm_through_router(addr: str, *, vocab: int, n: int = 6) -> None:
    """Through-router warmup: the direct per-server pass compiles the
    fresh-prefill/decode programs, but only a routed request exercises
    the disagg handoff path (leg1 clip, /kv_export, /kv_import, leg2
    suffix-prefill on the decode server).  Run the same pass in BOTH
    phases so the colocated control pays identical compile costs."""
    import aiohttp

    async with aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=300)) as session:
        for i in range(n):
            plen = 12 + 7 * i
            payload = {
                "rid": f"routewarm-{i}",
                "trace_id": f"routewarm-{i}",
                "input_ids": [3 + (j % max(1, vocab - 4))
                              for j in range(plen)],
                "sampling_params": {"max_new_tokens": 6,
                                    "temperature": 1.0},
            }
            async with session.post(
                    f"http://{addr}/generate", json=payload) as resp:
                await resp.json()


def _router_snap(addr: str) -> Dict[str, Any]:
    import urllib.request

    try:
        with urllib.request.urlopen(
                f"http://{addr}/metrics", timeout=5) as r:
            return json.loads(r.read())
    except Exception:  # noqa: BLE001 — metrics are best-effort evidence
        return {}


def _run_ab(args, p, arrivals: List[wl.Arrival],
            rates: List[float], source: Dict[str, Any]) -> int:
    """Disaggregated-vs-colocated A/B at matched arrival rate.

    Two sequential phases over the SAME workload, seed, and total server
    count: a colocated control (N role=both replicas) and the disagg
    fleet (1 prefill + N-1 decode servers, role-aware router).  Client
    pins sampler stream ids per trace_id, so the two phases must produce
    bit-identical token streams — the exactness gate.  The perf verdict
    is decode-interference elimination: disagg inter-token p99 must not
    exceed the colocated control's.  ``--chaos`` kills the prefill
    server mid-way through the last disagg rate; the driver's failover
    retries (the RemoteInfEngine contract) must recover every
    trajectory for the zero-lost gate."""
    import threading

    import jax

    from areal_tpu.utils.runtime import enable_compile_cache

    enable_compile_cache()
    cfg, params = _tiny_model()
    vocab = cfg.vocab_size
    n_servers = max(3, args.servers)
    phases: Dict[str, Any] = {}
    streams: Dict[str, Dict[str, Dict[str, Any]]] = {}

    for mode in ("colocated", "disagg"):
        stops: List[Any] = []
        chaos_timer: Optional[threading.Timer] = None
        try:
            server_addrs = []
            if mode == "colocated":
                specs = [("both", None)] * n_servers
            else:
                # decode servers need the host tier: /kv_import installs
                # pages as host-tier entries that swap-in re-scatters
                specs = [("prefill", None)] + \
                    [("decode", True)] * (n_servers - 1)
            for role, off in specs:
                a, stop = _boot_server(cfg, params, args, role=role,
                                       host_offload=off)
                server_addrs.append(a)
                stops.append(stop)
            addr, rstop = _boot_router(server_addrs,
                                       disagg=(mode == "disagg"))
            stops.append(rstop)
            print(f"[{mode}] fleet up: {specs} -> {addr}",
                  file=sys.stderr, flush=True)

            asyncio.run(_warmup(server_addrs, vocab=vocab,
                                max_seq_len=args.max_seq_len))
            asyncio.run(_warm_through_router(addr, vocab=vocab))

            telemetry.set_enabled(True)
            telemetry.EVENTS.clear()
            curve = []
            phase_streams: Dict[str, Dict[str, Any]] = {}
            for ri, rate in enumerate(rates):
                last = ri == len(rates) - 1
                chaos_here = args.chaos and mode == "disagg" and last
                retries = 2 if chaos_here else 0
                if chaos_here:
                    span = (arrivals[-1].t / rate) if arrivals else 1.0
                    kill_stop = stops[0]  # the prefill server
                    chaos_timer = threading.Timer(
                        max(0.2, 0.4 * span), kill_stop)
                    chaos_timer.start()
                    print(f"[{mode}] chaos: prefill kill armed at "
                          f"{max(0.2, 0.4 * span):.1f}s into x{rate:g}",
                          file=sys.stderr, flush=True)
                t0 = time.perf_counter()
                results = asyncio.run(_drive(
                    addr, arrivals, rate=rate, vocab=vocab,
                    seed=args.seed, timeout=args.timeout,
                    max_seq_len=args.max_seq_len, pin_streams=True,
                    record=True, retries=retries))
                wall = time.perf_counter() - t0
                for r in results:
                    if r.get("ok") and "trace_id" in r:
                        phase_streams[r["trace_id"]] = {
                            "tokens": r.pop("tokens"),
                            "logprobs": r.pop("logprobs"),
                        }
                summary = _rate_summary(rate, arrivals, results, wall)
                summary["chaos"] = bool(chaos_here)
                curve.append(summary)
                lat = summary["latency_s"] or {}
                print(f"[{mode}] rate x{rate:g}: "
                      f"ok={summary['ok']}/{summary['n']} "
                      f"p50={lat.get('p50')} p99={lat.get('p99')}",
                      file=sys.stderr, flush=True)
            router_snap = _router_snap(addr)

            events_path = ""
            slo_report: Dict[str, Any] = {}
            if args.telemetry_dir:
                events_path = os.path.join(
                    args.telemetry_dir, f"events_{mode}.jsonl")
                telemetry.EVENTS.dump_jsonl(events_path)
                slo_report = slo_mod.build_report(
                    events_path, run_id=f"replay-{mode}",
                    source_name=events_path)
            telemetry.set_enabled(False)
            telemetry.EVENTS.clear()
            phases[mode] = {
                "curve": curve,
                "router": {k: router_snap.get(k) for k in
                           ("handoffs", "handoff_fallbacks", "roles",
                            "failovers")},
                "events_jsonl": events_path,
                "slo": {k: slo_report.get(k) for k in
                        ("inter_token_s", "ttft_s", "e2e_s",
                         "handoff", "trajectories")} if slo_report else {},
            }
            streams[mode] = phase_streams
            if slo_report and mode == "disagg" and args.slo_report:
                with open(args.slo_report, "w") as f:
                    json.dump(slo_report, f, indent=2)
                    f.write("\n")
                md = os.path.splitext(args.slo_report)[0] + ".md"
                with open(md, "w") as f:
                    f.write(slo_mod.render_markdown(slo_report))
        finally:
            if chaos_timer is not None:
                chaos_timer.cancel()
            for stop in reversed(stops):
                try:
                    stop()
                except Exception as e:  # noqa: BLE001 — teardown only
                    print(f"teardown: {str(e)[:120]}", file=sys.stderr)

    # exactness: same trace_id => same pinned stream => identical tokens
    # regardless of fleet topology (counter-keyed sampler; logprob
    # mismatches are reported but informational — decode-vs-suffix XLA
    # programs may differ in the last ulp at the handoff boundary)
    common = sorted(set(streams["colocated"]) & set(streams["disagg"]))
    token_mism = [t for t in common
                  if streams["colocated"][t]["tokens"]
                  != streams["disagg"][t]["tokens"]]
    lp_mism = [t for t in common
               if streams["colocated"][t]["logprobs"]
               != streams["disagg"][t]["logprobs"]]
    bit_identity = {
        "compared": len(common),
        "token_mismatches": len(token_mism),
        "token_mismatch_ids": token_mism[:8],
        "logprob_mismatches": len(lp_mism),
    }

    def _it_p99(mode: str) -> Optional[float]:
        d = (phases[mode]["slo"] or {}).get("inter_token_s") or {}
        return d.get("p99")

    co_p99, dis_p99 = _it_p99("colocated"), _it_p99("disagg")
    interference = {
        "colocated_inter_token_p99": co_p99,
        "disagg_inter_token_p99": dis_p99,
        "win": (co_p99 is not None and dis_p99 is not None
                and dis_p99 <= co_p99),
    }
    disagg_errors = sum(s["errors"] for s in phases["disagg"]["curve"])
    gates = {
        "bit_identity": len(common) > 0 and not token_mism,
        "handoffs_nonzero":
            int(phases["disagg"]["router"].get("handoffs") or 0) > 0,
        "zero_lost": disagg_errors == 0,
    }

    out: Dict[str, Any] = {
        "schema": SCHEMA,
        "mode": "disagg_ab",
        "source": source,
        "fleet": {"model": args.model, "servers": n_servers,
                  "n_slots": args.n_slots,
                  "max_seq_len": args.max_seq_len,
                  "chaos": bool(args.chaos),
                  "device_kind": jax.devices()[0].device_kind},
        "workload": wl.summarize(arrivals),
        "phases": phases,
        "bit_identity": bit_identity,
        "interference": interference,
        "gates": gates,
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")
    print(json.dumps(out))
    failed = [k for k, v in gates.items() if not v]
    if failed:
        print(f"FAIL: disagg gates violated: {failed}", file=sys.stderr)
        return 1
    print(f"disagg A/B ok: {bit_identity['compared']} streams "
          f"bit-identical, handoffs="
          f"{phases['disagg']['router'].get('handoffs')}, "
          f"inter-token p99 {dis_p99} vs colocated {co_p99}",
          file=sys.stderr)
    return 0


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--model", default="tiny", choices=("tiny",),
                   help="the tiny test model: counts, identity and control "
                        "flow only; rates come from benchmarks/run.py")
    p.add_argument("--servers", type=int, default=1,
                   help="self-hosted GenServer count (ignored with --addr)")
    p.add_argument("--router", action="store_true",
                   help="front the servers with the real Router (forced "
                        "on when --servers > 1)")
    p.add_argument("--addr", default="",
                   help="target an existing fleet instead of self-hosting")
    p.add_argument("--trace", default="",
                   help="events.jsonl to replay (arrival clock + shapes)")
    p.add_argument("--workload", default="mixed", choices=["mixed"],
                   help="synthetic workload when no --trace is given")
    p.add_argument("--duration", type=float, default=8.0,
                   help="synthetic workload span at 1x, seconds")
    p.add_argument("--base-rps", type=float, default=2.0,
                   help="synthetic workload request rate at 1x")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rates", default="1,4,16",
                   help="comma-separated arrival-rate multipliers (1-100x)")
    p.add_argument("--n-slots", type=int, default=8)
    p.add_argument("--max-seq-len", type=int, default=256)
    p.add_argument("--host-offload", action="store_true",
                   help="self-hosted servers spill evicted prefixes to a "
                        "host-DRAM LRU tier (ISSUE 16)")
    p.add_argument("--host-cache-mb", type=int, default=64,
                   help="host overflow tier capacity per server, MiB")
    p.add_argument("--host-min-tokens", type=int, default=32,
                   help="minimum retained length worth spilling to host")
    p.add_argument("--max-new-tokens", type=int, default=16,
                   help="synthetic workload decode-budget ceiling")
    p.add_argument("--disagg", action="store_true",
                   help="disaggregated A/B (ISSUE 17): colocated control "
                        "vs 1-prefill + N-1-decode fleet over the same "
                        "workload, gated on stream bit-identity")
    p.add_argument("--chaos", action="store_true",
                   help="with --disagg: kill the prefill server mid-way "
                        "through the last rate; zero lost trajectories "
                        "required (driver retries emulate client failover)")
    p.add_argument("--no-warmup", action="store_true",
                   help="skip the pre-measurement compile warmup")
    p.add_argument("--timeout", type=float, default=300.0,
                   help="per-request client timeout (timeouts count as "
                        "errors, i.e. lost trajectories)")
    p.add_argument("--out", default="", help="curve JSON path")
    p.add_argument("--telemetry-dir", default="",
                   help="enable telemetry and dump events.jsonl here")
    p.add_argument("--slo-report", default="",
                   help="also build an SLO report JSON from the run's "
                        "events (markdown twin next to it)")
    args = p.parse_args()

    rates = sorted({float(r) for r in args.rates.split(",") if r})
    if not rates:
        p.error("--rates must name at least one multiplier")
    if any(r <= 0 or r > 100 for r in rates):
        p.error("--rates multipliers must be in (0, 100]")

    if args.telemetry_dir:
        os.makedirs(args.telemetry_dir, exist_ok=True)

    # workload ---------------------------------------------------------
    if args.trace:
        arrivals = wl.arrivals_from_trace(
            args.trace, default_budget=args.max_new_tokens)
        if not arrivals:
            p.error(f"--trace {args.trace} has no rollout_submit events")
        source = {"trace": args.trace}
    else:
        arrivals = wl.synthetic_mixed(
            seed=args.seed, duration_s=args.duration,
            base_rps=args.base_rps,
            max_prompt_len=max(16, args.max_seq_len // 2),
            max_new_tokens=args.max_new_tokens)
        source = {"synthetic": args.workload, "seed": args.seed,
                  "duration_s": args.duration, "base_rps": args.base_rps}
    print(f"workload: {wl.summarize(arrivals)}", file=sys.stderr, flush=True)

    if args.chaos and not args.disagg:
        p.error("--chaos requires --disagg")
    if args.disagg:
        if args.addr:
            p.error("--disagg self-hosts both fleets; drop --addr")
        return _run_ab(args, p, arrivals, rates, source)

    # fleet ------------------------------------------------------------
    stops = []
    fleet: Dict[str, Any] = {"external": bool(args.addr)}
    vocab = 512
    warm_addrs: List[str]
    if args.addr:
        addr = args.addr
        warm_addrs = [addr]
        _wait_health(addr)
    else:
        from areal_tpu.utils.runtime import enable_compile_cache

        enable_compile_cache()
        cfg, params = _tiny_model()
        vocab = cfg.vocab_size
        server_addrs = []
        for _ in range(args.servers):
            a, stop = _boot_server(cfg, params, args)
            server_addrs.append(a)
            stops.append(stop)
        addr = server_addrs[0]
        warm_addrs = server_addrs
        use_router = args.router or args.servers > 1
        if use_router:
            addr, stop = _boot_router(server_addrs)
            stops.append(stop)
        fleet.update(model=args.model, servers=args.servers,
                     router=use_router, n_slots=args.n_slots,
                     max_seq_len=args.max_seq_len,
                     device_kind=jax.devices()[0].device_kind)
        print(f"fleet up: {server_addrs} -> {addr}",
              file=sys.stderr, flush=True)

    # replay -----------------------------------------------------------
    curve = []
    run_prefix_cache: Optional[Dict[str, Any]] = None
    try:
        if not args.no_warmup:
            tw = time.perf_counter()
            asyncio.run(_warmup(warm_addrs, vocab=vocab,
                                max_seq_len=args.max_seq_len))
            print(f"warmup done in {time.perf_counter() - tw:.1f}s",
                  file=sys.stderr, flush=True)
        # telemetry goes live only now: warmup/compile spans are not SLO
        # evidence, and a half-recorded warmup trace would fail the
        # completeness linter
        if args.telemetry_dir:
            telemetry.set_enabled(True)
        run_cache_before = _scrape_prefix_stats(warm_addrs)
        for rate in rates:
            cache_before = _scrape_prefix_stats(warm_addrs)
            t0 = time.perf_counter()
            results = asyncio.run(_drive(
                addr, arrivals, rate=rate, vocab=vocab, seed=args.seed,
                timeout=args.timeout, max_seq_len=args.max_seq_len))
            wall = time.perf_counter() - t0
            summary = _rate_summary(rate, arrivals, results, wall)
            # hit-rate-vs-latency: every point on the latency curve
            # carries the prefix-cache composition that produced it
            summary["prefix_cache"] = _prefix_cache_delta(
                cache_before, _scrape_prefix_stats(warm_addrs))
            curve.append(summary)
            lat = summary["latency_s"] or {}
            print(f"rate x{rate:g}: ok={summary['ok']}/{summary['n']} "
                  f"p50={lat.get('p50')} p99={lat.get('p99')} "
                  f"tok/s={summary['output_tokens_per_s']} "
                  f"hit_rate={summary['prefix_cache']['hit_rate']}",
                  file=sys.stderr, flush=True)
        run_prefix_cache = _prefix_cache_delta(
            run_cache_before, _scrape_prefix_stats(warm_addrs))
    finally:
        for stop in reversed(stops):
            try:
                stop()
            except Exception as e:  # noqa: BLE001 — teardown only
                print(f"teardown: {str(e)[:120]}", file=sys.stderr)

    out: Dict[str, Any] = {
        "schema": SCHEMA,
        "source": source,
        "fleet": fleet,
        "workload": wl.summarize(arrivals),
        "rates": curve,
        "prefix_cache": run_prefix_cache,
    }

    if args.telemetry_dir:
        events_path = os.path.join(args.telemetry_dir, "events.jsonl")
        n_events = telemetry.EVENTS.dump_jsonl(events_path)
        out["telemetry"] = {
            "events_jsonl": events_path,
            "n_events": n_events,
            "dropped_events": telemetry.EVENTS.dropped,
        }
        if args.slo_report:
            report = slo_mod.build_report(
                events_path, run_id="replay",
                source_name=events_path)
            # the prefix-cache composition rides the SLO report so
            # check_slo.py can band the global hit rate alongside the
            # latency percentiles (baseline key: prefix_cache.hit_rate)
            if run_prefix_cache is not None:
                report["prefix_cache"] = run_prefix_cache
            with open(args.slo_report, "w") as f:
                json.dump(report, f, indent=2)
                f.write("\n")
            md_path = os.path.splitext(args.slo_report)[0] + ".md"
            with open(md_path, "w") as f:
                f.write(slo_mod.render_markdown(report))
            out["slo_report"] = args.slo_report
    elif args.slo_report:
        p.error("--slo-report requires --telemetry-dir (events feed it)")

    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
