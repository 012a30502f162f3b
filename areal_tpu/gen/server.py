"""HTTP generation server.

Serves the wire protocol the client backend speaks
(areal_tpu/engine/jax_remote.py) — the role SGLang's HTTP server plays for
the reference (areal/engine/sglang_remote.py:22 builds /generate,
/update_weights_from_disk, /pause_generation against it):

    POST /generate                 {rid, input_ids, sampling_params} ->
                                   {output_tokens, output_logprobs,
                                    output_versions, stop_reason, version}
    POST /pause_generation         decode loop parks (weight-update window)
    POST /continue_generation
    POST /update_weights_from_disk {path, version?}
    POST /update_weights_chunk     {name, dtype, shape, data_b64, commit?}
    GET  /health, /metrics

A dedicated worker thread owns all device computation (admission, decode
steps, weight swaps) so the asyncio loop never blocks on XLA; handlers talk
to it through the engine's queues and concurrent futures.  Registration in
name_resolve mirrors the reference's server wrappers
(areal/launcher/sglang_server.py registers its address for discovery).
"""

import argparse
import asyncio
import base64
import threading
import time
import weakref
from typing import Optional

import numpy as np

from aiohttp import web

from areal_tpu.analysis.lockcheck import lock_guarded
from areal_tpu.gen.engine import GenEngine, GenRequest
from areal_tpu.models.model_config import TransformerConfig, tiny_config
from areal_tpu.utils import logging, name_resolve, names, network, telemetry
from areal_tpu.utils.runtime import device_report, enable_compile_cache

logger = logging.getLogger("gen.server")


@lock_guarded
class GenServer:
    # the weight-update and handoff mailboxes are handed between asyncio
    # handlers and the device-worker thread; every touch must hold
    # _cmd_lock (areal-lint C1, runtime-validated under
    # AREAL_DEBUG_LOCKS=1)
    _GUARDED_FIELDS = {
        "_pending_weight_update": "_cmd_lock",
        "_pending_handoffs": "_cmd_lock",
    }

    def __init__(self, engine: GenEngine, role: str = "both"):
        if role not in ("prefill", "decode", "both"):
            raise ValueError(f"unknown server role: {role!r}")
        self.engine = engine
        # Disaggregated serving (ISSUE 17): the role is a routing
        # *advertisement* — the engine itself stays fully capable either
        # way (export/import/generate all work on any role), so a router
        # can always fall back to colocated `both` semantics when a role
        # pool empties or a breaker opens.
        self.role = role
        self.paused = threading.Event()  # set => paused
        self.shutdown = threading.Event()
        self._weight_futures: "list" = []
        self._chunk_buf = {}
        self._unstaged_params = None  # (host tree, version) staging fallback
        self._last_committed_version: Optional[int] = None
        self._cmd_lock = threading.Lock()
        self._pending_weight_update: Optional[dict] = None
        # KV-handoff mailbox: /kv_export and /kv_import enqueue here and
        # the worker thread services the engine calls (which touch the
        # device cache) between decode steps — even while paused, so a
        # weight-update window never deadlocks an in-flight handoff.
        self._pending_handoffs: "list" = []
        self.worker = threading.Thread(target=self._run, daemon=True)
        self.step_count = 0
        self.tokens_out = 0
        self.last_error: float = 0.0
        self._register_telemetry()

    def _register_telemetry(self):
        """Scrape-time collector mirroring engine/server counters into the
        shared `gen` registry (utils/telemetry.py).  Sampling happens only
        when /metrics is rendered — the decode loop never touches it.  The
        collector holds a weakref so short-lived servers (tests, benches)
        don't pin their engines through the process-global registry."""
        reg = telemetry.GEN
        self_ref = weakref.ref(self)

        def _collect():
            srv = self_ref()
            if srv is None:
                return
            eng = srv.engine
            # every engine.stats entry is a monotonic counter; mirroring the
            # dict generically keeps the exposition tolerant of key churn
            for k, v in eng.stats.items():
                try:
                    reg.counter(f"{k}_total").set_total(float(v))
                except (TypeError, ValueError):
                    continue
            reg.counter(
                "decode_steps_total", "Productive decode-loop steps"
            ).set_total(srv.step_count)
            reg.counter(
                "tokens_generated_total", "Decode tokens delivered"
            ).set_total(srv.tokens_out)
            reg.gauge("active_requests", "Occupied slots").set(
                eng.active_count()
            )
            reg.gauge("weight_version", "Live weight version").set(eng.version)
            reg.gauge(
                "last_pause_seconds",
                "Most recent weight-swap pause window (histogram: "
                "areal_gen_pause_window_seconds)",
            ).set(eng.last_pause_s)
            reg.gauge("staged_standby", "Standby weights staged (0/1)").set(
                1.0 if eng.has_standby else 0.0
            )
            reg.gauge(
                "decode_attended_fraction",
                "Attended / ceiling decode columns",
            ).set(eng.decode_attended_fraction())
            for t, occ in enumerate(eng.tier_occupancy()):
                reg.gauge(
                    "tier_occupancy", "Occupied slots per decode tier"
                ).set(occ, tier=str(t))
            # speculative decode (ISSUE 12): lifetime acceptance rate
            # (unlabeled) + the windowed per-tier rates steering each
            # tier's draft-length rung
            drafted = float(eng.stats.get("spec_drafted", 0))
            accepted = float(eng.stats.get("spec_accepted", 0))
            rate_g = reg.gauge(
                "spec_acceptance_rate",
                "Draft tokens accepted / drafted (per-tier series are "
                "the controller's acceptance window)",
            )
            rate_g.set(accepted / drafted if drafted else 0.0)
            for t, r in enumerate(eng.spec_acceptance_rates()):
                rate_g.set(r, tier=str(t))
            # unified radix/paged prefix cache (ISSUE 16): the global
            # hit-rate over all admissions (device hits + host swap-ins);
            # the underlying hits/misses/evictions/host_swaps counters
            # ride the generic engine.stats mirror above
            reg.gauge(
                "prefix_cache_hit_rate",
                "Admissions served from the radix/paged prefix cache",
            ).set(eng.prefix_cache_hit_rate())
            # ragged paged-decode attention (ISSUE 19): mean KV pages the
            # kernel gathered per collapsed dispatch; the raw counters
            # ride the generic engine.stats mirror above
            disp = float(eng.stats.get("ragged_dispatches", 0))
            pages = float(eng.stats.get("ragged_attended_pages", 0))
            reg.gauge(
                "ragged_attended_pages",
                "Mean KV pages gathered per ragged kernel dispatch",
            ).set(pages / disp if disp else 0.0)

        reg.add_collector(_collect)

    # ------------------------------ worker ------------------------------

    def start(self):
        self.worker.start()

    def _run(self):
        while not self.shutdown.is_set():
            upd = None
            with self._cmd_lock:
                if self._pending_weight_update is not None:
                    upd = self._pending_weight_update
                    self._pending_weight_update = None
            if upd is not None:
                try:
                    if upd.get("stage_params") is not None:
                        # device placement interleaves with decode steps —
                        # generation is NOT paused for staging
                        v = self.engine.stage_params(
                            upd["stage_params"], version=upd.get("version")
                        )
                    elif upd.get("commit_staged"):
                        v = self.engine.commit_staged(
                            live=bool(upd.get("live"))
                        )
                    elif upd.get("live") and upd.get("params") is not None:
                        # live commit without standby HBM: the pause is the
                        # host->device placement, but in-flight requests
                        # wait it out instead of dying
                        v = self.engine.swap_weights_live(
                            upd["params"], version=upd.get("version")
                        )
                    else:
                        v = self.engine.load_weights(
                            path=upd.get("path"),
                            params=upd.get("params"),
                            version=upd.get("version"),
                        )
                    upd["future"].set_result(v)
                    if not upd.get("stage_params"):
                        logger.info(f"weights at version {v}")
                except Exception as e:  # noqa: BLE001 — surface to the caller
                    upd["future"].set_exception(e)
                continue
            self._service_handoffs()
            if self.paused.is_set():
                time.sleep(0.005)
                continue
            try:
                stepped = self.engine.step()
            except Exception:  # noqa: BLE001 — the loop must survive XLA errors
                logger.exception("decode step failed; aborting in-flight requests")
                self.last_error = time.time()
                self.engine.abort_all("abort")
                continue
            self.step_count += 1 if stepped else 0
            self.tokens_out += stepped
            if not stepped:
                time.sleep(0.002)

    def _service_handoffs(self):
        """Drain the KV-handoff mailbox on the worker thread.  The
        engine's export/import methods gather/scatter against the device
        cache, so they must run where every other device touch runs —
        here, between decode steps — never on an HTTP handler thread."""
        ops = None
        with self._cmd_lock:
            if self._pending_handoffs:
                ops = self._pending_handoffs
                self._pending_handoffs = []
        if not ops:
            return
        for op in ops:
            t0 = time.perf_counter()
            try:
                if op["kind"] == "export":
                    op["future"].set_result(
                        self.engine.export_request_kv(op["input_ids"])
                    )
                else:
                    op["future"].set_result(
                        self.engine.import_request_kv(op["entry"])
                    )
                telemetry.HANDOFF.observe(
                    time.perf_counter() - t0, op=op["kind"]
                )
            except Exception as e:  # noqa: BLE001 — surface to the caller
                op["future"].set_exception(e)

    def _queue_handoff(self, **kw):
        import concurrent.futures

        fut = concurrent.futures.Future()
        with self._cmd_lock:
            self._pending_handoffs.append({"future": fut, **kw})
        return fut

    # ----------------------------- handlers -----------------------------

    @staticmethod
    def _req_from_body(body: dict, on_done) -> GenRequest:
        """Wire body -> GenRequest (shared by /generate and
        /generate_batch)."""
        sp = body.get("sampling_params", {})
        pixel_values = None
        image_grid_thw = None
        if body.get("pixel_values_b64"):
            pixel_values = np.frombuffer(
                base64.b64decode(body["pixel_values_b64"]), dtype=np.float32
            ).reshape(body["pixel_values_shape"])
            image_grid_thw = np.asarray(body["image_grid_thw"], np.int64)
        return GenRequest(
            rid=body.get("rid", ""),
            trace_id=str(body.get("trace_id", "") or ""),
            group_id=str(body.get("group_id", "") or ""),
            group_n=int(body.get("group_n", 0) or 0),
            input_ids=[int(t) for t in body["input_ids"]],
            max_new_tokens=int(sp.get("max_new_tokens", 256)),
            min_new_tokens=int(sp.get("min_new_tokens", 0)),
            temperature=float(sp.get("temperature", 1.0)),
            top_p=float(sp.get("top_p", 1.0)),
            top_k=int(sp.get("top_k", 0)),
            stop_token_ids=[int(t) for t in sp.get("stop_token_ids", [])],
            pixel_values=pixel_values,
            image_grid_thw=image_grid_thw,
            # disaggregated handoff (ISSUE 17): leg-2 resubmissions pin
            # the sampler stream so the continuation samples the exact
            # keys the colocated run would have used
            stream_id=int(body.get("stream_id", 0) or 0),
            on_done=on_done,
        )

    @staticmethod
    def _result_payload(r: GenRequest, version: int) -> dict:
        return {
            "output_tokens": r.output_tokens,
            "output_logprobs": r.output_logprobs,
            "output_versions": r.output_versions,
            "stop_reason": r.stop_reason or "stop",
            "version": version,
            "trace_id": r.trace_id,
            # prompt tokens served from resident K/V (radix device hit or
            # host swap-in) — failover clients use this to confirm a
            # resubmission warm-started instead of cold-prefilling
            "cache_hit_tokens": r.cache_hit_tokens,
            # the counter-keyed sampler stream this request decoded on;
            # a handoff leg-2 (or failover resubmit) passes it back in
            # so the continuation stays bit-identical
            "stream_id": r.stream_id,
        }

    async def generate(self, request: web.Request) -> web.Response:
        body = await request.json()
        loop = asyncio.get_running_loop()
        fut = loop.create_future()

        def on_done(r: GenRequest):
            loop.call_soon_threadsafe(fut.set_result, r)

        self.engine.submit(self._req_from_body(body, on_done))
        r: GenRequest = await fut
        return web.json_response(self._result_payload(r, self.engine.version))

    async def generate_batch(self, request: web.Request) -> web.Response:
        """Submit a whole group in one POST ({"requests": [...]}) so every
        member lands in one admission window and the engine's cluster
        fan-out shares their common prefix (GRPO groups: one prefill +
        fan-out instead of group_size prefills).  Responds with
        {"results": [...]} in request order once ALL members finish."""
        body = await request.json()
        reqs_in = body.get("requests", [])
        if not reqs_in:
            return web.json_response({"error": "empty batch"}, status=400)
        loop = asyncio.get_running_loop()
        futs = [loop.create_future() for _ in reqs_in]

        def make_done(fut):
            def on_done(r: GenRequest):
                loop.call_soon_threadsafe(fut.set_result, r)

            return on_done

        reqs = [
            self._req_from_body(b, make_done(f))
            for b, f in zip(reqs_in, futs)
        ]
        self.engine.submit_batch(reqs)
        done = await asyncio.gather(*futs)
        version = self.engine.version
        return web.json_response(
            {"results": [self._result_payload(r, version) for r in done]}
        )

    # ------------------------- KV handoff (ISSUE 17) --------------------

    _HANDOFF_TIMEOUT_S = 30.0

    async def kv_export(self, request: web.Request) -> web.Response:
        """Serialize the retained KV pages covering a prefix of
        `input_ids` to the wire format (gather on the existing bucket
        ladder -> host -> base64).  404 when neither the device radix nor
        the host tier retains a usable prefix — the router then falls
        back to a cold leg-2 prefill, which the counter-keyed sampler
        keeps bit-identical anyway."""
        from areal_tpu.gen import kv_pool

        body = await request.json()
        fut = self._queue_handoff(
            kind="export",
            input_ids=[int(t) for t in body["input_ids"]],
        )
        try:
            entry = await asyncio.wait_for(
                asyncio.wrap_future(fut), timeout=self._HANDOFF_TIMEOUT_S
            )
        except asyncio.TimeoutError:
            return web.json_response(
                {"error": "kv_export timed out"}, status=503
            )
        if entry is None:
            return web.json_response(
                {"error": "no exportable prefix"}, status=404
            )
        doc = kv_pool.wire_encode_entry(entry)
        return web.json_response(doc)

    async def kv_import(self, request: web.Request) -> web.Response:
        """Install a wire-format KV entry into the host overflow tier;
        the next admission matching its token prefix swaps it in as a
        warm-cache hit (the same path a local spill round trip takes)."""
        from areal_tpu.gen import kv_pool

        body = await request.json()
        try:
            entry = kv_pool.wire_decode_entry(body)
        except (KeyError, ValueError) as e:
            return web.json_response(
                {"error": f"malformed wire entry: {e}"}, status=400
            )
        fut = self._queue_handoff(kind="import", entry=entry)
        try:
            ok = await asyncio.wait_for(
                asyncio.wrap_future(fut), timeout=self._HANDOFF_TIMEOUT_S
            )
        except asyncio.TimeoutError:
            return web.json_response(
                {"error": "kv_import timed out"}, status=503
            )
        if not ok:
            return web.json_response(
                {"error": "no host tier on this server "
                          "(start with --host-offload)"},
                status=409,
            )
        return web.json_response(
            {"ok": True, "valid_len": int(entry["valid_len"])}
        )

    async def pause(self, request: web.Request) -> web.Response:
        self.paused.set()
        return web.json_response({"ok": True})

    async def resume(self, request: web.Request) -> web.Response:
        self.paused.clear()
        return web.json_response({"ok": True})

    def _queue_weight_update(self, **kw):
        import concurrent.futures

        fut = concurrent.futures.Future()
        with self._cmd_lock:
            self._pending_weight_update = {"future": fut, **kw}
        return fut

    async def update_weights_from_disk(self, request: web.Request) -> web.Response:
        body = await request.json()
        fut = self._queue_weight_update(
            path=body["path"], version=body.get("version")
        )
        version = await asyncio.wrap_future(fut)
        return web.json_response({"ok": True, "version": version})

    async def update_weights_chunk(self, request: web.Request) -> web.Response:
        """Transfer path: the trainer streams named arrays — whole, or as
        (offset, bytes) pieces for arrays larger than the chunk budget —
        and `commit` swaps them in (counterpart of the reference's NCCL
        broadcast bucket protocol, fsdp_engine.py:298-330, over HTTP/DCN).

        Two encodings: `application/octet-stream` carries the raw bytes in
        the body with metadata in X-Weight-* headers (the fast path — no
        base64 inflation or json parse per chunk); a JSON body with
        `data_b64` remains for legacy clients and for `commit`."""
        if "application/octet-stream" in request.headers.get("Content-Type", ""):
            h = request.headers
            import json as _json

            name = h["X-Weight-Name"]
            data = await request.read()
            entry = self._chunk_buf.setdefault(
                name,
                {
                    "buf": bytearray(int(h["X-Weight-Nbytes"])),
                    "dtype": h.get("X-Weight-Dtype", "bfloat16"),
                    "shape": _json.loads(h.get("X-Weight-Shape", "[]")),
                },
            )
            off = int(h.get("X-Weight-Offset", 0))
            entry["buf"][off : off + len(data)] = data
            return web.json_response({"ok": True, "received": name})
        body = await request.json()
        if body.get("prepare"):
            # stage onto the DEVICE while generation keeps running, so the
            # later commit is an O(abort) pointer swap instead of an
            # O(model-bytes) placement inside the pause (VERDICT r3 weak
            # #2).  Sent by the trainer's stage_weights after streaming.
            if not self._chunk_buf:
                return web.json_response(
                    {"error": "prepare without staged chunks"}, status=409
                )
            params = self._assemble_params()
            fut = self._queue_weight_update(
                stage_params=params, version=body.get("version")
            )
            staged = await asyncio.wrap_future(fut)
            if not staged:
                # no standby HBM: keep the assembled HOST tree so commit
                # can still place it (the pre-staging is an optimisation,
                # never a correctness requirement)
                self._unstaged_params = (params, body.get("version"))
            return web.json_response({"ok": True, "staged": bool(staged)})
        if body.get("commit"):
            if self.engine.has_standby and (
                body.get("version") is None
                or body["version"] == self.engine.staged_version
            ):
                # pre-staged: the swap itself runs on the worker thread —
                # which is also the stepper, so `live: true` (no abort,
                # in-flight requests keep decoding across the swap, versions
                # recorded per token) is race-free by construction
                fut = self._queue_weight_update(
                    commit_staged=True, live=bool(body.get("live"))
                )
                version = await asyncio.wrap_future(fut)
                self._last_committed_version = version
                return web.json_response({"ok": True, "version": version})
            if self._unstaged_params is not None and (
                body.get("version") is None
                or body["version"] == self._unstaged_params[1]
            ):
                params, version = self._unstaged_params
                self._unstaged_params = None
                fut = self._queue_weight_update(
                    params=params, version=version,
                    live=bool(body.get("live")),
                )
                version = await asyncio.wrap_future(fut)
                self._last_committed_version = version
                return web.json_response({"ok": True, "version": version})
            if not self._chunk_buf:
                # idempotent retry: a commit whose response was lost leaves
                # an empty buffer — if that version is already live, say so
                # instead of failing a transfer that in fact succeeded
                if (
                    body.get("version") is None
                    or body["version"] == self._last_committed_version
                ):
                    return web.json_response(
                        {"ok": True, "version": self.engine.version}
                    )
                return web.json_response(
                    {"error": "commit without staged chunks"}, status=409
                )
            params = self._assemble_params()
            fut = self._queue_weight_update(
                params=params, version=body.get("version"),
                live=bool(body.get("live")),
            )
            version = await asyncio.wrap_future(fut)
            self._last_committed_version = version
            return web.json_response({"ok": True, "version": version})
        name = body["name"]
        data = base64.b64decode(body["data_b64"])
        entry = self._chunk_buf.setdefault(
            name,
            {
                "buf": bytearray(int(body["nbytes"])),
                "dtype": body["dtype"],
                "shape": body["shape"],
            },
        )
        off = int(body["offset"])
        entry["buf"][off : off + len(data)] = data
        return web.json_response({"ok": True, "received": name})

    def _assemble_params(self):
        """Drain the chunk buffer into a host param tree."""
        from areal_tpu.models.hf import state_to_params

        host = {name: self._assemble(e) for name, e in self._chunk_buf.items()}
        self._chunk_buf = {}
        return state_to_params(
            iter(host.items()), self.engine.model_config, dtype="bfloat16"
        )

    @staticmethod
    def _assemble(entry) -> np.ndarray:
        import ml_dtypes

        dtype = (
            np.dtype(ml_dtypes.bfloat16)
            if entry["dtype"] == "bfloat16"
            else np.dtype(entry["dtype"])
        )
        # view straight over the staged bytearray — bytes(...) would copy
        # the whole model a second time on the commit path
        return np.frombuffer(entry["buf"], dtype=dtype).reshape(
            entry["shape"]
        )

    async def health(self, request: web.Request) -> web.Response:
        if not self.worker.is_alive() and not self.shutdown.is_set():
            return web.json_response({"status": "dead"}, status=500)
        return web.json_response(
            {
                "status": "paused" if self.paused.is_set() else "ok",
                "role": self.role,
                "version": self.engine.version,
                "active": self.engine.active_count(),
                "last_error": self.last_error,
            }
        )

    async def metrics(self, request: web.Request) -> web.Response:
        # Prometheus text exposition on request (?format=prometheus or an
        # Accept header asking for text/openmetrics); legacy JSON stays the
        # default for existing consumers
        if telemetry.wants_prometheus(
            request.query.get("format"), request.headers.get("Accept", "")
        ):
            return web.Response(
                text=telemetry.GEN.render_prometheus(),
                content_type="text/plain",
            )
        # engine.stats lookups go through _stat so a stats-key rename
        # degrades a counter to 0 instead of 500ing the whole scrape — but
        # every degraded lookup is counted (areal_gen_stats_key_misses_total)
        # so the drift is visible on the Prometheus surface (ISSUE 18)
        stats = self.engine.stats

        def _stat(key: str):
            if key not in stats:
                telemetry.GEN_STATS_KEY_MISSES.inc()
            return stats.get(key, 0)

        return web.json_response(
            {
                "decode_steps": self.step_count,
                "tokens_generated": self.tokens_out,
                "active": self.engine.active_count(),
                "role": self.role,
                "version": self.engine.version,
                # achieved generation-idle window of the last weight swap
                "last_pause_s": round(self.engine.last_pause_s, 4),
                "staged": self.engine.has_standby,
                # prefill-side token accounting: cold vs retained-reuse vs
                # group fan-out (shared) — the grouped-prefill savings
                "prefill_tokens": _stat("prefill_tokens"),
                "suffix_tokens": _stat("suffix_tokens"),
                "reused_tokens": _stat("reused_tokens"),
                "shared_tokens": _stat("shared_tokens"),
                "copy_calls": _stat("copy_calls"),
                # abort-reservation TTL observability (VERDICT r6 #10):
                # reservations that expired unclaimed — nonzero means
                # aborted clients are not resubmitting within
                # abort_reserve_s and the retained-prefix handoff is
                # silently degrading to fresh prefills
                "reservations_lapsed": _stat("reservations_lapsed"),
                # tiered decode (ISSUE 5): attended span / configured
                # ceiling over all decode dispatches (1.0 = paying the
                # full max_seq_len width), per-cohort occupancy, and
                # cross-tier cache-row migrations
                "decode_attended_fraction": round(
                    self.engine.decode_attended_fraction(), 4
                ),
                "tier_occupancy": self.engine.tier_occupancy(),
                "tier_slots": list(self.engine.tier_size),
                "tier_lens": list(self.engine.tier_bounds),
                "tier_migrations": _stat("tier_migrations"),
                # speculative decode (ISSUE 12): draft/accept counters and
                # the lifetime acceptance rate; per-tier windowed rates
                # live on the Prometheus surface (spec_acceptance_rate)
                "spec_drafted": _stat("spec_drafted"),
                "spec_accepted": _stat("spec_accepted"),
                "spec_acceptance_rate": round(
                    _stat("spec_accepted")
                    / max(1, _stat("spec_drafted")),
                    4,
                ),
                "verify_calls": _stat("verify_calls"),
                # unified radix/paged prefix cache (ISSUE 16): admission
                # hits/misses through the one shared mechanism, device
                # evictions, and host-DRAM spill/swap-in round trips
                "prefix_cache_hits": _stat("prefix_cache_hits"),
                "prefix_cache_misses": _stat("prefix_cache_misses"),
                "prefix_cache_evictions": _stat("prefix_cache_evictions"),
                "prefix_cache_host_swaps": _stat("prefix_cache_host_swaps"),
                "prefix_cache_hit_rate": round(
                    self.engine.prefix_cache_hit_rate(), 4
                ),
                "prefix_cache_partial_hits": _stat("prefix_cache_partial_hits"),
                # disaggregated prefill/decode handoff (ISSUE 17): the
                # router's decode-pool placement reads tier_occupancy
                # above; these counters are the transfer ledger
                "kv_handoff_exports": _stat("kv_handoff_exports"),
                "kv_handoff_imports": _stat("kv_handoff_imports"),
                "kv_handoff_bytes": _stat("kv_handoff_bytes"),
                "kv_handoff_failures": _stat("kv_handoff_failures"),
                # ragged paged-decode attention (ISSUE 19): collapsed
                # grid-wide kernel dispatches and the page-granular read
                # ledger (pages actually gathered, slots x steps)
                "ragged_dispatches": _stat("ragged_dispatches"),
                "ragged_attended_pages": _stat("ragged_attended_pages"),
                # the chip this process holds (one process per chip):
                # platform, kind, count, peak_bytes_in_use
                "device": device_report(),
            }
        )

    # ------------------------------ wiring ------------------------------

    def app(self) -> web.Application:
        app = web.Application(client_max_size=1024**3)
        app.router.add_post("/generate", self.generate)
        app.router.add_post("/generate_batch", self.generate_batch)
        app.router.add_post("/pause_generation", self.pause)
        app.router.add_post("/continue_generation", self.resume)
        app.router.add_post("/update_weights_from_disk", self.update_weights_from_disk)
        app.router.add_post("/update_weights_chunk", self.update_weights_chunk)
        app.router.add_post("/kv_export", self.kv_export)
        app.router.add_post("/kv_import", self.kv_import)
        app.router.add_get("/health", self.health)
        app.router.add_get("/metrics", self.metrics)
        return app




def serve(
    engine: GenEngine,
    host: str = "0.0.0.0",
    port: Optional[int] = None,
    experiment_name: str = "",
    trial_name: str = "",
    server_idx: int = 0,
    role: str = "both",
):
    """Blocking serve; registers the address in name_resolve for discovery
    (reference: sglang_server.py registration)."""
    port = port or network.find_free_port()
    server = GenServer(engine, role=role)
    server.start()
    if experiment_name:
        name_resolve.add(
            names.gen_server(experiment_name, trial_name, str(server_idx)),
            f"{network.gethostip()}:{port}",
            replace=True,
        )
    logger.info(f"generation server on {host}:{port}")
    web.run_app(server.app(), host=host, port=port, print=None)


def main():
    # multi-host launchers point every process at a shared rendezvous store
    name_resolve.reconfigure_from_env()
    p = argparse.ArgumentParser()
    p.add_argument("--model-path", default="")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--n-slots", type=int, default=8)
    p.add_argument("--max-seq-len", type=int, default=2048)
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel degree: shard the model + KV cache "
                        "over the first tp local devices")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel degree (MoE serving): shard the "
                        "[E, ., .] expert leaves over ep devices")
    p.add_argument("--experiment-name", default="")
    p.add_argument("--trial-name", default="")
    p.add_argument("--server-idx", type=int, default=0)
    p.add_argument("--no-decode-window", action="store_true",
                   help="disable the bucketed decode key window (attend "
                        "the full max-seq-len cache width — the legacy "
                        "ceiling-bound behavior)")
    p.add_argument("--decode-tiers", type=int, default=1,
                   help="number of length-cohort slot tiers; >1 keeps one "
                        "long rollout from inflating the short cohort's "
                        "attended window")
    p.add_argument("--decode-tier-lens", default="",
                   help="explicit per-tier length ceilings (comma list, "
                        "ascending; overrides --decode-tiers)")
    p.add_argument("--decode-tier-slots", default="",
                   help="explicit per-tier slot counts (comma list, must "
                        "sum to --n-slots)")
    p.add_argument("--spec-decode", action="store_true",
                   help="self-speculative decoding: prompt-lookup drafts "
                        "verified in one dispatch per tier; output streams "
                        "stay bit-identical to plain decode")
    p.add_argument("--spec-ladder", default="",
                   help="static draft-length ladder (comma list incl. 0, "
                        "e.g. '0,3,7'); each nonzero rung is its own "
                        "verify program per (tier, K) bucket")
    p.add_argument("--spec-draft-len", type=int, default=0,
                   help="pin the draft length instead of adapting along "
                        "the ladder (benches/tests)")
    p.add_argument("--ragged-attn", dest="ragged_attn", action="store_const",
                   const=True, default=None,
                   help="paged decode attention (ISSUE 19): one Pallas "
                        "kernel dispatch covers the whole slot grid and "
                        "reads each slot's pages through the KV page "
                        "table, instead of copying every tier's key window "
                        "out of the cache; output streams are bit-identical "
                        "either way (a slot of latent rows has a paged "
                        "kernel of its own, equal to float32 rounding).  "
                        "Neither flag: the engine takes the "
                        "kernel wherever it applies.  --ragged-attn "
                        "requires it (an error at start-up when the "
                        "per-slot window exceeds the kernel VMEM budget)")
    p.add_argument("--no-ragged-attn", dest="ragged_attn",
                   action="store_const", const=False,
                   help="the copy path, whatever the engine could take")
    p.add_argument("--role", choices=("prefill", "decode", "both"),
                   default="both",
                   help="disaggregated-fleet role advertised to the "
                        "router: prefill servers take admissions and "
                        "export KV via /kv_export, decode servers import "
                        "via /kv_import and continue the stream; `both` "
                        "is the colocated default and the router's "
                        "fallback when a role pool is empty")
    p.add_argument("--host-offload", action="store_true",
                   help="spill evicted retained prefixes to a host-DRAM "
                        "LRU tier and swap them back on radix hits")
    p.add_argument("--host-cache-mb", type=int, default=64,
                   help="host-DRAM overflow tier capacity in MiB "
                        "(with --host-offload)")
    p.add_argument("--telemetry", action="store_true",
                   help="enable trajectory-lifecycle event emission "
                        "(utils/telemetry.py; also via AREAL_TELEMETRY=1)")
    args = p.parse_args()
    enable_compile_cache()
    if args.telemetry:
        telemetry.set_enabled(True)
    if args.role == "decode" and not args.host_offload:
        # a decode-role server receives its work as /kv_import host-tier
        # entries; without the tier every import would 409
        logger.info("--role decode implies --host-offload; enabling it")
        args.host_offload = True
    tier_kw = dict(
        decode_window=not args.no_decode_window,
        decode_tiers=args.decode_tiers,
        decode_tier_lens=(
            [int(x) for x in args.decode_tier_lens.split(",")]
            if args.decode_tier_lens else None
        ),
        decode_tier_slots=(
            [int(x) for x in args.decode_tier_slots.split(",")]
            if args.decode_tier_slots else None
        ),
        spec_decode=args.spec_decode,
        spec_ladder=(
            [int(x) for x in args.spec_ladder.split(",")]
            if args.spec_ladder else None
        ),
        spec_draft_len=args.spec_draft_len or None,
        host_offload=args.host_offload,
        host_cache_mb=args.host_cache_mb,
        ragged_attn=args.ragged_attn,
    )
    if args.model_path:
        cfg = TransformerConfig.from_hf(args.model_path)
        engine = GenEngine(
            cfg.replace(dtype="bfloat16"),
            model_path=args.model_path,
            n_slots=args.n_slots,
            max_seq_len=args.max_seq_len,
            tp=args.tp,
            ep=args.ep,
            **tier_kw,
        )
    else:
        engine = GenEngine(tiny_config(), n_slots=args.n_slots,
                           max_seq_len=args.max_seq_len, tp=args.tp,
                           ep=args.ep, **tier_kw)
    serve(
        engine,
        port=args.port or None,
        experiment_name=args.experiment_name,
        trial_name=args.trial_name,
        server_idx=args.server_idx,
        role=args.role,
    )


if __name__ == "__main__":
    main()
