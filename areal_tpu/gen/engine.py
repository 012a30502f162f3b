"""Continuous-batching generation engine on a fixed slot grid.

The TPU-native replacement for the SGLang/vLLM servers the reference wraps
(areal/launcher/sglang_server.py:117, realhf generation servers) and for the
legacy native decode loop (realhf/impl/model/nn/real_llm_generate.py).
Design for XLA's static shapes:

- `n_slots` concurrent sequences in a preallocated KV cache
  [L, S, M, Hkv, hd]; admission assigns a free slot, completion frees it —
  continuous batching without shape changes.
- TWO compiled programs: `forward_prefill` per (rows, prompt-bucket) pair
  (both power-of-two padded) and ONE `forward_decode` step advancing every
  slot; idle slots decode garbage that is never read (cheaper than
  recompiling for occupancy).
- **Batched admission**: every free slot is filled from the pending queue in
  ONE prefill call (rows padded to a power of two, dummy rows target a
  scratch cache slot) — a burst of N prompts costs O(log N) device
  round-trips, not N.
- **Model-parallel serving**: with `tp > 1` the engine owns a
  (dp=1, fsdp=1, sp=1, tp) mesh; params shard with the same
  `param_partition_specs` the trainer uses (megatron column/row layout) and
  the KV cache shards its kv-head axis, so a 7B model serves across chips
  the way the reference serves via SGLang's server-side tp
  (areal/api/alloc_mode.py:377 inference d x t x p).
- Cache and rng are donated; steady-state decode allocates nothing.
- Weight reload (`load_weights`) aborts in-flight requests with
  stop_reason="abort" — the client's interruption loop resubmits with
  accumulated tokens (reference behavior: remote_inf_engine.py:428-478) —
  then bumps `version`; per-token versions let decoupled PPO weight stale
  spans correctly.
- **KV prefix reuse** (VERDICT r3 #3): freed slots retain their cache and
  token history; admission matches each prompt against retained prefixes
  (longest common prefix) and prefills only the suffix via
  `forward_prefill_cached` — so an interruption resume or a multi-turn
  agentic turn pays O(new tokens), not O(context).  This is the in-engine
  counterpart of the radix-cache reuse the reference inherits from SGLang
  (areal/core/remote_inf_engine.py:404-413 rid->server affinity exists to
  exploit it; our router preserves the same affinity).  Reuse across a
  weight reload keeps old-policy KV behind new-policy decoding — exactly
  the mixed-version trajectory regime decoupled PPO + per-token versions
  are built for; `engine.retain_kv_on_reload = False` is strict recompute.
- **Abort-storm discipline** (VERDICT r4 #3): admission drains a window of
  the pending queue and prefix-matches it against every free slot globally
  (highest lcp first) before fresh prompts get slots, and abort-freed
  slots carry a short reservation (`abort_reserve_s`) that withholds them
  from fresh prompts until their aborted owner has had an RTT to
  resubmit — so a publish that aborts N in-flight requests over few slots
  no longer hands the retained prefixes to whoever arrives first.
- **Group fan-out prefill** (ISSUE 2): GRPO samples every group as
  `group_size` requests over the SAME prompt, and per-slot retained reuse
  can serve at most one of them — the other G-1 used to pay a full
  redundant prefill.  Admission now clusters its window by longest common
  prefix (explicit `group_id` groups first, content-discovered clusters
  second), prefills ONE representative per cluster, fans the computed
  prefix K/V out to sibling slots with a batched device-side cache copy
  (ops/kv_copy.py — bucketed lengths, no new compile signatures in steady
  state), and suffix-prefills only each sibling's remainder.  When a free
  slot's retained cache already covers the cluster prefix (multi-turn),
  the representative rides THAT via suffix prefill and nobody recomputes
  the prefix at all.  `seq_tokens`/`kv_version` bookkeeping make shared
  prefixes compose with the live weight swap exactly like retained ones
  (strict mode zeroes both).  This is the in-engine counterpart of
  SGLang's RadixAttention / vLLM's shared PagedAttention blocks.
- **Tiered decode** (ISSUE 5): decode used to attend over the full
  `max_seq_len` cache width for every slot on every step, so steady-state
  cost scaled with the configured ceiling, not with what slots hold.  Now
  every decode dispatch carries a STATIC bucketed `key_window` K (the
  same pow2 ladder as prompt buckets — zero new XLA signatures in steady
  state) bounding attention reads, masks, and the cache write to the
  occupied span.  Because one long slot would inflate K for the whole
  grid, the slot grid partitions into **length-cohort tiers** — static
  contiguous slot blocks, `decode_tiers`/`decode_tier_lens`/
  `decode_tier_slots` — and `step()` runs one decode dispatch per
  non-empty tier with that tier's own K.  Admission places requests by
  prompt + `max_new_tokens` budget; a slot that outgrows its cohort
  mid-generation migrates to a roomier tier via a device-side cache-row
  copy (ops/kv_copy.py) or, when nothing is free, simply grows its own
  tier's K bucket (ceilings are placement hints, never correctness).
  Decode sampling is counter-keyed per slot (fold(decode_key, stream_id,
  position)) so the token streams are bit-identical however the grid is
  partitioned — the tiered-vs-untiered parity contract.  `lengths`,
  `rope_pos`, `last_tokens` and the sampling params live device-resident
  between chunks (host mirrors kept for bookkeeping; re-synced only when
  admission/free/migration dirties them).  This is the slot-grid analogue
  of vLLM's block-granular PagedAttention and Sarathi-Serve's principle
  that steady-state serving cost should track occupied context.
- **Unified radix/paged KV pool** (ISSUE 16, gen/kv_pool.py): the prefix
  mechanisms above used to keep separate lookup state; now ONE structure
  fronts them all.  A page table maps logical slots to physical cache
  rows and every compiled decode/verify program reads the cache THROUGH
  it (models/transformer.py `rows=`), so a tier migration is an O(1)
  host-side row remap — the old device-side migration copy is gone, and
  the displaced retained prefix survives at the vacated logical slot
  instead of being overwritten.  A compressed radix tree indexes every
  resident prefix (device-retained and host-spilled alike); admission
  matching, fan-out representatives, and failover resubmits all hit
  through one exact-lcp walk.  An optional LRU host-DRAM overflow tier
  (`host_offload`) spills about-to-be-overwritten prefixes via bucketed
  device->host gathers (ops/kv_copy.py) and swaps them back on a radix
  hit — bit-identical round trip, so token streams are invariant to
  spill scheduling.  Lookups stay host-side and block shapes ride the
  existing bucket ladders: steady state still mints zero XLA programs.
"""

# areal-lint: hot-path
import functools
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from areal_tpu.analysis.lockcheck import lock_guarded

from areal_tpu.gen.sampling import sample_tokens, sample_tokens_keyed
from areal_tpu.gen.spec import (
    DEFAULT_SPEC_LADDER,
    SpecController,
    propose_draft,
)
from areal_tpu.gen.kv_pool import KVPool, lcp_ids
from areal_tpu.models.model_config import TransformerConfig
from areal_tpu.ops.kv_copy import gather_kv_prefix, scatter_kv_prefix
from areal_tpu.models.transformer import (
    forward_prefill,
    forward_prefill_cached,
    forward_verify,
    COLUMN_LEAVES as _COLUMN_LEAVES,
    init_kv_cache,
    init_params,
    kv_cache_partition_specs,
    param_partition_specs,
    slot_kind,
)
from areal_tpu.models.hf import load_hf_params
from areal_tpu.parallel import build_mesh, shard_pytree
from areal_tpu.utils import logging, telemetry
from areal_tpu.utils.datapack import round_up_to_bucket

logger = logging.getLogger("gen.engine")

# a retained or shared prefix shorter than this is computed again: the
# suffix program costs more than it saves
REUSE_MIN_TOKENS = 16
# how long a slot freed by an abort is withheld from fresh prompts, so that
# its owner's resubmission (one round trip away) finds its prefix
ABORT_RESERVE_S = 1.0
# how long a declared group waits for its missing members before the
# members that came are admitted without them
GROUP_HOLD_S = 0.05
# a prefix shorter than this is not worth a round trip to the host tier
HOST_MIN_TOKENS = 32


def plan_decode_tiers(
    n_slots: int,
    max_seq_len: int,
    n_tiers: int,
    quantum: int = 128,
) -> tuple:
    """Default length-cohort layout: (tier ceilings, slots per tier).

    Ceilings double up to `max_seq_len` (each at least 2 x quantum so the
    lowest cohort still spans a few buckets); slot counts halve away from
    tier 0 — the short cohort is where most rollouts live — with the last
    two tiers equal so the counts sum exactly:
        n_slots=64, n_tiers=3, max=16384 -> lens (4096, 8192, 16384),
        slots (32, 16, 16).
    """
    if n_tiers <= 1:
        return [max_seq_len], [n_slots]
    if n_slots >> (n_tiers - 1) < 1:
        raise ValueError(
            f"decode_tiers={n_tiers} needs n_slots >= {1 << (n_tiers - 1)}"
        )
    slots = [n_slots >> (i + 1) for i in range(n_tiers - 1)]
    slots.append(n_slots - sum(slots))  # tier 0 largest block
    lens = [
        max(2 * quantum, max_seq_len >> (n_tiers - 1 - i))
        for i in range(n_tiers)
    ]
    lens[-1] = max_seq_len
    return lens, slots


@dataclass
class GenRequest:
    rid: str
    input_ids: List[int]
    max_new_tokens: int = 256
    min_new_tokens: int = 0
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0
    stop_token_ids: List[int] = field(default_factory=list)
    # vision inputs (VLM serving): pre-patchified pixels in image order and
    # the per-image (t, h, w) patch grids — the AutoProcessor wire format
    pixel_values: Optional["np.ndarray"] = None  # [N, patch_dim]
    image_grid_thw: Optional["np.ndarray"] = None  # [n_img, 3]
    # group fan-out: siblings sampling the same prompt (a GRPO group) carry
    # a shared affinity key + the expected group size, so admission can
    # hold for the full group, cluster it in one window, and the router can
    # keep the members on one replica (the KV prefix is only shareable
    # within one engine's cache)
    group_id: str = ""
    group_n: int = 0
    # telemetry (utils/telemetry.py): trajectory trace id carried from the
    # wire + the submit() timestamp backing the admission-wait histogram;
    # first_token_ts/finish_ts complete the per-request latency triple
    # (TTFT / end-to-end / inter-token) on the same perf_counter clock
    trace_id: str = ""
    submit_ts: float = 0.0
    first_token_ts: float = 0.0
    finish_ts: float = 0.0
    # filled by the engine
    output_tokens: List[int] = field(default_factory=list)
    output_logprobs: List[float] = field(default_factory=list)
    output_versions: List[int] = field(default_factory=list)
    stop_reason: str = ""
    # prompt tokens inherited from the unified prefix cache at admission
    # (retained reuse, fan-out share, or host swap-in) — surfaced on the
    # wire so a failover resubmit can prove its radix warm start
    cache_hit_tokens: int = 0
    # sampler stream override (disaggregated handoff): 0 means "allocate a
    # fresh stream at admission" (the normal path); nonzero pins the
    # counter-keyed sampler stream so a decode-role server continues a
    # prefill-role server's token stream bit-identically — the per-token
    # key is fold(fold(decode_key, stream_id), position), a pure function
    # of data that rides the wire
    stream_id: int = 0
    on_done: Optional[Callable[["GenRequest"], None]] = None

    def finish(self, reason: str):
        self.stop_reason = reason
        self.finish_ts = time.perf_counter()
        if self.on_done is not None:
            self.on_done(self)


@lock_guarded
class GenEngine:
    # lock-discipline contract (areal-lint C1; runtime-validated under
    # AREAL_DEBUG_LOCKS=1): the worker thread and control threads (abort,
    # weight publish) hand requests across these fields, so every touch
    # must hold _lock.  The tiered-decode state joined the contract in
    # ISSUE 9: _dev_state/_state_dirty are the device mirror handoff
    # (abort/free/admission dirties them from control threads while the
    # decode loop consumes them) and _next_stream is the stream-id
    # allocator shared by all admission paths.  Slot arrays (slot_req,
    # lengths, retained_len, ...) are worker-owned between the documented
    # lock sections and stay outside the contract.
    _GUARDED_FIELDS = {
        "_holdback": "_lock",
        "_abort_gen": "_lock",
        "_state_dirty": "_lock",
        "_dev_state": "_lock",
        "_next_stream": "_lock",
    }

    # slot lifecycle automaton (areal-lint C7): slot s is owned iff
    # slot_req[s] is not None; an acquire must settle every per-slot
    # array below for the same index in the same block (or via a helper
    # whose transitive write set covers it); a release must settle the
    # retained prefix length; _reserved_until/kv_version/_slot_vlm remain
    # writable on freed slots (abort reservations, migration sources).
    _SLOT_TYPESTATE = {
        "owner": "slot_req",
        "acquire_writes": [
            "lengths",
            "rope_pos",
            "last_tokens",
            "temperature",
            "top_p",
            "top_k",
            "retained_len",
            "_reserved_until",
            "kv_version",
            "stream_ids",
        ],
        "release_writes": ["_reserved_until", "kv_version", "_slot_vlm"],
        "version_field": "kv_version",
        "retained_field": "retained_len",
    }

    def __init__(
        self,
        model_config: TransformerConfig,
        params=None,
        model_path: Optional[str] = None,
        n_slots: int = 8,
        max_seq_len: int = 2048,
        prompt_bucket: int = 128,
        kv_dtype: str = "bfloat16",
        seed: int = 0,
        decode_chunk: int = 8,
        tp: int = 1,
        ep: int = 1,
        devices=None,
        share_prefix: bool = True,
        decode_window: bool = True,
        decode_tiers: int = 1,
        decode_tier_lens: Optional[List[int]] = None,
        decode_tier_slots: Optional[List[int]] = None,
        spec_decode: bool = False,
        spec_ladder: Optional[List[int]] = None,
        spec_draft_len: Optional[int] = None,
        host_offload: bool = False,
        host_cache_mb: int = 64,
        ragged_attn: Optional[bool] = None,
    ):
        self.model_config = model_config.replace(remat=False)
        # what a slot holds is the model kind's to say (`models/transformer.py
        # SlotKind`): columns of keys and values, a recurrent state of fixed
        # size, both, or latent rows.  An option built on what the kind
        # lacks is refused by name, never ignored, and before any weight is
        # drawn or read
        self._kind = kind = slot_kind(self.model_config)
        several_tiers = decode_tiers > 1 or len(decode_tier_slots or ()) > 1
        refused: Dict[str, List[str]] = {}
        for name, on, capability in (
            ("model_config", True, "generate"),
            ("spec_decode", spec_decode, "verify"),
            ("host_offload", host_offload, "host_tier"),
            ("decode_tiers > 1", several_tiers, "tiers"),
            (f"tp={tp}", tp > 1, "tp"),
            (f"ep={ep}", ep > 1, "ep"),
            ("a vision tower", self.model_config.vision is not None, "vision"),
        ):
            if on and capability in kind.lacks:
                refused.setdefault(kind.lacks[capability], []).append(name)
        if refused:
            raise ValueError("; ".join(
                f"{', '.join(names)}: {why}" for why, names in refused.items()
            ))
        if params is None:
            if model_path:
                host, mc = load_hf_params(model_path, model_config, dtype="bfloat16")
                self.model_config = mc.replace(
                    dtype=model_config.dtype, param_dtype="bfloat16", remat=False
                )
                params = host
            else:
                params = init_params(self.model_config, jax.random.PRNGKey(seed))
        # a ring of a sliding layer's window follows a recurrent state's
        # rule: valid at the length it was taken at, copied whole
        self._window = "window" in kind.holds
        self._holds_state = "state" in kind.holds
        self._state = self._holds_state or self._window
        # latent rows are columns too: one a position, reused, copied and
        # exported by position as keys and values are
        self._latent = "latent" in kind.holds
        self._columns = "kv" in kind.holds or self._latent
        # a cache that makes contexts of `max_seq_len` servable: a fresh
        # prefill dispatch takes ONE row whatever its bucket, a suffix
        # dispatch eight rows (`_admit_fresh_batch`, `_admit_suffix_batch`)
        self._long_rows = self._latent or self._window
        if tp > 1 and self.model_config.num_kv_heads % tp != 0:
            raise ValueError(
                f"tp={tp} must divide num_kv_heads="
                f"{self.model_config.num_kv_heads} (kv-head-sharded cache)"
            )
        if ep > 1 and (
            self.model_config.num_experts == 0
            or self.model_config.num_experts % ep != 0
        ):
            raise ValueError(
                f"ep={ep} needs a MoE model with num_experts divisible by it "
                f"(num_experts={self.model_config.num_experts})"
            )
        # serving mesh: tensor + expert parallel — dp across servers is the
        # client's job (core/remote.py multi-server routing), so the mesh
        # reuses the trainer's partition specs with dp=fsdp=sp=1.  ep>1
        # shards the [E, ., .] expert leaves (the reference's inference-side
        # expert dims, alloc_mode.py:80-117); without it a large MoE's
        # experts are replicated per server and don't fit.
        self.mesh = build_mesh(dp=1, fsdp=1, sp=1, tp=tp, ep=ep, devices=devices)
        self._pspecs = param_partition_specs(self.model_config, tp=tp)
        if self.model_config.vision is not None:
            # VLM: materialise a scratch tower if the checkpoint lacks one
            # (mirrors JaxVLMEngine.initialize) and replicate it — the tower
            # is small relative to the decoder
            from areal_tpu.models.vision import init_vision_params

            params = dict(params)
            if "vision" not in params:
                logger.warning(
                    "VLM config but the checkpoint has no visual.* weights; "
                    "initialising a RANDOM vision tower — image-conditioned "
                    "outputs will be noise until real weights are loaded"
                )
                params["vision"] = init_vision_params(
                    self.model_config.vision, jax.random.PRNGKey(seed + 1)
                )
            self._pspecs = dict(self._pspecs)
            self._pspecs["vision"] = jax.tree_util.tree_map(
                lambda _: P(), params["vision"]
            )
        self.params = shard_pytree(self.mesh, params, self._pspecs)
        self.n_slots = n_slots
        if (
            self.model_config.pos_emb == "learned"
            and max_seq_len > self.model_config.max_position_embeddings
        ):
            # jnp.take clamps out-of-range rows, so positions past the table
            # would silently reuse the last embedding — fail loudly instead
            raise ValueError(
                f"max_seq_len {max_seq_len} exceeds the learned position "
                f"table ({self.model_config.max_position_embeddings}); "
                "gpt2-family models cannot extrapolate positions"
            )
        self.max_seq_len = max_seq_len
        self.prompt_bucket = prompt_bucket
        self.kv_dtype = kv_dtype
        # slot n_slots is the scratch row: dummy admission rows (power-of-two
        # padding) prefill into it, and decode advances it harmlessly
        self._cache_shardings = {
            k: NamedSharding(self.mesh, spec)
            for k, spec in kv_cache_partition_specs(self.model_config).items()
        }
        self.cache = init_kv_cache(
            self.model_config, n_slots + 1, max_seq_len, kv_dtype,
            shardings=self._cache_shardings,
        )
        # bytes a fan-out copy moves: a slot's recurrent state (with a
        # hybrid's convolution windows) whole, and one position's keys and
        # values over the attention blocks (or its latent rows over the
        # sublayers) for each position shared
        self._state_bytes = sum(
            int(a.nbytes) // (n_slots + 1)
            for name, a in self.cache.items() if name not in _COLUMN_LEAVES
        )
        self._kv_token_bytes = sum(
            int(a.nbytes) // ((n_slots + 1) * max_seq_len)
            for name, a in self.cache.items() if name in _COLUMN_LEAVES
        )
        self.rng = jax.random.PRNGKey(seed)
        self.version = 0
        self._standby = None  # (sharded tree, version) pre-staged weights
        self.last_pause_s = 0.0  # achieved generation-idle window
        # >0 while inside a compound pause entry point (load_weights /
        # commit_staged): the nested swap tail must not double-record its
        # sub-window into the pause histogram
        self._pause_depth = 0

        # host-side slot state (scratch slot included, never assigned)
        S = n_slots + 1
        self.slot_req: List[Optional[GenRequest]] = [None] * S
        self.lengths = np.zeros(S, np.int32)
        # logical rope position per slot; equals lengths for text slots,
        # trails it for VLM slots (mrope compresses image placeholder runs)
        self.rope_pos = np.zeros(S, np.int32)
        self.last_tokens = np.zeros(S, np.int32)
        self.temperature = np.ones(S, np.float32)
        self.top_p = np.ones(S, np.float32)
        self.top_k = np.zeros(S, np.int32)
        self.pending: "queue.Queue[GenRequest]" = queue.Queue()
        self._lock = threading.Lock()

        # KV prefix reuse: freed slots keep their cache; seq_tokens mirrors
        # each slot's cache content (prompt + generated, the pending
        # last_token included) so admission can prefix-match against it
        self.kv_reuse = True
        self.reuse_min_tokens = REUSE_MIN_TOKENS
        self.retain_kv_on_reload = True
        self.seq_tokens = np.zeros((S, max_seq_len), np.int32)
        self.retained_len = np.zeros(S, np.int32)  # cache-valid prefix (free slots)
        # power retention: tokens the slot's state has taken in, as the
        # host knows it (prefill, then every dispatched decode step).  A
        # state that ran past the host's `lengths` (a stop inside a chunk,
        # an abort with a chunk in flight) cannot be cut back, so the slot
        # retains nothing
        self._state_len = np.zeros(S, np.int64)
        # ... and the most padded tokens (rows x bucket) one prefill dispatch
        # takes, None: one dispatch whatever its size
        self._state_admit_tokens = kind.admit_tokens(
            self.model_config, max_seq_len
        )
        # members of a declared group admitted so far: a later one that
        # has to compute the whole prompt again is a `sibling_reprefill`
        self._group_admitted: Dict[str, int] = {}
        # abort-storm protection (VERDICT r4 #3): slots freed by an abort
        # keep a short reservation so a fresh prompt arriving before the
        # aborted request's resubmission cannot overwrite its retained
        # prefix; admission also scans a WINDOW of the pending queue and
        # prefix-matches globally before handing any slot to a fresh prompt
        self.abort_reserve_s = ABORT_RESERVE_S
        self.admission_window = max(64, 4 * n_slots)
        # the lcp scan is O(window x slots x prefix); cap how much of the
        # drain window it touches independently of the drain size so large
        # slot grids do not pay the full quadratic host cost per pass
        self.match_window = max(64, 2 * n_slots)
        # cross-slot prefix sharing (group fan-out prefill)
        self.share_prefix = share_prefix
        self.share_min_tokens = REUSE_MIN_TOKENS
        self.group_hold_s = GROUP_HOLD_S
        self._group_first_seen: Dict[str, float] = {}
        # bumped by abort_all so an _admit pass that raced it can tell its
        # drained-but-unadmitted requests were already terminally finished
        self._abort_gen = 0
        self._reserved_until = np.zeros(S, np.float64)
        self._holdback: List[GenRequest] = []  # drained but not yet admitted
        # no-progress guard: a pass that parked everything records the slot
        # set + earliest reservation expiry so subsequent steps skip the
        # O(window x slots) rescan until something can actually change
        self._parked_free: Optional[frozenset] = None
        self._parked_until: float = 0.0
        self._slot_vlm = np.zeros(S, bool)  # VLM slots never reuse (mrope)
        # --- unified radix/paged KV pool (ISSUE 16) --------------------
        # page-table indirection (logical slot -> physical cache row, read
        # by every decode/verify dispatch), a radix tree over all resident
        # prefixes (one exact-lcp match serves retained reuse, fan-out,
        # and failover resubmits), and the optional LRU host-DRAM
        # overflow tier.  Prefixes shorter than host_min_tokens are not
        # worth a device<->host round trip and just evict.
        self.host_min_tokens = HOST_MIN_TOKENS
        self.pool = KVPool(
            n_slots,
            host_bytes=(int(host_cache_mb) << 20) if host_offload else 0,
        )
        # --- tiered decode (ISSUE 5) -----------------------------------
        # length-cohort tiers: contiguous slot blocks [tier_start[t],
        # tier_start[t] + tier_size[t]) with ascending ceilings
        # tier_bounds[t] (the last always max_seq_len).  Ceilings steer
        # admission placement and migration; correctness never depends on
        # them — a cohort outlier just grows its own tier's K bucket.
        self.decode_window = decode_window and "window" not in kind.lacks
        if decode_tier_lens is not None or decode_tier_slots is not None:
            if not (decode_tier_lens and decode_tier_slots):
                raise ValueError(
                    "decode_tier_lens and decode_tier_slots come together"
                )
            if len(decode_tier_lens) != len(decode_tier_slots):
                raise ValueError("tier lens/slots length mismatch")
        else:
            decode_tier_lens, decode_tier_slots = plan_decode_tiers(
                n_slots, max_seq_len, max(1, decode_tiers), prompt_bucket
            )
        if sum(decode_tier_slots) != n_slots:
            raise ValueError(
                f"decode_tier_slots {decode_tier_slots} must sum to "
                f"n_slots={n_slots}"
            )
        if list(decode_tier_lens) != sorted(decode_tier_lens):
            raise ValueError("decode_tier_lens must ascend")
        self.tier_bounds = [
            min(int(b), max_seq_len) for b in decode_tier_lens
        ]
        self.tier_bounds[-1] = max_seq_len
        self.tier_size = [int(c) for c in decode_tier_slots]
        self.tier_start = list(np.cumsum([0] + self.tier_size[:-1]))
        self.n_tiers = len(self.tier_size)
        self.slot_tier = np.zeros(S, np.int32)
        for t in range(self.n_tiers):
            lo = self.tier_start[t]
            self.slot_tier[lo : lo + self.tier_size[t]] = t
        self.slot_tier[n_slots] = self.n_tiers - 1  # scratch: never decoded
        # decode sampling is counter-keyed: key = fold(fold(_decode_key,
        # stream_id), cache position).  stream_ids are assigned at
        # admission in arrival order — identical however the grid is
        # tiered — so token streams are partition-invariant AND fresh per
        # request (no gumbel-noise reuse across requests in one slot).
        self._decode_key = jax.random.fold_in(jax.random.PRNGKey(seed), 0xD)
        self.stream_ids = np.zeros(S, np.int32)
        self._next_stream = 1
        # device-resident decode state (tokens/lengths/rope_pos/active/
        # sampling params): uploaded only when host bookkeeping diverges
        # (admission, free, migration, abort) — steady-state chunks flow
        # device->device with zero uploads
        self._dev_state: Optional[Dict[str, Any]] = None
        self._state_dirty = True
        # the in-flight ledger (`_launched`, `_landed`, `_not_starved`): the
        # newest program handed to the device, the newest one a download has
        # proven finished, and since when the two are equal while the engine
        # has work (None: something is queued, or nothing is wanted); and
        # when the last step() that left work behind returned
        self._n_launched = 0
        self._n_landed = 0
        self._starved_since: Optional[float] = None
        self._step_returned: Optional[float] = None
        # --- self-speculative decode (ISSUE 12) ------------------------
        # Prompt-lookup drafting + one-dispatch verification.  D rides a
        # small STATIC ladder (each nonzero rung is its own verify program
        # per (tier, K) — budgeted in analysis/signature_budget.json);
        # spec_draft_len pins D for benches/tests, otherwise the
        # per-tier acceptance-rate controller adapts along the ladder.
        # Correctness never depends on any of this: verification samples
        # every position under the SAME counter-keyed PRNG plain decode
        # would use, so the output stream is bit-identical for any D.
        self.spec_decode = spec_decode
        if spec_draft_len is not None:
            if spec_draft_len <= 0:
                raise ValueError("spec_draft_len must be positive")
            self.spec_ladder = (0, int(spec_draft_len))
        else:
            self.spec_ladder = tuple(
                sorted(set(int(d) for d in (spec_ladder or DEFAULT_SPEC_LADDER)))
            )
        self.spec_draft_len = spec_draft_len
        self._spec = SpecController(ladder=self.spec_ladder)
        self._spec_max_d = max(self.spec_ladder)
        # per-tier D chosen for the CURRENT step — a self attr so the
        # dispatch site's static arg is provably on the configured ladder
        # (areal-lint C6 value lattice: self.<attr> is engine config)
        self._spec_tier_d: Dict[int, int] = {}
        # --- ragged paged-decode attention (ISSUE 19) -------------------
        # Every decode/verify step is ONE grid-wide dispatch whose Pallas
        # kernel reads each slot's occupied pages through the page table,
        # instead of a dispatch a tier that copies the tier's whole key
        # window out of the cache first (bit-identical streams; tiers
        # remain as admission/migration placement policy).  Nobody said
        # (None): the engine takes the kind's kernel wherever it applies
        # (`SlotKind.kernel_refusal`: the pool's widths and dtype, the
        # window, a backend the kernel runs on; a kind without one says so);
        # otherwise the copy path, without a word.  True requires it (a
        # kernel that cannot be honoured is an error, not a quiet
        # downgrade), False is the copy path.  Resolved ONCE here, so the
        # dispatch site's static flag is an engine-lifetime attribute
        # (areal-lint C6 value lattice).
        why_not = kind.kernel_refusal(
            self.model_config, self.cache, max_seq_len, kv_dtype, tp
        )
        if ragged_attn and why_not:
            raise ValueError(f"ragged_attn requested but {why_not}")
        self.ragged_attn = not why_not if ragged_attn is None else bool(ragged_attn)
        self._ragged_ok = self.ragged_attn
        # grid-wide D chosen for the CURRENT collapsed verify step — a
        # self attr for the same C6 reason as _spec_tier_d
        self._spec_grid_d = 0
        # weight version of the OLDEST K/V in each slot's valid prefix:
        # retained and shared prefixes propagate it, so strict-version
        # audits can prove no pre-swap KV seeds post-swap decoding
        self.kv_version = np.zeros(S, np.int64)
        self.stats = {
            "prefill_calls": 0,
            "prefill_tokens": 0,  # real prompt tokens through fresh prefill
            "suffix_calls": 0,
            "suffix_tokens": 0,  # real tokens through suffix prefill
            "reused_tokens": 0,  # retained-prefix tokens NOT recomputed
            "shared_tokens": 0,  # cluster-prefix tokens fanned out, not recomputed
            "copy_calls": 0,  # device-side cross-slot prefix copies
            "decode_calls": 0,
            # abort reservations whose TTL expired before the aborted
            # owner resubmitted — makes the abort_reserve_s assumption
            # observable (VERDICT r6 #10): a storm that reclaims in time
            # keeps this at 0; a rising count means the TTL is too short
            # (or clients stopped resubmitting)
            "reservations_lapsed": 0,
            # tiered decode (ISSUE 5): cohort migrations (device-side
            # cache-row copies to a roomier tier) and the attended-span
            # accounting — attended/ceiling column-steps, whose ratio is
            # decode_attended_fraction (1.0 = decode pays the full
            # max_seq_len ceiling; the window's whole point is << 1)
            "tier_migrations": 0,
            "decode_attended_cols": 0,
            "decode_ceiling_cols": 0,
            # host->device re-uploads of the decode state (dirtied by
            # admission/free/migration); steady state adds none
            "state_syncs": 0,
            # speculative decode (ISSUE 12): draft tokens proposed /
            # accepted (their ratio is the acceptance rate steering the
            # per-tier D ladder) and verify dispatches issued.  The server
            # telemetry mirror exports these as
            # areal_gen_spec_drafted_total / areal_gen_spec_accepted_total.
            "spec_drafted": 0,
            "spec_accepted": 0,
            "verify_calls": 0,
            # unified prefix cache (ISSUE 16): admission outcomes through
            # the radix/paged pool.  hits = admitted rows that inherited a
            # resident prefix (retained reuse, fan-out siblings, host
            # swap-ins); misses = cold/VLM admissions; evictions =
            # resident prefixes overwritten or LRU-dropped before any hit
            # consumed them; host_swaps = device<->host prefix transfers
            # (spills + swap-ins).  The server mirrors all four as
            # areal_gen_prefix_cache_*_total and derives the global
            # hit-rate gauge from hits / (hits + misses).
            "prefix_cache_hits": 0,
            "prefix_cache_misses": 0,
            "prefix_cache_evictions": 0,
            "prefix_cache_host_swaps": 0,
            # page-granular sub-prefix sharing (ISSUE 17 satellite): hits
            # whose inherited span is a page-rounded PARTIAL prefix copied
            # from a donor slot that a longer match claimed — counted
            # inside prefix_cache_hits too, this key is the breakdown
            "prefix_cache_partial_hits": 0,
            # disaggregated handoff (ISSUE 17): cross-server KV page
            # streaming.  exports/imports count /kv_export gathers and
            # /kv_import host-tier installs; bytes is the wire KV payload
            # both ways; failures are export misses (prefix no longer
            # resident) or imports refused (host tier disabled).  The
            # server mirrors them as areal_gen_kv_handoff_*_total.
            "kv_handoff_exports": 0,
            "kv_handoff_imports": 0,
            "kv_handoff_bytes": 0,
            "kv_handoff_failures": 0,
            # ragged paged-decode attention (ISSUE 19): collapsed
            # grid-wide kernel dispatches, and the page-granular read
            # accounting (pages the kernel actually gathered, summed over
            # slots x steps).  The server mirrors both as
            # areal_gen_ragged_*_total and derives the pages-per-dispatch
            # gauge from their ratio.
            "ragged_dispatches": 0,
            "ragged_attended_pages": 0,
            # where a step()'s host time goes (telemetry.span totals, always
            # on: a dozen clock reads a step).  The five phases tile
            # step(): admit = _admit() with the prefill/suffix dispatches it
            # issues; sync = device-state rebuild; dispatch = migration
            # planning, drafting, issuing decode/verify programs; fetch =
            # the downloads, i.e. the host waiting for the device; deliver =
            # token scan, stops, callbacks, frees.
            "t_step_admit_s": 0.0,
            "t_step_sync_s": 0.0,
            "t_step_dispatch_s": 0.0,
            "t_step_fetch_s": 0.0,
            "t_step_deliver_s": 0.0,
            "engine_steps": 0,  # step() calls that found an active slot
            # the in-flight ledger's account (`_launched` / `_landed`
            # below), on the same clock and as cheap: seconds the engine
            # HAD work while the device had none of its programs queued,
            # inside steps and between them ...
            "t_starved_s": 0.0,
            # ... the part of t_step_admit_s that _admit spent in the
            # downloads of its own prefill / suffix / VLM dispatches (the
            # span `admit_fetch`, nested in `step_admit`): admission's
            # wait for the device, which no reordering of a step hides ...
            "t_admit_fetch_s": 0.0,
            # ... and the caller's time: from the return of a step() that
            # left work behind to the entry of the next, which no step_*
            # phase covers
            "t_between_steps_s": 0.0,
            # tokens step() handed to requests in its deliver phase (what it
            # returns, summed): over decode_passes the slots live in a pass.
            # A request's FIRST token comes from its prefill, in admit
            "tokens_delivered": 0,
            # forward passes over the weights the decode path asked the
            # device for: n per decode dispatch (n fused forward+sample
            # iterations), 1 per verify dispatch
            "decode_passes": 0,
            # ... of which the sampler built its top-k/top-p candidate
            # window (a whole-vocabulary sort): a live slot of the
            # dispatch was restricted and not greedy
            "sampler_window_passes": 0,
            # submit -> slot grant, summed over admitted requests
            "t_queue_wait_s": 0.0,
            "admitted": 0,
            # power retention (a slot is one recurrent state): rows of a
            # suffix dispatch that started from ANOTHER slot's state (the
            # group fan-out) and the bytes of those states; requests whose
            # partial match of a retained slot was dropped because a state
            # cannot be cut back to a prefix; members of a declared group
            # that computed their whole prompt although a sibling had
            # been admitted before them
            "state_copies": 0,
            "state_copy_bytes": 0,
            "state_reuse_dropped": 0,
            "sibling_reprefills": 0,
            # ... and the rows whose state the decode passes stepped, live
            # or not (a pass steps its whole block where it lies): beside
            # `decode_passes`, what the state's bytes of a pass follow
            "state_rows_stepped": 0,
            # a slot of columns beside rings of a window (full and sliding
            # layers in one stack): the same rows counted for the rings a
            # fan-out copies whole, and their bytes without the columns'
            "window_copies": 0,
            "window_copy_bytes": 0,
            # ... and, counted on the device with a decode chunk's tokens:
            # the columns ONE full layer's attention had to read (positions
            # attended, summed over live slots and passes), and the held
            # experts there were to touch (passes x expert layers x held)
            "kv_columns_read": 0,
            "expert_slots": 0,
            # latent mixture of experts told what it holds: (token, expert)
            # assignments of live slots to experts held here, and held
            # experts that got any row (their two matrices are then read),
            # both summed over decode passes and expert blocks; counted on
            # the device and fetched with the chunk's tokens
            "expert_assignments_held": 0,
            "experts_touched": 0,
            # latent attention around a shortcut expert layer, counted the
            # same way: every assignment the router made for a live slot (k
            # a token and expert layer), those to an identity expert (no
            # product), and the latent rows attention read (positions
            # attended, summed over slots, passes and sublayers)
            "expert_assignments": 0,
            "identity_assignments": 0,
            "latent_rows_read": 0,
        }

        # decode_chunk: tokens generated per host round-trip.  The decode scan
        # runs this many fused forward+sample steps on device before the host
        # sees anything — the host applies stop conditions in arrears and
        # discards overshoot (slots that stopped mid-chunk decode garbage that
        # is never delivered).  Chunking amortises the host's per-dispatch
        # cost over several device steps.
        self.decode_chunk = max(1, decode_chunk)
        cfg = self.model_config
        # ragged kernel closure constants: page granularity rides the SAME
        # prompt-bucket ladder the key_window buckets on (so page-count
        # buckets and K buckets are 1:1 — no extra signature axis), and
        # tp>1 wraps the kernel in shard_map over the kv-head axis
        _kernel_page = prompt_bucket
        _kernel_mesh = self.mesh if tp > 1 else None
        # what a decode pass of the kind counts ((): nothing)
        self._pass_counters = counters = kind.counters

        def _stream_keys(decode_key, streams, pos):
            # counter-keyed sampling shared by every text prefill path:
            # key = fold(fold(decode_key, stream), position) — the SAME
            # scheme decode chunks use, with `pos` the index of the last
            # WRITTEN token (one before the first decode key), so the
            # whole token stream is a pure function of (stream_id,
            # position).  That makes the stream invariant to placement:
            # a fresh prefill here, a suffix resume after failover, or a
            # cross-server handoff import all sample identical tokens.
            return jax.vmap(
                lambda s, p: jax.random.fold_in(
                    jax.random.fold_in(decode_key, s), p
                )
            )(streams, pos)

        def _prefill(
            params, cache, ids, plen, slot_ids, streams, decode_key,
            temp, tp, tk,
        ):
            logits, cache = forward_prefill(params, cfg, ids, plen, cache, slot_ids)
            with jax.named_scope("sampler"):
                keys = _stream_keys(decode_key, streams, plen - 1)
                tok, logp = sample_tokens_keyed(
                    logits.astype(jnp.float32), keys, temp, tk, tp
                )
            return tok, logp, cache

        def _suffix_prefill(
            params, cache, ids, starts, slens, slot_ids, copy_src,
            streams, decode_key, temp, tp, tk, copy_block, key_window,
        ):
            logits, cache = forward_prefill_cached(
                params, cfg, ids, starts, slens, cache, slot_ids,
                copy_src=copy_src, copy_block=copy_block,
                key_window=key_window,
            )
            with jax.named_scope("sampler"):
                keys = _stream_keys(decode_key, streams, starts + slens - 1)
                tok, logp = sample_tokens_keyed(
                    logits.astype(jnp.float32), keys, temp, tk, tp
                )
            return tok, logp, cache

        def _decode_chunk(
            params, cache, tokens, lengths, rope_pos, streams, active,
            temp, tp, tk, decode_key, rows, n, base, size, key_window,
            ragged,
        ):
            """Advance ONE length-cohort tier — the `size` slots at
            logical positions [base, base+size) — by `n` fused
            decode+sample steps.  `tokens`/`lengths`/`rope_pos` are the
            FULL device-resident state arrays (donated; returned with the
            block advanced), so consecutive tier dispatches chain
            device->device with no host upload.  `key_window` statically
            bounds the attended span (bucket ladder); `active` drops idle
            slots' cache writes.  `rows` is the page table (traced data):
            each logical slot reads/writes its KV through its physical
            row, so a migration remap costs zero new programs."""
            rows_b = jax.lax.slice_in_dim(rows, base, base + size)
            tok_b = jax.lax.slice_in_dim(tokens, base, base + size)
            len_b = jax.lax.slice_in_dim(lengths, base, base + size)
            rp_b = jax.lax.slice_in_dim(rope_pos, base, base + size)
            act_b = jax.lax.slice_in_dim(active, base, base + size)
            temp_b = jax.lax.slice_in_dim(temp, base, base + size)
            tp_b = jax.lax.slice_in_dim(tp, base, base + size)
            tk_b = jax.lax.slice_in_dim(tk, base, base + size)
            st_b = jax.lax.slice_in_dim(streams, base, base + size)
            with jax.named_scope("sampler"):
                slot_keys = jax.vmap(
                    lambda s: jax.random.fold_in(decode_key, s)
                )(st_b)

            def body(carry, _):
                cache, tok_b, len_b, rp_b = carry
                logits, cache, pass_counts = kind.decode(
                    params, cfg, tok_b, len_b, cache,
                    rope_positions=rp_b, key_window=key_window,
                    slot_base=base, active=act_b, rows=rows_b,
                    ragged=ragged, page_size=_kernel_page,
                    mesh=_kernel_mesh,
                )
                # counter-based keys: (stream, cache position) — unique
                # per generated token, independent of how the grid is
                # partitioned into dispatches
                with jax.named_scope("sampler"):
                    keys = jax.vmap(jax.random.fold_in)(slot_keys, len_b)
                    # live=act_b: a released slot keeps its top_k/top_p
                    # and must not keep the window alive for the grid
                    tok, logp = sample_tokens_keyed(
                        logits.astype(jnp.float32), keys, temp_b, tk_b, tp_b,
                        live=act_b,
                    )
                out = (tok, logp, pass_counts) if counters else (tok, logp)
                return (cache, tok, len_b + 1, rp_b + 1), out

            (cache, tok_b, len_b, rp_b), (toks, logps, *counts) = jax.lax.scan(
                body, (cache, tok_b, len_b, rp_b), None, length=n
            )
            tokens = jax.lax.dynamic_update_slice_in_dim(tokens, tok_b, base, 0)
            lengths = jax.lax.dynamic_update_slice_in_dim(lengths, len_b, base, 0)
            rope_pos = jax.lax.dynamic_update_slice_in_dim(rope_pos, rp_b, base, 0)
            # one fused download: tokens are exactly representable in f32
            rows_out = [toks.astype(jnp.float32), logps]
            if counters:
                # a third row carries each pass's counters in its first
                # entries: fetched with the tokens, no sync of their own
                # (float32 holds them exactly: each is under 2 ** 24)
                rows_out.append(counts[0].astype(jnp.float32))
                width = max(size, len(counters))
                if width > size:
                    # a block of fewer slots than counters is widened
                    rows_out[:2] = [
                        jnp.pad(r, ((0, 0), (0, width - size)))
                        for r in rows_out[:2]
                    ]
                rows_out[2] = jnp.pad(
                    rows_out[2], ((0, 0), (0, width - len(counters)))
                )
            out = jnp.stack(rows_out)  # [2 (3), n, size]
            return out, cache, tokens, lengths, rope_pos

        def _verify_chunk(
            params, cache, tokens, lengths, rope_pos, streams, active,
            temp, tp, tk, decode_key, rows, drafts, draft_lens,
            base, size, key_window, d_max, ragged,
        ):
            """Speculative step for ONE tier: score the pending token plus
            up to `d_max` prompt-lookup drafts per slot in a single
            `forward_verify` dispatch, sample every position under the
            SAME counter-keyed PRNG plain decode would use, and accept the
            leading run of drafts that match what the sampler emits — so
            the delivered stream is bit-identical to non-speculative
            decode at any temperature.  Per-slot state (lengths/rope/last
            token) advances by the accepted count ON DEVICE; rejected
            draft positions get their freshly-written K/V zeroed before
            the dispatch returns, so no rejected write outlives it."""
            Dp1 = d_max + 1
            rows_b = jax.lax.slice_in_dim(rows, base, base + size)
            tok_b = jax.lax.slice_in_dim(tokens, base, base + size)
            len_b = jax.lax.slice_in_dim(lengths, base, base + size)
            rp_b = jax.lax.slice_in_dim(rope_pos, base, base + size)
            act_b = jax.lax.slice_in_dim(active, base, base + size)
            temp_b = jax.lax.slice_in_dim(temp, base, base + size)
            tp_b = jax.lax.slice_in_dim(tp, base, base + size)
            tk_b = jax.lax.slice_in_dim(tk, base, base + size)
            st_b = jax.lax.slice_in_dim(streams, base, base + size)
            inputs = jnp.concatenate([tok_b[:, None], drafts], axis=1)
            n_write = draft_lens + 1  # pending token + real draft positions
            logits, cache = forward_verify(
                params, cfg, inputs, len_b, cache,
                rope_positions=rp_b, key_window=key_window,
                slot_base=base, active=act_b, n_write=n_write,
                rows=rows_b, ragged=ragged, page_size=_kernel_page,
                mesh=_kernel_mesh,
            )  # [size, Dp1, V]
            # position-keyed sampling: logits[:, j] is the distribution at
            # sequence position len + j, exactly the row a plain decode
            # step would sample with key fold(fold(decode_key, stream),
            # len + j) — flattening to [size*Dp1] preserves per-row
            # determinism (sample_tokens_keyed is fully row-vmapped)
            offs = jnp.arange(Dp1, dtype=jnp.int32)
            pos = len_b[:, None] + offs[None, :]  # [size, Dp1]
            with jax.named_scope("sampler"):
                slot_keys = jax.vmap(
                    lambda s: jax.random.fold_in(decode_key, s)
                )(st_b)
                keys = jax.vmap(
                    jax.vmap(jax.random.fold_in, in_axes=(None, 0))
                )(slot_keys, pos)
                V = logits.shape[-1]
                tok_f, logp_f = sample_tokens_keyed(
                    logits.astype(jnp.float32).reshape(size * Dp1, V),
                    keys.reshape(size * Dp1, *keys.shape[2:]),
                    jnp.repeat(temp_b, Dp1),
                    jnp.repeat(tk_b, Dp1),
                    jnp.repeat(tp_b, Dp1),
                    live=jnp.repeat(act_b, Dp1),
                )
            sampled = tok_f.reshape(size, Dp1)
            logp = logp_f.reshape(size, Dp1)
            # accept the leading run where the draft IS what the sampler
            # emitted; the first mismatch position already carries the
            # correct (non-speculative) token, so a+1 tokens always emit
            ok = (sampled[:, :d_max] == drafts) & (
                offs[None, :d_max] < draft_lens[:, None]
            )
            a = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
            n_emit = jnp.where(act_b, a + 1, 0)
            # rejected-draft K/V must not outlive this dispatch: zero the
            # cache rows at positions above the accepted frontier (index M
            # scatter-drops everything else) — the decode-side analogue of
            # the idle-slot write clamp, made auditable by tests
            M_cache = cache["k"].shape[2]
            rej = (offs[None, :] >= n_emit[:, None]) & (
                offs[None, :] < n_write[:, None]
            ) & act_b[:, None]
            rej_idx = jnp.where(rej, pos, M_cache)
            slots = rows_b  # zero the PHYSICAL rows the writes landed in
            cache = {
                "k": cache["k"].at[:, slots[:, None], rej_idx].set(
                    0, mode="drop"
                ),
                "v": cache["v"].at[:, slots[:, None], rej_idx].set(
                    0, mode="drop"
                ),
            }
            # advance the device-resident state by the accepted count
            new_tok = jnp.where(
                act_b, jnp.take_along_axis(sampled, a[:, None], 1)[:, 0],
                tok_b,
            )
            tokens = jax.lax.dynamic_update_slice_in_dim(
                tokens, new_tok, base, 0
            )
            lengths = jax.lax.dynamic_update_slice_in_dim(
                lengths, len_b + n_emit, base, 0
            )
            rope_pos = jax.lax.dynamic_update_slice_in_dim(
                rope_pos, rp_b + n_emit, base, 0
            )
            # decode-layout download: [2, Dp1, size] + per-slot emit count
            out = jnp.stack([sampled.T.astype(jnp.float32), logp.T])
            return out, n_emit, cache, tokens, lengths, rope_pos

        # ONE cache aval family for every program (ISSUE 17): each
        # cache-producing program pins its cache output to the SAME
        # NamedSharding device_put installed at init (kv-head axis on
        # "tp"), so device_put-fresh, prefill-, decode-, and
        # scatter-produced caches are signature-identical.  Without the
        # pin XLA infers PartitionSpec() for program outputs, splitting
        # every downstream jit into a cold (device_put) and a resident
        # family — the PR 16 cold-start re-mint — and silently degrading
        # the kv-head sharding under tp>1.
        rep = NamedSharding(self.mesh, P())
        # _sync_device_state commits its uploads to this same replicated
        # sharding: a bare jnp.asarray upload is an UNCOMMITTED
        # SingleDeviceSharding, while chained chunk outputs carry `rep` —
        # mixing the two mints a second executable per (static args)
        # signature (the PR 16 cold-start re-mint class, caught again by
        # the ragged soak's exact program accounting)
        self._rep_sharding = rep
        cache_sh = self._cache_shardings
        self._prefill_fn = jax.jit(
            _prefill, donate_argnums=(1,),
            out_shardings=(rep, rep, cache_sh),
        )
        # the suffix program carries the cross-slot prefix fan-out fused in
        # (ops/kv_copy.py gather/scatter before the layer scan): copy_block
        # is static and always from the prompt-bucket ladder, so compile
        # count stays O(log^2 buckets x log slots), same family as
        # admission — and a grouped pass costs no extra dispatch
        self._suffix_prefill_fn = jax.jit(
            _suffix_prefill, static_argnums=(12, 13), donate_argnums=(1,),
            out_shardings=(rep, rep, cache_sh),
        )
        # signature family: (tier block, chunk, K bucket) — tiers and
        # chunk are fixed per engine, K rides the pow2 prompt-bucket
        # ladder, so steady state compiles O(tiers x log(M/quantum))
        # programs and then mints none (pinned by test); the page-table
        # rows arg is traced data and adds no signatures
        self._decode_fn = jax.jit(
            _decode_chunk, static_argnums=(12, 13, 14, 15, 16),
            donate_argnums=(1, 2, 3, 4),
            out_shardings=(rep, cache_sh, rep, rep, rep),
        )
        # verify signature family: (tier block, K bucket, D rung) — D
        # rides the small static spec ladder (D=0 reuses the decode
        # program outright), so spec decode adds
        # tiers x ladder x |nonzero rungs| programs at most, budgeted in
        # analysis/signature_budget.json ("verify") and pinned by the
        # jit-cache soak tests
        self._verify_fn = jax.jit(
            _verify_chunk, static_argnums=(14, 15, 16, 17, 18),
            donate_argnums=(1, 2, 3, 4),
            out_shardings=(rep, rep, cache_sh, rep, rep, rep),
        )
        # host-DRAM overflow tier (ISSUE 16): spill gathers one physical
        # row's bucketed prefix (block static on the prompt ladder — one
        # program per bucket); swap-in scatters it back shape-keyed (same
        # ladder bound), with the cache donated so the restore is in-place
        self._host_gather_fn = jax.jit(gather_kv_prefix, static_argnums=(2,))
        # out_shardings pins the scatter-produced cache to the SAME layout
        # device_put installed at init (kv-head axis on "tp"), so a swap-in
        # or handoff import never changes the cache aval the decode family
        # compiled against — the PR 16 cold-start re-mint is gone, and tp>1
        # swap-ins keep the sharded layout instead of silently gathering
        self._host_scatter_fn = jax.jit(
            scatter_kv_prefix, donate_argnums=(0,),
            out_shardings=self._cache_shardings,
        )
        self._init_vlm()
        self._warmup_host_tier()

    def _init_vlm(self) -> None:
        """Compile the vision tower + image-conditioned prefill when the
        model is a VLM (cfg.vision set and the checkpoint carries a tower);
        text-only engines skip all of it."""
        cfg = self.model_config
        self._vlm = (
            cfg.vision is not None
            and cfg.image_token_id is not None
            and cfg.mrope_section is not None
            and isinstance(self.params, dict)
            and "vision" in self.params
        )
        if not self._vlm:
            return
        from areal_tpu.models.vision import (
            merge_image_embeds,
            mrope_cos_sin,
            vision_forward,
        )

        vcfg = cfg.vision

        def _embed_images(vparams, pv, img_ids, pos_hw):
            return vision_forward(
                vparams, vcfg, pv, img_ids, patch_pos_hw=pos_hw
            )

        def _vlm_prefill(
            params, cache, ids, mpos, image_embeds, plen, slot_ids,
            rng, temp, tp, tk,
        ):
            dtype = jnp.dtype(cfg.dtype)
            with jax.named_scope("embed"):
                text = jnp.take(params["embedding"].astype(dtype), ids, axis=0)
                x = merge_image_embeds(
                    text, ids, image_embeds, cfg.image_token_id
                )
                rope = mrope_cos_sin(
                    mpos, cfg.head_dim_, cfg.rope_theta, cfg.mrope_section
                )
            logits, cache = forward_prefill(
                params, cfg, ids, plen, cache, slot_ids,
                inputs_embeds=x, rope=rope,
            )
            with jax.named_scope("sampler"):
                tok, logp = sample_tokens(
                    logits.astype(jnp.float32), rng, temp, tk, tp
                )
            return tok, logp, cache

        self._embed_images_fn = jax.jit(_embed_images)
        # same single cache aval family as the text programs
        rep = NamedSharding(self.mesh, P())
        cache_sh = self._cache_shardings
        self._vlm_prefill_fn = jax.jit(
            _vlm_prefill, donate_argnums=(1,),
            out_shardings=(rep, rep, cache_sh),
        )

    # ------------------------------------------------------------------
    # submission / weights
    # ------------------------------------------------------------------

    def submit(self, req: GenRequest) -> None:
        if len(req.input_ids) + 1 >= self.max_seq_len:
            req.finish("length")
            return
        # one clock read per request: backs the admission-queue-wait
        # histogram without any conditional on the hot submit path
        req.submit_ts = time.perf_counter()
        self.pending.put(req)

    def submit_batch(self, reqs: List[GenRequest]) -> None:
        """Enqueue a whole group contiguously, so one admission window sees
        every member and the cluster fan-out can share their prefix; the
        group hold (`group_hold_s`) covers members that still straggle in
        through separate submits."""
        for req in reqs:
            self.submit(req)

    def active_count(self) -> int:
        with self._lock:
            return (
                sum(r is not None for r in self.slot_req)
                + self.pending.qsize()
                + len(self._holdback)
            )

    # --- the in-flight ledger ---------------------------------------------
    # The serving thread's own account, on the host's clock, of when the
    # device had nothing of the engine's queued: stats["t_starved_s"].  The
    # device runs one stream in order, so two sequence numbers and one time
    # stamp are the whole of it.  Every program whose output the engine
    # downloads, or that such a program always follows in the same admission
    # (`_prefill_shared_spans`), takes the next launch number when its
    # dispatch has returned; a download that returns proves its program and
    # every earlier one finished.  Programs that are launched and never
    # downloaded (the uploads of `_sync_device_state`, the host tier's
    # gathers and scatters, a weight swap's placement) take no number and
    # close no interval: the account then calls the device starved a little
    # LONGER than it was, never shorter.  It is the ENGINE's account: where
    # a trainer shares the chip (`ColocatedEngine`), its programs may fill
    # an interval this one calls starved.  No lock and no flag: three
    # attribute reads and at most one clock read a call.

    def _launched(self) -> None:
        """The device has just been handed a program: it is starved no
        longer."""
        since = self._starved_since
        if since is not None:
            self._starved_since = None
            self.stats["t_starved_s"] += time.perf_counter() - since
        self._n_launched += 1

    def _landed(self, launch: int) -> None:
        """A download of launch number `launch`'s output has returned.  If
        that was the newest launch, the device has nothing queued from now
        until the next one."""
        self._n_landed = launch
        if launch == self._n_launched:
            self._starved_since = time.perf_counter()

    def _not_starved(self) -> None:
        """An engine without work is idle, not starved, and a paused one is
        timed by `publish_swap`: the open interval is dropped uncounted, and
        the caller's stretch before the next step() with it."""
        self._starved_since = None
        self._step_returned = None

    def abort_all(self, reason: str = "abort") -> int:
        """Finish every in-flight request immediately (weight update /
        shutdown). Returns how many were aborted.

        Each abort-freed slot gets a short reservation
        (`abort_reserve_s`): the aborted client WILL resubmit with the
        same prompt + accumulated tokens within an RTT, and handing the
        slot to a fresh prompt first would overwrite the retained prefix
        exactly when it is most valuable (the r4 abort-storm thrash)."""
        deadline = time.monotonic() + self.abort_reserve_s
        version_before = self.version
        # finish() runs user on_done callbacks and wakes waiters; calling
        # it under _lock deadlocks any callback that re-enters the engine
        # (areal-lint C5 blocking-under-lock) — collect under the lock,
        # call after release
        to_finish: List[GenRequest] = []
        n_in_slot = 0
        with self._lock:
            self._abort_gen += 1  # a racing _admit must drop its leftovers
            for s, req in enumerate(self.slot_req):
                if req is not None:
                    to_finish.append(req)
                    self.slot_req[s] = None
                    # retained prefix makes the client's resubmission (same
                    # prompt + accumulated tokens) a suffix-only prefill
                    self.retained_len[s] = self._retained_after(s)
                    # reserve only prefixes the owner's resubmission can
                    # actually claim: its lcp is capped below len(ids) by
                    # the admission match, so at retained_len ==
                    # reuse_min_tokens the slot would sit
                    # reserved-yet-unclaimable for the whole TTL — the
                    # threshold must be STRICTLY greater (ADVICE r5)
                    if (
                        self.kv_reuse
                        and self.retained_len[s] > self.reuse_min_tokens
                    ):
                        self._reserved_until[s] = deadline
                    self.pool.note_free(
                        s, self.seq_tokens[s], int(self.retained_len[s])
                    )
            self._state_dirty = True
            self._group_admitted.clear()
            n_in_slot = len(to_finish)
            to_finish.extend(self._holdback)
            self._holdback = []
            while True:
                try:
                    to_finish.append(self.pending.get_nowait())
                except queue.Empty:
                    break
        if telemetry.is_enabled():
            # only slot-holding requests were mid-decode: those are the
            # interrupt spans the resume events pair with (queued/held-back
            # requests just bounce through the client's resubmit loop)
            for req in to_finish[:n_in_slot]:
                telemetry.emit(
                    "interrupt", trace_id=req.trace_id or req.rid,
                    reason=reason, version_before=version_before,
                    generated=len(req.output_tokens),
                )
        for req in to_finish:
            req.finish(reason)
        self._not_starved()
        return len(to_finish)

    def load_weights(
        self, path: Optional[str] = None, params=None, version: Optional[int] = None
    ) -> int:
        """Swap weights; aborts in-flight generation first (interruptible
        generation: clients resubmit and the new prefill recomputes under the
        new policy). Returns the new version."""
        took: Dict[str, float] = {}
        with telemetry.span("publish_swap", took):
            version_before = self.version
            self._pause_depth += 1
            try:
                aborted = self.abort_all("abort")
                if aborted:
                    logger.info(f"aborted {aborted} requests for weight update")
                if params is None:
                    import os

                    assert path is not None
                    pinned = os.path.join(path, f"v{int(version)}") \
                        if version is not None else None
                    if pinned is not None and os.path.isdir(pinned):
                        # recovery replays pin the version: load exactly that
                        # snapshot, not the newest — a later, never-trained-on
                        # v{N} may have survived the crash on disk
                        path = pinned
                    else:
                        path, dir_version = self._resolve_ckpt_dir(path)
                        if version is None:
                            # adopt the trainer's version from the v{N} dir name
                            # — a fresh server must not restart its version
                            # counter at 1 while the trainer is at N (staleness
                            # gates compare them)
                            version = dir_version
                    params, _ = load_hf_params(path, self.model_config, dtype="bfloat16")
                self.swap_weights_live(params, version=version)
            finally:
                self._pause_depth -= 1
        # achieved generation-idle window for the unstaged ABORT path spans
        # the abort + checkpoint load + host->device placement, not just the
        # swap tail (staged swaps record theirs in commit_staged)
        self.last_pause_s = took["t_publish_swap_s"]
        self._record_pause(self.last_pause_s, "reload_abort", version_before)
        return self.version

    def swap_weights_live(self, params, version: Optional[int] = None) -> int:
        """Non-aborting weight swap — the colocated in-memory publish.

        In-flight requests keep their slots and KV and continue decoding
        under the NEW policy from the next chunk on; per-token
        `output_versions` record the transition, which is exactly the
        mixed-version trajectory the decoupled loss's behavior weight is
        built to consume (reference interruptible generation,
        blog/AReaL_v0_3.md:203-207, achieves the same semantics by
        abort+resume because SGLang cannot hot-swap mid-request — here the
        params tree is one pointer read per dispatch, so nothing needs to
        die).  KV computed under the old weights stays, matching the radix
        cache the reference leans on (remote_inf_engine.py:404-413).

        Callers must not race a swap against an in-flight `step()` if they
        care about exact version stamping (ColocatedEngine parks the
        stepper first); the swap itself is atomic either way.

        `load_weights` (the aborting path) delegates here for the shared
        publish tail, so every swap invariant lives in one place.
        """
        if self.model_config.vision is not None and "vision" not in params:
            # text-only update for a VLM: keep the current tower (already
            # sharded on device; device_put under the same spec is a no-op)
            params = dict(params)
            params["vision"] = self.params["vision"]
        took: Dict[str, float] = {}
        with telemetry.span("publish_swap", took):
            version_before = self.version
            self._not_starved()  # generation pauses: this span times it
            self.params = shard_pytree(self.mesh, params, self._pspecs)
            self.version = version if version is not None else self.version + 1
            if not self.retain_kv_on_reload:
                # strict mode applies to EVERY weight-swap path: retained
                # prefixes hold old-policy KV and must not seed suffix
                # prefills.  Shared (fan-out) prefixes are zeroed exactly the
                # same way — once a sibling's slot frees, its copied prefix IS
                # a retained prefix, and kv_version tracks its true origin.
                self.retained_len[:] = 0
                self._reserved_until[:] = 0.0  # nothing left to reserve
                self.kv_version[:] = self.version  # no pre-swap KV survives
                # the host tier is old-policy KV too: strict mode drops every
                # resident prefix from the pool, spilled ones included
                self.pool.clear()
            if getattr(self, "_standby", None) is not None:
                staged_v = self._standby[1]
                if staged_v is None or staged_v <= self.version:
                    # staged_v <= version: committing later would ROLL BACK the
                    # version.  staged_v None: its ordering vs this publish is
                    # unknowable, and a later commit would install the OLDER
                    # staged weights under a version bump (+1) — poisoning the
                    # staleness accounting that trusts versions to order
                    # policies.  Either way the standby must die (it also pins
                    # a full bf16 param copy of HBM); the commit's 409 tells
                    # the staging client to re-push.
                    logger.warning(
                        "weight publish discarding superseded standby (staged "
                        f"v{staged_v}, now v{self.version})"
                    )
                    self._standby = None
                # a STRICTLY NEWER standby (e.g. v6 staged via prepare while a
                # v5 disk publish lands) stays valid for its pending commit
        self.last_pause_s = took["t_publish_swap_s"]
        if self._pause_depth == 0:
            # top-level live publish; nested calls (load_weights /
            # commit_staged) record their full window themselves
            self._record_pause(self.last_pause_s, "swap_live", version_before)
        return self.version

    def stage_params(self, params, version: Optional[int] = None) -> bool:
        """Pre-place fresh weights on device while generation KEEPS RUNNING
        (VERDICT r3 weak #2: the staged-transfer commit's ~30s was dominated
        by host->device placement *inside* the pause window).  The standby
        tree costs a second bf16 param copy of HBM; if that does not fit,
        returns False and the caller falls back to commit-time placement."""
        if self.model_config.vision is not None and "vision" not in params:
            params = dict(params)
            params["vision"] = self.params["vision"]
        try:
            # no block_until_ready: allocation (and OOM) is synchronous but
            # the copy streams asynchronously, so the worker thread gets
            # back to decoding while DMA proceeds; the first program under
            # the new params waits for any transfer still in flight
            standby = shard_pytree(self.mesh, params, self._pspecs)
        except Exception as e:  # noqa: BLE001 — OOM => unstaged fallback
            logger.warning(f"weight staging failed ({str(e)[:120]}); "
                           "commit will place from host")
            self._standby = None
            return False
        self._standby = (standby, version)
        return True

    @property
    def staged_version(self) -> Optional[int]:
        """Version of the pre-staged standby weights, or None when nothing
        is staged (public surface for gen/server.py and tests)."""
        return self._standby[1] if self._standby is not None else None

    @property
    def has_standby(self) -> bool:
        return self._standby is not None

    def commit_staged(self, live: bool = False) -> int:
        """Swap pre-staged weights in.  Default: abort in-flight + pointer
        swap — the whole pause is O(abort), not O(model bytes).  `live=True`
        skips the abort entirely (swap_weights_live semantics: in-flight
        requests keep decoding, per-token versions record the transition).
        Returns the version."""
        if getattr(self, "_standby", None) is None:
            raise RuntimeError("commit_staged without stage_params")
        took: Dict[str, float] = {}
        with telemetry.span("publish_swap", took):
            version_before = self.version
            self._pause_depth += 1
            try:
                if not live:
                    aborted = self.abort_all("abort")
                    if aborted:
                        logger.info(
                            f"aborted {aborted} requests for staged weight swap"
                        )
                standby, version = self._standby
                self._standby = None
                # shared swap tail (device_put of the already-sharded standby
                # under the same spec is a no-op, so this stays a pointer swap)
                self.swap_weights_live(standby, version=version)
            finally:
                self._pause_depth -= 1
        self.last_pause_s = took["t_publish_swap_s"]
        self._record_pause(
            self.last_pause_s,
            "commit_live" if live else "commit_abort",
            version_before,
        )
        return self.version

    def _record_pause(
        self, dur: float, kind: str, version_before: int
    ) -> None:
        """Every weight-publish pause window lands in the evidence
        histogram (cold path — the swap itself dwarfs the observe); the
        event stream additionally records the version transition when
        telemetry is on."""
        telemetry.PAUSE_WINDOW.observe(dur)
        if telemetry.is_enabled():
            telemetry.emit(
                "pause", kind=kind, dur_s=dur,
                version_before=version_before, version_after=self.version,
            )

    def release_memory(self, drop_params: bool = True) -> None:
        """Colocated time-share (alloc `a|b`, VERDICT r3 weak #4): free the
        HBM this engine holds so a trainer can use the same chips.  Aborts
        in-flight requests (clients resume later via the retained-prefix
        machinery being rebuilt fresh), drops the KV cache, and with
        `drop_params` the bf16 serving weights too — a VLM's small vision
        tower is kept so an in-memory text-weight handoff can restage."""
        self.abort_all("abort")
        self.cache = None
        self._standby = None
        with self._lock:
            self._dev_state = None  # rebuilt from host mirrors at restage
            self._state_dirty = True
        self.retained_len[:] = 0  # cache is gone; no prefix survives
        self._reserved_until[:] = 0.0
        self.kv_version[:] = self.version
        self.pool.clear()  # radix entries and host spills die with it
        if drop_params:
            if isinstance(self.params, dict) and "vision" in self.params:
                self.params = {"vision": self.params["vision"]}
            else:
                self.params = None

    def restage(self, params=None, version: Optional[int] = None) -> None:
        """Re-arm serving after release_memory: shard fresh weights (an
        IN-MEMORY handoff from a colocated trainer — no disk snapshot or
        chunk stream inside the pause) and reallocate the KV cache."""
        if params is not None:
            if (
                self.model_config.vision is not None
                and "vision" not in params
                and isinstance(self.params, dict)
                and "vision" in self.params
            ):
                params = dict(params)
                params["vision"] = self.params["vision"]
            self.params = shard_pytree(self.mesh, params, self._pspecs)
            if version is not None:
                self.version = version
        elif self.params is None or (
            isinstance(self.params, dict) and "embedding" not in self.params
        ):
            # None (text model released) or a vision-only remnant (VLM
            # released): either way the text weights are gone
            raise RuntimeError("restage() needs params after release_memory")
        if self.cache is None:
            self.cache = init_kv_cache(
                self.model_config, self.n_slots + 1, self.max_seq_len,
                self.kv_dtype, shardings=self._cache_shardings,
            )
            # fresh physical rows: the identity page table is correct again
            self.pool.reset()

    @staticmethod
    def _resolve_ckpt_dir(path: str):
        """Trainers publish atomic per-version snapshots `root/v{N}`
        (jax_train.py _update_weights_disk); pick the newest and return
        (dir, version).  A plain checkpoint dir (config.json present) is
        used as-is with version None."""
        import os
        import re

        if os.path.exists(os.path.join(path, "config.json")):
            return path, None
        vs = sorted(
            (int(m.group(1)), os.path.join(path, d))
            for d in (os.listdir(path) if os.path.isdir(path) else [])
            if (m := re.fullmatch(r"v(\d+)", d))
        )
        if not vs:
            raise FileNotFoundError(f"no checkpoint under {path}")
        return vs[-1][1], vs[-1][0]

    # ------------------------------------------------------------------
    # stepping
    # ------------------------------------------------------------------

    def _maybe_spill(self, slots: List[int]) -> None:
        """LRU-spill retained prefixes about to be overwritten into the
        host-DRAM overflow tier (no-op when `host_offload` is off).  The
        gather is one bucketed program per block; the download rides the
        admission boundary where the engine already syncs its planning
        state.  Prefixes below `host_min_tokens` are not worth the round
        trip and simply evict."""
        if self.pool.host is None or self.cache is None:
            return
        for s in slots:
            vlen = int(self.retained_len[s])
            toks = self.pool.device_tokens(s)
            if toks is None or vlen < self.host_min_tokens:
                continue
            if len(toks) != vlen:
                continue  # stale index entry: never spill mismatched KV
            block = round_up_to_bucket(
                vlen, self.prompt_bucket, self.max_seq_len
            )
            kv_dev = self._host_gather_fn(
                self.cache, jnp.asarray(self.pool.row(s), jnp.int32), block
            )
            # areal-lint: disable=host-sync delivery point: spill download at the admission boundary (one bucketed row gather per eviction)
            kv = {k: np.asarray(v) for k, v in kv_dev.items()}
            evicted = self.pool.host_put(
                self.seq_tokens[s], vlen, int(self.kv_version[s]), block, kv
            )
            self.pool.drop_device(s)
            self.stats["prefix_cache_host_swaps"] += 1
            self.stats["prefix_cache_evictions"] += evicted

    def _swap_in_host_hits(
        self,
        entries: List[tuple],
        matched: set,
        free_set: set,
        slot_of_entry: Dict[int, tuple],
        reuse_admitted: List[tuple],
    ) -> None:
        """Admission stage for the host overflow tier: requests the device
        match left cold probe the radix over HOST-spilled prefixes; a hit
        scatters the spilled KV back into a free row (bit-identical bytes
        — the spill kept the raw cache dtype) and the request then rides
        the ordinary suffix-prefill path as if the prefix had never left
        HBM.  The landing slot's own retained prefix spills first when
        eligible, so a swap-in never silently destroys resident state."""
        now = time.monotonic()
        for i, (req, is_vlm) in enumerate(entries[: self.match_window]):
            if is_vlm or i in matched or not free_set:
                continue
            host_m = self.pool.match_host(req.input_ids)
            if not host_m:
                continue
            limit = len(req.input_ids) - 1
            best_hid, best_l = None, 0
            for hid, l in host_m.items():
                ent = self.pool.host_entry(hid)
                if ent is None:
                    continue
                l = min(int(l), ent.valid_len, limit)
                if l >= self.reuse_min_tokens and l > best_l:
                    best_hid, best_l = hid, l
            if best_hid is None:
                continue
            open_slots = [
                s for s in free_set
                if not self._slot_vlm[s] and self._reserved_until[s] <= now
            ]
            if not open_slots:
                return  # nothing can land anywhere this pass
            # overwrite the least valuable retained cache, spilling it
            # onward when it is itself worth keeping
            s = min(open_slots, key=lambda u: int(self.retained_len[u]))
            self._maybe_spill([s])
            ent = self.pool.host_take(best_hid)
            if ent is None:
                continue
            self.cache = self._host_scatter_fn(
                self.cache,
                {k: jnp.asarray(v) for k, v in ent.kv.items()},
                jnp.asarray(self.pool.row(s), jnp.int32),
            )
            vlen = ent.valid_len
            with self._lock:
                if self.pool.drop_device(s):
                    self.stats["prefix_cache_evictions"] += 1
                self.seq_tokens[s, :vlen] = ent.tokens
                self.retained_len[s] = vlen
                self.kv_version[s] = ent.version
                self._slot_vlm[s] = False
                self._reserved_until[s] = 0.0
                self.pool.note_free(s, self.seq_tokens[s], vlen)
            self.stats["prefix_cache_host_swaps"] += 1
            matched.add(i)
            free_set.remove(s)
            slot_of_entry[i] = (s, best_l)
            reuse_admitted.append((s, req, best_l, s, False))

    def _warmup_host_tier(self) -> None:
        """Pre-compile the host-tier transfer family from COLD (the PR 16
        cold-start caveat, ISSUE 17 satellite): one gather -> host ->
        scatter round trip of the scratch row per block bucket, run at
        init before any serving dispatch.  Afterwards every gather/scatter
        rung is compiled AND the cache is already scatter-produced (with
        `out_shardings` keeping its aval identical to the device_put one),
        so the first real spill, swap-in, or handoff import mid-serving
        mints nothing — the signature soak asserts this starting cold."""
        if self.pool.host is None or self.cache is None:
            return
        row = jnp.asarray(self.pool.row(self.n_slots), jnp.int32)
        v = 1
        while True:
            b = round_up_to_bucket(v, self.prompt_bucket, self.max_seq_len)
            kv_dev = self._host_gather_fn(self.cache, row, b)
            # areal-lint: disable=host-sync warmup-only: one scratch-row round trip per block bucket before serving starts
            kv = {k: np.asarray(a) for k, a in kv_dev.items()}
            self.cache = self._host_scatter_fn(
                self.cache, {k: jnp.asarray(a) for k, a in kv.items()}, row
            )
            if b >= self.max_seq_len:
                break
            v = b + 1

    # ------------------------------------------------------------------
    # disaggregated handoff (ISSUE 17): cross-server KV page streaming
    # ------------------------------------------------------------------

    def export_request_kv(self, input_ids: List[int]) -> Optional[dict]:
        """Serialize the resident KV prefix covering `input_ids` for a
        cross-server handoff (/kv_export).  Walks the radix for the best
        device-retained match first (normally the just-finished leg's own
        slot), then the host tier; gathers the covered span on the bucket
        ladder — the SAME host_gather program family the spill path uses,
        zero new steady-state signatures — and returns a host-tier-format
        entry {tokens, valid_len, version, block, kv} the importing
        engine installs verbatim.  Non-destructive: the donor prefix
        stays resident here, so a failed import loses nothing.  Returns
        None (counting a failure) when nothing covering at least
        reuse_min_tokens is resident; the router then continues the
        stream colocated, which the counter-keyed sampler keeps
        bit-identical anyway.

        Thread contract: worker thread only (the server's handoff
        mailbox) — radix walks and the donated cache ref are
        worker-owned."""
        if "handoff" in self._kind.lacks:
            raise ValueError(f"export_request_kv: {self._kind.lacks['handoff']}")
        limit = len(input_ids) - 1
        best_slot, best_l = None, 0
        if self.cache is not None:
            for s, l in self.pool.match_device(input_ids).items():
                toks = self.pool.device_tokens(s)
                if toks is None or len(toks) != int(self.retained_len[s]):
                    continue
                l = min(int(l), limit)
                if l > best_l:
                    best_slot, best_l = s, l
        if best_slot is not None and best_l >= self.reuse_min_tokens:
            block = round_up_to_bucket(
                best_l, self.prompt_bucket, self.max_seq_len
            )
            kv_dev = self._host_gather_fn(
                self.cache,
                jnp.asarray(self.pool.row(best_slot), jnp.int32),
                block,
            )
            # areal-lint: disable=host-sync delivery point: handoff export download — one bucketed row gather per /kv_export
            kv = {k: np.asarray(a) for k, a in kv_dev.items()}
            entry = {
                "tokens": np.asarray(
                    self.pool.device_tokens(best_slot)[:best_l], np.int64
                ),
                "valid_len": int(best_l),
                "version": int(self.kv_version[best_slot]),
                "block": int(block),
                "kv": kv,
            }
        else:
            best_hid, best_hl = None, 0
            if self.pool.host is not None:
                for hid, l in self.pool.match_host(input_ids).items():
                    ent = self.pool.host_entry(hid)
                    if ent is None:
                        continue
                    l = min(int(l), ent.valid_len, limit)
                    if l > best_hl:
                        best_hid, best_hl = hid, l
            if best_hid is None or best_hl < self.reuse_min_tokens:
                self.stats["kv_handoff_failures"] += 1
                return None
            ent = self.pool.host_entry(best_hid)
            self.pool.host.touch(best_hid)
            # a partial host match exports the entry's full block; the
            # importer attends nothing past valid_len, so the extra
            # positions are dead weight, never wrong bytes
            entry = {
                "tokens": np.asarray(ent.tokens[:best_hl], np.int64),
                "valid_len": int(best_hl),
                "version": int(ent.version),
                "block": int(ent.block),
                "kv": ent.kv,
            }
        self.stats["kv_handoff_exports"] += 1
        self.stats["kv_handoff_bytes"] += sum(
            int(a.nbytes) for a in entry["kv"].values()
        )
        return entry

    def import_request_kv(self, entry: dict) -> bool:
        """Install an exported prefix (/kv_import) as a host-tier entry;
        the request that follows admits through the ordinary radix match
        + swap-in path as a warm-cache hit, re-scattering the pages on
        the same bucket ladder — a bit-identical round trip, exactly like
        a local spill.  Returns False (counting a failure) when the host
        tier is disabled; decode-role servers always enable it (--role
        decode forces host_offload).  Worker thread only, like export."""
        if "handoff" in self._kind.lacks:
            raise ValueError(f"import_request_kv: {self._kind.lacks['handoff']}")
        if self.pool.host is None:
            self.stats["kv_handoff_failures"] += 1
            return False
        tokens = np.asarray(entry["tokens"], np.int64)
        vlen = int(entry["valid_len"])
        kv = {k: np.asarray(a) for k, a in entry["kv"].items()}
        evicted = self.pool.host_put(
            tokens, vlen, int(entry["version"]), int(entry["block"]), kv
        )
        self.stats["prefix_cache_evictions"] += evicted
        self.stats["kv_handoff_imports"] += 1
        self.stats["kv_handoff_bytes"] += sum(
            int(a.nbytes) for a in kv.values()
        )
        return True

    def _apply_group_hold(self, entries: List[tuple]):
        """Park members of a declared group (`group_id` + `group_n`) until
        the whole group shares one admission window — the cluster fan-out
        can only share a prefix among co-resident requests.  The hold TTL
        (`group_hold_s`) bounds the wait: a sibling that already finished
        never resubmits, so partial groups must eventually admit.
        Returns (entries, held, hold_deadlines)."""
        if self.group_hold_s <= 0 or not any(
            r.group_id and r.group_n > 1 and not v for r, v in entries
        ):
            return entries, [], []
        now = time.monotonic()
        counts: Dict[str, int] = {}
        need: Dict[str, int] = {}
        for req, is_vlm in entries:
            if req.group_id and req.group_n > 1 and not is_vlm:
                counts[req.group_id] = counts.get(req.group_id, 0) + 1
                need[req.group_id] = max(
                    need.get(req.group_id, 0), req.group_n
                )
        hold: set = set()
        deadlines: List[float] = []
        for gid, cnt in counts.items():
            if cnt >= need[gid]:
                self._group_first_seen.pop(gid, None)
                continue
            first = self._group_first_seen.setdefault(gid, now)
            if now - first < self.group_hold_s:
                hold.add(gid)
                deadlines.append(first + self.group_hold_s)
            else:  # TTL lapsed: admit the partial group
                self._group_first_seen.pop(gid, None)
        if not hold:
            return entries, [], []
        held = [r for r, v in entries if not v and r.group_id in hold]
        entries = [
            (r, v) for r, v in entries if v or r.group_id not in hold
        ]
        return entries, held, deadlines

    def _plan_clusters(
        self, entries: List[tuple], matched: set
    ) -> List[dict]:
        """Cluster the admission window by shared prompt prefix ->
        [{"members": [entry idx], "share": tokens}].

        Explicit groups (GRPO siblings carrying group_id) cluster by key in
        O(window); the rest cluster content-based — sorted by a bounded
        prefix key, then adjacent-lcp runs (lcp is an ultrametric, so the
        min over any chain through a set equals the set's lcp).  The
        shared span is capped at min(len) - 1 so every sibling still
        suffix-prefills at least one token (its last-position logits seed
        sampling); clusters whose span misses `share_min_tokens` dissolve.

        Entries already matched to a retained slot never become siblings
        (their own retained prefix is at least as long) but do serve as
        representatives — the fallback path where the cluster prefix is
        never recomputed at all."""
        cand = [
            i for i, (req, is_vlm) in enumerate(entries)
            if not is_vlm and len(req.input_ids) > self.share_min_tokens
        ]
        if len(cand) < 2:
            return []
        by_gid: Dict[str, List[int]] = {}
        rest: List[int] = []
        for i in cand:
            gid = entries[i][0].group_id
            (by_gid.setdefault(gid, []) if gid else rest).append(i)
        raw = [m for m in by_gid.values() if len(m) >= 2]
        rest.extend(i for m in by_gid.values() if len(m) == 1 for i in m)
        rest = rest[: self.match_window]  # bound the host-side sort/scan
        if len(rest) >= 2:
            rest.sort(key=lambda i: tuple(entries[i][0].input_ids[:64]))
            run = [rest[0]]
            run_share: Optional[int] = None
            for prev, cur in zip(rest, rest[1:]):
                l = lcp_ids(
                    entries[prev][0].input_ids, entries[cur][0].input_ids
                )
                tentative = l if run_share is None else min(run_share, l)
                if tentative >= self.share_min_tokens:
                    run.append(cur)
                    run_share = tentative
                else:
                    if len(run) >= 2:
                        raw.append(run)
                    run = [cur]
                    run_share = None
            if len(run) >= 2:
                raw.append(run)
        clusters: List[dict] = []
        for members in raw:
            ids0 = entries[members[0]][0].input_ids
            share = min(
                lcp_ids(ids0, entries[i][0].input_ids)
                for i in members[1:]
            )
            share = min(
                share,
                min(len(entries[i][0].input_ids) for i in members) - 1,
            )
            # a cluster of only retained-matched members has nothing to fan
            # out; require at least one potential sibling
            if share >= self.share_min_tokens and any(
                i not in matched for i in members
            ):
                clusters.append({"members": sorted(members), "share": share})
        return clusters

    def _admit(self) -> None:
        """Fill every free slot from the pending queue in ONE bucketed
        prefill call.  Rows are padded to a power of two; padding rows
        prefill a single token into the scratch slot (index n_slots), so
        compiled-program count stays O(log n_slots x log buckets) and a
        burst of N prompts no longer pays N sequential device round-trips
        (round-1 review weak #2).

        With kv_reuse, prompts whose prefix matches a freed slot's retained
        cache go through a SUFFIX prefill instead (forward_prefill_cached):
        multi-turn turns and interruption resumes pay O(new tokens).

        Abort-storm discipline (VERDICT r4 #3): a WINDOW of the pending
        queue is drained and prefix-matched against every free slot
        GLOBALLY (highest lcp wins) before any slot is handed to a fresh
        prompt, and abort-reserved slots are withheld from fresh prompts
        until their reservation lapses — so when N aborted clients race
        back over few slots, the retained prefixes go to the requests that
        can actually reuse them instead of to whoever arrived first.

        Group fan-out (ISSUE 2): remaining requests cluster by longest
        common prefix; each cluster prefills one representative, fans its
        prefix K/V out to sibling slots with a device-side cache copy, and
        the siblings suffix-prefill only their remainder — a GRPO group of
        G pays ~1/G of the old grouped prefill FLOPs.  Reservations keep
        applying per SLOT to the abort-resubmission flow: each aborted
        sibling reclaims its own retained slot through the global matching
        above (its retained prefix is strictly longer than the cluster's),
        so a storm never collapses a cluster onto one reserved slot."""
        free = [s for s in range(self.n_slots) if self.slot_req[s] is None]
        if not free:
            return
        if self._parked_free is not None:
            # a previous pass admitted nothing; until a reservation expires,
            # a group hold lapses, a slot frees, or a new request arrives,
            # rescanning would produce the same nothing
            if (
                not self.pending.qsize()
                and time.monotonic() < self._parked_until
                and frozenset(free) == self._parked_free
            ):
                return
            self._parked_free = None
        # intake: held-back requests first (FIFO across admission passes),
        # then drain fresh submissions up to the scan window.  The holdback
        # swap runs under the lock (ADVICE r5): a concurrent abort_all
        # either sees these requests in _holdback and finishes them, or the
        # generation counter tells this pass to drop its leftovers — never
        # a resurrection after their terminal 'abort' callback.
        with self._lock:
            abort_gen = self._abort_gen
            intake = self._holdback
            self._holdback = []
        while len(intake) < self.admission_window:
            try:
                intake.append(self.pending.get_nowait())
            except queue.Empty:
                break
        if not intake:
            return
        entries: List[tuple] = []  # (req, is_vlm) in arrival order
        for req in intake:
            if req.pixel_values is not None:
                if not self._vlm:
                    # "length" terminates the client's interruption loop;
                    # "abort" would make it resubmit the same request forever
                    req.finish("length")
                    logger.error(
                        f"request {req.rid} carries pixels but the model is "
                        "text-only; returned empty (config mismatch)"
                    )
                    continue
                err = self._validate_vlm_request(req)
                if err:
                    req.finish("length")
                    logger.error(f"rejecting VLM request {req.rid}: {err}")
                    continue
                entries.append((req, True))
            else:
                entries.append((req, False))
        held: List[GenRequest] = []
        group_deadlines: List[float] = []
        if self.share_prefix:
            entries, held, group_deadlines = self._apply_group_hold(entries)

        admitted: List[tuple] = []  # (slot, req)
        # suffix rows: (slot, req, start, kv_src, shared) — retained reuse
        # and cluster fan-out ride ONE bucketed call (the fan-out copy is
        # fused into the suffix program)
        reuse_admitted: List[tuple] = []
        vlm_admitted: List[tuple] = []
        shared_admitted: List[tuple] = []
        free_set = set(free)
        matched: set = set()
        slot_of_entry: Dict[int, tuple] = {}  # entry idx -> (slot, lcp)
        cands: List[tuple] = []  # (-lcp, entry idx, slot), sorted
        dev_claimed: set = set()  # slots won by a device-retained match
        # retention: entries whose match of a retained slot was partial
        cut_back: set = set()
        # retention: fresh cluster representatives, (slot, req, share, slot,
        # None) rows of the suffix dispatch whose prefix is prefilled first
        rep_admitted: List[tuple] = []
        if self.kv_reuse:
            # global matching through the radix index: ONE tree walk per
            # request returns the exact lcp against every resident prefix
            # (identical numbers to the old per-slot seq_tokens scan — the
            # entries mirror seq_tokens[:retained_len] by construction,
            # re-validated against the live retained_len so a stale entry
            # can cost a hit but never fabricate one).  All (request,
            # slot) pairs then assign greedily, best lcp first, ties by
            # arrival order; the scanned window stays capped at
            # match_window independently of the drain window.
            cand_set = {
                s for s in free
                if not self._slot_vlm[s]
                and self.retained_len[s] >= self.reuse_min_tokens
            }
            if cand_set:
                for i, (req, is_vlm) in enumerate(
                    entries[: self.match_window]
                ):
                    if is_vlm:
                        continue
                    # capped at len(ids) - 1 so at least one suffix token
                    # runs through prefill (its logits seed sampling)
                    limit = len(req.input_ids) - 1
                    for s, l in self.pool.match_device(
                        req.input_ids
                    ).items():
                        if s not in cand_set:
                            continue
                        toks = self.pool.device_tokens(s)
                        if toks is None or len(toks) != int(
                            self.retained_len[s]
                        ):
                            continue
                        l = min(int(l), limit)
                        if self._state and l != int(self.retained_len[s]):
                            # a state cannot be cut back to a prefix: only
                            # a prompt that extends the slot's WHOLE
                            # sequence (the next turn) continues from it
                            if l >= self.reuse_min_tokens:
                                cut_back.add(i)
                            continue
                        if l >= self.reuse_min_tokens:
                            # ties broken by arrival order (i ascending)
                            cands.append((-l, i, s))
                cands.sort()
                for negl, i, s in cands:
                    if i in matched or s not in free_set:
                        continue
                    matched.add(i)
                    free_set.remove(s)
                    dev_claimed.add(s)
                    slot_of_entry[i] = (s, -negl)
                    reuse_admitted.append((s, entries[i][0], -negl, s, False))
        if self.kv_reuse and self.pool.host is not None and free_set:
            self._swap_in_host_hits(
                entries, matched, free_set, slot_of_entry, reuse_admitted
            )

        # page-granular sub-prefix sharing (ISSUE 17 satellite): a request
        # whose best device match LOST its donor slot to a longer match
        # can still inherit the donor's prefix up to a page
        # (prompt-bucket) boundary — the fused fan-out copy duplicates
        # rows [0, span) of the donor's physical row into the loser's own
        # slot before the layer scan, and the loser suffix-prefills from
        # span on.  Safe by construction: the donor is CLAIMED this pass
        # (never handed to a fresh prompt, so its retained K/V survives
        # until the suffix dispatch) and its winner writes only from its
        # own lcp >= the loser's lcp >= span, so the copy reads settled
        # K/V even inside the one shared dispatch.  Exact-lcp IN-PLACE
        # partial hits (the greedy winners above) are untouched — page
        # rounding applies only to this new copy-based share path.
        partial_of: Dict[int, tuple] = {}  # entry idx -> (donor slot, span)
        if self.share_prefix and dev_claimed and not self._state:
            page = self.prompt_bucket
            for negl, i, s in cands:  # still sorted: longest span first
                if i in matched or i in partial_of or s not in dev_claimed:
                    continue
                span = ((-negl) // page) * page
                if span >= self.share_min_tokens:
                    partial_of[i] = (s, span)

        clusters: List[dict] = (
            self._plan_clusters(entries, matched) if self.share_prefix else []
        )
        cluster_of: Dict[int, int] = {}
        for cid, cl in enumerate(clusters):
            for i in cl["members"]:
                cluster_of[i] = cid
                # a retained-matched member is the preferred representative
                # — the fallback path where NOBODY recomputes the cluster
                # prefix (multi-turn branch points).  The share is capped
                # at its retained lcp: that span is valid in its row BEFORE
                # the suffix batch runs, so the fused fan-out copy and the
                # representative's own suffix can share one dispatch.
                if "rep_slot" not in cl and i in slot_of_entry:
                    s, lcp = slot_of_entry[i]
                    if self._state and lcp > cl["share"]:
                        # its state lies past the cluster's common prefix
                        continue
                    cl["rep_slot"] = s
                    cl["share"] = min(cl["share"], lcp)

        # fresh prompts take the remaining UNRESERVED slots, least-valuable
        # retained cache first; reserved slots stay parked for their
        # aborted owner's resubmission until the TTL lapses
        now = time.monotonic()
        for s in free_set:
            # owner never came back: the reservation lapses here (counted
            # once — the slot re-enters the open pool below) rather than
            # silently evaporating
            if 0.0 < self._reserved_until[s] <= now:
                self._reserved_until[s] = 0.0
                self.stats["reservations_lapsed"] += 1
        # open slots grouped by length-cohort tier, least-valuable retained
        # cache first within each tier; a request lands in the smallest
        # tier whose ceiling covers its prompt + max_new_tokens budget,
        # falling UP to roomier tiers when its cohort is full and DOWN
        # (optimistic placement, migration may follow) only as a last
        # resort — admission capacity is unchanged: a request is parked
        # only when NO open slot exists anywhere
        open_by_tier: List[List[int]] = [[] for _ in range(self.n_tiers)]
        for s in sorted(
            (s for s in free_set if self._reserved_until[s] <= now),
            key=lambda s: int(self.retained_len[s]),
        ):
            open_by_tier[int(self.slot_tier[s])].append(s)
        n_open = sum(len(t) for t in open_by_tier)

        def _pick_slot(req: GenRequest) -> Optional[int]:
            budget = len(req.input_ids) + req.max_new_tokens + 1
            pref = next(
                (t for t, b in enumerate(self.tier_bounds) if b >= budget),
                self.n_tiers - 1,
            )
            for t in list(range(pref, self.n_tiers)) + list(
                range(pref - 1, -1, -1)
            ):
                if open_by_tier[t]:
                    return open_by_tier[t].pop(0)
            return None

        leftover: List[GenRequest] = list(held)
        for i, (req, is_vlm) in enumerate(entries):
            if i in matched:
                continue
            if not n_open:
                leftover.append(req)
                if req.group_id:
                    # the group already had its co-resident window; a later
                    # pass must admit the leftover members immediately (they
                    # still content-cluster among themselves) instead of
                    # re-parking them for the hold TTL
                    self._group_first_seen[req.group_id] = 0.0
                continue
            s = _pick_slot(req)
            n_open -= 1
            if i in cut_back:
                self.stats["state_reuse_dropped"] += 1
            cid = cluster_of.get(i)
            if cid is not None and clusters[cid].get("rep_slot") is not None:
                shared_admitted.append(
                    (s, req, clusters[cid]["share"],
                     clusters[cid]["rep_slot"], True)
                )
            elif is_vlm:
                vlm_admitted.append((s, req))
            elif i in partial_of:
                # partial rows never become cluster representatives: their
                # copied span settles only inside the suffix dispatch, too
                # late for a sibling's fused copy to read
                donor, span = partial_of[i]
                self.stats["prefix_cache_partial_hits"] += 1
                shared_admitted.append((s, req, span, donor, True))
            elif self._state and cid is not None:
                # the representative of a retention cluster: the state its
                # siblings start from is the one after the SHARED span, so
                # that span alone is prefilled into its slot and it then
                # continues from there beside them, in the same dispatch
                clusters[cid]["rep_slot"] = s
                rep_admitted.append((s, req, clusters[cid]["share"], s, None))
            else:
                admitted.append((s, req))
                if cid is not None:
                    # first member to land a slot becomes the cluster's
                    # representative; later members fan out from it
                    clusters[cid]["rep_slot"] = s
        # a representative whose siblings all stayed behind shares nothing
        sources = {row[3] for row in shared_admitted}
        for row in [r for r in rep_admitted if r[0] not in sources]:
            rep_admitted.remove(row)
            admitted.append(row[:2])
        finish_aborted: List[GenRequest] = []
        with self._lock:
            if self._abort_gen != abort_gen:
                # an abort_all landed mid-pass and already finished every
                # request it could see; the ones we drained would otherwise
                # be resurrected behind their terminal callback.  finish()
                # runs user callbacks — defer it past the lock (C5)
                finish_aborted = leftover
                leftover = []
            else:
                # merge, don't overwrite: a concurrent submit may have
                # repopulated _holdback since the intake swap (C5
                # atomicity-split on the guarded field)
                self._holdback = leftover + self._holdback
        for req in finish_aborted:
            req.finish("abort")
        if leftover and not (
            admitted or reuse_admitted or vlm_admitted or shared_admitted
            or rep_admitted
        ):
            # everything parked behind reservations or a group hold: arm
            # the no-progress guard until the earliest one expires
            expiries = [
                float(self._reserved_until[s])
                for s in free
                if self._reserved_until[s] > now
            ] + group_deadlines
            self._parked_free = frozenset(free)
            self._parked_until = min(expiries) if expiries else now + 0.05
        # prefix-cache accounting: every admitted row is a hit (inherited
        # a resident prefix) or a miss (cold/VLM prefill); retained
        # prefixes about to be overwritten spill to the host tier BEFORE
        # any prefill dispatch can clobber their rows
        self.stats["prefix_cache_hits"] += (
            len(reuse_admitted) + len(shared_admitted)
        )
        self.stats["prefix_cache_misses"] += (
            len(admitted) + len(vlm_admitted) + len(rep_admitted)
        )
        # members of a declared group that compute their whole prompt
        # although a sibling was admitted before them (a state is not
        # there to share once its owner decodes on)
        for req, whole in (
            [(r, True) for _, r in admitted]
            + [(row[1], True) for row in rep_admitted]
            + [(row[1], False) for row in reuse_admitted + shared_admitted]
        ):
            if not (req.group_id and req.group_n > 1):
                continue
            seen = self._group_admitted.get(req.group_id, 0)
            if seen and whole and self._state:
                self.stats["sibling_reprefills"] += 1
            if seen + 1 >= req.group_n:
                self._group_admitted.pop(req.group_id, None)
            else:
                self._group_admitted[req.group_id] = seen + 1
        overwrite = (
            [s for s, _ in admitted]
            + [s for s, _ in vlm_admitted]
            + [s for s, *_ in shared_admitted]
        )
        if overwrite:
            self._maybe_spill(overwrite)
        # recorded before the prefill dispatches so the admission event
        # always precedes the request's first decode/finish in the log
        now_pc = time.perf_counter()
        for s, req in admitted:
            self._record_admission(req, s, "fresh", 0, now_pc)
        for s, req in vlm_admitted:
            self._record_admission(req, s, "vlm", 0, now_pc)
        for s, req, start, _, shared in reuse_admitted + shared_admitted:
            self._record_admission(
                req, s, "shared" if shared else "reuse", start, now_pc
            )
        for s, req, *_ in rep_admitted:
            self._record_admission(req, s, "fresh", 0, now_pc)
        if vlm_admitted:
            self._admit_vlm_batch(vlm_admitted)
        if admitted:
            self._admit_fresh_batch(admitted)
        if rep_admitted:
            self._prefill_shared_spans(rep_admitted)
        if reuse_admitted or shared_admitted:
            # one suffix call for retained reuse AND cluster siblings: by
            # now every copy source row holds its cluster prefix (fresh
            # representatives prefilled above; retained representatives'
            # shares were capped at their already-valid lcp), so the fused
            # fan-out copy inside the program reads only settled K/V
            self._admit_suffix_batch(
                reuse_admitted + shared_admitted + rep_admitted
            )

    def _record_admission(
        self, req: GenRequest, slot: int, kind: str, inherited: int,
        now_pc: float,
    ) -> None:
        """One admitted request: its queue wait (submit -> slot grant,
        covering holdback/group-hold) goes to the stats counters and the
        histogram always; with telemetry enabled, also the admission +
        prefill lifecycle events with the cold/inherited prefill token
        split (`kind` says whether the inherited span came from a retained
        prefix or a fan-out share)."""
        wait = max(0.0, now_pc - req.submit_ts) if req.submit_ts else 0.0
        self.stats["t_queue_wait_s"] += wait
        self.stats["admitted"] += 1
        telemetry.ADMISSION_WAIT.observe(wait)
        if not telemetry.is_enabled():
            return
        tid = req.trace_id or req.rid
        telemetry.emit(
            "admission", trace_id=tid, kind=kind, slot=int(slot),
            tier=int(self.slot_tier[slot]), queue_wait_s=wait,
        )
        total = len(req.input_ids)
        telemetry.emit(
            "prefill", trace_id=tid, kind=kind, total_tokens=total,
            inherited_tokens=int(inherited),
            cold_tokens=total - int(inherited),
        )

    def _assign_streams(
        self, reqs: List[GenRequest], n_rows: int
    ) -> np.ndarray:
        """Counter-keyed sampler streams for one admission batch, assigned
        BEFORE the prefill dispatch (the batch's first sampled token is
        already stream-keyed).  Fresh requests draw from the shared
        allocator in batch (arrival) order — the partition-invariance
        contract — while a nonzero req.stream_id (a disaggregated handoff
        continuing another server's stream) is honored verbatim.
        Allocated ids are written back to req.stream_id so a prefill-role
        server can hand its stream over the wire.  Pad rows keep stream 0
        (never allocated; their samples land in the scratch slot and are
        discarded)."""
        streams = np.zeros(n_rows, np.int32)
        with self._lock:
            for i, req in enumerate(reqs):
                if req.stream_id:
                    streams[i] = req.stream_id
                else:
                    streams[i] = self._next_stream
                    self._next_stream += 1
                    req.stream_id = int(streams[i])
        return streams

    def _admit_fresh_batch(self, admitted: List[tuple]) -> None:
        """Full prefill for prompts with no reusable prefix anywhere: ONE
        bucketed forward_prefill call (pow2 rows, scratch-slot padding)."""
        bucket = round_up_to_bucket(
            max(max(len(r.input_ids) for _, r in admitted), 1),
            self.prompt_bucket,
            self.max_seq_len,
        )
        if self._split_dispatch(
            self._admit_fresh_batch, admitted, self._fresh_weight(bucket)
        ):
            return
        S = 1 << (len(admitted) - 1).bit_length()  # power-of-two rows
        ids = np.zeros((S, bucket), np.int32)
        plens = np.ones(S, np.int32)
        slot_ids = np.full(S, self.n_slots, np.int32)  # default: scratch
        temp = np.ones(S, np.float32)
        top_p = np.ones(S, np.float32)
        top_k = np.zeros(S, np.int32)
        for i, (s, req) in enumerate(admitted):
            n = len(req.input_ids)
            ids[i, :n] = req.input_ids
            plens[i] = n
            slot_ids[i] = self.pool.row(s)  # write through the page table
            temp[i] = req.temperature
            top_p[i] = req.top_p
            top_k[i] = req.top_k
        streams = self._assign_streams([r for _, r in admitted], S)
        toks, logps, self.cache = self._prefill_fn(
            self.params,
            self.cache,
            ids,
            jnp.asarray(plens),
            jnp.asarray(slot_ids),
            jnp.asarray(streams),
            self._decode_key,
            jnp.asarray(temp),
            jnp.asarray(top_p),
            jnp.asarray(top_k),
        )
        self._launched()
        with telemetry.span("admit_fetch", self.stats):
            # areal-lint: disable=host-sync delivery point: one batched fetch per admission pass hands sampled tokens to the host scheduler
            toks, logps = np.asarray(toks), np.asarray(logps)
        self._landed(self._n_launched)
        self.stats["prefill_calls"] += 1
        self.stats["prefill_tokens"] += int(plens[: len(admitted)].sum())
        with self._lock:
            for i, (s, req) in enumerate(admitted):
                # a retained prefix that neither matched nor spilled is
                # evicted by this overwrite
                if self.pool.drop_device(s):
                    self.stats["prefix_cache_evictions"] += 1
                self.slot_req[s] = req
                self.lengths[s] = plens[i]
                self.rope_pos[s] = plens[i]
                self.last_tokens[s] = int(toks[i])
                self.temperature[s] = req.temperature
                self.top_p[s] = req.top_p
                self.top_k[s] = req.top_k
                self.retained_len[s] = 0
                self._state_len[s] = plens[i]
                self._reserved_until[s] = 0.0
                self._slot_vlm[s] = False
                self.kv_version[s] = self.version
                # decode-key stream: assigned in batch (arrival) order by
                # _assign_streams so sampled streams are identical however
                # slots are tiered (or pinned by a handoff's stream_id)
                self.stream_ids[s] = streams[i]
                n = len(req.input_ids)
                self.seq_tokens[s, :n] = req.input_ids
            self._state_dirty = True
        for i, (s, req) in enumerate(admitted):
            self._record_token(s, int(toks[i]), float(logps[i]))

    def _fresh_weight(self, bucket: int) -> int:
        """What a row of the fresh-prefill program weighs against
        `_state_admit_tokens`: its bucket, but `max_seq_len` for long rows:
        ONE row a dispatch whatever its bucket (a row of `max_seq_len` is
        what fits; fewer programs for the shorter buckets)."""
        return self.max_seq_len if self._long_rows else bucket

    def _split_dispatch(self, admit, rows: List[tuple], bucket: int) -> bool:
        """A hybrid stack's prefill of more than `_state_admit_tokens`
        padded tokens goes in several dispatches of `admit`, in the rows'
        order; -> whether it did."""
        if self._state_admit_tokens is None:
            return False
        per = max(1, self._state_admit_tokens // bucket)
        if len(rows) <= per:
            return False
        for i in range(0, len(rows), per):
            admit(rows[i: i + per])
        return True

    def _prefill_shared_spans(self, reps: List[tuple]) -> None:
        """A state, or a ring: put the state after each cluster's SHARED span
        into its representative's slot, with the fresh-prefill program.
        Nothing is sampled for anyone and nothing is fetched; the suffix
        dispatch that follows starts every member, the representative too,
        from that state."""
        bucket = round_up_to_bucket(
            max(start for _, _, start, _, _ in reps),
            self.prompt_bucket, self.max_seq_len,
        )
        if self._split_dispatch(
            self._prefill_shared_spans, reps, self._fresh_weight(bucket)
        ):
            return
        S = 1 << (len(reps) - 1).bit_length()
        ids = np.zeros((S, bucket), np.int32)
        plens = np.ones(S, np.int32)
        slot_ids = np.full(S, self.n_slots, np.int32)  # pad rows: scratch
        for i, (s, req, start, _, _) in enumerate(reps):
            ids[i, :start] = req.input_ids[:start]
            plens[i] = start
            slot_ids[i] = self.pool.row(s)
        _, _, self.cache = self._prefill_fn(
            self.params, self.cache, ids, jnp.asarray(plens),
            jnp.asarray(slot_ids), jnp.zeros(S, jnp.int32), self._decode_key,
            jnp.ones(S, jnp.float32), jnp.ones(S, jnp.float32),
            jnp.zeros(S, jnp.int32),
        )
        self._launched()  # landed with the suffix dispatch that follows
        self.stats["prefill_calls"] += 1
        self.stats["prefill_tokens"] += int(plens[: len(reps)].sum())

    def _admit_suffix_batch(self, batch: List[tuple]) -> None:
        """Suffix-only prefill into slots whose cache (about to) hold the
        prompt's prefix: ONE bucketed forward_prefill_cached call, same
        O(log) compiled-program discipline as fresh admission.

        `batch` rows are (slot, req, start, kv_src, shared): `start` counts
        prompt tokens the row inherits rather than recomputes — the
        retained lcp, or the cluster's shared span — and `kv_src` is the
        slot whose cache computed them (the slot itself for retained
        reuse, the cluster representative for fan-out siblings).  Shared
        rows get their prefix K/V via the copy FUSED into the suffix
        program (ops/kv_copy.py; retained rows self-copy as identity), so
        retained reuse and group fan-out cost one dispatch together.
        kv_src's kv_version propagates so strict-version audits stay
        exact; `shared` picks the stat bucket for the skipped tokens."""
        bucket = round_up_to_bucket(
            max(len(r.input_ids) - start for _, r, start, _, _ in batch),
            self.prompt_bucket,
            self.max_seq_len,
        )
        # in order: fan-out siblings come before the representatives whose
        # state they start from, which continue from it last.  A row of
        # latent attention brings a window beside its suffix (the fan-out
        # copy gathers every row's shared span at once: 64 rows of 8,192
        # positions did not fit the chip), so it weighs an eighth of
        # max_seq_len at least: eight rows a dispatch of short suffixes
        weight = (
            max(bucket, self.max_seq_len // 8) if self._long_rows else bucket
        )
        if self._split_dispatch(self._admit_suffix_batch, batch, weight):
            return
        S = 1 << (len(batch) - 1).bit_length()
        if self._long_rows:
            # ... and always that many (scratch rows fill up, their blocks
            # of attention are skipped): one program a (bucket, copied
            # span, window), not one for every count of siblings
            S = max(S, 1 << (self._state_admit_tokens // weight).bit_length() - 1)
        ids = np.zeros((S, bucket), np.int32)
        starts = np.zeros(S, np.int32)
        slens = np.ones(S, np.int32)
        slot_ids = np.full(S, self.n_slots, np.int32)
        copy_src = np.full(S, self.n_slots, np.int32)  # pad: scratch
        temp = np.ones(S, np.float32)
        top_p = np.ones(S, np.float32)
        top_k = np.zeros(S, np.int32)
        max_shared = 0
        for i, (s, req, start, kv_src, shared) in enumerate(batch):
            suffix = req.input_ids[start:]
            n = len(suffix)
            ids[i, :n] = suffix
            starts[i] = start
            slens[i] = n
            slot_ids[i] = self.pool.row(s)  # physical rows: page table
            copy_src[i] = self.pool.row(kv_src)
            temp[i] = req.temperature
            top_p[i] = req.top_p
            top_k[i] = req.top_k
            if shared:
                max_shared = max(max_shared, start)
        # bucketed fan-out span; 0 (no shared rows) skips the copy and
        # compiles the same retained-only program as before
        copy_block = (
            round_up_to_bucket(max_shared, self.prompt_bucket,
                               self.max_seq_len)
            if max_shared and self._columns else 0
        )
        # bucketed attended span: attention reads O(P x key_window), not
        # O(P x max_seq_len) — short sequences in a deep cache stop paying
        # for the whole row
        key_window = round_up_to_bucket(
            int((starts[: len(batch)] + slens[: len(batch)]).max()),
            self.prompt_bucket,
            self.max_seq_len,
        )
        if not self._columns:
            # a state has no columns to window: a row starts from the whole
            # state of `copy_src`, so the suffix program comes in one shape
            # per (rows, bucket)
            key_window = 0
        elif copy_block and self._state:
            # rows of several groups share a dispatch (siblings of one, the
            # representative of another), so the copied span and the window
            # vary apart: one program a window, whatever the mix.  (A closed
            # loop's clipped budgets end groups TOGETHER, and the mix of the
            # groups that replace them is one no warm-up has met: a pair of
            # its own compiled inside `rollout_ssm_dense_4k`'s window, PR 53)
            copy_block = key_window
        if self._state:
            # rows that start from ANOTHER slot's state (the group fan-out):
            # the state (and, hybrid, the convolution windows) whole, and
            # the K/V columns of the shared span where the slot has any
            n_copies = sum(1 for s, _, _, src, _ in batch if src != s)
            self.stats["state_copies"] += n_copies
            self.stats["state_copy_bytes"] += n_copies * (
                self._state_bytes + copy_block * self._kv_token_bytes
            )
            if self._window:
                self.stats["window_copies"] += n_copies
                self.stats["window_copy_bytes"] += n_copies * self._state_bytes
        streams = self._assign_streams([r for _, r, *_ in batch], S)
        toks, logps, self.cache = self._suffix_prefill_fn(
            self.params,
            self.cache,
            ids,
            jnp.asarray(starts),
            jnp.asarray(slens),
            jnp.asarray(slot_ids),
            jnp.asarray(copy_src),
            jnp.asarray(streams),
            self._decode_key,
            jnp.asarray(temp),
            jnp.asarray(top_p),
            jnp.asarray(top_k),
            copy_block,
            key_window,
        )
        self._launched()
        with telemetry.span("admit_fetch", self.stats):
            # areal-lint: disable=host-sync delivery point: one batched fetch per suffix-admission pass (retained reuse + fan-out share it)
            toks, logps = np.asarray(toks), np.asarray(logps)
        self._landed(self._n_launched)
        self.stats["suffix_calls"] += 1
        if copy_block:
            self.stats["copy_calls"] += 1
        self.stats["suffix_tokens"] += int(slens[: len(batch)].sum())
        for i, (_, _, start, _, shared) in enumerate(batch):
            if shared is not None:  # None: its own span, prefilled just now
                self.stats[
                    "shared_tokens" if shared else "reused_tokens"
                ] += int(start)
        with self._lock:
            for i, (s, req, start, kv_src, shared) in enumerate(batch):
                n_total = len(req.input_ids)
                # the slot's index entry retires: consumed by its own hit
                # (retained reuse — not an eviction) or clobbered by a
                # fan-out sibling landing on it (counted)
                if self.pool.drop_device(s) and shared:
                    self.stats["prefix_cache_evictions"] += 1
                req.cache_hit_tokens = 0 if shared is None else int(start)
                self.slot_req[s] = req
                self.lengths[s] = n_total
                self._state_len[s] = n_total
                self.rope_pos[s] = n_total
                self.last_tokens[s] = int(toks[i])
                self.temperature[s] = req.temperature
                self.top_p[s] = req.top_p
                self.top_k[s] = req.top_k
                self.retained_len[s] = 0
                self._reserved_until[s] = 0.0
                # oldest KV in the slot: the inherited prefix's version
                # (suffix tokens are current-version by construction)
                self.kv_version[s] = min(
                    int(self.kv_version[kv_src]), self.version
                )
                self.stream_ids[s] = streams[i]
                self.seq_tokens[s, :n_total] = req.input_ids
            self._state_dirty = True
        for i, (s, req, _, _, _) in enumerate(batch):
            self._record_token(s, int(toks[i]), float(logps[i]))

    def _validate_vlm_request(self, req: GenRequest) -> Optional[str]:
        """Reject malformed wire inputs BEFORE they reach the decode worker:
        a bad grid must not hang or abort-storm the whole server."""
        cfg = self.model_config
        m = cfg.vision.spatial_merge_size
        try:
            grid = np.asarray(req.image_grid_thw, np.int64).reshape(-1, 3)
            pv = np.asarray(req.pixel_values)
        except (ValueError, TypeError) as e:
            return f"malformed pixel inputs: {e}"
        if pv.ndim != 2 or pv.shape[1] != cfg.vision.patch_dim:
            return (
                f"pixel_values shape {pv.shape} != [N, {cfg.vision.patch_dim}]"
            )
        if (grid <= 0).any():
            return f"non-positive grid entries: {grid.tolist()}"
        if ((grid[:, 1] % m) != 0).any() or ((grid[:, 2] % m) != 0).any():
            return f"grid h/w must divide merge size {m}: {grid.tolist()}"
        n_patches = int((grid[:, 0] * grid[:, 1] * grid[:, 2]).sum())
        if n_patches != pv.shape[0]:
            return f"grid implies {n_patches} patches, got {pv.shape[0]}"
        n_placeholders = int(
            np.sum(np.asarray(req.input_ids) == cfg.image_token_id)
        )
        expected = int(
            (grid[:, 0] * (grid[:, 1] // m) * (grid[:, 2] // m)).sum()
        )
        if n_placeholders != expected:
            return (
                f"{n_placeholders} image placeholders but grids imply "
                f"{expected} merged embeddings"
            )
        return None

    def _admit_vlm_batch(self, vlm_admitted: List[tuple]) -> None:
        """Image-conditioned prefill for a batch of requests: ONE vision
        tower call over all patches and ONE bucketed prefill (the same
        O(log)-programs admission discipline as the text path).  Merged
        embeddings concatenate in request order, which matches the
        flattened row order the in-prefill scatter consumes; each slot's
        logical rope position continues past its images' compressed extent
        while the cache index tracks real tokens."""
        from areal_tpu.models.vision import mrope_position_ids

        cfg = self.model_config
        m2 = cfg.vision.spatial_merge_size ** 2
        bucket = round_up_to_bucket(
            max(len(r.input_ids) for _, r in vlm_admitted),
            self.prompt_bucket,
            self.max_seq_len,
        )
        S = 1 << (len(vlm_admitted) - 1).bit_length()
        ids = np.zeros((S, bucket), np.int32)
        mpos = np.zeros((3, S, bucket), np.int32)
        plens = np.ones(S, np.int32)
        slot_ids = np.full(S, self.n_slots, np.int32)
        temp = np.ones(S, np.float32)
        top_p = np.ones(S, np.float32)
        top_k = np.zeros(S, np.int32)
        rope_next = np.zeros(S, np.int32)
        pv_parts, grids = [], []
        for i, (s, req) in enumerate(vlm_admitted):
            r_ids = np.asarray(req.input_ids, np.int32)
            n = len(r_ids)
            ids[i, :n] = r_ids
            plens[i] = n
            slot_ids[i] = self.pool.row(s)
            temp[i] = req.temperature
            top_p[i] = req.top_p
            top_k[i] = req.top_k
            grid = np.asarray(req.image_grid_thw, np.int64).reshape(-1, 3)
            r_mpos = mrope_position_ids(
                r_ids, grid, cfg.image_token_id,
                spatial_merge_size=cfg.vision.spatial_merge_size,
            )
            mpos[:, i, :n] = r_mpos
            rope_next[i] = int(r_mpos.max()) + 1
            pv_parts.append(np.asarray(req.pixel_values, np.float32))
            grids.append(grid)

        pv_all = np.concatenate(pv_parts, axis=0)
        n_patches = pv_all.shape[0]
        # bucket the patch count (pow2 multiples of the merge group) so the
        # vision jit compiles O(log) variants; pad patches carry img id -1
        n_pad = m2 * (
            1 << max(0, (max(1, (n_patches + m2 - 1) // m2) - 1).bit_length())
        )
        pv_pad = np.zeros((n_pad, pv_all.shape[1]), np.float32)
        pv_pad[:n_patches] = pv_all
        img_ids = np.full(n_pad, -1, np.int32)
        ofs = gid = 0
        for grid in grids:
            for t, h, w in grid:
                n = int(t * h * w)
                img_ids[ofs : ofs + n] = gid
                ofs += n
                gid += 1
        from areal_tpu.models.vision import vision_rot_pos_ids

        pos_hw = np.zeros((n_pad, 2), np.int32)
        real_pos = vision_rot_pos_ids(
            np.concatenate(grids), cfg.vision.spatial_merge_size
        )
        pos_hw[: real_pos.shape[0]] = real_pos
        embeds = self._embed_images_fn(
            self.params["vision"],
            jnp.asarray(pv_pad, jnp.dtype(cfg.dtype)),
            jnp.asarray(img_ids),
            jnp.asarray(pos_hw),
        )
        self.rng, sub = jax.random.split(self.rng)
        toks, logps, self.cache = self._vlm_prefill_fn(
            self.params,
            self.cache,
            ids,
            jnp.asarray(mpos),
            embeds,
            jnp.asarray(plens),
            jnp.asarray(slot_ids),
            sub,
            jnp.asarray(temp),
            jnp.asarray(top_p),
            jnp.asarray(top_k),
        )
        self._launched()
        with telemetry.span("admit_fetch", self.stats):
            # areal-lint: disable=host-sync delivery point: one batched fetch per VLM admission pass
            toks, logps = np.asarray(toks), np.asarray(logps)
        self._landed(self._n_launched)
        with self._lock:
            for i, (s, req) in enumerate(vlm_admitted):
                if self.pool.drop_device(s):
                    self.stats["prefix_cache_evictions"] += 1
                self.slot_req[s] = req
                self.lengths[s] = plens[i]
                self.rope_pos[s] = rope_next[i]
                self.last_tokens[s] = int(toks[i])
                self.temperature[s] = req.temperature
                self.top_p[s] = req.top_p
                self.top_k[s] = req.top_k
                # mrope decouples rope from cache index: prefix reuse would
                # need the image context too — VLM slots never retain
                self._slot_vlm[s] = True
                self.retained_len[s] = 0
                self._reserved_until[s] = 0.0
                self.kv_version[s] = self.version
                self.stream_ids[s] = self._next_stream
                self._next_stream += 1
            self._state_dirty = True
        for i, (s, req) in enumerate(vlm_admitted):
            self._record_token(s, int(toks[i]), float(logps[i]))

    def _record_token(self, s: int, tok: int, logp: float) -> None:
        req = self.slot_req[s]
        if req is None:  # aborted between decode and delivery
            return
        req.output_tokens.append(tok)
        req.output_logprobs.append(logp)
        req.output_versions.append(self.version)
        if req.first_token_ts == 0.0:
            req.first_token_ts = time.perf_counter()
        # the sampled token's K/V lands at cache position lengths[s] on the
        # next decode step; mirror it for prefix matching
        self.seq_tokens[s, min(int(self.lengths[s]), self.max_seq_len - 1)] = tok
        n_out = len(req.output_tokens)
        stop_ids = req.stop_token_ids or (
            [self.model_config.eos_token_id]
            if self.model_config.eos_token_id is not None
            else []
        )
        hit_stop = tok in stop_ids and n_out >= req.min_new_tokens
        total_len = self.lengths[s] + 1  # prompt + generated so far
        if hit_stop:
            self._free(s, "stop")
        elif n_out >= req.max_new_tokens or total_len + 1 >= self.max_seq_len:
            self._free(s, "length")

    def _retained_after(self, s: int) -> int:
        """What slot `s`, being freed, keeps for a later prompt: the
        cache-backed prefix (positions < lengths; the pending last token
        was never written).  Nothing for a VLM slot, nor for a retention
        state that ran past `lengths`."""
        if self._slot_vlm[s] or (
            self._state and self._state_len[s] != self.lengths[s]
        ):
            return 0
        return int(self.lengths[s])

    def _free(self, s: int, reason: str) -> None:
        req = self.slot_req[s]
        with self._lock:
            self.slot_req[s] = None
            self.retained_len[s] = self._retained_after(s)
            self.pool.note_free(
                s, self.seq_tokens[s], int(self.retained_len[s])
            )
            self._state_dirty = True
        if req is not None:
            req.finish(reason)

    def tier_occupancy(self) -> List[int]:
        """Active slots per length-cohort tier (metrics surface).  Called
        from the server's metrics thread while the worker mutates
        slot_req — snapshot under the lock."""
        with self._lock:
            return [
                sum(
                    self.slot_req[s] is not None
                    for s in range(
                        self.tier_start[t],
                        self.tier_start[t] + self.tier_size[t],
                    )
                )
                for t in range(self.n_tiers)
            ]

    def spec_acceptance_rates(self) -> List[float]:
        """Windowed per-tier draft acceptance rate steering the D ladder
        (metrics surface; 0.0 before any verify dispatch has reported)."""
        return [
            self._spec.acceptance_rate(t) or 0.0
            for t in range(self.n_tiers)
        ]

    def decode_attended_fraction(self) -> float:
        """Attended span / configured ceiling over all decode dispatches:
        1.0 means decode paid the full `max_seq_len` width (the pre-window
        behavior); the bucketed key-window drives this toward
        occupied/ceiling."""
        ceiling = self.stats["decode_ceiling_cols"]
        return (
            self.stats["decode_attended_cols"] / ceiling if ceiling else 1.0
        )

    def prefix_cache_hit_rate(self) -> float:
        """Fraction of admissions that reused resident K/V through the
        radix/paged pool (device hits + host swap-ins) over all
        admissions; the /metrics gauge mirrors this."""
        h = self.stats["prefix_cache_hits"]
        m = self.stats["prefix_cache_misses"]
        return h / (h + m) if (h + m) else 0.0

    def _plan_migrations(self, n: int) -> None:
        """Move slots about to outgrow their tier's ceiling into a roomier
        cohort.  Since decode reads the cache through the page table
        (ISSUE 16), a migration is a pure HOST-SIDE row remap — zero
        device copies, zero new programs: the request keeps its physical
        row under a new logical slot, and the destination's old retained
        prefix re-homes at the vacated slot (still radix-matchable, where
        the old copy path destroyed it).  When nothing roomier is free the
        slot simply stays — its own tier's K bucket grows to cover it
        (the top-tier fallback: ceilings are placement hints, never
        correctness)."""
        if self.n_tiers == 1:
            return
        now = time.monotonic()
        free_by_tier: List[List[int]] = [[] for _ in range(self.n_tiers)]
        for s in range(self.n_slots):
            if self.slot_req[s] is None and self._reserved_until[s] <= now:
                # prefer overwriting the least valuable retained cache
                free_by_tier[int(self.slot_tier[s])].append(s)
        for t in range(self.n_tiers):
            free_by_tier[t].sort(key=lambda s: int(self.retained_len[s]))
        moves: List[tuple] = []  # (src, dst)
        for s in range(self.n_slots):
            req = self.slot_req[s]
            t = int(self.slot_tier[s])
            if req is None or t == self.n_tiers - 1:
                continue
            if int(self.lengths[s]) + n < self.tier_bounds[t]:
                continue  # still inside its cohort for this whole chunk
            remaining = max(0, req.max_new_tokens - len(req.output_tokens))
            need = min(int(self.lengths[s]) + remaining + 1, self.max_seq_len)
            dst = None
            for u in range(t + 1, self.n_tiers):
                if self.tier_bounds[u] >= min(
                    need, int(self.lengths[s]) + n + 1
                ) and free_by_tier[u]:
                    # smallest adequate tier; the top tier always qualifies
                    if self.tier_bounds[u] >= need or u == self.n_tiers - 1:
                        dst = free_by_tier[u].pop(0)
                        break
            if dst is not None:
                moves.append((s, dst))
        if not moves:
            return
        with self._lock:
            for s, dst in moves:
                req = self.slot_req[s]
                if req is None:  # aborted since planning
                    continue
                dst_retained = int(self.retained_len[dst])
                dst_version = int(self.kv_version[dst])
                dst_vlm = bool(self._slot_vlm[dst])
                dst_tokens = self.seq_tokens[dst].copy()
                self.slot_req[dst] = req
                self.slot_req[s] = None
                for arr in (
                    self.lengths, self.rope_pos, self.last_tokens,
                    self.temperature, self.top_p, self.top_k,
                    self.kv_version, self.stream_ids, self._slot_vlm,
                ):
                    arr[dst] = arr[s]
                self.seq_tokens[dst] = self.seq_tokens[s]
                self.retained_len[dst] = 0
                self._reserved_until[dst] = 0.0
                # zero-copy remap: the request's KV follows it to `dst`
                # through the page table, and `dst`'s old retained prefix
                # (physical row + radix entry) re-homes at the vacated
                # logical slot — nothing is destroyed, nothing moves
                self.pool.swap(s, dst)
                self.seq_tokens[s] = dst_tokens
                self.retained_len[s] = (
                    0 if dst_vlm else dst_retained
                )
                self.kv_version[s] = dst_version
                self._slot_vlm[s] = dst_vlm
                self._reserved_until[s] = 0.0
                self.stats["tier_migrations"] += 1
            self._state_dirty = True

    def _sync_device_state(self) -> None:  # holds: _lock
        """(Re)build the device-resident decode state from the host
        bookkeeping mirrors.  Runs only when a host-side mutation
        (admission, free, migration, abort) dirtied the mirrors — the
        steady-state decode loop chains the previous chunk's outputs
        instead (C2 host-upload discipline: uploads live HERE, never per
        dispatch)."""
        active = np.asarray(
            [r is not None for r in self.slot_req], bool
        )
        # uploads are COMMITTED to the replicated sharding the chunk
        # programs emit: an uncommitted jnp.asarray here and a chained
        # chunk output there would otherwise each mint their own
        # executable per static signature (2x every decode/verify
        # program — pinned by the ragged soak's exact accounting)
        put = functools.partial(jax.device_put, device=self._rep_sharding)
        self._dev_state = {
            "tokens": put(self.last_tokens),
            "lengths": put(self.lengths),
            "rope_pos": put(self.rope_pos),
            "streams": put(self.stream_ids),
            "active": put(active),
            "temp": put(self.temperature),
            "top_p": put(self.top_p),
            "top_k": put(self.top_k),
            # page table: logical slot -> physical cache row (migration
            # remaps dirty the state, so this re-uploads exactly when it
            # changes and never per dispatch)
            "rows": put(self.pool.device_rows()),
            # stay on the host: the rows just uploaded as live, for
            # stats["state_rows_stepped"], and the sampler's own predicate
            # over them, for stats["sampler_window_passes"]
            "live": active,
            "wants_window": active
            & ((self.top_k > 0) | (self.top_p < 1.0))
            & (self.temperature > 0.0),
        }
        self._state_dirty = False
        self.stats["state_syncs"] += 1

    def _count_passes(
        self, st, base: int, size: int, n: int, kernel: bool = False
    ) -> None:
        """`n` passes over slots [base, base+size) were dispatched from the
        snapshot `st`, through the kind's decode kernel or not.  A state
        kernel steps the block's live rows and leaves the others where they
        lie; the plain path reads and rewrites every row of the block."""
        self.stats["decode_passes"] += n
        if self._holds_state:
            rows = int(st["live"][base:base + size].sum()) if kernel else size
            self.stats["state_rows_stepped"] += n * rows
        if st["wants_window"][base:base + size].any():
            self.stats["sampler_window_passes"] += n

    def _dispatch_ragged(self, st, n, active, spec_plan) -> List[tuple]:
        """ISSUE 19: advance the WHOLE slot grid in one fused ragged
        dispatch.  The Pallas kernel gathers each slot's true page span
        through the page table, so the per-tier dispatch fan-out (one
        program per occupied length cohort) collapses into a single
        program per step; tiers remain as admission/migration placement
        policy but no longer cost a dispatch each.  When any tier drafted
        this step, every slot rides ONE grid-wide verify at the largest
        chosen D — draftless slots carry draft_lens=0 and emit exactly
        their plain-decode token (the counter-keyed sampler makes the
        stream partition-invariant, so collapsing dispatches cannot
        change it).  Returns dev_outs entries for step()'s delivery loop
        (tier label -1 = collapsed grid)."""
        M = self.max_seq_len
        page = self.prompt_bucket
        span = int(max(self.lengths[s] for s in active))
        lens = self.lengths[: self.n_slots].astype(np.int64)
        if spec_plan:
            d_grid = max(self._spec_tier_d[t] for t in spec_plan)
            self._spec_grid_d = d_grid
            drafts = np.zeros((self.n_slots, d_grid), np.int32)
            dlens = np.zeros(self.n_slots, np.int32)
            for t, (dr, dl) in spec_plan.items():
                lo = self.tier_start[t]
                hi = lo + self.tier_size[t]
                drafts[lo:hi, : dr.shape[1]] = dr
                dlens[lo:hi] = dl
            if self.decode_window:
                key_window = round_up_to_bucket(
                    span + d_grid + 1, page, M
                )
            else:
                key_window = M
            out_t, nem_t, self.cache, tok, ln, rp = self._verify_fn(
                self.params,
                self.cache,
                st["tokens"],
                st["lengths"],
                st["rope_pos"],
                st["streams"],
                st["active"],
                st["temp"],
                st["top_p"],
                st["top_k"],
                self._decode_key,
                st["rows"],
                jnp.asarray(drafts),
                jnp.asarray(dlens),
                0,
                self.n_slots,
                key_window,
                self._spec_grid_d,
                True,
            )
            self._launched()
            st["tokens"], st["lengths"], st["rope_pos"] = tok, ln, rp
            rows = d_grid + 1
            self.stats["verify_calls"] += 1
            self._count_passes(st, 0, self.n_slots, 1, kernel=True)
            self.stats["spec_drafted"] += int(dlens.sum())
            attended = np.minimum(lens + rows, key_window)
            pages = int(((attended + page - 1) // page).sum())
            self.stats["ragged_dispatches"] += 1
            self.stats["ragged_attended_pages"] += pages
            # attended accounting is page-granular and PER SLOT — what
            # the kernel actually read, not tier_size x key_window
            self.stats["decode_attended_cols"] += pages * page
            self.stats["decode_ceiling_cols"] += M * self.n_slots * rows
            return [(-1, 0, self.n_slots, out_t, nem_t, rows, dlens)]
        if self.decode_window:
            key_window = round_up_to_bucket(span + n, page, M)
        else:
            key_window = M
        out_t, self.cache, tok, ln, rp = self._decode_fn(
            self.params,
            self.cache,
            st["tokens"],
            st["lengths"],
            st["rope_pos"],
            st["streams"],
            st["active"],
            st["temp"],
            st["top_p"],
            st["top_k"],
            self._decode_key,
            st["rows"],
            n,
            0,
            self.n_slots,
            key_window,
            True,
        )
        self._launched()
        st["tokens"], st["lengths"], st["rope_pos"] = tok, ln, rp
        self.stats["decode_calls"] += 1
        self._count_passes(st, 0, self.n_slots, n, kernel=True)
        self.stats["ragged_dispatches"] += 1
        self.stats["decode_ceiling_cols"] += M * self.n_slots * n
        steps = np.arange(1, n + 1, dtype=np.int64)[:, None]
        if self._state:
            self._state_len[active] += n
        if self._window:
            # the full layers' kernel (ops/windowed_decode.py) walks each
            # live slot's columns by length: what `kv_columns_read` counts
            # on the device
            attended = np.minimum(lens[None, active] + steps, key_window)
            self.stats["decode_attended_cols"] += int(attended.sum())
        elif self._holds_state and self._columns:
            # a hybrid slot: the kernel (ops/mamba1_decode.py) steps the
            # states, the attention blocks copy their bucketed key window
            # as on the plain path
            self.stats["decode_attended_cols"] += key_window * self.n_slots * n
        elif self._state:
            # the state kernel (ops/retention_decode.py) steps states of
            # fixed size: no page is attended and nothing is windowed
            self.stats["decode_attended_cols"] += M * self.n_slots * n
        else:
            attended = np.minimum(lens[None, :] + steps, key_window)
            pages = int(((attended + page - 1) // page).sum())
            self.stats["ragged_attended_pages"] += pages
            self.stats["decode_attended_cols"] += pages * page
        return [(-1, 0, self.n_slots, out_t, None, n, None)]

    def step(self, chunk: Optional[int] = None) -> int:
        """Admit pending prompts, then advance every active slot by up to
        `chunk` tokens — ONE fused device program per non-empty
        length-cohort tier, each bounded to its own bucketed `key_window`
        (ISSUE 5: decode attention reads track the occupied span, not the
        `max_seq_len` ceiling).  Returns generated-token count actually
        delivered (overshoot past stop conditions excluded).

        A slot at its cache limit no longer clamps the whole grid's chunk
        (VERDICT r3 weak #3): the decode kernel clamps that slot's writes to
        its last cache position and the host frees it at the boundary, so
        every other slot keeps full-chunk round-trips.  Delivery is
        vectorised — stop/length scanning is numpy over [chunk, active]
        token matrices, not a Python token loop (slot grids of 64-256 would
        otherwise pay O(slots x chunk) interpreter overhead per step)."""
        # the five step_* phases tile this method: their totals in
        # self.stats say where a step's host time goes, and under a
        # profiler session they lie beside the device's operations
        # The phases are wrapped in place, on purpose.  Splitting dispatch
        # and delivery into methods of their own cost 0.9 s of set-up per
        # warmed program on the chip (+22 s in `rollout_decode`), by a
        # mechanism nobody has found (PERF.md, Findings of PR 24 and Open
        # questions): before restructuring this method, compare `warm_s`
        # of that cell at `--seconds 1` on parent and change.
        phase, stats = telemetry.span, self.stats
        # the in-flight ledger (`_launched`, above abort_all) is a line at
        # each launch and each download below, for the same reason
        returned = self._step_returned
        if returned is not None:
            # the caller's time since the last step that left work behind
            self._step_returned = None
            stats["t_between_steps_s"] += time.perf_counter() - returned
        with phase("step_admit", stats):
            self._admit()
        n = chunk or self.decode_chunk
        with phase("step_dispatch", stats):
            # a verify dispatch can advance a slot by up to D+1 tokens in
            # one step — migration planning must see the larger overshoot
            self._plan_migrations(
                max(n, self._spec_max_d + 1) if self.spec_decode else n
            )
        with phase("step_sync", stats), self._lock:
            active = [s for s in range(self.n_slots) if self.slot_req[s] is not None]
            if not active:
                self._not_starved()
                return 0
            # dirty-check + rebuild + snapshot are one atomic unit: an
            # abort/free landing between them would leave this chunk
            # decoding from stale device mirrors
            if self._dev_state is None or self._state_dirty:
                self._sync_device_state()
            st = self._dev_state
        stats["engine_steps"] += 1
        S = self.n_slots + 1
        with phase("step_dispatch", stats):
            # per-tier dispatch: only tiers holding an active slot run; each
            # gets a key window bucketed from ITS occupants' spans
            tier_active = [[] for _ in range(self.n_tiers)]
            for s in active:
                tier_active[int(self.slot_tier[s])].append(s)
            M = self.max_seq_len
            # prompt-lookup drafting (ISSUE 12): host-side n-gram match over
            # each slot's accumulated tokens (seq_tokens holds the pending
            # last token at index lengths[s]); per-tier D comes off the static
            # ladder via the acceptance controller, or is pinned by
            # spec_draft_len.  Drafts are capped by cache room and remaining
            # token budget.  The chosen D parks in _spec_tier_d so the
            # dispatch's static arg is a self attr (C6 on-ladder lattice).
            spec_plan: Dict[int, tuple] = {}
            if self.spec_decode:
                self._spec_tier_d = {}
                for t in range(self.n_tiers):
                    if not tier_active[t]:
                        continue
                    d_t = (
                        self.spec_draft_len
                        if self.spec_draft_len is not None
                        else self._spec.draft_len(t)
                    )
                    if d_t <= 0:
                        continue
                    lo = self.tier_start[t]
                    drafts = np.zeros((self.tier_size[t], d_t), np.int32)
                    dlens = np.zeros(self.tier_size[t], np.int32)
                    for s in tier_active[t]:
                        req = self.slot_req[s]
                        if req is None:
                            continue
                        L = int(self.lengths[s])
                        cap = min(
                            d_t,
                            self.max_seq_len - 2 - L,
                            req.max_new_tokens - len(req.output_tokens) - 1,
                        )
                        if cap <= 0:
                            continue
                        d = propose_draft(self.seq_tokens[s, : L + 1], cap)
                        if d.size:
                            drafts[s - lo, : d.size] = d
                            dlens[s - lo] = d.size
                    if dlens.any():
                        self._spec_tier_d[t] = d_t
                        spec_plan[t] = (drafts, dlens)
            # decode-chunk telemetry is the one per-dispatch cost, so it is
            # gated on the flag (the trace-id list is built at emission)
            tele = telemetry.is_enabled()
            t_dispatch = time.perf_counter()
            # (tier label, block lo, block size, device out, device n_emit or
            # None, out rows, draft lens); label -1 = collapsed ragged grid
            dev_outs: List[tuple] = []
            try:
                if self._ragged_ok:
                    # ISSUE 19: one grid-wide ragged dispatch replaces the
                    # whole per-tier fan-out below
                    dev_outs.extend(
                        self._dispatch_ragged(st, n, active, spec_plan)
                    )
                for t in range(self.n_tiers):
                    if self._ragged_ok or not tier_active[t]:
                        continue
                    plan = spec_plan.get(t)
                    if plan is not None:
                        # speculative step: pending token + D drafts verified
                        # in ONE dispatch; state advances by accepted count on
                        # device.  D=0 tiers fall through to the plain decode
                        # program below — no degenerate verify signature.
                        drafts, dlens = plan
                        if self.decode_window:
                            span = int(
                                max(self.lengths[s] for s in tier_active[t])
                            )
                            key_window = round_up_to_bucket(
                                span + self._spec_tier_d[t] + 1,
                                self.prompt_bucket, M,
                            )
                        else:
                            key_window = M
                        out_t, nem_t, self.cache, tok, ln, rp = self._verify_fn(
                            self.params,
                            self.cache,
                            st["tokens"],
                            st["lengths"],
                            st["rope_pos"],
                            st["streams"],
                            st["active"],
                            st["temp"],
                            st["top_p"],
                            st["top_k"],
                            self._decode_key,
                            st["rows"],
                            drafts,
                            dlens,
                            self.tier_start[t],
                            self.tier_size[t],
                            key_window,
                            self._spec_tier_d[t],
                            False,
                        )
                        self._launched()
                        st["tokens"], st["lengths"], st["rope_pos"] = tok, ln, rp
                        rows = self._spec_tier_d[t] + 1
                        self.stats["verify_calls"] += 1
                        self._count_passes(
                            st, self.tier_start[t], self.tier_size[t], 1
                        )
                        self.stats["spec_drafted"] += int(dlens.sum())
                        self.stats["decode_attended_cols"] += (
                            key_window * self.tier_size[t] * rows
                        )
                        self.stats["decode_ceiling_cols"] += (
                            M * self.tier_size[t] * rows
                        )
                        dev_outs.append((
                            t, self.tier_start[t], self.tier_size[t],
                            out_t, nem_t, rows, dlens,
                        ))
                        continue
                    if self.decode_window:
                        span = int(max(self.lengths[s] for s in tier_active[t]))
                        key_window = round_up_to_bucket(
                            span + n, self.prompt_bucket, M
                        )
                    else:
                        key_window = M
                    out_t, self.cache, tok, ln, rp = self._decode_fn(
                        self.params,
                        self.cache,
                        st["tokens"],
                        st["lengths"],
                        st["rope_pos"],
                        st["streams"],
                        st["active"],
                        st["temp"],
                        st["top_p"],
                        st["top_k"],
                        self._decode_key,
                        st["rows"],
                        n,
                        self.tier_start[t],
                        self.tier_size[t],
                        key_window,
                        False,
                    )
                    self._launched()
                    st["tokens"], st["lengths"], st["rope_pos"] = tok, ln, rp
                    if self._state:
                        self._state_len[tier_active[t]] += n
                    self.stats["decode_calls"] += 1
                    self._count_passes(
                        st, self.tier_start[t], self.tier_size[t], n
                    )
                    self.stats["decode_attended_cols"] += (
                        key_window * self.tier_size[t] * n
                    )
                    self.stats["decode_ceiling_cols"] += (
                        M * self.tier_size[t] * n
                    )
                    dev_outs.append((
                        t, self.tier_start[t], self.tier_size[t],
                        out_t, None, n, None,
                    ))
            except Exception:
                # a failed dispatch may have consumed (donated) device state
                with self._lock:
                    self._dev_state = None
                    self._state_dirty = True
                self._not_starved()
                raise
        nm = max(rows for _, _, _, _, _, rows, _ in dev_outs)
        toks = np.zeros((nm, S), np.int32)
        logps = np.zeros((nm, S), np.float32)
        # per-slot usable token count: full chunk for decode tiers, the
        # accepted-run length (>= 1: the corrected token always emits) for
        # verify tiers — delivery masks everything beyond it
        avail = np.zeros(S, np.int64)
        # the launches above are the newest len(dev_outs), in this order
        for launch, (t, lo, sz, out_t, nem_t, rows, dlens) in enumerate(
            dev_outs, self._n_launched - len(dev_outs) + 1
        ):
            # the host waits for the device here
            with phase("step_fetch", stats):
                # areal-lint: disable=host-sync delivery point: ONE fused download per tier chunk is the designed host round-trip cadence
                arr = np.asarray(out_t)  # [2, rows, block size]
                if nem_t is not None:
                    # areal-lint: disable=host-sync delivery point: the accepted-count fetch rides the same per-tier delivery round-trip
                    nem = np.asarray(nem_t).astype(np.int64)
            with phase("step_deliver", stats):
                self._landed(launch)
                hi = lo + sz
                toks[:rows, lo:hi] = arr[0, :, :sz].astype(np.int32)
                logps[:rows, lo:hi] = arr[1, :, :sz]
                for i, name in enumerate(self._pass_counters):
                    stats[name] += int(arr[2, :, i].sum())
                if nem_t is None:
                    avail[lo:hi] = rows
                    drafted = accepted = 0
                else:
                    avail[lo:hi] = nem
                    drafted = int(dlens.sum())
                    accepted = int(np.maximum(nem - 1, 0).sum())
                    self.stats["spec_accepted"] += accepted
                    if t >= 0:
                        self._spec.record(t, drafted, accepted)
                    else:
                        # collapsed grid-wide verify (ISSUE 19): feed each
                        # tier's acceptance controller its own slots' outcome
                        # so the per-tier D ladder keeps adapting
                        for tt in range(self.n_tiers):
                            l2 = self.tier_start[tt] - lo
                            h2 = l2 + self.tier_size[tt]
                            d_tt = int(dlens[l2:h2].sum())
                            if d_tt:
                                self._spec.record(
                                    tt, d_tt,
                                    int(np.maximum(nem[l2:h2] - 1, 0).sum()),
                                )
                if tele:
                    lat = time.perf_counter() - t_dispatch
                    telemetry.DECODE_CHUNK.observe(lat, tier=str(t))
                    in_block = active if t < 0 else tier_active[t]
                    n_act = len(in_block)
                    ids = [
                        (r.trace_id or r.rid)
                        for r in (self.slot_req[s] for s in in_block)
                        if r is not None
                    ]
                    if nem_t is None:
                        telemetry.emit(
                            "decode_chunk",
                            tier=t,
                            chunk=n,
                            n_active=n_act,
                            latency_s=lat,
                            trace_ids=ids,
                        )
                    else:
                        telemetry.emit(
                            "spec_verify",
                            tier=t,
                            draft_len=rows - 1,
                            drafted=drafted,
                            accepted=accepted,
                            n_active=n_act,
                            latency_s=lat,
                            trace_ids=ids,
                        )

        with phase("step_deliver", stats):
            delivered = 0
            to_finish: List[tuple] = []
            version = self.version
            with self._lock:
                # re-snapshot under the lock: a concurrent abort_all (weight
                # update) may have freed slots while the chunk was on device
                pairs = [
                    (s, self.slot_req[s])
                    for s in active
                    if self.slot_req[s] is not None
                ]
                if not pairs:
                    self._not_starved()
                    return 0
                A = np.asarray([s for s, _ in pairs])
                reqs = [r for _, r in pairs]
                a = len(pairs)
                tk = toks[:, A]  # [nm, a]
                lp = logps[:, A]
                av = avail[A]  # per-slot usable rows (ragged under spec decode)
                c0 = np.fromiter((len(r.output_tokens) for r in reqs), np.int64, a)
                max_new = np.fromiter((r.max_new_tokens for r in reqs), np.int64, a)
                min_new = np.fromiter((r.min_new_tokens for r in reqs), np.int64, a)
                eos = self.model_config.eos_token_id
                stop = np.zeros((nm, a), bool)
                for j, r in enumerate(reqs):
                    sids = r.stop_token_ids or ([eos] if eos is not None else [])
                    if sids:
                        stop[:, j] = np.isin(tk[:, j], sids)
                steps = np.arange(1, nm + 1, dtype=np.int64)[:, None]  # [nm, 1]
                # rows past a slot's avail are rejected-draft / pad garbage:
                # they neither deliver nor trigger stop conditions
                valid = steps <= av[None, :]
                out_count = c0[None, :] + steps
                hit_stop = stop & (out_count >= min_new[None, :]) & valid
                # freeing at total_len + 1 >= max_seq_len keeps the NEXT decode
                # write in-bounds (same rule the token loop applied)
                total_len = self.lengths[A][None, :] + steps
                hit_len = ((out_count >= max_new[None, :]) | (
                    total_len + 1 >= self.max_seq_len
                )) & valid
                done = hit_stop | hit_len
                any_done = done.any(axis=0)
                last = np.where(any_done, done.argmax(axis=0), av - 1)  # inclusive

                for j, (s, req) in enumerate(pairs):
                    k = int(last[j]) + 1
                    seq = tk[:k, j]
                    if c0[j] == 0 and k > 0 and req.first_token_ts == 0.0:
                        req.first_token_ts = time.perf_counter()
                    req.output_tokens.extend(seq.tolist())
                    req.output_logprobs.extend(lp[:k, j].tolist())
                    req.output_versions.extend([version] * k)
                    L = int(self.lengths[s])
                    # delivered tokens occupy cache positions L+1 .. L+k (the
                    # pending last_token's K/V was written at L this chunk)
                    self.seq_tokens[s, L + 1 : L + 1 + k] = seq
                    self.lengths[s] = L + k
                    self.rope_pos[s] += k
                    self.last_tokens[s] = int(seq[-1])
                    delivered += k
                    if any_done[j]:
                        reason = "stop" if hit_stop[last[j], j] else "length"
                        self.slot_req[s] = None
                        self.retained_len[s] = (
                            0 if self._slot_vlm[s] or (
                                self._state
                                and self._state_len[s] != self.lengths[s]
                            ) else self.lengths[s]
                        )
                        self.pool.note_free(
                            s, self.seq_tokens[s], int(self.retained_len[s])
                        )
                        to_finish.append((req, reason))
                if to_finish:
                    # host mirrors diverged from the device state (stop
                    # trimming); resync before the next chunk
                    self._state_dirty = True
            for req, reason in to_finish:
                req.finish(reason)
            stats["tokens_delivered"] += delivered
            if len(to_finish) == a and not self.active_count():
                self._not_starved()  # the last request ended: idle
            else:
                self._step_returned = time.perf_counter()
            return delivered

    def generate_blocking(self, reqs: List[GenRequest]) -> List[GenRequest]:
        """Synchronous helper (tests / offline eval): run until all done."""
        for r in reqs:
            self.submit(r)
        while any(not r.stop_reason for r in reqs):
            if self.step() == 0:
                # queued work may be parked behind an abort reservation
                # (holdback); only a genuinely idle engine is done
                if self.active_count() == 0:
                    break
                time.sleep(0.001)
            time.sleep(0)
        return reqs
