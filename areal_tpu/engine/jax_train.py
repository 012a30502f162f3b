"""JaxTrainEngine — the SPMD training backend.

Capability counterpart of BOTH reference train engines: FSDPEngine
(areal/engine/fsdp_engine.py:64) and MegatronEngine
(areal/engine/megatron_engine.py:67).  One engine suffices on TPU because a
single GSPMD mesh (dp, fsdp, sp, tp) subsumes FSDP2 sharding, megatron TP/SP
and Ulysses:

- "create_process_group" = build the Mesh (no NCCL group zoo).
- "parallelize_model" = device_put params with PartitionSpecs from
  `areal_tpu.models.param_partition_specs`; XLA inserts all collectives.
- train_batch = ONE jit per (loss_fn, shape-signature): micro-batch gradient
  accumulation is a `lax.scan` over a stacked [n_mb, rows, row_len] batch —
  the whole optimizer step (fwd, bwd, accumulate, clip, adamw, lr schedule)
  is a single XLA program with donated state (the reference needs a python
  loop over micro-batches + DTensor full_tensor gathers).
- Batches use the row-packed layout (utils/data.py `pack_into_rows`):
  packed like the reference's flat layout (base_hf_engine.py:257
  prepare_mb_list) yet shardable over (dp, fsdp) with static shapes.

Loss functions follow the reference's protocol (engine_api.py train_batch):
`loss_fn(logits, mb) -> (sum_loss, stats_sums)`, `loss_weight_fn(batch) ->
float`; gradients are globally normalised by the summed weight across all
micro-batches (fsdp_engine.py:499-606's global loss-weight normalisation).
loss_fn must be a *stable* callable — the compiled step is cached per
(id(loss_fn), shapes).
"""
# areal-lint: hot-path

import os
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from areal_tpu.api.config import TrainEngineConfig
from areal_tpu.api.engine import TrainEngine
from areal_tpu.api.io_struct import (
    FinetuneSpec,
    SaveLoadMeta,
    WeightUpdateMeta,
)
from areal_tpu.models import (
    TransformerConfig,
    forward_lm as model_forward_lm,
    init_params,
    param_partition_specs,
)
from areal_tpu.models.hf import load_hf_params, save_hf_checkpoint
from areal_tpu.models.transformer import attention_block_counts
from areal_tpu.parallel import (
    batch_spec,
    build_mesh,
    distributed,
    mesh_from_alloc,
    shard_pytree,
)
from areal_tpu.utils import logging, name_resolve, names, telemetry
from areal_tpu.utils import stats as tracker
from areal_tpu.utils.data import (
    RowPackedBatch,
    pack_into_rows,
    unpack_rows,
)
from areal_tpu.utils.datapack import round_up_to_bucket
from areal_tpu.ops.attention import implementations_taken
from areal_tpu.ops.functional import lm_logprobs_entropy
from areal_tpu.utils.runtime import enable_compile_cache

logger = logging.getLogger("jax_train")


def _logp_hook(model_out, mb):
    """Default forward hook: next-token logprobs at predictor positions
    (the reference's compute_logp convention, ppo/actor.py:52)."""
    labels = jnp.roll(mb["input_ids"], -1, axis=-1)
    logp, _, _ = lm_logprobs_entropy(model_out, labels, with_entropy=False)
    return logp


class JaxTrainEngine(TrainEngine):
    def __init__(
        self,
        config: TrainEngineConfig,
        model_config: Optional[TransformerConfig] = None,
    ):
        self.config = config
        self.model_config = model_config
        self.mesh = None
        self.params = None
        self.opt_state = None
        self.step_count = 0
        self._version = 0
        self._optimizer = None
        self._schedule = None
        self._train_step_cache: Dict[Tuple, Callable] = {}
        self._last_train_step: Optional[Tuple[Callable, Any]] = None
        self._forward_cache: Dict[Tuple, Callable] = {}
        self._ft_spec: Optional[FinetuneSpec] = None
        self._transfer_executor = None  # lazy: weight-transfer push thread
        self._staged = None  # (meta.type, version) staged by stage_weights
        self.last_weight_update_seconds: Optional[float] = None
        self.initialized = False
        # the jitted step functions call self._model_fn(params, cfg, ids,
        # positions, segment_ids, mesh=mesh); the default returns a deferred
        # LMOutput (chunked-head memory discipline); value/reward engines
        # override it to return per-token values instead
        self._model_fn = model_forward_lm

    # ------------------------------------------------------------------
    # setup
    # ------------------------------------------------------------------

    def create_process_group(self, alloc_mode=None) -> None:
        if self.mesh is not None:
            return
        # multi-host: join the global JAX runtime first (env-gated no-op in
        # the single-process dev path) — the TPU equivalent of
        # init_process_group (reference: fsdp_engine.py:112)
        distributed.init_distributed()
        if alloc_mode is not None and getattr(alloc_mode, "train", None):
            self.mesh = mesh_from_alloc(alloc_mode.train)
        else:
            m = self.config.mesh
            self.mesh = build_mesh(
                dp=m.data_parallel_size,
                fsdp=m.fsdp_parallel_size,
                sp=m.sequence_parallel_size,
                tp=m.tensor_parallel_size,
                ep=getattr(m, "expert_parallel_size", 1),
            )
        logger.info(f"mesh: {dict(zip(self.mesh.axis_names, self.mesh.devices.shape))}")

    def initialize(
        self,
        addr: Optional[str] = None,
        ft_spec: Optional[FinetuneSpec] = None,
    ) -> None:
        enable_compile_cache()
        self.create_process_group()
        self._ft_spec = ft_spec
        cfg = self.config
        if getattr(cfg, "attn_impl", "auto") not in (
            "auto", "splash", "naive", "ring",
        ):
            # forwarded verbatim into the model config: an unknown value
            # (typo, or this field's pre-wiring legacy spellings) would
            # silently select the splash/auto ladder
            raise ValueError(
                f"unknown attn_impl {cfg.attn_impl!r}: use auto, splash, "
                "naive, or ring"
            )
        if cfg.path and not cfg.init_from_scratch:
            host_params, mc = load_hf_params(
                cfg.path, self.model_config, dtype=cfg.param_dtype
            )
            self.model_config = mc
        else:
            if self.model_config is None:
                raise ValueError("init_from_scratch requires model_config")
            host_params = init_params(
                self.model_config.replace(param_dtype=cfg.param_dtype),
                jax.random.PRNGKey(0),
            )
        # this clamp must run AFTER the checkpoint resolves model_config:
        # the common route (gpt2 checkpoint via cfg.path, model_config=None)
        # only learns pos_emb=='learned' from the loaded config, and the
        # packer's row shapes are compiled from max_pack_length below
        if (
            self.model_config.pos_emb == "learned"
            and cfg.max_pack_length > self.model_config.max_position_embeddings
        ):
            # jnp.take clamps, so rows packed past the table would silently
            # train every overflow position on the last embedding.
            # max_pack_length is a cap (row lengths bucket up to it), so
            # clamping keeps short batches working; a single sequence longer
            # than the table still fails loudly in the packer.
            logger.warning(
                "clamping max_pack_length %d to the learned position table "
                "(%d): gpt2-family models cannot extrapolate positions",
                cfg.max_pack_length,
                self.model_config.max_position_embeddings,
            )
            cfg.max_pack_length = self.model_config.max_position_embeddings
        self.model_config = self.model_config.replace(
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            remat=cfg.gradient_checkpointing,
            remat_policy=getattr(cfg, "remat_policy", "full"),
            scan_unroll=getattr(cfg, "scan_unroll", 1),
            layer_group_size=getattr(cfg, "layer_group_size", 1),
            # an explicitly-set model config wins; the engine config is the
            # yaml-reachable path for checkpoints (from_hf leaves "auto")
            attn_impl=(
                self.model_config.attn_impl
                if self.model_config.attn_impl != "auto"
                else getattr(cfg, "attn_impl", "auto")
            ),
        )
        # fail the two-level scan contracts HERE, before any tracing: a
        # non-divisor group size inside jit surfaces as a trace error deep
        # in the first train step otherwise.  effective_scan_unroll warns
        # loudly on a non-divisor unroll and falls back to 1; the value it
        # settles on rides every train-stats dict so a silently forfeited
        # unroll is visible in logged artifacts, not just stderr.
        mc_ = self.model_config
        if mc_.num_layers % max(1, mc_.layer_group_size):
            raise ValueError(
                f"layer_group_size={mc_.layer_group_size} must divide "
                f"num_layers={mc_.num_layers}"
            )
        from areal_tpu.models.transformer import effective_scan_unroll

        self._effective_scan_unroll = effective_scan_unroll(mc_)
        if getattr(cfg, "lora", None) is not None and cfg.lora.enabled:
            from areal_tpu.models.lora import add_lora_params

            self.model_config = self.model_config.replace(
                lora_rank=cfg.lora.rank,
                lora_alpha=cfg.lora.alpha,
                lora_targets=tuple(cfg.lora.target_modules),
            )
            host_params = add_lora_params(
                host_params, self.model_config, jax.random.PRNGKey(1)
            )
        specs = param_partition_specs(
            self.model_config, tp=self.mesh.shape["tp"]
        )
        # subtrees the text-model spec doesn't know (e.g. the vision tower
        # loaded from a VLM checkpoint) are small: replicate them
        for key in host_params:
            if key not in specs:
                specs[key] = jax.tree_util.tree_map(
                    lambda _: P(), host_params[key]
                )
        self.params = shard_pytree(self.mesh, host_params, specs)

        if cfg.optimizer is not None:
            self._build_optimizer(ft_spec)
        self.initialized = True
        n = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(self.params))
        logger.info(f"initialized {n / 1e6:.1f}M params on mesh {self.mesh.shape}")

    def _build_optimizer(self, ft_spec: Optional[FinetuneSpec]) -> None:
        oc = self.config.optimizer
        total_steps = ft_spec.total_train_steps if ft_spec is not None else 1_000_000
        # the schedule is indexed per optimizer update, and PPO-style engines
        # make ppo_n_minibatches updates per dataset iteration
        total_steps *= max(1, getattr(self.config, "ppo_n_minibatches", 1))
        warmup = int(oc.warmup_steps_proportion * total_steps)
        peak, floor = oc.lr, oc.lr * oc.min_lr_ratio
        if oc.lr_scheduler_type == "cosine":
            decay = optax.cosine_decay_schedule(
                peak, max(1, total_steps - warmup), alpha=oc.min_lr_ratio
            )
        elif oc.lr_scheduler_type == "linear":
            decay = optax.linear_schedule(peak, floor, max(1, total_steps - warmup))
        else:
            decay = optax.constant_schedule(peak)
        if warmup > 0:
            self._schedule = optax.join_schedules(
                [optax.linear_schedule(0.0, peak, warmup), decay], [warmup]
            )
        else:
            self._schedule = decay
        wd_mask = jax.tree_util.tree_map(lambda p: p.ndim >= 2, self.params)
        self._optimizer = optax.chain(
            optax.clip_by_global_norm(oc.gradient_clipping),
            optax.adamw(
                learning_rate=self._schedule,
                b1=oc.beta1,
                b2=oc.beta2,
                eps=oc.eps,
                weight_decay=oc.weight_decay,
                mask=wd_mask,
            ),
        )
        buffers = jax.tree_util.tree_map_with_path(
            lambda path, _: getattr(path[-1], "key", None) == "router_bias",
            self.params,
        )
        if any(jax.tree_util.tree_leaves(buffers)):
            # a sigmoid router's selection bias is a buffer: load balancing
            # moves it, no gradient does.  Outside the optimizer it is
            # neither decayed nor given moments, and its update is its
            # (zero) gradient
            self._optimizer = optax.masked(
                self._optimizer,
                jax.tree_util.tree_map(lambda b: not b, buffers),
            )
        if self.model_config.lora_rank:
            # adapters only: optax.masked keeps moment state solely for the
            # adapter leaves — the memory point of LoRA (the base weights
            # are already stop_gradient-frozen in the forward)
            from areal_tpu.models.lora import trainable_mask

            self._optimizer = optax.masked(
                self._optimizer, trainable_mask(self.params)
            )
        # Eager init: zeros_like inherits each param's NamedSharding for
        # mu/nu; scalar counters are explicitly replicated over the mesh so
        # the compiled step sees one consistent device set (and so an orbax
        # restore — which commits whatever it loads — matches too).
        with self.mesh:
            self.opt_state = self._optimizer.init(self.params)
        self.opt_state = self._replicate_scalars(self.opt_state)

    def _replicate_scalars(self, tree):
        rep = NamedSharding(self.mesh, P())
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, rep)
            if isinstance(x, jax.Array) and x.ndim == 0
            else x,
            tree,
        )

    def destroy(self) -> None:
        self.params = None
        self.opt_state = None
        self._train_step_cache.clear()
        self._forward_cache.clear()
        if self._transfer_executor is not None:
            self._transfer_executor.shutdown(wait=False)
            self._transfer_executor = None
        self.initialized = False

    # ------------------------------------------------------------------
    # data-parallel topology (single-controller: one process owns the mesh)
    # ------------------------------------------------------------------

    @property
    def data_parallel_rank(self) -> int:
        return jax.process_index()

    @property
    def data_parallel_world_size(self) -> int:
        return jax.process_count()

    def is_data_parallel_head(self) -> bool:
        return jax.process_index() == 0

    def current_data_parallel_head(self) -> int:
        return 0

    # ------------------------------------------------------------------
    # batch preparation
    # ------------------------------------------------------------------

    def _row_len(self, batch: Dict[str, np.ndarray]) -> int:
        lens = batch["attention_mask"].astype(np.int64).sum(-1)
        longest = int(lens.max()) if lens.size else 1
        return round_up_to_bucket(
            longest, self.config.pack_length_quantum, self.config.max_pack_length
        )

    def _prepare_rows(
        self, batch: Dict[str, np.ndarray], n_mbs: int
    ) -> Tuple[RowPackedBatch, Dict[str, np.ndarray], int]:
        """Row-pack a padded batch; rows divisible by n_mbs * dp * fsdp."""
        row_len = self._row_len(batch)
        dp = (self.mesh.shape["dp"] * self.mesh.shape["fsdp"]
              * self.mesh.shape.get("ep", 1))
        rp = pack_into_rows(
            batch, row_len, rows_multiple=n_mbs * dp, rows_bucket_pow2=True
        )
        data = dict(rp.data)
        data["input_ids"] = data["input_ids"].astype(np.int32)
        # filler rows/tokens must never contribute to the loss
        if "loss_mask" in data:
            data["loss_mask"] = data["loss_mask"] * (data["segment_ids"] >= 0)
        return rp, data, row_len

    def _stack_mbs(self, data: Dict[str, np.ndarray], n_mbs: int) -> Dict[str, np.ndarray]:
        """[R, L] -> [n_mbs, R/n_mbs, L]; rows were FFD-balanced so token
        counts are roughly even across micro-batches."""
        out = {}
        for k, v in data.items():
            R = v.shape[0]
            out[k] = v.reshape(n_mbs, R // n_mbs, *v.shape[1:])
        return out

    def _device_batch(self, data: Dict[str, np.ndarray], stacked: bool):
        """Shard host arrays: rows over (dp, fsdp), sequence over sp.

        Multi-process: the batch must be identical on every process (the
        dist-rollout coordinator broadcasts it); each process contributes
        its local shards."""
        spec = batch_spec()
        if stacked:
            spec = P(None, *spec)
        if jax.process_count() > 1:
            return distributed.make_global_batch(
                self.mesh, {k: spec for k in data}, data
            )
        sharding = NamedSharding(self.mesh, spec)
        return {k: jax.device_put(v, sharding) for k, v in data.items()}

    # ------------------------------------------------------------------
    # train / eval / forward
    # ------------------------------------------------------------------

    def _call_model(self, params, batch):
        """Model forward over one (micro-)batch dict.  The single seam the
        jitted step/eval/forward programs call; modality subclasses (VLM)
        override it to consume extra batch keys (pixels, mrope)."""
        return self._model_fn(
            params,
            self.model_config,
            batch["input_ids"],
            batch["positions"],
            batch["segment_ids"],
            mesh=self.mesh,
        )

    def _build_train_step(self, loss_fn: Callable):
        optimizer = self._optimizer
        schedule = self._schedule
        call_model = self._call_model
        model_config, mesh = self.model_config, self.mesh

        def train_step(params, opt_state, batch, total_weight, step_idx):
            def mb_loss(p, mb):
                logits = call_model(p, mb)
                with jax.named_scope("loss"):
                    loss, stats = loss_fn(logits, mb)
                    # what the forward counted (expert rows of a share)
                    stats = {**stats,
                             **(getattr(logits, "counters", None) or {})}
                    return loss / total_weight, stats

            grad_fn = jax.value_and_grad(mb_loss, has_aux=True)
            if batch["input_ids"].shape[0] == 1:
                # single micro-batch: no accumulator buffer (one full
                # gradient tree of HBM saved — the margin that decides the
                # largest fitting batch on a 16G chip)
                (loss, stats), grads = grad_fn(
                    params, jax.tree_util.tree_map(lambda v: v[0], batch)
                )
            else:
                # accumulate at master-weight precision: fp32 masters get
                # fp32 accumulation (reference behavior); bf16-master
                # (memory-tight) runs avoid doubling gradient HBM
                zero_grads = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, p.dtype), params
                )

                def scan_body(carry, mb):
                    grads_acc, loss_acc = carry
                    (loss, stats), grads = grad_fn(params, mb)
                    grads_acc = jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(a.dtype), grads_acc, grads
                    )
                    return (grads_acc, loss_acc + loss), stats

                (grads, loss), stats = jax.lax.scan(
                    scan_body, (zero_grads, jnp.zeros((), jnp.float32)), batch
                )
                # sums over micro-batches; a `*_max` stat is their maximum
                stats = {
                    k: (jnp.max if k.endswith("_max") else jnp.sum)(v, axis=0)
                    for k, v in stats.items()
                }
            stats = dict(stats)
            # how often the splash block mask's narrowing engaged, summed
            # over micro-batches (one layer, one kv head; `_local` and
            # `_global` apart where a stack mixes the two; absent where the
            # forward does not take the splash kernel)
            seg = batch["segment_ids"]
            stats.update(attention_block_counts(
                model_config, seg.reshape(-1, seg.shape[-1]), mesh
            ))
            with jax.named_scope("optimizer"):
                grad_norm = optax.global_norm(grads)
                updates, new_opt_state = optimizer.update(
                    grads, opt_state, params
                )
                new_params = optax.apply_updates(params, updates)
                stats["grad_norm"] = grad_norm
                stats["loss"] = loss
                # lr is evaluated inside the jitted step: an eager schedule
                # call per step costs several device round-trips (each one
                # blocks the host until the device answers)
                stats["lr"] = schedule(step_idx)
            return new_params, new_opt_state, stats

        # pin state outputs to the CURRENT shardings: without this, GSPMD
        # is free to re-layout the updated params/opt-state however it
        # likes — on a real mesh that silently abandons the intended
        # fsdp/tp distribution after step 1, and every downstream program
        # consuming params (forward, export, serving publish) retraces
        # against the drifted shardings (one surprise compile each)
        def shard_of(x):
            return getattr(x, "sharding", None)

        out_shardings = (
            jax.tree_util.tree_map(shard_of, self.params),
            jax.tree_util.tree_map(shard_of, self.opt_state),
            None,  # stats: let XLA choose (replicated scalars)
        )
        return jax.jit(
            train_step, donate_argnums=(0, 1), out_shardings=out_shardings
        )

    # keys the jitted forward program may read (the _call_model seam plus
    # the in-tree post-hooks).  forward() filters the packed batch to these
    # so EXTRA rollout keys (rewards, versions, loss_mask, ...) and their
    # pipeline-dependent dtypes can never change the jit cache signature —
    # workflows adding fields must not trigger surprise in-loop recompiles,
    # and warm_shapes' synthetic batches compile the very program the real
    # call requests.  Subclasses with richer model seams extend (VLM adds
    # pixels/mrope); custom post_hooks reading other per-token keys must
    # extend it too.
    FORWARD_KEYS = ("input_ids", "positions", "segment_ids")

    def _forward_batch_view(self, data: Dict[str, np.ndarray]):
        return {k: data[k] for k in self.FORWARD_KEYS if k in data}

    def _forward_fn_for(self, post_hook, row_len: int, n_rows: int):
        """Resolve (building + caching if needed) the jitted forward for a
        (hook, shape) signature; returns the cache key."""
        if post_hook is None:
            post_hook = _logp_hook
        key = ("fwd", post_hook, row_len, n_rows)
        if key not in self._forward_cache:
            call_model = self._call_model

            def fwd_step(params, batch):
                logits = call_model(params, batch)
                return post_hook(logits, batch)

            # multi-process: output rows are sharded across hosts — jit
            # replicates them so every process can read the full array
            out_shardings = (
                NamedSharding(self.mesh, P())
                if jax.process_count() > 1
                else None
            )
            self._forward_cache[key] = jax.jit(
                fwd_step, out_shardings=out_shardings
            )
        return key

    def precompile_forward(
        self,
        input_: Dict[str, np.ndarray],
        post_hook: Optional[Callable] = None,
    ) -> None:
        """AOT-compile the no-grad forward for this batch's shape signature
        (see precompile_train_batch)."""
        assert self.initialized
        rp, data, row_len = self._prepare_rows(input_, 1)
        dev_batch = self._device_batch(self._forward_batch_view(data),
                                       stacked=False)
        key = self._forward_fn_for(post_hook, row_len,
                                   data["input_ids"].shape[0])
        with self.mesh:
            self._forward_cache[key].lower(self.params, dev_batch).compile()

    def precompile_train_batch(
        self, input_: Dict[str, np.ndarray], loss_fn: Callable
    ) -> None:
        """Compile the train-step program for this batch's shape signature
        WITHOUT executing it.  AOT `jit.lower(...).compile()` populates the
        same executable cache the real call uses (measured: the next real
        call is a cache hit), and — unlike executing a warm step — donates
        nothing and mutates nothing.  PPOActor.warm_shapes drives this so
        varying rollout lengths never compile inside the training loop."""
        assert self.initialized and self._optimizer is not None
        n_mbs = max(1, self.config.mb_spec.n_mbs)
        rp, data, row_len = self._prepare_rows(input_, n_mbs)
        stacked = self._stack_mbs(data, n_mbs)
        dev_batch = self._device_batch(stacked, stacked=True)
        key = (loss_fn, n_mbs, row_len, stacked["input_ids"].shape[1])
        if key not in self._train_step_cache:
            self._train_step_cache[key] = self._build_train_step(loss_fn)
        with self.mesh:
            self._train_step_cache[key].lower(
                self.params,
                self.opt_state,
                dev_batch,
                jnp.float32(1.0),
                jnp.int32(self.step_count),
            ).compile()

    def _consume_telemetry(
        self, input_: Dict[str, np.ndarray]
    ) -> Dict[str, np.ndarray]:
        """Strip telemetry-only keys and record consumption evidence.

        `trace_keys` must never reach _prepare_rows: train_batch devices
        the WHOLE prepared batch (there is no FORWARD_KEYS filter on this
        path), so an extra key would mint a new XLA signature per run
        mode.  Staleness-at-consumption = trainer's current version minus
        each row's max behavior version (per-token `versions`, -1 =
        prompt) — the paper's bounded-staleness evidence, observed here
        at the exact consumption point."""
        keys = input_.get("trace_keys")
        if keys is not None:
            input_ = {k: v for k, v in input_.items() if k != "trace_keys"}
        if not telemetry.is_enabled():
            return input_
        versions = np.asarray(input_.get("versions", ()))
        if versions.ndim != 2:
            return input_
        behavior = np.where(versions >= 0, versions, -1).max(axis=-1)
        tks = None if keys is None else np.asarray(keys).reshape(-1).tolist()
        consumed = self._version
        for i, bv in enumerate(behavior.tolist()):
            if bv < 0:
                continue
            staleness = max(0, consumed - int(bv))
            telemetry.STALENESS_AT_CONSUMPTION.observe(staleness)
            telemetry.emit(
                "train_consume",
                trace_key=(tks[i] if tks is not None and i < len(tks) else None),
                behavior_version=int(bv),
                consumed_version=consumed,
                staleness=staleness,
            )
        return input_

    def attention_impls(self) -> Dict:
        """(T, Hq, Hkv, hd) -> "splash" | "einsum" | "ring" for every
        program this process has traced (ops/attention.py logs each once)."""
        return implementations_taken()

    def train_step_hlo(self) -> str:
        """Compiled HLO text of the train-step program built last — what
        shows whether attention is the Pallas kernel (`tpu_custom_call`)
        or the einsum.  Compiles again; a persistent-cache hit once the
        step has run."""
        step_fn, avals = self._last_train_step
        with self.mesh:
            return step_fn.lower(*avals).compile().as_text()

    def _scan_stats(self) -> Dict[str, float]:
        """Layer-scan configuration evidence for every stats dict: the
        group size actually compiled and the unroll the scan actually used
        (a non-divisor scan_unroll falls back to 1 with a warning — this
        keeps the fallback visible in logged artifacts too)."""
        return {
            "layer_group_size": float(
                max(1, self.model_config.layer_group_size)
            ),
            "effective_scan_unroll": float(
                getattr(self, "_effective_scan_unroll", 1)
            ),
        }

    def train_batch(
        self,
        input_: Dict[str, np.ndarray],
        loss_fn: Callable,
        loss_weight_fn: Callable,
    ) -> Dict[str, float]:
        assert self.initialized and self._optimizer is not None
        input_ = self._consume_telemetry(input_)
        n_mbs = max(1, self.config.mb_spec.n_mbs)
        with telemetry.span("pack"):
            rp, data, row_len = self._prepare_rows(input_, n_mbs)
            total_weight = float(loss_weight_fn(data))
            if total_weight <= 0:
                raise ValueError(
                    "loss_weight_fn returned non-positive total weight"
                )
            stacked = self._stack_mbs(data, n_mbs)
            dev_batch = self._device_batch(stacked, stacked=True)

        # the callable itself is part of the key: the strong reference keeps
        # it alive, so CPython cannot reuse its address for a different fn
        key = (loss_fn, n_mbs, row_len, stacked["input_ids"].shape[1])
        step_args = (
            self.params,
            self.opt_state,
            dev_batch,
            jnp.float32(total_weight),
            # optax evaluates the schedule at the pre-increment count
            jnp.int32(self.step_count),
        )
        if key not in self._train_step_cache:
            self._train_step_cache[key] = self._build_train_step(loss_fn)
        step_fn = self._train_step_cache[key]
        if self._last_train_step is None or (
            self._last_train_step[0] is not step_fn
        ):
            # shapes + mesh shardings of this program's arguments (the
            # scalars are uncommitted and stay so), so train_step_hlo can
            # lower it again after the buffers are donated
            self._last_train_step = (
                step_fn,
                jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(
                        x.shape, x.dtype,
                        sharding=x.sharding
                        if isinstance(x.sharding, NamedSharding) else None,
                    ),
                    step_args,
                ),
            )

        t0 = time.perf_counter()
        with telemetry.span("update"), self.mesh:
            self.params, self.opt_state, stats = step_fn(*step_args)
        self.step_count += 1
        if self.config.async_stats:
            # deferred fetch: the caller reads stats later (one batched
            # transfer), so the NEXT step can be dispatched while this one
            # still runs — per-step step_time/tflops/mfu are omitted because
            # there is no sync point to measure them against
            pending = tracker.PendingTrainStats(
                stats,
                lambda tree: {
                    k: float(v)
                    for k, v in distributed.fetch_replicated(tree).items()
                },
            )
            def _finish(st: Dict[str, float]) -> Dict[str, float]:
                st = {**st, "total_loss_weight": total_weight}
                st.update(self._scan_stats())
                if telemetry.is_enabled():
                    telemetry.publish_train_stats(st)
                return st

            return pending.then(_finish)
        # ONE host transfer for every stat; per-scalar float() would pay a
        # device round-trip each.  Stats are replicated reductions, so each
        # process reads its own full replica.
        stats = {
            k: float(v) for k, v in distributed.fetch_replicated(stats).items()
        }
        stats["total_loss_weight"] = total_weight
        stats.update(self._scan_stats())
        stats["step_time"] = time.perf_counter() - t0
        # per-chip MFU from the analytic flops model (the role of the
        # reference's flops_counter + kineto categorisation, monitor.py:404)
        from areal_tpu.utils.profiling import mfu, train_flops_per_token

        seg = data["segment_ids"]
        tokens = int((seg >= 0).sum())
        # attention flops scale with SEGMENT length, not packed row length —
        # rows packed with several short sequences attend within segments
        n_segs = int(np.sum(np.where(seg.max(axis=-1) >= 0, seg.max(axis=-1) + 1, 0)))
        mean_seg = max(1, tokens // max(1, n_segs))
        n_chips = self.mesh.devices.size
        tps = tokens / max(stats["step_time"], 1e-9)
        stats["tflops_per_chip"] = (
            tps * train_flops_per_token(self.model_config, mean_seg)
            / 1e12 / n_chips
        )
        m = mfu(tps, self.model_config, mean_seg, n_chips=n_chips)
        if m is not None:
            stats["mfu"] = m
        if telemetry.is_enabled():
            telemetry.publish_train_stats(stats)
        return stats

    def eval_batch(
        self,
        input_: Dict[str, np.ndarray],
        loss_fn: Callable,
        loss_weight_fn: Callable,
    ) -> Dict[str, float]:
        assert self.initialized
        # honor mb_spec: eval must not materialise logits for rows the train
        # path would split across micro-batches
        n_mbs = max(1, self.config.mb_spec.n_mbs)
        rp, data, row_len = self._prepare_rows(input_, n_mbs)
        total_weight = float(loss_weight_fn(data))
        stacked = self._stack_mbs(data, n_mbs)
        dev_batch = self._device_batch(stacked, stacked=True)

        key = ("eval", loss_fn, n_mbs, row_len, stacked["input_ids"].shape[1])
        if key not in self._forward_cache:

            call_model = self._call_model

            def eval_step(params, batch):
                def mb_loss(carry, mb):
                    logits = call_model(params, mb)
                    with jax.named_scope("loss"):
                        loss, stats = loss_fn(logits, mb)
                    return carry + loss, stats

                loss, stats = jax.lax.scan(mb_loss, jnp.zeros(()), batch)
                return loss, jax.tree_util.tree_map(
                    lambda s: jnp.sum(s, axis=0), stats
                )

            self._forward_cache[key] = jax.jit(eval_step)
        with self.mesh:
            loss, stats = self._forward_cache[key](self.params, dev_batch)
        loss, stats = distributed.fetch_replicated((loss, stats))
        out = {k: float(v) for k, v in stats.items()}
        out["loss"] = float(loss) / max(total_weight, 1e-8)
        return out

    def forward(
        self,
        input_: Dict[str, np.ndarray],
        output_key: str = "logprobs",
        post_hook: Optional[Callable] = None,
        aggregate_fn: Callable = None,
    ) -> np.ndarray:
        """No-grad forward; returns a padded [B, L] array aligned with the
        input batch (default: next-token logprobs at predictor positions,
        the reference's compute_logp convention)."""
        assert self.initialized
        if output_key != "logprobs":
            raise NotImplementedError(
                "forward() returns per-token arrays directly; output_key "
                "selection does not apply to this engine"
            )
        if aggregate_fn is not None:
            raise NotImplementedError(
                "forward() runs one fused program — there are no per-microbatch "
                "outputs to aggregate; post-process the returned array instead"
            )
        # no span of its own: `pack` is one train step's packing, and the
        # caller's span (`logp`) covers this one
        rp, data, row_len = self._prepare_rows(input_, 1)
        dev_batch = self._device_batch(self._forward_batch_view(data),
                                       stacked=False)
        key = self._forward_fn_for(post_hook, row_len,
                                   data["input_ids"].shape[0])
        with self.mesh:
            out = self._forward_cache[key](self.params, dev_batch)
            if jax.process_count() > 1:
                # out_shardings replicated it; read the local full replica
                out = distributed.fetch_replicated(out)
            rows_out = np.asarray(out)
        B, L = input_["attention_mask"].shape
        return unpack_rows(rp, rows_out, B, L)

    # ------------------------------------------------------------------
    # weights
    # ------------------------------------------------------------------

    def _host_params(self):
        if jax.process_count() == 1:
            return jax.tree_util.tree_map(np.asarray, self.params)
        # multi-process: shards live on other hosts; replicate leaf-by-leaf
        # through jit (bounded extra memory: one leaf) and read the local
        # replica — the role of DTensor.full_tensor() in the reference's
        # save path (fsdp_engine.py:228-254)
        rep = NamedSharding(self.mesh, P())
        gather = jax.jit(lambda x: x, out_shardings=rep)
        return jax.tree_util.tree_map(
            lambda x: np.asarray(gather(x).addressable_data(0)), self.params
        )

    def _export_params(self):
        """Host params in served form: LoRA adapters folded into the base
        (reference pushes merged weights, fsdp_engine.py:270)."""
        from areal_tpu.models.lora import merge_lora

        return merge_lora(self._host_params(), self.model_config)

    @telemetry.span("export_params")
    def export_device_params(self):
        """Serving-ready bf16 params WITHOUT leaving the device — the
        colocated publish path (engine/colocated.py): trainer and serving
        engine share the chips, so the disk/host round trip of the other
        publish modes is pure waste there.  Leaves are COPIES (jnp.array
        copy=True), so the trainer's next donated update cannot invalidate
        the serving engine's buffers.  LoRA folds on the host path only —
        adapters make this fall back to _export_params."""
        if self.model_config.lora_rank > 0:
            return self._export_params()
        # keep the configured param_dtype: an fp32 smoke config must stay
        # fp32 or the serving engine retraces mid-measurement
        target = jnp.dtype(self.model_config.param_dtype)

        # ONE program with a name of its own (`jit_export_params` in a
        # profile) instead of an eager `jit_copy` per leaf
        def export_params(params):
            return jax.tree_util.tree_map(
                lambda x: jnp.array(x, target, copy=True)
                if jnp.issubdtype(x.dtype, jnp.floating)
                else x,
                params,
            )

        key = ("export", target)
        if key not in self._forward_cache:
            self._forward_cache[key] = jax.jit(export_params)
        return self._forward_cache[key](self.params)

    def update_weights(self, meta: WeightUpdateMeta) -> None:
        """Publish fresh weights to inference servers.

        - "disk" (reference: fsdp_engine.py:403-425): write an HF snapshot
          under `meta.path/v{version}` — staged in a temp dir and renamed,
          so a client that misses a pause can never read a half-written
          checkpoint (round-1 weak #8) — and publish a version timestamp in
          name_resolve.  Servers resolve the newest `v*` dir.
        - "transfer" (reference NCCL path: fsdp_engine.py:298-401): stream
          host-gathered bf16 arrays chunk-wise over HTTP straight into each
          server (`/update_weights_chunk`), then commit.  No shared
          filesystem in the loop.
        """
        try:
            if meta.type == "disk":
                if self._staged != ("disk", self._version):
                    self._write_disk_snapshot(meta)
                if distributed.is_head():
                    name_resolve.add(
                        names.update_weights_from_disk(
                            meta.experiment_name, meta.trial_name, self._version
                        ),
                        str(time.time_ns()),
                        replace=True,
                    )
            elif meta.type == "transfer":
                self._update_weights_transfer(meta)
            else:
                raise NotImplementedError(f"weight update type {meta.type!r}")
        finally:
            # ALWAYS consume the staged marker: a failed commit (e.g. a
            # server restarted and lost its staged chunks -> 409) must make
            # the retry re-push rather than skip to another doomed commit
            self._staged = None

    def stage_weights(self, meta: WeightUpdateMeta) -> None:
        """Run the EXPENSIVE half of a weight publish while generation is
        still running, so only the cheap commit sits inside the pause
        window: disk = export + snapshot write (publication of the
        name_resolve version key waits for update_weights); transfer =
        export + chunk streaming into the servers' staging buffers (the
        swap waits for the commit).  Call with the same version that
        update_weights will publish."""
        if meta.type == "disk":
            self._write_disk_snapshot(meta)
        elif meta.type == "transfer":
            self._push_transfer_chunks(meta)
        else:
            raise NotImplementedError(f"weight update type {meta.type!r}")
        self._staged = (meta.type, self._version)

    def _write_disk_snapshot(self, meta: WeightUpdateMeta) -> None:
        final = os.path.join(meta.path, f"v{self._version}")
        tmp = os.path.join(meta.path, f".tmp-v{self._version}-{os.getpid()}")
        if distributed.is_head():
            host = self._export_params()
            save_hf_checkpoint(
                host,
                self.model_config,
                tmp,
                save_dtype="bfloat16",
                tokenizer_src=self.config.path or None,
            )
            if os.path.isdir(final):  # re-publish of the same version
                import shutil

                shutil.rmtree(final)
            os.rename(tmp, final)
            self._prune_weight_dirs(meta.path, keep=2)
        else:
            self._host_params()  # participate in the replication collectives

    @staticmethod
    def _prune_weight_dirs(root: str, keep: int) -> None:
        import re
        import shutil

        vs = sorted(
            (int(m.group(1)), d)
            for d in os.listdir(root)
            if (m := re.fullmatch(r"v(\d+)", d)) and os.path.isdir(os.path.join(root, d))
        )
        for _, d in vs[:-keep]:
            shutil.rmtree(os.path.join(root, d), ignore_errors=True)

    def _server_addrs(self, meta: WeightUpdateMeta, timeout: float = 30.0) -> list:
        """Same discovery chain as the rollout client
        (core/remote.py:_discover_servers), with a registration-race poll."""
        env = os.environ.get("AREAL_LLM_SERVER_ADDRS")
        if env:
            return env.split(",")
        key = names.gen_servers(meta.experiment_name, meta.trial_name)
        deadline = time.monotonic() + timeout
        while True:
            found = name_resolve.get_subtree(key)
            if found:
                return sorted(found)
            if time.monotonic() >= deadline:
                raise RuntimeError(
                    "no generation servers registered for weight transfer"
                )
            time.sleep(0.5)

    def _update_weights_transfer(self, meta: WeightUpdateMeta) -> None:
        """Chunk-streamed push + commit (reference NCCL-broadcast intent,
        fsdp_engine.py:298-401, over HTTP/DCN).  With a prior
        `stage_weights` call the chunks already sit in the servers'
        staging buffers and only the commit (weight swap) runs here.  The
        measured wall time lands in `self.last_weight_update_seconds`."""
        t0 = time.perf_counter()
        if self._staged != ("transfer", self._version):
            self._push_transfer_chunks(meta)
        self._commit_transfer(meta)
        self._notify_router(meta)
        self.last_weight_update_seconds = time.perf_counter() - t0

    def _ensure_transfer_executor(self):
        if self._transfer_executor is None:
            import concurrent.futures

            self._transfer_executor = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="weight-transfer"
            )
        return self._transfer_executor

    def _run_on_transfer_thread(self, coro) -> None:
        """Run an asyncio coroutine on the dedicated transfer thread (the
        caller thread may own its own event loop) and block on it —
        weight publication is a synchronous control-plane action."""
        import asyncio

        self._ensure_transfer_executor().submit(asyncio.run, coro).result()

    def _push_transfer_chunks(self, meta: WeightUpdateMeta) -> None:
        """Stream every HF-named array, sliced into <= chunk_mb pieces, as
        raw `application/octet-stream` bodies (name/dtype/shape/offset in
        X-Weight-* headers — no base64 inflation or per-chunk json parse)
        into every server's staging buffer (gen/server.py assembles by
        (name, offset)).  Does NOT swap weights — safe while the servers
        are still generating."""
        import asyncio
        import json as _json

        import ml_dtypes

        from areal_tpu.models.hf import params_to_hf_state
        from areal_tpu.utils.http import apost_bytes_with_retry

        host = self._export_params()
        if not distributed.is_head():
            return
        addrs = self._server_addrs(meta)
        bf16 = np.dtype(ml_dtypes.bfloat16)
        chunk_bytes = max(1, meta.chunk_mb) << 20
        # bf16 raw bytes are built while the host tree is alive (fp32
        # masters: transient ~3x model bytes), then the host tree is
        # dropped so only ~1x bf16 remains for the push
        state = [
            (name, np.ascontiguousarray(arr.astype(bf16)).tobytes(), list(arr.shape))
            for name, arr in params_to_hf_state(host, self.model_config)
        ]
        del host

        version = self._version

        async def push(addr: str):
            import aiohttp

            from areal_tpu.utils.http import (
                arequest_with_retry,
                get_default_connector,
            )

            async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=600.0, sock_connect=30.0),
                connector=get_default_connector(),
            ) as session:
                for name, raw, shape in state:
                    meta_hdrs = {
                        "X-Weight-Name": name,
                        "X-Weight-Dtype": "bfloat16",
                        "X-Weight-Shape": _json.dumps(shape),
                        "X-Weight-Nbytes": str(len(raw)),
                    }
                    for off in range(0, len(raw) or 1, chunk_bytes):
                        await apost_bytes_with_retry(
                            addr=addr,
                            endpoint="/update_weights_chunk",
                            data=raw[off : off + chunk_bytes],
                            headers={**meta_hdrs, "X-Weight-Offset": str(off)},
                            timeout=300.0,
                            session=session,
                        )
                # device-stage the assembled tree while generation keeps
                # running: the later commit becomes an O(abort) pointer
                # swap (best-effort — a server without standby HBM falls
                # back to commit-time placement)
                await arequest_with_retry(
                    addr=addr,
                    endpoint="/update_weights_chunk",
                    payload={"prepare": True, "version": version},
                    method="POST",
                    timeout=600.0,
                )

        async def run():
            await asyncio.gather(*[push(a) for a in addrs])

        self._run_on_transfer_thread(run())

    def _commit_transfer(self, meta: WeightUpdateMeta) -> None:
        """Swap the staged weights in on every server."""
        import asyncio

        from areal_tpu.utils.http import arequest_with_retry

        if not distributed.is_head():
            return
        addrs = self._server_addrs(meta)
        version = self._version

        async def run():
            await asyncio.gather(*[
                arequest_with_retry(
                    addr=a,
                    endpoint="/update_weights_chunk",
                    payload={"commit": True, "version": version,
                             "live": meta.live_commit},
                    method="POST",
                    timeout=600.0,
                )
                for a in addrs
            ])

        self._run_on_transfer_thread(run())

    def _notify_router(self, meta: WeightUpdateMeta) -> None:
        """Transfer publishes leave no disk checkpoint for a router's
        watcher to see, so its fleet staleness gate needs the version pushed
        explicitly (ADVICE r3: the gate's budget otherwise never grows and
        admission wedges at 409).  Best-effort: the router also polls the
        backends' served version as a safety net."""
        if not distributed.is_head():
            return
        try:
            addr = name_resolve.get(
                names.gen_router(meta.experiment_name, meta.trial_name)
            )
        except Exception:  # noqa: BLE001 — no router in this deployment
            return
        version = self._version

        def _post():
            try:
                import requests

                requests.post(
                    f"http://{addr}/set_version",
                    json={"version": version},
                    timeout=5,
                )
            except Exception as e:  # noqa: BLE001 — poller covers the miss
                logger.warning(
                    f"router /set_version failed (poll covers it): {e}"
                )

        # fire-and-forget on the transfer thread: a stale router address
        # must not stall the publish path on a connect timeout
        self._ensure_transfer_executor().submit(_post)

    def save(self, meta: SaveLoadMeta) -> None:
        """Model weights as an HF safetensors dir (interop with inference
        servers and transformers); optimizer state via orbax/tensorstore —
        sharded (each process writes only the shards it owns), structure-
        checked on restore, and not tied to optax's leaf ordering the way
        the old positional npz dump was (round-1 weak #5).

        With LoRA: exports (with_optim=False) fold the adapters into the
        base weights for downstream consumers; recover checkpoints
        (with_optim=True) keep the base UNMERGED and persist the adapters
        alongside the optimizer state so load() round-trips exactly."""
        from areal_tpu.models.lora import split_lora

        lora_on = self.model_config.lora_rank > 0
        if meta.with_optim:
            host, host_adapters = (
                split_lora(self._host_params()) if lora_on
                else (self._host_params(), None)
            )
        else:
            host, host_adapters = self._export_params(), None
        save_hf_checkpoint(
            host,
            self.model_config,
            meta.path,
            save_dtype="bfloat16" if not meta.with_optim else "float32",
            tokenizer_src=self.config.path or None,
        )
        if meta.with_optim and self.opt_state is not None:
            import orbax.checkpoint as ocp

            state = {
                "opt_state": self.opt_state,
                "step": jnp.asarray(self.step_count, jnp.int32),
            }
            if host_adapters is not None:
                state["lora"] = host_adapters
            ckptr = ocp.StandardCheckpointer()
            with self.mesh:
                ckptr.save(
                    os.path.abspath(os.path.join(meta.path, "optimizer_state")),
                    state,
                    force=True,
                )
                ckptr.wait_until_finished()
            ckptr.close()

    def load(self, meta: SaveLoadMeta) -> None:
        host_params, mc = load_hf_params(
            meta.path, self.model_config, dtype=self.config.param_dtype
        )
        lora_on = (
            self.model_config is not None and self.model_config.lora_rank > 0
        )
        self.model_config = mc.replace(
            dtype=self.config.dtype,
            param_dtype=self.config.param_dtype,
            remat=self.config.gradient_checkpointing,
            remat_policy=getattr(self.config, "remat_policy", "full"),
            scan_unroll=getattr(self.config, "scan_unroll", 1),
            layer_group_size=getattr(self.config, "layer_group_size", 1),
            lora_rank=self.model_config.lora_rank if lora_on else 0,
            lora_alpha=self.model_config.lora_alpha,
            lora_targets=self.model_config.lora_targets if lora_on else (),
        )
        # the checkpoint may carry a different depth: re-apply the
        # grouped-scan contracts against the loaded num_layers
        if self.model_config.num_layers % max(
            1, self.model_config.layer_group_size
        ):
            raise ValueError(
                f"layer_group_size={self.model_config.layer_group_size} "
                f"must divide the loaded checkpoint's "
                f"num_layers={self.model_config.num_layers}"
            )
        from areal_tpu.models.transformer import effective_scan_unroll

        self._effective_scan_unroll = effective_scan_unroll(self.model_config)
        if lora_on:
            from areal_tpu.models.lora import add_lora_params

            host_params = add_lora_params(
                host_params, self.model_config, jax.random.PRNGKey(1)
            )
        self.params = shard_pytree(
            self.mesh,
            host_params,
            param_partition_specs(self.model_config, tp=self.mesh.shape["tp"]),
        )
        opt_path = os.path.abspath(os.path.join(meta.path, "optimizer_state"))
        if meta.with_optim and os.path.isdir(opt_path):
            import orbax.checkpoint as ocp

            from areal_tpu.models.lora import split_lora

            template = {
                "opt_state": self.opt_state,
                "step": jnp.asarray(self.step_count, jnp.int32),
            }
            if lora_on:
                # sharded live adapters as the template: orbax restores
                # each process's shards in place (np.asarray would crash on
                # multi-host global arrays)
                template["lora"] = split_lora(self.params)[1]
            ckptr = ocp.StandardCheckpointer()
            with self.mesh:
                # the live opt_state is the template: orbax restores each
                # leaf with the matching sharding and validates structure
                restored = ckptr.restore(opt_path, template)
            ckptr.close()
            self.opt_state = self._replicate_scalars(restored["opt_state"])
            self.step_count = int(restored["step"])
            if lora_on:
                layers = dict(self.params["layers"])
                for key, arr in restored["lora"].items():
                    sub_name, leaf = key.split(".", 1)
                    sub = dict(layers[sub_name])
                    sub[leaf] = jax.device_put(
                        arr, self.params["layers"][sub_name][leaf].sharding
                    )
                    layers[sub_name] = sub
                self.params = {**self.params, "layers": layers}

    def step_lr_scheduler(self) -> None:
        # the schedule is step-indexed inside the jitted update; nothing to do
        pass

    def set_version(self, version: int) -> None:
        self._version = version

    def get_version(self) -> int:
        return self._version
