"""RLVR (RL with verifiable rewards) rollout workflow.

Behavioral counterpart of the reference's `RLVRWorkflow`
(areal/workflow/rlvr.py:37): generate `n_samples` completions per prompt
concurrently, score each with a (sync) reward function run off-loop, and emit
one padded trajectory batch.  Per-token `versions` from the inference engine
ride along for decoupled-PPO staleness correction.
"""

import asyncio
import os
import uuid
from typing import Any, Callable, Dict, Optional

import numpy as np

from areal_tpu.api.config import GenerationHyperparameters
from areal_tpu.api.io_struct import ModelRequest
from areal_tpu.api.reward import AsyncRewardWrapper
from areal_tpu.api.workflow import RolloutWorkflow
from areal_tpu.utils import logging, telemetry
from areal_tpu.utils.data import pad_sequences_to_tensors

logger = logging.getLogger("rlvr")


class RLVRWorkflow(RolloutWorkflow):
    def __init__(
        self,
        reward_fn: Callable[..., float],
        gconfig: GenerationHyperparameters,
        tokenizer=None,
        enable_thinking: bool = False,
        rollout_stat_scope: str = "rollout",
        dump_dir: Optional[str] = None,
    ):
        self.reward_fn = AsyncRewardWrapper(reward_fn)
        self.gconfig = gconfig
        self.tokenizer = tokenizer
        self.enable_thinking = enable_thinking
        self.dump_dir = dump_dir
        if dump_dir:
            os.makedirs(dump_dir, exist_ok=True)

    def _tokenize_prompt(self, data: Dict[str, Any]):
        if "input_ids" in data:
            return list(data["input_ids"])
        if self.tokenizer is None:
            raise ValueError("need tokenizer or pre-tokenized input_ids")
        if "messages" in data:
            return self.tokenizer.apply_chat_template(
                data["messages"],
                add_generation_prompt=True,
                tokenize=True,
                enable_thinking=self.enable_thinking,
            )
        return self.tokenizer.encode(data["prompt"])

    def _build_request(self, data: Dict[str, Any]) -> ModelRequest:
        """Hook: subclasses (vision) add modality payloads to the request.

        A dataset item may carry its own `max_new_tokens` to cap this
        prompt's generation budget below the workflow default (e.g.
        per-difficulty budgets, or benchmark workloads with realistic
        length variance)."""
        overrides = {"n_samples": 1}
        if "max_new_tokens" in data:
            overrides["max_new_tokens"] = min(
                int(data["max_new_tokens"]), self.gconfig.max_new_tokens
            )
        return ModelRequest(
            rid=str(uuid.uuid4()),
            input_ids=self._tokenize_prompt(data),
            gconfig=self.gconfig.new(**overrides),
            tokenizer=self.tokenizer,
        )

    def _reward_kwargs(self, data: Dict[str, Any]) -> Dict[str, Any]:
        """Hook: subclasses filter non-picklable/heavy fields (images)."""
        return data

    async def arun_episode(self, engine, data: Dict[str, Any]):
        n = self.gconfig.n_samples
        req = self._build_request(data)
        reqs = [req.copy() for _ in range(n)]
        if n > 1:
            # GRPO group: declare the siblings so routing keeps them on one
            # replica and the engine admits them as one prefix-sharing
            # cluster (one prefill + KV fan-out instead of n prefills)
            for k, r in enumerate(reqs):
                r.rid = f"{req.rid}-{k}"
                r.group_id = req.rid
                r.group_n = n
        # pin the lifecycle trace id here (not in agenerate) so reward and
        # trainer-consumption events can be joined to generation-side spans
        for r in reqs:
            r.trace_id = r.rid
        # submit of the group's samples to the last of them finished
        with telemetry.span("generate"):
            resps = await asyncio.gather(
                *[engine.agenerate(r) for r in reqs]
            )
        results = []
        for r, resp in zip(reqs, resps):
            completion_str = (
                self.tokenizer.decode(resp.output_tokens)
                if self.tokenizer is not None
                else ""
            )
            prompt_str = (
                self.tokenizer.decode(resp.input_tokens)
                if self.tokenizer is not None
                else ""
            )
            # spans an await: episodes of one event loop overlap on its line
            with telemetry.span("reward"):
                reward = await self.reward_fn(
                    prompt_str,
                    completion_str,
                    resp.input_tokens,
                    resp.output_tokens,
                    **self._reward_kwargs(data),
                )
            seq = resp.input_tokens + resp.output_tokens
            logprobs = [0.0] * resp.input_len + resp.output_logprobs
            loss_mask = [0] * resp.input_len + [1] * resp.output_len
            versions = [-1] * resp.input_len + resp.output_versions
            result = dict(
                input_ids=np.array(seq, dtype=np.int32),
                logprobs=np.array(logprobs, dtype=np.float32),
                loss_mask=np.array(loss_mask, dtype=np.int32),
                versions=np.array(versions, dtype=np.int32),
                rewards=np.float32(reward),
            )
            if telemetry.is_enabled():
                out_v = [v for v in resp.output_versions if v >= 0]
                telemetry.emit(
                    "reward",
                    trace_id=r.trace_id,
                    reward=float(reward),
                    output_len=resp.output_len,
                    stop_reason=resp.stop_reason,
                    version_min=min(out_v) if out_v else -1,
                    version_max=max(out_v) if out_v else -1,
                )
                # 0-d scalar: pad_sequences_to_tensors stacks it to [B], and
                # the trainer strips it before device transfer (no new XLA
                # signature); keyed only when enabled so concat across a run
                # sees a consistent key set
                result["trace_keys"] = np.int64(telemetry.trace_key(r.trace_id))
            results.append(self._augment_result(result, data, resp))
            if self.dump_dir:
                self._dump(data, prompt_str, completion_str, reward, resp)
        batch = pad_sequences_to_tensors(results)
        return self._augment_batch(batch, data, len(results))

    def _augment_result(self, result, data, resp):
        """Hook: subclasses add per-sample keys (vision: mrope positions)."""
        return result

    def _augment_batch(self, batch, data, n_samples: int):
        """Hook: subclasses add batch-level payloads (vision: pixels)."""
        return batch

    def _dump(self, data, prompt_str, completion_str, reward, resp):
        qid = str(data.get("query_id", data.get("qid", "unknown")))
        path = os.path.join(self.dump_dir, f"{qid}.txt")
        with open(path, "a") as f:
            f.write(
                f"prompt: {prompt_str}\ncompletion: {completion_str}\n"
                f"reward: {reward} stop: {resp.stop_reason} "
                f"len: {resp.output_len}\n{'-' * 40}\n"
            )
