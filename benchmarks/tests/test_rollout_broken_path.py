"""`rollout_decode`'s run with the timed path broken underneath: `correct`
has to come out false.  The harness's look for a chip is skipped by the CPU
rehearsal (toy sizes, float32, the comparison exact); everything else of a
run is driven: closed loop, window, the float32 reference, the result line.
The fault is planted where the engine hands a finished request back."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

WRAPPER = """
import runpy, sys
import areal_tpu.gen.engine as engine
finish = engine.GenRequest.finish
def broken(self, reason):
    if len(self.output_tokens) > 4:
        {fault}
    return finish(self, reason)
engine.GenRequest.finish = broken
sys.argv = ["benchmarks/run.py"] + sys.argv[1:]
runpy.run_path("benchmarks/run.py", run_name="__main__")
"""


@pytest.mark.parametrize("fault, correct, failed", [
    ("pass", True, False),
    # a token altered where it is produced: the log-prob that came with it
    # is another token's, and every later one was conditioned on it
    ("self.output_tokens[2] = (self.output_tokens[2] + 1) % 512", False, False),
    # an answer altered: one log-prob off by what int8 weights would cost
    ("self.output_logprobs[2] -= 0.5", False, False),
    # every other request delivered a token without its log-prob: those are
    # counted as failed, the rest still compare
    ("len(self.output_tokens) % 2 and self.output_logprobs.pop()", False, True),
])
def test_correct_sees_the_fault(fault, correct, failed):
    out = subprocess.run(
        [sys.executable, "-c", WRAPPER.format(fault=fault),
         "--workload", "rollout_decode", "--seed", "3000000061",
         "--seconds", "2", "--trace", "0", "--cpu-rehearsal"],
        cwd=REPO, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.splitlines()[-1])
    assert line["correct"] is correct, line["compared"]
    assert (line["failed"] > 0) is failed
    # the numbers compared stand beside their limits, last on the line and
    # as the last lines of standard error
    assert list(line)[-1] == "compared"
    compared = line["compared"]
    assert set(compared) == {"logprob_mean_abs", "logprob_max_abs", "failed"}
    over = [k for k, c in compared.items() if c["value"] > c["limit"]]
    assert bool(over) is (not correct)
    tail = out.stderr.strip().splitlines()[-len(compared):]
    assert all(x.startswith("compared ") for x in tail)
