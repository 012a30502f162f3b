"""Each cell's control (`controls/<cell>.json` through `control_run.py`) has
to come out not `correct`, by the numbers it says it fails.  Here at the
rehearsal's size on the CPU; the readings on the chip at the cell's own size
are in PERF.md."""

import glob
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CONTROLS = sorted(glob.glob(os.path.join(BENCH, "controls", "*.json")))


@pytest.mark.parametrize(
    "path", CONTROLS, ids=[os.path.basename(p)[:-5] for p in CONTROLS])
def test_control_is_not_correct(path):
    name = os.path.basename(path)[:-5]
    with open(path) as f:
        control = json.load(f)
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "control_run.py"),
         "--workload", name, "--seed", "3000000067", "--seconds", "2",
         "--trace", "0", "--cpu-rehearsal"],
        cwd=os.path.dirname(BENCH), capture_output=True, text=True,
        timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.splitlines()[-1])
    assert line["correct"] is False
    for number in control["fails"]:
        c = line["compared"][number]
        assert c["value"] > c["limit"], (number, c)
