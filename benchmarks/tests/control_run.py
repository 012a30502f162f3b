#!/usr/bin/env python3
"""A cell's control: the cell as `run.py` runs it, with `controls/<cell>.json`'s
`patch` laid over the blocks of its workload file (the precision below the
one the cell states).  Same arguments as `run.py`; the result line must say
`correct` false, with the numbers in the control's `fails` over their limits.

    chiprun -- python3 benchmarks/tests/control_run.py --workload rollout_decode \\
        --seed 4100004001 --seconds 40 --trace 0

`test_controls.py` runs each control at the rehearsal's size on the CPU; the
benchmark's own runs never run one.
"""

import json
import os
import runpy
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from benchmarks.lib import loader  # noqa: E402


def main():
    name = sys.argv[sys.argv.index("--workload") + 1]
    with open(os.path.join(BENCH, "controls", f"{name}.json")) as f:
        patch = json.load(f)["patch"]
    load_cell = loader.load_cell

    def load_patched(cell_name, root=loader.BENCH_ROOT):
        cell = load_cell(cell_name, root)
        for block, keys in patch.items():
            cell[block] = {**cell[block], **keys}
        return cell

    loader.load_cell = load_patched
    sys.argv[0] = os.path.join(BENCH, "run.py")
    runpy.run_path(sys.argv[0], run_name="__main__")


if __name__ == "__main__":
    main()
