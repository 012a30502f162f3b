"""Find a cell's files by name.

Everything that belongs to one cell, one configuration, one per-layer
metric, one reader or one kind of cell is a file of its own under the
benchmark directory, found by the name `BENCHMARK.json` (or another data
file) gives it.  A later PR adds files; it edits none.

    workloads/<cell>.json        traffic parameters, `config`, `kind`, `chips`
    configs/<config>.json        the published config.json keys + `bench`
    layer_metrics/*.json         declarative per-layer metrics (`reader`, `moves`,
                                 `cells` where only some cells have it to read)
    readers/<reader>.py          `read(ctx, spec) -> float | None`
    kinds/<kind>.py              `run(cell, config, args, bench) -> result`
"""

import importlib.util
import json
import os

BENCH_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CELL_KEYS = ("config", "kind", "chips", "why", "traffic")
METRIC_KEYS = ("name", "unit", "layer", "moves", "reader")


class BenchFileError(Exception):
    """A data file is missing, or says something the harness cannot do."""


def _read_json(path):
    if not os.path.isfile(path):
        raise BenchFileError(f"no such benchmark file: {path}")
    with open(path) as f:
        return json.load(f)


def _require(d, keys, path):
    missing = [k for k in keys if k not in d]
    if missing:
        raise BenchFileError(f"{path} lacks {missing}")


def load_cell(name, root=BENCH_ROOT):
    path = os.path.join(root, "workloads", f"{name}.json")
    cell = _read_json(path)
    _require(cell, CELL_KEYS, path)
    if cell["chips"] not in (1, 4):
        raise BenchFileError(f"{path}: chips must be 1 or 4")
    cell["name"] = name
    return cell


def load_config(name, root=BENCH_ROOT):
    path = os.path.join(root, "configs", f"{name}.json")
    cfg = _read_json(path)
    _require(cfg, ("bench", "hidden_size", "num_hidden_layers"), path)
    _require(cfg["bench"], ("source", "reduced", "dtype"), path)
    cfg["bench"]["name"] = name
    return cfg


def _load_module(kind, name, root):
    """`<root>/<kind>/<name>.py`, else the one shipped with the harness: a
    benchmark directory elsewhere (a test's, a later PR's) uses both."""
    path = os.path.join(root, kind, f"{name}.py")
    if not os.path.isfile(path):
        path = os.path.join(BENCH_ROOT, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise BenchFileError(f"unknown {kind[:-1]} {name!r}: no {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name, root=BENCH_ROOT):
    """-> the reader's `read(ctx, spec)`."""
    return _load_module("readers", name, root).read


def load_kind(name, root=BENCH_ROOT):
    """-> the kind's `run(cell, config, bench)`."""
    return _load_module("kinds", name, root).run


def load_layer_metrics(cell_name, root=BENCH_ROOT):
    """The cell's per-layer metrics, each with its reader resolved (an
    unknown reader is refused here, before a run).

    A file that lists `cells` is for those cells: something only some
    programs have to read (a scope, a kernel, a counter of one kind).  A
    file WITHOUT the key is for every cell that `BENCHMARK.json` holds to
    the file's `moves` metric, those that later PRs add too: such a cell
    inherits the metric with no new file and no new entry, and has to print
    a number for it.  A `moves` that names no end-to-end metric is refused,
    as is a file without `cells` where there is no `BENCHMARK.json` to ask.
    """
    d = os.path.join(root, "layer_metrics")
    moved = _cells_by_end_to_end(root)
    out = []
    for fn in sorted(os.listdir(d)) if os.path.isdir(d) else []:
        if not fn.endswith(".json"):
            continue
        path = os.path.join(d, fn)
        spec = _read_json(path)
        _require(spec, METRIC_KEYS, path)
        if moved is not None and spec["moves"] not in moved:
            raise BenchFileError(
                f"{path}: moves {spec['moves']!r} is no end-to-end metric of "
                "BENCHMARK.json")
        if "cells" in spec:
            mine = cell_name in spec["cells"]
        elif moved is None:
            raise BenchFileError(
                f"{path} lists no cells and there is no BENCHMARK.json to "
                f"say which cells report {spec['moves']!r}")
        else:
            cells = moved[spec["moves"]]
            mine = cells is None or cell_name in cells
        if not mine:
            continue
        spec["read"] = load_reader(spec["reader"], root)
        out.append(spec)
    return out


def _cells_by_end_to_end(root):
    """{end-to-end metric: its `workloads`, None for every cell} from the
    `BENCHMARK.json` beside the benchmark directory; None without one."""
    path = os.path.join(os.path.dirname(root), "BENCHMARK.json")
    if not os.path.isfile(path):
        return None
    return {m["name"]: m.get("workloads")
            for m in _read_json(path)["end_to_end"]}


def end_to_end_metrics(cell_name, root=BENCH_ROOT):
    """Names of the end-to-end metrics `BENCHMARK.json` holds this cell to
    (no `workloads` key on a metric means every cell)."""
    moved = _cells_by_end_to_end(root)
    if moved is None:
        return None
    return [name for name, cells in moved.items()
            if cells is None or cell_name in cells]
