"""C6 — jit signature budgets: the compile-cache ladder as a static proof.

PR 2/PR 5 perf rests on decode/prefill staying on a *finite, enumerable*
ladder of XLA signatures: every static argument of a jitted hot-path
callable must come from the pow2 bucket ladders
(``round_up_to_bucket``, ``plan_decode_tiers``) or engine-lifetime
config, never from raw lengths or ad-hoc arithmetic.  The soak tests pin
this at runtime; this checker proves it at lint time and quantifies it:

- ``off-ladder-static``: a call site of a registered jitted callable
  (assignments shaped ``self._x_fn = jax.jit(f, static_argnums=...)`` or
  ``x_fn = jax.jit(...)`` in a ``# areal-lint: hot-path`` file) passes a
  static argument the abstract evaluator cannot prove on-ladder.  The
  value lattice: ``0``/``None``/bools are sentinels; ``round_up_to_bucket(...)``
  is ladder by construction; ``self.<attr>`` (engine-lifetime config) is
  a fixed point; ``min``/``max``/``int``/ternaries/``or`` of safe values
  stay safe; local names resolve through every reaching assignment;
  parameters resolve one level into resolved callers.  Arithmetic
  (``span + n``), ``len(...)`` and bare non-zero literals are OFF-ladder
  — each would mint an unbounded signature family.
- ``signature-budget-stale``: ``analysis/signature_budget.json`` (the
  checked-in per-function budget, cross-checked by the soak tests via
  observed-compiled-programs ≤ budget) no longer matches what the ladder
  math derives from its own reference configs.  Regenerate with
  ``python scripts/lint.py --write-budget``.

The budget arithmetic below deliberately re-derives the ladder in pure
Python (no jax/numpy import: the lint CLI and CI hook run in bare
venvs) and mirrors ``areal_tpu/utils/datapack.py round_up_to_bucket`` /
``gen/engine.py plan_decode_tiers`` exactly; test_lint.py pins the two
against each other.
"""

import ast
import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from areal_tpu.analysis.callgraph import CallGraph, FuncInfo, dotted_name
from areal_tpu.analysis.core import Finding, SourceFile, apply_suppression

RULE_OFF_LADDER = "off-ladder-static"
RULE_STALE = "signature-budget-stale"

BUDGET_PATH = os.path.join("areal_tpu", "analysis", "signature_budget.json")

_LADDER_CALLS = {"round_up_to_bucket"}
_SAFE_WRAPPERS = {"min", "max", "int", "abs"}


# --------------------------- ladder arithmetic -------------------------
# Pure-python mirrors of the runtime bucket math (datapack.py /
# engine.plan_decode_tiers).  Keep in lockstep — test_lint.py compares
# them against the real implementations.


def ladder_values(quantum: int, max_len: int) -> List[int]:
    """Every value `round_up_to_bucket(n, quantum, max_len)` can return."""
    vals: List[int] = []
    b = quantum
    while b < max_len:
        vals.append(b)
        b *= 2
    vals.append(max_len)
    return vals


def pow2_row_counts(n_slots: int) -> int:
    """Distinct `1 << (k - 1).bit_length()` paddings for k in 1..n_slots."""
    return (n_slots - 1).bit_length() + 1 if n_slots > 0 else 0


def plan_tier_count(n_slots: int, n_tiers: int) -> int:
    if n_tiers <= 1:
        return 1
    if n_slots >> (n_tiers - 1) < 1:
        raise ValueError(f"decode_tiers={n_tiers} needs more slots")
    return n_tiers


def compute_budgets(config: Dict[str, int]) -> Dict[str, int]:
    """Static signature budget per jitted hot-path function for one
    engine config.  These are upper bounds on distinct compiled programs:
    static-arg combinations x shape buckets x the x2 sharding family
    (cold device_put vs decode-output resident arrays).  Soak tests
    assert observed `_cache_size()` <= these."""
    if "train_shapes" in config:
        # train-step cache (engine/jax_train.py _train_step_cache): one
        # program per (loss_fn, n_mbs, row_len, padded_len) signature.
        # The reference soak drives exactly `train_shapes` distinct
        # signatures; the two-level layer scan (layer_group_size), remat
        # rung, and scan unroll are engine-lifetime config baked into the
        # traced program — they must add NO signature axis.
        return {"train_step": config["train_shapes"]}
    q = config["prompt_bucket"]
    m = config["max_seq_len"]
    slots = config["n_slots"]
    tiers = plan_tier_count(slots, config.get("decode_tiers", 1))
    ladder = len(ladder_values(q, m))
    rows = pow2_row_counts(slots)
    return {
        # per non-empty tier: key_window rides ladder(q, m)
        "decode": tiers * ladder,
        # pow2 row padding x prompt bucket x sharding family
        "prefill": rows * ladder * 2,
        # + static (copy_block in ladder+{0}, key_window in ladder)
        "suffix_prefill": rows * ladder * (ladder + 1) * 2,
        # host spill: one program per block bucket (row is traced)
        "host_gather": ladder,
        # host swap-in: shape-keyed on the same bucketed block
        "host_scatter": ladder,
        # speculative verify (ISSUE 12): per tier x key bucket x nonzero
        # draft-length rung (D=0 reuses the decode program, so only the
        # nonzero rungs of the spec ladder mint verify signatures);
        # spec_rungs=0 (spec decode off) budgets zero verify programs
        "verify": tiers * ladder * config.get("spec_rungs", 0),
        # ragged paged-decode attention (ISSUE 19): the collapsed
        # grid-wide dispatch drops the tier factor entirely — one decode
        # program per K bucket plus one verify program per (K bucket,
        # nonzero D rung).  Page-count buckets add NO axis: the kernel's
        # page size rides the prompt-bucket quantum, so each K bucket IS
        # its page-count bucket (K/q pages, 1:1).  ragged=0 (flag off)
        # budgets zero ragged programs.
        "ragged_decode": ladder
        * (1 + config.get("spec_rungs", 0))
        * config.get("ragged", 0),
    }


def budget_drift(doc: Dict) -> List[str]:
    """Mismatches between the checked-in budgets and what the ladder math
    derives from the document's own reference configs (empty = fresh)."""
    problems: List[str] = []
    refs = doc.get("reference_configs")
    if not isinstance(refs, dict) or not refs:
        return ["no reference_configs section"]
    for name, entry in refs.items():
        cfg = entry.get("config", {})
        try:
            fresh = compute_budgets(cfg)
        except (KeyError, ValueError) as e:
            problems.append(f"{name}: unusable config ({e})")
            continue
        stored = entry.get("budgets", {})
        if stored != fresh:
            problems.append(
                f"{name}: stored budgets {stored} != derived {fresh}"
            )
    return problems


def render_budget_doc(reference_configs: Dict[str, Dict[str, int]]) -> Dict:
    """The signature_budget.json payload for a set of reference configs
    (what `scripts/lint.py --write-budget` emits)."""
    return {
        "comment": (
            "Static jit-signature budgets (areal-lint C6).  For each "
            "reference engine config: the maximum number of distinct "
            "compiled programs each hot-path jitted callable may mint, "
            "derived from the pow2 bucket ladders.  The jit-cache soak "
            "tests assert observed _cache_size() <= budget; lint "
            "(`signature-budget-stale`) asserts these numbers match the "
            "ladder math.  Regenerate: python scripts/lint.py "
            "--write-budget.  This file is the authoritative ladder "
            "spec (docs/architecture.md, Shape ladders)."
        ),
        "formulas": {
            "ladder(q, M)": "|{q*2^k : q*2^k < M}| + 1  (round_up_to_bucket image)",
            "rows(S)": "(S-1).bit_length() + 1  (pow2 row paddings)",
            "decode": "decode_tiers * ladder(prompt_bucket, max_seq_len)",
            "prefill": "rows(n_slots) * ladder * 2",
            "suffix_prefill": "rows(n_slots) * ladder * (ladder + 1) * 2",
            "host_gather": "ladder  (traced row; one program per block bucket)",
            "host_scatter": "ladder  (shape-keyed on the bucketed block)",
            "verify": (
                "decode_tiers * ladder * spec_rungs  (nonzero draft-length"
                " rungs of the spec ladder; 0 when spec decode is off)"
            ),
            "ragged_decode": (
                "ladder * (1 + spec_rungs) * ragged  (collapsed grid-wide"
                " dispatch: the tier factor is gone, and page-count"
                " buckets map 1:1 onto K buckets because the kernel page"
                " size IS the prompt-bucket quantum; 0 when the ragged"
                " flag is off)"
            ),
            "train_step": (
                "train_shapes  (distinct (loss_fn, n_mbs, row_len,"
                " padded_len) signatures the soak drives;"
                " layer_group_size / remat rung / scan unroll are"
                " engine-lifetime config and add NO axis)"
            ),
        },
        "reference_configs": {
            name: {"config": cfg, "budgets": compute_budgets(cfg)}
            for name, cfg in reference_configs.items()
        },
    }


# --------------------------- jit def collection ------------------------


@dataclass
class JitDef:
    name: str  # handle attribute/name, e.g. "_decode_fn"
    line: int
    static_positions: List[int]
    params: List[str] = field(default_factory=list)  # wrapped fn params


def _static_positions(call: ast.Call) -> List[int]:
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return [v.value]
            if isinstance(v, (ast.Tuple, ast.List)):
                out = []
                for el in v.elts:
                    if isinstance(el, ast.Constant) and isinstance(
                        el.value, int
                    ):
                        out.append(el.value)
                    else:
                        return []
                return out
    return []


def collect_jit_defs(sf: SourceFile) -> List[JitDef]:
    defs: List[JitDef] = []
    if sf.tree is None:
        return defs
    fn_params: Dict[str, List[str]] = {}
    for n in ast.walk(sf.tree):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fn_params[n.name] = [
                a.arg for a in list(n.args.posonlyargs) + list(n.args.args)
            ]
    for n in ast.walk(sf.tree):
        if not isinstance(n, ast.Assign) or not isinstance(
            n.value, ast.Call
        ):
            continue
        if dotted_name(n.value.func) != "jax.jit":
            continue
        static = _static_positions(n.value)
        if not static:
            continue
        wrapped = n.value.args[0] if n.value.args else None
        params: List[str] = []
        if isinstance(wrapped, ast.Name):
            params = fn_params.get(wrapped.id, [])
        for tgt in n.targets:
            if (
                isinstance(tgt, ast.Attribute)
                and isinstance(tgt.value, ast.Name)
                and tgt.value.id == "self"
            ):
                defs.append(JitDef(tgt.attr, n.lineno, static, params))
            elif isinstance(tgt, ast.Name):
                defs.append(JitDef(tgt.id, n.lineno, static, params))
    return defs


# ------------------------- abstract evaluation -------------------------


class _Safety:
    def __init__(self, graph: CallGraph, depth: int = 2):
        self.graph = graph
        self.depth = depth

    def safe(
        self, expr: ast.AST, fi: FuncInfo, depth: Optional[int] = None
    ) -> Tuple[bool, str]:
        depth = self.depth if depth is None else depth
        if isinstance(expr, ast.Constant):
            v = expr.value
            if v is None or isinstance(v, (bool, str)):
                return True, ""
            if v == 0:
                return True, ""
            return (
                False,
                f"literal {v!r} is not provably on the bucket ladder",
            )
        if isinstance(expr, ast.Attribute):
            return True, ""  # engine-lifetime config / module constant
        if isinstance(expr, ast.Subscript):
            return self.safe(expr.value, fi, depth)
        if isinstance(expr, ast.Call):
            d = dotted_name(expr.func) or ""
            base = d.split(".")[-1]
            if base in _LADDER_CALLS:
                return True, ""
            if base in _SAFE_WRAPPERS:
                for a in expr.args:
                    ok, why = self.safe(a, fi, depth)
                    if not ok:
                        return False, why
                return True, ""
            if base == "len":
                return False, "len(...) is a raw (unbucketed) length"
            return False, f"call {d or '<expr>'}(...) not on the ladder"
        if isinstance(expr, ast.BoolOp):
            for v in expr.values:
                ok, why = self.safe(v, fi, depth)
                if not ok:
                    return False, why
            return True, ""
        if isinstance(expr, ast.IfExp):
            for v in (expr.body, expr.orelse):
                ok, why = self.safe(v, fi, depth)
                if not ok:
                    return False, why
            return True, ""
        if isinstance(expr, ast.Name):
            return self._safe_name(expr.id, fi, depth)
        if isinstance(expr, (ast.BinOp, ast.UnaryOp)):
            return (
                False,
                "arithmetic on lengths can leave the ladder — wrap it in "
                "round_up_to_bucket(...)",
            )
        return False, "expression shape not recognized as ladder-safe"

    def _safe_name(
        self, name: str, fi: FuncInfo, depth: int
    ) -> Tuple[bool, str]:
        assigns: List[ast.AST] = []
        augmented = False
        for n in ast.walk(fi.node):
            if isinstance(n, ast.Assign):
                for tgt in n.targets:
                    if isinstance(tgt, ast.Name) and tgt.id == name:
                        assigns.append(n.value)
            elif isinstance(n, ast.AnnAssign) and isinstance(
                n.target, ast.Name
            ):
                if n.target.id == name and n.value is not None:
                    assigns.append(n.value)
            elif isinstance(n, ast.AugAssign) and isinstance(
                n.target, ast.Name
            ):
                if n.target.id == name:
                    augmented = True
        if augmented:
            return False, f"`{name}` is arithmetically updated (+=)"
        if assigns:
            for v in assigns:
                ok, why = self.safe(v, fi, depth)
                if not ok:
                    return False, f"`{name}` <- {why}"
            return True, ""
        # a parameter: every resolved caller must pass something safe
        params = _param_names(fi.node)
        if name in params:
            if depth <= 0:
                return True, ""  # depth-bounded benefit of the doubt
            pos = params.index(name)
            for caller_key, calls in self.graph.calls.items():
                for call, callee in calls:
                    if callee != fi.key:
                        continue
                    arg = _arg_for_param(call, fi, pos, name)
                    if arg is None:
                        continue  # default applies
                    ok, why = self.safe(
                        arg, self.graph.functions[caller_key], depth - 1
                    )
                    if not ok:
                        return False, f"caller passes `{name}` = {why}"
            default = _default_for_param(fi.node, pos)
            if default is not None:
                ok, why = self.safe(default, fi, depth)
                if not ok:
                    return False, f"default for `{name}`: {why}"
            return True, ""
        return (
            False,
            f"`{name}` has no reaching definition the checker can prove "
            f"on-ladder",
        )


def _param_names(fn: ast.AST) -> List[str]:
    return [a.arg for a in list(fn.args.posonlyargs) + list(fn.args.args)]


def _arg_for_param(
    call: ast.Call, fi: FuncInfo, pos: int, name: str
) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    # positional: methods are invoked without the explicit self
    eff = pos - 1 if fi.cls_key is not None else pos
    if 0 <= eff < len(call.args):
        return call.args[eff]
    return None


def _default_for_param(fn: ast.AST, pos: int) -> Optional[ast.AST]:
    args = list(fn.args.posonlyargs) + list(fn.args.args)
    defaults = list(fn.args.defaults)
    offset = len(args) - len(defaults)
    if pos >= offset:
        return defaults[pos - offset]
    return None


# ------------------------------ the checker ----------------------------


def check_jit_signatures(
    files: Dict[str, SourceFile], root: Optional[str] = None
) -> List[Finding]:
    graph = CallGraph(files)
    safety = _Safety(graph)
    findings: List[Finding] = []

    for rel, sf in files.items():
        if sf.tree is None or not sf.hot:
            continue
        defs = {d.name: d for d in collect_jit_defs(sf)}
        if not defs:
            continue
        for key, fi in graph.functions.items():
            if fi.rel != rel:
                continue
            for call in ast.walk(fi.node):
                if not isinstance(call, ast.Call):
                    continue
                jd = _match_handle(call, defs)
                if jd is None:
                    continue
                for p in jd.static_positions:
                    expr = _static_arg_expr(call, jd, p)
                    if expr is None:
                        continue
                    ok, why = safety.safe(expr, fi)
                    if not ok:
                        pname = (
                            jd.params[p]
                            if p < len(jd.params)
                            else f"arg{p}"
                        )
                        findings.append(
                            apply_suppression(
                                sf,
                                Finding(
                                    RULE_OFF_LADDER,
                                    sf.rel,
                                    expr.lineno,
                                    f"static arg `{pname}` of "
                                    f"{jd.name} can mint an off-ladder "
                                    f"signature: {why} — every value "
                                    f"must come from "
                                    f"round_up_to_bucket/engine config "
                                    f"(see signature_budget.json)",
                                ),
                            )
                        )

    if root is not None:
        findings.extend(_budget_findings(root))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


def _match_handle(call: ast.Call, defs: Dict[str, JitDef]) -> Optional[JitDef]:
    f = call.func
    if (
        isinstance(f, ast.Attribute)
        and isinstance(f.value, ast.Name)
        and f.value.id == "self"
    ):
        return defs.get(f.attr)
    if isinstance(f, ast.Name):
        return defs.get(f.id)
    return None


def _static_arg_expr(
    call: ast.Call, jd: JitDef, pos: int
) -> Optional[ast.AST]:
    if pos < len(call.args):
        return call.args[pos]
    if pos < len(jd.params):
        pname = jd.params[pos]
        for kw in call.keywords:
            if kw.arg == pname:
                return kw.value
    return None


def _budget_findings(root: str) -> List[Finding]:
    path = os.path.join(root, BUDGET_PATH)
    if not os.path.exists(path):
        return [
            Finding(
                RULE_STALE,
                BUDGET_PATH,
                1,
                "signature budget file missing — generate it with "
                "`python scripts/lint.py --write-budget`",
            )
        ]
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [
            Finding(RULE_STALE, BUDGET_PATH, 1, f"unreadable budget: {e}")
        ]
    return [
        Finding(RULE_STALE, BUDGET_PATH, 1, p) for p in budget_drift(doc)
    ]
