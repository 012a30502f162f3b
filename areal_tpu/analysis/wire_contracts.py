"""areal-lint v3 (ISSUE 18): cross-process wire-contract checking.

The fleet is 4+ processes glued together by string-keyed JSON bodies,
lifecycle event names, metric names, and GenServerConfig→argparse→engine
plumbing — seams no type checker sees.  Three checkers close them, driven
by the checked-in contract registry `areal_tpu/analysis/wire_contracts.json`:

- C8  `payload-contract` / `payload-silent-default`
      Per HTTP endpoint, producer key-sets (dict literals and
      `payload["k"] = ...` writes flowing into utils/http helpers,
      `session.post(..., json=...)`, `web.json_response(...)`) are checked
      against consumer key-sets (`body["k"]` / `body.get("k", d)` reads in
      handlers and clients) through the registry.  A hard read of a key no
      producer writes is an error; a `.get` with a silent literal default
      on a key every producer writes is a warning (the silent-0 class);
      response contracts are checked in the reverse direction.
- C9  `metric-contract` / `event-contract`
      Every Counter/Gauge/Histogram name constructed anywhere must be
      pinned in tests/data/metrics_schema.json and vice versa (no orphans
      either way); every event name passed to `telemetry.emit` must be one
      obs/trace.py's parser consumes and vice versa.
- C10 `config-plumbing`
      GenServerConfig field ↔ build_cmd flag ↔ gen/server.py argparse flag
      ↔ GenEngine kwarg must line up end-to-end (the /generate-body leg of
      each chain is covered by the C8 `generate` contract).

Registry self-consistency problems (unreadable JSON, keys nothing produces
or consumes, declared-but-never-emitted events) surface as
`wire-registry-stale` anchored at the registry file itself — those are
fixed by editing the registry, not suppressed in code.
"""

import ast
import json
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

from areal_tpu.analysis.core import Finding, SourceFile, apply_suppression

CONTRACTS_PATH = os.path.join("areal_tpu", "analysis", "wire_contracts.json")
SCHEMA_PATH = os.path.join("tests", "data", "metrics_schema.json")
FAKE_SERVER_REL = os.path.join("tests", "fake_server.py")
TRACE_REL = os.path.join("areal_tpu", "obs", "trace.py")

RULE_PAYLOAD = "payload-contract"
RULE_SILENT = "payload-silent-default"
RULE_METRIC = "metric-contract"
RULE_EVENT = "event-contract"
RULE_CONFIG = "config-plumbing"
RULE_REGISTRY = "wire-registry-stale"

WIRE_RULES = (
    RULE_PAYLOAD, RULE_SILENT, RULE_METRIC, RULE_EVENT, RULE_CONFIG,
    RULE_REGISTRY,
)

# JSON-returning post helpers available everywhere (utils/http.py).
_GLOBAL_HELPERS = {
    "arequest_with_retry": {"endpoint_arg": 1, "payload_arg": 2,
                            "returns": "json"},
}


# --------------------------------------------------------------------------
# small AST helpers
# --------------------------------------------------------------------------

def _dotted(node: ast.AST) -> Optional[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _call_attr(call: ast.Call) -> str:
    """Trailing name of the called function — works even when the receiver
    is itself a call (self._get_session().post(...))."""
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def _const_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _unwrap(node: ast.AST) -> ast.AST:
    """Peel await / ternary / `or {}` / dict(...) wrappers so payload and
    view sources are recognized through the common idioms."""
    while True:
        if isinstance(node, ast.Await):
            node = node.value
        elif isinstance(node, ast.IfExp):
            node = node.body
        elif isinstance(node, ast.BoolOp) and isinstance(node.op, ast.Or):
            node = node.values[0]
        elif (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "dict"
            and len(node.args) == 1
            and not node.keywords
        ):
            node = node.args[0]
        else:
            return node


def _path_from_url(node: ast.AST) -> Optional[str]:
    """Endpoint path from a URL expression: a constant, or an f-string
    whose trailing constant part carries the path (f"http://{addr}/x").
    Fully dynamic paths (f"{addr}{path}") resolve to None and the site is
    skipped."""
    s = _const_str(node)
    if s is not None:
        i = s.find("://")
        if i >= 0:
            j = s.find("/", i + 3)
            return s[j:].split("?")[0] if j >= 0 else None
        return s.split("?")[0] if s.startswith("/") else None
    if isinstance(node, ast.JoinedStr) and node.values:
        last = node.values[-1]
        ls = _const_str(last)
        if ls is not None and "/" in ls:
            return ls[ls.find("/"):].split("?")[0]
    return None


def _iter_functions(tree: ast.AST) -> List[Tuple[str, ast.AST]]:
    """(qualname, node) for every function/method, including nested ones.
    Each is scanned as its own unit."""
    out: List[Tuple[str, ast.AST]] = []

    def rec(node: ast.AST, prefix: str):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{prefix}{child.name}"
                out.append((q, child))
                rec(child, q + ".")
            elif isinstance(child, ast.ClassDef):
                rec(child, f"{prefix}{child.name}.")
            else:
                rec(child, prefix)

    rec(tree, "")
    return out


def _dict_keys(node: ast.Dict, prefix: str = "") -> Tuple[Dict[str, int], bool]:
    """Constant keys (dotted for one nesting level) -> lineno; the bool is
    True when the dict is `open` (has ** spreads or computed keys)."""
    keys: Dict[str, int] = {}
    open_ = False
    for k, v in zip(node.keys, node.values):
        ks = _const_str(k) if k is not None else None
        if ks is None:
            open_ = True
            continue
        keys[prefix + ks] = getattr(k, "lineno", node.lineno)
        if isinstance(v, ast.Dict) and not prefix:
            sub, sub_open = _dict_keys(v, prefix=ks + ".")
            keys.update(sub)
            open_ = open_ or sub_open
    return keys, open_


# --------------------------------------------------------------------------
# contract registry
# --------------------------------------------------------------------------

class _Key:
    __slots__ = ("required", "tolerant_ok", "external")

    def __init__(self, spec: Any):
        spec = spec if isinstance(spec, dict) else {}
        self.required = bool(spec.get("required", False))
        self.tolerant_ok = bool(spec.get("tolerant_reads_ok", False))
        self.external = bool(spec.get("external_producer", False))


class _Contract:
    def __init__(self, cid: str, spec: Dict[str, Any]):
        self.cid = cid
        self.path = spec["path"]
        self.app = spec.get("app", "gen")
        self.request = {k: _Key(v) for k, v in spec.get("request", {}).items()}
        self.response = {k: _Key(v) for k, v in spec.get("response", {}).items()}
        # "<cid>#request"/"<cid>#response": this direction's body is the
        # verbatim body of another contract direction (KV handoff relay)
        self.forwarded = {
            "request": spec.get("request_forwarded_from"),
            "response": spec.get("response_forwarded_from"),
        }

    def keys(self, direction: str) -> Dict[str, _Key]:
        return self.request if direction == "request" else self.response


class WireContracts:
    def __init__(self, doc: Dict[str, Any]):
        self.doc = doc
        self.contracts: Dict[str, _Contract] = {
            cid: _Contract(cid, spec)
            for cid, spec in doc.get("endpoints", {}).items()
        }
        self.by_path: Dict[str, List[_Contract]] = {}
        for c in self.contracts.values():
            self.by_path.setdefault(c.path, []).append(c)
        self.apps: Dict[str, str] = doc.get("apps", {})
        self.client_targets: Dict[str, str] = doc.get("client_targets", {})
        self.helpers: Dict[str, Dict[str, Any]] = dict(_GLOBAL_HELPERS)
        for h in doc.get("post_helpers", []):
            self.helpers[h["method"]] = h
        self.bindings: Dict[Tuple[str, str], List[Dict[str, Any]]] = {}
        for b in doc.get("bindings", []):
            fn = b["function"]
            file, _, qual = fn.partition("::")
            self.bindings.setdefault(
                (os.path.normpath(file), qual), []
            ).append(b)
        ev = doc.get("events", {})
        self.events: Dict[str, Dict[str, Any]] = {
            e["name"]: e for e in ev.get("names", [])
        }
        met = doc.get("metrics", {})
        self.dynamic_metric_files: Dict[str, str] = {
            os.path.normpath(d["file"]): d.get("reason", "")
            for d in met.get("dynamic_sites", [])
        }
        self.dynamic_patterns: List[re.Pattern] = [
            re.compile(p["pattern"]) for p in met.get("dynamic_patterns", [])
        ]
        self.unpinned_metrics: Dict[str, str] = {
            u["name"]: u.get("reason", "")
            for u in met.get("unpinned", [])
        }
        self.config_chains: Dict[str, Any] = doc.get("config_chains", {})
        self.train_config_chains: Dict[str, Any] = doc.get(
            "train_config_chains", {}
        )

    @classmethod
    def load(cls, root: str) -> "WireContracts":
        with open(os.path.join(root, CONTRACTS_PATH), encoding="utf-8") as f:
            return cls(json.load(f))

    def resolve(self, path: str, app_hint: str) -> Optional[_Contract]:
        cands = self.by_path.get(path)
        if not cands:
            return None
        if len(cands) == 1:
            return cands[0]
        for c in cands:
            if c.app == app_hint:
                return c
        return cands[0]


# --------------------------------------------------------------------------
# C8: payload contracts
# --------------------------------------------------------------------------

class _Payload:
    """A producer-side JSON body being built in a function."""

    def __init__(self, keys: Dict[str, int], open_: bool):
        self.keys = dict(keys)
        self.open = open_


class _View:
    """A consumer-side body (request body in a handler, parsed response in
    a client); reads on it are contract reads."""

    def __init__(self, contract: _Contract, direction: str, prefix: str = ""):
        self.contract = contract
        self.direction = direction
        self.prefix = prefix


class _Site:
    def __init__(self, contract, direction, sf, line, payload=None):
        self.contract = contract
        self.direction = direction
        self.sf = sf
        self.line = line
        self.payload = payload  # _Payload (closed or open) or None


class _Read:
    def __init__(self, contract, direction, key, kind, sf, line):
        self.contract = contract
        self.direction = direction
        self.key = key
        self.kind = kind  # "hard" | "silent" | "tolerant" | "membership"
        self.sf = sf
        self.line = line


class _C8Scanner:
    def __init__(self, wc: WireContracts):
        self.wc = wc
        self.producers: List[_Site] = []
        self.reads: List[_Read] = []
        self.augment_writes: List[_Read] = []  # key writes on open bodies
        self.findings: List[Finding] = []

    # -- handler registration maps ------------------------------------

    def _handler_map(self, sf: SourceFile) -> Dict[str, str]:
        """method name -> endpoint path, from app.router.add_post/add_get
        calls in this file."""
        out: Dict[str, str] = {}
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = _dotted(node.func) or ""
            if not fn.endswith((".add_post", ".add_get")):
                continue
            if len(node.args) != 2:
                continue
            path = _const_str(node.args[0])
            h = node.args[1]
            if path and isinstance(h, ast.Attribute):
                out[h.attr] = path
            elif path and isinstance(h, ast.Name):
                out[h.id] = path
        return out

    # -- per-file scan -------------------------------------------------

    def scan_file(self, sf: SourceFile):
        if sf.tree is None:
            return
        handlers = self._handler_map(sf)
        # handler contracts only resolve for files whose serving app is
        # declared in the registry; an aiohttp app in an undeclared file
        # would otherwise steal contracts for colliding paths (/health)
        app_hint = self.wc.apps.get(os.path.normpath(sf.rel))
        if app_hint is None and handlers:
            for path in sorted(set(handlers.values())):
                if path in self.wc.by_path:
                    self.findings.append(Finding(
                        RULE_PAYLOAD, sf.rel, 1,
                        f"file serves '{path}' but is not mapped to an app "
                        f"in wire_contracts.json 'apps' — its handlers are "
                        f"unchecked",
                    ))
        for qual, fn in _iter_functions(sf.tree):
            self._scan_function(sf, qual, fn, handlers, app_hint)

    def _client_contract(self, sf, qual, path) -> Optional[_Contract]:
        key = f"{os.path.normpath(sf.rel)}::{qual}"
        hint = self.wc.client_targets.get(key, "gen")
        return self.wc.resolve(path, hint)

    def _scan_function(self, sf, qual, fn, handlers, app_hint):
        env: Dict[str, Any] = {}  # name -> _Payload | _View
        resp_env: Dict[str, _Contract] = {}
        method_name = fn.name
        handler_contract: Optional[_Contract] = None
        if method_name in handlers and app_hint is not None:
            handler_contract = self.wc.resolve(handlers[method_name], app_hint)
        producer_return: Optional[Tuple[_Contract, str]] = None
        for b in self.wc.bindings.get((os.path.normpath(sf.rel), qual), []):
            c = self.wc.contracts.get(b["contract"])
            if c is None:
                continue
            if b["role"] == "consumer":
                for var in b.get("vars", []):
                    env[var] = _View(c, b["direction"])
            elif b["role"] == "producer" and b.get("returns"):
                producer_return = (c, b["direction"])

        def record_payload(contract, direction, node, line):
            node = _unwrap(node)
            if isinstance(node, ast.Dict):
                keys, open_ = _dict_keys(node)
                self.producers.append(
                    _Site(contract, direction, sf, line,
                          _Payload(keys, open_))
                )
                return
            if isinstance(node, ast.Name):
                info = env.get(node.id)
                if isinstance(info, _Payload):
                    self.producers.append(
                        _Site(contract, direction, sf, line, info)
                    )
                    return
                if isinstance(info, _View):
                    return  # passthrough forward: augment writes cover it
            # unresolvable (call result, attribute, ...) — not checkable

        def endpoint_of_call(call) -> Tuple[Optional[_Contract], Optional[ast.AST]]:
            """(contract, payload_node) when `call` posts JSON to a
            statically-known endpoint; (None, None) otherwise."""
            attr = _call_attr(call)
            # session.post(url, json=...) / requests.post(url, json=...)
            if attr in ("post", "get") and call.args:
                path = _path_from_url(call.args[0])
                if path is None:
                    return None, None
                c = self._client_contract(sf, qual, path)
                if c is None:
                    self.findings.append(Finding(
                        RULE_PAYLOAD, sf.rel, call.lineno,
                        f"HTTP {attr.upper()} to '{path}' but no contract "
                        f"for that endpoint in wire_contracts.json",
                    ))
                    return None, None
                payload = None
                for kw in call.keywords:
                    if kw.arg == "json":
                        payload = kw.value
                return c, payload
            if attr == "urlopen" and call.args:
                path = _path_from_url(call.args[0])
                if path is None:
                    return None, None
                return self._client_contract(sf, qual, path), None
            helper = self.wc.helpers.get(attr)
            if helper is not None:
                ep = None
                payload = None
                for kw in call.keywords:
                    if kw.arg == "endpoint":
                        ep = _const_str(kw.value)
                    elif kw.arg in ("payload", "json"):
                        payload = kw.value
                ei, pi = helper["endpoint_arg"], helper.get("payload_arg")
                if ep is None and len(call.args) > ei:
                    ep = _const_str(call.args[ei])
                if payload is None and pi is not None and len(call.args) > pi:
                    payload = call.args[pi]
                if ep is None:
                    return None, None
                c = self._client_contract(sf, qual, ep)
                if c is None:
                    self.findings.append(Finding(
                        RULE_PAYLOAD, sf.rel, call.lineno,
                        f"{attr}() targets '{ep}' but no contract for that "
                        f"endpoint in wire_contracts.json",
                    ))
                return c, payload
            return None, None

        def handle_call(call: ast.Call):
            attr = _call_attr(call)
            # producer: HttpRequest(endpoint=..., payload=...)
            if attr == "HttpRequest":
                ep = payload = None
                for kw in call.keywords:
                    if kw.arg == "endpoint":
                        ep = _const_str(kw.value)
                    elif kw.arg == "payload":
                        payload = kw.value
                if ep and payload is not None:
                    c = self._client_contract(sf, qual, ep)
                    if c is None:
                        self.findings.append(Finding(
                            RULE_PAYLOAD, sf.rel, call.lineno,
                            f"HttpRequest targets '{ep}' but no contract "
                            f"for that endpoint in wire_contracts.json",
                        ))
                    else:
                        record_payload(c, "request", payload, call.lineno)
                return
            # producer: web.json_response({...}) in a handler/bound fn
            if attr == "json_response":
                ctx_contract = handler_contract or (
                    producer_return[0] if producer_return else None
                )
                if ctx_contract is None or not call.args:
                    return
                for kw in call.keywords:
                    if kw.arg == "status":
                        sv = kw.value
                        if (isinstance(sv, ast.Constant)
                                and isinstance(sv.value, int)
                                and sv.value >= 400):
                            return  # error path: not the success contract
                record_payload(ctx_contract, "response", call.args[0],
                               call.lineno)
                return
            # producer: posts through helpers / session.post
            c, payload = endpoint_of_call(call)
            if c is not None and payload is not None:
                record_payload(c, "request", payload, call.lineno)
            # consumer: X.get("k"[, default])
            if attr == "get" and call.args:
                key = _const_str(call.args[0])
                recv = call.func.value if isinstance(call.func, ast.Attribute) else None
                view = self._view_of(recv, env, resp_env)
                if key is not None and view is not None:
                    if len(call.args) < 2:
                        kind = "silent"
                    elif isinstance(call.args[1], ast.Constant):
                        kind = "silent"
                    elif (isinstance(call.args[1],
                                     (ast.List, ast.Tuple, ast.Dict, ast.Set))
                            and not getattr(call.args[1], "elts",
                                            getattr(call.args[1], "keys", ()))):
                        # .get("k", []) / .get("k", {}) — an empty container
                        # literal degrades exactly like a constant default
                        kind = "silent"
                    else:
                        kind = "tolerant"
                    self.reads.append(_Read(
                        view.contract, view.direction, view.prefix + key,
                        kind, sf, call.lineno,
                    ))

        def handle_subscript(sub: ast.Subscript):
            key = _const_str(sub.slice)
            if key is None:
                return
            if not isinstance(sub.value, ast.Name):
                # r.json()["k"] / (await resp.json())["k"] direct reads
                view = self._view_of(sub.value, env, resp_env)
                if view is not None and isinstance(sub.ctx, ast.Load):
                    self.reads.append(_Read(
                        view.contract, view.direction, view.prefix + key,
                        "hard", sf, sub.lineno,
                    ))
                return
            info = env.get(sub.value.id)
            if isinstance(info, _View):
                if isinstance(sub.ctx, ast.Load):
                    self.reads.append(_Read(
                        info.contract, info.direction, info.prefix + key,
                        "hard", sf, sub.lineno,
                    ))
                elif isinstance(sub.ctx, ast.Store):
                    self.augment_writes.append(_Read(
                        info.contract, info.direction, info.prefix + key,
                        "write", sf, sub.lineno,
                    ))
            elif isinstance(info, _Payload) and isinstance(sub.ctx, ast.Store):
                info.keys.setdefault(key, sub.lineno)

        def handle_compare(cmp: ast.Compare):
            if (len(cmp.ops) == 1 and isinstance(cmp.ops[0], (ast.In, ast.NotIn))
                    and isinstance(cmp.comparators[0], ast.Name)):
                info = env.get(cmp.comparators[0].id)
                key = _const_str(cmp.left)
                if isinstance(info, _View) and key is not None:
                    self.reads.append(_Read(
                        info.contract, info.direction, info.prefix + key,
                        "membership", sf, cmp.lineno,
                    ))

        def _register_with_item(item):
            ce = item.context_expr
            if not isinstance(ce, ast.Call):
                return
            c, _payload = endpoint_of_call(ce)
            if c is not None and item.optional_vars is not None:
                if isinstance(item.optional_vars, ast.Name):
                    resp_env[item.optional_vars.id] = c

        def handle_assign(target, value):
            if not isinstance(target, ast.Name):
                # tuple unpack of status_json helpers:
                #   status, body = await self._leg_post(addr, "/x", payload, n)
                if (isinstance(target, ast.Tuple)
                        and len(target.elts) == 2
                        and isinstance(target.elts[1], ast.Name)):
                    vv = _unwrap(value)
                    if isinstance(vv, ast.Call):
                        helper = self.wc.helpers.get(_call_attr(vv))
                        if helper and helper.get("returns") == "status_json":
                            c, _p = endpoint_of_call(vv)
                            if c is not None:
                                env[target.elts[1].id] = _View(c, "response")
                return
            name = target.id
            vv = _unwrap(value)
            # view: body = await request.json() (handler)
            if isinstance(vv, ast.Call):
                attr = _call_attr(vv)
                if attr == "json" and isinstance(vv.func, ast.Attribute):
                    recv = vv.func.value
                    if (isinstance(recv, ast.Name)
                            and recv.id == "request"
                            and handler_contract is not None):
                        env[name] = _View(handler_contract, "request")
                        return
                    if isinstance(recv, ast.Name) and recv.id in resp_env:
                        env[name] = _View(resp_env[recv.id], "response")
                        return
                if attr == "loads":
                    # m = json.loads(r.read()) under `with urlopen(...) as r`
                    inner = vv.args[0] if vv.args else None
                    while isinstance(inner, ast.Call):
                        inner = (inner.func.value
                                 if isinstance(inner.func, ast.Attribute)
                                 else None)
                    if isinstance(inner, ast.Name) and inner.id in resp_env:
                        env[name] = _View(resp_env[inner.id], "response")
                        return
                # view: raw = await arequest_with_retry(endpoint="/x", ...)
                helper = self.wc.helpers.get(attr)
                if helper and helper.get("returns") == "json":
                    c, _p = endpoint_of_call(vv)
                    if c is not None:
                        env[name] = _View(c, "response")
                        return
                # response object: r = session.post(url, ...) / a helper
                # returning a requests.Response — r.json()["k"] reads later
                if (attr in ("post", "get")
                        or (helper and helper.get("returns") == "respobj")):
                    c, _p = endpoint_of_call(vv)
                    if c is not None:
                        resp_env[name] = c
                        return
                # sub-view: sp = body.get("sampling_params", {})
                if attr == "get" and isinstance(vv.func, ast.Attribute):
                    view = self._view_of(vv.func.value, env, resp_env)
                    key = _const_str(vv.args[0]) if vv.args else None
                    if view is not None and key is not None:
                        pref = view.prefix + key + "."
                        if any(k.startswith(pref)
                               for k in view.contract.keys(view.direction)):
                            env[name] = _View(view.contract, view.direction,
                                              pref)
                            return
            if isinstance(vv, ast.Subscript) and isinstance(vv.value, ast.Name):
                view = env.get(vv.value.id)
                key = _const_str(vv.slice)
                if isinstance(view, _View) and key is not None:
                    pref = view.prefix + key + "."
                    if any(k.startswith(pref)
                           for k in view.contract.keys(view.direction)):
                        env[name] = _View(view.contract, view.direction, pref)
                        return
            if isinstance(vv, ast.Dict):
                keys, open_ = _dict_keys(vv)
                env[name] = _Payload(keys, open_)
                return
            if isinstance(vv, ast.Name) and vv.id in env:
                info = env[vv.id]
                if isinstance(info, _Payload):
                    env[name] = _Payload(info.keys, info.open)
                else:
                    env[name] = _View(info.contract, info.direction,
                                      info.prefix)
                return

        def walk_node(node: ast.AST):
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                walk_node(node.value)
                handle_assign(node.targets[0], node.value)
                if isinstance(node.targets[0], ast.Subscript):
                    handle_subscript(node.targets[0])
                return
            if (isinstance(node, ast.AnnAssign) and node.value is not None):
                walk_node(node.value)
                handle_assign(node.target, node.value)
                return
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    walk_node(item.context_expr)
                    _register_with_item(item)
                for stmt in node.body:
                    walk_node(stmt)
                return
            if isinstance(node, ast.Call):
                handle_call(node)
            elif isinstance(node, ast.Subscript):
                handle_subscript(node)
            elif isinstance(node, ast.Compare):
                handle_compare(node)
            elif isinstance(node, ast.Return) and producer_return is not None:
                if node.value is not None:
                    c, d = producer_return
                    record_payload(c, d, node.value, node.lineno)
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue  # nested functions scan as their own unit
                walk_node(child)

        for stmt in fn.body:
            walk_node(stmt)

    @staticmethod
    def _view_of(node, env, resp_env) -> Optional[_View]:
        node = _unwrap(node) if node is not None else None
        if isinstance(node, ast.Name):
            info = env.get(node.id)
            if isinstance(info, _View):
                return info
            return None
        # (await resp.json()).get(...)
        if isinstance(node, ast.Call):
            if _call_attr(node) == "json" and isinstance(
                    node.func, ast.Attribute):
                recv = node.func.value
                if isinstance(recv, ast.Name) and recv.id in resp_env:
                    return _View(resp_env[recv.id], "response")
        return None


def check_payload_contracts(
    files: Dict[str, SourceFile],
    root: Optional[str] = None,
    contracts: Optional[WireContracts] = None,
    fake_server: Optional[SourceFile] = None,
) -> List[Finding]:
    wc = contracts or WireContracts.load(root)
    scanner = _C8Scanner(wc)
    scan_files = dict(files)
    # tests/ is excluded from the default scan, but the fake server IS a
    # wire producer/consumer the real clients run against — load it
    # explicitly so its contract drift is caught (the PR-17 class).
    if fake_server is not None:
        scan_files[FAKE_SERVER_REL] = fake_server
    elif root is not None:
        fp = os.path.join(root, FAKE_SERVER_REL)
        if os.path.exists(fp):
            scan_files[FAKE_SERVER_REL] = SourceFile.from_path(
                fp, rel=FAKE_SERVER_REL
            )
    for sf in scan_files.values():
        scanner.scan_file(sf)

    findings = list(scanner.findings)
    produced: Dict[Tuple[str, str, str], int] = {}
    hard_reads: Dict[Tuple[str, str, str], _Read] = {}
    soft_reads: Dict[Tuple[str, str, str], _Read] = {}

    for site in scanner.producers:
        c, d, p = site.contract, site.direction, site.payload
        spec = c.keys(d)
        for key, line in p.keys.items():
            produced[(c.cid, d, key)] = produced.get((c.cid, d, key), 0) + 1
            if key not in spec:
                findings.append(apply_suppression(site.sf, Finding(
                    RULE_PAYLOAD, site.sf.rel, line,
                    f"producer writes key '{key}' not in the {c.path} "
                    f"{d} contract (renamed or stale? update "
                    f"wire_contracts.json or the producer)",
                )))
        if not p.open:
            for key, kspec in spec.items():
                if kspec.required and key not in p.keys:
                    findings.append(apply_suppression(site.sf, Finding(
                        RULE_PAYLOAD, site.sf.rel, site.line,
                        f"producer for {c.path} {d} omits required key "
                        f"'{key}' (every producer must write it)",
                    )))

    for w in scanner.augment_writes:
        produced[(w.contract.cid, w.direction, w.key)] = (
            produced.get((w.contract.cid, w.direction, w.key), 0) + 1
        )
        if w.key not in w.contract.keys(w.direction):
            findings.append(apply_suppression(w.sf, Finding(
                RULE_PAYLOAD, w.sf.rel, w.line,
                f"writes key '{w.key}' into a forwarded {w.contract.path} "
                f"{w.direction} body but the contract has no such key",
            )))

    for r in scanner.reads:
        spec = r.contract.keys(r.direction)
        if r.key not in spec:
            findings.append(apply_suppression(r.sf, Finding(
                RULE_PAYLOAD, r.sf.rel, r.line,
                f"reads key '{r.key}' from the {r.contract.path} "
                f"{r.direction} body but no producer writes it (not in "
                f"the contract)",
            )))
            continue
        kspec = spec[r.key]
        if r.kind == "hard":
            hard_reads.setdefault((r.contract.cid, r.direction, r.key), r)
        elif r.kind in ("silent", "tolerant"):
            soft_reads.setdefault((r.contract.cid, r.direction, r.key), r)
        if (r.kind == "silent" and kspec.required and not kspec.tolerant_ok):
            findings.append(apply_suppression(r.sf, Finding(
                RULE_SILENT, r.sf.rel, r.line,
                f".get('{r.key}') with a silent default, but every "
                f"producer of {r.contract.path} {r.direction} writes it — "
                f"a rename would silently degrade instead of failing "
                f"(mark tolerant_reads_ok in wire_contracts.json if "
                f"intentional)",
            )))

    # registry health: every contract key must have a producer somewhere
    for c in wc.contracts.values():
        for d in ("request", "response"):
            src_cid, src_dir = c.cid, d
            fwd = c.forwarded.get(d)
            if fwd:
                src_cid, _, src_dir = fwd.partition("#")
            for key, kspec in c.keys(d).items():
                n = produced.get((src_cid, src_dir, key), 0)
                if n or kspec.external:
                    continue
                hr = hard_reads.get((c.cid, d, key))
                sr = soft_reads.get((c.cid, d, key))
                if hr is not None:
                    findings.append(apply_suppression(hr.sf, Finding(
                        RULE_PAYLOAD, hr.sf.rel, hr.line,
                        f"required read of '{key}' from {c.path} {d} but "
                        f"NO producer writes that key anywhere",
                    )))
                elif sr is not None:
                    findings.append(apply_suppression(sr.sf, Finding(
                        RULE_SILENT, sr.sf.rel, sr.line,
                        f"reads '{key}' from {c.path} {d} with a default "
                        f"but no producer writes it — always the default",
                    )))
                else:
                    findings.append(Finding(
                        RULE_REGISTRY, CONTRACTS_PATH, 1,
                        f"contract key '{key}' on {c.path} {d} is neither "
                        f"produced nor consumed by any scanned code — "
                        f"stale registry entry",
                    ))
    return findings


# --------------------------------------------------------------------------
# C9: telemetry-name contracts (metrics + lifecycle events)
# --------------------------------------------------------------------------

_REGISTRY_PREFIX = {"GEN": "areal_gen_", "ROUTER": "areal_router_",
                    "TRAIN": "areal_train_"}
_METRIC_CTORS = ("counter", "gauge", "histogram")


def _metric_candidates(call: ast.Call, aliases: Dict[str, str]) -> Optional[List[str]]:
    """Fully-qualified candidate names for a metric construction, or None
    when the receiver is statically unresolvable (parametric registry)."""
    name = _const_str(call.args[0]) if call.args else None
    if name is None:
        return None
    if name.startswith("areal_"):
        return [name]
    recv = call.func.value if isinstance(call.func, ast.Attribute) else None
    d = _dotted(recv) if recv is not None else None
    if d is not None:
        tail = d.rsplit(".", 1)[-1]
        tail = aliases.get(tail, tail)
        if tail in _REGISTRY_PREFIX:
            return [_REGISTRY_PREFIX[tail] + name]
    return [p + name for p in _REGISTRY_PREFIX.values()]


def check_telemetry_contracts(
    files: Dict[str, SourceFile],
    root: Optional[str] = None,
    contracts: Optional[WireContracts] = None,
    schema: Optional[Dict[str, List[str]]] = None,
    trace_sf: Optional[SourceFile] = None,
) -> List[Finding]:
    wc = contracts or WireContracts.load(root)
    findings: List[Finding] = []
    if schema is None:
        try:
            with open(os.path.join(root, SCHEMA_PATH), encoding="utf-8") as f:
                schema = json.load(f)
        except FileNotFoundError:
            # Scratch --root trees (CLI drives, fixtures) carry no pinned
            # schema; degrade to "nothing pinned" so constructed metrics
            # still surface as findings instead of crashing the suite.
            schema = {}
    pinned = {name for names in schema.values() for name in names}

    # ---- metric constructions ---------------------------------------
    covered: set = set()
    for sf in files.values():
        if sf.tree is None:
            continue
        # registry aliases (`reg = telemetry.TRAIN`) are tracked per scope:
        # a function's alias must not leak into a sibling that takes the
        # registry as a parameter (register_staleness-style helpers)
        ctor_calls: List[Tuple[ast.Call, Dict[str, str]]] = []

        def _collect(node: ast.AST, aliases: Dict[str, str]):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    _collect(child, dict(aliases))
                    continue
                if (isinstance(child, ast.Assign) and len(child.targets) == 1
                        and isinstance(child.targets[0], ast.Name)):
                    d = _dotted(child.value)
                    if (d is not None
                            and d.rsplit(".", 1)[-1] in _REGISTRY_PREFIX):
                        aliases[child.targets[0].id] = d.rsplit(".", 1)[-1]
                if (isinstance(child, ast.Call)
                        and isinstance(child.func, ast.Attribute)
                        and child.func.attr in _METRIC_CTORS):
                    ctor_calls.append((child, dict(aliases)))
                _collect(child, aliases)

        _collect(sf.tree, {})
        for node, aliases in ctor_calls:
            if not node.args:
                continue
            raw_name = _const_str(node.args[0])
            if raw_name is None:
                if os.path.normpath(sf.rel) not in wc.dynamic_metric_files:
                    findings.append(apply_suppression(sf, Finding(
                        RULE_METRIC, sf.rel, node.lineno,
                        "dynamically-named metric construction in a file "
                        "not allowlisted under metrics.dynamic_sites in "
                        "wire_contracts.json — pin the name or register "
                        "the site with a reason",
                    )))
                continue
            cands = _metric_candidates(node, aliases)
            covered.update(cands)
            if raw_name in wc.unpinned_metrics:
                continue
            if not any(c in pinned for c in cands):
                findings.append(apply_suppression(sf, Finding(
                    RULE_METRIC, sf.rel, node.lineno,
                    f"metric '{raw_name}' (candidates: {sorted(cands)}) is "
                    f"constructed here but not pinned in "
                    f"tests/data/metrics_schema.json — scrape tests will "
                    f"never notice it disappearing",
                )))

    for name in sorted(pinned):
        if name in covered:
            continue
        if any(p.match(name) for p in wc.dynamic_patterns):
            continue
        findings.append(Finding(
            RULE_METRIC, SCHEMA_PATH, 1,
            f"metrics_schema.json pins '{name}' but no code constructs it "
            f"(orphaned schema entry)",
        ))

    # ---- lifecycle events -------------------------------------------
    if trace_sf is None:
        trace_sf = files.get(os.path.normpath(TRACE_REL))
    emitted: Dict[str, Tuple[SourceFile, int]] = {}
    for sf in files.values():
        if sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "emit"):
                continue
            recv = _dotted(node.func.value) or ""
            tail = recv.rsplit(".", 1)[-1]
            if tail not in ("telemetry", "EVENTS"):
                continue
            name = _const_str(node.args[0]) if node.args else None
            if name is not None:
                emitted.setdefault(name, (sf, node.lineno))

    consumed: Dict[str, int] = {}
    if trace_sf is not None and trace_sf.tree is not None:
        for node in ast.walk(trace_sf.tree):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and re.match(r"^_[A-Z_]*EVENTS$", node.targets[0].id)
                    and isinstance(node.value, ast.Tuple)):
                for elt in node.value.elts:
                    s = _const_str(elt)
                    if s is not None:
                        consumed.setdefault(s, node.lineno)
            if isinstance(node, ast.Compare):
                left = node.left
                is_event_expr = (
                    (isinstance(left, ast.Name) and left.id == "name")
                    or (isinstance(left, ast.Subscript)
                        and _const_str(left.slice) == "event")
                    or (isinstance(left, ast.Call)
                        and isinstance(left.func, ast.Attribute)
                        and left.func.attr == "get" and left.args
                        and _const_str(left.args[0]) == "event")
                )
                if not is_event_expr:
                    continue
                for comp in node.comparators:
                    s = _const_str(comp)
                    if s is not None:
                        consumed.setdefault(s, node.lineno)
                    elif isinstance(comp, ast.Tuple):
                        for elt in comp.elts:
                            es = _const_str(elt)
                            if es is not None:
                                consumed.setdefault(es, node.lineno)

    declared = wc.events
    for name, (sf, line) in sorted(emitted.items()):
        if name not in declared:
            findings.append(apply_suppression(sf, Finding(
                RULE_EVENT, sf.rel, line,
                f"telemetry.emit('{name}') but the event is not declared "
                f"in wire_contracts.json — trace reconstruction will drop "
                f"it silently",
            )))
    if trace_sf is not None:
        for name, line in sorted(consumed.items()):
            if name not in declared:
                findings.append(apply_suppression(trace_sf, Finding(
                    RULE_EVENT, trace_sf.rel, line,
                    f"obs/trace.py parses event '{name}' that is not "
                    f"declared in wire_contracts.json (parsed-but-never-"
                    f"emitted ghost?)",
                )))
    for name, spec in sorted(declared.items()):
        if name not in emitted and not spec.get("emit_exempt"):
            findings.append(Finding(
                RULE_REGISTRY, CONTRACTS_PATH, 1,
                f"event '{name}' is declared but nothing emits it "
                f"(add emit_exempt with a reason, or delete it)",
            ))
        if trace_sf is not None and name not in consumed and not spec.get(
                "consume_exempt"):
            anchor = emitted.get(name)
            if anchor is not None:
                findings.append(apply_suppression(anchor[0], Finding(
                    RULE_EVENT, anchor[0].rel, anchor[1],
                    f"event '{name}' is emitted but obs/trace.py never "
                    f"consumes it — an emitted-but-never-parsed span",
                )))
            else:
                findings.append(Finding(
                    RULE_REGISTRY, CONTRACTS_PATH, 1,
                    f"event '{name}' is declared but obs/trace.py never "
                    f"consumes it (add consume_exempt with a reason)",
                ))
    return findings


# --------------------------------------------------------------------------
# C10: GenServerConfig -> argparse -> engine kwarg plumbing
# --------------------------------------------------------------------------

def _collect_flags(fn_node: ast.AST) -> Dict[str, int]:
    """--flag strings appearing in a function body, from constants and
    f-string heads; '=value' suffixes stripped."""
    flags: Dict[str, int] = {}

    def add(s: str, line: int):
        for piece in s.split():
            if piece.startswith("--"):
                flags.setdefault(piece.split("=")[0], line)

    for node in ast.walk(fn_node):
        s = _const_str(node)
        if s is not None and s.startswith("--"):
            add(s, node.lineno)
        elif isinstance(node, ast.JoinedStr) and node.values:
            head = _const_str(node.values[0])
            if head is not None and head.startswith("--"):
                add(head, node.lineno)
    return flags


def check_config_plumbing(
    files: Dict[str, SourceFile],
    root: Optional[str] = None,
    contracts: Optional[WireContracts] = None,
) -> List[Finding]:
    wc = contracts or WireContracts.load(root)
    cc = wc.config_chains
    if not cc:
        return []
    findings: List[Finding] = []
    f = cc.get("files", {})
    cfg_sf = files.get(os.path.normpath(f.get("config", "")))
    srv_sf = files.get(os.path.normpath(f.get("server", "")))
    eng_sf = files.get(os.path.normpath(f.get("engine", "")))
    if cfg_sf is None or srv_sf is None or eng_sf is None:
        return [Finding(
            RULE_REGISTRY, CONTRACTS_PATH, 1,
            f"config_chains.files points at missing files "
            f"({sorted(f.values())})",
        )]

    # -- config fields + build_cmd flags --
    cfg_fields: Dict[str, int] = {}
    build_flags: Dict[str, int] = {}
    cls_line = 1
    for node in ast.walk(cfg_sf.tree):
        if isinstance(node, ast.ClassDef) and node.name == f.get(
                "config_class", "GenServerConfig"):
            cls_line = node.lineno
            for item in node.body:
                if isinstance(item, ast.AnnAssign) and isinstance(
                        item.target, ast.Name):
                    cfg_fields[item.target.id] = item.lineno
                elif isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    if item.name == f.get("build_cmd", "build_cmd"):
                        build_flags = _collect_flags(item)

    # -- server argparse flags + engine call kwargs --
    arg_flags: Dict[str, int] = {}
    engine_call_kwargs: Dict[str, int] = {}
    dict_literals: Dict[str, Dict[str, int]] = {}
    engine_cls = f.get("engine_class", "GenEngine")
    for node in ast.walk(srv_sf.tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument"
                and node.args):
            flag = _const_str(node.args[0])
            if flag and flag.startswith("--"):
                arg_flags.setdefault(flag, node.lineno)
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            v = node.value
            if isinstance(v, ast.Dict):
                keys, _open = _dict_keys(v)
                dict_literals[node.targets[0].id] = keys
            elif (isinstance(v, ast.Call) and isinstance(v.func, ast.Name)
                    and v.func.id == "dict"):
                dict_literals[node.targets[0].id] = {
                    kw.arg: kw.value.lineno
                    for kw in v.keywords if kw.arg is not None
                }
    for node in ast.walk(srv_sf.tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func) or ""
        if d.rsplit(".", 1)[-1] != engine_cls:
            continue
        for kw in node.keywords:
            if kw.arg is not None:
                engine_call_kwargs.setdefault(kw.arg, node.lineno)
            elif isinstance(kw.value, ast.Name):  # **tier_kw splat
                for k, ln in dict_literals.get(kw.value.id, {}).items():
                    engine_call_kwargs.setdefault(k, ln)

    # -- engine __init__ params --
    engine_params: Dict[str, int] = {}
    for node in ast.walk(eng_sf.tree):
        if isinstance(node, ast.ClassDef) and node.name == engine_cls:
            for item in node.body:
                if (isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef))
                        and item.name == "__init__"):
                    a = item.args
                    for p in (a.posonlyargs + a.args + a.kwonlyargs):
                        engine_params[p.arg] = item.lineno

    chains = cc.get("chains", [])
    chained_fields = {c["field"] for c in chains if c.get("field")}
    chained_flags = {c["flag"] for c in chains if c.get("flag")}

    for chain in chains:
        field = chain.get("field")
        flag = chain.get("flag")
        kwarg = chain.get("engine_kwarg")
        label = field or flag or kwarg
        if field and field not in cfg_fields:
            findings.append(apply_suppression(cfg_sf, Finding(
                RULE_CONFIG, cfg_sf.rel, cls_line,
                f"chain '{label}': GenServerConfig has no field '{field}' "
                f"(renamed without updating wire_contracts.json?)",
            )))
        if flag and flag not in arg_flags:
            findings.append(apply_suppression(srv_sf, Finding(
                RULE_CONFIG, srv_sf.rel, 1,
                f"chain '{label}': gen/server.py argparse has no '{flag}' "
                f"flag",
            )))
        if flag and (field or chain.get("build_emits")) \
                and flag not in build_flags:
            findings.append(apply_suppression(cfg_sf, Finding(
                RULE_CONFIG, cfg_sf.rel, cfg_fields.get(field, cls_line),
                f"chain '{label}': build_cmd never emits '{flag}' — "
                f"launchers silently drop the configured value",
            )))
        if kwarg:
            if kwarg not in engine_params:
                findings.append(apply_suppression(eng_sf, Finding(
                    RULE_CONFIG, eng_sf.rel,
                    engine_params.get("__any__", 1),
                    f"chain '{label}': GenEngine.__init__ has no "
                    f"'{kwarg}' parameter",
                )))
            if kwarg not in engine_call_kwargs:
                findings.append(apply_suppression(srv_sf, Finding(
                    RULE_CONFIG, srv_sf.rel, 1,
                    f"chain '{label}': gen/server.py main() never passes "
                    f"'{kwarg}' to {engine_cls} — the flag is parsed but "
                    f"dropped",
                )))

    for flag, line in sorted(arg_flags.items()):
        if flag not in chained_flags:
            findings.append(apply_suppression(srv_sf, Finding(
                RULE_CONFIG, srv_sf.rel, line,
                f"argparse flag '{flag}' is not covered by any "
                f"config_chains entry in wire_contracts.json — add a "
                f"chain (or a server_only entry with a reason)",
            )))
    for field, line in sorted(cfg_fields.items()):
        if field not in chained_fields:
            findings.append(apply_suppression(cfg_sf, Finding(
                RULE_CONFIG, cfg_sf.rel, line,
                f"GenServerConfig.{field} is not covered by any "
                f"config_chains entry in wire_contracts.json — add a "
                f"chain (or a config_only entry with a reason)",
            )))
    for flag, line in sorted(build_flags.items()):
        if flag not in arg_flags:
            findings.append(apply_suppression(cfg_sf, Finding(
                RULE_CONFIG, cfg_sf.rel, line,
                f"build_cmd emits '{flag}' but gen/server.py argparse "
                f"does not accept it — launched servers will crash",
            )))
        if flag not in chained_flags:
            findings.append(apply_suppression(cfg_sf, Finding(
                RULE_CONFIG, cfg_sf.rel, line,
                f"build_cmd flag '{flag}' is not covered by any "
                f"config_chains entry in wire_contracts.json",
            )))
    return findings


# --------------------------------------------------------------------------
# C10 (train half): TrainEngineConfig -> bench flag -> model-config replace
# --------------------------------------------------------------------------

def _class_ann_fields(
    sf: SourceFile, cls_name: str
) -> Tuple[Dict[str, int], int]:
    fields: Dict[str, int] = {}
    cls_line = 1
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.ClassDef) and node.name == cls_name:
            cls_line = node.lineno
            for item in node.body:
                if isinstance(item, ast.AnnAssign) and isinstance(
                        item.target, ast.Name):
                    fields[item.target.id] = item.lineno
    return fields, cls_line


def check_train_config_plumbing(
    files: Dict[str, SourceFile],
    root: Optional[str] = None,
    contracts: Optional[WireContracts] = None,
) -> List[Finding]:
    """The train-side config chains (ISSUE 20): each declared
    TrainEngineConfig knob must (a) exist as a config field, (b) be
    exposed AND read by the e2e bench's argparse when a flag is declared,
    and (c) — when it steers the backbone — exist on TransformerConfig
    and be plumbed through a `.replace(` call in the train engine.  Unlike
    the GenServer chain this is a DECLARED-chains check, not an
    exhaustive-coverage sweep: TrainEngineConfig has dozens of fields with
    their own plumbing idioms; the registry lists the chains whose silent
    breakage has bitten (a flag parsed but dropped trains a different
    model than the artifact records)."""
    wc = contracts or WireContracts.load(root)
    tc = wc.train_config_chains
    if not tc:
        return []
    f = tc.get("files", {})
    cfg_sf = files.get(os.path.normpath(f.get("config", "")))
    bench_sf = files.get(os.path.normpath(f.get("bench", "")))
    model_sf = files.get(os.path.normpath(f.get("model_config", "")))
    eng_sf = files.get(os.path.normpath(f.get("engine", "")))
    if cfg_sf is None or bench_sf is None or model_sf is None \
            or eng_sf is None:
        return [Finding(
            RULE_REGISTRY, CONTRACTS_PATH, 1,
            f"train_config_chains.files points at missing files "
            f"({sorted(f.values())})",
        )]

    cfg_fields, cfg_line = _class_ann_fields(
        cfg_sf, f.get("config_class", "TrainEngineConfig"))
    model_fields, _ = _class_ann_fields(
        model_sf, f.get("model_class", "TransformerConfig"))

    # bench argparse flags + `args.<dest>` reads (a parsed-but-never-read
    # flag silently trains the default)
    bench_flags: Dict[str, int] = {}
    args_reads: Dict[str, int] = {}
    for node in ast.walk(bench_sf.tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument"
                and node.args):
            flag = _const_str(node.args[0])
            if flag and flag.startswith("--"):
                bench_flags.setdefault(flag, node.lineno)
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "args"):
            args_reads.setdefault(node.attr, node.lineno)

    # model-config kwargs the engine plumbs via `.replace(...)`
    replace_kwargs: Dict[str, int] = {}
    for node in ast.walk(eng_sf.tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "replace"):
            for kw in node.keywords:
                if kw.arg is not None:
                    replace_kwargs.setdefault(kw.arg, node.lineno)

    findings: List[Finding] = []
    for chain in tc.get("chains", []):
        field = chain.get("field")
        flag = chain.get("flag")
        mfield = chain.get("model_field")
        label = field or flag or mfield
        if field and field not in cfg_fields:
            findings.append(apply_suppression(cfg_sf, Finding(
                RULE_CONFIG, cfg_sf.rel, cfg_line,
                f"train chain '{label}': TrainEngineConfig has no field "
                f"'{field}' (renamed without updating "
                f"wire_contracts.json?)",
            )))
        if flag:
            if flag not in bench_flags:
                findings.append(apply_suppression(bench_sf, Finding(
                    RULE_CONFIG, bench_sf.rel, 1,
                    f"train chain '{label}': {bench_sf.rel} argparse has "
                    f"no '{flag}' flag",
                )))
            else:
                dest = flag.lstrip("-").replace("-", "_")
                if dest not in args_reads:
                    findings.append(apply_suppression(bench_sf, Finding(
                        RULE_CONFIG, bench_sf.rel, bench_flags[flag],
                        f"train chain '{label}': '{flag}' is parsed but "
                        f"`args.{dest}` is never read — the flag is "
                        f"silently dropped",
                    )))
        if mfield:
            if mfield not in model_fields:
                findings.append(apply_suppression(model_sf, Finding(
                    RULE_CONFIG, model_sf.rel, 1,
                    f"train chain '{label}': TransformerConfig has no "
                    f"field '{mfield}'",
                )))
            if mfield not in replace_kwargs:
                findings.append(apply_suppression(eng_sf, Finding(
                    RULE_CONFIG, eng_sf.rel, 1,
                    f"train chain '{label}': {eng_sf.rel} never plumbs "
                    f"'{mfield}' into a model-config .replace(...) — the "
                    f"engine knob cannot reach the backbone",
                )))
    return findings


# --------------------------------------------------------------------------
# suite entry point
# --------------------------------------------------------------------------

def check_wire_contracts(
    files: Dict[str, SourceFile], root: str
) -> List[Finding]:
    try:
        wc = WireContracts.load(root)
    except (OSError, ValueError, KeyError) as e:
        return [Finding(
            RULE_REGISTRY, CONTRACTS_PATH, 1,
            f"wire_contracts.json unreadable: {e}",
        )]
    findings: List[Finding] = []
    findings.extend(check_payload_contracts(files, root, contracts=wc))
    findings.extend(check_telemetry_contracts(files, root, contracts=wc))
    findings.extend(check_config_plumbing(files, root, contracts=wc))
    findings.extend(check_train_config_plumbing(files, root, contracts=wc))
    return findings
