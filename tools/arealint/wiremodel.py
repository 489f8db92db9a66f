"""Wire-contract model of the HTTP/SSE control and data plane.

Parsed with ``ast``, never imported — the same provenance contract as the
counter / fault / mesh / resource catalogs: a catalog the linter checks
code against must itself be derived from the tree it checks, so a
refactor that moves an endpoint invalidates the model instead of
silently checking against a stale one.

Two sides are modeled (docs/static_analysis.md "Wire rules"):

- **server endpoints** (:func:`parse_server_module`): every
  ``router.add_post/add_get`` registration with a literal path, plus a
  transitive walk of the handler (same-module helpers only) collecting
  request-body fields read (``d["k"]`` = required, ``d.get("k")`` /
  ``"k" in d`` = optional), response-body keys written
  (``web.json_response({...})``), HTTP statuses emitted
  (``status=`` constants, ``web.HTTPxxx`` raises), and SSE frame keys
  (dict literals reaching ``resp.write(... json.dumps(x) ...)``,
  including frames fed through an ``asyncio.Queue``).
- **client call sites** (:func:`parse_client_modules`): direct
  ``session.post(f"{base}/path", json={...})`` calls and calls through
  client-class wrappers (``GenAPIClient.generate`` → ``_request_json``),
  with the payload fields sent, response / SSE frame keys read
  (including the ``asyncio.gather`` + ``zip`` fan-out idiom), statuses
  branched on, and whether the call path retries on HTTP statuses.

Everything that does not resolve statically DEGRADES: a dynamic path,
computed field name, ``**kwargs`` payload, or unrecognized receiver
produces no model entry (and marks the key set open where one-sided
knowledge would otherwise fabricate a finding). A deliberate one-sided
field is vouched for in place::

    **hbm_gauges,  # arealint: wire(/metrics_json, hbm gauge keys merged from HBMMonitor.check)

The annotation names the ENDPOINT (so a refactor that moves the line to
a different handler invalidates it) and requires a reason, same as
``# arealint: ok`` / ``owns``.
"""

import ast
import dataclasses
import pathlib
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

WIRE_RE = re.compile(
    r"#\s*arealint:\s*wire\(\s*(?P<endpoint>[^,()]+?)\s*,\s*(?P<reason>[^)]+?)\s*\)"
)
WIRE_BARE_RE = re.compile(r"#\s*arealint:\s*wire\b")

# aiohttp's web.HTTPxxx exception classes the tree raises (raising one
# sends that status). Names not listed degrade to no-status.
AIOHTTP_STATUS = {
    "HTTPBadRequest": 400,
    "HTTPUnauthorized": 401,
    "HTTPForbidden": 403,
    "HTTPNotFound": 404,
    "HTTPConflict": 409,
    "HTTPTooManyRequests": 429,
    "HTTPInternalServerError": 500,
    "HTTPBadGateway": 502,
    "HTTPServiceUnavailable": 503,
    "HTTPGatewayTimeout": 504,
}

ROUTE_METHODS = {
    "add_post": "POST",
    "add_get": "GET",
    "add_put": "PUT",
    "add_delete": "DELETE",
}

# Every endpoint can answer 200 (success) and 500 (unhandled handler
# exception — aiohttp converts it); clients may branch on these freely.
IMPLICIT_STATUSES = frozenset({200, 500})

_MAX_DEPTH = 8


# --------------------------------------------------------------------- #
# Declaration + verification (the provenance contract)
# --------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class WireDefs:
    """DECLARED wire surface: which modules register routes, which
    modules speak to them, and which endpoints must never be re-POSTed
    on an HTTP status (the request may already be executing server-side).
    Verified against the tree before use; entries that no longer match
    are dropped (degrade, never guess)."""

    server_modules: Tuple[str, ...]
    client_modules: Tuple[str, ...]
    non_idempotent: Tuple[str, ...]


DEFAULT_WIRE_DEFS = WireDefs(
    server_modules=(
        "areal_tpu/gateway/api.py",
        "areal_tpu/gen/server.py",
        "areal_tpu/system/gserver_manager.py",
    ),
    client_modules=(
        "areal_tpu/gen/client.py",
        "areal_tpu/gateway/scheduler.py",
        "areal_tpu/gateway/autoscaler.py",
        "areal_tpu/system/fleet.py",
        "areal_tpu/system/partial_rollout.py",
        "areal_tpu/system/rollout_worker.py",
        "areal_tpu/system/gserver_manager.py",
        "areal_tpu/apps/launcher.py",
        "areal_tpu/apps/obs.py",
    ),
    # re-sending one of these on a 5xx may double-execute a request the
    # server is still running (double-billed rid / double weight load)
    non_idempotent=(
        "/generate",
        "/generate_stream",
        "/update_weights_from_disk",
    ),
)


@dataclasses.dataclass(frozen=True)
class WireSpec:
    """The VERIFIED declaration ``Config.wire`` carries: only modules
    that exist (servers: and register at least one literal route) and
    non-idempotent paths some surviving server actually registers."""

    servers: Tuple[str, ...]
    clients: Tuple[str, ...]
    non_idempotent: frozenset


def verify_defs(
    root: pathlib.Path, defs: WireDefs = DEFAULT_WIRE_DEFS
) -> Tuple[Optional[WireSpec], List[str]]:
    """Check the declaration against the tree. Returns ``(spec, dropped)``
    where ``dropped`` lists human-readable reasons for every declared
    entry that failed verification. ``spec`` is None when no server
    module survives (wire rules disabled entirely)."""
    dropped: List[str] = []
    servers: List[str] = []
    registered: Set[str] = set()
    for rel in defs.server_modules:
        p = root / rel
        if not p.is_file():
            dropped.append(f"server module {rel}: file missing")
            continue
        try:
            tree = ast.parse(p.read_text(), filename=str(p))
        except SyntaxError:
            dropped.append(f"server module {rel}: does not parse")
            continue
        routes = find_routes(tree)
        if not routes:
            dropped.append(f"server module {rel}: registers no literal route")
            continue
        servers.append(rel)
        registered.update(path for _m, path, _h, _ln in routes)
    clients: List[str] = []
    for rel in defs.client_modules:
        p = root / rel
        if not p.is_file():
            dropped.append(f"client module {rel}: file missing")
            continue
        clients.append(rel)
    non_idem: List[str] = []
    for path in defs.non_idempotent:
        if path in registered:
            non_idem.append(path)
        else:
            dropped.append(f"non-idempotent path {path}: no server registers it")
    if not servers:
        return None, dropped
    return (
        WireSpec(tuple(servers), tuple(clients), frozenset(non_idem)),
        dropped,
    )


def from_repo(root: pathlib.Path) -> Optional[WireSpec]:
    spec, _dropped = verify_defs(pathlib.Path(root))
    return spec


# --------------------------------------------------------------------- #
# Model dataclasses
# --------------------------------------------------------------------- #


@dataclasses.dataclass
class KeySet:
    """A set of wire keys with open ends: literal keys, literal prefixes
    (from ``{f"engine_{k}": v ...}`` comprehensions), and an ``open``
    flag set when any contributor did not resolve (an open set covers
    everything — degrade, never guess)."""

    keys: Dict[str, int] = dataclasses.field(default_factory=dict)
    prefixes: List[str] = dataclasses.field(default_factory=list)
    open: bool = False

    def covers(self, key: str) -> bool:
        return (
            self.open
            or key in self.keys
            or any(key.startswith(p) for p in self.prefixes)
        )

    def merge(self, other: "KeySet") -> None:
        for k, ln in other.keys.items():
            self.keys.setdefault(k, ln)
        for p in other.prefixes:
            if p not in self.prefixes:
                self.prefixes.append(p)
        self.open = self.open or other.open


@dataclasses.dataclass
class Endpoint:
    path: str
    method: str
    module: str
    handler: str
    lineno: int  # registration line
    required: Dict[str, int] = dataclasses.field(default_factory=dict)
    optional: Dict[str, int] = dataclasses.field(default_factory=dict)
    # True when the body escapes (stored / passed outside the module):
    # the handler may read fields we cannot see, so "client sends a field
    # no handler reads" must not fire.
    fields_open: bool = False
    response: KeySet = dataclasses.field(default_factory=KeySet)
    statuses: Dict[int, int] = dataclasses.field(default_factory=dict)
    sse: Optional[KeySet] = None

    def emits(self, status: int) -> bool:
        return status in IMPLICIT_STATUSES or status in self.statuses


@dataclasses.dataclass
class ClientCall:
    """One resolved client-side HTTP call site."""

    module: str
    lineno: int
    method: str
    path: str
    via: str  # "session.post" or "GenAPIClient.generate"
    payload: Optional[Dict[str, int]] = None  # None = unresolved payload
    reads: Dict[str, int] = dataclasses.field(default_factory=dict)
    sse_reads: Dict[str, int] = dataclasses.field(default_factory=dict)
    status_branches: Dict[int, int] = dataclasses.field(default_factory=dict)
    # raise_for_status() (possibly inside the wrapper) or a broad
    # except around the call: non-2xx statuses are handled generically
    generic_status_guard: bool = False
    # the call path re-sends the request on RETRYABLE HTTP statuses
    # (a wrapper with retry_connection_only left False)
    retries_status: bool = False
    # the call returns an SSE frame iterator (generate_stream): reads
    # associate with the endpoint's frame keys, not its response body
    sse_wrapper: bool = False


@dataclasses.dataclass
class WireModel:
    spec: WireSpec
    endpoints: Dict[Tuple[str, str], List[Endpoint]]  # (method, path)
    calls: List[ClientCall]
    servers_present: bool  # all spec.servers were in the scanned set
    clients_present: bool

    def lookup(self, method: str, path: str) -> List[Endpoint]:
        return self.endpoints.get((method, path), [])

    def path_known(self, path: str) -> bool:
        return any(p == path for (_m, p) in self.endpoints)

    def calls_to(self, method: str, path: str) -> List[ClientCall]:
        return [
            c for c in self.calls if c.method == method and c.path == path
        ]


# --------------------------------------------------------------------- #
# Shared AST helpers
# --------------------------------------------------------------------- #


def _dotted(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


def _const_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _unwrap_await(node: ast.AST) -> ast.AST:
    return node.value if isinstance(node, ast.Await) else node


def wire_annotation(
    lines: Sequence[str], lineno: int
) -> Optional[Tuple[Optional[str], Optional[str]]]:
    """The ``# arealint: wire(<endpoint>, <reason>)`` annotation on
    ``lineno`` or a comment-only line directly above. Returns
    ``(endpoint, reason)``; ``(None, None)`` for a present-but-malformed
    annotation; None when absent. 1-indexed."""
    for ln in (lineno, lineno - 1):
        if not (1 <= ln <= len(lines)):
            continue
        text = lines[ln - 1]
        if ln != lineno and not text.strip().startswith("#"):
            continue
        m = WIRE_RE.search(text)
        if m:
            return m.group("endpoint").strip(), m.group("reason").strip()
        if WIRE_BARE_RE.search(text):
            return None, None
    return None


def _vouched(lines: Sequence[str], lineno: int, endpoint: str) -> bool:
    ann = wire_annotation(lines, lineno)
    return ann is not None and ann[0] == endpoint and bool(ann[1])


class _ModuleIndex:
    """Light per-module symbol index: top-level functions, classes with
    their methods, a parent-function map (closures see enclosing params),
    and the import alias table."""

    def __init__(self, tree: ast.Module, src: str):
        self.tree = tree
        self.lines = src.splitlines()
        self.functions: Dict[str, ast.AST] = {}
        self.classes: Dict[str, ast.ClassDef] = {}
        self.methods: Dict[str, Dict[str, ast.AST]] = {}
        self.parent_fn: Dict[int, Optional[ast.AST]] = {}
        for node in tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions[node.name] = node
            elif isinstance(node, ast.ClassDef):
                self.classes[node.name] = node
                self.methods[node.name] = {
                    n.name: n
                    for n in node.body
                    if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                }
        self._map_parents(tree, None)

    def _map_parents(self, node: ast.AST, fn: Optional[ast.AST]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.parent_fn[id(child)] = fn
                self._map_parents(child, child)
            else:
                self._map_parents(child, fn)

    def param_names(self, fn: ast.AST) -> List[str]:
        a = fn.args
        names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
        if a.vararg:
            names.append(a.vararg.arg)
        if a.kwarg:
            names.append(a.kwarg.arg)
        return names

    def enclosing_params(self, fn: ast.AST) -> Dict[str, ast.AST]:
        """Param name -> annotation node, walking out through enclosing
        functions (closures)."""
        out: Dict[str, ast.AST] = {}
        cur: Optional[ast.AST] = fn
        while cur is not None:
            a = cur.args
            for p in a.posonlyargs + a.args + a.kwonlyargs:
                out.setdefault(p.arg, p.annotation)
            cur = self.parent_fn.get(id(cur))
        return out


def _walk_fn(fn: ast.AST) -> Iterable[ast.AST]:
    """Walk a function body WITHOUT descending into nested defs (their
    bodies are analyzed on their own when called)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(
            node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
        ):
            continue
        stack.extend(ast.iter_child_nodes(node))


def find_routes(tree: ast.Module) -> List[Tuple[str, str, str, int]]:
    """``(method, path, handler_name, lineno)`` for every
    ``<x>.router.add_*("/literal", handler)`` call in the module.
    Dynamic paths and unrecognized handler expressions are skipped."""
    out: List[Tuple[str, str, str, int]] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if not (
            isinstance(fn, ast.Attribute)
            and fn.attr in ROUTE_METHODS
            and isinstance(fn.value, ast.Attribute)
            and fn.value.attr == "router"
        ):
            continue
        if len(node.args) < 2:
            continue
        path = _const_str(node.args[0])
        if path is None:
            continue  # dynamic path: degrade
        h = node.args[1]
        if isinstance(h, ast.Attribute):
            handler = h.attr
        elif isinstance(h, ast.Name):
            handler = h.id
        else:
            continue
        out.append((ROUTE_METHODS[fn.attr], path, handler, node.lineno))
    return out


# --------------------------------------------------------------------- #
# Server side
# --------------------------------------------------------------------- #


class _HandlerScan:
    """Transitive walk of one handler (same-module helpers only),
    collecting body-field reads, response keys, statuses, and SSE frame
    keys into an :class:`Endpoint`."""

    def __init__(self, idx: _ModuleIndex, cls: Optional[str], ep: Endpoint):
        self.idx = idx
        self.cls = cls
        self.ep = ep
        self._seen: Set[Tuple[int, frozenset]] = set()
        self._queue_frames: Optional[KeySet] = None

    # ---- entry ----

    def run(self, fn: ast.AST) -> None:
        params = self.idx.param_names(fn)
        roles: Dict[str, str] = {}
        for p in params:
            if p in ("self", "cls"):
                continue
            roles[p] = "request"
            break  # the single aiohttp request argument
        self._scan(fn, roles, {}, 0)

    # ---- function-level scan ----

    def _scan(
        self,
        fn: ast.AST,
        roles: Dict[str, str],  # param/var name -> "request" | "body"
        consts: Dict[str, object],  # param name -> constant call-site arg
        depth: int,
    ) -> Tuple[List[str], bool]:
        """Returns ``(return_roles, returns_body)`` where return_roles
        marks tuple slots of the return value that carry the body."""
        key = (
            id(fn),
            frozenset(roles.items()),
            frozenset((k, repr(v)) for k, v in consts.items()),
        )
        if key in self._seen or depth > _MAX_DEPTH:
            return [], False
        self._seen.add(key)
        body_vars = {n for n, r in roles.items() if r == "body"}
        request_vars = {n for n, r in roles.items() if r == "request"}
        stream_vars: Set[str] = set()
        ret_slots: List[str] = []
        returns_body = False

        def is_body(node: ast.AST) -> bool:
            return isinstance(node, ast.Name) and node.id in body_vars

        nodes = list(_walk_fn(fn))
        # pass 1 (to fixpoint): bind body / stream vars before reads are
        # attributed — the AST walk is not source-ordered
        for _pass in range(2):
            for node in nodes:
                if not (
                    isinstance(node, ast.Assign) and len(node.targets) == 1
                ):
                    continue
                tgt, val = node.targets[0], _unwrap_await(node.value)
                if (
                    isinstance(tgt, ast.Name)
                    and isinstance(val, ast.Call)
                    and isinstance(val.func, ast.Attribute)
                    and val.func.attr == "json"
                    and isinstance(val.func.value, ast.Name)
                    and val.func.value.id in request_vars
                ):
                    body_vars.add(tgt.id)
                elif isinstance(val, ast.Call) and _pass == 0:
                    slots = self._local_call_body_slots(
                        val, body_vars, request_vars, consts, depth
                    )
                    if slots is not None:
                        whole, tuple_slots = slots
                        if isinstance(tgt, ast.Name) and whole:
                            body_vars.add(tgt.id)
                        elif isinstance(tgt, ast.Tuple):
                            for i, el in enumerate(tgt.elts):
                                if (
                                    isinstance(el, ast.Name)
                                    and i in tuple_slots
                                ):
                                    body_vars.add(el.id)
                if (
                    isinstance(tgt, ast.Name)
                    and isinstance(val, ast.Call)
                    and (_dotted(val.func) or "").endswith("StreamResponse")
                ):
                    stream_vars.add(tgt.id)
        # pass 2: reads / responses / statuses / SSE / escapes / returns
        for node in nodes:
            if isinstance(node, ast.Subscript) and is_body(node.value):
                k = _const_str(node.slice)
                if k is not None:
                    self.ep.required.setdefault(k, node.lineno)
                else:
                    self.ep.fields_open = True
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "get"
                and is_body(node.func.value)
                and node.args
            ):
                k = _const_str(node.args[0])
                if k is not None:
                    self.ep.optional.setdefault(k, node.lineno)
                else:
                    self.ep.fields_open = True
            if isinstance(node, ast.Compare) and len(node.ops) == 1:
                if isinstance(node.ops[0], (ast.In, ast.NotIn)) and is_body(
                    node.comparators[0]
                ):
                    k = _const_str(node.left)
                    if k is not None:
                        self.ep.optional.setdefault(k, node.lineno)
            # --- responses / statuses ---
            if isinstance(node, ast.Call):
                d = _dotted(node.func) or ""
                if d.endswith("json_response"):
                    self._take_json_response(node, fn, consts)
                elif node.func and self._is_http_exc(node):
                    pass  # handled at the Raise below
                else:
                    self._maybe_recurse_local(
                        node, fn, body_vars, request_vars, depth
                    )
            if isinstance(node, ast.Raise) and node.exc is not None:
                exc = node.exc
                name = None
                if isinstance(exc, ast.Call):
                    name = (_dotted(exc.func) or "").rsplit(".", 1)[-1]
                elif isinstance(exc, (ast.Name, ast.Attribute)):
                    name = (_dotted(exc) or "").rsplit(".", 1)[-1]
                if name in AIOHTTP_STATUS:
                    self.ep.statuses.setdefault(
                        AIOHTTP_STATUS[name], node.lineno
                    )
            # --- SSE writes ---
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "write"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in stream_vars
                and node.args
            ):
                self._take_sse_write(node.args[0], fn)
            # --- body escaping the module (degrade the warn direction) ---
            if isinstance(node, ast.Call):
                for a in node.args:
                    av = a.value if isinstance(a, ast.Starred) else a
                    if is_body(av) and not self._is_local_call(node):
                        callee = (_dotted(node.func) or "").rsplit(".", 1)[-1]
                        # dict(d) / json.dumps(d) etc read, don't hide reads
                        if callee not in (
                            "dict", "dumps", "len", "str", "repr",
                        ):
                            self.ep.fields_open = True
            # --- returns ---
            if isinstance(node, ast.Return) and node.value is not None:
                v = node.value
                if is_body(v):
                    returns_body = True
                elif isinstance(v, ast.Tuple):
                    for i, el in enumerate(v.elts):
                        if is_body(el):
                            ret_slots.append(str(i))
        return ret_slots, returns_body

    # ---- helpers ----

    def _is_http_exc(self, call: ast.Call) -> bool:
        name = (_dotted(call.func) or "").rsplit(".", 1)[-1]
        return name in AIOHTTP_STATUS

    def _is_local_call(self, call: ast.Call) -> bool:
        return self._resolve_local(call) is not None

    def _resolve_local(self, call: ast.Call) -> Optional[ast.AST]:
        """Same-class method (``self._x(...)``) or same-module function."""
        f = call.func
        if (
            isinstance(f, ast.Attribute)
            and isinstance(f.value, ast.Name)
            and f.value.id == "self"
            and self.cls is not None
        ):
            return self.idx.methods.get(self.cls, {}).get(f.attr)
        if isinstance(f, ast.Name):
            return self.idx.functions.get(f.id)
        return None

    def _local_call_body_slots(
        self,
        call: ast.Call,
        body_vars: Set[str],
        request_vars: Set[str],
        consts: Dict[str, object],
        depth: int,
    ) -> Optional[Tuple[bool, Set[int]]]:
        """Recurse into a local callee, binding request/body roles from
        the call-site args; returns (returns_body, body_tuple_slots)."""
        target = self._resolve_local(call)
        if target is None:
            return None
        roles = self._bind_roles(call, target, body_vars, request_vars)
        callee_consts = self._bind_consts(call, target)
        ret_slots, returns_body = self._scan(
            target, roles, callee_consts, depth + 1
        )
        return returns_body, {int(s) for s in ret_slots}

    def _maybe_recurse_local(
        self,
        call: ast.Call,
        fn: ast.AST,
        body_vars: Set[str],
        request_vars: Set[str],
        depth: int,
    ) -> None:
        target = self._resolve_local(call)
        if target is None or target is fn:
            return
        roles = self._bind_roles(call, target, body_vars, request_vars)
        self._scan(target, roles, self._bind_consts(call, target), depth + 1)

    def _bind_roles(
        self,
        call: ast.Call,
        target: ast.AST,
        body_vars: Set[str],
        request_vars: Set[str],
    ) -> Dict[str, str]:
        roles: Dict[str, str] = {}
        params = [
            p for p in self.idx.param_names(target) if p not in ("self", "cls")
        ]
        args = list(call.args)
        for i, a in enumerate(args):
            if i >= len(params):
                break
            if isinstance(a, ast.Name):
                if a.id in body_vars:
                    roles[params[i]] = "body"
                elif a.id in request_vars:
                    roles[params[i]] = "request"
        for kw in call.keywords:
            if kw.arg and isinstance(kw.value, ast.Name):
                if kw.value.id in body_vars:
                    roles[kw.arg] = "body"
                elif kw.value.id in request_vars:
                    roles[kw.arg] = "request"
        return roles

    def _bind_consts(self, call: ast.Call, target: ast.AST) -> Dict[str, object]:
        consts: Dict[str, object] = {}
        params = [
            p for p in self.idx.param_names(target) if p not in ("self", "cls")
        ]
        for i, a in enumerate(call.args):
            if i < len(params) and isinstance(a, ast.Constant):
                consts[params[i]] = a.value
        for kw in call.keywords:
            if kw.arg and isinstance(kw.value, ast.Constant):
                consts[kw.arg] = kw.value.value
        return consts

    def _take_json_response(
        self, call: ast.Call, fn: ast.AST, consts: Dict[str, object]
    ) -> None:
        status = 200
        for kw in call.keywords:
            if kw.arg == "status":
                if isinstance(kw.value, ast.Constant) and isinstance(
                    kw.value.value, int
                ):
                    status = kw.value.value
                elif (
                    isinstance(kw.value, ast.Name)
                    and isinstance(consts.get(kw.value.id), int)
                ):
                    status = consts[kw.value.id]  # type: ignore[assignment]
                else:
                    status = -1  # dynamic: record keys, skip status
        if status not in (200, -1):
            self.ep.statuses.setdefault(status, call.lineno)
        if call.args:
            ks = self._resolve_keys(call.args[0], fn, 0)
            self.ep.response.merge(ks)

    def _take_sse_write(self, arg: ast.AST, fn: ast.AST) -> None:
        """A ``resp.write(...)`` on a StreamResponse: find json.dumps
        payloads inside the written expression."""
        if self.ep.sse is None:
            self.ep.sse = KeySet()
        for node in ast.walk(arg):
            if (
                isinstance(node, ast.Call)
                and (_dotted(node.func) or "").endswith("dumps")
                and node.args
            ):
                self.ep.sse.merge(self._resolve_frame(node.args[0], fn))

    def _resolve_frame(self, expr: ast.AST, fn: ast.AST) -> KeySet:
        if isinstance(expr, ast.Dict):
            return self._resolve_keys(expr, fn, 0)
        if isinstance(expr, ast.Name):
            # frame pulled off a queue: the frames are whatever the class
            # puts into queues (put/put_nowait dict literals); any
            # non-literal put opens the set
            for node in _walk_fn(fn):
                if (
                    isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id == expr.id
                ):
                    val = _unwrap_await(node.value)
                    if self._is_queue_get(val):
                        return self._class_queue_frames()
                    if isinstance(val, ast.Dict):
                        return self._resolve_keys(val, fn, 0)
        return KeySet(open=True)

    def _is_queue_get(self, val: ast.AST) -> bool:
        if isinstance(val, ast.Call):
            d = _dotted(val.func) or ""
            if d.endswith(".get") and not val.args:
                return True
            if d.endswith("wait_for") and val.args:
                return self._is_queue_get(val.args[0])
        return False

    def _class_queue_frames(self) -> KeySet:
        if self._queue_frames is not None:
            return self._queue_frames
        ks = KeySet()
        scope: ast.AST = (
            self.idx.classes.get(self.cls) if self.cls else self.idx.tree
        ) or self.idx.tree
        for node in ast.walk(scope):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("put_nowait", "put")
                and node.args
            ):
                if isinstance(node.args[0], ast.Dict):
                    ks.merge(self._resolve_keys(node.args[0], None, 0))
                else:
                    ks.open = True
        return ks

    def _resolve_keys(
        self, expr: ast.AST, fn: Optional[ast.AST], depth: int
    ) -> KeySet:
        """Key set of a response/frame expression. Dict literals resolve
        (recursing through ``**`` splats into nested literals, same-class
        method returns, and prefix comprehensions); a ``wire()``-vouched
        splat is skipped; anything else opens the set."""
        ks = KeySet()
        if depth > _MAX_DEPTH:
            ks.open = True
            return ks
        if isinstance(expr, ast.Dict):
            for k, v in zip(expr.keys, expr.values):
                if k is None:  # **splat
                    ks.merge(self._resolve_splat(v, fn, depth))
                else:
                    key = _const_str(k)
                    if key is not None:
                        ks.keys.setdefault(key, k.lineno)
                    else:
                        ks.open = True
            return ks
        if isinstance(expr, ast.Name) and fn is not None:
            for node in _walk_fn(fn):
                if (
                    isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id == expr.id
                    and isinstance(_unwrap_await(node.value), ast.Dict)
                ):
                    ks.merge(
                        self._resolve_keys(
                            _unwrap_await(node.value), fn, depth + 1
                        )
                    )
                    # constant subscript stores extend the literal
                    for n2 in _walk_fn(fn):
                        if (
                            isinstance(n2, ast.Assign)
                            and len(n2.targets) == 1
                            and isinstance(n2.targets[0], ast.Subscript)
                            and isinstance(n2.targets[0].value, ast.Name)
                            and n2.targets[0].value.id == expr.id
                        ):
                            k2 = _const_str(n2.targets[0].slice)
                            if k2 is not None:
                                ks.keys.setdefault(k2, n2.lineno)
                            else:
                                ks.open = True
                    return ks
            ks.open = True
            return ks
        ks.open = True
        return ks

    def _resolve_splat(
        self, v: ast.AST, fn: Optional[ast.AST], depth: int
    ) -> KeySet:
        if isinstance(v, ast.Dict):
            return self._resolve_keys(v, fn, depth + 1)
        if isinstance(v, ast.DictComp):
            ks = KeySet()
            if (
                isinstance(v.key, ast.JoinedStr)
                and v.key.values
                and isinstance(v.key.values[0], ast.Constant)
                and isinstance(v.key.values[0].value, str)
                and v.key.values[0].value
            ):
                ks.prefixes.append(v.key.values[0].value)
            else:
                k = _const_str(v.key)
                if k is not None:
                    ks.keys[k] = v.lineno
                else:
                    ks.open = True
            return ks
        if isinstance(v, ast.Call):
            # same-class method call: union of its returned dict keys
            target = self._resolve_local(v)
            if target is not None:
                ks = KeySet()
                found = False
                for node in _walk_fn(target):
                    if isinstance(node, ast.Return) and node.value is not None:
                        found = True
                        ks.merge(
                            self._resolve_keys(node.value, target, depth + 1)
                        )
                if found:
                    return ks
        if _vouched(self.idx.lines, v.lineno, self.ep.path):
            return KeySet()  # deliberately one-sided: vouched, not open
        return KeySet(open=True)


def parse_server_module(
    relpath: str, tree: ast.Module, src: str
) -> List[Endpoint]:
    """Endpoint catalog of one route-registering module."""
    idx = _ModuleIndex(tree, src)
    # which class does each handler belong to? (registration happens in a
    # method of the owning class — find the class whose methods include
    # the handler name)
    out: List[Endpoint] = []
    for method, path, handler, lineno in find_routes(tree):
        cls = None
        fn = idx.functions.get(handler)
        if fn is None:
            for cname, methods in idx.methods.items():
                if handler in methods:
                    cls, fn = cname, methods[handler]
                    break
        if fn is None:
            continue  # handler not in this module: degrade
        ep = Endpoint(
            path=path, method=method, module=relpath,
            handler=handler, lineno=lineno,
        )
        _HandlerScan(idx, cls, ep).run(fn)
        out.append(ep)
    return out


# --------------------------------------------------------------------- #
# Client side
# --------------------------------------------------------------------- #

_UNRESOLVED = object()  # payload sentinel: passed but not statically known


@dataclasses.dataclass
class WrapperInfo:
    """A client-class method that performs (or forwards to) an HTTP
    call. ``path`` / ``payload`` / ``method`` are either resolved values
    or ``("param", <name>)`` markers meaning the caller supplies them."""

    cls: str
    name: str
    module: str
    params: Tuple[str, ...]  # in order, excluding self
    method: object  # str | ("param", name) | None
    path: object  # str | ("param", name) | None
    payload: object  # dict | ("param", name) | {} (no body) | _UNRESOLVED
    retry_param: bool  # has a retry_connection_only parameter
    status_retrying: bool  # retries on HTTP statuses unless told not to
    guard: bool  # raise_for_status() somewhere in the chain
    sse: bool  # async generator yielding SSE frames
    lineno: int = 0


def _fn_params(fn: ast.AST) -> Tuple[str, ...]:
    a = fn.args
    return tuple(
        p.arg
        for p in a.posonlyargs + a.args + a.kwonlyargs
        if p.arg not in ("self", "cls")
    )


def _bind_call_args(
    call: ast.Call, params: Sequence[str]
) -> Dict[str, ast.AST]:
    """Map a call's args onto the callee's (self-less) param names.
    ``**kwargs`` splats make the binding unresolvable -> empty map for
    those names (degrade)."""
    out: Dict[str, ast.AST] = {}
    for i, a in enumerate(call.args):
        if isinstance(a, ast.Starred):
            break
        if i < len(params):
            out[params[i]] = a
    for kw in call.keywords:
        if kw.arg:
            out[kw.arg] = kw.value
    return out


def _ann_class_name(ann: Optional[ast.AST]) -> Optional[str]:
    """Bare class name of an annotation, unwrapping Optional[...] and
    string annotations."""
    if ann is None:
        return None
    if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
        text = ann.value.strip().strip("'\"")
        return text.split("[")[-1].rstrip("]").split(".")[-1] or None
    if isinstance(ann, ast.Subscript):
        base = (_dotted(ann.value) or "").rsplit(".", 1)[-1]
        if base in ("Optional", "Union"):
            inner = ann.slice
            if isinstance(inner, ast.Tuple):
                for el in inner.elts:
                    n = _ann_class_name(el)
                    if n is not None and n != "None":
                        return n
                return None
            return _ann_class_name(inner)
        return None
    d = _dotted(ann)
    return d.rsplit(".", 1)[-1] if d else None


def _resolve_url(
    url: ast.AST, fn_params: Sequence[str], idx: _ModuleIndex, fn: ast.AST
) -> object:
    """A request URL expression -> literal "/path", ("param", name) when
    the trailing component is a parameter of the enclosing function
    (chain), or None (dynamic: degrade)."""
    if isinstance(url, ast.JoinedStr) and url.values:
        last = url.values[-1]
        s = _const_str(last)
        if s is not None and s.startswith("/"):
            return s
        if isinstance(last, ast.FormattedValue) and isinstance(
            last.value, ast.Name
        ):
            name = last.value.id
            if name in idx.enclosing_params(fn) or name in fn_params:
                return ("param", name)
    return None


def _resolve_payload_expr(
    expr: Optional[ast.AST],
    fn: ast.AST,
    fn_params: Sequence[str],
) -> object:
    """json= expression -> dict of field->lineno, ("param", name), {} for
    an absent body, or _UNRESOLVED."""
    if expr is None:
        return {}
    if isinstance(expr, ast.Dict):
        out: Dict[str, int] = {}
        for k in expr.keys:
            key = _const_str(k) if k is not None else None
            if key is None:
                return _UNRESOLVED  # splat / computed field name
            out[key] = k.lineno
        return out
    if isinstance(expr, ast.Name):
        if expr.id in fn_params:
            return ("param", expr.id)
        # local dict literal, possibly extended by constant subscript
        # stores (body["deadline_s"] = ...)
        base: Optional[Dict[str, int]] = None
        for node in _walk_fn(fn):
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == expr.id
            ):
                val = _unwrap_await(node.value)
                if isinstance(val, ast.Dict):
                    r = _resolve_payload_expr(val, fn, fn_params)
                    base = r if isinstance(r, dict) else None
                else:
                    base = None
        if base is None:
            return _UNRESOLVED
        for node in _walk_fn(fn):
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Subscript)
                and isinstance(node.targets[0].value, ast.Name)
                and node.targets[0].value.id == expr.id
            ):
                k = _const_str(node.targets[0].slice)
                if k is None:
                    return _UNRESOLVED
                base.setdefault(k, node.lineno)
        return base
    if isinstance(expr, ast.Constant) and expr.value is None:
        return {}
    return _UNRESOLVED


def _direct_http_call(
    call: ast.Call,
) -> Optional[Tuple[object, ast.AST, Optional[ast.AST], Optional[ast.AST]]]:
    """Recognize ``<...session...>.post/get/request(url, ...)``. Returns
    ``(method, url_expr, json_expr, kwargs_splat)`` with method a str or
    the raw arg node (for .request); None when the receiver does not look
    like an aiohttp session."""
    f = call.func
    if not (isinstance(f, ast.Attribute) and f.attr in ("post", "get", "request")):
        return None
    recv = _dotted(f.value) or ""
    if "session" not in recv.rsplit(".", 1)[-1].lower():
        return None
    json_expr = None
    kwargs_splat = None
    for kw in call.keywords:
        if kw.arg == "json":
            json_expr = kw.value
        elif kw.arg is None:
            kwargs_splat = kw.value
    if f.attr == "request":
        if len(call.args) < 2:
            return None
        return call.args[0], call.args[1], json_expr, kwargs_splat
    if not call.args:
        return None
    method = "POST" if f.attr == "post" else "GET"
    return method, call.args[0], json_expr, kwargs_splat


def _resolve_kwargs_json(
    splat: ast.AST, fn: ast.AST
) -> Optional[ast.AST]:
    """``**req_kw`` where ``req_kw = {"json": <expr>, ...}`` locally
    (plain or annotated assignment)."""
    if not isinstance(splat, ast.Name):
        return None
    for node in _walk_fn(fn):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            tgt: ast.AST = node.targets[0]
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            tgt = node.target
        else:
            continue
        if (
            isinstance(tgt, ast.Name)
            and tgt.id == splat.id
            and isinstance(node.value, ast.Dict)
        ):
            for k, v in zip(node.value.keys, node.value.values):
                if _const_str(k) == "json":
                    return v
    return None


class _ClientScan:
    """Per-module client-side scan. Shares a cross-module wrapper table
    (client classes are defined in one module, used from others)."""

    def __init__(
        self,
        relpath: str,
        tree: ast.Module,
        src: str,
        wrappers: Dict[Tuple[str, str], WrapperInfo],
    ):
        self.relpath = relpath
        self.idx = _ModuleIndex(tree, src)
        self.wrappers = wrappers
        self.client_classes = {c for (c, _n) in wrappers}

    # ---- all functions (methods + module functions + closures) ----

    def _all_functions(self) -> List[Tuple[Optional[str], ast.AST]]:
        out: List[Tuple[Optional[str], ast.AST]] = []
        for node in ast.walk(self.idx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                cls = None
                for cname, methods in self.idx.methods.items():
                    if methods.get(node.name) is node:
                        cls = cname
                        break
                out.append((cls, node))
        return out

    # ---- wrapper discovery ----

    def discover_wrappers(self) -> bool:
        """One discovery round: direct HTTP calls plus forwarding to
        already-known wrappers. Returns True when a new wrapper was
        registered (caller iterates to fixpoint)."""
        changed = False
        for cls, fn in self._all_functions():
            if cls is None:
                continue
            if (cls, fn.name) in self.wrappers:
                continue
            info = self._wrapper_from_fn(cls, fn)
            if info is not None:
                self.wrappers[(cls, fn.name)] = info
                self.client_classes.add(cls)
                changed = True
        return changed

    def _fn_facts(self, fn: ast.AST) -> Tuple[bool, bool, bool]:
        guard = sse = False
        for node in _walk_fn(fn):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "raise_for_status"
            ):
                guard = True
            if isinstance(node, ast.Yield):
                sse = True
        retry_param = "retry_connection_only" in _fn_params(fn)
        return guard, sse, retry_param

    def _wrapper_from_fn(self, cls: str, fn: ast.AST) -> Optional[WrapperInfo]:
        params = _fn_params(fn)
        guard, sse, retry_param = self._fn_facts(fn)
        # (a) a direct session call
        for node in _walk_fn(fn):
            if not isinstance(node, ast.Call):
                continue
            direct = _direct_http_call(node)
            if direct is None:
                continue
            method_raw, url, json_expr, splat = direct
            splat_opaque = False
            if json_expr is None and splat is not None:
                json_expr = _resolve_kwargs_json(splat, fn)
                # a **kwargs splat that does not resolve to a local
                # literal may still carry a json body: degrade
                splat_opaque = json_expr is None
            method: object
            if isinstance(method_raw, str):
                method = method_raw
            elif isinstance(method_raw, ast.Constant):
                method = str(method_raw.value)
            elif (
                isinstance(method_raw, ast.Name)
                and method_raw.id in params
            ):
                method = ("param", method_raw.id)
            else:
                method = None
            path = _resolve_url(url, params, self.idx, fn)
            payload = (
                _UNRESOLVED
                if splat_opaque
                else _resolve_payload_expr(json_expr, fn, params)
            )
            return WrapperInfo(
                cls=cls, name=fn.name, module=self.relpath, params=params,
                method=method, path=path, payload=payload,
                retry_param=retry_param,
                status_retrying=retry_param,  # retries unless flag passed
                guard=guard, sse=sse, lineno=fn.lineno,
            )
        # (b) forwards to a known wrapper of the same class
        for node in _walk_fn(fn):
            if not isinstance(node, ast.Call):
                continue
            base = self._self_wrapper(cls, node)
            if base is None:
                continue
            bound = _bind_call_args(node, base.params)
            method = self._forward(base.method, bound, params)
            path = self._forward(base.path, bound, params)
            payload = self._forward_payload(base.payload, bound, params, fn)
            retrying = base.status_retrying and not self._retry_flag_true(
                bound
            )
            return WrapperInfo(
                cls=cls, name=fn.name, module=self.relpath, params=params,
                method=method, path=path, payload=payload,
                retry_param=False, status_retrying=retrying,
                guard=guard or base.guard, sse=sse or base.sse,
                lineno=fn.lineno,
            )
        return None

    def _self_wrapper(self, cls: str, call: ast.Call) -> Optional[WrapperInfo]:
        f = call.func
        if (
            isinstance(f, ast.Attribute)
            and isinstance(f.value, ast.Name)
            and f.value.id == "self"
        ):
            return self.wrappers.get((cls, f.attr))
        return None

    @staticmethod
    def _retry_flag_true(bound: Dict[str, ast.AST]) -> bool:
        v = bound.get("retry_connection_only")
        return (
            isinstance(v, ast.Constant) and v.value is True
        )

    def _forward(
        self, slot: object, bound: Dict[str, ast.AST], params: Sequence[str]
    ) -> object:
        """Resolve a wrapper slot through one forwarding level: constant
        stays, a param slot takes the call-site arg (constant -> value,
        enclosing param -> new param slot, else unresolved None)."""
        if not (isinstance(slot, tuple) and slot and slot[0] == "param"):
            return slot
        arg = bound.get(slot[1])
        if arg is None:
            return None
        s = _const_str(arg)
        if s is not None:
            return s
        if isinstance(arg, ast.Name) and arg.id in params:
            return ("param", arg.id)
        return None

    def _forward_payload(
        self,
        slot: object,
        bound: Dict[str, ast.AST],
        params: Sequence[str],
        fn: ast.AST,
    ) -> object:
        if not (isinstance(slot, tuple) and slot and slot[0] == "param"):
            return slot
        arg = bound.get(slot[1])
        if arg is None:
            return {}
        if isinstance(arg, ast.Name) and arg.id in params:
            return ("param", arg.id)
        return _resolve_payload_expr(arg, fn, params)

    # ---- receiver typing ----

    def _receiver_class(
        self, recv: ast.AST, fn: ast.AST
    ) -> Optional[str]:
        """Conservative client-class typing of a call receiver:
        annotated params (walking out through closures), ``CLS(...)``
        constructor assignments, ``async with CLS(...) as x``, and
        ``self.attr`` assigned from any of those inside the class."""
        if isinstance(recv, ast.Name):
            ann = self.idx.enclosing_params(fn).get(recv.id, _UNRESOLVED)
            if ann is not _UNRESOLVED:
                name = _ann_class_name(ann)
                if name in self.client_classes:
                    return name
            scope: Optional[ast.AST] = fn
            while scope is not None:
                name = self._bound_class_in(scope, recv.id)
                if name is not None:
                    return name
                scope = self.idx.parent_fn.get(id(scope))
            return None
        if (
            isinstance(recv, ast.Attribute)
            and isinstance(recv.value, ast.Name)
            and recv.value.id == "self"
        ):
            cls = self._class_of(fn)
            if cls is None:
                return None
            for m in self.idx.methods.get(cls, {}).values():
                for node in _walk_fn(m):
                    if not (
                        isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Attribute)
                        and isinstance(node.targets[0].value, ast.Name)
                        and node.targets[0].value.id == "self"
                        and node.targets[0].attr == recv.attr
                    ):
                        continue
                    name = self._value_class(node.value, m)
                    if name is not None:
                        return name
        return None

    def _class_of(self, fn: ast.AST) -> Optional[str]:
        for cname, methods in self.idx.methods.items():
            if methods.get(getattr(fn, "name", "")) is fn:
                return cname
        return None

    def _bound_class_in(self, scope: ast.AST, var: str) -> Optional[str]:
        for node in _walk_fn(scope):
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == var
            ):
                name = self._value_class(node.value, scope)
                if name is not None:
                    return name
            if isinstance(node, (ast.AsyncWith, ast.With)):
                for item in node.items:
                    if (
                        isinstance(item.optional_vars, ast.Name)
                        and item.optional_vars.id == var
                    ):
                        name = self._value_class(item.context_expr, scope)
                        if name is not None:
                            return name
        return None

    def _value_class(self, value: ast.AST, fn: ast.AST) -> Optional[str]:
        value = _unwrap_await(value)
        if isinstance(value, ast.BoolOp):
            for v in value.values:
                name = self._value_class(v, fn)
                if name is not None:
                    return name
            return None
        if isinstance(value, ast.Call):
            name = (_dotted(value.func) or "").rsplit(".", 1)[-1]
            if name in self.client_classes:
                return name
        if isinstance(value, ast.Name):
            ann = self.idx.enclosing_params(fn).get(value.id, _UNRESOLVED)
            if ann is not _UNRESOLVED:
                name = _ann_class_name(ann)
                if name in self.client_classes:
                    return name
        return None

    # ---- call-site collection ----

    def collect_calls(self, lines: Sequence[str]) -> List[ClientCall]:
        out: List[ClientCall] = []
        for cls, fn in self._all_functions():
            out.extend(self._calls_in_fn(cls, fn, lines))
        return out

    def _calls_in_fn(
        self, cls: Optional[str], fn: ast.AST, lines: Sequence[str]
    ) -> List[ClientCall]:
        out: List[ClientCall] = []
        params = _fn_params(fn)
        for node in _walk_fn(fn):
            if not isinstance(node, ast.Call):
                continue
            made = self._direct_site(node, fn, params)
            if made is None:
                made = self._wrapper_site(cls, node, fn, params)
            if made is None:
                continue
            for call in made:
                self._associate_reads(call, node, fn)
                self._associate_status(call, node, fn)
                out.append(call)
        return out

    def _direct_site(
        self, node: ast.Call, fn: ast.AST, params: Sequence[str]
    ) -> Optional[List[ClientCall]]:
        direct = _direct_http_call(node)
        if direct is None:
            return None
        method_raw, url, json_expr, splat = direct
        splat_opaque = False
        if json_expr is None and splat is not None:
            json_expr = _resolve_kwargs_json(splat, fn)
            splat_opaque = json_expr is None
        if isinstance(method_raw, str):
            method = method_raw
        elif isinstance(method_raw, ast.Constant):
            method = str(method_raw.value)
        else:
            return []  # dynamic method: degrade
        path = _resolve_url(url, params, self.idx, fn)
        if not isinstance(path, str):
            return []  # param/dynamic path: wrapper machinery owns it
        payload = (
            _UNRESOLVED
            if splat_opaque
            else _resolve_payload_expr(json_expr, fn, params)
        )
        return [
            ClientCall(
                module=self.relpath, lineno=node.lineno, method=method,
                path=path, via=f"session.{method.lower()}",
                payload=payload if isinstance(payload, dict) else None,
            )
        ]

    def _wrapper_site(
        self,
        cls: Optional[str],
        node: ast.Call,
        fn: ast.AST,
        params: Sequence[str],
    ) -> Optional[List[ClientCall]]:
        f = node.func
        if not isinstance(f, ast.Attribute):
            return None
        w: Optional[WrapperInfo] = None
        if (
            isinstance(f.value, ast.Name)
            and f.value.id == "self"
            and cls is not None
        ):
            w = self.wrappers.get((cls, f.attr))
        else:
            rcls = self._receiver_class(f.value, fn)
            if rcls is not None:
                w = self.wrappers.get((rcls, f.attr))
        if w is None:
            return None
        bound = _bind_call_args(node, w.params)
        method = self._forward(w.method, bound, params)
        path = self._forward(w.path, bound, params)
        payload = self._forward_payload(w.payload, bound, params, fn)
        if isinstance(w.payload, dict) and isinstance(payload, dict):
            # payload baked into the wrapper body: its key linenos point
            # into the wrapper's module, so report at this call site
            payload = {k: node.lineno for k in payload}
        retrying = w.status_retrying and not self._retry_flag_true(bound)
        paths: List[str] = []
        if isinstance(path, str):
            paths = [path]
        elif isinstance(path, tuple):
            return []  # still parameterized at this site: degrade
        else:
            # IfExp with two literal paths resolves as both calls
            slot = w.path
            if isinstance(slot, tuple) and slot and slot[0] == "param":
                arg = bound.get(slot[1])
                if isinstance(arg, ast.IfExp):
                    a, b = _const_str(arg.body), _const_str(arg.orelse)
                    if a is not None and b is not None:
                        paths = [a, b]
            if not paths:
                return []
        if not isinstance(method, str):
            return []  # dynamic method: degrade
        return [
            ClientCall(
                module=self.relpath, lineno=node.lineno, method=method,
                path=p, via=f"{w.cls}.{w.name}",
                payload=payload if isinstance(payload, dict) else None,
                generic_status_guard=w.guard,
                retries_status=retrying,
                sse_wrapper=w.sse,
            )
            for p in paths
        ]

    # ---- read / status association ----

    def _associate_reads(
        self, call: ClientCall, node: ast.Call, fn: ast.AST
    ) -> None:
        sse = call.sse_wrapper
        sink = call.sse_reads if sse else call.reads
        # pass 1: names bound to the call / a gather over it (the AST
        # walk is not source-ordered, so bind before the loop pass)
        gen_names: Set[str] = set()
        for st in _walk_fn(fn):
            if (
                isinstance(st, ast.Assign)
                and len(st.targets) == 1
                and isinstance(st.targets[0], ast.Name)
                and self._contains(st.value, node)
            ):
                tgt = st.targets[0].id
                val = _unwrap_await(st.value)
                if val is node and sse:
                    gen_names.add(tgt)
                elif val is node:
                    self._collect_var_reads(fn, tgt, sink)
                elif self._is_gather_of(val, node):
                    self._gather_reads(fn, tgt, sink)
        for st in _walk_fn(fn):
            if isinstance(st, ast.AsyncFor):
                it = st.iter
                if it is node or (
                    isinstance(it, ast.Name) and it.id in gen_names
                ):
                    if isinstance(st.target, ast.Name):
                        self._collect_var_reads(
                            st, st.target.id, sink, include_self=True
                        )
            # async with session.post(...) as resp: -> resp.json() var
            if isinstance(st, (ast.AsyncWith, ast.With)):
                for item in st.items:
                    if item.context_expr is node and isinstance(
                        item.optional_vars, ast.Name
                    ):
                        self._resp_obj_reads(
                            st, item.optional_vars.id, call
                        )

    def _contains(self, tree: ast.AST, target: ast.AST) -> bool:
        return any(n is target for n in ast.walk(tree))

    def _is_gather_of(self, val: ast.AST, node: ast.Call) -> bool:
        if not (
            isinstance(val, ast.Call)
            and (_dotted(val.func) or "").endswith("gather")
        ):
            return False
        return self._contains(val, node)

    def _gather_reads(
        self, fn: ast.AST, coll: str, sink: Dict[str, int]
    ) -> None:
        """``results = await gather(*(c.metrics(u) for u in ...))`` then
        ``for u, r in zip(urls, results): r.get("k")`` — bind the zip/
        direct loop element and collect its reads inside the loop."""
        for st in _walk_fn(fn):
            if not isinstance(st, (ast.For, ast.AsyncFor)):
                continue
            it = st.iter
            elem: Optional[str] = None
            if isinstance(it, ast.Name) and it.id == coll:
                if isinstance(st.target, ast.Name):
                    elem = st.target.id
            elif (
                isinstance(it, ast.Call)
                and (_dotted(it.func) or "").endswith("zip")
                and isinstance(st.target, ast.Tuple)
            ):
                for i, a in enumerate(it.args):
                    if (
                        isinstance(a, ast.Name)
                        and a.id == coll
                        and i < len(st.target.elts)
                        and isinstance(st.target.elts[i], ast.Name)
                    ):
                        elem = st.target.elts[i].id
            if elem is not None:
                self._collect_var_reads(st, elem, sink, include_self=True)

    def _collect_var_reads(
        self,
        scope: ast.AST,
        var: str,
        sink: Dict[str, int],
        include_self: bool = False,
    ) -> None:
        nodes = (
            ast.walk(scope) if include_self else _walk_fn(scope)
        )
        for n in nodes:
            if (
                isinstance(n, ast.Subscript)
                and isinstance(n.value, ast.Name)
                and n.value.id == var
            ):
                k = _const_str(n.slice)
                if k is not None:
                    sink.setdefault(k, n.lineno)
            if (
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr == "get"
                and isinstance(n.func.value, ast.Name)
                and n.func.value.id == var
                and n.args
            ):
                k = _const_str(n.args[0])
                if k is not None:
                    sink.setdefault(k, n.lineno)

    def _resp_obj_reads(
        self, with_node: ast.AST, resp: str, call: ClientCall
    ) -> None:
        for n in ast.walk(with_node):
            if (
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr == "raise_for_status"
                and isinstance(n.func.value, ast.Name)
                and n.func.value.id == resp
            ):
                call.generic_status_guard = True
            if (
                isinstance(n, ast.Assign)
                and len(n.targets) == 1
                and isinstance(n.targets[0], ast.Name)
            ):
                val = _unwrap_await(n.value)
                if (
                    isinstance(val, ast.Call)
                    and isinstance(val.func, ast.Attribute)
                    and val.func.attr == "json"
                    and isinstance(val.func.value, ast.Name)
                    and val.func.value.id == resp
                ):
                    # reads happen in the enclosing function after the
                    # with-block too; collect across the whole function
                    fn = self._enclosing_fn(with_node)
                    self._collect_var_reads(
                        fn if fn is not None else with_node,
                        n.targets[0].id, call.reads, include_self=True,
                    )
            if isinstance(n, ast.Compare) and len(n.ops) == 1:
                left = n.left
                if (
                    isinstance(left, ast.Attribute)
                    and left.attr == "status"
                    and isinstance(left.value, ast.Name)
                    and left.value.id == resp
                ):
                    self._take_status_compare(n, call)

    def _enclosing_fn(self, node: ast.AST) -> Optional[ast.AST]:
        for _cls, fn in self._all_functions():
            if any(n is node for n in ast.walk(fn)):
                return fn
        return None

    def _take_status_compare(self, cmp: ast.Compare, call: ClientCall) -> None:
        op, right = cmp.ops[0], cmp.comparators[0]
        if isinstance(op, (ast.Eq, ast.NotEq)):
            if isinstance(right, ast.Constant) and isinstance(
                right.value, int
            ):
                call.status_branches.setdefault(right.value, cmp.lineno)
        elif isinstance(op, (ast.Lt, ast.LtE, ast.Gt, ast.GtE)):
            call.generic_status_guard = True
        elif isinstance(op, (ast.In, ast.NotIn)):
            if isinstance(right, (ast.Tuple, ast.List, ast.Set)):
                for el in right.elts:
                    if isinstance(el, ast.Constant) and isinstance(
                        el.value, int
                    ):
                        call.status_branches.setdefault(el.value, cmp.lineno)

    def _associate_status(
        self, call: ClientCall, node: ast.Call, fn: ast.AST
    ) -> None:
        """``except ... as e: e.status == N`` branches in any try that
        encloses the call site."""
        for st in _walk_fn(fn):
            if not isinstance(st, ast.Try):
                continue
            if not any(
                self._contains(body_stmt, node) for body_stmt in st.body
            ):
                continue
            for h in st.handlers:
                if h.name is None:
                    continue
                call.generic_status_guard = True
                for n in ast.walk(h):
                    if (
                        isinstance(n, ast.Compare)
                        and len(n.ops) == 1
                        and isinstance(n.left, ast.Attribute)
                        and n.left.attr == "status"
                        and isinstance(n.left.value, ast.Name)
                        and n.left.value.id == h.name
                    ):
                        self._take_status_compare(n, call)


# --------------------------------------------------------------------- #
# Assembly
# --------------------------------------------------------------------- #


def parse_client_modules(
    modules: Dict[str, Tuple[ast.Module, str]]
) -> List[ClientCall]:
    """Client call sites across ``{relpath: (tree, src)}``. Wrapper
    discovery runs to fixpoint across ALL modules first (a wrapper class
    defined in one module is typed at its use sites in others)."""
    wrappers: Dict[Tuple[str, str], WrapperInfo] = {}
    scans = [
        _ClientScan(rel, tree, src, wrappers)
        for rel, (tree, src) in sorted(modules.items())
    ]
    for _round in range(4):
        changed = False
        for s in scans:
            s.client_classes = {c for (c, _n) in wrappers} | s.client_classes
            changed = s.discover_wrappers() or changed
        if not changed:
            break
    calls: List[ClientCall] = []
    for s in scans:
        s.client_classes = {c for (c, _n) in wrappers}
        calls.extend(s.collect_calls(s.idx.lines))
    return calls


def build_model(
    spec: WireSpec, modules: Dict[str, Tuple[ast.Module, str]]
) -> WireModel:
    """Assemble the wire model from the SCANNED module set (``modules``
    maps repo-relative posix paths to parsed trees). Modules the spec
    declares but the scan does not include leave ``servers_present`` /
    ``clients_present`` False — rules needing the full surface degrade."""
    endpoints: Dict[Tuple[str, str], List[Endpoint]] = {}
    for rel in spec.servers:
        if rel not in modules:
            continue
        tree, src = modules[rel]
        for ep in parse_server_module(rel, tree, src):
            endpoints.setdefault((ep.method, ep.path), []).append(ep)
    client_modules = {
        rel: modules[rel] for rel in spec.clients if rel in modules
    }
    calls = parse_client_modules(client_modules)
    return WireModel(
        spec=spec,
        endpoints=endpoints,
        calls=calls,
        servers_present=all(rel in modules for rel in spec.servers),
        clients_present=all(rel in modules for rel in spec.clients),
    )
