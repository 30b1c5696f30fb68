"""graftlint: repo-invariant static analysis + compiled-artifact contract
helpers (DESIGN.md §24).

Eighteen rounds of hardening produced a set of invariants this stack's
performance and correctness rest on — zero host syncs in the step loop,
donated buffers never touched after dispatch, f32 accumulation on every
adapter matmul, every emitted event present in EVENT_SCHEMA, lock
discipline in the threaded host subsystems. Until this module they were
enforced by scattered one-off pins (a jaxpr grep here, a source-regex
scan there), so every new module re-derived or silently skipped them.
This module makes them MECHANICAL:

  - an AST lint engine with a rule registry (`RULES`), per-line
    `# graftlint: disable=<rule>(<reason>)` suppressions, and a
    machine-readable finding model — driven by `tools/graft_lint.py`
    (text/JSON output, bench_compare-style exit codes, runs as a tier-1
    test over the whole package);
  - compiled-artifact helpers (`jaxpr_*`, `hlo_*`) that consolidate the
    hand-rolled jaxpr/HLO greps from tests/test_lora.py,
    test_lora_fused.py, test_telemetry.py behind one API — also the
    substrate of `tools/check_compiled_contracts.py`, which lowers
    representative train/decode/multitenant programs and pins retrace
    counts, a collective census, donation, and named-scope spans.

The lint half imports ONLY the stdlib (ast/tokenize/re) — linting must
never initialize a jax backend. The artifact helpers import jax lazily
inside each function.

Suppression grammar (one comment suppresses its own line; a comment
alone on a line suppresses the next line — for calls whose expression
spans lines, anchor the comment on the line the finding names):

    x = float(loss)  # graftlint: disable=sync-hazard(flush boundary)
    # graftlint: disable=sync-hazard(flush boundary),donation-hazard(why)

Every suppression must name a shipped rule AND carry a non-empty reason
— a bare `disable=<rule>` or an unknown rule name is itself a finding
(`bad-suppression`), so silent drift of the suppression inventory is
impossible.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import os
import re
import tokenize
from typing import (Any, Callable, Dict, Iterable, Iterator, List, Optional,
                    Sequence, Set, Tuple)

# ---------------------------------------------------------------------------
# configuration: which invariant applies where (paths are suffix-matched
# against the scanned file's repo-relative posix path)
# ---------------------------------------------------------------------------

#: modules whose code runs on (or is reachable from) the train/decode
#: step loop: a host sync here stalls the device pipeline. models/ and
#: ops/ are traced code — they must never be ABLE to sync.
STEP_LOOP_MODULES: Tuple[str, ...] = (
    "mobilefinetuner_tpu/train/trainer.py",
    "mobilefinetuner_tpu/serve/engine.py",
    "mobilefinetuner_tpu/multitenant/engine.py",
    "mobilefinetuner_tpu/cli/common.py",
    "mobilefinetuner_tpu/models/",
    "mobilefinetuner_tpu/ops/",
)

#: modules whose matmul/einsum chains feed training math: every
#: kwarg-capable contraction must pin its accumulation dtype. The infix
#: `@` operator is exempt BY DESIGN — it is the base-model forward's
#: compute-dtype path (bf16 base matmuls are intended); adapter/loss
#: math that needs f32 accumulation must use the kwarg-capable
#: spellings (jnp.einsum/matmul/dot/tensordot, lax.dot_general).
DTYPE_ACCUM_MODULES: Tuple[str, ...] = (
    "mobilefinetuner_tpu/models/",
    "mobilefinetuner_tpu/ops/",
)

#: the threaded host subsystems: each must DECLARE its cross-thread
#: shared state in a module-level GRAFT_SHARED_STATE literal, and every
#: declared guarded field must be touched only under its declared lock.
THREADED_MODULES: Tuple[str, ...] = (
    "mobilefinetuner_tpu/data/prefetch.py",
    "mobilefinetuner_tpu/io/async_ckpt.py",
    "mobilefinetuner_tpu/core/metrics_http.py",
    "mobilefinetuner_tpu/serve/engine.py",
    "mobilefinetuner_tpu/multitenant/engine.py",
    "tools/serve_router.py",
)

#: the zero-sync structural pin (was test_observability's source grep):
#: "never" = no jax import anywhere, even lazy; "toplevel" = module
#: level must stay jax-free (lazy in-function imports allowed).
NO_JAX_MODULES: Dict[str, str] = {
    "mobilefinetuner_tpu/core/metrics_http.py": "never",
    "mobilefinetuner_tpu/core/trace.py": "toplevel",
    "mobilefinetuner_tpu/core/telemetry.py": "toplevel",
}

#: step builders whose returned callable donates these positional args
#: (jax.jit(..., donate_argnums=...) calls are detected from their own
#: literal donate_argnums)
DONATING_BUILDERS: Dict[str, Tuple[int, ...]] = {
    "make_train_step": (0, 2),
    "make_multi_train_step": (0, 2),
}

#: modules scanned for serve-taxonomy phase=/reason= literals
SERVE_TAXONOMY_MODULES: Tuple[str, ...] = (
    "mobilefinetuner_tpu/serve/engine.py",
    "tools/serve_bench.py",
)


# ---------------------------------------------------------------------------
# finding + suppression model
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Finding:
    rule: str
    path: str          # repo-relative posix path
    line: int          # 1-based
    col: int           # 0-based
    message: str
    suppressed: bool = False
    reason: str = ""   # the suppression's reason when suppressed

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def render(self) -> str:
        tail = f"  [suppressed: {self.reason}]" if self.suppressed else ""
        return (f"{self.path}:{self.line}:{self.col}: "
                f"{self.rule}: {self.message}{tail}")


_SUPPRESS_RE = re.compile(r"graftlint:\s*disable=(.*)$")
_ITEM_RE = re.compile(r"\s*([a-z][a-z0-9-]*)\s*(?:\(([^()]*)\))?\s*$")


def _split_items(spec: str) -> List[str]:
    """Split `rule1(reason, with commas),rule2(...)` on TOP-LEVEL commas
    only — reasons are prose and may contain commas."""
    items, depth, cur = [], 0, []
    for ch in spec:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth = max(depth - 1, 0)
        if ch == "," and depth == 0:
            items.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if "".join(cur).strip():
        items.append("".join(cur))
    return items


def parse_suppressions(source: str, path: str
                       ) -> Tuple[Dict[int, Dict[str, str]], List[Finding]]:
    """-> ({line: {rule: reason}}, malformed-suppression findings).

    A comment on a code line covers that line; a comment alone on its
    line covers the NEXT line. Missing reason / unparseable item =>
    `bad-suppression` finding (never silently honored)."""
    by_line: Dict[int, Dict[str, str]] = {}
    bad: List[Finding] = []
    try:
        toks = list(tokenize.generate_tokens(io.StringIO(source).readline))
    except (tokenize.TokenError, SyntaxError, IndentationError):
        return by_line, bad
    lines = source.splitlines()
    for tok in toks:
        if tok.type != tokenize.COMMENT:
            continue
        m = _SUPPRESS_RE.search(tok.string)
        if not m:
            continue
        lineno = tok.start[0]
        src_line = lines[lineno - 1] if lineno <= len(lines) else ""
        standalone = src_line.strip().startswith("#")
        target = lineno + 1 if standalone else lineno
        entry = by_line.setdefault(target, {})
        for item in _split_items(m.group(1)):
            im = _ITEM_RE.match(item)
            if not im or im.group(2) is None or not im.group(2).strip():
                bad.append(Finding(
                    "bad-suppression", path, lineno, tok.start[1],
                    f"malformed suppression {item.strip()!r}: grammar is "
                    f"disable=<rule>(<reason>), reason required"))
                continue
            name, reason = im.group(1), im.group(2).strip()
            if name not in RULES and name != "bad-suppression":
                bad.append(Finding(
                    "bad-suppression", path, lineno, tok.start[1],
                    f"suppression names unknown rule {name!r} "
                    f"(shipped: {', '.join(sorted(RULES))})"))
                continue
            entry[name] = reason
    return by_line, bad


# ---------------------------------------------------------------------------
# module / project model
# ---------------------------------------------------------------------------

class LintError(Exception):
    """Engine-level failure (unreadable path, syntax error): graft_lint
    exits 1 on these, distinct from findings (exit 2)."""


class Module:
    """One parsed source file + its suppression table."""

    def __init__(self, abspath: str, relpath: str):
        self.abspath = abspath
        self.relpath = relpath.replace(os.sep, "/")
        with open(abspath, encoding="utf-8") as f:
            self.source = f.read()
        try:
            self.tree = ast.parse(self.source, filename=relpath)
        except SyntaxError as e:
            raise LintError(f"{relpath}: syntax error: {e}") from e
        self.suppressions, self.bad_suppressions = parse_suppressions(
            self.source, self.relpath)

    def matches(self, suffixes: Iterable[str]) -> bool:
        return any(self.relpath.endswith(s) or
                   (s.endswith("/") and s.rstrip("/") + "/" in
                    "/" + self.relpath)
                   for s in suffixes)


class Project:
    """The scanned file set. `modules` are the files named on the CLI
    (fully linted); `aux_modules` are the sibling `tools/` sources that
    cross-file rules (emit-schema, serve-taxonomy) must see even when
    only the package directory was passed."""

    def __init__(self, paths: Sequence[str]):
        self.modules: List[Module] = []
        seen: Set[str] = set()
        roots: Set[str] = set()
        for p in paths:
            p = os.path.abspath(p)
            if os.path.isdir(p):
                files = sorted(
                    os.path.join(dp, f)
                    for dp, dns, fns in os.walk(p)
                    if "__pycache__" not in dp
                    for f in fns if f.endswith(".py"))
            elif os.path.isfile(p):
                files = [p]
            else:
                raise LintError(f"no such path: {p}")
            for f in files:
                if f not in seen:
                    seen.add(f)
                    self.modules.append(Module(f, self._rel(f)))
            roots.add(self._repo_root(p))
        self._seen = seen
        self.repo_root = sorted(roots)[0] if roots else os.getcwd()
        self.aux_modules: List[Module] = []
        tools = os.path.join(self.repo_root, "tools")
        if os.path.isdir(tools):
            for f in sorted(os.listdir(tools)):
                full = os.path.join(tools, f)
                if f.endswith(".py") and full not in seen:
                    try:
                        self.aux_modules.append(Module(full, self._rel(full)))
                    except LintError:
                        pass  # aux files never fail the run structurally

    @staticmethod
    def _repo_root(path: str) -> str:
        """Walk up to the directory that CONTAINS mobilefinetuner_tpu."""
        d = path if os.path.isdir(path) else os.path.dirname(path)
        while True:
            if os.path.isdir(os.path.join(d, "mobilefinetuner_tpu")):
                return d
            parent = os.path.dirname(d)
            if parent == d:
                return os.path.dirname(path) or os.getcwd()
            d = parent

    def _rel(self, abspath: str) -> str:
        root = self._repo_root(abspath)
        return os.path.relpath(abspath, root).replace(os.sep, "/")

    def all_modules(self) -> List[Module]:
        return self.modules + self.aux_modules


# ---------------------------------------------------------------------------
# small AST helpers
# ---------------------------------------------------------------------------

def dotted_name(node: ast.AST) -> Optional[str]:
    """'jnp.einsum' for Attribute chains over Names; None otherwise."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def base_name(node: ast.AST) -> Optional[str]:
    """The root Name of an Attribute/Subscript/Call chain."""
    while isinstance(node, (ast.Attribute, ast.Subscript, ast.Starred)):
        node = node.value
    if isinstance(node, ast.Call):
        return base_name(node.func)
    if isinstance(node, ast.Name):
        return node.id
    return None


def _kwarg(call: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _target_names(target: ast.expr) -> List[str]:
    if isinstance(target, ast.Name):
        return [target.id]
    if isinstance(target, (ast.Tuple, ast.List)):
        out: List[str] = []
        for elt in target.elts:
            out.extend(_target_names(elt))
        return out
    if isinstance(target, ast.Starred):
        return _target_names(target.value)
    return []


def _function_nodes(tree: ast.AST) -> List[ast.AST]:
    return [n for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]


# ---------------------------------------------------------------------------
# host-dataflow classification (sync-hazard's false-positive filter)
# ---------------------------------------------------------------------------

_HOST_BUILTINS = {"float", "int", "len", "round", "bool", "str", "repr",
                  "abs", "format"}
# builtins whose result is host iff every argument is host (sum() of
# DEVICE arrays is a device scalar, so these are conditional)
_HOST_IF_ARGS = {"sum", "min", "max", "sorted", "any", "all", "list",
                 "tuple", "dict", "set", "zip", "enumerate"}
_HOST_ROOTS = {"np", "numpy", "os", "time", "math", "json", "re",
               "statistics", "collections", "dataclasses", "itertools"}
_STATIC_ATTRS = {"shape", "ndim", "size", "dtype", "itemsize", "nbytes"}
_HOST_METHODS = {"item", "tolist", "keys", "values", "items", "qsize",
                 "split", "strip", "join", "get_nowait"}


def _is_host_expr(node: ast.AST, host: Set[str]) -> bool:
    """True when `node` is statically known to produce HOST data (so a
    float()/np.asarray over it cannot be a device sync). Conservative:
    unknown => False (flag it; an intentional sync gets a suppression)."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Name):
        return node.id in host
    if isinstance(node, ast.Attribute):
        if node.attr in _STATIC_ATTRS:
            return True
        return _is_host_expr(node.value, host)
    if isinstance(node, ast.Subscript):
        return _is_host_expr(node.value, host)
    if isinstance(node, ast.Call):
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id in _HOST_BUILTINS:
            return True
        if isinstance(fn, ast.Name) and fn.id in _HOST_IF_ARGS:
            return bool(node.args) and all(
                _is_host_expr(a, host) for a in node.args)
        d = dotted_name(fn)
        if d:
            root = d.split(".")[0]
            if root in _HOST_ROOTS:
                return True
            if d.endswith("device_get"):
                return True
        if isinstance(fn, ast.Attribute):
            if fn.attr in _HOST_METHODS:
                return True
            if _is_host_expr(fn.value, host):
                return True  # method on a host object stays host
        return False
    if isinstance(node, (ast.BinOp,)):
        return _is_host_expr(node.left, host) and \
            _is_host_expr(node.right, host)
    if isinstance(node, ast.UnaryOp):
        return _is_host_expr(node.operand, host)
    if isinstance(node, ast.BoolOp):
        return all(_is_host_expr(v, host) for v in node.values)
    if isinstance(node, ast.Compare):
        return _is_host_expr(node.left, host) and \
            all(_is_host_expr(c, host) for c in node.comparators)
    if isinstance(node, ast.IfExp):
        return _is_host_expr(node.body, host) and \
            _is_host_expr(node.orelse, host)
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        return all(_is_host_expr(e, host) for e in node.elts)
    if isinstance(node, (ast.JoinedStr, ast.FormattedValue)):
        return True
    if isinstance(node, (ast.GeneratorExp, ast.ListComp, ast.SetComp)):
        return _is_host_expr(node.elt, host)
    return False


def _collect_host_names(fn: ast.AST) -> Set[str]:
    """Names inside `fn` bound from host-producing expressions (a few
    fixpoint passes so chains like `h = device_get(x); v = h[0]`
    propagate)."""
    host: Set[str] = set()
    for _ in range(3):
        before = len(host)
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                if _is_host_expr(node.value, host):
                    for t in node.targets:
                        host.update(_target_names(t))
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                if _is_host_expr(node.value, host):
                    host.update(_target_names(node.target))
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                it = node.iter
                host_iter = _is_host_expr(it, host)
                if isinstance(it, ast.Call):
                    d = dotted_name(it.func)
                    if d in ("zip", "enumerate", "range", "sorted",
                             "reversed"):
                        host_iter = host_iter or any(
                            _is_host_expr(a, host) for a in it.args)
                if host_iter:
                    host.update(_target_names(node.target))
            elif isinstance(node, ast.comprehension):
                if _is_host_expr(node.iter, host):
                    host.update(_target_names(node.target))
            elif isinstance(node, ast.withitem) and \
                    node.optional_vars is not None:
                if _is_host_expr(node.context_expr, host):
                    host.update(_target_names(node.optional_vars))
        if len(host) == before:
            break
    return host


# ---------------------------------------------------------------------------
# rule registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Rule:
    name: str
    doc: str
    fn: Callable[[Project], Iterator[Finding]]


RULES: Dict[str, Rule] = {}


def rule(name: str, doc: str):
    def deco(fn):
        RULES[name] = Rule(name, doc, fn)
        return fn
    return deco


# ---------------------------------------------------------------------------
# rule: sync-hazard
# ---------------------------------------------------------------------------

@rule("sync-hazard",
      "host-sync call (float()/.item()/np.asarray/device_get/"
      "block_until_ready) reachable from a step-loop module")
def _rule_sync_hazard(project: Project) -> Iterator[Finding]:
    for mod in project.modules:
        if not mod.matches(STEP_LOOP_MODULES):
            continue
        funcs = _function_nodes(mod.tree)
        # map each call node to its innermost enclosing function
        owner: Dict[ast.AST, ast.AST] = {}
        for fn in funcs:
            for sub in ast.walk(fn):
                owner[sub] = fn  # innermost wins: funcs walk outer->inner?
        # ensure innermost wins: walk functions by position (outer first),
        # later (inner) assignments overwrite
        host_of: Dict[ast.AST, Set[str]] = {
            fn: _collect_host_names(fn) for fn in funcs}
        # nested defs read closure variables: a name host in an ancestor
        # scope is host in the child (funcs is outer-first, so parents
        # are resolved before children)
        parent: Dict[ast.AST, ast.AST] = {}
        for fn in funcs:
            for sub in ast.walk(fn):
                if sub is not fn and sub in host_of:
                    parent[sub] = fn  # innermost enclosing wins (later
                    #                   overwrites walk outer->inner)
        for fn in funcs:
            p = parent.get(fn)
            if p is not None:
                host_of[fn] = host_of[fn] | host_of[p]
        # `x = np.asarray(x)` must not launder x into the host set for
        # its own check: map every call to the names ITS OWN statement
        # assigns, and ignore those names as host evidence at that site
        self_targets: Dict[int, Set[str]] = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Assign):
                names: Set[str] = set()
                for t in node.targets:
                    names.update(_target_names(t))
                for sub in ast.walk(node.value):
                    if isinstance(sub, ast.Call):
                        self_targets[id(sub)] = names
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            kind = None
            arg = node.args[0] if node.args else None
            fn_expr = node.func
            if isinstance(fn_expr, ast.Name) and fn_expr.id == "float":
                kind = "float()"
            elif isinstance(fn_expr, ast.Attribute):
                d = dotted_name(fn_expr) or ""
                if fn_expr.attr == "item" and not node.args:
                    kind, arg = ".item()", fn_expr.value
                elif d in ("np.asarray", "numpy.asarray"):
                    kind = "np.asarray"
                elif fn_expr.attr == "device_get":
                    kind, arg = "device_get", None
                elif fn_expr.attr == "block_until_ready":
                    kind, arg = "block_until_ready", None
            if kind is None:
                continue
            enclosing = owner.get(node)
            host = host_of.get(enclosing, set()) if enclosing is not None \
                else set()
            host = host - self_targets.get(id(node), set())
            if kind in ("float()", ".item()", "np.asarray") and \
                    arg is not None and _is_host_expr(arg, host):
                continue  # host-side conversion, not a device sync
            if enclosing is None and kind in ("float()", "np.asarray"):
                continue  # module-level constants are host by definition
            yield Finding(
                "sync-hazard", mod.relpath, node.lineno, node.col_offset,
                f"{kind} in step-loop module: forces a device->host sync "
                f"on the hot path (move it behind the buffered-metrics "
                f"flush, or suppress with the reason it is intentional)")


# ---------------------------------------------------------------------------
# rule: donation-hazard
# ---------------------------------------------------------------------------

def _donate_literal(node: ast.expr) -> Optional[Tuple[int, ...]]:
    """Positions from a donate_argnums expression. Conditional
    spellings like `(2, 3) if donate else ()` (the engines' CPU
    opt-out) contribute the UNION of both branches — on the platform
    where donation is live, those positions are donated."""
    if isinstance(node, ast.IfExp):
        a = _donate_literal(node.body) or ()
        b = _donate_literal(node.orelse) or ()
        return tuple(sorted(set(a) | set(b))) or None
    try:
        val = ast.literal_eval(node)
    except ValueError:
        return None
    if isinstance(val, int):
        return (val,)
    if isinstance(val, (tuple, list)):
        return tuple(int(v) for v in val)
    return None


def _donating_positions(call: ast.Call,
                        donating: Dict[str, Tuple[int, ...]]
                        ) -> Optional[Tuple[int, ...]]:
    """Donated positional-arg indices for a call that BUILDS a step
    (make_train_step / jax.jit(..., donate_argnums=...)), else None."""
    d = dotted_name(call.func)
    leaf = d.split(".")[-1] if d else None
    if leaf in donating:
        dn = _kwarg(call, "donate")
        if dn is not None and isinstance(dn, ast.Constant) and not dn.value:
            return None
        return donating[leaf]
    if d in ("jax.jit", "jit"):
        dn = _kwarg(call, "donate_argnums")
        if dn is None:
            return None
        return _donate_literal(dn)
    return None


def _ref_path(node: ast.AST) -> Optional[str]:
    """A trackable reference path: a bare Name ('pool_k') or a
    self-attribute chain ('self.pool_k'). Anything else — subscripts,
    calls, non-self attributes — is not tracked."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        d = dotted_name(node)
        if d is not None and d.startswith("self."):
            return d
    return None


def _target_paths(target: ast.expr) -> List[str]:
    """_target_names extended with self-attribute targets, for the
    donation rule: `self.pool_k, self.pool_v = ...` rebinds both."""
    p = _ref_path(target)
    if p is not None:
        return [p]
    if isinstance(target, (ast.Tuple, ast.List)):
        out: List[str] = []
        for elt in target.elts:
            out.extend(_target_paths(elt))
        return out
    if isinstance(target, ast.Starred):
        return _target_paths(target.value)
    return []


@rule("donation-hazard",
      "a donated argument's name is referenced after the dispatching "
      "call without rebinding (the buffer no longer exists)")
def _rule_donation_hazard(project: Project) -> Iterator[Finding]:
    for mod in project.modules:
        donating: Dict[str, Tuple[int, ...]] = dict(DONATING_BUILDERS)
        # local builders that RETURN a donating builder's call
        for fn in _function_nodes(mod.tree):
            for node in ast.walk(fn):
                if isinstance(node, ast.Return) and \
                        isinstance(node.value, ast.Call):
                    pos = _donating_positions(node.value, donating)
                    if pos is not None:
                        donating[fn.name] = pos
        # self-attribute step bindings are MODULE-wide: the engines
        # bind `self._step = jax.jit(..., donate_argnums=...)` in a
        # builder method and dispatch from another (serve decode,
        # multitenant admit)
        selfsteps: Dict[str, Tuple[int, ...]] = {}
        for fn in _function_nodes(mod.tree):
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) and \
                        isinstance(node.value, ast.Call):
                    pos = _donating_positions(node.value, donating)
                    if pos is None:
                        continue
                    for t in node.targets:
                        for path in _target_paths(t):
                            if path.startswith("self."):
                                selfsteps[path] = pos
        for fn in _function_nodes(mod.tree):
            yield from _scan_donation_scope(mod, fn, donating, selfsteps)


def _scan_donation_scope(mod: Module, fn: ast.AST,
                         donating: Dict[str, Tuple[int, ...]],
                         selfsteps: Optional[Dict[str, Tuple[int, ...]]]
                         = None) -> Iterator[Finding]:
    """Linear-order scan of one function body: find step-building
    assignments, then dispatching calls, then post-call reads of the
    donated names. Lexical order approximates execution order — good
    enough for the loop-shaped code this repo writes, and the rule's
    fixtures pin exactly that shape. Names are tracked as paths: bare
    locals AND self-attribute chains (`self._step` bindings, donated
    `self.pool_k` args — the serve/multitenant engine pattern)."""
    stepfns: Dict[str, Tuple[int, ...]] = dict(selfsteps or {})
    # pass 1: which local names hold donating callables
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            pos = _donating_positions(node.value, donating)
            call = node.value
            if pos is None:
                # propagate through .lower(...).compile() chains
                f = call.func
                if isinstance(f, ast.Attribute) and \
                        f.attr in ("compile", "lower"):
                    root = base_name(f.value)
                    if root in stepfns:
                        pos = stepfns[root]
            if pos is not None:
                for t in node.targets:
                    for path in _target_paths(t):
                        stepfns[path] = pos
    if not stepfns:
        return
    # pass 2: dispatch sites and post-dispatch reads, in source order.
    # A dispatch's liveness starts at its END line, so the donated
    # args of a multi-line call are not their own post-call reads.
    events: List[Tuple[int, str, Any]] = []  # (line, kind, payload)
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            cpath = _ref_path(node.func)
            if cpath is not None and cpath in stepfns:
                donated = []
                for i in stepfns[cpath]:
                    if i < len(node.args):
                        p = _ref_path(node.args[i])
                        if p is not None:
                            donated.append(p)
                if donated:
                    end = getattr(node, "end_lineno", None) or node.lineno
                    events.append((end, "dispatch", (node, set(donated))))
    if not events:
        return
    # rebindings + reads
    for node in ast.walk(fn):
        path = _ref_path(node)
        if path is None:
            continue
        ctx = getattr(node, "ctx", None)
        if isinstance(ctx, ast.Store):
            events.append((node.lineno, "store", path))
        elif isinstance(ctx, ast.Load):
            events.append((node.lineno, "load", (node, path)))
    events.sort(key=lambda e: (e[0], 0 if e[1] == "dispatch" else 1))
    live: Dict[str, int] = {}  # donated path -> dispatch end line
    reported: Set[Tuple[str, int]] = set()
    for line, kind, payload in events:
        if kind == "dispatch":
            node, names = payload
            # names rebound by the dispatch's own assignment stay valid
            assign_targets: Set[str] = set()
            parent = _assign_parent(fn, node)
            if parent is not None:
                for t in parent.targets:
                    assign_targets.update(_target_paths(t))
            for n in names - assign_targets:
                live[n] = line
        elif kind == "store":
            live.pop(payload, None)
        elif kind == "load":
            name_node, n = payload
            if n in live and name_node.lineno > live[n]:
                key = (n, name_node.lineno)
                if key not in reported:
                    reported.add(key)
                    yield Finding(
                        "donation-hazard", mod.relpath, name_node.lineno,
                        name_node.col_offset,
                        f"{n!r} was donated to the step dispatched at "
                        f"line {live[n]} and is read afterwards without "
                        f"rebinding — the buffer has been consumed")


def _assign_parent(fn: ast.AST, call: ast.Call) -> Optional[ast.Assign]:
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and node.value is call:
            return node
    return None


# ---------------------------------------------------------------------------
# rule: untraced-branch
# ---------------------------------------------------------------------------

def _static_args_of(jit_call: Optional[ast.Call],
                    fn: ast.AST) -> Set[str]:
    """Param names made static by a jit call's static_argnames /
    static_argnums (literal values only)."""
    static: Set[str] = set()
    if jit_call is None:
        return static
    args = fn.args
    ordered = [a.arg for a in args.posonlyargs + args.args]
    sa = _kwarg(jit_call, "static_argnames")
    if sa is not None:
        try:
            v = ast.literal_eval(sa)
            static.update([v] if isinstance(v, str) else v)
        except ValueError:
            pass
    sn = _kwarg(jit_call, "static_argnums")
    if sn is not None:
        try:
            v = ast.literal_eval(sn)
            for i in ([v] if isinstance(v, int) else v):
                if 0 <= i < len(ordered):
                    static.add(ordered[i])
        except ValueError:
            pass
    return static


def _jitted_functions(mod: Module) -> Dict[str, Tuple[ast.AST, Set[str]]]:
    """{name: (FunctionDef, static_param_names)} for functions that are
    jitted: decorated with jax.jit (bare or via partial), or passed by
    name to a jax.jit(...) call in this module."""
    defs = {fn.name: fn for fn in _function_nodes(mod.tree)}
    jitted: Dict[str, Tuple[ast.AST, Set[str]]] = {}
    for name, fn in defs.items():
        for dec in fn.decorator_list:
            d = dotted_name(dec)
            if d in ("jax.jit", "jit"):
                jitted[name] = (fn, set())
            elif isinstance(dec, ast.Call):
                dd = dotted_name(dec.func)
                if dd in ("jax.jit", "jit"):
                    jitted[name] = (fn, _static_args_of(dec, fn))
                elif dd in ("partial", "functools.partial") and dec.args:
                    if dotted_name(dec.args[0]) in ("jax.jit", "jit"):
                        jitted[name] = (fn, _static_args_of(dec, fn))
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) and \
                dotted_name(node.func) in ("jax.jit", "jit") and node.args:
            tgt = node.args[0]
            if isinstance(tgt, ast.Name) and tgt.id in defs:
                jitted[tgt.id] = (defs[tgt.id],
                                  _static_args_of(node, defs[tgt.id]))
    return jitted


def _tracer_names_in_test(test: ast.AST, params: Set[str]) -> List[str]:
    """Parameter names the branch condition reads as VALUES (static
    shape/dtype reads, is-None checks, isinstance, and len() are
    exempt — they are trace-time constants)."""
    hits: List[str] = []

    def visit(node: ast.AST) -> None:
        if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
            return
        if isinstance(node, ast.Call):
            d = dotted_name(node.func)
            if d in ("isinstance", "hasattr", "callable", "len",
                     "getattr"):
                return
        if isinstance(node, ast.Compare) and \
                all(isinstance(op, (ast.Is, ast.IsNot, ast.In, ast.NotIn))
                    for op in node.ops):
            # `x is None` and `"key" in tree` read pytree STRUCTURE —
            # trace-time constants, not tracer values
            return
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load) \
                and node.id in params:
            hits.append(node.id)
            return
        for child in ast.iter_child_nodes(node):
            visit(child)

    visit(test)
    return hits


@rule("untraced-branch",
      "Python `if`/`while` on a tracer-valued expression inside a "
      "jitted function (the branch is taken at TRACE time, silently)")
def _rule_untraced_branch(project: Project) -> Iterator[Finding]:
    for mod in project.modules:
        for name, (fn, static) in _jitted_functions(mod).items():
            args = fn.args
            params = {a.arg for a in
                      args.posonlyargs + args.args + args.kwonlyargs
                      } - static
            for node in ast.walk(fn):
                if isinstance(node, (ast.If, ast.While)):
                    hits = _tracer_names_in_test(node.test, params)
                    if hits:
                        yield Finding(
                            "untraced-branch", mod.relpath, node.lineno,
                            node.col_offset,
                            f"branch on tracer-valued {sorted(set(hits))} "
                            f"inside jitted {name!r}: the Python branch "
                            f"freezes one side at trace time — use "
                            f"jnp.where/lax.cond, or mark the argument "
                            f"static")


# ---------------------------------------------------------------------------
# rule: dtype-accum
# ---------------------------------------------------------------------------

_ACCUM_FUNCS = ("einsum", "matmul", "dot", "tensordot", "dot_general")


@rule("dtype-accum",
      "matmul/einsum in models//ops/ without preferred_element_type "
      "(accumulation dtype silently follows the input dtype)")
def _rule_dtype_accum(project: Project) -> Iterator[Finding]:
    for mod in project.modules:
        if not mod.matches(DTYPE_ACCUM_MODULES):
            continue
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            d = dotted_name(node.func)
            if not d:
                continue
            parts = d.split(".")
            if parts[-1] not in _ACCUM_FUNCS:
                continue
            if parts[0] not in ("jnp", "jax", "lax", "numpy") and \
                    len(parts) > 1:
                continue
            if len(parts) == 1:  # bare einsum(...) — a local helper
                continue
            if parts[0] == "numpy" or parts[0] == "np":
                continue  # host-side numpy math is not device accumulation
            if _kwarg(node, "preferred_element_type") is None:
                yield Finding(
                    "dtype-accum", mod.relpath, node.lineno,
                    node.col_offset,
                    f"{d} without preferred_element_type: on bf16 inputs "
                    f"the accumulator silently degrades to bf16 — pin it "
                    f"(jnp.float32) or suppress with the reason the "
                    f"input dtype is already the accumulation dtype")


# ---------------------------------------------------------------------------
# rule: emit-schema (+ serve-taxonomy)
# ---------------------------------------------------------------------------

def collect_emit_sites(modules: Iterable[Module]
                       ) -> Dict[str, List[Tuple[str, int]]]:
    """{event_name: [(relpath, line), ...]} for every `.emit("x", ...)`
    and `event="x"` literal across the given modules."""
    found: Dict[str, List[Tuple[str, int]]] = {}
    for mod in modules:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "emit" and node.args and \
                    isinstance(node.args[0], ast.Constant) and \
                    isinstance(node.args[0].value, str):
                found.setdefault(node.args[0].value, []).append(
                    (mod.relpath, node.lineno))
            ev = _kwarg(node, "event")
            if ev is not None and isinstance(ev, ast.Constant) and \
                    isinstance(ev.value, str):
                found.setdefault(ev.value, []).append(
                    (mod.relpath, node.lineno))
    return found


def _schema_key_lines(project: Project, const_name: str) -> Dict[str, int]:
    """{key: line} of a dict-literal constant in core/telemetry.py (to
    anchor never-emitted findings at their declaration)."""
    for mod in project.all_modules():
        if not mod.relpath.endswith("core/telemetry.py"):
            continue
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Assign) and \
                    any(isinstance(t, ast.Name) and t.id == const_name
                        for t in node.targets) and \
                    isinstance(node.value, ast.Dict):
                return {k.value: k.lineno for k in node.value.keys
                        if isinstance(k, ast.Constant)}
    return {}


@rule("emit-schema",
      "telemetry emit sites and EVENT_SCHEMA must agree in BOTH "
      "directions (no unknown events emitted, no dead taxonomy)")
def _rule_emit_schema(project: Project) -> Iterator[Finding]:
    from mobilefinetuner_tpu.core.telemetry import EVENT_SCHEMA
    found = collect_emit_sites(project.all_modules())
    key_lines = _schema_key_lines(project, "EVENT_SCHEMA")
    for name, sites in sorted(found.items()):
        if name not in EVENT_SCHEMA:
            path, line = sites[0]
            yield Finding(
                "emit-schema", path, line, 0,
                f"emitted event {name!r} is not declared in EVENT_SCHEMA "
                f"(core/telemetry.py) — every event must land in the "
                f"schema + validator before it ships")
    # the dead-taxonomy direction only makes sense over a scan that
    # includes the schema's home module — a partial lint (one
    # subpackage, a fixture project) must not report every event it
    # happens not to contain as dead
    if not key_lines:
        return
    anchor = "mobilefinetuner_tpu/core/telemetry.py"
    for name in sorted(set(EVENT_SCHEMA) - set(found)):
        yield Finding(
            "emit-schema", anchor, key_lines.get(name, 1), 0,
            f"EVENT_SCHEMA declares {name!r} but no source ever emits it "
            f"(dead taxonomy) — wire the event or drop the entry")


@rule("emit-fields",
      "a literal-kwarg emit site must carry every REQUIRED field of "
      "its event's EVENT_SCHEMA entry (splat sites are validated at "
      "runtime by validate_event; this catches the static half — a "
      "field dropped at the call site would otherwise only surface "
      "when a reader validates the stream)")
def _rule_emit_fields(project: Project) -> Iterator[Finding]:
    from mobilefinetuner_tpu.core.telemetry import (EVENT_SCHEMA,
                                                    OPTIONAL_FIELDS)
    for mod in project.all_modules():
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "emit" and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            ev = node.args[0].value
            if ev not in EVENT_SCHEMA:
                continue  # emit-schema already reports unknown events
            if any(kw.arg is None for kw in node.keywords):
                continue  # **payload splat: runtime validate_event's job
            provided = {kw.arg for kw in node.keywords}
            required = set(EVENT_SCHEMA[ev]) \
                - set(OPTIONAL_FIELDS.get(ev, ()))
            missing = sorted(required - provided)
            if missing:
                yield Finding(
                    "emit-fields", mod.relpath, node.lineno, 0,
                    f"emit({ev!r}) missing required schema field(s) "
                    f"{', '.join(missing)} — EVENT_SCHEMA is a floor; "
                    f"a None must be passed explicitly, not dropped")


_SNAKE = re.compile(r"^[a-z_]+$")


@rule("serve-taxonomy",
      "request lifecycle phase=/reason= literals in the serve layer "
      "must match REQUEST_PHASES/REQUEST_REASONS, both directions")
def _rule_serve_taxonomy(project: Project) -> Iterator[Finding]:
    from mobilefinetuner_tpu.core.telemetry import (REQUEST_PHASES,
                                                    REQUEST_REASONS)
    mods = [m for m in project.all_modules()
            if m.matches(SERVE_TAXONOMY_MODULES)]
    if not mods:
        return
    phases: Dict[str, Tuple[str, int]] = {}
    reasons: Dict[str, Tuple[str, int]] = {}
    for mod in mods:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            for kw, sink in (("phase", phases), ("reason", reasons)):
                v = _kwarg(node, kw)
                if v is not None and isinstance(v, ast.Constant) and \
                        isinstance(v.value, str) and _SNAKE.match(v.value):
                    sink.setdefault(v.value, (mod.relpath, node.lineno))
    anchor = mods[0].relpath
    for name, (path, line) in sorted(phases.items()):
        if name not in REQUEST_PHASES:
            yield Finding("serve-taxonomy", path, line, 0,
                          f"request phase {name!r} not in REQUEST_PHASES")
    for name in sorted(set(REQUEST_PHASES) - set(phases)):
        yield Finding("serve-taxonomy", anchor, 1, 0,
                      f"REQUEST_PHASES declares {name!r} but no serve "
                      f"emit site uses it (dead taxonomy)")
    for name, (path, line) in sorted(reasons.items()):
        if name not in REQUEST_REASONS:
            yield Finding("serve-taxonomy", path, line, 0,
                          f"request reason {name!r} not in REQUEST_REASONS")
    for name in sorted(set(REQUEST_REASONS) - set(reasons)):
        yield Finding("serve-taxonomy", anchor, 1, 0,
                      f"REQUEST_REASONS declares {name!r} but no serve "
                      f"emit site uses it (dead taxonomy)")


# ---------------------------------------------------------------------------
# rule: lock-discipline
# ---------------------------------------------------------------------------

def _shared_state_decl(mod: Module) -> Optional[dict]:
    for node in mod.tree.body:
        if isinstance(node, ast.Assign) and \
                any(isinstance(t, ast.Name) and t.id == "GRAFT_SHARED_STATE"
                    for t in node.targets):
            try:
                val = ast.literal_eval(node.value)
            except ValueError:
                return None
            return val if isinstance(val, dict) else None
    return None


def _self_attr(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and node.value.id == "self":
        return node.attr
    return None


def _scan_lock_method(mod: Module, cls_name: str, method: ast.AST,
                      lock: str, guarded: Set[str], helpers: Set[str]
                      ) -> Iterator[Finding]:
    """Flag guarded-field accesses / locked-helper calls outside
    `with self.<lock>` within one method body."""

    def visit(node: ast.AST, under: bool) -> Iterator[Finding]:
        if isinstance(node, ast.With):
            is_lock = any(
                _self_attr(item.context_expr) == lock
                for item in node.items)
            for item in node.items:
                yield from visit(item.context_expr, under)
            for child in node.body:
                yield from visit(child, under or is_lock)
            return
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            body = node.body if isinstance(node.body, list) else [node.body]
            for child in body:
                yield from visit(child, False)  # closures run later
            return
        attr = _self_attr(node)
        if attr in guarded and not under:
            yield Finding(
                "lock-discipline", mod.relpath, node.lineno,
                node.col_offset,
                f"{cls_name}.{attr} is declared guarded by "
                f"self.{lock} but accessed outside it in "
                f"{getattr(method, 'name', '?')}()")
            return  # one finding per access site
        if isinstance(node, ast.Call):
            fattr = _self_attr(node.func)
            if fattr in helpers and not under:
                yield Finding(
                    "lock-discipline", mod.relpath, node.lineno,
                    node.col_offset,
                    f"{cls_name}.{fattr}() requires self.{lock} held "
                    f"(declared locked helper) but is called outside it "
                    f"in {getattr(method, 'name', '?')}()")
        for child in ast.iter_child_nodes(node):
            yield from visit(child, under)

    for stmt in method.body:
        yield from visit(stmt, False)


@rule("lock-discipline",
      "threaded host subsystems must declare their cross-thread state "
      "(GRAFT_SHARED_STATE) and touch guarded fields only under the "
      "declared lock")
def _rule_lock_discipline(project: Project) -> Iterator[Finding]:
    for mod in project.modules:
        if not mod.matches(THREADED_MODULES):
            continue
        decl = _shared_state_decl(mod)
        if decl is None:
            yield Finding(
                "lock-discipline", mod.relpath, 1, 0,
                "threaded module has no GRAFT_SHARED_STATE declaration "
                "(a literal dict: {class: {'lock': attr|None, 'guarded': "
                "[...], 'locked_helpers': [...], 'channels': [...], "
                "'note': ...}})")
            continue
        classes = {n.name: n for n in ast.walk(mod.tree)
                   if isinstance(n, ast.ClassDef)}
        for cls_name, spec in decl.items():
            if cls_name not in classes:
                yield Finding(
                    "lock-discipline", mod.relpath, 1, 0,
                    f"GRAFT_SHARED_STATE names unknown class {cls_name!r}")
                continue
            if not isinstance(spec, dict):
                yield Finding(
                    "lock-discipline", mod.relpath, 1, 0,
                    f"GRAFT_SHARED_STATE[{cls_name!r}] must be a dict")
                continue
            lock = spec.get("lock")
            guarded = set(spec.get("guarded", ()) or ())
            helpers = set(spec.get("locked_helpers", ()) or ())
            if lock is None:
                if guarded or helpers:
                    yield Finding(
                        "lock-discipline", mod.relpath, 1, 0,
                        f"{cls_name}: guarded fields declared but lock is "
                        f"None — name the lock or move the fields to "
                        f"'channels'")
                continue
            cls = classes[cls_name]
            for method in cls.body:
                if not isinstance(method, (ast.FunctionDef,
                                           ast.AsyncFunctionDef)):
                    continue
                if method.name in ("__init__", "__del__") or \
                        method.name in helpers:
                    continue
                yield from _scan_lock_method(
                    mod, cls_name, method, lock, guarded, helpers)


# ---------------------------------------------------------------------------
# rule: no-jax-import
# ---------------------------------------------------------------------------

def _is_jax_import(node: ast.AST) -> bool:
    if isinstance(node, ast.Import):
        return any(a.name == "jax" or a.name.startswith("jax.")
                   for a in node.names)
    if isinstance(node, ast.ImportFrom):
        m = node.module or ""
        return m == "jax" or m.startswith("jax.")
    return False


@rule("no-jax-import",
      "zero-sync observability modules must not import jax (scrape/emit "
      "paths must be structurally unable to touch a device)")
def _rule_no_jax_import(project: Project) -> Iterator[Finding]:
    for mod in project.modules:
        policy = next((p for s, p in NO_JAX_MODULES.items()
                       if mod.relpath.endswith(s)), None)
        if policy is None:
            continue
        if policy == "never":
            nodes: List[ast.AST] = list(ast.walk(mod.tree))
        else:
            # toplevel = everything that executes at import time: the
            # module body INCLUDING statements nested in try/if/with
            # (the `try: import jax` idiom is still a module-level
            # import, and a class body executes at import time) — only
            # function bodies are deferred
            nodes = []
            stack: List[ast.AST] = list(mod.tree.body)
            while stack:
                n = stack.pop()
                nodes.append(n)
                if not isinstance(n, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Lambda)):
                    stack.extend(ast.iter_child_nodes(n))
        for node in nodes:
            if _is_jax_import(node):
                yield Finding(
                    "no-jax-import", mod.relpath, node.lineno,
                    node.col_offset,
                    f"jax import in a zero-sync module "
                    f"(policy: {policy}) — this code runs on scrape/emit "
                    f"hot paths and must not be able to touch a device")


# ---------------------------------------------------------------------------
# engine entry
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LintResult:
    findings: List[Finding]          # unsuppressed, the exit-2 set
    suppressed: List[Finding]
    files: int
    rules: List[str]

    def to_dict(self) -> dict:
        return {
            "files": self.files,
            "rules": self.rules,
            "counts": {
                "findings": len(self.findings),
                "suppressed": len(self.suppressed),
            },
            "findings": [f.to_dict() for f in self.findings],
            "suppressed": [f.to_dict() for f in self.suppressed],
        }


def run_lint(paths: Sequence[str],
             rules: Optional[Sequence[str]] = None) -> LintResult:
    """Run the selected rules (default: all) over `paths`. Raises
    LintError on unreadable paths / syntax errors."""
    project = Project(paths)
    selected = list(RULES) if rules is None else list(rules)
    unknown = [r for r in selected if r not in RULES]
    if unknown:
        raise LintError(f"unknown rule(s): {', '.join(unknown)} "
                        f"(shipped: {', '.join(sorted(RULES))})")
    supp_by_path = {m.relpath: m.suppressions for m in project.modules}
    findings: List[Finding] = []
    suppressed: List[Finding] = []
    # malformed suppressions are findings themselves (only for primary
    # modules — aux tools files are read for cross-file scans only)
    for mod in project.modules:
        findings.extend(mod.bad_suppressions)
    for name in selected:
        for f in RULES[name].fn(project):
            table = supp_by_path.get(f.path, {})
            reason = table.get(f.line, {}).get(f.rule)
            if reason is not None:
                f.suppressed, f.reason = True, reason
                suppressed.append(f)
            else:
                findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    suppressed.sort(key=lambda f: (f.path, f.line, f.rule))
    return LintResult(findings, suppressed, len(project.modules), selected)


# ===========================================================================
# compiled-artifact helpers: the one API behind the old jaxpr/HLO greps
# (jax imported lazily — the lint half above must stay jax-free)
# ===========================================================================

def _iter_eqns(jaxpr) -> Iterator:
    """All equations of a (Closed)Jaxpr INCLUDING sub-jaxprs (scan/cond
    bodies, custom_vjp call jaxprs, pallas kernels ride in params)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        yield eqn
        for val in eqn.params.values():
            yield from _iter_param_jaxprs(val)


def _iter_param_jaxprs(val) -> Iterator:
    if hasattr(val, "eqns") or hasattr(val, "jaxpr"):
        yield from _iter_eqns(val)
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _iter_param_jaxprs(v)


def jaxpr_primitive_counts(fn, *args, **kwargs) -> Dict[str, int]:
    """{primitive_name: count} over fn's jaxpr, sub-jaxprs included."""
    import jax
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    counts: Dict[str, int] = {}
    for eqn in _iter_eqns(jaxpr):
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
    return counts


def jaxpr_contains(fn, primitive: str, *args, **kwargs) -> bool:
    return jaxpr_primitive_counts(fn, *args, **kwargs).get(primitive, 0) > 0


def jaxpr_dot_census(fn, *args, **kwargs) -> List[dict]:
    """One entry per dot_general in fn's jaxpr (sub-jaxprs included):
    {"preferred_element_type": numpy-dtype-or-None}. The structural spine
    of the f32-accumulation pins (CPU emulates bf16 matmuls in f32, so a
    numeric-only check is vacuous — the jaxpr param is the contract)."""
    import jax
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    out = []
    for eqn in _iter_eqns(jaxpr):
        if eqn.primitive.name == "dot_general":
            out.append({"preferred_element_type":
                        eqn.params.get("preferred_element_type")})
    return out


def assert_dots_accumulate_f32(fn, *args, min_dots: int = 1, **kwargs):
    """Every dot_general in fn's jaxpr must carry
    preferred_element_type=float32; at least `min_dots` must exist."""
    import numpy as np
    dots = jaxpr_dot_census(fn, *args, **kwargs)
    assert len(dots) >= min_dots, \
        f"expected >= {min_dots} dot_general eqns, found {len(dots)}"
    for i, d in enumerate(dots):
        pet = d["preferred_element_type"]
        assert pet is not None and np.dtype(pet) == np.float32, \
            f"dot_general #{i} accumulates in {pet} (want float32)"


_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_ALIAS_RE = re.compile(r"\{[\d,\s]*\}\s*:\s*\(\s*\d+\s*,\s*\{[^}]*\}\s*"
                       r"(?:,\s*(?:may|must)-alias\s*)?\)")
_COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                     "collective-permute", "all-to-all")
# matches the op APPLICATION (`all-gather(...)` / `all-gather-start(`),
# never `-done(` continuations or instruction-NAME references (a name
# like %all-gather.1 is followed by `.1`/`)` — no open paren)
_COLLECTIVE_RE = re.compile(
    r"\b(" + "|".join(_COLLECTIVE_KINDS) + r")(?:-start)?\(")
# the TPU's reduce-scatter fusion: the header of its called computation,
# and a call site
_ARS_HEADER_RE = re.compile(r"^%?all-reduce-scatter[\w.\-]* \(")
_ARS_CALL_RE = re.compile(r"calls=%?all-reduce-scatter[\w.\-]*")


def hlo_named_scopes(hlo_text: str) -> Set[str]:
    """All `/`-path components of op_name metadata in compiled HLO,
    with autodiff transform markers (jvp(...)/transpose(...)) peeled."""
    comps: Set[str] = set()
    for name in _OP_NAME_RE.findall(hlo_text):
        for part in re.split(r"[/()]", name):
            if part:
                comps.add(part)
    return comps


def missing_hlo_scopes(hlo_text: str, scopes: Iterable[str]) -> List[str]:
    """Scopes NOT present as a path component of any op_name. ONE
    matcher for every caller (test_telemetry's wrapper and the
    compiled-contract pins): a scope counts when it is a full
    `/ ( )`-delimited component, so autodiff transform markers —
    `jvp(embed)/...`, `transpose(jvp(mlp))/...` — still match."""
    comps = hlo_named_scopes(hlo_text)
    return [s for s in scopes if s not in comps]


def assert_hlo_scopes(hlo_text: str, scopes: Iterable[str]) -> None:
    missing = missing_hlo_scopes(hlo_text, scopes)
    assert not missing, \
        f"named scopes missing from compiled HLO metadata: {missing}"


def hlo_collective_census(hlo_text: str,
                          scopes: Iterable[str] = ()) -> Dict[str, int]:
    """{collective_kind: count} over compiled HLO text. Async pairs
    (all-gather-start/-done) count ONCE (the -start); the census is the
    pod-bill observable — a GSPMD regression that materializes a
    V-sharded embed all-gather moves a number here.

    The TPU compiler emits a reduce-scatter as a fusion that calls an
    `all-reduce-scatter...` computation holding an all-reduce: the call
    counts as one reduce-scatter, the computation's own ops not at all.
    scopes: count only the collectives whose op_name has one of these
    as a path component (missing_hlo_scopes' match) — e.g. the block's
    `attention` / `mlp`, which only ops inside the layer scan carry."""
    counts = {k: 0 for k in _COLLECTIVE_KINDS}
    want = set(scopes)
    in_ars = False
    for line in hlo_text.splitlines():
        if in_ars or _ARS_HEADER_RE.match(line):
            in_ars = not line.startswith("}")
            continue
        if want and not want & hlo_named_scopes(line):
            continue
        if _ARS_CALL_RE.search(line):
            counts["reduce-scatter"] += 1
            continue
        for m in _COLLECTIVE_RE.finditer(line):
            counts[m.group(1)] += 1
    return counts


def hlo_donated_inputs(hlo_text: str) -> int:
    """Number of input->output alias entries in the compiled module
    header (donation verification: a donating step whose aliasing
    silently vanished doubles its peak HBM). The entry shape
    `{out_idx}: (param, {param_idx}[, may-alias])` only occurs in the
    HloModule header's input_output_alias block, so a global count is
    the block's count."""
    return len(_ALIAS_RE.findall(hlo_text))
