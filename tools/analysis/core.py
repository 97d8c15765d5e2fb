"""Shared machinery for the static passes: package loading, comment
maps, a class/method index with cross-module base resolution, lock
summaries propagated through the intra-package call graph, and the
held-lock-set function walker the lock-order and guarded-by passes
both drive.

Everything is parameterized by an ``AnalysisConfig`` so the fixture
corpus (`tools/analysis/fixtures/`) runs the identical engine against
a miniature registry.
"""
from __future__ import annotations

import ast
import io
import tokenize
from dataclasses import dataclass, field
from pathlib import Path

# make `repro.concurrency` importable when running from tools/
import sys

_SRC = Path(__file__).resolve().parents[2] / "src"
if str(_SRC) not in sys.path:
    sys.path.insert(0, str(_SRC))

from repro import concurrency as conc  # noqa: E402


# --------------------------------------------------------------- findings
@dataclass
class Finding:
    rule: str
    path: str           # repo-relative
    line: int
    func: str           # module.Class.method ('' for file-level)
    message: str
    suppressed: bool = False

    def key(self) -> str:
        return f"{self.rule}:{self.func}:{self.message}"

    def render(self) -> str:
        tag = " [suppressed]" if self.suppressed else ""
        return f"{self.path}:{self.line}: {self.rule} {self.message}" \
               f" (in {self.func or '<module>'}){tag}"


# ----------------------------------------------------------------- config
# Methods whose call is an endpoint/RPC round trip: blocking, and the
# transport may take the queue-pair CV, the work signal, and the async
# client lock underneath.
RPC_BLOCKING_ATTRS = frozenset({
    "call", "call_submit", "call_result",
    "fetch", "fetch_submit", "fetch_result",
})
RPC_IMPLIED_LOCKS = ("queues.cv", "queues._work", "rpcclient._lock")

# Blocking by name: sleeps, joins, event waits.
BLOCKING_ATTRS = frozenset({"sleep", "join", "wait", "wait_for"})
BLOCKING_NAMES = frozenset({"sleep_us"})

SUPPRESS_TOKEN = "lock-order: ok"
UNGUARDED_TOKEN = "unguarded-ok:"
GUARDED_TOKEN = "guarded-by:"
REQUIRES_TOKEN = "requires-lock:"


@dataclass
class AnalysisConfig:
    """Registry + resolution tables one analysis run works against."""

    specs: tuple = conc.LOCK_ORDER
    sanctioned: dict = field(default_factory=lambda: dict(
        conc.SANCTIONED_EDGES))
    same_name_ok: dict = field(default_factory=lambda: dict(
        conc.SAME_NAME_OK))
    never_together: dict = field(default_factory=lambda: dict(
        conc.NEVER_TOGETHER))
    # context-manager methods that hold locks for their caller's body
    with_funcs: dict = field(default_factory=lambda: {
        "_write_gate": ("sharded._maintenance", "sharded._mutate"),
    })
    # `self.<attr>` object types, per module basename — lets the walker
    # resolve `self.store.method()` / `st = self.store; st.method()`
    # calls into the package class index
    attr_types: dict = field(default_factory=lambda: {
        ("endpoint", "store"): ("GraphStore",),
        ("ingest", "store"): ("ShardedGraphStore", "GraphStore"),
        ("supervisor", "store"): ("ShardedGraphStore",),
        ("runtime", "scheduler"): ("BatchScheduler",),
        ("scheduler", "qos"): ("QoSTelemetry",),
    })

    def __post_init__(self):
        self.by_name = {s.name: s for s in self.specs}
        self.site_map = {}
        for s in self.specs:
            for mod, attr in s.sites:
                self.site_map[(mod, attr)] = s

    def resolve_attr(self, module: str, attr: str):
        return self.site_map.get((module, attr))


# ---------------------------------------------------------------- loading
@dataclass
class ModuleInfo:
    path: Path
    rel: str                 # repo-relative path string
    modname: str             # basename stem, e.g. "sharded"
    tree: ast.Module
    source: str
    comments: dict           # line -> comment text (sans '#')

    def comment(self, line: int) -> str:
        return self.comments.get(line, "")


def _comment_map(source: str) -> dict:
    out: dict[int, str] = {}
    try:
        toks = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in toks:
            if tok.type == tokenize.COMMENT:
                out[tok.start[0]] = tok.string.lstrip("#").strip()
    except tokenize.TokenError:
        pass
    return out


def load_package(root: Path, repo_root: Path | None = None
                 ) -> list[ModuleInfo]:
    """Parse every ``.py`` under ``root`` (recursive, skipping caches)."""
    repo_root = repo_root or root
    mods = []
    for p in sorted(root.rglob("*.py")):
        if "__pycache__" in p.parts:
            continue
        src = p.read_text()
        try:
            tree = ast.parse(src, filename=str(p))
        except SyntaxError as e:  # a fixture may be deliberately odd
            raise RuntimeError(f"{p}: unparseable: {e}") from e
        try:
            rel = str(p.relative_to(repo_root))
        except ValueError:
            rel = str(p)
        mods.append(ModuleInfo(p, rel, p.stem, tree, src,
                               _comment_map(src)))
    return mods


# ------------------------------------------------------------ class index
@dataclass
class FuncInfo:
    key: str                 # "modname.Class.method" / "modname.func"
    node: ast.AST            # FunctionDef
    module: ModuleInfo
    cls: str | None          # enclosing class name or None


class PackageIndex:
    """Classes, methods and module functions across the package, with
    base-class resolution by identifier name (cross-module)."""

    def __init__(self, modules: list[ModuleInfo]):
        self.modules = modules
        self.classes: dict[str, dict[str, FuncInfo]] = {}
        self.bases: dict[str, list[str]] = {}
        self.mod_funcs: dict[tuple[str, str], FuncInfo] = {}
        self.functions: dict[str, FuncInfo] = {}
        for m in modules:
            for node in m.tree.body:
                if isinstance(node, ast.ClassDef):
                    meths = self.classes.setdefault(node.name, {})
                    self.bases.setdefault(node.name, [
                        b.id for b in node.bases
                        if isinstance(b, ast.Name)])
                    for sub in node.body:
                        if isinstance(sub, (ast.FunctionDef,
                                            ast.AsyncFunctionDef)):
                            fi = FuncInfo(
                                f"{m.modname}.{node.name}.{sub.name}",
                                sub, m, node.name)
                            meths.setdefault(sub.name, fi)
                            self.functions[fi.key] = fi
                elif isinstance(node, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    fi = FuncInfo(f"{m.modname}.{node.name}", node, m,
                                  None)
                    self.mod_funcs[(m.modname, node.name)] = fi
                    self.functions[fi.key] = fi

    def method(self, cls: str, name: str,
               _seen: frozenset = frozenset()) -> FuncInfo | None:
        if cls in _seen or cls not in self.classes:
            return None
        if name in self.classes[cls]:
            return self.classes[cls][name]
        for b in self.bases.get(cls, ()):
            hit = self.method(b, name, _seen | {cls})
            if hit is not None:
                return hit
        return None


# ---------------------------------------------------------- lock summaries
@dataclass
class FuncSummary:
    acquires: set = field(default_factory=set)   # lock names (transitive)
    blocks: bool = False
    opaque: bool = False                         # may invoke a callback

    def merge(self, other: "FuncSummary") -> bool:
        before = (len(self.acquires), self.blocks, self.opaque)
        self.acquires |= other.acquires
        self.blocks = self.blocks or other.blocks
        self.opaque = self.opaque or other.opaque
        return (len(self.acquires), self.blocks,
                self.opaque) != before


def _attr_chain(node: ast.AST) -> list[str] | None:
    """['self', 'store', '_lock'] for self.store._lock; None if not a
    pure name/attribute chain."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return None


class FunctionWalker:
    """Walks one function body maintaining the held-lock set.

    Subclasses hook ``on_acquire`` / ``on_call`` / ``on_blocking`` /
    ``on_access``; the walker handles with-regions, local lock/object
    aliases, nested function definitions (visited with the held set at
    their *call* sites), and ``# requires-lock:`` seeding.
    """

    def __init__(self, cfg: AnalysisConfig, index: PackageIndex,
                 fi: FuncInfo):
        self.cfg = cfg
        self.index = index
        self.fi = fi
        self.mod = fi.module
        self.aliases: dict[str, list[str]] = {}   # local -> attr chain
        self.nested: dict[str, ast.FunctionDef] = {}
        self.held: list[str] = []

    # hooks -------------------------------------------------------------
    def on_acquire(self, lockname: str, node: ast.AST) -> None: ...

    def on_call(self, target: FuncInfo, node: ast.AST) -> None: ...

    def on_opaque_call(self, desc: str, node: ast.AST) -> None: ...

    def on_blocking(self, desc: str, node: ast.AST) -> None: ...

    def on_access(self, attr: str, is_store: bool,
                  node: ast.AST) -> None: ...

    # resolution --------------------------------------------------------
    def _self_attr(self, node: ast.AST) -> str | None:
        """'x' for self.x, or for a local alias of self.x."""
        chain = _attr_chain(node)
        if chain is None:
            return None
        if chain[0] in self.aliases:
            chain = self.aliases[chain[0]] + chain[1:]
        if len(chain) == 2 and chain[0] == "self":
            return chain[1]
        return None

    def _lock_of(self, node: ast.AST):
        """LockSpec for a with-item / receiver expression, or None."""
        chain = _attr_chain(node)
        if chain is None:
            return None
        if chain[0] in self.aliases:
            chain = self.aliases[chain[0]] + chain[1:]
        # self._mutate  /  self.store._lock — bind by (module, attr)
        return self.cfg.resolve_attr(self.mod.modname, chain[-1])

    def _callee(self, func: ast.AST) -> FuncInfo | None:
        """Resolve a call target into the package index."""
        if isinstance(func, ast.Name):
            if func.id in self.nested:
                return FuncInfo(f"{self.fi.key}.<{func.id}>",
                                self.nested[func.id], self.mod,
                                self.fi.cls)
            return self.index.mod_funcs.get((self.mod.modname, func.id))
        if isinstance(func, ast.Attribute):
            chain = _attr_chain(func)
            if chain is None:
                return None
            if chain[0] in self.aliases:
                chain = self.aliases[chain[0]] + chain[1:]
            if chain[0] != "self":
                return None
            if len(chain) == 2 and self.fi.cls:
                return self.index.method(self.fi.cls, chain[1])
            if len(chain) == 3:
                for cls in self.cfg.attr_types.get(
                        (self.mod.modname, chain[1]), ()):
                    hit = self.index.method(cls, chain[2])
                    if hit is not None:
                        return hit
        return None

    # walking -----------------------------------------------------------
    def run(self) -> None:
        node = self.fi.node
        # `# requires-lock: _attr` on the def line seeds the held set
        for ln in range(node.lineno,
                        node.body[0].lineno if node.body else node.lineno):
            c = self.mod.comment(ln)
            if REQUIRES_TOKEN in c:
                attr = c.split(REQUIRES_TOKEN, 1)[1].strip().split()[0]
                spec = self.cfg.resolve_attr(self.mod.modname, attr)
                if spec is not None:
                    self.held.append(spec.name)
        for sub in ast.walk(node):
            if isinstance(sub, ast.FunctionDef) and sub is not node:
                self.nested[sub.name] = sub
        self._stmts(node.body)

    def _stmts(self, body: list) -> None:
        for st in body:
            self._stmt(st)

    def _stmt(self, st: ast.stmt) -> None:
        if isinstance(st, ast.Assign) and len(st.targets) == 1 \
                and isinstance(st.targets[0], ast.Name):
            chain = _attr_chain(st.value)
            if chain is not None and chain[0] == "self":
                self.aliases[st.targets[0].id] = chain
        if isinstance(st, (ast.With, ast.AsyncWith)):
            self._with(st)
            return
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return                      # visited at call sites
        for name, value in ast.iter_fields(st):
            if name in ("body", "orelse", "finalbody"):
                self._stmts(value)
            elif name == "handlers":
                for h in value:
                    self._stmts(h.body)
            elif isinstance(value, ast.expr):
                self._expr(value)
            elif isinstance(value, list):
                for v in value:
                    if isinstance(v, ast.expr):
                        self._expr(v)

    def _with(self, st: ast.With) -> None:
        pushed = 0
        for item in st.items:
            ce = item.context_expr
            spec = self._lock_of(ce)
            if spec is not None:
                self.on_acquire(spec.name, ce)
                self.held.append(spec.name)
                pushed += 1
                continue
            if isinstance(ce, ast.Call):
                self._expr(ce)
                # `with self._write_gate():` — gate holds for the body
                names = None
                if isinstance(ce.func, ast.Attribute):
                    names = self.cfg.with_funcs.get(ce.func.attr)
                elif isinstance(ce.func, ast.Name):
                    names = self.cfg.with_funcs.get(ce.func.id)
                for nm in names or ():
                    self.on_acquire(nm, ce)
                    self.held.append(nm)
                    pushed += 1
            else:
                self._expr(ce)
        self._stmts(st.body)
        for _ in range(pushed):
            self.held.pop()

    def _expr(self, node: ast.AST) -> None:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                self._call(sub)
            elif isinstance(sub, ast.Attribute) \
                    and isinstance(sub.ctx, (ast.Load, ast.Store)):
                attr = self._self_attr(sub)
                if attr is not None:
                    self.on_access(attr, isinstance(sub.ctx, ast.Store),
                                   sub)

    def _call(self, node: ast.Call) -> None:
        func = node.func
        # blocking by shape: time.sleep / sleep_us / x.join / ev.wait
        if isinstance(func, ast.Name) and func.id in BLOCKING_NAMES:
            self.on_blocking(func.id, node)
        elif isinstance(func, ast.Attribute):
            if func.attr in RPC_BLOCKING_ATTRS:
                self.on_blocking(f"endpoint RPC .{func.attr}()", node)
                for nm in RPC_IMPLIED_LOCKS:
                    if nm in self.cfg.by_name:
                        self.on_acquire(nm, node)
                return
            if func.attr in BLOCKING_ATTRS:
                # waiting on a condition you HOLD releases it — that is
                # the cv protocol, not a blocking call under the lock
                spec = self._lock_of(func.value)
                if not (spec is not None and spec.name in self.held):
                    self.on_blocking(f".{func.attr}()", node)
            # x.acquire() outside a with: treated as an ordering event
            if func.attr == "acquire":
                spec = self._lock_of(func.value)
                if spec is not None:
                    self.on_acquire(spec.name, node)
        target = self._callee(func)
        if target is not None:
            self.on_call(target, node)
            return
        # opaque callback: a local/parameter name holding `self.<attr>`
        # that is not a resolvable method (e.g. a transition hook)
        if isinstance(func, ast.Name) and func.id in self.aliases:
            chain = self.aliases[func.id]
            if len(chain) == 2 and chain[0] == "self":
                self.on_opaque_call(f"callback self.{chain[1]}", node)


def build_summaries(cfg: AnalysisConfig, index: PackageIndex
                    ) -> dict[str, FuncSummary]:
    """Fixed-point lock summaries over the intra-package call graph:
    which locks a call to each function may acquire (transitively) and
    whether it may block."""

    class _Collector(FunctionWalker):
        def __init__(self, cfg, index, fi, summaries):
            super().__init__(cfg, index, fi)
            self.summaries = summaries
            self.out = FuncSummary()

        def on_acquire(self, lockname, node):
            self.out.acquires.add(lockname)

        def on_blocking(self, desc, node):
            self.out.blocks = True

        def on_opaque_call(self, desc, node):
            self.out.opaque = True

        def on_call(self, target, node):
            if target.key in self.summaries:
                self.out.merge(self.summaries[target.key])
            elif target.node is not self.fi.node:
                # nested function: collect inline with a sub-walker
                sub = _Collector(self.cfg, self.index, target,
                                 self.summaries)
                sub.run()
                self.out.merge(sub.out)

    summaries = {k: FuncSummary() for k in index.functions}
    for _ in range(12):                 # call-graph depth bound
        changed = False
        for key, fi in index.functions.items():
            w = _Collector(cfg, index, fi, summaries)
            try:
                w.run()
            except RecursionError:
                continue
            changed |= summaries[key].merge(w.out)
        if not changed:
            break
    return summaries


# --------------------------------------------------------------- baseline
def load_baseline(path: Path) -> set[str]:
    """Baseline entries: one ``<rule>:<func>:<attr-or-detail>`` key per
    line; ``#`` comments carry the per-entry justification."""
    if not path.exists():
        return set()
    out = set()
    for line in path.read_text().splitlines():
        line = line.split("#", 1)[0].strip()
        if line:
            out.add(line)
    return out


def apply_baseline(findings: list[Finding], baseline: set[str]
                   ) -> list[Finding]:
    for f in findings:
        if f.key() in baseline:
            f.suppressed = True
    return findings
