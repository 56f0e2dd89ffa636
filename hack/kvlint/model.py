"""Phase 1 of the whole-program analyzer: the project model.

PR 2's rules were file-local visitors; the bug classes PRs 1-4 kept
adding — lock-order cycles *between* components, silent drift between
code and the documented config/metrics/trace surface — are invisible
to any single file.  This module builds the cross-file symbol table the
project rules (KV006-KV008) consume:

* **classes** — every class in the analyzed set: its lock attributes
  (``threading.Lock/RLock/Condition`` assignments, including ones
  wrapped by ``lockorder.tracked``), its attribute->class type bindings
  (``self._index = index`` with an annotated parameter, or a direct
  ``self._x = ClassName(...)``), and per-method lock behavior: which
  locks a method acquires, which calls it makes while holding which
  locks, and the lexically nested ``with <lock>`` pairs.
* **lock-order declarations** — the annotation vocabulary:
  ``# kvlint: lock-order: A < B`` (A is always acquired before B) and
  ``# kvlint: lock-order: L ascending`` (multiple instances of L are
  only ever acquired in ascending instance order).
* **env reads** — every literal ``os.environ[...]`` /
  ``os.environ.get`` / ``os.getenv`` name, including names passed
  through a same-module helper that forwards its first parameter to
  ``os.environ`` (the ``_env_int("TRACE_RING_SIZE", ...)`` pattern).
* **metric registrations** — ``Counter/Gauge/Histogram/Summary(...)``
  first-argument names, with module-level string constants resolved
  through f-strings (the ``f"{_NAMESPACE}_..."`` pattern).
* **stage names** — string literals handed to ``span``/``obs_span``,
  ``add_completed``, ``start_trace`` and ``root_trace``: the
  ``kvtpu_stage_latency_seconds{stage=...}`` label vocabulary.
* **the documented surface** — knobs parsed from the env-var tables of
  ``docs/configuration.md`` and ``docs/observability.md``, metric
  names (with ``*`` wildcards) from the metrics-inventory table, and
  every backticked token of ``docs/observability.md`` as the stage
  vocabulary.  Native C++ sources and repo-root scripts are scanned
  for ``getenv("...")`` so knobs read outside Python (e.g.
  ``KVTPU_NATIVE_DEBUG``) don't read as doc-only drift.

The model is deliberately an over-approximation where it must be (a
call on an attribute typed as a base class resolves to every subclass
that defines the method) and silent where it cannot know (calls on
unresolvable receivers are skipped); docs/static-analysis.md documents
both choices.
"""

from __future__ import annotations

import ast
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from hack.kvlint.base import SourceFile, dotted_name
from hack.kvlint.guards import is_lock_call as _is_lock_call

_METRIC_FACTORIES = {"Counter", "Gauge", "Histogram", "Summary"}

_SPAN_CALLS = {
    "span", "obs_span", "add_completed", "start_trace", "root_trace",
}

LOCK_ORDER_RE = re.compile(
    r"kvlint:\s*lock-order:\s*"
    r"([A-Za-z_][\w.]*)\s*(?:<\s*([A-Za-z_][\w.]*)|(ascending))"
)

_ENV_NAME_RE = re.compile(r"^[A-Z][A-Z0-9_]{2,}$")
_GETENV_SRC_RE = re.compile(r"getenv\(\s*\"([A-Z][A-Z0-9_]{2,})\"")

DOCS_CONFIG = os.path.join("docs", "configuration.md")
DOCS_OBSERVABILITY = os.path.join("docs", "observability.md")


@dataclass(frozen=True)
class LockRef:
    """One lock identity, aggregated across instances.

    ``owner`` is the declaring class name (or ``module:<stem>`` for a
    module-level lock), ``attr`` the attribute name — shard stripes of
    one class collapse onto a single node, which is exactly what makes
    same-node nesting (two shards of one striped structure) visible as
    a self-edge.
    """

    owner: str
    attr: str

    @property
    def name(self) -> str:
        return f"{self.owner}.{self.attr}"


@dataclass
class CallSite:
    """A call made while holding ``held`` locks."""

    receiver: Optional[str]  # "self", attr chain ("self._index"), name
    method: str
    held: Tuple[LockRef, ...]
    path: str
    line: int


@dataclass
class MethodModel:
    name: str
    path: str
    line: int
    # Locks this method acquires directly (lexical `with`).
    acquires: List[Tuple[LockRef, int]] = field(default_factory=list)
    # (outer, inner, line-of-inner) for lexically nested acquisition.
    nested: List[Tuple[LockRef, LockRef, int]] = field(default_factory=list)
    calls: List[CallSite] = field(default_factory=list)


@dataclass
class ClassModel:
    name: str
    path: str
    line: int
    bases: List[str] = field(default_factory=list)
    lock_attrs: Set[str] = field(default_factory=set)
    # self.<attr> -> inferred class name (constructor call or annotated
    # parameter assignment).
    attr_types: Dict[str, str] = field(default_factory=dict)
    methods: Dict[str, MethodModel] = field(default_factory=dict)
    # Resource attrs for KV008: attr -> (kind, line).
    resources: Dict[str, Tuple[str, int]] = field(default_factory=dict)
    # Attr names referenced by each method (KV008 close-path search).
    method_attr_refs: Dict[str, Set[str]] = field(default_factory=dict)


@dataclass
class EnvRead:
    name: str
    path: str
    line: int


@dataclass
class MetricRegistration:
    name: str
    path: str
    line: int
    # Factory class name ("Counter", "Gauge", ...). Counters gain a
    # `_total` suffix at exposition, so docs may show either form.
    kind: str = ""


@dataclass
class StageUse:
    name: str
    path: str
    line: int


@dataclass
class OrderDecl:
    """One `# kvlint: lock-order:` annotation."""

    first: str
    second: Optional[str]  # None for `ascending`
    ascending: bool
    path: str
    line: int


@dataclass
class DocSurface:
    """The documented contract surface parsed from docs/."""

    root: str
    # knob name -> (doc path, line) of its table row.
    knobs: Dict[str, Tuple[str, int]] = field(default_factory=dict)
    # exact metric name (namespace stripped) -> (doc path, line)
    metrics: Dict[str, Tuple[str, int]] = field(default_factory=dict)
    metric_wildcards: List[str] = field(default_factory=list)
    stages: Set[str] = field(default_factory=set)
    # env names read outside the analyzed Python set (native C++,
    # repo-root scripts): documented-but-unread must not fire on them.
    external_env_reads: Set[str] = field(default_factory=set)


class ProjectModel:
    """The cross-file symbol table rule phases consume."""

    def __init__(self, sources: Sequence[SourceFile]) -> None:
        self.sources = list(sources)
        self.by_path: Dict[str, SourceFile] = {s.path: s for s in sources}
        self.classes: Dict[str, ClassModel] = {}
        self.subclasses: Dict[str, Set[str]] = {}
        self.env_reads: List[EnvRead] = []
        self.metric_registrations: List[MetricRegistration] = []
        self.stage_uses: List[StageUse] = []
        self.order_decls: List[OrderDecl] = []
        self.docs: Optional[DocSurface] = None
        # True when the analyzed roots cover a whole top-level package
        # (the CI invocation); whole-program-only checks key off this.
        self.whole_program = False
        for source in self.sources:
            self._scan_source(source)
        self._link_subclasses()

    # -- per-file scan --------------------------------------------------

    def _scan_source(self, source: SourceFile) -> None:
        self._collect_order_decls(source)
        env_helpers = _env_helper_params(source.tree)
        module_consts = _module_str_constants(source.tree)
        # Module-level locks first, so a function defined above the
        # lock assignment still resolves `with _lock:` against it.
        for node in source.tree.body:
            self._scan_module_level(source, node)
        module_cls = self.classes.get(_module_owner(source.path))
        for node in source.tree.body:
            if isinstance(node, ast.ClassDef):
                self._scan_class(source, node, module_consts)
            elif isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef)
            ):
                # Module-level functions acquire module-level locks by
                # bare name (`with _lock:`) — scan them as methods of
                # the synthetic module class so KV006 sees the edges.
                if module_cls is not None:
                    self._scan_method(
                        source, module_cls, node, {}, module_scope=True
                    )
        for node in ast.walk(source.tree):
            self._maybe_env_read(source, node, env_helpers)
            self._maybe_metric(source, node, module_consts)
            self._maybe_stage(source, node)

    def _collect_order_decls(self, source: SourceFile) -> None:
        for lineno, (_, comment) in sorted(source.comments.items()):
            match = LOCK_ORDER_RE.search(comment)
            if not match:
                continue
            first, second, ascending = match.groups()
            self.order_decls.append(
                OrderDecl(
                    first=first,
                    second=second,
                    ascending=bool(ascending),
                    path=source.path,
                    line=lineno,
                )
            )

    def _scan_module_level(
        self, source: SourceFile, node: ast.AST
    ) -> None:
        """Module-level locks: ``_lock = threading.Lock()``."""
        if isinstance(node, ast.Assign) and _is_lock_call(node.value):
            for target in node.targets:
                if isinstance(target, ast.Name):
                    owner = _module_owner(source.path)
                    cls = self.classes.setdefault(
                        owner, ClassModel(owner, source.path, node.lineno)
                    )
                    cls.lock_attrs.add(target.id)

    def _scan_class(
        self,
        source: SourceFile,
        node: ast.ClassDef,
        module_consts: Dict[str, str],
    ) -> None:
        existing = self.classes.get(node.name)
        cls = ClassModel(node.name, source.path, node.lineno)
        cls.bases = [
            base_name
            for base in node.bases
            if (base_name := dotted_name(base)) is not None
        ]
        if existing is not None:
            # Same class name in two files: merge (rule output degrades
            # to the union, which over-reports rather than missing).
            cls = existing
            cls.bases.extend(
                b
                for base in node.bases
                if (b := dotted_name(base)) is not None and b not in cls.bases
            )
        self.classes[node.name] = cls

        # Parameter annotations of every method feed attr typing:
        #   def __init__(self, index: Index): self._index = index
        param_types: Dict[str, str] = {}
        for item in node.body:
            if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for arg in item.args.args + item.args.kwonlyargs:
                    ann = arg.annotation
                    if ann is not None:
                        ann_name = _annotation_class(ann)
                        if ann_name:
                            param_types[arg.arg] = ann_name

        for item in node.body:
            if not isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            self._scan_method(source, cls, item, param_types)

    def _scan_method(
        self,
        source: SourceFile,
        cls: ClassModel,
        func: ast.AST,
        param_types: Dict[str, str],
        module_scope: bool = False,
    ) -> None:
        method = MethodModel(func.name, source.path, func.lineno)
        cls.methods[func.name] = method
        refs: Set[str] = set()
        cls.method_attr_refs[func.name] = refs

        def self_attr(node: ast.AST) -> Optional[str]:
            if (
                isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "self"
            ):
                return node.attr
            if module_scope and isinstance(node, ast.Name):
                # `with _lock:` on a module-level lock.
                return node.id
            return None

        def visit(node: ast.AST, held: Tuple[LockRef, ...]) -> None:
            if isinstance(node, ast.ClassDef):
                return
            attr = self_attr(node)
            if attr is not None:
                refs.add(attr)
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                self._scan_attr_assign(cls, node, param_types)
            if isinstance(node, (ast.With, ast.AsyncWith)):
                # `with a, b:` acquires a then b — items nest left to
                # right exactly like the nested-with form, so each item
                # sees every earlier item of the same statement as held.
                acquired: List[LockRef] = []
                for item in node.items:
                    visit(item.context_expr, held + tuple(acquired))
                    lock_attr = self_attr(item.context_expr)
                    if (
                        lock_attr is not None
                        and lock_attr in cls.lock_attrs
                    ):
                        ref = LockRef(cls.name, lock_attr)
                        method.acquires.append((ref, node.lineno))
                        for outer in held + tuple(acquired):
                            method.nested.append(
                                (outer, ref, node.lineno)
                            )
                        acquired.append(ref)
                inner = held + tuple(acquired)
                for stmt in node.body:
                    visit(stmt, inner)
                return
            if isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                # Same soundness rule as KV001: a closure can escape
                # the `with` block, so it never inherits held locks.
                body = (
                    node.body
                    if isinstance(node.body, list)
                    else [node.body]
                )
                for stmt in body:
                    visit(stmt, ())
                return
            if isinstance(node, ast.Call):
                self._record_call(source, method, node, held)
            for child in ast.iter_child_nodes(node):
                visit(child, held)

        for stmt in func.body:
            visit(stmt, ())

    def _scan_attr_assign(
        self,
        cls: ClassModel,
        node: ast.AST,
        param_types: Dict[str, str],
    ) -> None:
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        else:  # AnnAssign
            targets, value = [node.target], node.value
        for target in targets:
            if not (
                isinstance(target, ast.Attribute)
                and isinstance(target.value, ast.Name)
                and target.value.id == "self"
            ):
                continue
            attr = target.attr
            if value is None:
                continue
            if _is_lock_call(value):
                cls.lock_attrs.add(attr)
                continue
            kind = _resource_kind(value)
            if kind is not None:
                cls.resources.setdefault(attr, (kind, node.lineno))
            if isinstance(value, ast.Call):
                callee = dotted_name(value.func)
                if callee:
                    # self._x = Foo(...) / pkg.Foo(...) -> type Foo
                    cls.attr_types.setdefault(
                        attr, callee.rsplit(".", 1)[-1]
                    )
            elif isinstance(value, ast.Name):
                inferred = param_types.get(value.id)
                if inferred:
                    cls.attr_types.setdefault(attr, inferred)

    def _record_call(
        self,
        source: SourceFile,
        method: MethodModel,
        node: ast.Call,
        held: Tuple[LockRef, ...],
    ) -> None:
        func = node.func
        if isinstance(func, ast.Attribute):
            receiver = dotted_name(func.value)
            method.calls.append(
                CallSite(
                    receiver=receiver,
                    method=func.attr,
                    held=held,
                    path=source.path,
                    line=node.lineno,
                )
            )
        elif isinstance(func, ast.Name):
            method.calls.append(
                CallSite(
                    receiver=None,
                    method=func.id,
                    held=held,
                    path=source.path,
                    line=node.lineno,
                )
            )

    # -- env / metrics / stages ----------------------------------------

    def _maybe_env_read(
        self,
        source: SourceFile,
        node: ast.AST,
        env_helpers: Set[str],
    ) -> None:
        name: Optional[str] = None
        line = getattr(node, "lineno", 0)
        if isinstance(node, ast.Call):
            callee = dotted_name(node.func)
            if callee in ("os.environ.get", "os.getenv", "environ.get"):
                name = _literal_str(node.args[0]) if node.args else None
            elif (
                callee in env_helpers
                or (
                    callee
                    and callee.rsplit(".", 1)[-1] in env_helpers
                )
            ):
                name = _literal_str(node.args[0]) if node.args else None
        elif isinstance(node, ast.Subscript):
            base = dotted_name(node.value)
            if base in ("os.environ", "environ"):
                name = _literal_str(node.slice)
        if name and _ENV_NAME_RE.match(name):
            self.env_reads.append(EnvRead(name, source.path, line))

    def _maybe_metric(
        self,
        source: SourceFile,
        node: ast.AST,
        module_consts: Dict[str, str],
    ) -> None:
        if not isinstance(node, ast.Call):
            return
        callee = dotted_name(node.func)
        if not callee:
            return
        kind = callee.rsplit(".", 1)[-1]
        if kind not in _METRIC_FACTORIES:
            return
        if not node.args:
            return
        name = _resolve_str(node.args[0], module_consts)
        if name:
            self.metric_registrations.append(
                MetricRegistration(name, source.path, node.lineno, kind)
            )

    def _maybe_stage(self, source: SourceFile, node: ast.AST) -> None:
        if not isinstance(node, ast.Call):
            return
        callee = dotted_name(node.func)
        if not callee:
            return
        if callee.rsplit(".", 1)[-1] not in _SPAN_CALLS:
            return
        if not node.args:
            return
        name = _literal_str(node.args[0])
        if name:
            self.stage_uses.append(
                StageUse(name, source.path, node.lineno)
            )

    # -- subclass map ---------------------------------------------------

    def _link_subclasses(self) -> None:
        for cls in self.classes.values():
            for base in cls.bases:
                base_name = base.rsplit(".", 1)[-1]
                self.subclasses.setdefault(base_name, set()).add(cls.name)

    def transitive_subclasses(self, name: str) -> Set[str]:
        out: Set[str] = set()
        frontier = [name]
        while frontier:
            current = frontier.pop()
            for sub in self.subclasses.get(current, ()):
                if sub not in out:
                    out.add(sub)
                    frontier.append(sub)
        return out

    # -- call resolution ------------------------------------------------

    def resolve_call(
        self, caller: ClassModel, call: CallSite
    ) -> List[Tuple[ClassModel, MethodModel]]:
        """Possible (class, method) targets of a call site.

        ``self.m()`` resolves within the class (and its subclasses —
        a template method may run overridden under the base's lock).
        ``self._attr.m()`` resolves through the attr's inferred type,
        widened to every subclass defining ``m`` (an attr typed as the
        ``Index`` ABC may hold any backend).  Unresolvable receivers
        resolve to nothing — the documented soundness gap.
        """
        targets: List[Tuple[ClassModel, MethodModel]] = []

        def add_type(type_name: str) -> None:
            seen: Set[str] = set()
            for candidate in [type_name, *self.transitive_subclasses(
                type_name
            )]:
                if candidate in seen:
                    continue
                seen.add(candidate)
                cls = self.classes.get(candidate)
                if cls is None:
                    continue
                target = cls.methods.get(call.method)
                if target is not None:
                    targets.append((cls, target))

        if call.receiver == "self":
            add_type(caller.name)
        elif call.receiver and call.receiver.startswith("self."):
            attr = call.receiver.split(".", 1)[1]
            if "." not in attr:
                type_name = caller.attr_types.get(attr)
                if type_name:
                    add_type(type_name)
        return targets


# -- docs parsing -------------------------------------------------------


def find_project_root(paths: Sequence[str]) -> Optional[str]:
    """Nearest ancestor of an analyzed path holding docs/configuration.md.

    No cwd fallback: an ad-hoc file outside any project tree gets no
    documented surface, and the doc-dependent KV007 checks stay off.
    """
    for path in paths:
        current = os.path.abspath(path)
        if os.path.isfile(current):
            current = os.path.dirname(current)
        while True:
            if os.path.isfile(os.path.join(current, DOCS_CONFIG)):
                return current
            parent = os.path.dirname(current)
            if parent == current:
                break
            current = parent
    return None


_TABLE_ROW_RE = re.compile(r"^\s*\|(.+)\|\s*$")
_BACKTICK_RE = re.compile(r"`([^`]+)`")


def _row_cells(line: str) -> List[str]:
    match = _TABLE_ROW_RE.match(line)
    if not match:
        return []
    return [cell.strip() for cell in match.group(1).split("|")]


def parse_docs(root: str) -> DocSurface:
    docs = DocSurface(root=root)
    config_path = os.path.join(root, DOCS_CONFIG)
    obs_path = os.path.join(root, DOCS_OBSERVABILITY)
    for doc_path in (config_path, obs_path):
        if not os.path.isfile(doc_path):
            continue
        rel = os.path.relpath(doc_path, os.getcwd())
        with open(doc_path, encoding="utf-8") as handle:
            for lineno, line in enumerate(handle, start=1):
                cells = _row_cells(line)
                if not cells:
                    continue
                # Env knobs: first-cell backticked ALL-CAPS tokens of
                # any table (the env tables; invariant rows that quote
                # e.g. `PYTHONHASHSEED` in cell one count too, which
                # is correct — the knob IS documented there).
                for token in _BACKTICK_RE.findall(cells[0]):
                    if _ENV_NAME_RE.match(token):
                        docs.knobs.setdefault(token, (rel, lineno))
    if os.path.isfile(obs_path):
        rel = os.path.relpath(obs_path, os.getcwd())
        with open(obs_path, encoding="utf-8") as handle:
            in_inventory = False
            for lineno, line in enumerate(handle, start=1):
                if line.startswith("#"):
                    in_inventory = "metrics inventory" in line.lower()
                for token in _BACKTICK_RE.findall(line):
                    docs.stages.add(token)
                if not in_inventory:
                    continue
                cells = _row_cells(line)
                if not cells:
                    continue
                for token in _BACKTICK_RE.findall(cells[0]):
                    if token.endswith("*"):
                        docs.metric_wildcards.append(token[:-1])
                    elif re.match(r"^[a-z][a-z0-9_]+$", token):
                        docs.metrics.setdefault(token, (rel, lineno))
    docs.external_env_reads = _scan_external_env_reads(root)
    return docs


def _scan_external_env_reads(root: str) -> Set[str]:
    """Env names read outside the analyzed Python set: native C++
    (``std::getenv``) and repo-root scripts (chip_smoke.py etc.)."""
    names: Set[str] = set()
    patterns = [
        os.path.join(root, "*.py"),
        os.path.join(root, "hack", "*.py"),
        os.path.join(root, "**", "native", "src", "*.cpp"),
        os.path.join(root, "**", "native", "src", "*.hpp"),
    ]
    for pattern in patterns:
        for path in glob.glob(pattern, recursive=True):
            try:
                with open(path, encoding="utf-8", errors="ignore") as fh:
                    text = fh.read()
            except OSError:
                continue
            for match in _GETENV_SRC_RE.finditer(text):
                names.add(match.group(1))
            # Python-side literal reads in scripts.
            for match in re.finditer(
                r"environ(?:\.get)?[\[(]\s*[\"']([A-Z][A-Z0-9_]{2,})[\"']",
                text,
            ):
                names.add(match.group(1))
    return names


def attach_docs(model: ProjectModel, paths: Sequence[str]) -> None:
    """Locate and parse the documented surface; mark whole-program
    scope (an analyzed directory directly under the project root —
    the ``python -m hack.kvlint <package>`` CI shape)."""
    root = find_project_root(paths)
    if root is None:
        return
    model.docs = parse_docs(root)
    for path in paths:
        abspath = os.path.abspath(path)
        if os.path.isdir(abspath) and os.path.dirname(abspath) == root:
            model.whole_program = True
            break


# -- small AST helpers --------------------------------------------------


def _module_owner(path: str) -> str:
    """Unique synthetic owner for a file's module-level locks.

    Path-derived (not the bare stem): every package has an
    ``__init__.py``, and merging their same-named module locks onto one
    node would invent self-edges that exist in no program."""
    rel = os.path.splitext(path)[0].replace(os.sep, ".").lstrip(".")
    return f"module:{rel}"


def _resource_kind(node: ast.AST) -> Optional[str]:
    if not isinstance(node, ast.Call):
        return None
    callee = dotted_name(node.func)
    if not callee:
        return None
    leaf = callee.rsplit(".", 1)[-1]
    if leaf == "Thread":
        return "thread"
    if leaf in ("ThreadPoolExecutor", "ProcessPoolExecutor"):
        return "executor"
    if callee in ("socket.socket",):
        return "socket"
    if leaf == "socket" and callee != "socket.socket":
        # ctx.socket(zmq.SUB) — the ZMQ socket-from-context shape.
        return "zmq socket"
    if callee in ("zmq.Context", "Context"):
        return "zmq context"
    return None


def _annotation_class(node: ast.AST) -> Optional[str]:
    """Class name of a simple annotation; Optional[X] unwraps to X."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value.rsplit(".", 1)[-1] or None
    name = dotted_name(node)
    if name:
        return name.rsplit(".", 1)[-1]
    if isinstance(node, ast.Subscript):
        base = dotted_name(node.value)
        if base and base.rsplit(".", 1)[-1] == "Optional":
            return _annotation_class(node.slice)
    return None


def _literal_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _module_str_constants(tree: ast.AST) -> Dict[str, str]:
    consts: Dict[str, str] = {}
    for node in getattr(tree, "body", []):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            target = node.targets[0]
            value = _literal_str(node.value)
            if isinstance(target, ast.Name) and value is not None:
                consts[target.id] = value
    return consts


def _resolve_str(
    node: ast.AST, consts: Dict[str, str]
) -> Optional[str]:
    """Literal, module-constant, f-string-of-constants, or
    constant-concatenation string value; None when dynamic."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.Name):
        return consts.get(node.id)
    if isinstance(node, ast.JoinedStr):
        parts: List[str] = []
        for value in node.values:
            if isinstance(value, ast.Constant):
                parts.append(str(value.value))
            elif isinstance(value, ast.FormattedValue):
                resolved = _resolve_str(value.value, consts)
                if resolved is None:
                    return None
                parts.append(resolved)
            else:
                return None
        return "".join(parts)
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        left = _resolve_str(node.left, consts)
        right = _resolve_str(node.right, consts)
        if left is not None and right is not None:
            return left + right
    return None


def _env_helper_params(tree: ast.AST) -> Set[str]:
    """Names of module functions that forward their first parameter to
    ``os.environ`` (``def _env_int(name, default): os.environ.get(name)``
    — call sites with a literal first arg then count as env reads)."""
    helpers: Set[str] = set()
    for node in getattr(tree, "body", []):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        params = [a.arg for a in node.args.args]
        if not params:
            continue
        first = params[0]
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                callee = dotted_name(sub.func)
                if (
                    callee in ("os.environ.get", "os.getenv", "environ.get")
                    and sub.args
                    and isinstance(sub.args[0], ast.Name)
                    and sub.args[0].id == first
                ):
                    helpers.add(node.name)
                    break
            elif isinstance(sub, ast.Subscript):
                base = dotted_name(sub.value)
                if (
                    base in ("os.environ", "environ")
                    and isinstance(sub.slice, ast.Name)
                    and sub.slice.id == first
                ):
                    helpers.add(node.name)
                    break
    return helpers


def build_model(
    sources: Sequence[SourceFile], paths: Sequence[str]
) -> ProjectModel:
    model = ProjectModel(sources)
    attach_docs(model, paths)
    return model
