"""nornlint v3 — JAX dataflow analysis over the interprocedural call graph.

The lock rules (interproc.py) answer "what is *held* here"; the JAX bug
classes that bite a TPU serving stack are about what a *value* is allowed
to do after a dispatch.  This module tracks device-array values through
locals, ``self`` attributes and returns (bounded by the same
``MAX_HELD_DEPTH`` hop budget the held-lock propagation uses) and powers
three project rules:

* **NL-JAX04 — use-after-donate.**  A value passed to a jitted callable
  whose signature declares ``donate_argnums`` is read again afterwards on
  any path.  XLA frees a donated buffer the moment the program consumes
  it, so the later read touches deleted memory (on CPU it silently
  aliases; on TPU it is a runtime error or corruption).  Three witness
  shapes: a read after the donate with no rebind in between, a donated
  ``self`` attribute that is never rebound, and the *exception path* —
  ``self.x = donating(self.x)`` with no enclosing ``try`` whose broad
  handler drops/rebuilds ``self.x`` (the bug class PR 10's "failing step
  rebuilds the donated pool" hardening fixed by hand).
* **NL-JAX05 — unbounded shape-class dispatch.**  A call into a jitted /
  shard_mapped program whose operands derive from unbucketed
  request-dependent sizes (``len(texts)``, list lengths, un-pow2'd ``k``)
  without passing through a recognized bucketing helper
  (``round_up_pow2`` / ``pow2_class`` / ``*bucket*`` / ``bit_length``
  ladders).  Every distinct size compiles a fresh program — the churn
  the bench ledger invariants only sample at exit, enforced statically.
* **NL-JAX06 — host-device sync on an owner/dispatcher thread.**
  ``.item()``, ``float()/int()/bool()`` of a device expression,
  ``np.asarray`` of a device expression or ``block_until_ready``
  reachable (within the hop budget) from a function annotated with the
  ``# nornlint: thread-role=<name>`` grammar — the genserve scheduler
  loop, the QueryBatcher dispatcher, the broker serve loop.  A sync on
  those threads stalls every queued request behind one host round-trip.
  ``thread-role=none`` on a callee stops propagation (the escape hatch
  for helpers that deliberately sync off the hot loop).

The runtime twin is tools/nornjit: this module predicts recompile churn
and donation misuse from the AST; nornjit watches the live compile
stream under ``NORNJIT=1`` and fails tests that compile after their
declared warmup.  A static NL-JAX05 hit nornjit never observes is a
false-positive candidate; churn nornjit catches that this pass missed is
a resolution gap — same ratchet as nornsan vs NL-LK01.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from typing import Iterator, Optional

from .core import Finding, dotted_name
from .interproc import (
    MAX_HELD_DEPTH,
    FunctionInfo,
    ModuleInfo,
    ProjectContext,
    _finding,
    register_project,
)

_JIT_NAMES = {"jit", "jax.jit", "pjit", "jax.pjit"}
_SHARD_MAP_NAMES = {"shard_map", "jax.experimental.shard_map.shard_map"}
_THREAD_ROLE_RE = re.compile(r"#\s*nornlint:\s*thread-role=([A-Za-z0-9_\-]+)")
# name fragments that launder a request-dependent size into a bounded
# shape class (the pow2 ladders and bucket helpers the repo already uses)
_BUCKET_FRAGMENTS = ("pow2", "bucket", "shape_class", "round_up",
                     "bit_length")
_HOST_SYNC_CASTS = {"float", "int", "bool", "complex"}
_NUMPY_ROOTS = {"np", "numpy", "onp"}
_DEVICE_ROOTS = ("jnp", "jax")
_BROAD_HANDLERS = {"Exception", "BaseException"}


# ---------------------------------------------------------------------------
# Jit / donation registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class JitTarget:
    """One jitted (or shard_mapped) callable the package can dispatch."""

    display: str                     # human name for witnesses
    relpath: str
    line: int                        # declaration site (donation witness)
    donate_pos: frozenset = frozenset()    # donated positional indexes
    donate_names: frozenset = frozenset()  # donated parameter names

    @property
    def donating(self) -> bool:
        return bool(self.donate_pos or self.donate_names)


def _literal_argnums(node: Optional[ast.expr]) -> frozenset:
    """Literal donate_argnums spec: int or tuple/list of ints."""
    if node is None:
        return frozenset()
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return frozenset({node.value})
    if isinstance(node, (ast.Tuple, ast.List)):
        out = set()
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.add(e.value)
            else:
                return frozenset()
        return frozenset(out)
    return frozenset()


def _jit_call_spec(call: ast.Call) -> Optional[tuple[frozenset, frozenset]]:
    """(donate_pos, donate_names) when ``call`` is jit/pjit/shard_map
    (possibly through functools.partial), else None."""
    name = dotted_name(call.func) or ""
    leaf = name.split(".")[-1]
    if name in _JIT_NAMES or leaf in {"shard_map"}:
        pos = frozenset()
        names: frozenset = frozenset()
        for kw in call.keywords:
            if kw.arg == "donate_argnums":
                pos = _literal_argnums(kw.value)
            elif kw.arg == "donate_argnames":
                if isinstance(kw.value, (ast.Tuple, ast.List)):
                    vals = kw.value.elts
                else:
                    vals = [kw.value]
                names = frozenset(
                    v.value for v in vals
                    if isinstance(v, ast.Constant) and isinstance(v.value, str)
                )
        return pos, names
    if name in {"functools.partial", "partial"} and call.args:
        inner = call.args[0]
        inner_name = dotted_name(inner) or ""
        if inner_name in _JIT_NAMES:
            fake = ast.Call(func=inner, args=[], keywords=call.keywords)
            return _jit_call_spec(fake) or (frozenset(), frozenset())
    return None


def _positional_params(fn_node: ast.AST) -> list[str]:
    args = fn_node.args
    return [a.arg for a in list(args.posonlyargs) + list(args.args)]


class JitRegistry:
    """Every jitted callable reachable by name, with donation metadata."""

    def __init__(self) -> None:
        self.by_qualname: dict[str, JitTarget] = {}
        # (relpath, local name) -> target, for jit objects bound by
        # assignment (``_patch_rows_donated = jax.jit(..., donate_...)``)
        self.by_local: dict[tuple[str, str], JitTarget] = {}

    def add_decorated(self, fi: FunctionInfo) -> Optional[JitTarget]:
        for dec in fi.node.decorator_list:
            spec = None
            if isinstance(dec, ast.Call):
                spec = _jit_call_spec(dec)
            elif (dotted_name(dec) or "") in _JIT_NAMES:
                spec = (frozenset(), frozenset())
            if spec is None:
                continue
            pos, names = spec
            params = _positional_params(fi.node)
            # positions and names are two views of one donation set:
            # callers pass the operand either way
            names = names | frozenset(
                params[p] for p in pos if p < len(params)
            )
            pos = pos | frozenset(
                i for i, n in enumerate(params) if n in names
            )
            tgt = JitTarget(display=fi.display(), relpath=fi.relpath,
                            line=fi.node.lineno, donate_pos=pos,
                            donate_names=names)
            self.by_qualname[fi.qualname] = tgt
            if fi.cls is None:
                self.by_local[(fi.relpath, fi.name)] = tgt
            return tgt
        return None

    def add_assigned(self, mi: ModuleInfo) -> None:
        for node in ast.walk(mi.ctx.tree):
            if not (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)):
                continue
            spec = _jit_call_spec(node.value)
            if spec is None:
                continue
            pos, names = spec
            for t in node.targets:
                if isinstance(t, ast.Name):
                    self.by_local[(mi.relpath, t.id)] = JitTarget(
                        display=t.id, relpath=mi.relpath, line=node.lineno,
                        donate_pos=pos, donate_names=names,
                    )

    def resolve(self, call: ast.Call, mi: ModuleInfo,
                project: ProjectContext) -> Optional[JitTarget]:
        """The JitTarget a call dispatches to, resolved through local
        names, from-imports and module attributes."""
        d = dotted_name(call.func)
        if d is None:
            return None
        parts = d.split(".")
        if len(parts) == 1:
            tgt = self.by_local.get((mi.relpath, d))
            if tgt is not None:
                return tgt
            q = mi.functions.get(d)
            if q and q in self.by_qualname:
                return self.by_qualname[q]
            pair = mi.from_imports.get(d)
            if pair:
                owner = project.by_modname.get(pair[0])
                if owner is not None:
                    tgt = self.by_local.get((owner.relpath, pair[1]))
                    if tgt is not None:
                        return tgt
                    q = owner.functions.get(pair[1])
                    if q and q in self.by_qualname:
                        return self.by_qualname[q]
            return None
        if parts[0] == "self":
            return None
        owner = project.resolve_module_ref(".".join(parts[:-1]), mi)
        if owner is not None:
            tgt = self.by_local.get((owner.relpath, parts[-1]))
            if tgt is not None:
                return tgt
            q = owner.functions.get(parts[-1])
            if q and q in self.by_qualname:
                return self.by_qualname[q]
        return None


# ---------------------------------------------------------------------------
# Statement-ordered function scan
# ---------------------------------------------------------------------------

def _stmt_exprs(stmt: ast.stmt) -> list[ast.AST]:
    """The expressions a statement itself evaluates (compound statements
    contribute only their header, their bodies are scanned as separate
    statements — no double counting)."""
    if isinstance(stmt, ast.Assign):
        return [stmt.value]
    if isinstance(stmt, ast.AnnAssign):
        return [stmt.value] if stmt.value is not None else []
    if isinstance(stmt, ast.AugAssign):
        return [stmt.value, stmt.target]
    if isinstance(stmt, (ast.Expr, ast.Return)):
        return [stmt.value] if stmt.value is not None else []
    if isinstance(stmt, (ast.If, ast.While)):
        return [stmt.test]
    if isinstance(stmt, (ast.For, ast.AsyncFor)):
        return [stmt.iter]
    if isinstance(stmt, (ast.With, ast.AsyncWith)):
        return [i.context_expr for i in stmt.items]
    if isinstance(stmt, ast.Raise):
        return [n for n in (stmt.exc, stmt.cause) if n is not None]
    if isinstance(stmt, ast.Assert):
        return [n for n in (stmt.test, stmt.msg) if n is not None]
    if isinstance(stmt, ast.Delete):
        return []
    return []


def _assigned_names(stmt: ast.stmt) -> set[str]:
    """Dotted names this statement rebinds (``x``, ``self.attr``,
    ``seq.page_table``).  A subscript store (``self.x[0] = ...``) does
    NOT rebind the base and is excluded on purpose."""
    out: set[str] = set()

    def collect(t: ast.expr) -> None:
        if isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                collect(e)
        elif isinstance(t, ast.Starred):
            collect(t.value)
        elif isinstance(t, (ast.Name, ast.Attribute)):
            d = dotted_name(t)
            if d:
                out.add(d)

    if isinstance(stmt, ast.Assign):
        for t in stmt.targets:
            collect(t)
    elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
        collect(stmt.target)
    elif isinstance(stmt, (ast.For, ast.AsyncFor)):
        collect(stmt.target)
    elif isinstance(stmt, (ast.With, ast.AsyncWith)):
        for i in stmt.items:
            if i.optional_vars is not None:
                collect(i.optional_vars)
    return out


def _reads_value(exprs: list[ast.AST], value: str) -> Optional[ast.AST]:
    """First Load of ``value`` (or of an attribute/subscript rooted at
    it) inside the given expressions."""
    for root in exprs:
        for node in ast.walk(root):
            if isinstance(node, (ast.Name, ast.Attribute)) \
                    and isinstance(getattr(node, "ctx", None), ast.Load):
                d = dotted_name(node)
                if d == value or (d and d.startswith(value + ".")):
                    return node
    return None


def _paths_compatible(a: tuple, b: tuple) -> bool:
    """True when two branch paths can lie on one execution path (neither
    took the *other* arm of a shared If/Try)."""
    for x, y in zip(a, b):
        if x != y:
            return False
    return True


@dataclasses.dataclass
class _Stmt:
    node: ast.stmt
    path: tuple                 # branch path: ((id(If), "body"), ...)
    tries: tuple                # enclosing ast.Try nodes, outermost first


def _collect_stmts(fn_node: ast.AST) -> list[_Stmt]:
    out: list[_Stmt] = []

    def visit(body: list[ast.stmt], path: tuple, tries: tuple) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue  # nested scopes are separate functions
            out.append(_Stmt(stmt, path, tries))
            if isinstance(stmt, ast.If):
                visit(stmt.body, path + ((id(stmt), "body"),), tries)
                visit(stmt.orelse, path + ((id(stmt), "else"),), tries)
            elif isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                visit(stmt.body, path + ((id(stmt), "body"),), tries)
                visit(stmt.orelse, path + ((id(stmt), "else"),), tries)
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                visit(stmt.body, path, tries)
            elif isinstance(stmt, ast.Try):
                visit(stmt.body, path, tries + (stmt,))
                for h in stmt.handlers:
                    visit(h.body, path + ((id(stmt), id(h)),), tries)
                visit(stmt.orelse, path, tries)
                visit(stmt.finalbody, path, tries)

    visit(list(fn_node.body), (), ())
    return out


def _handler_is_broad(h: ast.ExceptHandler) -> bool:
    if h.type is None:
        return True
    types = h.type.elts if isinstance(h.type, ast.Tuple) else [h.type]
    return any(
        (dotted_name(t) or "").split(".")[-1] in _BROAD_HANDLERS
        for t in types
    )


def _exception_path_protected(tries: tuple, value: str) -> bool:
    """True when an enclosing try has a broad handler that rebinds the
    donated attribute (drop/rebuild before anyone can read it)."""
    for t in tries:
        for h in t.handlers:
            if not _handler_is_broad(h):
                continue
            for sub in ast.walk(h):
                if isinstance(sub, ast.stmt) and value in _assigned_names(sub):
                    return True
    return False


def _unwrap_operand(node: ast.expr) -> Optional[str]:
    """Tracked dotted name of a donated operand; ``self.x[0]`` tracks the
    base ``self.x`` (donating an element consumes the holder's buffer)."""
    while isinstance(node, ast.Subscript):
        node = node.value
    return dotted_name(node)


@dataclasses.dataclass
class _Donation:
    value: str                  # dotted name of the consumed operand
    target: JitTarget
    call: ast.Call
    stmt: _Stmt
    index: int                  # position in the statement order


@dataclasses.dataclass
class _HostSync:
    desc: str
    node: ast.AST


@dataclasses.dataclass
class _FnScan:
    """One function's dataflow facts, shared by the three rules."""

    fi: FunctionInfo
    stmts: list[_Stmt]
    donations: list[_Donation]
    consumed_params: dict[int, JitTarget]     # param index -> via target
    taint_sinks: list[tuple[ast.Call, JitTarget, str, int]]
    host_syncs: list[_HostSync]


class DataflowContext:
    """Package-wide value-flow tables; built once per lint run and memoized
    on the ProjectContext (the <60s ``make lint`` budget rides on every
    rule pass sharing this instance)."""

    def __init__(self, project: ProjectContext):
        self.project = project
        self.registry = JitRegistry()
        for mi in project.modules.values():
            self.registry.add_assigned(mi)
        for fi in project.functions.values():
            self.registry.add_decorated(fi)
        self.scans: dict[str, _FnScan] = {}
        # donation summaries propagate through wrappers up to the hop
        # budget: a function that forwards a parameter into a donated
        # position (without reading it after) donates that parameter too
        for _hop in range(MAX_HELD_DEPTH):
            changed = self._scan_all()
            if not changed:
                break
        self._propagate_roles()

    # -- per-function scan ---------------------------------------------------
    def _scan_all(self) -> bool:
        changed = False
        for fi in self.project.functions.values():
            scan = self._scan_fn(fi)
            self.scans[fi.qualname] = scan
            if scan.consumed_params and fi.qualname not in \
                    self.registry.by_qualname:
                params = _positional_params(fi.node)
                pos = frozenset(scan.consumed_params)
                names = frozenset(
                    params[p] for p in pos if p < len(params))
                self.registry.by_qualname[fi.qualname] = JitTarget(
                    display=fi.display(), relpath=fi.relpath,
                    line=fi.node.lineno, donate_pos=pos, donate_names=names,
                )
                if fi.cls is None:
                    self.registry.by_local[(fi.relpath, fi.name)] = \
                        self.registry.by_qualname[fi.qualname]
                changed = True
        return changed

    def _scan_fn(self, fi: FunctionInfo) -> _FnScan:
        mi = self.project.modules[fi.relpath]
        stmts = _collect_stmts(fi.node)
        scan = _FnScan(fi=fi, stmts=stmts, donations=[],
                       consumed_params={}, taint_sinks=[], host_syncs=[])
        aliases: dict[str, JitTarget] = {}
        tainted: dict[str, tuple[int, str]] = {}  # name -> (line, seed)
        params = _positional_params(fi.node)

        for idx, st in enumerate(stmts):
            exprs = _stmt_exprs(st.node)
            # local aliasing of jit objects (``patch = donated if d else
            # plain``): the alias may donate, so it carries the union
            if isinstance(st.node, ast.Assign) \
                    and len(st.node.targets) == 1 \
                    and isinstance(st.node.targets[0], ast.Name):
                tgt = self._alias_target(st.node.value, mi)
                if tgt is not None:
                    aliases[st.node.targets[0].id] = tgt
            # donations anywhere inside this statement's expressions
            for root in exprs:
                for node in ast.walk(root):
                    if not isinstance(node, ast.Call):
                        continue
                    jt = self._resolve_jit(node, mi, aliases)
                    if jt is None:
                        continue
                    if jt.donating:
                        for operand in self._donated_operands(node, jt):
                            val = _unwrap_operand(operand)
                            if val:
                                scan.donations.append(_Donation(
                                    value=val, target=jt, call=node,
                                    stmt=st, index=idx))
                    # NL-JAX05 sink: tainted operand reaching a jit call
                    hit = self._taint_hit(node, tainted)
                    if hit is not None:
                        scan.taint_sinks.append((node, jt) + hit)
            # NL-JAX05 taint propagation (after sink check: a statement
            # that both launders and dispatches is judged on entry state)
            if isinstance(st.node, ast.Assign):
                for t in st.node.targets:
                    if isinstance(t, ast.Name):
                        verdict = self._taint_verdict(
                            st.node.value, tainted)
                        if verdict is None:
                            tainted.pop(t.id, None)
                        else:
                            tainted[t.id] = verdict
            # NL-JAX06 host-sync sites
            for root in exprs:
                for node in ast.walk(root):
                    if isinstance(node, ast.Call):
                        desc = self._classify_host_sync(node, mi)
                        if desc:
                            scan.host_syncs.append(_HostSync(desc, node))

        # a donated bare-parameter operand consumes the CALLER's buffer
        # no matter what this function does with the local name after —
        # the wrapper itself donates that position (summary propagation)
        for don in scan.donations:
            if don.value in params:
                scan.consumed_params[params.index(don.value)] = don.target
        return scan

    def _alias_target(self, value: ast.expr, mi: ModuleInfo) \
            -> Optional[JitTarget]:
        """JitTarget for ``x = jit_obj`` / ``x = a if cond else b`` —
        the conditional carries the union of donation sets."""
        if isinstance(value, ast.IfExp):
            a = self._alias_target(value.body, mi)
            b = self._alias_target(value.orelse, mi)
            if a is None and b is None:
                return None
            a = a or JitTarget("", mi.relpath, 0)
            b = b or JitTarget("", mi.relpath, 0)
            keep = a if a.donating or not b.donating else b
            return JitTarget(
                display=keep.display or (a.display or b.display),
                relpath=keep.relpath, line=keep.line or a.line or b.line,
                donate_pos=a.donate_pos | b.donate_pos,
                donate_names=a.donate_names | b.donate_names,
            )
        if isinstance(value, (ast.Name, ast.Attribute)):
            fake = ast.Call(func=value, args=[], keywords=[])
            return self.registry.resolve(fake, mi, self.project)
        return None

    def _resolve_jit(self, call: ast.Call, mi: ModuleInfo,
                     aliases: dict[str, JitTarget]) -> Optional[JitTarget]:
        if isinstance(call.func, ast.Name) and call.func.id in aliases:
            return aliases[call.func.id]
        return self.registry.resolve(call, mi, self.project)

    @staticmethod
    def _donated_operands(call: ast.Call, jt: JitTarget) -> list[ast.expr]:
        out = []
        for p in jt.donate_pos:
            if p < len(call.args) \
                    and not isinstance(call.args[p], ast.Starred):
                out.append(call.args[p])
        for kw in call.keywords:
            if kw.arg in jt.donate_names:
                out.append(kw.value)
        return out

    @staticmethod
    def _read_after(stmts: list[_Stmt], don: _Donation) \
            -> Optional[tuple[ast.AST, int]]:
        """First read of the donated value after the consuming statement
        (branch-compatible paths only); None when it is rebound first or
        never touched again."""
        rebound_at = _assigned_names(don.stmt.node)
        if don.value in rebound_at:
            return None  # ``x = f(x)`` — rebound by its own statement
        for st in stmts[don.index + 1:]:
            if not _paths_compatible(don.stmt.path, st.path):
                continue
            node = _reads_value(_stmt_exprs(st.node), don.value)
            if node is not None:
                return node, getattr(st.node, "lineno", 0)
            if don.value in _assigned_names(st.node):
                return None  # rebound before any read on this path
        return "fell-through"  # type: ignore[return-value]

    # -- NL-JAX05 taint ------------------------------------------------------
    # An int derived from len() only churns shapes when it reaches a SIZE
    # position (array-constructor dims, list multiplication); a container
    # whose length is request-dependent churns wherever it is handed to a
    # program (asarray/stack of it bakes len() into the operand shape).
    _SHAPE_CONSTRUCTORS = {
        "zeros", "ones", "full", "empty", "arange", "eye", "tile",
        "repeat", "broadcast_to", "reshape", "resize", "linspace",
    }

    @staticmethod
    def _is_laundered(value: ast.expr) -> bool:
        for node in ast.walk(value):
            if isinstance(node, ast.Call):
                leaf = (dotted_name(node.func) or "").split(".")[-1]
                if any(f in leaf.lower() for f in _BUCKET_FRAGMENTS):
                    return True
        return False

    def _taint_verdict(self, value: ast.expr,
                       tainted: dict) -> Optional[tuple[int, str, str]]:
        """(seed line, seed description, kind) when the expression carries
        a request-dependent size; kind is 'int' (a scalar count) or
        'sized' (a container whose LENGTH is request-dependent).  None
        when clean or laundered through a bucketing helper."""
        if self._is_laundered(value):
            return None
        seed: Optional[tuple[int, str]] = None
        sized = False
        for node in ast.walk(value):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id == "len":
                seed = seed or (node.lineno, "len(...)")
            elif isinstance(node, ast.Name) and node.id in tainted:
                line, desc, kind = tainted[node.id]
                seed = seed or (line, desc)
                sized = sized or kind == "sized"
        if seed is None:
            return None
        # a list/comprehension built with a tainted count has a
        # request-dependent LENGTH: the taint graduates from scalar to
        # shape ("sized")
        if not sized:
            for node in ast.walk(value):
                if isinstance(node, (ast.List, ast.ListComp,
                                     ast.GeneratorExp)):
                    sized = True
                    break
        return seed + (("sized" if sized else "int"),)

    def _taint_hit(self, call: ast.Call,
                   tainted: dict) -> Optional[tuple[str, int]]:
        """(description, seed line) when an operand of a jit dispatch
        carries an unlaundered request-dependent size in a position that
        determines the program's shape."""
        for arg in list(call.args) + [k.value for k in call.keywords]:
            if self._is_laundered(arg):
                continue
            # a request-sized container anywhere in the operand: its
            # length becomes the operand shape
            for node in ast.walk(arg):
                if isinstance(node, ast.Name) and node.id in tainted \
                        and tainted[node.id][2] == "sized":
                    line, seed, _k = tainted[node.id]
                    return (f"'{node.id}' has a length derived from "
                            f"{seed} at line {line}", line)
            # a tainted scalar (or a bare len()) inside a SIZE position:
            # array-constructor dims or list multiplication
            for node in ast.walk(arg):
                size_exprs: list[ast.AST] = []
                if isinstance(node, ast.Call):
                    leaf = (dotted_name(node.func) or "").split(".")[-1]
                    if leaf in self._SHAPE_CONSTRUCTORS:
                        size_exprs = list(node.args) \
                            + [k.value for k in node.keywords]
                elif isinstance(node, ast.BinOp) \
                        and isinstance(node.op, ast.Mult) \
                        and (isinstance(node.left, ast.List)
                             or isinstance(node.right, ast.List)):
                    size_exprs = [node.right if isinstance(node.left,
                                                           ast.List)
                                  else node.left]
                for se in size_exprs:
                    for sub in ast.walk(se):
                        if isinstance(sub, ast.Call) \
                                and isinstance(sub.func, ast.Name) \
                                and sub.func.id == "len":
                            return ("sizes an operand with len(...) "
                                    "directly", sub.lineno)
                        if isinstance(sub, ast.Name) and sub.id in tainted:
                            line, seed, _k = tainted[sub.id]
                            return (f"'{sub.id}' derives from {seed} at "
                                    f"line {line}", line)
        return None

    # -- NL-JAX06 host-sync classification ----------------------------------
    def _classify_host_sync(self, call: ast.Call,
                            mi: ModuleInfo) -> Optional[str]:
        func = call.func
        d = dotted_name(func)
        if d == "jax.block_until_ready":
            return "jax.block_until_ready() blocks on the device"
        if isinstance(func, ast.Attribute):
            if func.attr == "block_until_ready":
                return ".block_until_ready() blocks on the device"
            if func.attr == "item" and not call.args and not call.keywords \
                    and "jax" in mi.ctx.imports:
                return ".item() forces a device->host sync"
        if isinstance(func, ast.Name) and func.id in _HOST_SYNC_CASTS:
            if self._mentions_device(call.args):
                return (f"{func.id}() of a device expression forces a "
                        "device->host sync")
        if d is not None and d.split(".")[0] in _NUMPY_ROOTS \
                and d.split(".")[-1] in {"asarray", "array"}:
            if self._mentions_device(call.args):
                return (f"{d}() of a device expression forces a "
                        "device->host transfer")
        return None

    @staticmethod
    def _mentions_device(exprs: list) -> bool:
        for root in exprs:
            for node in ast.walk(root):
                d = dotted_name(node)
                if d and d.split(".")[0] in _DEVICE_ROOTS:
                    return True
        return False

    # -- NL-JAX06 role propagation ------------------------------------------
    def _propagate_roles(self) -> None:
        """entry_roles[qualname][role] = (depth, (caller, line)) — the
        same bounded fixed point as held-lock propagation, over thread
        roles instead of lock identities."""
        self.entry_roles: dict[str, dict] = \
            {q: {} for q in self.project.functions}
        self.role_blocked: set[str] = set()
        for q, fi in self.project.functions.items():
            role = self._declared_role(fi)
            if role == "none":
                self.role_blocked.add(q)
            elif role is not None:
                self.entry_roles[q][role] = (0, None)
        worklist = list(self.project.functions.values())
        while worklist:
            fi = worklist.pop()
            base = self.entry_roles[fi.qualname]
            if not base:
                continue
            for site in fi.calls:
                line = getattr(site.node, "lineno", 0)
                for callee in site.callees:
                    if callee in self.role_blocked:
                        continue
                    dest = self.entry_roles.get(callee)
                    if dest is None:
                        continue
                    changed = False
                    for role, (depth, _p) in base.items():
                        nd = depth + 1
                        if nd > MAX_HELD_DEPTH:
                            continue
                        if role not in dest or dest[role][0] > nd:
                            dest[role] = (nd, (fi.qualname, line))
                            changed = True
                    if changed:
                        worklist.append(self.project.functions[callee])

    def _declared_role(self, fi: FunctionInfo) -> Optional[str]:
        ctx = self.project.modules[fi.relpath].ctx
        first = min([fi.node.lineno]
                    + [d.lineno for d in fi.node.decorator_list])
        for lineno in (fi.node.lineno, first - 1):
            if 1 <= lineno <= len(ctx.lines):
                m = _THREAD_ROLE_RE.search(ctx.lines[lineno - 1])
                if m:
                    return m.group(1)
        return None

    def role_chain(self, qualname: str, role: str) -> str:
        steps: list[str] = []
        q = qualname
        for _ in range(MAX_HELD_DEPTH):
            info = self.entry_roles.get(q, {}).get(role)
            if info is None or info[1] is None:
                break
            caller, line = info[1]
            cfi = self.project.functions.get(caller)
            steps.append(f"{cfi.display() if cfi else caller}:{line}")
            q = caller
        return " <- ".join(steps)


def _dataflow(project: ProjectContext) -> DataflowContext:
    df = getattr(project, "_nornlint_dataflow", None)
    if df is None:
        df = DataflowContext(project)
        project._nornlint_dataflow = df  # type: ignore[attr-defined]
    return df


# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------

@register_project(
    "NL-JAX04",
    "error",
    "use-after-donate: a value passed to a jitted function declaring "
    "donate_argnums is read again afterwards (or survives an exception "
    "path) — XLA freed that buffer at dispatch",
)
def nl_jax04(project: ProjectContext) -> Iterator[Finding]:
    rule = nl_jax04
    df = _dataflow(project)
    for fi in project.functions.values():
        scan = df.scans.get(fi.qualname)
        if scan is None:
            continue
        for don in scan.donations:
            donate_line = getattr(don.call, "lineno", 0)
            where = (f"{don.target.display} "
                     f"({don.target.relpath}:{don.target.line})")
            rebound = don.value in _assigned_names(don.stmt.node)
            if rebound:
                # happy path rebinds in the same statement; the hazard
                # left is the exception path for state that outlives the
                # frame: the attr still references the consumed buffer
                # when the dispatch raises mid-donation
                if "." not in don.value:
                    continue  # a local dies with the frame on raise
                if _exception_path_protected(don.stmt.tries, don.value):
                    continue
                yield _finding(
                    rule, fi, don.call,
                    f"'{don.value}' is donated to {where} and rebound by "
                    "the same statement, but still references the "
                    "consumed buffer if the call raises — wrap the "
                    "dispatch in a try whose except drops or rebuilds "
                    f"'{don.value}' before re-raising "
                    "(docs/linting.md#nl-jax04)",
                )
                continue
            read = DataflowContext._read_after(scan.stmts, don)
            if read is None:
                continue  # rebound before any read
            if read == "fell-through":
                if "." not in don.value:
                    continue  # consumed local, never touched again: fine
                yield _finding(
                    rule, fi, don.call,
                    f"attribute '{don.value}' is donated to {where} at "
                    f"line {donate_line} and never rebound — it "
                    "permanently references a freed buffer; assign the "
                    "program's result back (docs/linting.md#nl-jax04)",
                )
                continue
            _node, read_line = read
            yield _finding(
                rule, fi, don.call,
                f"'{don.value}' is donated to {where} at line "
                f"{donate_line} and read again at line {read_line} — "
                "the buffer is freed on donation; rebind the result "
                "before reading, or call the non-donating variant "
                "(docs/linting.md#nl-jax04)",
            )


@register_project(
    "NL-JAX05",
    "warning",
    "unbounded shape-class dispatch: a jit/shard_map call site whose "
    "operands derive from unbucketed request-dependent sizes (len(...), "
    "un-pow2'd k) — every distinct size compiles a fresh program",
)
def nl_jax05(project: ProjectContext) -> Iterator[Finding]:
    rule = nl_jax05
    df = _dataflow(project)
    for fi in project.functions.values():
        scan = df.scans.get(fi.qualname)
        if scan is None:
            continue
        for call, jt, desc, _seed_line in scan.taint_sinks:
            yield _finding(
                rule, fi, call,
                f"operand of jitted {jt.display} "
                f"({jt.relpath}:{jt.line}) {desc} without passing "
                "through a bucketing helper (round_up_pow2 / pow2_class "
                "/ *bucket*) — every distinct request size compiles a "
                "fresh program; bucket the size first "
                "(docs/linting.md#nl-jax05)",
            )


@register_project(
    "NL-JAX06",
    "warning",
    "host-device sync (.item(), float()/np.asarray() of a device value, "
    "block_until_ready) reachable from a function annotated "
    "'# nornlint: thread-role=...' — the owner/dispatcher loop stalls "
    "behind one host round-trip",
)
def nl_jax06(project: ProjectContext) -> Iterator[Finding]:
    rule = nl_jax06
    df = _dataflow(project)
    for fi in project.functions.values():
        scan = df.scans.get(fi.qualname)
        if scan is None or not scan.host_syncs:
            continue
        roles = df.entry_roles.get(fi.qualname) or {}
        if not roles:
            continue
        role = sorted(roles)[0]
        chain = df.role_chain(fi.qualname, role)
        via = f" (reachable via {chain})" if chain else ""
        for sync in scan.host_syncs:
            yield _finding(
                rule, fi, sync.node,
                f"{sync.desc} on the '{role}' thread{via} — every queued "
                "request stalls behind this round-trip; move the sync "
                "off the dispatcher loop, or annotate the helper "
                "'# nornlint: thread-role=none' with a rationale if the "
                "sync is deliberately bounded (docs/linting.md#nl-jax06)",
            )
