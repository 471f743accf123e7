"""RL0xx — lock discipline.

Mechanizes the locking contracts written in prose in
``docs/ARCHITECTURE.md`` (I2 atomic apply, I4 no-wait dispatch):

* RL001: a guarded attribute is read or written on a path that does not
  (lexically) hold its lock. Guarded-by relations come from two sources:
  the declarative :data:`SPEC` registry for the classes whose contracts
  are part of the architecture (``GraphQueryServer._ingest_lock`` /
  ``GraphQueryServer._serve_lock`` — the serving tier's seal-swap planes —
  ``GraphRPCServer._conn_lock``, ``SnapshotQueryEngine._rank_lock``,
  ``Spans._lock``), and
  inference for everything else —
  any attribute *written* under ``with self.<lock>`` somewhere in a class
  is treated as guarded by that lock everywhere in the class.
* RL002: inconsistent nested acquisition order — the same class acquires
  lock B inside lock A on one path and A inside B on another (a deadlock
  seed the moment two threads take the two paths).
* RL003: a blocking call (``.result()``, ``.block_until_ready()``,
  ``.join()``, ``.wait()``, ``sleep``) made while holding a lock — the
  exact shape that serializes the apply plane the paper's no-wait
  dispatch rule exists to avoid.

Scope and honesty: the analysis is lexical and intra-method. ``with
self._lock:`` blocks are the only acquisition form tracked (the repo has
no bare ``.acquire()`` calls); calls into other methods are not followed,
so a helper that *requires* the lock held is the caller's responsibility —
exactly the contract the registry documents. ``__init__`` is exempt
(objects under construction are unshared). Closures defined inside a
method are checked with an *empty* held-set: they execute later, on
whatever thread calls them, so a definition site inside a ``with`` block
proves nothing.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Iterable, Optional

from repro.analysis.staticcheck.core import (FileContext, Finding,
                                             register_checker, register_rule)

RL001 = register_rule(
    "RL001", "guarded attribute accessed without holding its lock")
RL002 = register_rule(
    "RL002", "inconsistent lock-acquisition order within a class")
RL003 = register_rule(
    "RL003", "blocking call while holding a lock (no-wait dispatch, I4)")


@dataclasses.dataclass(frozen=True)
class ClassLockSpec:
    """Guarded-by map for one class: lock attr -> guarded attr names."""
    locks: dict[str, frozenset[str]]
    exempt_methods: frozenset[str] = frozenset({"__init__"})


# The architectural locking contracts. These override inference: if a
# class name appears here, exactly these relations are enforced.
SPEC: dict[str, ClassLockSpec] = {
    # the seal-swap discipline: the re-entrant write-plane lock serializes
    # ingest/seal/re-shard state, the read-plane lock guards only the
    # pending queue + published snapshot + serving counters. Query compute
    # runs on immutable published views outside BOTH. The only permitted
    # runtime nesting is _ingest_lock -> _serve_lock (the seal-time
    # publish); nothing may acquire the write lock while holding the read
    # lock (RL002 would flag the lexical shape of such a path).
    "GraphQueryServer": ClassLockSpec(locks={
        "_ingest_lock": frozenset({
            "graph", "_seals", "reshard_events",
            # degraded mode (I11): the failed-seal backlog and its
            # lifetime counter mutate only on the write plane (step /
            # reseal); the read plane stamps responses from the
            # lock-free _degraded_hint instead
            "_seal_backlog", "seal_failures",
        }),
        "_serve_lock": frozenset({
            "_pending_cheap", "_pending_expensive", "_serving",
            "_published", "_touch_buffer", "_touch_buffered",
            "served", "windows", "shed_overload", "shed_deadline",
            "queue_wait_s",
            "latencies_s", "_kind_latencies", "_lane_latencies",
        }),
        # prewarm mailbox: the one-slot coalescing target the publish
        # path hands to the trace-prewarm worker, plus its run counter
        "_prewarm_lock": frozenset({
            "_prewarm_target", "prewarm_runs",
        }),
    }),
    # the span accumulator every serving stage writes to: a leaf lock
    # (nothing is acquired while it is held) over its three tallies
    "Spans": ClassLockSpec(locks={
        "_lock": frozenset({"_seconds", "_counts", "_counters"}),
    }),
    # the RPC listener's only shared mutable state is the live-connection
    # set (reader threads add/remove themselves; stop() snapshots it) —
    # everything else is per-connection locals or the query server's own
    # planes above
    "GraphRPCServer": ClassLockSpec(locks={
        "_conn_lock": frozenset({"_conns"}),
    }),
    # WAL writer lock: guards the control-log file handle and its fsync
    # batcher. The per-shard segment writers are deliberately NOT here —
    # each ShardWal is shard-owned state touched only by its shard's
    # seal (sealcheck's plane rules cover that relation)
    "GraphWal": ClassLockSpec(locks={
        "_lock": frozenset({"_control_f", "_control_synced"}),
    }),
    # chaos hook: armed faults are read from the parallel apply plane
    # (seal entry) and mutated from test/operator threads. The stall
    # sleep and the fault raise happen OUTSIDE the lock (RL003)
    "FaultInjector": ClassLockSpec(locks={
        "_lock": frozenset({"_fail_once", "_down", "_stall",
                            "faults_fired"}),
    }),
    # the engine's own lock guards the rank cache and telemetry counters
    # — including the replica-plane counters (mirror hit/miss, routed
    # windows, fan-out histogram), which concurrent flushers race on —
    # independent of the server's coarser lock. The versioned result
    # cache and the prewarm signature memory ride the same lock: the
    # cheap/expensive dispatchers and the prewarm worker all touch them
    "SnapshotQueryEngine": ClassLockSpec(locks={
        "_rank_lock": frozenset({
            "_rank_cache", "rank_cache_hits", "rank_warm_starts",
            "rank_cold_starts", "vectorized_calls",
            "mirror_hits", "mirror_misses", "routed_windows",
            "fanout_hist",
            "_result_cache", "result_cache_hits", "result_cache_misses",
            "result_cache_evictions", "_warm_signatures",
            "_warmed_traces",
        }),
    }),
}

# attribute-call names that block the calling thread
BLOCKING_ATTRS = frozenset(
    {"result", "block_until_ready", "join", "wait", "sleep"})
# mutator method names that count as writes for guard inference
MUTATOR_ATTRS = frozenset(
    {"append", "extend", "insert", "pop", "popitem", "remove", "clear",
     "update", "add", "discard", "setdefault", "sort"})
_LOCK_CTORS = frozenset({"Lock", "RLock"})


def _self_attr(node: ast.AST) -> Optional[str]:
    """'X' when node is exactly ``self.X``."""
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


def _lock_ctor_name(call: ast.AST) -> bool:
    """True for ``threading.Lock()`` / ``Lock()`` / ``threading.RLock()``."""
    if not isinstance(call, ast.Call):
        return False
    fn = call.func
    if isinstance(fn, ast.Attribute):
        return fn.attr in _LOCK_CTORS
    return isinstance(fn, ast.Name) and fn.id in _LOCK_CTORS


def _methods(cls: ast.ClassDef) -> list[ast.FunctionDef]:
    return [n for n in cls.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]


def _infer_spec(cls: ast.ClassDef) -> Optional[ClassLockSpec]:
    """Infer a lock spec for an unregistered class: locks are
    ``self.X = threading.Lock()/RLock()`` in ``__init__``; guarded attrs
    are whatever gets *written* under ``with self.X`` anywhere."""
    lock_names: set[str] = set()
    for m in _methods(cls):
        if m.name != "__init__":
            continue
        for st in ast.walk(m):
            if isinstance(st, ast.Assign) and _lock_ctor_name(st.value):
                for tgt in st.targets:
                    attr = _self_attr(tgt)
                    if attr:
                        lock_names.add(attr)
    if not lock_names:
        return None

    guarded: dict[str, set[str]] = {lk: set() for lk in lock_names}

    def record_writes(stmts: Iterable[ast.stmt], held: frozenset[str]):
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Lambda)):
                continue
            if isinstance(st, (ast.With, ast.AsyncWith)):
                acquired = {a for item in st.items
                            if (a := _self_attr(item.context_expr))
                            in lock_names}
                record_writes(st.body, held | frozenset(acquired))
                continue
            for node in ast.walk(st):
                attr = None
                if isinstance(node, (ast.Assign, ast.AugAssign)):
                    targets = (node.targets
                               if isinstance(node, ast.Assign)
                               else [node.target])
                    for tgt in targets:
                        base = tgt.value if isinstance(tgt, ast.Subscript) \
                            else tgt
                        attr = _self_attr(base)
                        if attr:
                            for lk in held:
                                guarded[lk].add(attr)
                elif (isinstance(node, ast.Call)
                      and isinstance(node.func, ast.Attribute)
                      and node.func.attr in MUTATOR_ATTRS):
                    attr = _self_attr(node.func.value)
                    if attr:
                        for lk in held:
                            guarded[lk].add(attr)
            # statements with nested bodies keep the held set
            for field in ("body", "orelse", "finalbody", "handlers"):
                sub = getattr(st, field, None)
                if sub:
                    record_writes(
                        [s for s in sub if isinstance(s, ast.stmt)], held)

    for m in _methods(cls):
        if m.name != "__init__":
            record_writes(m.body, frozenset())
    locks = {lk: frozenset(attrs - lock_names)
             for lk, attrs in guarded.items() if attrs}
    if not locks:
        return None
    return ClassLockSpec(locks=locks)


class _MethodScanner:
    """Lexical lock-hold walk over one method."""

    def __init__(self, ctx: FileContext, cls_name: str, spec: ClassLockSpec,
                 findings: list[Finding],
                 nest_pairs: list[tuple[str, str, ast.AST]]):
        self.ctx = ctx
        self.cls_name = cls_name
        self.spec = spec
        self.findings = findings
        self.nest_pairs = nest_pairs
        # attr -> the locks that guard it; holding ANY of them satisfies
        # the access (inference can attribute one attr to several locks
        # when it is only ever written under a nested acquisition)
        self.guard_of: dict[str, set[str]] = {}
        for lk, attrs in spec.locks.items():
            for attr in attrs:
                self.guard_of.setdefault(attr, set()).add(lk)

    def scan(self, fn: ast.FunctionDef) -> None:
        self._visit_body(fn.body, frozenset())

    # -- walk ---------------------------------------------------------------
    def _visit_body(self, stmts, held: frozenset[str]) -> None:
        for st in stmts:
            self._visit(st, held)

    def _visit(self, node: ast.AST, held: frozenset[str]) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # a closure runs later, on an unknown thread: empty held-set
            self._visit_body(node.body, frozenset())
            return
        if isinstance(node, ast.Lambda):
            self._visit(node.body, frozenset())
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            acquired = set()
            for item in node.items:
                attr = _self_attr(item.context_expr)
                if attr in self.spec.locks:
                    acquired.add(attr)
                    for outer in held:
                        if outer != attr:
                            self.nest_pairs.append(
                                (outer, attr, item.context_expr))
                else:
                    self._visit(item.context_expr, held)
            self._visit_body(node.body, held | frozenset(acquired))
            return

        attr = _self_attr(node)
        if attr is not None:
            guards = self.guard_of.get(attr)
            if guards and not (held & guards):
                kind = ("write" if isinstance(
                    getattr(node, "ctx", None), (ast.Store, ast.Del))
                    else "read")
                lock = "'" + "'/'".join(sorted(guards)) + "'"
                self.findings.append(self.ctx.finding(
                    node, RL001,
                    f"{kind} of '{self.cls_name}.{attr}' without holding "
                    f"{lock} (guarded attribute)"))
            # still descend: self.X[i] etc. handled by caller's iteration
        if isinstance(node, ast.Call) and held:
            fn = node.func
            if (isinstance(fn, ast.Attribute)
                    and fn.attr in BLOCKING_ATTRS
                    and not isinstance(fn.value, ast.Constant)):
                self.findings.append(self.ctx.finding(
                    node, RL003,
                    f"blocking call '.{fn.attr}()' while holding "
                    f"{sorted(held)} (I4: no-wait dispatch)"))

        for child in ast.iter_child_nodes(node):
            self._visit(child, held)


@register_checker()   # lock discipline applies everywhere
def check_locks(ctx: FileContext):
    findings: list[Finding] = []
    for cls in [n for n in ast.walk(ctx.tree)
                if isinstance(n, ast.ClassDef)]:
        spec = SPEC.get(cls.name) or _infer_spec(cls)
        if spec is None:
            continue
        nest_pairs: list[tuple[str, str, ast.AST]] = []
        for m in _methods(cls):
            if m.name in spec.exempt_methods:
                continue
            _MethodScanner(ctx, cls.name, spec, findings, nest_pairs).scan(m)
        # RL002: (A inside B) and (B inside A) both observed in this class
        orders = {(a, b) for a, b, _ in nest_pairs}
        for a, b, node in nest_pairs:
            if (b, a) in orders:
                findings.append(ctx.finding(
                    node, RL002,
                    f"'{b}' acquired inside '{a}' but the opposite order "
                    f"also occurs in '{cls.name}' (deadlock seed)"))
    return findings
