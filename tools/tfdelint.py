"""AST project lint: lock discipline, greedy-path rng ban, knob audit.

Three rules over the source tree (no execution, no jax import needed for
the first two):

1. **Lock discipline** — classes whose methods run on more than one
   thread (HTTP handler threads vs the step/rollup loop) are declared in
   LOCKED_CLASSES with the lock attribute that guards their shared
   state. Inside their methods, attribute writes must happen under a
   ``with self.<lock>`` block:

   - any augmented assignment to an attribute (``rep.outstanding += n``,
     ``self._evictions += 1``) — the read-modify-write the GIL does NOT
     make atomic across the read and the write, the exact bug class the
     PR 8 review fixed by hand in the Router;
   - plain assignment to a ``self.*`` attribute or a ``self.*[...]``
     subscript (``self._inflight[tid] = idx``) — the publish of shared
     state.

   Plain assignment to a *local* object's attribute stays legal
   (constructing a new object before publishing it is the standard
   pattern). ``__init__`` and per-class allow-listed methods/attributes
   are exempt; classes whose instances are serialized by an EXTERNAL
   lock (``_BatcherBase`` runs entirely under ``ReplicaServer.lock``)
   are declared with ``external=...`` and skipped with that reason in
   the audit output, so the exemption is a reviewable line here, not
   silence.

   A spec may additionally name ``guarded_attrs``: attributes where ANY
   access — reads included — must happen under the lock, because the
   object behind the attribute is only single-threaded by virtue of
   that lock (``ReplicaServer.batcher``: the step loop mutates the
   batcher's queue under ``self.lock``, so even ``len(b._queue)`` from
   a handler thread is a race — the exact bug the PR 14 review fixed in
   the Router's /load path).

2. **Greedy-path `jax.random.split` ban** — in `tfde_tpu/inference/`,
   every ``jax.random.split`` call must be lexically inside an ``if``
   whose condition mentions ``temperature`` or ``greedy``: splitting on
   the greedy path burns a key derivation per token for a sampler that
   never consumes it, and (worse) makes greedy outputs depend on the rng
   plumbing, breaking the bit-identity pins.

3. **Knob audit** — every string literal matching ``TFDE_[A-Z0-9_]+``
   in `tfde_tpu/` and `tools/` must be registered in
   `tfde_tpu/knobs.py` (prefix families like ``TFDE_RETRY_`` count);
   an unregistered name is a knob the operator cannot discover and the
   import-time typo check cannot defend. The reverse holds too: a
   registered knob that no literal in those trees names (itself, its
   family's prefix, or for a family one of its members) has lost its
   last reader and goes with it.

Run: ``python tools/tfdelint.py [--root DIR]`` — exits 1 and lists
violations. `tools/lintgate.py` embeds the same pass and diffs its
output against the checked-in baseline.
"""

import argparse
import ast
import dataclasses
import os
import re
import sys
from typing import Dict, List, Optional, Set, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

_KNOB_RE = re.compile(r"TFDE_[A-Z0-9_]+\Z")


@dataclasses.dataclass(frozen=True)
class LockSpec:
    """One threaded class's lock-discipline declaration."""

    #: attribute name of the guarding lock on self ('_lock', 'lock')
    lock: Optional[str] = "_lock"
    #: methods exempt from the check (beyond __init__): constructors,
    #: single-threaded setup, or methods that acquire the lock via a
    #: helper the AST pass can't see through
    exempt_methods: Tuple[str, ...] = ()
    #: self-attributes writable without the lock (documented reasons)
    exempt_attrs: Tuple[str, ...] = ()
    #: self-attributes where ANY access (reads included) must hold the
    #: lock: the attribute is a handle to an object that is only
    #: single-threaded under this lock
    guarded_attrs: Tuple[str, ...] = ()
    #: set when the class is serialized by a lock its OWNER holds; the
    #: class is skipped and the reason surfaces in the audit output
    external: Optional[str] = None


#: (repo-relative file, class name) -> LockSpec. Adding a threaded class
#: here is part of adding the class; the audit census in lintgate's
#: baseline pins this table's coverage.
LOCKED_CLASSES: Dict[Tuple[str, str], LockSpec] = {
    ("tfde_tpu/inference/router.py", "Router"): LockSpec(
        lock="_lock",
        # snapshot/exposition methods read shared state without the lock
        # by design (stale reads are fine for status surfaces); writes
        # anywhere must still be locked — the rule below only exempts a
        # method from the check entirely, so keep this list empty and
        # let reads pass (reads are never flagged).
    ),
    ("tfde_tpu/observability/aggregate.py", "ClusterAggregator"): LockSpec(
        lock="_lock",
    ),
    ("tfde_tpu/observability/metrics.py", "Registry"): LockSpec(
        lock="_lock",
    ),
    ("tfde_tpu/inference/server.py", "_BatcherBase"): LockSpec(
        external="ReplicaServer.lock — the HTTP server holds its RLock "
                 "around every submit/step/take_progress/cancel call; the "
                 "batcher itself is single-threaded by contract",
    ),
    ("tfde_tpu/inference/router.py", "ReplicaServer"): LockSpec(
        lock="lock",
        # the batcher is the object _BatcherBase's external-lock entry
        # above points at: it is only single-threaded while this
        # server's lock is held, so even READING through self.batcher
        # from a handler thread races the step loop
        guarded_attrs=("batcher",),
    ),
    # KV-capacity observability (PR 15): written from the batcher's step
    # loop under ReplicaServer.lock but READ from handler threads
    # (/load's kv block) and test threads, so each carries its own lock
    ("tfde_tpu/observability/capacity.py", "CapacityLedger"): LockSpec(
        lock="_lock",
        # the counters of every kind of layer live in this one class
        # (`_count` adds, `counters` copies): no access outside the lock,
        # reads included
        guarded_attrs=("_counters",),
    ),
    ("tfde_tpu/observability/capacity.py", "UsageMeter"): LockSpec(
        lock="_lock",
    ),
    ("tfde_tpu/observability/capacity.py", "UsageLog"): LockSpec(
        lock="_lock",
        # called only from write() with the lock already held (the
        # _locked suffix is the contract; the AST pass can't see a
        # caller-held lock)
        exempt_methods=("_compact_locked",),
    ),
    # boot & readiness (PR 17): phase edges arrive from the owner's
    # boot thread while /load handler threads snapshot() and the
    # module-level serving-path marks fan in from the batcher step loop
    ("tfde_tpu/observability/boot.py", "BootLedger"): LockSpec(
        lock="_lock",
        # called only from begin()/end()/ready()/new_epoch() with the
        # lock already held (the _locked suffix is the contract)
        exempt_methods=("_close_open_locked",),
    ),
    # paged KV (PR 18): allocations/frees arrive from the batcher step
    # loop under ReplicaServer.lock, but stats() is read from /load
    # handler threads and the paged capacity ledger, so the free-list
    # and refcounts carry their own lock
    ("tfde_tpu/inference/paged.py", "BlockPool"): LockSpec(
        lock="_lock",
    ),
    ("tfde_tpu/observability/capacity.py", "PagedCapacityLedger"): LockSpec(
        lock="_lock",
    ),
}

#: files whose jax.random.split calls must be temperature-guarded
GREEDY_BAN_DIRS = ("tfde_tpu/inference",)

#: files exempt from the knob audit: the registry itself (it declares
#: every name) and this linter (it documents the pattern)
KNOB_AUDIT_EXEMPT = ("tfde_tpu/knobs.py", "tools/tfdelint.py")


def _iter_py(root: str, subdirs=("tfde_tpu", "tools")) -> List[str]:
    out = []
    for sub in subdirs:
        base = os.path.join(root, sub)
        for dirpath, _dirnames, filenames in os.walk(base):
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    out.append(os.path.join(dirpath, fn))
    return sorted(out)


def _rel(root: str, path: str) -> str:
    return os.path.relpath(path, root).replace(os.sep, "/")


# -- rule 1: lock discipline --------------------------------------------------
def _with_holds_lock(node: ast.With, lock: str) -> bool:
    for item in node.items:
        e = item.context_expr
        if (isinstance(e, ast.Attribute) and e.attr == lock
                and isinstance(e.value, ast.Name) and e.value.id == "self"):
            return True
    return False


class _LockVisitor(ast.NodeVisitor):
    def __init__(self, spec: LockSpec, filename: str, cls: str):
        self.spec = spec
        self.filename = filename
        self.cls = cls
        self.violations: List[str] = []
        self._lock_depth = 0
        self._method = None

    def _flag(self, node, what: str) -> None:
        self.violations.append(
            f"{self.filename}:{node.lineno}: {self.cls}.{self._method}: "
            f"{what} outside `with self.{self.spec.lock}` — shared state "
            f"mutated from handler threads must hold the class lock "
            f"(tools/tfdelint.py lock-discipline rule)")

    def check_method(self, fn: ast.FunctionDef) -> None:
        self._method = fn.name
        self._lock_depth = 0
        for stmt in fn.body:
            self.visit(stmt)

    def visit_With(self, node: ast.With) -> None:
        held = _with_holds_lock(node, self.spec.lock)
        if held:
            self._lock_depth += 1
        for stmt in node.body:
            self.visit(stmt)
        if held:
            self._lock_depth -= 1

    def visit_FunctionDef(self, node) -> None:
        # a nested function (thread target, callback) runs on its own
        # schedule: its body is checked with the lock NOT held, whatever
        # the enclosing context (the closure outlives the with block)
        saved = self._lock_depth
        self._lock_depth = 0
        for stmt in node.body:
            self.visit(stmt)
        self._lock_depth = saved

    visit_AsyncFunctionDef = visit_FunctionDef

    def _is_self_attr_target(self, t) -> bool:
        return (isinstance(t, ast.Attribute)
                and isinstance(t.value, ast.Name) and t.value.id == "self")

    def _is_self_subscript_target(self, t) -> bool:
        return (isinstance(t, ast.Subscript)
                and self._is_self_attr_target(t.value))

    def _attr_name(self, t) -> str:
        if isinstance(t, ast.Subscript):
            t = t.value
        return t.attr if isinstance(t, ast.Attribute) else "?"

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        # ANY attribute aug-assign (self.* or local-object.*) is a
        # read-modify-write on possibly-shared state
        t = node.target
        if isinstance(t, (ast.Attribute, ast.Subscript)) \
                and self._lock_depth == 0:
            name = self._attr_name(t)
            if name not in self.spec.exempt_attrs:
                self._flag(node, f"augmented write to .{name}")
        self.generic_visit(node)

    def visit_Assign(self, node: ast.Assign) -> None:
        if self._lock_depth == 0:
            for t in node.targets:
                if (self._is_self_attr_target(t)
                        or self._is_self_subscript_target(t)):
                    name = self._attr_name(t)
                    if name not in self.spec.exempt_attrs:
                        self._flag(node, f"write to self.{name}")
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute) -> None:
        # guarded attrs: reads count too — the attribute is a handle to
        # an object whose thread-safety IS this lock
        if (self._lock_depth == 0
                and node.attr in self.spec.guarded_attrs
                and isinstance(node.value, ast.Name)
                and node.value.id == "self"):
            self._flag(node, f"access to self.{node.attr}")
        self.generic_visit(node)


def lint_locks(root: str, table=None) -> Tuple[List[str], Dict[str, str]]:
    """Returns (violations, audit) where audit maps 'file::Class' to its
    status ('checked' or the external-lock reason)."""
    table = LOCKED_CLASSES if table is None else table
    violations: List[str] = []
    audit: Dict[str, str] = {}
    for (relpath, clsname), spec in sorted(table.items()):
        path = os.path.join(root, relpath)
        key = f"{relpath}::{clsname}"
        if spec.external is not None:
            audit[key] = f"external lock: {spec.external}"
            continue
        try:
            tree = ast.parse(open(path).read(), filename=relpath)
        except (OSError, SyntaxError) as e:
            violations.append(f"{relpath}: could not parse for lock "
                              f"discipline: {e}")
            continue
        cls = next((n for n in ast.walk(tree)
                    if isinstance(n, ast.ClassDef) and n.name == clsname),
                   None)
        if cls is None:
            violations.append(
                f"{relpath}: class {clsname} not found — LOCKED_CLASSES "
                f"is stale; update tools/tfdelint.py")
            continue
        audit[key] = "checked"
        for item in cls.body:
            if not isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if item.name == "__init__" or item.name in spec.exempt_methods:
                continue
            v = _LockVisitor(spec, relpath, clsname)
            v.check_method(item)
            violations.extend(v.violations)
    return violations, audit


# -- rule 2: greedy-path jax.random.split ban ---------------------------------
def _is_random_split(call: ast.Call) -> bool:
    f = call.func
    return (isinstance(f, ast.Attribute) and f.attr == "split"
            and isinstance(f.value, ast.Attribute) and f.value.attr == "random")


class _SplitVisitor(ast.NodeVisitor):
    """Tracks whether any enclosing `if` condition mentions temperature/
    greedy/sampled; flags unguarded jax.random.split calls. BOTH branches
    of a guarded `if` count as guarded — the author has branched on the
    greedy/sampling distinction, and the split belongs to whichever side
    they put it on. A function whose NAME marks it as the sampling-only
    program (`*_sampled`) is guarded throughout: it is a distinct jit
    entry point the greedy path never calls (speculative.py's
    `_spec_round_sampled` vs `_spec_round`)."""

    GUARD_WORDS = ("temperature", "greedy", "sampled")

    def __init__(self, filename: str):
        self.filename = filename
        self.violations: List[str] = []
        self._guard = 0

    def _guarded_test(self, test) -> bool:
        src = ast.dump(test)
        return any(w in src for w in self.GUARD_WORDS)

    def visit_FunctionDef(self, node) -> None:
        guarded = "sampled" in node.name
        if guarded:
            self._guard += 1
        self.generic_visit(node)
        if guarded:
            self._guard -= 1

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_If(self, node: ast.If) -> None:
        guarded = self._guarded_test(node.test)
        if guarded:
            self._guard += 1
        for stmt in node.body:
            self.visit(stmt)
        for stmt in node.orelse:
            self.visit(stmt)
        if guarded:
            self._guard -= 1

    def visit_IfExp(self, node: ast.IfExp) -> None:
        guarded = self._guarded_test(node.test)
        if guarded:
            self._guard += 1
        self.visit(node.body)
        self.visit(node.orelse)
        if guarded:
            self._guard -= 1
        self.visit(node.test)

    def visit_Call(self, node: ast.Call) -> None:
        if _is_random_split(node) and self._guard == 0:
            self.violations.append(
                f"{self.filename}:{node.lineno}: jax.random.split on an "
                f"unguarded path — in inference code every split must sit "
                f"under an `if` mentioning temperature/greedy, or greedy "
                f"decoding pays for (and depends on) sampling rng "
                f"(tools/tfdelint.py greedy-split rule)")
        self.generic_visit(node)


def lint_greedy_split(root: str, dirs=GREEDY_BAN_DIRS) -> List[str]:
    violations: List[str] = []
    for path in _iter_py(root, dirs):
        rel = _rel(root, path)
        try:
            tree = ast.parse(open(path).read(), filename=rel)
        except (OSError, SyntaxError) as e:
            violations.append(f"{rel}: could not parse: {e}")
            continue
        v = _SplitVisitor(rel)
        v.visit(tree)
        violations.extend(v.violations)
    return violations


# -- rule 3: knob audit -------------------------------------------------------
def collect_knob_literals(root: str, subdirs=("tfde_tpu", "tools")):
    """All (file, line, name) TFDE_* string literals in the tree."""
    hits = []
    for path in _iter_py(root, subdirs):
        rel = _rel(root, path)
        if rel in KNOB_AUDIT_EXEMPT:
            continue
        try:
            tree = ast.parse(open(path).read(), filename=rel)
        except (OSError, SyntaxError):
            continue
        for node in ast.walk(tree):
            if (isinstance(node, ast.Constant) and isinstance(node.value, str)
                    and _KNOB_RE.match(node.value)):
                hits.append((rel, node.lineno, node.value))
    return hits


def lint_knobs(root: str) -> Tuple[List[str], List[str]]:
    """Returns (violations, sorted unique knob names seen)."""
    from tfde_tpu import knobs

    violations = []
    seen: Set[str] = set()
    for rel, lineno, name in collect_knob_literals(root):
        seen.add(name)
        if not knobs.is_registered(name):
            violations.append(
                f"{rel}:{lineno}: env knob {name!r} is not registered in "
                f"tfde_tpu/knobs.py — add a Knob entry (name, kind, "
                f"default, doc) so the typo check and the README table "
                f"cover it (tools/tfdelint.py knob-audit rule)")
    return violations, sorted(seen)


def lint_unread_knobs(seen) -> List[str]:
    """Registered knobs that none of the `seen` literals reads. A member
    composed from its family's prefix (``TFDE_RETRY_`` + suffix) is read
    by the prefix; a family is read by any member."""
    from tfde_tpu import knobs

    families = {s for s in seen
                if s in knobs.REGISTRY and knobs.REGISTRY[s].prefix}
    violations = []
    for name, knob in sorted(knobs.REGISTRY.items()):
        read = (name in seen
                or any(name.startswith(f) for f in families)
                or (knob.prefix and any(s.startswith(name) for s in seen)))
        if not read:
            violations.append(
                f"tfde_tpu/knobs.py: knob {name!r} is registered but no "
                f"file under tfde_tpu/ or tools/ reads it — delete the "
                f"Knob entry with its last reader (tools/tfdelint.py "
                f"knob-audit rule)")
    return violations


# -- entry points -------------------------------------------------------------
def lint_repo(root: str = ROOT) -> dict:
    """Run all three rules; returns {violations: [...], audit: {...},
    knobs_seen: [...]} — the structure lintgate baselines."""
    lock_v, audit = lint_locks(root)
    split_v = lint_greedy_split(root)
    knob_v, seen = lint_knobs(root)
    return {
        "violations": lock_v + split_v + knob_v + lint_unread_knobs(seen),
        "lock_audit": audit,
        "knobs_seen": seen,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=ROOT)
    args = ap.parse_args()
    result = lint_repo(args.root)
    for key in ("lock_audit",):
        for cls, status in sorted(result[key].items()):
            print(f"  {cls}: {status}")
    print(f"  knob audit: {len(result['knobs_seen'])} TFDE_* names seen")
    if result["violations"]:
        print("tfdelint: FAIL")
        for v in result["violations"]:
            print(f"  - {v}")
        return 1
    print("tfdelint: pass")
    return 0


if __name__ == "__main__":
    sys.exit(main())
