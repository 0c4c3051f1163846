"""R4 — shared module state mutates only under the module's ``_LOCK``.

Two modules hold state that several threads mutate at once: jax_cost's
registries (the compile-ahead worker fills them from its background
thread while the search thread dispatches) and the trace recorder's
ring and totals in ``core/trace.py`` (every thread that records a span
or a count).  Every mutation of that state — assignment, augmented
increment, subscript store, or mutating method call — must sit
lexically inside a ``with _LOCK:`` block.  Module-level initializers
(outside any function) are exempt; reads are not restricted.
"""
from __future__ import annotations

import ast
import re
from typing import List

from ..lint import Rule, Violation, names_in

#: the lock-guarded module globals of each file (their header comments)
GUARDED = {
    "repro/core/jax_cost.py": re.compile(
        r"^_(RESET_AT|CA_PREFIXES|CA_CANCEL|CA_THREAD|CA_FIRST_ERROR|"
        r"JIT_FNS|SHARD_FNS|STACK_CONSTS|AOT_FNS|AOT_PENDING)$"),
    "repro/core/trace.py": re.compile(r"^_(RING|TOTALS|DROPPED_T1)$"),
}

MUTATORS = {"clear", "update", "pop", "popitem", "setdefault", "add",
            "append", "extend", "remove", "discard", "insert"}

FILES = tuple(GUARDED)


class CounterLockRule(Rule):
    rule_id = "R4"
    title = "shared module state (jax_cost, trace) mutates under _LOCK"

    def applies(self, path: str) -> bool:
        return any(path.endswith(f) for f in FILES)

    def check(self, tree: ast.AST, src: str, path: str) -> List[Violation]:
        # a file outside FILES (a forced fixture) is held to every set
        self._pats = [p for f, p in GUARDED.items()
                      if path.endswith(f)] or list(GUARDED.values())
        out: List[Violation] = []
        self._visit(tree, path, fn_depth=0, lock_depth=0, out=out)
        return out

    def _is_counter(self, name: str) -> bool:
        return any(p.match(name) for p in self._pats)

    def _visit(self, node: ast.AST, path: str, fn_depth: int,
               lock_depth: int, out: List[Violation]) -> None:
        for child in ast.iter_child_nodes(node):
            c_fn, c_lock = fn_depth, lock_depth
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                c_fn += 1
            elif isinstance(child, ast.With):
                if any("_LOCK" in names_in(item.context_expr)
                       for item in child.items):
                    c_lock += 1
            if fn_depth > 0 and lock_depth == 0:
                self._flag(child, path, out)
            self._visit(child, path, c_fn, c_lock, out)

    def _flag(self, node: ast.AST, path: str,
              out: List[Violation]) -> None:
        hits: List[str] = []
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                if isinstance(t, ast.Name) and self._is_counter(t.id):
                    hits.append(t.id)
                elif isinstance(t, ast.Subscript) and \
                        isinstance(t.value, ast.Name) and \
                        self._is_counter(t.value.id):
                    hits.append(t.value.id)
                elif isinstance(t, ast.Tuple):
                    for el in t.elts:
                        if isinstance(el, ast.Name) and \
                                self._is_counter(el.id):
                            hits.append(el.id)
        elif isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr in MUTATORS and \
                isinstance(node.func.value, ast.Name) and \
                self._is_counter(node.func.value.id):
            hits.append(f"{node.func.value.id}.{node.func.attr}()")
        for h in hits:
            out.append(Violation(
                self.rule_id, path, node.lineno,
                f"mutation of {h} outside `with _LOCK:` races the "
                f"other threads that mutate it — guard every mutation "
                f"of the module's shared state with the lock"))
