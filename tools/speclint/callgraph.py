"""Name-level jit-reachability over the linted files (DESIGN.md §16).

The trace-safety rule needs to know which functions execute *under a JAX
trace*.  Exact resolution is out of reach for a linter (bound methods,
closures, dispatch tables), so this is a deliberate over-approximation:

* **seeds** — every function reference passed to ``jax.jit`` /
  ``pl.pallas_call`` / ``lax.while_loop|fori_loop|scan|cond|switch`` /
  ``jax.vmap`` (at the callee's function-argument positions only), plus
  defs decorated with ``jit``/``remat``-family decorators.  Lambda seeds
  contribute their bodies directly.  ``functools.partial``, the
  scheduler's ``named(f, name)`` and the ``a if c else b`` jit-target
  idiom it uses are unwrapped.
* **edges** — a call ``anything.f(...)`` reaches every def named ``f``
  anywhere in the scanned set, whatever its receiver.

False reachability only ever *adds* findings, and the per-line
suppressions in core.py are the documented escape hatch; missed
reachability would silently hide findings, which is why edges match by
simple name instead of trying to be clever about receivers.
"""
from __future__ import annotations

import ast
from collections import deque
from typing import Dict, List, Set

# callable-argument positions of the tracing entry points we seed from
SEED_ARGS = {
    "jit": (0,), "pallas_call": (0,), "vmap": (0,), "pmap": (0,),
    "grad": (0,), "value_and_grad": (0,), "checkpoint": (0,), "remat": (0,),
    "shard_map": (0,), "custom_vjp": (0,),
    "while_loop": (0, 1), "fori_loop": (2,), "scan": (0,),
    "cond": (1, 2), "switch": (1, 2, 3, 4),
}
SEED_DECORATORS = {"jit", "pallas_call", "vmap", "pmap", "checkpoint",
                   "remat", "custom_vjp"}


def last_name(node) -> str | None:
    """`a.b.c` -> "c", `x` -> "x", else None."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


def root_name(node) -> str | None:
    """`a.b.c` -> "a", `x` -> "x", else None."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def func_targets(node) -> List[ast.AST]:
    """Function references inside a seed argument: names/attributes,
    lambdas, both arms of ``a if c else b``, ``partial(f, ...)`` and
    ``named(f, name)`` (the scheduler's program-naming wrapper)."""
    out: List[ast.AST] = []
    if isinstance(node, (ast.Name, ast.Attribute, ast.Lambda)):
        out.append(node)
    elif isinstance(node, ast.IfExp):
        out.extend(func_targets(node.body))
        out.extend(func_targets(node.orelse))
    elif isinstance(node, ast.Call) and last_name(node.func) in ("partial",
                                                                 "named"):
        if node.args:
            out.extend(func_targets(node.args[0]))
    return out


def calls_in(node) -> Set[str]:
    """Simple names of every call target under ``node``."""
    out = set()
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            nm = last_name(n.func)
            if nm:
                out.add(nm)
    return out


class FunctionInfo:
    __slots__ = ("src", "node", "name", "calls", "reachable")

    def __init__(self, src, node):
        self.src = src
        self.node = node
        self.name = node.name
        self.calls = calls_in(node)
        self.reachable = False


class Reachability:
    """``functions``: jit-reachable defs; ``lambdas``: (src, node) lambda
    seeds; ``by_name``: every def in the scanned set, by simple name."""

    def __init__(self, functions, lambdas, by_name):
        self.functions = functions
        self.lambdas = lambdas
        self.by_name = by_name


def analyze(files) -> Reachability:
    infos: List[FunctionInfo] = []
    by_name: Dict[str, List[FunctionInfo]] = {}
    for src in files:
        for node in ast.walk(src.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fi = FunctionInfo(src, node)
                infos.append(fi)
                by_name.setdefault(fi.name, []).append(fi)

    seed_names: Set[str] = set()
    lambdas = []
    for src in files:
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Call):
                nm = last_name(node.func)
                for pos in SEED_ARGS.get(nm, ()):
                    if pos < len(node.args):
                        for t in func_targets(node.args[pos]):
                            if isinstance(t, ast.Lambda):
                                lambdas.append((src, t))
                            else:
                                seed_names.add(last_name(t))
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    names = {last_name(x) for x in ast.walk(dec)
                             if isinstance(x, (ast.Name, ast.Attribute))}
                    if names & SEED_DECORATORS:
                        seed_names.add(node.name)

    work = deque(n for n in seed_names if n)
    for _, lam in lambdas:
        work.extend(calls_in(lam))
    processed: Set[str] = set()
    while work:
        nm = work.popleft()
        if nm in processed:
            continue
        processed.add(nm)
        for fi in by_name.get(nm, ()):
            if not fi.reachable:
                fi.reachable = True
                work.extend(fi.calls - processed)

    return Reachability([fi for fi in infos if fi.reachable],
                        lambdas, by_name)
