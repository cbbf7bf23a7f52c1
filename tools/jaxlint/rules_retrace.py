"""Retrace-hazard rules: jit executions that silently miss the compile
cache.

``jax.jit`` caches by *callable identity* plus abstract argument
signature.  The serving tier's whole design (warm bucket ladder,
compile hit/miss accounting) exists to guarantee steady-state dispatches
hit that cache — and one line of Python can quietly defeat it:

- ``retrace-loop`` — a ``jax.jit(...)`` call lexically inside a
  ``for``/``while`` body builds a *fresh* jitted callable every
  iteration: every call is a cache miss (seconds of XLA compile on the
  hot path).  Hoist the jit out of the loop.
- ``retrace-closure`` — ``jax.jit(<lambda or local def>)(...)``
  *immediately invoked*: the jitted wrapper is born, traced, executed
  and dropped in one expression, so each execution of that line
  re-traces.  Bind the jitted callable once (module level, ``self.``
  attribute, lru_cache) and call the binding.  One-shot init sites
  (trace once per object build, by design) carry a reasoned
  suppression instead.
- ``retrace-static-args`` — jit of a function whose signature has
  Python-scalar *config* defaults (``bool``/``str``) without declaring
  ``static_argnums``/``static_argnames``: a str argument fails tracing
  outright, and a bool flag either concretization-errors or doubles the
  executable count invisibly.  Declare the config args static (see
  ``nlp/sambay.py`` ``static_argnames=("kind",)`` for the compliant
  idiom).
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional

from tools.jaxlint.core import (Finding, Rule, dotted, iter_functions,
                                register_rule)


def _jit_names(tree: ast.Module) -> set:
    """Names that mean ``jax.jit`` in this module: 'jax.jit' always,
    plus bare aliases from ``from jax import jit [as j]``."""
    names = {"jax.jit"}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "jax":
            for a in node.names:
                if a.name == "jit":
                    names.add(a.asname or a.name)
    return names


def _is_jit_call(node: ast.Call, jit_names: set) -> bool:
    return dotted(node.func) in jit_names


def _partial_jit(node: ast.Call, jit_names: set) -> bool:
    """functools.partial(jax.jit, ...) — the decorator-with-options
    idiom (see ops/pallas_fused.py)."""
    if dotted(node.func) not in ("functools.partial", "partial"):
        return False
    return bool(node.args) and dotted(node.args[0]) in jit_names


def _has_static_decl(call: ast.Call) -> bool:
    return any(kw.arg in ("static_argnums", "static_argnames")
               for kw in call.keywords)


def _config_default_params(fn: ast.AST) -> List[str]:
    """Parameter names whose default is a Python-scalar config constant
    (bool/str) — the args that need a static declaration under jit."""
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.Lambda)):
        return []
    a = fn.args
    out = []
    pos = list(a.posonlyargs) + list(a.args)
    for arg, default in zip(pos[len(pos) - len(a.defaults):], a.defaults):
        if isinstance(default, ast.Constant) and \
                isinstance(default.value, (bool, str)):
            out.append(arg.arg)
    for arg, default in zip(a.kwonlyargs, a.kw_defaults):
        if default is not None and isinstance(default, ast.Constant) and \
                isinstance(default.value, (bool, str)):
            out.append(arg.arg)
    return out


class _FnIndex:
    """name -> FunctionDefs in the file (nearest-preceding-def wins when
    resolving a jit(f) reference)."""

    def __init__(self, tree: ast.Module):
        self.by_name: Dict[str, List[ast.AST]] = {}
        for _cls, fn in iter_functions(tree):
            self.by_name.setdefault(fn.name, []).append(fn)

    def resolve(self, name: str, before_line: int) -> Optional[ast.AST]:
        best = None
        for fn in self.by_name.get(name, ()):
            if fn.lineno <= before_line and (
                    best is None or fn.lineno > best.lineno):
                best = fn
        return best


@register_rule
class RetraceLoopRule(Rule):
    id = "retrace-loop"
    summary = ("jax.jit called inside a loop body — a fresh callable "
               "per iteration defeats the compile cache")

    def visit(self, src, report) -> None:
        jits = _jit_names(src.tree)
        # loop bodies, not loop line: `for x in jit(f)(xs)` in the
        # iterator expr evaluates once and is fine
        loop_bodies: List[ast.AST] = []
        for node in ast.walk(src.tree):
            if isinstance(node, (ast.For, ast.While, ast.AsyncFor)):
                loop_bodies.extend(node.body)
        for body_stmt in loop_bodies:
            for node in ast.walk(body_stmt):
                if isinstance(node, ast.Call) and (
                        _is_jit_call(node, jits) or
                        _partial_jit(node, jits)):
                    report(Finding(
                        self.id, src.relpath, node.lineno, node.col_offset,
                        "jax.jit called inside a loop body: each "
                        "iteration builds a fresh callable, so every "
                        "call is a trace+compile cache miss — hoist the "
                        "jit out of the loop and reuse the wrapper"))


@register_rule
class RetraceClosureRule(Rule):
    id = "retrace-closure"
    summary = ("immediately-invoked jax.jit of a lambda/local closure — "
               "re-traces on every execution of the line")

    def visit(self, src, report) -> None:
        jits = _jit_names(src.tree)
        index = _FnIndex(src.tree)
        for node in ast.walk(src.tree):
            # the hazard shape is Call(func=Call(jax.jit, ...)): the
            # wrapper never outlives the expression that traced it
            if not (isinstance(node, ast.Call) and
                    isinstance(node.func, ast.Call) and
                    _is_jit_call(node.func, jits)):
                continue
            jit_call = node.func
            target = jit_call.args[0] if jit_call.args else None
            what = "a lambda" if isinstance(target, ast.Lambda) else \
                "a callable"
            if isinstance(target, ast.Name):
                fn = index.resolve(target.id, jit_call.lineno)
                what = f"local function {target.id!r}" if fn is not None \
                    else f"{target.id!r}"
            report(Finding(
                self.id, src.relpath, jit_call.lineno,
                jit_call.col_offset,
                f"jax.jit({what}) is invoked immediately: the jitted "
                "wrapper is created, traced and dropped in one "
                "expression, so every execution re-traces — bind the "
                "wrapper once and call the binding (or suppress with a "
                "reason if this is a genuine one-shot)"))


@register_rule
class RetraceStaticArgsRule(Rule):
    id = "retrace-static-args"
    summary = ("jit of a function with Python-scalar config defaults "
               "(bool/str) but no static_argnums/static_argnames")

    def visit(self, src, report) -> None:
        jits = _jit_names(src.tree)
        index = _FnIndex(src.tree)

        def check(call: ast.Call, fn: Optional[ast.AST],
                  label: str) -> None:
            if fn is None or _has_static_decl(call):
                return
            params = _config_default_params(fn)
            if params:
                report(Finding(
                    self.id, src.relpath, call.lineno, call.col_offset,
                    f"jax.jit({label}) wraps a function with "
                    f"Python-scalar config default(s) "
                    f"{', '.join(repr(p) for p in params)} but declares "
                    "no static_argnums/static_argnames: a str argument "
                    "fails tracing and a traced bool flag either "
                    "concretization-errors or silently doubles the "
                    "executable count — declare the config args static"))

        # jit used as a plain call: jax.jit(f, ...)
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Call) and _is_jit_call(node, jits) \
                    and node.args:
                target = node.args[0]
                if isinstance(target, ast.Lambda):
                    check(node, target, "<lambda>")
                elif isinstance(target, ast.Name):
                    check(node, index.resolve(target.id, node.lineno),
                          target.id)
        # jit used as a decorator: @jax.jit / @partial(jax.jit, ...)
        for _cls, fn in iter_functions(src.tree):
            for dec in getattr(fn, "decorator_list", ()):
                if isinstance(dec, ast.Call) and (
                        _is_jit_call(dec, jits) or _partial_jit(dec, jits)):
                    check(dec, fn, fn.name)
                elif dotted(dec) in jits:
                    # bare @jax.jit has no kwargs at all
                    params = _config_default_params(fn)
                    if params:
                        report(Finding(
                            self.id, src.relpath, dec.lineno,
                            dec.col_offset,
                            f"@jax.jit on {fn.name!r} with Python-scalar "
                            f"config default(s) "
                            f"{', '.join(repr(p) for p in params)} — use "
                            "functools.partial(jax.jit, static_argnames="
                            "...) to declare them static"))