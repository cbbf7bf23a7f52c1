"""Dataflow rules: donation safety, resource-leak pairing, tracer
escape.  All three ride the CFG/def-use engine in ``dataflow.py``.

- ``donation-use-after`` — a binding passed at a donated position of a
  ``jax.jit(..., donate_argnums=...)`` callable (directly, through
  ``wrap_jit``/``AotDispatch``, through a ``buildPaged*Fn``-style
  builder, or through a same-module helper whose *summary* says it
  donates) is dead after the call; any read on a later path is a
  finding — including the exception edge, where the call may have
  consumed the buffers before raising (PR 15's ``_failBatch`` class).
  A path that re-assigns the binding (the ``k, v = step(k, v, ...)``
  idiom) or calls a helper whose summary rebuilds the owner
  (``_failBatch`` → ``_buildPools`` → ``self.pool = ...``) is clean.
- ``resource-leak`` — acquire/release pairing for KV pages
  (``<pool>.ensure(slot, ...)`` ↔ ``<pool>.release(slot)``) and
  free-list slots (``<free-ish>.get()/popleft()`` ↔ ``.put(slot)``):
  an acquisition with a CFG path to function exit (normal, ``return``
  or an explicit ``raise``) on which the handle is never mentioned
  again — released, stored into an owner field, or handed to any
  callee — leaked its pages/slot.  Paths that *touch* the handle are
  assumed to transfer ownership, so every finding is a handle dropped
  on the floor.
- ``tracer-escape`` — inside a jit/shard_map/scan body (decorated, or
  a local def passed to the transform — same detection machinery as
  the retrace rules), a write of a value derived from the traced
  parameters into ``self.*``, a ``global``/``nonlocal`` name, or a
  closed-over mutable smuggles a tracer out of the trace: it
  materializes once at trace time and is stale (or a leaked tracer
  reference) on every later dispatch.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from tools.jaxlint.core import Finding, Rule, dotted, register_rule, \
    walk_shallow
from tools.jaxlint.dataflow import (ASSIGN, CALL, CALLRET, USE, CFG,
                                    FuncInfo, ModuleModel, covers,
                                    expr_text, module_model, run_forward)

# -- donation specs -------------------------------------------------------


class Donation:
    """Donated argument positions (+ still-unresolved argnames) of one
    donating callable."""

    __slots__ = ("positions", "names")

    def __init__(self, positions: Sequence[int] = (),
                 names: Sequence[str] = ()):
        self.positions = tuple(sorted(set(positions)))
        self.names = tuple(sorted(set(names)))

    def __bool__(self) -> bool:
        return bool(self.positions or self.names)

    def merged(self, other: "Donation") -> "Donation":
        return Donation(self.positions + other.positions,
                        self.names + other.names)


def _int_values(node: ast.AST) -> List[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, ast.Call) and dotted(node.func) in ("tuple", "range") \
            and node.args:
        # ``tuple(range(1, 1 + n))``: a run of positions whose end only
        # the run time knows.  Its literal start is enough: the call that
        # feeds such a run spreads a starred argument over it, which is
        # donated when any donated position lies at or past the star
        if dotted(node.func) == "tuple":
            return _int_values(node.args[0])
        return _int_values(node.args[0])[:1] if len(node.args) > 1 else [0]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and
                isinstance(e.value, int)]
    return []


def _str_values(node: ast.AST) -> List[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts
                if isinstance(e, ast.Constant) and
                isinstance(e.value, str)]
    return []


def _jit_donation(call: ast.Call, model: ModuleModel) -> Optional[Donation]:
    """Donation of a direct ``jax.jit(f, donate_...)`` expression, with
    donate_argnames resolved to positions through the wrapped local
    def's signature when it resolves."""
    if dotted(call.func) not in model.jit_names:
        return None
    pos: List[int] = []
    names: List[str] = []
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            pos.extend(_int_values(kw.value))
        elif kw.arg == "donate_argnames":
            names.extend(_str_values(kw.value))
    if not pos and not names:
        return None
    if names and call.args and isinstance(call.args[0], ast.Name):
        target = call.args[0].id
        for info in model.functions:
            if info.node.name != target:
                continue
            a = info.node.args
            params = [p.arg for p in a.posonlyargs] + \
                [p.arg for p in a.args]
            left = []
            for n in names:
                if n in params:
                    pos.append(params.index(n))
                else:
                    left.append(n)
            names = left
            break
    return Donation(pos, names)


#: wrappers that preserve the wrapped callable's donation contract
_WRAPPER_TAILS = ("wrap_jit", "AotDispatch")


class _DonationIndex:
    """Cross-file registries: builder functions that *return* donating
    callables, and class-attribute bindings that *hold* them."""

    def __init__(self, models: List[ModuleModel]):
        self.models = models
        #: bare function/method name -> Donation of the callable it
        #: returns (buildPagedDecodeFn -> (1, 2)); name-keyed so
        #: ``self.lm.buildPagedDecodeFn()`` resolves without knowing
        #: the receiver's type
        self.builders: Dict[str, Donation] = {}
        #: (relpath, class, 'self.<binding text>') -> Donation
        self.class_bindings: Dict[Tuple[str, str, str], Donation] = {}
        #: (relpath, qualname) -> FuncInfo across every scanned module
        self.all_funcs: Dict[Tuple[str, str], FuncInfo] = {}
        #: summaries, fixpointed across same-module calls
        self.donates_params: Dict[Tuple[str, str], Set[int]] = {}
        self.donates_self: Dict[Tuple[str, str], Set[str]] = {}
        self.self_defs: Dict[Tuple[str, str], Set[str]] = {}
        self.model_of: Dict[Tuple[str, str], ModuleModel] = {}
        self._reads_first: Dict[Tuple[str, str], Set[str]] = {}
        self._rf_in_progress: Set[Tuple[str, str]] = set()
        for m in models:
            self.all_funcs.update(m.by_key)
        # builders stabilize in two rounds (a builder returning another
        # builder's result is the deepest chain in practice)
        for _ in range(2):
            for m in models:
                for info in m.functions:
                    d = self._returned_donation(info, m)
                    if d:
                        prev = self.builders.get(info.node.name)
                        self.builders[info.node.name] = \
                            d.merged(prev) if prev else d
        for m in models:
            self._collect_class_bindings(m)
        self._fixpoint_summaries()

    # -- donating-expression evaluation ----------------------------------
    def eval_expr(self, expr: Optional[ast.AST], model: ModuleModel,
                  cls: Optional[str],
                  local: Dict[str, Donation]) -> Optional[Donation]:
        if isinstance(expr, ast.Call):
            d = _jit_donation(expr, model)
            if d is not None:
                return d
            fname = dotted(expr.func)
            tail = fname.rsplit(".", 1)[-1] if fname else \
                (expr.func.attr if isinstance(expr.func, ast.Attribute)
                 else "")
            if tail in _WRAPPER_TAILS and expr.args:
                return self.eval_expr(expr.args[0], model, cls, local)
            if tail in self.builders:
                return self.builders[tail]
            return None
        if isinstance(expr, ast.Name):
            return local.get(expr.id)
        text = expr_text(expr)
        if text and text.startswith("self.") and cls is not None:
            return self.class_bindings.get(
                (model.src.relpath, cls, text))
        return None

    def _assigns_in_order(self, fn: ast.AST) -> List[ast.Assign]:
        out = [n for n in walk_shallow(fn) if isinstance(n, ast.Assign)]
        out.sort(key=lambda n: n.lineno)
        return out

    def _local_donations(self, info: FuncInfo,
                         model: ModuleModel) -> Dict[str, Donation]:
        local: Dict[str, Donation] = {}
        for a in self._assigns_in_order(info.node):
            d = self.eval_expr(a.value, model, info.cls, local)
            for t in a.targets:
                if isinstance(t, ast.Name):
                    if d:
                        local[t.id] = d
                    else:
                        local.pop(t.id, None)
        return local

    def _returned_donation(self, info: FuncInfo,
                           model: ModuleModel) -> Optional[Donation]:
        local = self._local_donations(info, model)
        out: Optional[Donation] = None
        for node in walk_shallow(info.node):
            if isinstance(node, ast.Return) and node.value is not None:
                d = self.eval_expr(node.value, model, info.cls, local)
                if d:
                    out = d.merged(out) if out else d
        return out

    def _collect_class_bindings(self, model: ModuleModel) -> None:
        for info in model.functions:
            if info.cls is None:
                continue
            # a property/cached_property returning a donating callable
            # makes the bare attribute read the donating binding
            for dec in info.node.decorator_list:
                tail = dotted(dec).rsplit(".", 1)[-1]
                if tail in ("property", "cached_property"):
                    d = self._returned_donation(info, model)
                    if d:
                        key = (model.src.relpath, info.cls,
                               f"self.{info.node.name}")
                        prev = self.class_bindings.get(key)
                        self.class_bindings[key] = \
                            d.merged(prev) if prev else d
            local: Dict[str, Donation] = {}
            for a in self._assigns_in_order(info.node):
                d = self.eval_expr(a.value, model, info.cls, local)
                for t in a.targets:
                    if isinstance(t, ast.Name):
                        if d:
                            local[t.id] = d
                        else:
                            local.pop(t.id, None)
                        continue
                    text = expr_text(t)
                    if d and text.startswith("self."):
                        key = (model.src.relpath, info.cls, text)
                        prev = self.class_bindings.get(key)
                        self.class_bindings[key] = \
                            d.merged(prev) if prev else d

    # -- call-site donation resolution -----------------------------------
    def donated_arg_texts(self, call: ast.Call, model: ModuleModel,
                          cls: Optional[str],
                          local: Dict[str, Donation]) -> List[str]:
        """Binding texts this call donates (caller's view)."""
        spec: Optional[Donation] = None
        if isinstance(call.func, ast.Call):
            # immediately-invoked jit: jax.jit(f, donate_argnums=0)(x)
            spec = self.eval_expr(call.func, model, cls, local)
        else:
            ctext = expr_text(call.func)
            if ctext:
                spec = local.get(ctext)
                if spec is None and ctext.startswith("self.") and \
                        cls is not None:
                    spec = self.class_bindings.get(
                        (model.src.relpath, cls, ctext))
        out: List[str] = []
        if spec:
            # a starred argument spreads over positions only the callee
            # knows: it is donated when a donated position lies at or
            # past it, and what follows it cannot be placed at all
            star = next((i for i, a in enumerate(call.args)
                         if isinstance(a, ast.Starred)), len(call.args))
            for p in spec.positions:
                if 0 <= p < star:
                    t = expr_text(call.args[p])
                    if t:
                        out.append(t)
            if star < len(call.args) and \
                    any(p >= star for p in spec.positions):
                t = expr_text(call.args[star].value)
                if t:
                    out.append(t)
            for n in spec.names:
                for kw in call.keywords:
                    if kw.arg == n:
                        t = expr_text(kw.value)
                        if t:
                            out.append(t)
            return out
        # interprocedural: a same-module helper whose summary donates
        ck = model.resolve_callee(call, cls)
        if ck is not None and ck in self.all_funcs:
            offset = 1 if "." in ck[1] else 0
            for j in self.donates_params.get(ck, ()):
                idx = j - offset
                if 0 <= idx < len(call.args):
                    t = expr_text(call.args[idx])
                    if t:
                        out.append(t)
        return out

    @staticmethod
    def _is_self_call(call: ast.Call) -> bool:
        f = call.func
        return isinstance(f, ast.Attribute) and \
            isinstance(f.value, ast.Name) and f.value.id == "self"

    def _param_names(self, info: FuncInfo) -> List[str]:
        a = info.node.args
        return [p.arg for p in a.posonlyargs] + [p.arg for p in a.args]

    def _fixpoint_summaries(self) -> None:
        # direct facts + the per-function resolved call list
        calls: Dict[Tuple[str, str],
                    List[Tuple[ast.Call, Tuple[str, str]]]] = {}
        locals_of: Dict[Tuple[str, str], Dict[str, Donation]] = {}
        model_of = self.model_of
        for m in self.models:
            for info in m.functions:
                key = (m.src.relpath, info.qualname)
                model_of[key] = m
                local = self._local_donations(info, m)
                locals_of[key] = local
                self.self_defs.setdefault(key, set())
                self.donates_params.setdefault(key, set())
                self.donates_self.setdefault(key, set())
                for node in walk_shallow(info.node):
                    if isinstance(node, (ast.Assign, ast.AugAssign,
                                         ast.AnnAssign)):
                        tgts = node.targets if isinstance(
                            node, ast.Assign) else [node.target]
                        for t in tgts:
                            for leaf in ast.walk(t):
                                text = expr_text(leaf) if isinstance(
                                    leaf, (ast.Attribute,
                                           ast.Subscript)) else ""
                                if text.startswith("self."):
                                    self.self_defs[key].add(text)
                    elif isinstance(node, ast.Call):
                        ck = m.resolve_callee(node, info.cls)
                        if ck is not None and ck in self.all_funcs:
                            calls.setdefault(key, []).append((node, ck))
                        elif isinstance(node.func, ast.Attribute):
                            # a method call on an owner field (e.g.
                            # self.state_.update(...)) may rebuild it
                            # in place — forgiving, same as the
                            # receiver kill in the main transfer
                            r = expr_text(node.func.value)
                            if r.startswith("self."):
                                self.self_defs[key].add(r)
        # fixpoint: donation facts and self-defines flow through
        # resolved same-module/self calls until stable
        info_of = self.all_funcs
        changed = True
        while changed:
            changed = False
            for key, info in info_of.items():
                m = model_of.get(key)
                if m is None:
                    continue
                params = self._param_names(info)
                local = locals_of.get(key, {})
                for node in walk_shallow(info.node):
                    if not isinstance(node, ast.Call):
                        continue
                    for t in self.donated_arg_texts(
                            node, m, info.cls, local):
                        if t in params:
                            j = params.index(t)
                            if j not in self.donates_params[key]:
                                self.donates_params[key].add(j)
                                changed = True
                        elif t.startswith("self.") and \
                                t not in self.donates_self[key]:
                            self.donates_self[key].add(t)
                            changed = True
                for node, ck in calls.get(key, ()):
                    if not self._is_self_call(node):
                        continue
                    if not (self.donates_self[ck] <=
                            self.donates_self[key]):
                        self.donates_self[key] |= self.donates_self[ck]
                        changed = True
                    if not (self.self_defs[ck] <= self.self_defs[key]):
                        self.self_defs[key] |= self.self_defs[ck]
                        changed = True

    def reads_first(self, key: Tuple[str, str]) -> Set[str]:
        """self.* binding texts a helper may READ before (re)defining
        them on some path — the summary that catches a failure handler
        touching a donated pool before the rebuild (the PR 15 class).
        Must-defined forward analysis (intersection join); self-call
        defines and reads recurse, with a cycle guard."""
        memo = self._reads_first.get(key)
        if memo is not None:
            return memo
        if key in self._rf_in_progress:
            return set()
        info = self.all_funcs.get(key)
        m = self.model_of.get(key)
        if info is None or m is None:
            self._reads_first[key] = set()
            return self._reads_first[key]
        self._rf_in_progress.add(key)
        try:
            cfg = info.cfg
            reads: Set[str] = set()
            # entry starts with nothing defined; join = intersection
            states: Dict[int, Optional[Set[str]]] = {cfg.entry: set()}
            work = [cfg.entry]
            while work:
                idx = work.pop()
                blk = cfg.blocks[idx]
                defined = set(states.get(idx) or ())

                def covered(t: str) -> bool:
                    return any(covers(d, t) for d in defined)

                for ev in blk.events:
                    if ev.kind == ASSIGN:
                        defined.add(ev.text)
                    elif ev.kind == USE:
                        if ev.text.startswith("self.") and \
                                not covered(ev.text):
                            reads.add(ev.text)
                    elif ev.kind == CALL:
                        if self._is_self_call(ev.node):
                            ck = m.resolve_callee(ev.node, info.cls)
                            if ck is not None and ck in self.all_funcs:
                                for t in self.reads_first(ck):
                                    if not covered(t):
                                        reads.add(t)
                    elif ev.kind == CALLRET:
                        if self._is_self_call(ev.node):
                            ck = m.resolve_callee(ev.node, info.cls)
                            if ck is not None:
                                defined |= self.self_defs.get(ck, set())
                        elif isinstance(ev.node.func, ast.Attribute):
                            r = expr_text(ev.node.func.value)
                            if r.startswith("self."):
                                defined.add(r)
                for s in blk.succ:
                    prev = states.get(s)
                    if prev is None:
                        states[s] = set(defined)
                        work.append(s)
                    else:
                        joined = prev & defined
                        if joined != prev:
                            states[s] = joined
                            work.append(s)
            self._reads_first[key] = reads
            return reads
        finally:
            self._rf_in_progress.discard(key)


@register_rule
class DonationUseAfterRule(Rule):
    id = "donation-use-after"
    summary = ("binding read after being passed at a donated argument "
               "position (donate_argnums/donate_argnames), including "
               "on the exception edge of the donating call")

    def __init__(self):
        self.models: List[ModuleModel] = []
        self.n_callables = 0
        self.n_analyzed = 0

    def visit(self, src, report) -> None:
        model = module_model(src)
        if model is not None:
            self.models.append(model)

    def collect_stats(self) -> Dict[str, int]:
        return {"donating_callables": self.n_callables,
                "donation_fns_analyzed": self.n_analyzed}

    def finalize(self, report) -> None:
        index = _DonationIndex(self.models)
        self.n_callables = len(index.builders) + len(index.class_bindings)
        for model in self.models:
            for info in model.functions:
                self._analyze(info, model, index, report)

    def _analyze(self, info: FuncInfo, model: ModuleModel,
                 index: _DonationIndex, report) -> None:
        key = (model.src.relpath, info.qualname)
        local = index._local_donations(info, model)
        # precompute per-call donations + callee resolution; skip the
        # CFG entirely when nothing in the function donates
        donations: Dict[int, List[str]] = {}
        callees: Dict[int, Tuple[str, str]] = {}
        interesting = False
        for node in walk_shallow(info.node):
            if not isinstance(node, ast.Call):
                continue
            texts = index.donated_arg_texts(node, model, info.cls, local)
            if texts:
                donations[id(node)] = texts
                interesting = True
            ck = model.resolve_callee(node, info.cls)
            if ck is not None and ck in index.all_funcs:
                callees[id(node)] = ck
                if index._is_self_call(node) and \
                        index.donates_self.get(ck):
                    interesting = True
        if not interesting:
            return
        self.n_analyzed += 1
        cfg = info.cfg
        findings: Dict[Tuple[int, int, str], int] = {}
        helper_findings: Dict[Tuple[int, int, str], Tuple[int, str]] = {}

        def transfer(state, ev, _bidx):
            if ev.kind == USE:
                for b, sites in state.items():
                    if sites and covers(b, ev.text):
                        fkey = (ev.node.lineno, ev.node.col_offset, b)
                        site = min(sites)
                        if fkey not in findings or \
                                site < findings[fkey]:
                            findings[fkey] = site
            elif ev.kind == ASSIGN:
                for b in [k for k in state if covers(ev.text, k)]:
                    state.pop(b)
            elif ev.kind == CALL:
                node = ev.node
                ck = callees.get(id(node))
                if ck is not None and index._is_self_call(node):
                    # a helper that reads a currently-donated owner
                    # field before rebuilding it is the PR 15
                    # `_failBatch` class — flag at the call site
                    rf = index.reads_first(ck)
                    if rf:
                        for b, sites in state.items():
                            if sites and any(covers(b, t) for t in rf):
                                fkey = (node.lineno, node.col_offset, b)
                                site = min(sites)
                                prev = helper_findings.get(fkey)
                                if prev is None or site < prev[0]:
                                    helper_findings[fkey] = (site, ck[1])
                for t in donations.get(id(node), ()):
                    state[t] = state.get(t, frozenset()) | \
                        frozenset((node.lineno,))
                if ck is not None and index._is_self_call(node):
                    for t in index.donates_self.get(ck, ()):
                        state[t] = state.get(t, frozenset()) | \
                            frozenset((node.lineno,))
            elif ev.kind == CALLRET:
                node = ev.node
                ck = callees.get(id(node))
                donated_here = set(donations.get(id(node), ()))
                if ck is not None and index._is_self_call(node):
                    # normal return: the helper's summary says which
                    # owner fields it rebuilt
                    for d in index.self_defs.get(ck, ()):
                        for b in [k for k in state if covers(d, k)]:
                            state.pop(b)
                    return
                # unresolved call: forgiving normal-path kills — the
                # callee may rebuild anything reachable through its
                # receiver or through an owner object passed as an arg
                # (a donated LEAF passed as an arg cannot be rebound by
                # the callee, so its donated state survives)
                f = node.func
                if isinstance(f, ast.Attribute):
                    r = expr_text(f.value)
                    if r:
                        for b in [k for k in state if covers(r, k)]:
                            state.pop(b)
                arg_texts = [expr_text(a) for a in node.args] + \
                    [expr_text(kw.value) for kw in node.keywords]
                for t in arg_texts:
                    if not t or t in donated_here:
                        continue
                    for b in [k for k in state
                              if k != t and covers(t, k)]:
                        state.pop(b)

        run_forward(cfg, transfer)
        for (line, col, binding), site in sorted(findings.items()):
            report(Finding(
                self.id, model.src.relpath, line, col,
                f"{binding!r} is read here, but a call on line {site} "
                "passed it at a donated argument position "
                "(donate_argnums): the buffer is consumed by the "
                "dispatch — on the normal path AND the exception edge "
                "— so this read sees freed memory; rebind the result "
                "(x = f(x)), rebuild the owner before reuse, or "
                "suppress with the reason the buffer provably "
                "survives"))
        for (line, col, binding), (site, helper) in \
                sorted(helper_findings.items()):
            report(Finding(
                self.id, model.src.relpath, line, col,
                f"this call into {helper!r} reads {binding!r}, which a "
                f"call on line {site} passed at a donated argument "
                "position: the buffer may already be consumed (on the "
                "exception edge it always is), so the helper sees "
                "freed memory; rebuild the owner before the read "
                "(the fixed _failBatch pattern) or suppress with the "
                "reason the buffer provably survives"))


# -- resource-leak --------------------------------------------------------

def _freeish(text: str) -> bool:
    return "free" in text.rsplit(".", 1)[-1].lower()


def _poolish(text: str) -> bool:
    return "pool" in text.lower()


_ACQ_GET_ATTRS = ("get", "get_nowait", "popleft", "pop")


@register_rule
class ResourceLeakRule(Rule):
    id = "resource-leak"
    summary = ("acquired KV pages / free-list slot with a CFG path to "
               "function exit that never releases or hands off the "
               "handle")

    def __init__(self):
        self.n_acquires = 0

    def collect_stats(self) -> Dict[str, int]:
        return {"resource_acquires": self.n_acquires}

    def visit(self, src, report) -> None:
        model = module_model(src)
        if model is None:
            return
        for info in model.functions:
            acquires = self._acquires(info.node)
            if not acquires:
                continue
            self.n_acquires += len(acquires)
            cfg = info.cfg
            for call, handle, what, get_kind in acquires:
                exits = self._leak_exits(cfg, call, handle, get_kind)
                if exits:
                    report(Finding(
                        self.id, src.relpath, call.lineno,
                        call.col_offset,
                        f"{what} acquired into {handle!r} can reach "
                        f"{' and '.join(sorted(exits))} without the "
                        "handle being released, stored into an owner "
                        "field, or passed on — the pages/slot leak; "
                        "release on every path (try/finally) or hand "
                        "the handle to its owner before exiting"))

    @staticmethod
    def _acquires(fn: ast.AST) -> List[Tuple[ast.Call, str, str, bool]]:
        out: List[Tuple[ast.Call, str, str, bool]] = []
        for node in walk_shallow(fn):
            if isinstance(node, ast.Assign) and \
                    len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name) and \
                    isinstance(node.value, ast.Call):
                f = node.value.func
                if isinstance(f, ast.Attribute) and \
                        f.attr in _ACQ_GET_ATTRS and \
                        _freeish(expr_text(f.value) or ""):
                    out.append((node.value, node.targets[0].id,
                                f"free-list slot "
                                f"({expr_text(f.value)}.{f.attr}())",
                                True))
            elif isinstance(node, ast.Call):
                f = node.func
                if isinstance(f, ast.Attribute) and \
                        f.attr == "ensure" and node.args and \
                        _poolish(expr_text(f.value) or ""):
                    handle = expr_text(node.args[0])
                    if handle:
                        out.append((node, handle,
                                    f"KV pages ({expr_text(f.value)}"
                                    f".ensure({handle}, ...))", False))
        return out

    @staticmethod
    def _leak_exits(cfg: CFG, call: ast.Call, handle: str,
                    get_kind: bool) -> Set[str]:
        # locate the acquire: tracking starts after the call event —
        # and, for `slot = q.get()`, after the handle's own define
        # (the exception edge of the get itself acquired nothing, and
        # the statement's own ASSIGN must not count as a hand-off)
        start: Optional[Tuple[int, int]] = None
        for block in cfg.blocks:
            for i, ev in enumerate(block.events):
                if ev.kind == CALL and ev.node is call:
                    start = (block.idx, i + 1)
                    break
            if start:
                break
        if start is None:
            return set()
        if get_kind:
            b, i = start
            found = None
            seen_d: Set[Tuple[int, int]] = set()
            stack_d = [(b, i)]
            while stack_d and found is None:
                b, i = stack_d.pop()
                if (b, i) in seen_d:
                    continue
                seen_d.add((b, i))
                blk = cfg.blocks[b]
                for j in range(i, len(blk.events)):
                    ev = blk.events[j]
                    if ev.kind == ASSIGN and ev.text == handle:
                        found = (b, j + 1)
                        break
                else:
                    for s in blk.succ:
                        if s != cfg.raise_idx:
                            stack_d.append((s, 0))
            if found is None:
                return set()
            start = found

        exits: Set[str] = set()
        seen: Set[Tuple[int, int]] = set()
        stack = [start]
        while stack:
            b, i = stack.pop()
            if (b, i) in seen:
                continue
            seen.add((b, i))
            blk = cfg.blocks[b]
            mentioned = False
            for ev in blk.events[i:]:
                if ev.kind in (USE, ASSIGN) and \
                        (ev.text == handle or covers(handle, ev.text)):
                    mentioned = True
                    break
            if mentioned:
                continue
            if b == cfg.exit_idx:
                exits.add("normal function exit")
                continue
            if b == cfg.raise_idx:
                exits.add("an uncaught raise")
                continue
            for s in blk.succ:
                stack.append((s, 0))
        return exits


# -- tracer-escape --------------------------------------------------------

_TRANSFORM_TAILS = {"shard_map", "pjit", "vmap"}
_SCAN_LIKE = {"scan": (0,), "while_loop": (0, 1), "fori_loop": (2,),
              "cond": (1, 2)}
_MUTATORS = {"append", "add", "extend", "update", "insert",
             "setdefault", "appendleft", "put"}


@register_rule
class TracerEscapeRule(Rule):
    id = "tracer-escape"
    summary = ("jit/shard_map/scan body writes a value derived from "
               "traced parameters into self.*, a global, or a "
               "closed-over mutable")

    def __init__(self):
        self.n_traced = 0

    def collect_stats(self) -> Dict[str, int]:
        return {"traced_bodies": self.n_traced}

    def visit(self, src, report) -> None:
        model = module_model(src)
        if model is None:
            return
        traced = self._traced_functions(model)
        self.n_traced += len(traced)
        for info, statics in traced.values():
            self._check(info, statics, src, report)

    # -- traced-body detection (retrace-rule machinery) -------------------
    def _traced_functions(self, model: ModuleModel
                          ) -> Dict[int, Tuple[FuncInfo, Set[str]]]:
        by_name: Dict[str, List[FuncInfo]] = {}
        for info in model.functions:
            by_name.setdefault(info.node.name, []).append(info)
        out: Dict[int, Tuple[FuncInfo, Set[str]]] = {}

        def statics_of(call: Optional[ast.Call],
                       fn: ast.AST) -> Set[str]:
            names: Set[str] = set()
            if call is None:
                return names
            a = fn.args
            params = [p.arg for p in a.posonlyargs] + \
                [p.arg for p in a.args]
            for kw in call.keywords:
                if kw.arg == "static_argnames":
                    names.update(_str_values(kw.value))
                elif kw.arg == "static_argnums":
                    for j in _int_values(kw.value):
                        if 0 <= j < len(params):
                            names.add(params[j])
            return names

        def mark(info: FuncInfo, call: Optional[ast.Call]) -> None:
            key = id(info.node)
            statics = statics_of(call, info.node)
            if key in out:
                out[key][1].update(statics)
            else:
                out[key] = (info, statics)

        def is_transform(name: str) -> bool:
            if name in model.jit_names:
                return True
            return name.rsplit(".", 1)[-1] in _TRANSFORM_TAILS

        # decorated bodies
        for info in model.functions:
            for dec in info.node.decorator_list:
                dname = dotted(dec)
                if dname and is_transform(dname):
                    mark(info, None)
                elif isinstance(dec, ast.Call):
                    dfn = dotted(dec.func)
                    if dfn and is_transform(dfn):
                        mark(info, dec)
                    elif dfn in ("functools.partial", "partial") and \
                            dec.args and dotted(dec.args[0]) and \
                            is_transform(dotted(dec.args[0])):
                        mark(info, dec)
        # local defs passed to a transform / scan-like combinator
        for node in ast.walk(model.src.tree):
            if not isinstance(node, ast.Call):
                continue
            fname = dotted(node.func)
            if not fname:
                continue
            arg_positions: Tuple[int, ...] = ()
            call_for_statics: Optional[ast.Call] = node
            if is_transform(fname):
                arg_positions = (0,)
            else:
                tail = fname.rsplit(".", 1)[-1]
                if tail in _SCAN_LIKE and \
                        fname.split(".", 1)[0] in ("jax", "lax"):
                    arg_positions = _SCAN_LIKE[tail]
                    call_for_statics = None
            for j in arg_positions:
                if j < len(node.args) and \
                        isinstance(node.args[j], ast.Name):
                    for info in by_name.get(node.args[j].id, ()):
                        mark(info, call_for_statics if j == 0 else None)
        return out

    # -- taint + escape check ---------------------------------------------
    def _check(self, info: FuncInfo, statics: Set[str], src,
               report) -> None:
        fn = info.node
        a = fn.args
        params = [p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
        params += [p.arg for p in a.kwonlyargs]
        traced = {p for p in params if p not in statics}
        if not traced:
            return
        local_names: Set[str] = set()
        assigns: List[Tuple[List[str], ast.AST]] = []
        globals_: Set[str] = set()

        def target_names(t: ast.AST) -> List[str]:
            return [n.id for n in ast.walk(t)
                    if isinstance(n, ast.Name) and
                    isinstance(n.ctx, ast.Store)]

        for node in walk_shallow(fn):
            if isinstance(node, (ast.Global, ast.Nonlocal)):
                globals_.update(node.names)
            elif isinstance(node, ast.Assign):
                names = []
                for t in node.targets:
                    names.extend(target_names(t))
                assigns.append((names, node.value))
                local_names.update(names)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                names = target_names(node.target)
                if node.value is not None:
                    assigns.append((names, node.value))
                local_names.update(names)
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                names = target_names(node.target)
                assigns.append((names, node.iter))
                local_names.update(names)
            elif isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    if item.optional_vars is not None:
                        local_names.update(
                            target_names(item.optional_vars))
            elif isinstance(node, ast.comprehension):
                local_names.update(target_names(node.target))

        def mentions_tainted(expr: ast.AST, tainted: Set[str]) -> bool:
            return any(isinstance(n, ast.Name) and n.id in tainted
                       for n in ast.walk(expr))

        tainted = set(traced)
        changed = True
        while changed:
            changed = False
            for names, value in assigns:
                if mentions_tainted(value, tainted):
                    for n in names:
                        if n not in tainted:
                            tainted.add(n)
                            changed = True

        def root_of(expr: ast.AST) -> str:
            while isinstance(expr, (ast.Attribute, ast.Subscript)):
                expr = expr.value
            return expr.id if isinstance(expr, ast.Name) else ""

        def closed_over(root: str) -> bool:
            # self-writes always count; otherwise the root must not be
            # a local or a (traced array) parameter of this body
            if root == "self":
                return True
            if root in globals_:
                return True
            return bool(root) and root not in local_names and \
                root not in params

        def flag(node: ast.AST, what: str) -> None:
            report(Finding(
                self.id, src.relpath, node.lineno, node.col_offset,
                f"{what} inside a traced body "
                f"({fn.name!r} is a jit/shard_map/scan body): the "
                "write happens once at trace time with a tracer "
                "value, so later dispatches see a stale (or leaked-"
                "tracer) object — return the value out of the traced "
                "function instead"))

        for node in walk_shallow(fn):
            if isinstance(node, (ast.Assign, ast.AugAssign,
                                 ast.AnnAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                value = node.value
                if value is None:
                    continue
                for t in targets:
                    if isinstance(t, (ast.Attribute, ast.Subscript)):
                        root = root_of(t)
                        if closed_over(root) and \
                                mentions_tainted(value, tainted):
                            kind = "attribute store" if isinstance(
                                t, ast.Attribute) else "subscript store"
                            flag(node, f"{kind} onto {root!r} of a "
                                       "traced-derived value")
                    elif isinstance(t, ast.Name) and t.id in globals_ \
                            and mentions_tainted(value, tainted):
                        flag(node, f"write to global/nonlocal "
                                   f"{t.id!r} of a traced-derived "
                                   "value")
            elif isinstance(node, ast.Call):
                f = node.func
                if isinstance(f, ast.Attribute) and \
                        f.attr in _MUTATORS:
                    root = root_of(f.value)
                    args_tainted = any(
                        mentions_tainted(arg, tainted)
                        for arg in list(node.args) +
                        [kw.value for kw in node.keywords])
                    if closed_over(root) and root and args_tainted:
                        flag(node, f".{f.attr}() on closed-over "
                                   f"{root!r} with a traced-derived "
                                   "value")
