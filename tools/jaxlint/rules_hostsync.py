"""Hidden host↔device sync rule for declared hot-path modules.

A streaming input path that starves the chip is exactly this class of
bug: the device can only stay busy while the host keeps its distance,
and every ``.item()`` / ``float(loss)`` / ``np.asarray(device_buf)`` on
a hot path is a silent ``block_until_ready`` — the step (or the serving
dispatch, or the prefetch consumer) stalls until the chip drains.

The rule is scoped to the modules that ARE hot paths (the step loop,
the serving tier, the ETL consumer) rather than the whole tree: a sync
in a CLI helper is free, the same sync inside the dispatch loop is a
chip stall.  Intentional sync points — D2H of a response payload, the
H2D completion fence of the staging ring — are *annotated*, not
silenced: ``# jaxlint: sync-ok -- <why this sync is the design>``.

Flagged shapes (inside function bodies of a hot module):

- ``x.item()``, ``x.numpy()``, ``x.block_until_ready()``,
  ``jax.device_get(x)`` — unambiguous sync primitives;
- ``np.asarray(x)`` / ``np.array(x)`` / ``np.ascontiguousarray(x)`` —
  a device array crossing into numpy is a D2H copy;
- ``float(x)`` / ``int(x)`` where ``x`` is a name or attribute (the
  ``float(loss)`` idiom; literal/arithmetic args are host scalars and
  skipped).
"""
from __future__ import annotations

import ast

from tools.jaxlint.core import (Finding, Rule, dotted, iter_functions,
                                register_rule, walk_shallow)

#: the declared hot-path set: step loop, serving tier, ETL consumer.
#: Extend this list when a new subsystem becomes a hot path — the rule
#: deliberately does nothing elsewhere.
HOT_PATH_SUFFIXES = (
    "models/multilayer.py",
    "models/graph.py",
    "remote/serving.py",
    "remote/scheduler.py",
    "parallel/inference.py",
    "parallel/meshtrainer.py",
    "parallel/zero.py",
    "parallel/moe.py",
    "nn/conf/embedding.py",
    "models/recsys.py",
    "datavec/pipeline.py",
    "datavec/iterators.py",
    "fault/elastic.py",
    "fault/coordination.py",
    "fault/chaos.py",
    "compile/aotcache.py",
    # request-scoped observability rides the serving hot path: a sync
    # inside a timeline note or retention sample stalls the decode loop
    "telemetry/context.py",
    "telemetry/timeseries.py",
    "telemetry/otlp.py",
)

_SYNC_ATTRS = {"item", "block_until_ready"}
_NUMPY_FUNCS = {"asarray", "array", "ascontiguousarray"}


def _numpy_aliases(tree: ast.Module) -> set:
    names = {"numpy"}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "numpy":
                    names.add(a.asname or "numpy")
    return names


@register_rule
class HostSyncRule(Rule):
    id = "host-sync"
    summary = ("host-device sync primitive on a declared hot-path "
               "module without a sync-ok annotation")

    def visit(self, src, report) -> None:
        if not src.relpath.endswith(HOT_PATH_SUFFIXES):
            return
        np_names = _numpy_aliases(src.tree)

        def flag(node: ast.AST, what: str) -> None:
            report(Finding(
                self.id, src.relpath, node.lineno, node.col_offset,
                f"{what} forces a host-device sync on a hot-path module "
                "(the device stalls until the value materializes) — "
                "move it off the hot path, or annotate the line with "
                "'# jaxlint: sync-ok -- <why this sync is the design>'"))

        for _cls, fn in iter_functions(src.tree):
            # constructors are config-coercion sites (int(batchSize),
            # float(timeout)), not hot loops — the float/int heuristic
            # would be all noise there; the unambiguous sync primitives
            # stay checked everywhere
            in_ctor = fn.name in ("__init__", "__new__")
            for node in walk_shallow(fn):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                if isinstance(f, ast.Attribute):
                    if f.attr in _SYNC_ATTRS:
                        flag(node, f".{f.attr}()")
                        continue
                    if f.attr == "numpy" and not node.args:
                        flag(node, ".numpy()")
                        continue
                name = dotted(f)
                if name == "jax.device_get":
                    flag(node, "jax.device_get()")
                elif isinstance(f, ast.Attribute) and \
                        f.attr in _NUMPY_FUNCS and \
                        isinstance(f.value, ast.Name) and \
                        f.value.id in np_names:
                    flag(node, f"{f.value.id}.{f.attr}()")
                elif isinstance(f, ast.Name) and f.id in ("float", "int") \
                        and not in_ctor \
                        and len(node.args) == 1 and not node.keywords and \
                        isinstance(node.args[0],
                                   (ast.Name, ast.Attribute)):
                    flag(node, f"{f.id}(<array-like>)")