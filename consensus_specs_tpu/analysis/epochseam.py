"""Pass 13 — epoch scalar-bypass gate.

Epoch processing's whole contract (specs/epoch_fast.py) is ONE
registered ``ops.epoch_sweep`` dispatch per ``process_epoch``: the
breaker, the watchdog, the fault injector, the lane guard and the
``epoch_sweep_*`` counters all live at that seam.  Package code that
imports the device program (``ops/epoch_sweep.py``) directly, or
reaches the wrapper's array internals (``StateArrays``,
``numpy_sweep``, the mask builders, the writeback helpers), runs epoch
math on a path no chaos schedule can kill, no breaker can trip, and no
counter records — the one-dispatch pin silently stops describing the
engine.

This pass flags, inside ``consensus_specs_tpu.*`` (tests and bench.py
sit outside the package and drive internals deliberately):

* any import of ``consensus_specs_tpu.ops.epoch_sweep`` outside its
  sole registered wrapper ``specs.epoch_fast`` — tighter than the
  generic ``bypass-direct-kernel`` gate, which allows ANY wrapper
  module to import ANY kernel;
* any ``from ...epoch_fast import <name>`` or ``epoch_fast.<name>``
  access whose name is not the wrapper's public surface
  (``ENABLED`` / ``SWEEP_SITE`` / ``scalar_epoch`` / ``numpy_epoch`` /
  ``fused_epoch`` / ``set_guard``).

A deliberate exception carries
``# speclint: disable=epoch-scalar-bypass -- <reason>``.
"""
from __future__ import annotations

import ast

from .core import Context, Finding, SourceFile

_WRAPPER = "consensus_specs_tpu.specs.epoch_fast"
_DEVICE = "consensus_specs_tpu.ops.epoch_sweep"

# the wrapper's whole public surface; everything else is engine-internal
_ALLOWED = frozenset({
    "ENABLED", "SWEEP_SITE", "scalar_epoch", "numpy_epoch", "fused_epoch",
    "set_guard",
})


def _absolute(sf: SourceFile, node: ast.ImportFrom) -> str:
    """Resolve a (possibly relative) from-import to a dotted module."""
    if node.level == 0:
        return node.module or ""
    pkg = sf.module.split(".") if sf.module else []
    if not sf.is_package and pkg:
        pkg = pkg[:-1]
    if node.level > 1:
        pkg = pkg[:len(pkg) - (node.level - 1)]
    return ".".join(pkg + (node.module.split(".") if node.module else []))


def _device_finding(sf: SourceFile, node: ast.AST) -> Finding:
    return Finding(
        "epoch-scalar-bypass", sf.rel, node.lineno, node.col_offset,
        "direct import of the fused epoch device program "
        "(ops.epoch_sweep) outside its registered wrapper "
        "specs.epoch_fast",
        hint="go through epoch_fast.fused_epoch — the ops.epoch_sweep "
             "dispatch seam owns the breaker/guard/counter contract")


def _surface_finding(sf: SourceFile, node: ast.AST, name: str) -> Finding:
    return Finding(
        "epoch-scalar-bypass", sf.rel, node.lineno, node.col_offset,
        f"epoch_fast.{name} is engine-internal — epoch array math "
        f"outside the seam runs unsupervised and uncounted",
        hint="use the public surface (ENABLED, SWEEP_SITE, "
             "scalar_epoch, numpy_epoch, fused_epoch, set_guard) or "
             "carry a "
             "reasoned disable")


def run(ctx: Context) -> list[Finding]:
    findings: list[Finding] = []
    for sf in ctx.files:
        if sf.module == _WRAPPER:
            continue            # the wrapper IS the seam implementation
        if not (sf.module or sf.forced):
            continue            # tests/bench drive internals deliberately
        aliases: set[str] = set()
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == _DEVICE or \
                            a.name.startswith(_DEVICE + "."):
                        findings.append(_device_finding(sf, node))
                    elif a.name == _WRAPPER and a.asname:
                        aliases.add(a.asname)
            elif isinstance(node, ast.ImportFrom):
                mod = _absolute(sf, node)
                if mod == _DEVICE or mod.startswith(_DEVICE + "."):
                    findings.append(_device_finding(sf, node))
                    continue
                for a in node.names:
                    if f"{mod}.{a.name}" == _DEVICE:
                        findings.append(_device_finding(sf, node))
                    elif f"{mod}.{a.name}" == _WRAPPER:
                        aliases.add(a.asname or a.name)
                    elif mod == _WRAPPER and a.name not in _ALLOWED:
                        findings.append(
                            _surface_finding(sf, node, a.name))
        if not aliases:
            continue
        for node in ast.walk(sf.tree):
            if (isinstance(node, ast.Attribute)
                    and isinstance(node.value, ast.Name)
                    and node.value.id in aliases
                    and node.attr not in _ALLOWED
                    and not node.attr.startswith("__")):
                findings.append(_surface_finding(sf, node, node.attr))
    return findings
