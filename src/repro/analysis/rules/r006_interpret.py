"""R006 — Pallas entry points must thread ``default_interpret()``.

``kernels.block_projection.default_interpret()`` is the single
authority on interpret-vs-compile (TPU detection + the
``REPRO_PALLAS_INTERPRET`` override).
A ``pl.pallas_call`` with a hard-coded ``interpret=True``/``False`` —
or with no ``interpret`` argument at all, which silently means
``False`` — pins one mode and breaks either the CPU test environment or
the TPU deployment.  Entry points must accept an ``interpret`` argument
defaulting to ``default_interpret()`` and thread it through.
"""
from __future__ import annotations

import ast

from repro.analysis.lint import Rule, call_name


class R006InterpretThreading(Rule):
    id = "R006"
    title = "pallas_call hard-codes (or omits) interpret="

    def on_call(self, node: ast.Call):
        name = call_name(node) or ""
        is_pallas = name.split(".")[-1] == "pallas_call"
        kw = {k.arg: k.value for k in node.keywords if k.arg}
        if is_pallas and "interpret" not in kw:
            self.report(node, "pallas_call without interpret=: this "
                              "hard-codes compiled mode. Thread "
                              "interpret=default_interpret() through the "
                              "entry point.")
            return
        val = kw.get("interpret")
        if (val is not None and isinstance(val, ast.Constant)
                and isinstance(val.value, bool)):
            self.report(node, f"interpret={val.value} is hard-coded: mode "
                              "selection belongs to default_interpret() "
                              "(TPU detection + REPRO_PALLAS_INTERPRET "
                              "override).")
