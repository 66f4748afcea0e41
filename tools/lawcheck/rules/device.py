"""Device-program law: no scatter in jitted step code (the Gram densify).

XLA serializes a [B*L]-update scatter into the [B, 2^18] feature space
update by update; ops/gram.py exists to avoid it, replacing 50 scatters per
batch with one [B, B] Gram matmul (one-hot two-level matmul densify). Any
``.at[...].add/.set`` that creeps back into step code reopens that path,
and nothing at runtime would flag it — the program still produces correct
bits. What the scatter costs on this machine is not measured (PERF.md);
the rule guards the design, not a number.
"""

from __future__ import annotations

import ast

from ..findings import Finding
from . import FileContext, Rule

_SCATTER_METHODS = frozenset({
    "add", "set", "mul", "multiply", "divide", "min", "max", "power",
    "apply", "get",
})


class TW004Scatter(Rule):
    id = "TW004"
    title = "indexed-update scatter in jitted step code"
    law = (
        "XLA serializes a [B*L]-update scatter into [B, 2^18] update by "
        "update; ops/gram.py's one-hot two-level matmul densify replaced "
        "it (one [B,B] Gram matmul per batch) — scatters must not creep "
        "back into step code (its cost on this machine: not measured, "
        "PERF.md). Bounded small-domain scatters (K centers, fixed "
        "columns) are exempt via an inline suppression stating the bound"
    )

    def check(self, ctx: FileContext):
        if not (ctx.path.startswith("twtml_tpu/ops/")
                or ctx.path.startswith("twtml_tpu/models/")):
            return []
        findings: list[Finding] = []
        for node in ast.walk(ctx.tree):
            # X.at[idx].add(v): Call(func=Attribute(value=Subscript(
            #   value=Attribute(attr='at')), attr='add'))
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _SCATTER_METHODS
                    and isinstance(node.func.value, ast.Subscript)
                    and isinstance(node.func.value.value, ast.Attribute)
                    and node.func.value.value.attr == "at"):
                continue
            findings.append(Finding(
                self.id, ctx.path, node.lineno,
                f".at[...].{node.func.attr}() indexed update in step code "
                "— " + self.law,
            ))
        return findings
