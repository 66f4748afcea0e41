"""TW008 — the pooled wire arena is a paid-for law (r17).

The reasons: packing shares a host core with parse and featurize, and host
RSS grows with uploaded bytes wherever the device client retains references
to upload buffers (tools/soak.py measures the slope). Fresh per-tick
wire-destination buffers pay both: allocator churn on the packing core,
and ever-new pages for the client to retain. r17's arena
(``twtml_tpu/features/arena.py``) fixes this by leasing pooled
destination buffers that retire when the batch's stats fetch delivers —
so a fresh wire-sized allocation in the pack hot path is a regression,
not a style choice.

The rule: inside the pack-path functions of the scoped modules (function
names starting with ``pack_`` or ``try_assemble``, plus the pipelines'
``_group_wire``), a direct ``np.empty``/``np.zeros``/``bytearray`` call
or a ``np.concatenate`` without an ``out=`` destination is a finding —
the destination must come from the arena (``lease_wire`` /
``_finish_pack``). Ground-truth helpers that build intermediate field
views (``np.stack``/``np.ascontiguousarray``) are not flagged: the law
covers the FINAL wire buffer, the one the transport client retains.
"""

from __future__ import annotations

import ast

from ..findings import Finding
from . import FileContext, Rule


class TW008WireArena(Rule):
    id = "TW008"
    title = "fresh wire-buffer allocation in the pack hot path (no arena)"
    law = (
        "host RSS can grow with uploaded bytes (retained transfer buffers "
        "pin ever-new pages) and the packing core pays allocator "
        "churn for every per-tick wire buffer; pack-path destination "
        "buffers must lease from twtml_tpu/features/arena.py "
        "(lease_wire / _finish_pack), retiring on fetch delivery"
    )
    # the pack/dispatch hot path: every module that builds a wire buffer
    # the transport client will see — r18 extended the law one rung up
    # the ladder to the fused featurize emitters (features/
    # featurize_native.py: the one-pass fill's destination arrays are
    # wire-adjacent and per-tick, so a fresh allocation there is the
    # same regression class)
    SCOPE = (
        "twtml_tpu/features/batch.py",
        "twtml_tpu/features/assemble.py",
        "twtml_tpu/features/featurize_native.py",
        "twtml_tpu/apps/common.py",
        "twtml_tpu/parallel/sharding.py",
        "twtml_tpu/parallel/distributed.py",
        "twtml_tpu/parallel/tenants.py",
    )
    _ALLOC = frozenset({
        "np.empty", "np.zeros", "numpy.empty", "numpy.zeros", "bytearray",
    })

    @staticmethod
    def _pack_functions(tree: ast.AST):
        for node in ast.walk(tree):
            if isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef)
            ) and (
                node.name.startswith("pack_")
                or node.name.startswith("try_assemble")
                or node.name.startswith("try_fill")
                or node.name in ("_group_wire", "_lease_views")
            ):
                yield node

    def check(self, ctx: FileContext):
        if ctx.path not in self.SCOPE:
            return []
        from .transport import dotted

        findings: list[Finding] = []
        for fn in self._pack_functions(ctx.tree):
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted(node.func)
                if name in self._ALLOC:
                    findings.append(Finding(
                        self.id, ctx.path, node.lineno,
                        f"{name}() allocates a fresh buffer inside pack-"
                        f"path function {fn.name}() — lease it from the "
                        "arena instead; " + self.law,
                    ))
                elif name in ("np.concatenate", "numpy.concatenate") and (
                    not any(kw.arg == "out" for kw in node.keywords)
                ):
                    findings.append(Finding(
                        self.id, ctx.path, node.lineno,
                        f"np.concatenate() without out= inside pack-path "
                        f"function {fn.name}() materializes a fresh wire "
                        "buffer — concatenate into an arena lease "
                        "(_finish_pack); " + self.law,
                    ))
        return findings
