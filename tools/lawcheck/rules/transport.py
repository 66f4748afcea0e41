"""Transport laws: backend-init timing, fetch seams, thread-side puts.

These three rules keep the transport design countable (CLAUDE.md restates
them as working rules; ROADMAP D5 re-examines TW002/TW003 against the
ledger):

- the conftest/driver must pin the virtual mesh BEFORE any backend init,
  so no module may touch the backend at import time (TW001);
- a host fetch synchronizes host and device, so fetches flow ONLY through
  the counted seam that pipelines and meters them (TW002);
- uploads are issued from ONE thread in dispatch order, so no
  thread-target/executor-submitted code may reach a ``jax.device_put``
  (TW003).
"""

from __future__ import annotations

import ast

from ..findings import Finding
from . import FileContext, Rule

# jax APIs whose CALL initializes (or requires) a live backend
_BACKEND_FNS = frozenset({
    "jax.devices", "jax.local_devices", "jax.device_count",
    "jax.local_device_count", "jax.default_backend", "jax.device_put",
    "jax.device_get", "jax.process_index", "jax.process_count",
    "jax.live_arrays",
})


def import_aliases(tree: ast.AST) -> dict[str, str]:
    """Map local names to the dotted module path they are bound to, for
    jax-family imports anywhere in the file (module scope or inline)."""
    out: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "jax" or a.name.startswith("jax."):
                    out[(a.asname or a.name.split(".")[0])] = (
                        a.name if a.asname else a.name.split(".")[0]
                    )
        elif isinstance(node, ast.ImportFrom) and node.module and (
            node.module == "jax" or node.module.startswith("jax.")
        ):
            for a in node.names:
                out[a.asname or a.name] = f"{node.module}.{a.name}"
    return out


def dotted(node: ast.expr, aliases: dict[str, str] | None = None) -> str:
    """Best-effort dotted path of an expression ("jax.numpy.zeros",
    "self._worker"); alias-expanded when ``aliases`` is given."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        head = node.id
        if aliases and head in aliases:
            head = aliases[head]
        parts.append(head)
    elif isinstance(node, ast.Call):
        # chained call like jnp.zeros(8).block_until_ready(): recurse into
        # the call's own callee so the chain still resolves
        inner = dotted(node.func, aliases)
        parts.append(f"{inner}()")
    else:
        return ""
    return ".".join(reversed(parts))


class TW001BackendInit(Rule):
    id = "TW001"
    title = "module-scope jax backend initialization"
    law = (
        "tests/conftest.py pins the 8-device virtual CPU mesh BEFORE any "
        "jax backend init, and the driver entry does the same via "
        "utils/backend.py; a module-scope jax.devices()/device_put/jnp "
        "array construction initializes the backend at import time, "
        "silently breaking the mesh pin for every later test/run "
        "(CLAUDE.md tests rule; utils/backend.py docstring)"
    )
    # the two places whose JOB is pre-init backend configuration
    ALLOW = frozenset({"tests/conftest.py", "twtml_tpu/utils/backend.py"})

    def check(self, ctx: FileContext):
        if ctx.path in self.ALLOW:
            return []
        aliases = import_aliases(ctx.tree)
        findings: list[Finding] = []
        for stmt in self._import_time_statements(ctx.tree):
            for node in self._calls_outside_defs(stmt):
                self._check_call(node, aliases, findings, ctx)
        return findings

    def _import_time_statements(self, tree):
        """Module-level statements plus class bodies (both execute at
        import), recursing through module-level if/try/with/for blocks."""
        out = []

        def visit(stmts):
            for stmt in stmts:
                if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                if isinstance(stmt, ast.ClassDef):
                    visit(stmt.body)
                    continue
                if isinstance(stmt, (ast.If, ast.Try, ast.With, ast.For, ast.While)):
                    out.append(stmt)  # headers/bodies below are filtered
                    visit(getattr(stmt, "body", []))
                    visit(getattr(stmt, "orelse", []))
                    visit(getattr(stmt, "finalbody", []))
                    for h in getattr(stmt, "handlers", []):
                        visit(h.body)
                    continue
                out.append(stmt)
        visit(tree.body)
        return out

    def _calls_outside_defs(self, stmt):
        """Call nodes in a statement, not descending into nested defs or
        lambdas (those run later) or nested block statements (already
        visited separately)."""
        calls = []
        stack = [stmt]
        while stack:
            node = stack.pop()
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.Lambda, ast.ClassDef)):
                    continue
                if node is stmt and isinstance(child, (ast.If, ast.Try, ast.With,
                                                       ast.For, ast.While)):
                    continue  # its statements were collected on their own
                if isinstance(child, ast.Call):
                    calls.append(child)
                stack.append(child)
        return calls

    def _check_call(self, node, aliases, findings, ctx):
        path = dotted(node.func, aliases)
        if not path.startswith("jax"):
            return
        if path in _BACKEND_FNS or path.startswith("jax.numpy.") or (
            path.startswith("jax.random.")
        ):
            findings.append(Finding(
                self.id, ctx.path, node.lineno,
                f"import-time call to {path}() initializes the jax backend "
                "before the conftest/driver mesh pin — " + self.law,
            ))


class TW002FetchSeam(Rule):
    id = "TW002"
    title = "host fetch outside the blessed counted seam"
    law = (
        "a host fetch (device_get / block_until_ready) synchronizes host "
        "and device and stalls the dispatch pipeline behind it — all "
        "fetches must flow through the counted seam "
        "(apps/common.FetchPipeline) that pipelines and times them, so "
        "the one-fetch-per-tick law stays countable (CLAUDE.md)"
    )
    # the seam implementation itself; tests/ and tools/ are out of scope
    # by construction (counting tests monkeypatch device_get, the soaks
    # close each pass with a fetch of their own)
    SEAM_FILES = frozenset({
        "twtml_tpu/apps/common.py",
    })

    def check(self, ctx: FileContext):
        if not ctx.path.startswith("twtml_tpu/"):
            return []
        if ctx.path in self.SEAM_FILES:
            return []
        aliases = import_aliases(ctx.tree)
        findings: list[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            path = dotted(node.func, aliases)
            if path == "jax.device_get" or path.endswith(".device_get") and (
                path.startswith("jax")
            ):
                findings.append(Finding(
                    self.id, ctx.path, node.lineno,
                    "jax.device_get outside the blessed fetch seam — "
                    + self.law,
                ))
            elif isinstance(node.func, ast.Attribute) and (
                node.func.attr == "block_until_ready"
            ):
                findings.append(Finding(
                    self.id, ctx.path, node.lineno,
                    ".block_until_ready() outside the blessed fetch seam "
                    "— " + self.law,
                ))
        return findings


class TW003ThreadPut(Rule):
    id = "TW003"
    title = "device_put reachable from a thread target"
    law = (
        "uploads stay on the ONE dispatching thread: jax.device_put from "
        "a thread target or executor races the dispatch order the wire "
        "leases, the fetch pipeline and the lockstep scheduler rely on "
        "(concurrent device_GETs are fine — the fetch pool exists to "
        "issue them; CLAUDE.md)"
    )

    def check(self, ctx: FileContext):
        if not (ctx.path.startswith("twtml_tpu/")
                or ctx.path.startswith("tools/")
                or ctx.path == "__graft_entry__.py"):
            return []
        aliases = import_aliases(ctx.tree)
        findings: list[Finding] = []
        module_funcs = {
            s.name: s for s in ctx.tree.body
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        class_methods: dict[str, dict[str, ast.AST]] = {
            s.name: {
                m.name: m for m in s.body
                if isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef))
            }
            for s in ctx.tree.body if isinstance(s, ast.ClassDef)
        }

        # walk with scope tracking: (enclosing class name, local func defs)
        def visit(node, cls: str | None, local_funcs: list[dict]):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    visit(child, child.name, local_funcs)
                    continue
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    nested = {
                        s.name: s for s in ast.walk(child)
                        if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef))
                        and s is not child
                    }
                    visit(child, cls, local_funcs + [nested])
                    continue
                if isinstance(child, ast.Call):
                    self._check_spawn(
                        child, cls, local_funcs, module_funcs,
                        class_methods, aliases, findings, ctx,
                    )
                visit(child, cls, local_funcs)

        visit(ctx.tree, None, [])
        return findings

    def _spawn_target(self, call: ast.Call, aliases) -> ast.expr | None:
        """The callable expression a spawn site hands to another thread:
        ``threading.Thread(target=X)`` or ``<executor>.submit(X, ...)``."""
        path = dotted(call.func, aliases)
        if path.endswith("Thread"):
            for kw in call.keywords:
                if kw.arg == "target":
                    return kw.value
            return None
        if isinstance(call.func, ast.Attribute) and call.func.attr == "submit":
            return call.args[0] if call.args else None
        return None

    def _check_spawn(self, call, cls, local_funcs, module_funcs,
                     class_methods, aliases, findings, ctx):
        target = self._spawn_target(call, aliases)
        if target is None:
            return
        # unwrap functools.partial(f, ...)
        if isinstance(target, ast.Call) and dotted(
            target.func, aliases
        ).endswith("partial") and target.args:
            target = target.args[0]
        offender = self._target_reaches_put(
            target, cls, local_funcs, module_funcs, class_methods, aliases,
        )
        if offender:
            findings.append(Finding(
                self.id, ctx.path, call.lineno,
                f"thread/executor target reaches jax.device_put via "
                f"{offender} — " + self.law,
            ))

    def _resolve(self, expr, cls, local_funcs, module_funcs, class_methods):
        """Callable expression -> function AST node, same module only."""
        if isinstance(expr, ast.Lambda):
            return expr
        if isinstance(expr, ast.Name):
            for scope in reversed(local_funcs):
                if expr.id in scope:
                    return scope[expr.id]
            return module_funcs.get(expr.id)
        if isinstance(expr, ast.Attribute) and isinstance(
            expr.value, ast.Name
        ) and expr.value.id == "self" and cls:
            return class_methods.get(cls, {}).get(expr.attr)
        return None

    def _has_put(self, fn, aliases) -> bool:
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                p = dotted(node.func, aliases)
                if p == "device_put" or p.endswith(".device_put"):
                    return True
        return False

    def _target_reaches_put(self, target, cls, local_funcs, module_funcs,
                            class_methods, aliases) -> str | None:
        # direct handle: submit(jax.device_put, x)
        tpath = dotted(target, aliases)
        if tpath == "device_put" or tpath.endswith(".device_put"):
            return tpath
        fn = self._resolve(target, cls, local_funcs, module_funcs, class_methods)
        if fn is None:
            return None
        name = getattr(fn, "name", "<lambda>")
        if self._has_put(fn, aliases):
            return f"{name}()"
        # one level deep: same-module callees of the target
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = self._resolve(
                node.func, cls, local_funcs, module_funcs, class_methods
            )
            if callee is not None and callee is not fn and self._has_put(
                callee, aliases
            ):
                return f"{name}() -> {getattr(callee, 'name', '<lambda>')}()"
        return None
