"""RPL002 — host-sync (device materialization) forbidden in hot paths.

The heartbeat tick must step 50k groups inside one 50 ms interval on
one core; a synchronous device round-trip in that loop (its cost on
an attached chip is not measured; unboundedly worse under queueing)
stalls the event loop and starves every group. Hot
functions are declared in tools/rplint/hotpaths.py or marked inline
with `# rplint: hot` on the def line.

Two classes of violation inside a hot function:

1. unconditional: calls that always synchronize with the device —
   `x.block_until_ready()`, `x.item()`, `jax.device_get(...)`,
   `jax.device_put(...)`.

2. taint-based: `float()`, `int()`, `np.asarray()`, `np.array()`,
   `np.ascontiguousarray()` applied to a DEVICE value. A name is
   device-tainted when assigned from a call to `jnp.*` / `jax.*` /
   any `*_jit(...)` function / `*.to_device_state()`; the taint
   follows attribute access (`new.commit_index` is device if `new`
   is). Host numpy stays untainted — the hot paths are numpy-native
   by design and casting host scalars is fine.

Intentional host syncs (e.g. the opt-in device backend's writeback in
device_tick) carry `# rplint: disable=RPL002` on the statement — the
suppression is the documentation that the round-trip is deliberate.
"""

from __future__ import annotations

import ast
import re

from ..engine import Finding, ModuleContext, dotted_name

_ALWAYS_SYNC_ATTRS = ("block_until_ready", "item")
_ALWAYS_SYNC_CALLS = ("jax.device_get", "jax.device_put")
_MATERIALIZERS = (
    "float",
    "int",
    "np.asarray",
    "np.array",
    "np.ascontiguousarray",
    "numpy.asarray",
    "numpy.array",
    "numpy.ascontiguousarray",
)
_DEVICE_CALL_RE = re.compile(
    r"(^|\.)(jnp|jax)(\.|$)|_jit$|(^|\.)to_device_state$"
)
_HOT_MARK_RE = re.compile(r"#\s*rplint:\s*hot\b")


def _device_producing(callname: str) -> bool:
    return bool(_DEVICE_CALL_RE.search(callname.rstrip("()")))


class HostSyncInHotPathRule:
    code = "RPL002"
    name = "host-sync-in-hot-path"

    def __init__(self, manifest: dict | None = None) -> None:
        if manifest is None:
            from .. import hotpaths

            manifest = hotpaths.HOT_FUNCTIONS
        self._manifest = manifest

    def _hot(self, ctx: ModuleContext, qualname: str, node: ast.AST) -> bool:
        for suffix, names in self._manifest.items():
            if ctx.path.endswith(suffix) and qualname in names:
                return True
        lines = ctx.source.splitlines()
        # decorator lines shift lineno; the def line is where the
        # marker belongs, scan the function's header span
        header_end = node.body[0].lineno if getattr(node, "body", None) else node.lineno
        for ln in range(node.lineno, min(header_end, len(lines)) + 1):
            if _HOT_MARK_RE.search(lines[ln - 1]):
                return True
        return False

    def check(self, ctx: ModuleContext):
        for fn in ctx.functions():
            if not self._hot(ctx, fn.qualname, fn.node):
                continue
            tainted = self._device_names(fn.node)
            for node in ast.walk(fn.node):
                if not isinstance(node, ast.Call):
                    continue
                msg = self._violation(node, tainted)
                if msg is None or ctx.suppressed(node, self.code):
                    continue
                yield Finding(
                    path=ctx.path,
                    line=node.lineno,
                    col=node.col_offset,
                    rule=self.code,
                    message=f"{msg} in hot path '{fn.qualname}'",
                    qualname=fn.qualname,
                )

    def _violation(self, call: ast.Call, tainted: set[str]) -> str | None:
        name = dotted_name(call.func)
        last = name.rsplit(".", 1)[-1]
        if last in _ALWAYS_SYNC_ATTRS and isinstance(call.func, ast.Attribute):
            return f"device sync '.{last}()'"
        if name in _ALWAYS_SYNC_CALLS:
            return f"device sync '{name}()'"
        if name in _MATERIALIZERS and call.args:
            dev = self._mentions_tainted(call.args[0], tainted)
            if dev:
                return (
                    f"'{name}()' materializes device value '{dev}' "
                    "(host<->device round-trip)"
                )
        return None

    def _device_names(self, func: ast.AST) -> set[str]:
        """Names assigned from device-producing calls within `func`."""
        tainted: set[str] = set()
        for node in ast.walk(func):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                if _device_producing(dotted_name(node.value.func)):
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            tainted.add(tgt.id)
                        elif isinstance(tgt, ast.Tuple):
                            for el in tgt.elts:
                                if isinstance(el, ast.Name):
                                    tainted.add(el.id)
        return tainted

    def _mentions_tainted(self, expr: ast.AST, tainted: set[str]) -> str | None:
        for node in ast.walk(expr):
            if isinstance(node, ast.Name) and node.id in tainted:
                return node.id
            if isinstance(node, ast.Call) and _device_producing(
                dotted_name(node.func)
            ):
                return dotted_name(node.func)
        return None
