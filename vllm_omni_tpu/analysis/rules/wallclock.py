"""OL4 — wall-clock-in-trace: timing jax dispatch without a sync.

jax dispatch is asynchronous: ``fn(x)`` returns a future-like array the
moment the computation is *enqueued*.  A ``perf_counter()`` pair around
it measures enqueue latency (microseconds) instead of execution
(milliseconds) — benchmark numbers that look 100× too good and drift
with queue depth.  The fix is ``jax.block_until_ready(out)`` (or
``out.block_until_ready()``) before reading the second timestamp.

Scope is the BENCH_PATHS manifest (benchmarks/, metrics/, ...).
The rule fires per function that (a) reads the clock at least twice —
i.e. measures a duration, (b) dispatches jax work (a ``jnp.``/``jax.``
call in the body), and (c) never syncs via ``block_until_ready``.
Functions that time host-side phases of an already-synchronous API
(e.g. an engine step that device_gets internally) suppress with a
reason or get baselined.
"""

from __future__ import annotations

import ast
from typing import Iterable

from vllm_omni_tpu.analysis.engine import FileContext, Finding, Rule
from vllm_omni_tpu.analysis.manifest import BENCH_PATHS, in_scope
from vllm_omni_tpu.analysis.rules._jitinfo import dotted

_CLOCKS = ("time.time", "time.perf_counter", "time.monotonic",
           "perf_counter", "monotonic")


class WallClockRule(Rule):
    id = "OL4"
    name = "wall-clock-in-trace"
    node_types = (ast.FunctionDef, ast.AsyncFunctionDef)

    def applies(self, ctx: FileContext) -> bool:
        return in_scope(ctx.path, BENCH_PATHS)

    def visit(self, node, ctx: FileContext) -> Iterable[Finding]:
        # analyze this def's OWN body: timing in a nested def is that
        # def's responsibility (it gets its own visit)
        clock_calls, has_jax, has_sync = [], False, False
        for sub in ast.walk(node):
            if sub is not node and isinstance(
                    sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # ast.walk still descends; filter below
            if not isinstance(sub, ast.Call):
                continue
            if self._owner(sub, node, ctx) is not node:
                continue
            fn = dotted(sub.func) or ""
            if fn in _CLOCKS:
                clock_calls.append(sub)
            elif fn.startswith(("jnp.", "jax.")) \
                    or fn.endswith(".block_until_ready"):
                has_jax = True
            if fn == "jax.block_until_ready" \
                    or fn.endswith(".block_until_ready"):
                has_sync = True
        if len(clock_calls) >= 2 and has_jax and not has_sync:
            yield ctx.finding(
                self.id, clock_calls[0],
                "wall-clock duration around jax dispatch without "
                "block_until_ready — async dispatch means this measures "
                "enqueue, not execution; sync the result before the "
                "second timestamp")

    @staticmethod
    def _owner(sub: ast.AST, fn_node: ast.AST, ctx: FileContext):
        """Nearest enclosing def of ``sub`` (to scope calls to the def
        being visited, not its nested defs)."""
        for anc in ctx.ancestors(sub):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return anc
        return None
