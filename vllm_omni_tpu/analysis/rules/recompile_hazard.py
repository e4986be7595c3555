"""OL11 — recompile-hazard: per-request values in jit cache keys,
cache keys blind to dispatch variants, and unwarmed executables.

XLA compiles one executable per input signature and a mid-traffic
cache miss stalls every in-flight request for the full compile (tens
of seconds per executable at a 152k vocabulary — docs/performance.md).
The whole per-shape discipline therefore hangs on three invariants at
every ``_run_jit(kind, shape_key, thunk)`` dispatch site
(``RECOMPILE`` manifest, analysis/manifest.py):

1. **bucketed keys** — every term of ``shape_key`` (and every literal
   shape handed to a jax array constructor near the dispatch) derives
   from bucketed values (``_bucket``/``_token_buckets``/
   ``auto_blocks``…) or static config.  A per-request int (``len(...)``
   of runtime data, a ``num_*_tokens`` read) flowing in unbucketed
   compiles a NEW executable per distinct value.  Resolution follows
   local reaching definitions and, for helper indirection (a ``warm``
   wrapper taking the key as a parameter), the cross-module call graph
   to a bounded depth.
2. **variants in the key** — the PR 11 ``n_deep`` bug class: an
   argument whose *presence/width* is conditional at the dispatch site
   (a ``kwargs["deepstack"] = ...`` under ``if``, a keyword bound only
   inside a branch) changes the traced program, so some term of the
   cache key must observe the same discriminator; otherwise a real
   compile is misread as a cache hit and the compile-stall
   introspection goes blind.
3. **warmed kinds** (``finish`` pass) — every ``kind`` string
   registered at a serving dispatch site must be reachable from the
   warmup bucket walker (``precompile``): an unwarmed executable is a
   guaranteed first-hit compile stall under traffic.

A deliberate exception carries a reasoned suppression::

    self._run_jit("oneshot", key, thunk)  # omnilint: disable=OL11 - offline tool
"""

from __future__ import annotations

import ast
from typing import Iterable, Optional

from vllm_omni_tpu.analysis.engine import (
    FileContext,
    Finding,
    ProgramGraph,
    Rule,
    dotted_names,
    own_nodes,
)
from vllm_omni_tpu.analysis.manifest import RECOMPILE
from vllm_omni_tpu.analysis.rules._lockinfo import callee_terminal


class RecompileHazardRule(Rule):
    id = "OL11"
    name = "recompile-hazard"
    node_types = ()
    # overridable in tests
    manifest = RECOMPILE
    MAX_DEPTH = 3

    def applies(self, ctx: FileContext) -> bool:
        return False  # package-wide: everything happens in finalize_run

    # ------------------------------------------------------------ finalize
    def finalize_run(self) -> Iterable[Finding]:
        graph = ProgramGraph.ensure(self.run_state)
        self._graph = graph
        self._defs_cache: dict = {}
        dispatch_fns = self.manifest["dispatch_fns"]
        sites = []  # (fi, call, exclusively_warmup, warm_reachable)
        has_dispatch: dict = {}  # path -> file mentions a dispatch fn
        for key in sorted(graph.functions):
            fi = graph.functions[key]
            if fi.path not in has_dispatch:
                has_dispatch[fi.path] = any(
                    fn in fi.ctx.source for fn in dispatch_fns)
            if not has_dispatch[fi.path]:
                continue
            in_warm = self._in_warmup(fi)
            warm_reach = in_warm or self._warm_reachable(fi)
            for node in own_nodes(fi.node):
                if (isinstance(node, ast.Call)
                        and callee_terminal(node.func) in dispatch_fns
                        and len(node.args) >= 2):
                    sites.append((fi, node, in_warm, warm_reach))
        findings: list = []
        served: dict = {}   # (group, kind) -> first serving site
        warmed: set = set()  # (group, kind)
        groups_with_sites: set = set()
        for fi, call, in_warm, warm_reach in sites:
            group = (fi.path, fi.cls_name or "")
            groups_with_sites.add(group)
            findings.extend(self._check_shape_key(fi, call))
            if not in_warm:
                findings.extend(self._check_variants(fi, call))
                findings.extend(self._check_array_ctors(fi, call))
            kinds = self._kind_strings(call.args[0], fi, self.MAX_DEPTH,
                                       set())
            for k in kinds or ():
                # a helper shared by precompile AND serving is both: its
                # kinds ARE warmed (warmup provably reaches the site)
                # and its dispatch still rides the serving invariants
                if warm_reach:
                    warmed.add((group, k))
                if not in_warm:
                    served.setdefault((group, k), (fi, call))
        warm_groups = {g for (g, _k) in warmed}
        for (group, k) in sorted(served):
            if (group, k) in warmed:
                continue
            if (group not in warm_groups
                    and any(kk == k for (_g, kk) in warmed)):
                # the warmup walker lives in ANOTHER module/class (a
                # hoisted free-function precompile(runner)): the
                # serving group has no warmup sites of its own, so a
                # globally-warmed kind counts — per-group precision
                # only applies where the group warms itself
                continue
            fi, call = served[(group, k)]
            wnames = "/".join(self.manifest["warmup_funcs"])
            findings.append(fi.ctx.finding(
                self.id, call,
                f"kind '{k}' is dispatched here but never reached from "
                f"the warmup bucket walker ({wnames}) — an unwarmed "
                "executable compiles on its first traffic hit, a "
                "guaranteed mid-stream stall; add it to the warmup "
                "walk or suppress with the reason it cannot be warmed"))
        return findings

    def _in_warmup(self, fi) -> bool:
        """Lexically inside a warmup walker (``precompile`` or a
        closure nested in one), or called exclusively from warmup
        functions (one hop of helper indirection)."""
        warm = self.manifest["warmup_funcs"]
        if any(part in warm for part in fi.qual.split(".")):
            return True
        callers = self._graph.callers_of(fi.key)
        return bool(callers) and all(
            any(part in warm for part in cfi.qual.split("."))
            for cfi, _ in callers)

    def _warm_reachable(self, fi) -> bool:
        """ANY caller is a warmup function: the warmup walk provably
        reaches this site, so its kinds are warmed — even when other
        (serving) callers reach it too."""
        warm = self.manifest["warmup_funcs"]
        return any(
            any(part in warm for part in cfi.qual.split("."))
            for cfi, _ in self._graph.callers_of(fi.key))

    # ------------------------------------------------------ reaching defs
    def _defs(self, fi) -> dict:
        """name -> [(value expr, how, conditional)] from the function's
        own assignments.  ``how`` records HOW the name reads off the
        value: None = the whole expression, an int i = element i of a
        literal tuple unpack, ("iter", None) = an element of the
        iterable (plain for-target), ("iter", i) = element i of each
        item (``for kind, fn in (("a", f), ...)``) — kept resolvable so
        the precompile kind loop stays provable."""
        if fi.key in self._defs_cache:
            return self._defs_cache[fi.key]
        defs: dict = {}

        def conditional(node) -> bool:
            cur = fi.ctx.parent(node)
            while cur is not None and cur is not fi.node:
                if isinstance(cur, ast.If):
                    return True
                cur = fi.ctx.parent(cur)
            return False

        def record(tgt, value, how=None, iterated=False, cond=False):
            if isinstance(tgt, (ast.Tuple, ast.List)):
                for i, elt in enumerate(tgt.elts):
                    record(elt, value, ("iter", i) if iterated else i,
                           cond=cond)
                return
            if isinstance(tgt, ast.Name):
                if iterated and how is None:
                    how = ("iter", None)
                defs.setdefault(tgt.id, []).append((value, how, cond))

        for node in own_nodes(fi.node):
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    record(tgt, node.value, cond=conditional(node))
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                record(node.target, node.value, cond=conditional(node))
            elif isinstance(node, ast.AugAssign):
                record(node.target, node.value, cond=conditional(node))
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                record(node.target, node.iter, iterated=True,
                       cond=conditional(node))
            elif isinstance(node, ast.NamedExpr):
                record(node.target, node.value, cond=conditional(node))
        self._defs_cache[fi.key] = defs
        return defs

    # ------------------------------------------------- shape-key bucketing
    def _check_shape_key(self, fi, call: ast.Call) -> list:
        evidence = self._per_request_evidence(call.args[1], fi,
                                              self.MAX_DEPTH, set())
        if evidence is None:
            return []
        desc, chain = evidence
        via = f" (via {' -> '.join(chain)})" if chain else ""
        return [fi.ctx.finding(
            self.id, call,
            f"per-request value in jit cache key: {desc} flows into "
            f"the `_run_jit` shape_key unbucketed{via} — every "
            "distinct value compiles a NEW executable mid-traffic; "
            "bucket it (_bucket/_token_buckets/auto_blocks) or build "
            "the key from static config")]

    def _per_request_evidence(self, expr, fi, depth: int,
                              visited: set) -> Optional[tuple]:
        """(description, call-chain) of the first per-request int
        reachable from ``expr`` without crossing a bucketing call, or
        None.  Chases local reaching definitions, and parameters
        through the call graph (helper indirection)."""
        if depth < 0:
            return None
        bucket_fns = self.manifest["bucket_fns"]
        bucket_attrs = self.manifest["bucket_attrs"]
        pr_attrs = self.manifest["per_request_attrs"]
        stack = [expr]
        while stack:
            node = stack.pop()
            if isinstance(node, ast.Compare) or (
                    isinstance(node, ast.UnaryOp)
                    and isinstance(node.op, ast.Not)):
                # a comparison/negation collapses per-request data into
                # a 2-valued discriminator — bounded by construction,
                # and exactly what a cache key SHOULD observe
                continue
            if isinstance(node, ast.Call):
                term = callee_terminal(node.func)
                if term in bucket_fns:
                    continue  # bucketed: the whole subtree is safe
                if term in ("len", "sum") and node.args:
                    if self._derives_from_runtime(node.args[0], fi,
                                                  depth, visited):
                        return (f"`{term}(...)` of runtime data", ())
                stack.extend(ast.iter_child_nodes(node))
                continue
            if isinstance(node, ast.Attribute):
                if node.attr in pr_attrs:
                    return (f"a `.{node.attr}` read", ())
                if node.attr == "shape":
                    # an operand's .shape in a key is the CORRECT
                    # discriminator — it observes what is actually
                    # traced (the n_deep fix is exactly this read)
                    continue
                if isinstance(node.value, ast.Name):
                    if node.value.id in ("self", "cls"):
                        # self-attrs are config/bucket tables (per-
                        # request state rides locals in this codebase)
                        continue
                    # field-sensitive projection: `asm.t_pad` follows
                    # the t_pad FIELD through the constructor the
                    # base name was built by, not every constructor
                    # argument
                    hit = self._field_evidence(node.value.id, node.attr,
                                               fi, depth, visited)
                    if hit is not None:
                        return hit
                    continue
                stack.extend(ast.iter_child_nodes(node))
                continue
            if isinstance(node, ast.Name):
                hit = self._name_evidence(node.id, fi, depth, visited)
                if hit is not None:
                    return hit
                continue
            stack.extend(ast.iter_child_nodes(node))
        return None

    def _name_evidence(self, name: str, fi, depth: int,
                       visited: set) -> Optional[tuple]:
        key = (fi.key, name)
        if key in visited:
            return None
        visited.add(key)
        defs = self._defs(fi)
        for value, _idx, _cond in defs.get(name, ()):
            hit = self._per_request_evidence(value, fi, depth - 1,
                                             visited)
            if hit is not None:
                return hit
        if name not in defs and name in fi.param_names():
            # helper indirection: classify what every caller passes
            for cfi, call in self._graph.callers_of(fi.key):
                arg = ProgramGraph.call_arg_for_param(call, fi, name)
                if arg is None:
                    continue
                hit = self._per_request_evidence(arg, cfi, depth - 1,
                                                 visited)
                if hit is not None:
                    desc, chain = hit
                    return (desc, (f"{cfi.qual} -> {fi.qual}",) + chain)
        return None

    def _field_evidence(self, base: str, attr: str, fi, depth: int,
                        visited: set) -> Optional[tuple]:
        """Per-request evidence for ONE field of a constructed object:
        resolve the base name's defining call through the graph, find
        the ``return Ctor(...)`` feeding that field (keyword, or
        positional against the ctor class's annotated field order), and
        classify the feeding expression in the callee's context."""
        key = (fi.key, f"{base}.{attr}")
        if key in visited or depth < 0:
            return None
        visited.add(key)
        for value, how, _c in self._defs(fi).get(base, ()):
            if how is not None or not isinstance(value, ast.Call):
                continue
            target = self._graph.resolve_call(value, fi.ctx)
            if target is None:
                continue
            for node in own_nodes(target.node):
                if not (isinstance(node, ast.Return)
                        and isinstance(node.value, ast.Call)):
                    continue
                field = self._ctor_field(node.value, attr, target.ctx)
                if field is None:
                    continue
                hit = self._per_request_evidence(field, target,
                                                 depth - 1, visited)
                if hit is not None:
                    desc, chain = hit
                    return (desc,
                            (f"{target.qual} builds .{attr}",) + chain)
        return None

    @staticmethod
    def _ctor_field(ctor: ast.Call, attr: str,
                    ctx: FileContext) -> Optional[ast.AST]:
        for kw in ctor.keywords:
            if kw.arg == attr:
                return kw.value
        term = callee_terminal(ctor.func)
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ClassDef) and node.name == term:
                fields = [st.target.id for st in node.body
                          if isinstance(st, ast.AnnAssign)
                          and isinstance(st.target, ast.Name)]
                if attr in fields:
                    idx = fields.index(attr)
                    if idx < len(ctor.args):
                        return ctor.args[idx]
                return None
        return None

    def _derives_from_runtime(self, expr, fi, depth: int,
                              visited: set) -> bool:
        """True when ``len(expr)``/``sum(expr)`` measures per-request
        data: anything reaching a function parameter without crossing
        a bucket call or a self-attribute (static config/tables)."""
        if depth < 0:
            return False
        bucket_fns = self.manifest["bucket_fns"]
        stack = [expr]
        while stack:
            node = stack.pop()
            if isinstance(node, ast.Call):
                if callee_terminal(node.func) in bucket_fns:
                    continue
                stack.extend(ast.iter_child_nodes(node))
                continue
            if isinstance(node, ast.Attribute):
                if isinstance(node.value, ast.Name) \
                        and node.value.id in ("self", "cls"):
                    continue  # static config/table read
                stack.extend(ast.iter_child_nodes(node))
                continue
            if isinstance(node, ast.Name):
                if node.id in fi.param_names():
                    return True
                key = (fi.key, "runtime:" + node.id)
                if key in visited:
                    continue
                visited.add(key)
                for value, _i, _c in self._defs(fi).get(node.id, ()):
                    stack.append(value)
                continue
            stack.extend(ast.iter_child_nodes(node))
        return False

    # ------------------------------------------------------- variant check
    def _check_variants(self, fi, call: ast.Call) -> list:
        """Every conditionally-present argument variant observable at
        the dispatch site must appear in the cache key (the ``n_deep``
        class: presence/width of an arg changes the traced program)."""
        if len(call.args) < 3 or not isinstance(call.args[2], ast.Lambda):
            return []
        key_names = self._key_names(call.args[1], fi)
        out: list = []
        for inner in ast.walk(call.args[2].body):
            if not isinstance(inner, ast.Call):
                continue
            for kw in inner.keywords:
                if kw.arg is None and isinstance(kw.value, ast.Name):
                    out.extend(self._check_kwargs_dict(
                        fi, call, kw.value.id, key_names))
                elif kw.arg is not None and isinstance(kw.value,
                                                       ast.Name):
                    out.extend(self._check_conditional_name(
                        fi, call, kw.arg, kw.value.id, key_names))
        return out

    @staticmethod
    def _maximal(names: set) -> set:
        """Drop every chain another chain extends: {"asm",
        "asm.deepstack"} -> {"asm.deepstack"} — a bare base name must
        not count as observing every field hung off it."""
        return {c for c in names
                if not any(o != c and o.startswith(c + ".")
                           for o in names)}

    @staticmethod
    def _observes(key_names: set, discriminators: set) -> bool:
        """Does any key chain observe any discriminator chain?  Exact
        match, or a dotted prefix relation in either direction — but
        never through a bare (dot-free) base name, which would make
        `asm.t_pad` in the key bless every other `asm.*` variant."""
        for k in key_names:
            for g in discriminators:
                if k == g:
                    return True
                if k.startswith(g + ".") and "." in g:
                    return True
                if g.startswith(k + ".") and "." in k:
                    return True
        return False

    def _key_names(self, key_expr, fi) -> set:
        names = dotted_names(key_expr)
        # a key passed as a local name: read its definitions too
        if isinstance(key_expr, ast.Name):
            for value, _i, _c in self._defs(fi).get(key_expr.id, ()):
                names |= dotted_names(value)
        return self._maximal(names)

    def _if_guards(self, node, fi) -> list:
        guards = []
        cur = fi.ctx.parent(node)
        while cur is not None and cur is not fi.node:
            if isinstance(cur, ast.If):
                guards.append(cur.test)
            cur = fi.ctx.parent(cur)
        return guards

    def _check_kwargs_dict(self, fi, call, dname: str,
                           key_names: set) -> list:
        out = []
        for node in own_nodes(fi.node):
            if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Subscript)):
                continue
            tgt = node.targets[0]
            if not (isinstance(tgt.value, ast.Name)
                    and tgt.value.id == dname):
                continue
            variant = None
            if isinstance(tgt.slice, ast.Constant):
                variant = tgt.slice.value
            guards = self._if_guards(node, fi)
            if not guards:
                continue  # unconditional: always part of the signature
            observed = set()
            for g in guards:
                observed |= dotted_names(g)
            observed |= dotted_names(node.value)
            observed.discard(dname)
            observed = self._maximal(observed)
            if not self._observes(key_names, observed):
                out.append(fi.ctx.finding(
                    self.id, call,
                    f"dispatch variant '{variant}' feeds the jitted "
                    "call only under a condition, but no term of the "
                    "shape_key observes that condition — a changed "
                    "variant re-traces the program while the cache "
                    "key claims a hit (the n_deep bug class); add "
                    "the discriminator to the key"))
        return out

    def _check_conditional_name(self, fi, call, kwarg: str, name: str,
                                key_names: set) -> list:
        defs = self._defs(fi).get(name, ())
        if not defs or not all(cond for _v, _i, cond in defs):
            return []  # unconditionally bound at least once
        observed = set()
        for value, _i, _c in defs:
            observed |= dotted_names(value)
        for node in own_nodes(fi.node):
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name) and tgt.id == name:
                        for g in self._if_guards(node, fi):
                            observed |= dotted_names(g)
        observed.discard(name)
        if self._observes(key_names, self._maximal(observed)):
            return []
        return [fi.ctx.finding(
            self.id, call,
            f"keyword '{kwarg}' is bound only inside a branch, but no "
            "term of the shape_key observes its discriminator — a "
            "changed variant re-traces the program while the cache "
            "key claims a hit (the n_deep bug class); add the "
            "discriminator to the key")]

    # -------------------------------------------------- array constructors
    def _check_array_ctors(self, fi, call: ast.Call) -> list:
        """Literal shape tuples handed to jax array constructors in the
        thunk: a per-request dim compiles per distinct value exactly
        like an unbucketed key term."""
        if len(call.args) < 3:
            return []
        ctors = self.manifest["array_ctors"]
        out = []
        for node in ast.walk(call.args[2]):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ctors and node.args):
                continue
            shape = node.args[0]
            hit = self._per_request_evidence(shape, fi, self.MAX_DEPTH,
                                             set())
            if hit is not None:
                desc, _chain = hit
                out.append(fi.ctx.finding(
                    self.id, node,
                    f"per-request value in a jitted array shape: {desc} "
                    f"sizes `{node.func.attr}(...)` inside the dispatch "
                    "thunk — pad to a bucket instead (every distinct "
                    "dim is a fresh XLA compile)"))
        return out

    # ------------------------------------------------------- kind strings
    def _kind_strings(self, expr, fi, depth: int,
                      visited: set) -> Optional[set]:
        """Every string literal ``expr`` can evaluate to, or None when
        unresolvable (no finding on what cannot be proven)."""
        if depth < 0:
            return None
        if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
            return {expr.value}
        if isinstance(expr, ast.IfExp):
            a = self._kind_strings(expr.body, fi, depth, visited)
            b = self._kind_strings(expr.orelse, fi, depth, visited)
            if a is None or b is None:
                return None
            return a | b
        if isinstance(expr, ast.Name):
            key = (fi.key, expr.id)
            if key in visited:
                return None
            visited.add(key)
            defs = self._defs(fi).get(expr.id, ())
            if defs:
                out: set = set()
                for value, how, _c in defs:
                    if how is None:
                        got = self._kind_strings(value, fi, depth,
                                                 visited)
                    elif isinstance(how, int):
                        got = self._unpacked_strings(value, how, fi,
                                                     depth, visited)
                    else:  # ("iter", unpack index | None)
                        got = self._iterated_strings(value, how[1], fi,
                                                     depth, visited)
                    if got is None:
                        return None
                    out |= got
                return out
            if expr.id in fi.param_names():
                out = set()
                resolved_any = False
                for cfi, call in self._graph.callers_of(fi.key):
                    arg = ProgramGraph.call_arg_for_param(call, fi,
                                                          expr.id)
                    if arg is None:
                        continue
                    got = self._kind_strings(arg, cfi, depth - 1,
                                             visited)
                    if got is None:
                        return None
                    out |= got
                    resolved_any = True
                return out if resolved_any else None
        return None

    def _iterated_strings(self, iterable, idx, fi, depth,
                          visited) -> Optional[set]:
        """Strings a for-loop target takes from a LITERAL iterable."""
        if isinstance(iterable, (ast.Tuple, ast.List)):
            out: set = set()
            for elt in iterable.elts:
                got = (self._unpacked_strings(elt, idx, fi, depth,
                                              visited)
                       if idx is not None
                       else self._kind_strings(elt, fi, depth, visited))
                if got is None:
                    return None
                out |= got
            return out
        return None

    def _unpacked_strings(self, value, idx, fi, depth,
                          visited) -> Optional[set]:
        """Element ``idx`` of a literal tuple/list (direct unpack:
        ``kind, fn = ("a", f1)``)."""
        if isinstance(value, (ast.Tuple, ast.List)) \
                and idx < len(value.elts):
            return self._kind_strings(value.elts[idx], fi, depth,
                                      visited)
        return None
