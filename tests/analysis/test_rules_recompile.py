"""OL11 recompile-hazard: per-request values in `_run_jit` shape keys,
dispatch variants the cache key does not observe, and kinds never
reached by the warmup walker — resolved over the ProgramGraph at
``finalize_run`` like OL10.
"""

import os

from vllm_omni_tpu.analysis.engine import REPO_ROOT, analyze_source
from tests.analysis.util import messages

PATH = "vllm_omni_tpu/worker/fix.py"


def lint11(src, path=PATH):
    return [f for f in analyze_source(src, path)
            if f.rule == "OL11" and not f.suppressed]


# ------------------------------------------------------- unbucketed keys
def test_len_of_runtime_data_in_key():
    src = '''
class R:
    def precompile(self):
        for b in self._batch_buckets:
            self._run_jit("decode", (b,), lambda: 1)

    def dispatch(self, scheds):
        return self._run_jit("decode", (len(scheds),), lambda: 1)
'''
    found = lint11(src)
    assert len(found) == 1, messages(found)
    assert "per-request value in jit cache key" in found[0].message
    assert "len(...)" in found[0].message


def test_unbucketed_key_through_local_name():
    src = '''
class R:
    def precompile(self):
        self._run_jit("decode", (8,), lambda: 1)

    def dispatch(self, scheds):
        b = len(scheds)
        key = (b,)
        return self._run_jit("decode", key, lambda: 1)
'''
    found = lint11(src)
    assert len(found) == 1, messages(found)


def test_per_request_attr_in_key():
    src = '''
class R:
    def precompile(self):
        self._run_jit("verify", (4,), lambda: 1)

    def dispatch(self, sc):
        return self._run_jit("verify", (sc.num_new_tokens,), lambda: 1)
'''
    found = lint11(src)
    assert len(found) == 1, messages(found)
    assert "num_new_tokens" in found[0].message


def test_bucketed_key_is_clean():
    src = '''
class R:
    def precompile(self):
        for b in self._batch_buckets:
            self._run_jit("decode", (b,), lambda: 1)
        for t in self._token_buckets:
            self._run_jit("unified", (t,), lambda: 1)

    def dispatch(self, scheds):
        b = _bucket(len(scheds), self._batch_buckets)
        self._run_jit("decode", (b,), lambda: 1)
        t = _bucket(sum(s.num_new_tokens for s in scheds),
                    self._token_buckets)
        return self._run_jit("unified", (t,), lambda: 1)
'''
    assert lint11(src) == [], messages(lint11(src))


def test_helper_indirection_resolves_key_param():
    # the `warm` wrapper idiom: the dispatch site's key is a parameter,
    # classified at every call site through the call graph
    src = '''
class R:
    def precompile(self, scheds):
        def warm(kind, key, thunk):
            return self._run_jit(kind, key, thunk)
        warm("decode", (len(scheds),), lambda: 1)
'''
    found = lint11(src)
    assert len(found) == 1, messages(found)
    assert "via" in found[0].message  # names the indirection chain


def test_per_request_array_shape_in_thunk():
    src = '''
class R:
    def precompile(self):
        self._run_jit("decode", (8,), lambda: 1)

    def dispatch(self, scheds):
        n = len(scheds)
        return self._run_jit(
            "decode", (8,),
            lambda: self._fn(jnp.zeros((n,), jnp.int32)))
'''
    found = lint11(src)
    assert len(found) == 1, messages(found)
    assert "jitted array shape" in found[0].message


# ---------------------------------------------------- variant-not-in-key
def test_conditional_kwargs_variant_not_in_key():
    src = '''
class R:
    def precompile(self):
        self._run_jit("unified", (8,), lambda: self._fn(0))

    def dispatch(self, asm):
        kwargs = {}
        if asm.deepstack is not None:
            kwargs["deepstack"] = asm.deepstack
        t = self._bucket(asm.total, self._token_buckets)
        return self._run_jit("unified", (t,),
                             lambda: self._fn(t, **kwargs))
'''
    found = lint11(src)
    assert len(found) == 1, messages(found)
    assert "deepstack" in found[0].message
    assert "n_deep" in found[0].message


def test_variant_observed_by_key_is_clean():
    src = '''
class R:
    def precompile(self):
        self._run_jit("unified", (8, 0), lambda: self._fn(0))

    def dispatch(self, asm):
        kwargs = {}
        if asm.deepstack is not None:
            kwargs["deepstack"] = asm.deepstack
        t = self._bucket(asm.total, self._token_buckets)
        key = (t, asm.deepstack.shape[0]
               if asm.deepstack is not None else 0)
        return self._run_jit("unified", key,
                             lambda: self._fn(t, **kwargs))
'''
    assert lint11(src) == [], messages(lint11(src))


def test_bare_base_name_in_key_does_not_bless_other_fields():
    # `asm.total` in the key must NOT count as observing the
    # `asm.deepstack` variant: prefix matching never crosses a bare name
    src = '''
class R:
    def precompile(self):
        self._run_jit("unified", (8,), lambda: self._fn(0))

    def dispatch(self, asm):
        kwargs = {}
        if asm.deepstack is not None:
            kwargs["deepstack"] = asm.deepstack
        return self._run_jit("unified", (asm.total,),
                             lambda: self._fn(**kwargs))
'''
    found = lint11(src)
    assert any("deepstack" in f.message for f in found), messages(found)


def test_conditionally_bound_keyword_not_in_key():
    src = '''
class R:
    def precompile(self):
        self._run_jit("unified", (8,), lambda: self._fn(0))

    def dispatch(self, asm, t):
        if asm.use_embeds:
            embeds = asm.embeds_buf
        return self._run_jit("unified", (t,),
                             lambda: self._fn(t, embeds=embeds))
'''
    found = lint11(src)
    assert len(found) == 1, messages(found)
    assert "'embeds'" in found[0].message


# --------------------------------------------------------- unwarmed kinds
def test_unwarmed_kind_is_flagged():
    src = '''
class R:
    def precompile(self):
        self._run_jit("decode", (8,), lambda: 1)

    def dispatch(self):
        return self._run_jit("spec_verify", (8,), lambda: 1)
'''
    found = lint11(src)
    assert len(found) == 1, messages(found)
    assert "spec_verify" in found[0].message
    assert "warmup" in found[0].message


def test_conditional_kind_strings_both_resolved():
    src = '''
class R:
    def precompile(self):
        for kind in ("dispatch", "dispatch_lp"):
            self._run_jit(kind, (8,), lambda: 1)

    def step(self, want_lp):
        kind = "dispatch_lp" if want_lp else "dispatch"
        return self._run_jit(kind, (8,), lambda: 1)
'''
    assert lint11(src) == [], messages(lint11(src))


def test_kind_loop_over_literal_tuples_resolves_unpack():
    # the real precompile idiom: `for kind, fn in (("a", f1), ("b", f2))`
    src = '''
class R:
    def precompile(self):
        for kind, fn in (("dispatch", 1), ("dispatch_lp", 2)):
            self._run_jit(kind, (8,), lambda: fn)

    def step(self, want_lp):
        kind = "dispatch_lp" if want_lp else "dispatch"
        return self._run_jit(kind, (8,), lambda: 1)
'''
    assert lint11(src) == [], messages(lint11(src))


def test_shared_dispatch_helper_counts_as_warmed():
    # a helper called from BOTH precompile and serving: warmup provably
    # reaches the site, so its kinds are warmed — no false positive on
    # the first refactor that routes both paths through one helper
    src = '''
class R:
    def precompile(self):
        self._go("decode")

    def serve(self):
        return self._go("decode")

    def _go(self, kind):
        return self._run_jit(kind, (8,), lambda: 1)
'''
    assert lint11(src) == [], messages(lint11(src))


def test_hoisted_warmup_module_credits_serving_kinds():
    # precompile hoisted OUT of the runner class into a free function:
    # the serving group has no warmup of its own, so a globally-warmed
    # kind counts (no bogus suppression on the refactor)
    from vllm_omni_tpu.analysis.engine import analyze_sources

    srcs = {
        "vllm_omni_tpu/worker/warmup.py": '''
def precompile(runner):
    for b in runner._batch_buckets:
        runner._run_jit("decode", (b,), lambda: 1)
''',
        "vllm_omni_tpu/worker/runner.py": '''
class R:
    def dispatch(self):
        return self._run_jit("decode", (8,), lambda: 1)
''',
    }
    found = [f for f in analyze_sources(srcs)
             if f.rule == "OL11" and not f.suppressed]
    assert found == [], messages(found)


def test_classmethod_wrapper_key_param_resolves():
    # @classmethod warm wrapper called as R.warm(...): cls is implicit
    # on every call shape — the key parameter must map to its real
    # argument, so the per-request len() is still flagged
    src = '''
class R:
    @classmethod
    def warm(cls, kind, key):
        return cls._run_jit(kind, key, lambda: 1)

    def precompile(self):
        for b in self._batch_buckets:
            R.warm("decode", (b,))

    def dispatch(self, scheds):
        return R.warm("decode", (len(scheds),))
'''
    found = lint11(src)
    assert len(found) == 1, messages(found)
    assert "len(...)" in found[0].message


def test_warm_wrapper_sites_count_as_warmup():
    src = '''
class R:
    def precompile(self):
        def warm(kind, key, thunk):
            return self._run_jit(kind, key, thunk)
        warm("unified", (8,), lambda: 1)

    def step(self):
        return self._run_jit("unified", (8,), lambda: 1)
'''
    assert lint11(src) == [], messages(lint11(src))


def test_suppression_with_reason_is_honored():
    src = '''
class R:
    def oneshot(self):
        return self._run_jit("export", (8,), lambda: 1)  # omnilint: disable=OL11 - offline tool, compile stall acceptable
'''
    assert lint11(src) == [], messages(lint11(src))


# ------------------------------------------------ PR 11 bug re-introduction
def _real_runner_source():
    with open(os.path.join(REPO_ROOT,
                           "vllm_omni_tpu/worker/model_runner.py"),
              encoding="utf-8") as fh:
        return fh.read()


def test_real_model_runner_is_clean():
    src = _real_runner_source()
    found = [f for f in analyze_source(
        src, "vllm_omni_tpu/worker/model_runner.py")
        if f.rule == "OL11" and not f.suppressed]
    assert found == [], messages(found)


def test_pr11_missing_cache_key_dim_is_caught_by_exactly_ol11():
    """The PR 11 ``n_deep`` bug, re-introduced by mutation of the REAL
    dispatch site: drop the deepstack level count from the unified
    cache key while the conditional kwarg keeps feeding the jitted
    call.  OL11 (and only OL11) must catch it."""
    src = _real_runner_source()
    needle = ("            (asm.t_pad, self._spec_v, asm.embeds is "
              "not None,\n             asm.deepstack.shape[0] if "
              "asm.deepstack is not None else 0,\n"
              "             self._kv_quant),")
    assert needle in src, "dispatch-site anchor moved - update the test"
    mutated = src.replace(
        needle,
        "            (asm.t_pad, self._spec_v, "
        "asm.embeds is not None, self._kv_quant),")
    found = [f for f in analyze_source(
        mutated, "vllm_omni_tpu/worker/model_runner.py")
        if not f.suppressed]
    new_rules = {f.rule for f in found}
    assert "OL11" in new_rules, messages(found)
    ol11 = [f for f in found if f.rule == "OL11"]
    assert any("'deepstack'" in f.message and "n_deep" in f.message
               for f in ol11), messages(ol11)


# ------------------------------------------- PR 20 quantized-layout keys
def test_kv_quant_layout_flag_in_key_is_static_config():
    # the resident-KV layout flag is manifest bucket_attrs: carrying
    # `self._kv_quant` in a dispatch key is the REQUIRED discriminator
    # for the int8 executable family, never a per-request hazard
    src = '''
class R:
    def precompile(self):
        for b in self._batch_buckets:
            self._run_jit("decode", (b, self._kv_quant), lambda: 1)

    def dispatch(self, scheds):
        b = self._bucket(len(scheds))
        return self._run_jit("decode", (b, self._kv_quant), lambda: 1)
'''
    assert lint11(src) == [], messages(lint11(src))


def test_quant_scales_kwarg_without_layout_key_is_caught():
    # the PR 20 bug class: the quantized path conditionally binds the
    # per-page scale operand but the cache key carries no layout
    # discriminator — flipping kv_cache_dtype mid-fleet would alias the
    # int8 executable onto the bf16 signature (or vice versa) and
    # miscount a real mid-traffic compile as a cache hit
    src = '''
class R:
    def precompile(self):
        self._run_jit("unified", (8,), lambda: self._fn(0))

    def dispatch(self, asm, t):
        kwargs = {}
        if self.kv_quantized:
            kwargs["kv_scales"] = asm.scales
        return self._run_jit("unified", (t,),
                             lambda: self._fn(t, **kwargs))
'''
    found = lint11(src)
    assert len(found) == 1, messages(found)
    assert "'kv_scales'" in found[0].message
