"""ops/grouped_matmul.py: the few-rows kernel (Pallas interpret mode, forced)
against ``jax.lax.ragged_dot`` over the group patterns that break such
kernels; ``_grouped_mlp``, ``routed_moe`` and ``routed_moe_share`` with the
kernel forced against the XLA form and the dense oracle; the choice between
the two forms as a pure function over the shapes every cell of the benchmark
produces; and the property the set-up's budget rests on: a step of many
sparse layers holds the kernel's jitted entry as at most two jaxprs."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_omni_tpu.models.common import transformer as tfm
from vllm_omni_tpu.ops import grouped_matmul as gm
from vllm_omni_tpu.ops import moe as moe_ops

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture
def forced(monkeypatch):
    """The op's own dispatch with the kernel taken wherever ``choice`` and
    the tiling allow, as on a platform that compiles Pallas; the kernel
    itself stays in interpret mode.  Yields the count of calls that took
    it, so that a test can see it was not the XLA form it compared."""
    monkeypatch.setattr(gm, "auto_use_pallas",
                        lambda op, reason="": not reason)
    before = gm.kernel_calls()
    return lambda: gm.kernel_calls() - before


# name -> (group sizes, rows P, kernel tiles or None for ``tiles()``'s)
PATTERNS = {
    "every_expert_some": ([3, 5, 8, 16], 32, None),
    "first_expert_empty": ([0, 7, 9], 16, None),
    "last_expert_empty": ([7, 9, 0], 16, None),
    "runs_of_empty_experts": ([0, 0, 5, 0, 0, 0, 11, 0], 16, None),
    "no_rows_at_all": ([0, 0, 0], 16, None),
    "a_group_of_one_row": ([1, 30, 1], 32, None),
    "one_row_each": ([1, 1, 1, 1], 16, None),
    "groups_off_the_row_tile": ([13, 21, 2, 27, 1], 64, None),
    "one_expert_holds_every_row": ([0, 48, 0], 48, None),
    "a_group_over_several_windows": ([3, 70, 7], 80, (16, 128)),
    "a_window_drawn_back_at_the_end": ([40, 24], 64, (32, 256)),
    "two_tiles_of_n": ([9, 0, 23], 32, (16, 128)),
    "rows_behind_the_last_group": ([5, 0, 6], 48, None),
    "only_rows_behind_the_last_group": ([0, 0], 32, None),
    "fewer_rows_than_a_tile": ([2, 0, 3], 8, None),
    "rows_off_the_sublane_tile": ([4, 9, 7], 20, None),
    "a_wide_window": ([60, 40, 28], 128, (128, 256)),
}


@pytest.mark.parametrize("name", sorted(PATTERNS))
def test_kernel_agrees_with_ragged_dot(name, rng):
    sizes, p, tile = PATTERNS[name]
    e, k, n = len(sizes), 128, 256
    ks = jax.random.split(rng, 2)
    rows = jax.random.normal(ks[0], (p, k), jnp.float32)
    w = jax.random.normal(ks[1], (e, k, n), jnp.float32) * k ** -0.5
    group_sizes = jnp.asarray(sizes, jnp.int32)
    if tile is None:
        got = gm.grouped_matmul(rows, w, group_sizes, use_pallas=True)
    else:
        got = gm._few_rows(rows, w, group_sizes, tm=tile[0], tn=tile[1])
    held = sum(sizes)
    want = jax.lax.ragged_dot(rows, w, group_sizes)
    assert got.shape == want.shape and got.dtype == want.dtype
    # float32 products summed over 128 terms in another order
    np.testing.assert_allclose(np.asarray(got[:held]),
                               np.asarray(want[:held]), atol=1e-5, rtol=1e-5)
    # rows no group holds (a share's absent pairs) come back as zeros
    assert not np.asarray(got[held:]).any()


def test_kernel_rounds_once_to_bfloat16(rng):
    """bfloat16 in, float32 accumulation, one rounding: within one step of
    the dtype of the float32 product."""
    sizes, p, k, n = [5, 0, 17, 10], 32, 256, 128
    ks = jax.random.split(rng, 2)
    rows = jax.random.normal(ks[0], (p, k), jnp.float32).astype(jnp.bfloat16)
    w = (jax.random.normal(ks[1], (len(sizes), k, n), jnp.float32)
         * k ** -0.5).astype(jnp.bfloat16)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    got = gm.grouped_matmul(rows, w, group_sizes, use_pallas=True)
    want = jax.lax.ragged_dot(rows.astype(jnp.float32),
                              w.astype(jnp.float32), group_sizes)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=2 ** -7, rtol=2 ** -7)


def _mlp_inputs(rng, sizes, p, hidden=128, inter=128, gated=True):
    ks = jax.random.split(rng, 3)
    e = len(sizes)
    xs = jax.random.normal(ks[0], (p, hidden), jnp.float32)
    up = jax.random.normal(
        ks[1], (e, hidden, (2 if gated else 1) * inter), jnp.float32) * 0.1
    down = jax.random.normal(ks[2], (e, inter, hidden), jnp.float32) * 0.1
    return xs, up, down, jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("gated", [True, False], ids=["gated", "ungated"])
def test_grouped_mlp_with_the_kernel_forced(gated, rng, forced):
    sizes = [6, 0, 11, 3]
    xs, up, down, group_sizes = _mlp_inputs(rng, sizes, 24, gated=gated)
    act = None if gated else (lambda h: jnp.square(jax.nn.relu(h)))
    h = jax.lax.ragged_dot(xs, up, group_sizes)
    h = moe_ops.silu_mul(h) if gated else act(h)
    want = jax.lax.ragged_dot(h, down, group_sizes)
    got = moe_ops._grouped_mlp(xs, up, down, group_sizes, act, 16)
    assert forced() == 2
    held = sum(sizes)
    np.testing.assert_allclose(np.asarray(got[:held]),
                               np.asarray(want[:held]), atol=1e-5, rtol=1e-4)


def _moe_weights(rng, t=24, hidden=128, e=8, inter=128):
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    x = jax.random.normal(k1, (t, hidden), jnp.float32)
    router_w = jax.random.normal(k2, (hidden, e), jnp.float32) * 0.2
    gate_up = jax.random.normal(k3, (e, hidden, 2 * inter), jnp.float32) * 0.1
    down = jax.random.normal(k4, (e, inter, hidden), jnp.float32) * 0.1
    return x, router_w, gate_up, down


def _dense_oracle(x, router_w, gate_up, down, k):
    layer = {"router": {"w": router_w},
             "experts": {"gate_up": gate_up, "down": down}}
    cfg = tfm.TransformerConfig(
        moe=True, num_experts=gate_up.shape[0], num_experts_per_tok=k)
    return tfm._moe_mlp_dense(layer, cfg, x)


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
def test_routed_moe_with_the_kernel_forced_matches_dense(jit, rng, forced):
    x, rw, gu, dn = _moe_weights(rng)
    want = _dense_oracle(x, rw, gu, dn, 2)
    fn = lambda *a: moe_ops.routed_moe(*a, 2)  # noqa: E731
    got = (jax.jit(fn) if jit else fn)(x, rw, gu, dn)
    assert forced() == 2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-4)


def test_routed_moe_share_with_the_kernel_forced_matches_dense(rng, forced):
    """Two shares of four experts each add up to the dense layer: the
    absent experts' pairs sort behind the last group, the kernel returns
    them as zeros and the share masks them out."""
    x, rw, gu, dn = _moe_weights(rng)
    want = _dense_oracle(x, rw, gu, dn, 3)
    got = sum(moe_ops.routed_moe_share(x, rw, gu[lo:lo + 4], dn[lo:lo + 4],
                                       3, lo, float32_logits=False)
              for lo in (0, 4))
    assert forced() == 4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-4)


# ------------------------------------------------------------- the choice
#: configuration -> the smallest step (rows of a decode bucket or of a token
#: bucket) whose grouped matmuls take the kernel, and the largest for the
#: gate/up (or up) product and for the down product; every bucket between
#: takes it too, and every smaller or larger one keeps ``ragged_dot``.  A
#: change of ``MIN_ROWS_AN_EXPERT``, of the VMEM budget, of ``tiles()`` or of
#: a cell's shapes shows here.
KERNEL_STEPS = {
    "thinker-l8": (16, 256, 512),  # 128 pairs over 128 up; 4 096: 52 / 44 MB
    "thinker-l8-chunked": (16, 256, 512),
    "glm47flash-l8": (16, 512, 512),  # 64 pairs over 64 up; 4 096: 52 / 56
    "lfm2moe-l12": (8, 512, 512),  # 32 pairs over 32 up; 4 096: 58 / 60
    "granite4h-l10-ep4": (8, 256, 128),  # 80 over 72 up; 2 560: 39.5 / 55.8
    "nemotron3super-l11-ep4": (32, 128, 128),  # 704 over 512; 5 632: 79 / 61
    "qwen3next-l8-ep4": (64, 512, 256),  # 640 over 512 up; 5 120: 48.0 / 49
    "dots3note-l5-ep8": (32, 256, 256),  # 256 over 256 up; 4 096: 67 / 67
    # 128 pairs over a router of 128 up (16 decode rows; the cell's 24 rows
    # are 1.5 rows an expert, all on the 16 held); 4 096 pairs past 48 MB
    "commandaplus-l4-ep8": (16, 256, 256),
    # 256 pairs over a router of 256 up (32 rows; the cell's 24 decode rows
    # are 0.75 rows an expert and keep ragged_dot); 4 096 pairs past 48 MB
    "mimov25-l7-ep16": (32, 256, 256),
}


def _one_chip_configs():
    for path in sorted((ROOT / "benchmark" / "configs").glob("*.json")):
        config = json.loads(path.read_text())
        if config.get("tensor_parallel_size", 1) == 1:
            yield config


@pytest.mark.parametrize("name", sorted(KERNEL_STEPS))
def test_which_buckets_take_the_kernel(name):
    from scripts.moe_grouped_sweep import cell_calls

    config = next(c for c in _one_chip_configs() if c["name"] == name)
    calls = cell_calls(config)
    steps = sorted({call[0] for call in calls.values()})
    lo, hi_up, hi_down = KERNEL_STEPS[name]
    for product, hi in (("up", hi_up), ("down", hi_down)):
        took = {}
        for t in steps:
            _, p, width, _, k, n = calls[f"{t}_{product}"]
            # no cell's shape is off the kernel's tiling
            assert not gm.kernel_reason(k, n)
            took[t] = gm.choice(p, width, k, n)
        kernel = [t for t in steps if took[t] == "kernel"]
        assert kernel == [t for t in steps if lo <= t <= hi], (product, took)
    # a one-row step and a whole chunk keep ragged_dot
    assert steps[0] < lo and steps[-1] > max(hi_up, hi_down)


def test_every_one_chip_configuration_is_in_the_table():
    assert {c["name"] for c in _one_chip_configs()} == set(KERNEL_STEPS)


@pytest.mark.parametrize("p,width,k,n,want", [
    (512, 128, 2048, 1536, "kernel"),       # thinker, 64 decode rows
    (4096, 128, 2048, 1536, "ragged_dot"),  # its 512-token step: 52 MB
    (4096, 128, 768, 2048, "kernel"),       # that step's down product: 44
    (1280, 72, 4096, 1536, "kernel"),       # granite's share: 17.8 rows
    (1280, 18, 4096, 1536, "kernel"),       # the same over 18: 71 rows
    (2048, 32, 2048, 3584, "kernel"),       # LFM2's 512 tokens: 64 rows
    (5632, 512, 1024, 2688, "ragged_dot"),  # Nemotron's 256 tokens: 79 MB
    (2816, 512, 2688, 1024, "kernel"),      # Nemotron's share: 5.5 rows
    (64, 128, 2048, 1536, "ragged_dot"),    # 8 decode rows: half a row each
    (128, 128, 2048, 1536, "kernel"),       # 16 decode rows: one row each
])
def test_choice_reads_rows_an_expert_from_the_router_width(p, width, k, n,
                                                           want):
    assert gm.choice(p, width, k, n) == want


def test_a_shape_off_the_tiling_is_refused_with_a_reason(monkeypatch):
    seen = []
    monkeypatch.setattr(
        gm, "auto_use_pallas",
        lambda op, reason="": seen.append((op, reason)) or not reason)
    rows = jnp.ones((16, 96), jnp.float32)
    w = jnp.ones((2, 96, 128), jnp.float32)
    out = gm.grouped_matmul(rows, w, jnp.asarray([7, 9], jnp.int32))
    assert seen and seen[0][0] == "grouped_matmul" and "96" in seen[0][1]
    np.testing.assert_allclose(np.asarray(out), 96.0)


# ------------------------------------------------- what the set-up rests on
def _entry_jaxprs(jaxpr, found, bare):
    """The distinct jaxprs of the kernel's jitted entry under ``jaxpr``,
    and the ``pallas_call`` equations that stand outside any such entry."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            bare.append(eqn)
        if (eqn.primitive.name in ("pjit", "jit")
                and eqn.params.get("name") == "_few_rows"):
            found[id(eqn.params["jaxpr"])] = found.get(
                id(eqn.params["jaxpr"]), 0) + 1
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _entry_jaxprs(sub, found, bare)


@pytest.mark.parametrize("layers", [2, 6])
def test_a_step_holds_the_kernels_entry_as_two_jaxprs(layers, rng, forced):
    """All sparse layers of a model have the same expert shapes, so a step
    program calls ONE traced entry for the gate/up product and one for the
    down product, however deep it is: what the warm set-up's budget rests
    on (PERF.md, PR 48)."""
    cfg = tfm.TransformerConfig(
        vocab_size=64, hidden_size=128, num_layers=layers, num_heads=2,
        num_kv_heads=1, head_dim=64, intermediate_size=128, moe=True,
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=128,
        moe_dispatch="routed")
    params = jax.eval_shape(
        lambda key: tfm.init_params(key, cfg, jnp.float32), rng)
    x = jax.ShapeDtypeStruct((8, cfg.hidden_size), jnp.float32)

    def mlps(params, x):
        for layer in params["layers"]:
            x = x + tfm._moe_mlp(layer, cfg, x)
        return x

    found, bare = {}, []
    _entry_jaxprs(jax.make_jaxpr(mlps)(params, x).jaxpr, found, bare)
    assert forced() == 2 * layers
    assert sum(found.values()) == 2 * layers
    assert len(found) == 2, found
    assert not bare


# ------------------------------------------------------------ the counter
@pytest.mark.parametrize("kernel", [True, False],
                         ids=["kernel", "ragged_dot"])
def test_the_counter_counts_the_steps_whose_program_holds_the_kernel(
        kernel, request):
    """``moe.grouped_kernel_steps_total``: the op's own choice, seen as a
    step's program is traced (``ARModelRunner._run_jit``) and added from
    the program's key on every later dispatch: each dispatch of an engine
    whose programs hold the kernel, none of one whose programs do not."""
    from vllm_omni_tpu.engine.llm_engine import EngineConfig, LLMEngine
    from vllm_omni_tpu.sampling_params import SamplingParams

    took = request.getfixturevalue("forced") if kernel else (lambda: 0)
    cfg = tfm.TransformerConfig(
        vocab_size=64, hidden_size=128, num_layers=2, num_heads=2,
        num_kv_heads=1, head_dim=64, intermediate_size=128, moe=True,
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=128,
        moe_dispatch="routed")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    engine = LLMEngine(params, cfg, EngineConfig(
        num_pages=32, page_size=4, max_model_len=64, max_num_seqs=4,
        async_scheduling=False))
    engine.generate([[1, 2, 3], [4, 5, 6, 7]],
                    SamplingParams(max_tokens=4, ignore_eos=True))
    total = engine.step_metrics.snapshot()["moe"][
        "grouped_kernel_steps_total"]
    assert engine.runner.dispatch_count >= 4
    assert total == (engine.runner.dispatch_count if kernel else 0)
    assert bool(took()) == kernel
