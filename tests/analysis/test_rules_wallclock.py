"""OL4 wall-clock-in-trace: timing jax dispatch without a sync."""

from tests.analysis.util import lint, messages

BENCH = "vllm_omni_tpu/benchmarks/fixture.py"
COLD = "vllm_omni_tpu/config/fixture.py"


def test_timed_dispatch_without_sync_flagged():
    src = '''
import time
import jax.numpy as jnp

def bench(x):
    t0 = time.perf_counter()
    y = jnp.dot(x, x)
    return time.perf_counter() - t0, y
'''
    found = lint(src, path=BENCH, rule="OL4")
    assert len(found) == 1, messages(found)
    assert "block_until_ready" in found[0].message


def test_block_until_ready_makes_it_clean():
    src = '''
import time
import jax
import jax.numpy as jnp

def bench(x):
    t0 = time.perf_counter()
    y = jax.block_until_ready(jnp.dot(x, x))
    return time.perf_counter() - t0, y

def bench_method(x):
    t0 = time.perf_counter()
    y = jnp.dot(x, x)
    y.block_until_ready()
    return time.perf_counter() - t0, y
'''
    assert lint(src, path=BENCH, rule="OL4") == []


def test_single_timestamp_and_host_only_timing_clean():
    src = '''
import time
import jax.numpy as jnp

def stamp(x):
    return time.time(), jnp.dot(x, x)   # no duration measured

def host_phase():
    t0 = time.perf_counter()
    total = sum(range(1000))
    return time.perf_counter() - t0, total
'''
    assert lint(src, path=BENCH, rule="OL4") == []


def test_out_of_scope_module_not_checked():
    src = '''
import time
import jax.numpy as jnp

def bench(x):
    t0 = time.perf_counter()
    y = jnp.dot(x, x)
    return time.perf_counter() - t0, y
'''
    assert lint(src, path=COLD, rule="OL4") == []


def test_nested_def_owns_its_own_timing():
    # outer def has the clocks, nested def has the jax call and its own
    # sync discipline: each is judged on its own body
    src = '''
import time
import jax
import jax.numpy as jnp

def outer(x):
    def inner(v):
        return jax.block_until_ready(jnp.dot(v, v))
    t0 = time.perf_counter()
    y = inner(x)
    return time.perf_counter() - t0, y
'''
    assert lint(src, path=BENCH, rule="OL4") == []
