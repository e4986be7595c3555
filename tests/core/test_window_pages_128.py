"""The window kind's bookkeeping at MiMo-V2.5's sizes (ISSUE 55): a
128-key window over 16-token pages, which is EIGHT pages a sequence where
command-a-plus-05-2026 holds 256, under 2 048-token chunks that are
sixteen windows long: a page is given back every 16 decoded tokens, never
one that the next query still reaches, under preemption (the refused
prefix hit is held through the engine, tests/engine/test_mimo_v2.py); at
the cell's own pool (24 sequences x (128 + 2 048) tokens, a
page of slack each and the null page: 3 289 pages)."""

import pytest

from vllm_omni_tpu.core.kv_cache_manager import (
    NULL_WINDOW_PAGE,
    KVCacheManager,
)
from vllm_omni_tpu.request import Request

WINDOW, PAGE, CHUNK, SEQS = 128, 16, 2048, 24
WINDOW_PAGES = -(-SEQS * (WINDOW + CHUNK) // PAGE) + SEQS + 1


def _manager(num_pages=16384):
    return KVCacheManager(num_pages=num_pages, page_size=PAGE,
                          enable_prefix_caching=False, window=WINDOW,
                          num_window_pages=WINDOW_PAGES)


def _held(kv, rid):
    return [p for p in kv.window_table(rid) if p != NULL_WINDOW_PAGE]


def _reached(kv, req) -> bool:
    """Every key the request's NEXT query admits (``t - 128 < s <= t`` at
    ``t = num_computed_tokens``) lies in a page the table still holds."""
    t = req.num_computed_tokens
    table = kv.window_table(req.request_id)
    return all(table[s // PAGE] != NULL_WINDOW_PAGE
               for s in range(max(t - WINDOW + 1, 0), t))


def test_the_pool_is_the_issues():
    assert WINDOW_PAGES == 3289
    # 3 289 pages x 16 tokens x 8 heads x (256 + 128) lanes x 2 B x 5 layers
    assert WINDOW_PAGES * PAGE * 8 * 384 * 2 * 5 == pytest.approx(
        1.62e9, rel=0.01)
    # and what the rows use: keys of 192 beside values of 128
    assert WINDOW_PAGES * PAGE * 8 * 320 * 2 * 5 == pytest.approx(
        1.35e9, rel=0.01)


def test_a_decoding_sequence_gives_a_page_back_every_sixteen_tokens():
    """A 4 224-token prompt in 2 048-token chunks (the check's), then 64
    decoded tokens one at a time: in decode the sequence holds 8 or 9
    pages, one goes back every 16 tokens, and at every allocation the next
    query's 128 keys are all in held pages."""
    kv = _manager()
    req = Request(request_id="d", prompt_token_ids=list(range(4224)))
    for n in (CHUNK, CHUNK, 128):
        assert kv.allocate(req, n) is not None
        assert _reached(kv, req)
        # a chunk's tiles read down to the chunk's first token's window
        assert len(_held(kv, "d")) <= (WINDOW + CHUNK) // PAGE + 1
        req.num_computed_tokens += n
    freed, gave = kv.window_pages_freed, []
    for _ in range(64):
        assert kv.allocate(req, 1) is not None
        assert _reached(kv, req)
        assert len(_held(kv, "d")) in (8, 9)
        gave.append(kv.window_pages_freed - freed)
        freed = kv.window_pages_freed
        req.num_computed_tokens += 1
    # the first decoded token lets go of what the last chunk's first rows
    # reached; from then on a page back every 16 tokens, never two at once
    assert gave[0] == 128 // PAGE
    at = [i for i, g in enumerate(gave) if g and i]
    assert {gave[i] for i in at} == {1} and len(at) == 63 // PAGE + 1
    assert [b - a for a, b in zip(at, at[1:])] == [PAGE] * (len(at) - 1)
    # over 250 pages given back by the compared tokens, as the file says
    assert kv.window_pages_freed > 250
    # the full kind kept every page
    assert kv.num_pages - kv.num_free_pages == -(-4288 // PAGE)
    kv.free(req)
    assert kv.window_pages_in_use == 0
    assert kv.num_free_pages == kv.num_pages


def test_twenty_four_sequences_fit_the_pool_at_any_length():
    """24 sequences of 16 384 tokens, their chunks interleaved: the pool
    sized from a window and a step's tokens a sequence never refuses."""
    kv = _manager(num_pages=SEQS * 16384 // PAGE + 1)
    reqs = [Request(request_id=f"s{i}", prompt_token_ids=list(range(16384)))
            for i in range(SEQS)]
    for _ in range(16384 // CHUNK):
        for req in reqs:
            assert kv.can_allocate(req, CHUNK)
            assert kv.allocate(req, CHUNK) is not None
            assert _reached(kv, req)
            req.num_computed_tokens += CHUNK
    assert kv.window_pages_high_water <= SEQS * (WINDOW + CHUNK) // PAGE
    for req in reqs:
        kv.free(req)
    assert kv.window_pages_in_use == 0


def test_a_preempted_sequence_starts_its_window_again():
    """Preemption frees both kinds of page; the resumed sequence computes
    from position 0 and slides again, and every query still reaches its
    keys."""
    kv = _manager()
    req = Request(request_id="pre", prompt_token_ids=list(range(5000)))
    for n in (CHUNK, CHUNK, 40):
        assert kv.allocate(req, n) is not None
        req.num_computed_tokens += n
    freed = kv.window_pages_freed
    assert freed == (4096 - WINDOW + 1) // PAGE
    kv.free(req)                      # the scheduler's preemption
    req.num_computed_tokens = 0
    assert kv.window_pages_in_use == 0 and kv.window_table("pre") == []
    for n in (CHUNK, CHUNK, 904):
        assert kv.allocate(req, n) is not None
        assert _reached(kv, req)
        req.num_computed_tokens += n
    table = kv.window_table("pre")
    gone = (4096 - WINDOW + 1) // PAGE
    assert table[:gone] == [NULL_WINDOW_PAGE] * gone
    assert NULL_WINDOW_PAGE not in table[gone:]
    assert kv.window_pages_freed == freed + gone
    kv.free(req)
    assert kv.window_pages_in_use == 0
