"""The window kind's bookkeeping at command-a-plus-05-2026's sizes (ISSUE
50): a 4 096-key window over 16-token pages, 256 pages a sequence a layer
where the only model that ran it before held 33 (a 513-key window).  The
manager counts tokens and is the same code; what is held here is what it
gives back, its high water, and a sequence that is preempted and resumed,
at the cell's own pool (24 sequences x (4 096 + 2 048) tokens, a page of
slack each and the null page: 9 241 pages)."""

import pytest

from vllm_omni_tpu.core.kv_cache_manager import (
    NULL_WINDOW_PAGE,
    KVCacheManager,
)
from vllm_omni_tpu.request import Request

WINDOW, PAGE, CHUNK, SEQS = 4096, 16, 2048, 24
WINDOW_PAGES = -(-SEQS * (WINDOW + CHUNK) // PAGE) + SEQS + 1


def _manager(num_pages=16384):
    return KVCacheManager(num_pages=num_pages, page_size=PAGE,
                          enable_prefix_caching=False, window=WINDOW,
                          num_window_pages=WINDOW_PAGES)


def _run(kv, req, tokens: int, chunk: int = CHUNK):
    """Allocate and "compute" ``tokens`` more tokens, a chunk at a time."""
    done = 0
    while done < tokens:
        n = min(chunk, tokens - done)
        assert kv.allocate(req, n) is not None
        req.num_computed_tokens += n
        done += n


def _held(kv, rid):
    return [p for p in kv.window_table(rid) if p != NULL_WINDOW_PAGE]


def test_the_pool_is_the_issues():
    assert WINDOW_PAGES == 9241
    # 9 241 pages x 16 tokens x 4 096 B x 3 layers
    assert WINDOW_PAGES * PAGE * 4096 * 3 == pytest.approx(1.82e9, rel=0.01)


def test_a_long_prompt_gives_its_pages_back_as_it_slides():
    """An 8 192-token prompt in 2 048-token chunks, then 8 decoded tokens:
    at every allocation the pages wholly below ``computed - 4095`` are
    back in the pool, the table keeps its length (the positions do not
    move), and the sequence never holds more than a window and a chunk."""
    kv = _manager()
    req = Request(request_id="long", prompt_token_ids=list(range(8192)))
    high = 0
    for step in range(4):
        assert kv.allocate(req, CHUNK) is not None
        computed = req.num_computed_tokens
        table = kv.window_table("long")
        assert len(table) == (computed + CHUNK) // PAGE
        gone = max(computed - WINDOW + 1, 0) // PAGE
        assert table[:gone] == [NULL_WINDOW_PAGE] * gone
        assert NULL_WINDOW_PAGE not in table[gone:]
        assert kv.window_pages_freed == gone
        high = max(high, kv.window_pages_in_use)
        req.num_computed_tokens += CHUNK
    # chunks 0-2 fill 384 pages; before the fourth, 2 049 positions are
    # out of reach: 128 pages back, 128 new
    assert high == 384 == (WINDOW + CHUNK) // PAGE
    assert kv.window_pages_high_water == 384
    _run(kv, req, 8, chunk=1)
    # before the last token (position 8 199) everything below 4 104 is out
    # of reach: 256 pages, as the issue counts
    assert kv.window_pages_freed == (8199 - WINDOW + 1) // PAGE == 256
    assert len(_held(kv, "long")) == 8200 // PAGE + 1 - 256 == 257
    # the rows a decode step writes and reads lie in held pages
    slots = kv.window_slot_mapping("long", 8199, 1)
    assert slots[0] // PAGE == kv.window_table("long")[8199 // PAGE] != 0
    # the full kind kept every page
    assert kv.num_pages - kv.num_free_pages == 8200 // PAGE + 1
    kv.free(req)
    assert kv.window_pages_in_use == 0
    assert kv.num_free_pages == kv.num_pages


def test_twenty_four_sequences_fit_the_pool_at_any_length():
    """24 sequences of 16 384 tokens, their chunks interleaved: the pool
    that was sized from a window and a step's tokens a sequence never
    refuses an allocation, and its high water stays a page a sequence
    under its size.  (The full kind's pool is made large enough here: the
    cell's 16 384 pages hold 24 sequences of the mix's mean, not of its
    longest.)"""
    kv = _manager(num_pages=SEQS * 16384 // PAGE + 1)
    reqs = [Request(request_id=f"s{i}", prompt_token_ids=list(range(16384)))
            for i in range(SEQS)]
    for _ in range(16384 // CHUNK):
        for req in reqs:
            assert kv.can_allocate(req, CHUNK)
            assert kv.allocate(req, CHUNK) is not None
            req.num_computed_tokens += CHUNK
    assert kv.window_pages_high_water <= SEQS * (WINDOW + CHUNK) // PAGE
    assert kv.window_pages_high_water < WINDOW_PAGES - 1
    for req in reqs:
        assert len(_held(kv, req.request_id)) <= (WINDOW + CHUNK) // PAGE
        kv.free(req)
    assert kv.window_pages_in_use == 0


def test_a_preempted_sequence_starts_its_window_again():
    """Preemption frees both kinds of page; the resumed sequence computes
    from position 0 and slides again, and what it gave back the first
    time is not counted as held."""
    kv = _manager()
    req = Request(request_id="pre", prompt_token_ids=list(range(10000)))
    _run(kv, req, 6144 + 40, chunk=CHUNK)
    # its last allocation stood at 6 144 computed tokens: 2 049 positions
    # out of reach
    freed = kv.window_pages_freed
    assert freed == (6144 - WINDOW + 1) // PAGE == 128
    assert kv.window_pages_in_use == -(-6184 // PAGE) - 128
    kv.free(req)                      # the scheduler's preemption
    req.num_computed_tokens = 0
    assert kv.window_pages_in_use == 0 and kv.window_table("pre") == []
    _run(kv, req, 10000)
    table = kv.window_table("pre")
    assert len(table) == 10000 // PAGE
    # before the last chunk (from 8 192) positions below 4 097 were out
    gone = (8192 - WINDOW + 1) // PAGE
    assert table[:gone] == [NULL_WINDOW_PAGE] * gone
    assert NULL_WINDOW_PAGE not in table[gone:]
    assert kv.window_pages_freed == freed + gone
    assert kv.window_pages_in_use == len(table) - gone
    kv.free(req)
    assert kv.window_pages_in_use == 0


def test_a_pool_too_small_refuses_without_side_effects():
    kv = KVCacheManager(num_pages=1024, page_size=PAGE,
                        enable_prefix_caching=False, window=WINDOW,
                        num_window_pages=130)
    req = Request(request_id="big", prompt_token_ids=list(range(8192)))
    assert kv.allocate(req, CHUNK) is not None     # 128 of 129 pages
    req.num_computed_tokens = CHUNK
    free = kv.num_free_pages
    assert not kv.can_allocate(req, CHUNK)
    assert kv.allocate(req, CHUNK) is None
    assert kv.num_free_pages == free and kv.window_pages_in_use == 128
