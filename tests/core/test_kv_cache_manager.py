"""KV block-pool accounting (mirrors the reference scheduler's block
lifecycle incl. transfer pinning, omni_ar_scheduler.py:444-594)."""

from vllm_omni_tpu.core.kv_cache_manager import KVCacheManager
from vllm_omni_tpu.request import Request


def _req(rid="r0", n=10):
    return Request(request_id=rid, prompt_token_ids=list(range(n)))


def test_allocate_and_slots():
    kv = KVCacheManager(num_pages=8, page_size=4)
    req = _req(n=10)
    table = kv.allocate(req, 10)
    assert len(table) == 3  # ceil(10/4)
    assert kv.num_free_pages == 5
    slots = kv.slot_mapping(req, 10)
    assert len(slots) == 10
    assert slots[0] == table[0] * 4
    assert slots[4] == table[1] * 4
    assert slots[9] == table[2] * 4 + 1


def test_incremental_growth():
    kv = KVCacheManager(num_pages=4, page_size=4)
    req = _req(n=4)
    kv.allocate(req, 4)
    req.num_computed_tokens = 4
    # next token needs a new page
    table = kv.allocate(req, 1)
    assert len(table) == 2
    assert kv.slot_mapping(req, 1) == [table[1] * 4]


def test_free_returns_pages():
    kv = KVCacheManager(num_pages=4, page_size=4)
    req = _req(n=16)
    assert kv.allocate(req, 16) is not None
    assert kv.num_free_pages == 0
    kv.free(req)
    assert kv.num_free_pages == 4


def test_out_of_pages():
    kv = KVCacheManager(num_pages=2, page_size=4)
    r1, r2 = _req("a", 8), _req("b", 4)
    assert kv.allocate(r1, 8) is not None
    assert not kv.can_allocate(r2, 4)
    assert kv.allocate(r2, 4) is None


def test_pin_for_transfer_delays_free():
    kv = KVCacheManager(num_pages=4, page_size=4)
    req = _req(n=10)
    kv.allocate(req, 10)
    snapshot = kv.pin_for_transfer(req, 6)  # 6 tokens -> 2 pages
    assert len(snapshot) == 2
    kv.free(req)
    # 3 pages allocated, 2 pinned -> only 1 + 1 untouched free
    assert kv.num_free_pages == 2
    kv.ack_transfer(req.request_id)
    assert kv.num_free_pages == 4


def test_ack_with_live_table_keeps_pages():
    kv = KVCacheManager(num_pages=4, page_size=4)
    req = _req(n=8)
    kv.allocate(req, 8)
    kv.pin_for_transfer(req, 8)
    kv.ack_transfer(req.request_id)  # request still running
    assert kv.num_free_pages == 2
    kv.free(req)
    assert kv.num_free_pages == 4


# ---- the second kind of state: one slot a sequence (docs/kv_cache.md)
def test_a_state_slot_comes_with_the_first_page_and_goes_in_free():
    kv = KVCacheManager(num_pages=8, page_size=4, num_state_slots=2)
    a, b = _req("a", 6), _req("b", 3)
    assert kv.state_slot("a") == -1
    kv.allocate(a, 6)
    slot_a = kv.state_slot("a")
    a.num_computed_tokens = 6
    kv.allocate(a, 1)  # growing the table takes no second slot
    assert kv.state_slot("a") == slot_a and kv.state_slot_allocs == 1
    kv.allocate(b, 3)
    assert {slot_a, kv.state_slot("b")} == {0, 1}
    assert kv.state_slots_in_use == 2
    # every slot taken: a third sequence gets neither pages nor slot
    c = _req("c", 2)
    assert kv.allocate(c, 2) is None
    assert "c" not in kv._tables and kv.num_free_pages == 8 - 2 - 1
    kv.free(a)
    assert kv.state_slot("a") == -1 and kv.state_slots_in_use == 1
    kv.allocate(c, 2)
    assert kv.state_slot("c") == slot_a  # handed out again
    assert kv.debug_snapshot()["state_slots"]["by_request"] == {
        "b": kv.state_slot("b"), "c": slot_a}


def test_a_model_without_recurrent_layers_has_no_slots():
    kv = KVCacheManager(num_pages=4, page_size=4)
    req = _req(n=4)
    kv.allocate(req, 4)
    assert kv.state_slot(req.request_id) == -1
    assert kv.state_slots_in_use == 0 and kv.state_slot_allocs == 0


def test_a_prefix_hit_is_refused_and_counted_where_slots_exist():
    """The index finds the shared page; with state slots the hit is not
    handed out (pages hold no recurrent state), once a request."""
    prompt = list(range(1, 11))

    def second_request(slots):
        kv = KVCacheManager(num_pages=8, page_size=4, num_state_slots=slots)
        first = Request(request_id="p0", prompt_token_ids=prompt)
        kv.allocate(first, len(prompt))
        first.num_computed_tokens = len(prompt)
        kv.free(first)
        again = Request(request_id="p1", prompt_token_ids=prompt + [99])
        return kv, kv.match_prefix(again), again

    kv, matched, _ = second_request(0)
    assert matched == 8 and kv.prefix_hits == 1
    kv, matched, again = second_request(2)
    assert matched == 0 and kv.prefix_hits == 0
    assert again.num_computed_tokens == 0
    assert kv.prefix_hits_refused == 1
    assert kv.match_prefix(again) == 0 and kv.prefix_hits_refused == 1
