"""``AsyncOmni`` with a request that streams: partial outputs reach the
request's stream and nothing else (no forwarding, no final count), a
request that does not stream is what it was, and an aborted request
leaves the engine."""

import asyncio
import threading
import time

import pytest

from vllm_omni_tpu.config.stage import StageConfig
from vllm_omni_tpu.entrypoints.async_omni import AsyncOmni
from vllm_omni_tpu.entrypoints.omni_stage import StageRequest
from vllm_omni_tpu.resilience.faults import FaultPlan, set_fault_plan


def _llm_stage(stage_id, *, final=False, sources=None, max_tokens=4):
    return StageConfig(
        stage_id=stage_id,
        stage_type="llm",
        engine_args={
            "model_factory": "tests.helpers:tiny_lm_factory",
            "num_pages": 64, "page_size": 4, "max_model_len": 128,
        },
        engine_input_source=sources if sources is not None else [stage_id - 1],
        final_output=final,
        final_output_type="text",
        default_sampling_params={"temperature": 0.0, "max_tokens": max_tokens},
    )


@pytest.fixture(scope="module")
def one_stage():
    omni = AsyncOmni(stage_configs=[_llm_stage(0, final=True, sources=[-1])])
    yield omni
    omni.shutdown()


def _collect(omni, prompt, sp, **kw):
    async def run():
        return [o async for o in omni.generate(prompt, sp, **kw)]

    return asyncio.run(run())


def test_generate_with_stream_yields_partials_then_the_whole_final(
        one_stage):
    outs = _collect(one_stage, "hello", {"max_tokens": 8}, stream=True)
    *partials, final = outs
    assert final.finished and len(final.outputs[0].token_ids) == 8
    assert len(partials) >= 6
    assert all(not p.finished for p in partials)
    assert all(p.final_output_type == "text" and p.stage_id == 0
               for p in partials)
    ids = [t for p in partials for t in p.outputs[0].token_ids]
    assert ids == final.outputs[0].token_ids[:len(ids)]
    text = "".join(p.outputs[0].text for p in partials)
    assert final.outputs[0].text.startswith(text) and text
    assert all(p.prompt_token_ids == [] for p in partials)


def test_generate_without_stream_is_one_output(one_stage):
    quiet = _collect(one_stage, "hello", {"max_tokens": 8})
    assert len(quiet) == 1 and quiet[0].finished
    loud = _collect(one_stage, "hello", {"max_tokens": 8}, stream=True)
    assert quiet[0].outputs[0].token_ids == loud[-1].outputs[0].token_ids
    assert quiet[0].outputs[0].text == loud[-1].outputs[0].text
    assert quiet[0].prompt_token_ids == loud[-1].prompt_token_ids


def test_a_stage_keeps_no_decoder_after_the_final(one_stage):
    _collect(one_stage, "hello", {"max_tokens": 5}, stream=True)
    stage = one_stage._omni.stages[0]
    assert stage._stream_decoders == {}
    # partials are no requests of their own in the stage's statistics
    one_stage._omni.harvest_stage_stats()
    n0 = one_stage.metrics.stages[0].num_requests
    _collect(one_stage, "hello", {"max_tokens": 5}, stream=True)
    one_stage._omni.harvest_stage_stats()
    assert one_stage.metrics.stages[0].num_requests == n0 + 1


def test_generate_to_calls_the_sink_on_the_loops_thread(one_stage):
    calls, threads = [], set()
    done = threading.Event()

    class Sink:
        def feed(self, item):
            threads.add(threading.get_ident())
            calls.append(item)

        def close(self):
            threads.add(threading.get_ident())
            calls.append("closed")
            done.set()

    loop = asyncio.new_event_loop()
    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()
    try:
        rid = asyncio.run_coroutine_threadsafe(
            one_stage.generate_to(Sink(), [1, 2, 3], {"max_tokens": 6},
                                  stream=True), loop).result(timeout=60)
        assert done.wait(60)
    finally:
        loop.call_soon_threadsafe(loop.stop)
        t.join(timeout=10)
    assert threads == {t.ident}
    assert calls[-1] == "closed" and calls.count("closed") == 1
    *partials, final = calls[:-1]
    assert final.finished and final.request_id == rid
    assert partials and not any(p.finished for p in partials)
    assert rid not in one_stage._streams
    assert rid not in one_stage._finals_seen


def test_partials_are_not_forwarded_and_end_nothing():
    """Two stages, both finals: the first streams, the second is fed its
    FINAL alone, and the stream ends after both finals."""
    omni = AsyncOmni(stage_configs=[
        _llm_stage(0, final=True, sources=[-1], max_tokens=6),
        _llm_stage(1, final=True, max_tokens=3),
    ])
    try:
        fed = []
        consumer = omni._omni.stages[1]
        submit = consumer.submit
        consumer.submit = lambda reqs: (fed.extend(reqs), submit(reqs))[1]
        outs = _collect(omni, [1, 2, 3], {}, stream=True)
    finally:
        omni.shutdown()
    finals = [o for o in outs if o.finished]
    partials = [o for o in outs if not o.finished]
    assert [o.stage_id for o in finals] == [0, 1]
    assert partials and {p.stage_id for p in partials} == {0}
    # every partial stands before its stage's final
    assert outs.index(finals[0]) > max(outs.index(p) for p in partials)
    (req,) = fed
    assert req.prompt_token_ids == finals[0].outputs[0].token_ids
    assert req.stream is False


def test_abort_takes_the_request_out_of_the_engine(one_stage):
    set_fault_plan(FaultPlan.parse("step:delay_ms=40"))
    try:
        eng = one_stage._omni.stages[0].engine

        async def run():
            seen = 0
            async for o in one_stage.generate(
                    [1, 2, 3], {"max_tokens": 100, "ignore_eos": True},
                    request_id="gone", stream=True):
                seen += 1
                if seen == 3:
                    one_stage.abort("gone")
            return seen

        seen = asyncio.run(run())
        assert 3 <= seen < 20
        deadline = time.monotonic() + 3
        while eng.has_unfinished_requests and time.monotonic() < deadline:
            time.sleep(0.02)
        assert not eng.has_unfinished_requests
        kv = eng.scheduler.kv
        assert kv.num_free_pages == kv.num_pages
        assert one_stage._omni.stages[0]._stream_decoders == {}
    finally:
        set_fault_plan(None)


def test_a_stage_in_a_process_of_its_own_is_sent_no_stream_flag():
    """``ProcStage.submit`` clears the flag before the request crosses the
    channel, so such a stage answers with its one final output (no
    worker is spawned here: the frame is what is looked at)."""
    from vllm_omni_tpu.entrypoints.stage_proc import ProcStage

    stage = ProcStage.__new__(ProcStage)
    stage._submit_ts, stage._trace_ctx = {}, {}
    stage._inflight, stage._fatal = set(), None
    frames = []
    stage._locked_send = frames.append
    req = StageRequest(request_id="r", prompt_token_ids=[1], stream=True)
    stage.submit([req])
    (frame,) = frames
    assert frame["type"] == "submit"
    assert [r.stream for r in frame["requests"]] == [False]
    # and an abort crosses it as an abort
    stage._started_ids = set()
    stage.abort("r")
    assert frames[-1] == {"type": "abort", "request_id": "r"}
    assert not stage.has_unfinished


def test_partials_wait_for_the_engines_device_wait_firsts_and_finals_do_not(
        one_stage):
    """A request's first partial and a batch without partials are handed
    over at once, in ``omni.emit``; the partials after the first wait for
    the moment the engine thread next starts to wait for the device (as
    ``omni.readback`` is about to open inside ``omni.step``), or for the
    return of a poll that had no such moment."""
    handed = []   # (phase open at the hand-over, what was handed over)
    hand_over = one_stage._hand_over

    def spy(outbox):
        stack = [p.name for p in one_stage._phases._stack]
        for items in outbox.values():
            for item in items[1::2]:
                kind = ("end" if not hasattr(item, "finished")
                        else "final" if item.finished
                        else "first" if item.metrics else "later")
                handed.append((stack[-1] if stack else None, kind))
        hand_over(outbox)

    one_stage._hand_over = spy
    try:
        outs = _collect(one_stage, [1, 2, 3], {"max_tokens": 10},
                        stream=True)
    finally:
        del one_stage._hand_over
    assert len(outs) >= 9
    kinds = [k for _, k in handed]
    assert kinds[0] == "first" and kinds[-2:] == ["final", "end"]
    assert kinds.count("later") == len(outs) - 2
    where = {k: {ph for ph, kk in handed if kk == k} for k in set(kinds)}
    assert where["first"] == {"omni.emit"}
    assert where["final"] == {"omni.emit"}
    assert where["later"] <= {"omni.step", "omni.loop", "omni.emit"}
    assert "omni.step" in where["later"]


def test_a_loop_closed_with_items_on_their_way_does_not_stop_the_engine(
        one_stage):
    """``asyncio.run`` closes its loop when the caller leaves; what the
    engine thread still holds for it is dropped, and the thread lives."""
    async def leave_early():
        async for _ in one_stage.generate([1, 2, 3], {"max_tokens": 30},
                                          stream=True):
            break

    for _ in range(3):
        asyncio.run(leave_early())
    outs = _collect(one_stage, [1, 2, 3], {"max_tokens": 4}, stream=True)
    assert outs[-1].finished
    assert one_stage.engine_thread_alive
