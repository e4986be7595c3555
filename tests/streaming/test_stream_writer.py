"""``ChatStream`` alone, over a socket pair: what it writes for a given
sequence of outputs, with no engine and no server in the way."""

import asyncio
import json
import socket
import types

import pytest

from vllm_omni_tpu.entrypoints.openai.chat_stream import (
    ChatStream,
    FlushQueue,
)
from vllm_omni_tpu.metrics.stats import StreamStats
from vllm_omni_tpu.outputs import CompletionOutput, OmniRequestOutput


class _Handler:
    """What ``ChatStream`` uses of the HTTP handler."""

    _ERROR_KIND_HTTP = {"shed": (429, "overloaded")}

    def __init__(self, sock):
        self.connection = sock
        self.aborted = []
        self.errors = []
        self.close_connection = False
        self.state = types.SimpleNamespace(
            model_name="m", flushes=FlushQueue(),
            omni=types.SimpleNamespace(
                metrics=types.SimpleNamespace(stream=StreamStats()),
                abort=self.aborted.append))

    def _sse_start(self):
        self.connection.sendall(b"HEADERS\n")

    def _sse_end(self):
        self.connection.sendall(b"END\n")

    def _logprob_content(self, ids, entries):
        return [{"token": str(i), "logprob": e} for i, e in zip(ids, entries)]

    def _chat_chunks(self, out, rid, created):
        yield {"audio": out.multimodal_output["audio"]}

    def _error(self, code, message, etype):
        self.errors.append((code, message, etype))

    def _surface_error(self, outs):
        self.errors.append((outs[0].error_kind, outs[0].error_message))


def _partial(text, ids, logprobs=None, first_token_mono=None):
    return OmniRequestOutput(
        request_id="r",
        outputs=[CompletionOutput(index=0, token_ids=ids, text=text,
                                  logprobs=logprobs)],
        metrics=({} if first_token_mono is None
                 else {"first_token_mono": first_token_mono}))


def _final(text, ids, logprobs=None, prompt=(1, 2, 3)):
    return OmniRequestOutput(
        request_id="r", finished=True, prompt_token_ids=list(prompt),
        outputs=[CompletionOutput(index=0, token_ids=ids, text=text,
                                  finish_reason="stop", logprobs=logprobs)])


@pytest.fixture()
def pair():
    a, b = socket.socketpair()
    yield a, b
    a.close()
    b.close()


def _play(handler, turns, **kw):
    """Feed each turn's items in ONE callback of a loop (as one hand-over
    of the engine thread delivers them), let the loop run dry between
    turns, close, and finish on this thread."""
    reply = ChatStream(handler, "id-1", 7, **kw)

    async def run():
        loop = asyncio.get_running_loop()
        for items in turns:
            loop.call_soon(lambda items=items: [reply.feed(i)
                                                for i in items])
            for _ in range(4):
                await asyncio.sleep(0)
        reply.close()

    asyncio.run(run())
    assert reply.done.is_set()
    reply.finish()
    return reply


def _read(sock):
    sock.setblocking(False)
    data = b""
    try:
        while chunk := sock.recv(1 << 20):
            data += chunk
    except BlockingIOError:
        pass
    events = []
    for line in data.decode().splitlines():
        if line.startswith("data: "):
            events.append(line[6:] if line[6:] == "[DONE]"
                          else json.loads(line[6:]))
    return data, events


def test_a_delta_of_no_text_sends_nothing_and_its_tokens_ride_the_next(pair):
    a, b = pair
    h = _Handler(a)
    _play(h, [
        [_partial("", [0xC3], logprobs=[-1.0])],     # half a character
        [_partial("é", [0xA9], logprobs=[-2.0])],
        [_partial("!", [33], logprobs=[-3.0])],
        [_final("é!?", [0xC3, 0xA9, 33, 63], logprobs=[-1., -2., -3., -4.])],
    ])
    data, events = _read(b)
    assert data.startswith(b"HEADERS\n") and data.endswith(b"END\n")
    assert events[-1] == "[DONE]"
    first, second, last = events[:-1]
    # the first event is the first TEXT: role, both tokens' logprobs
    assert first["choices"][0]["delta"] == {"role": "assistant",
                                            "content": "é"}
    assert first["choices"][0]["logprobs"]["content"] == [
        {"token": "195", "logprob": -1.0}, {"token": "169", "logprob": -2.0}]
    assert second["choices"][0]["delta"] == {"content": "!"}
    # the last carries what remains, its logprobs, the finish reason
    assert last["choices"][0]["delta"] == {"content": "?"}
    assert last["choices"][0]["finish_reason"] == "stop"
    assert last["choices"][0]["logprobs"]["content"] == [
        {"token": "63", "logprob": -4.0}]
    stats = h.state.omni.metrics.stream
    assert (stats.requests_total, stats.events_total,
            stats.tokens_total) == (1, 3, 4)


def test_deltas_delivered_together_leave_as_one_event(pair):
    a, b = pair
    h = _Handler(a)
    _play(h, [
        [_partial("a", [97], first_token_mono=0.0)],
        [_partial("b", [98]), _partial("c", [99]), _partial("d", [100])],
        [_final("abcde", [97, 98, 99, 100, 101])],
    ], include_usage=True)
    _, events = _read(b)
    contents = [e["choices"][0]["delta"]["content"] for e in events[:-1]]
    assert contents == ["a", "bcd", "e"]
    assert "usage" not in events[0] and "usage" not in events[1]
    assert events[2]["usage"] == {"prompt_tokens": 3,
                                  "completion_tokens": 5, "total_tokens": 8}
    stats = h.state.omni.metrics.stream
    assert stats.tokens_total / stats.events_total == 5 / 3
    assert stats.first_event_ms.snapshot()["count"] == 1


def test_a_reader_that_does_not_read_holds_up_nobody(pair):
    a, b = pair
    a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
    h = _Handler(a)
    reply = ChatStream(h, "id-1", 7)
    big = "x" * 2048
    got = []

    async def run():
        # far more than the socket takes, and nobody reads: every feed
        # returns at once, and the rest waits in ``pending``
        for i in range(200):
            reply.feed(_partial(big, [i]))
            await asyncio.sleep(0)
        assert reply.pending
        reply.close()

    asyncio.run(run())

    import threading

    def reader():
        b.settimeout(10)
        data = b""
        while not data.endswith(b"END\n"):
            data += b.recv(1 << 20)
        got.append(data)

    t = threading.Thread(target=reader)
    t.start()
    reply.finish()      # the handler's thread may wait for the reader
    t.join(timeout=20)
    text = "".join(
        json.loads(line[6:])["choices"][0]["delta"]["content"]
        for line in got[0].decode().splitlines()
        if line.startswith("data: {"))
    assert text == big * 200
    assert not h.aborted


def test_a_final_that_is_no_text_is_the_handler_threads(pair):
    a, b = pair
    h = _Handler(a)
    audio = OmniRequestOutput(request_id="r", finished=True,
                              final_output_type="audio",
                              multimodal_output={"audio": [0.5]})
    reply = _play(h, [[_partial("a", [97])],
                      [_final("ab", [97, 98])], [audio]])
    assert reply._deferred is None
    _, events = _read(b)
    assert [e["choices"][0]["delta"]["content"] for e in events[:2]] == \
        ["a", "b"]
    assert events[2:] == [{"audio": [0.5]}, "[DONE]"]


def test_an_error_before_any_output_is_the_handlers_to_answer(pair):
    a, b = pair
    h = _Handler(a)
    shed = OmniRequestOutput.from_error("r", "at capacity", kind="shed")
    _play(h, [[shed]])
    assert h.errors == [("shed", "at capacity")]
    assert _read(b)[0] == b""
    h2 = _Handler(a)
    _play(h2, [[ValueError("duplicate id")]])
    assert h2.errors == [(500, "duplicate id", "internal_error")]


def test_an_error_mid_stream_is_an_event_and_the_last(pair):
    a, b = pair
    h = _Handler(a)
    shed = OmniRequestOutput.from_error("r", "at capacity", kind="shed")
    _play(h, [[_partial("a", [97])], [shed], [_partial("b", [98])]])
    _, events = _read(b)
    assert events[0]["choices"][0]["delta"]["content"] == "a"
    assert events[1] == {"error": {"message": "at capacity",
                                   "type": "overloaded", "code": 429}}
    assert events[2:] == ["[DONE]"]


def test_a_client_that_is_gone_aborts_the_request(pair):
    a, b = pair
    h = _Handler(a)
    b.close()
    reply = _play(h, [[_partial("a", [97])], [_partial("b", [98])],
                      [_partial("c", [99])]])
    assert reply.lost and h.aborted == ["id-1"]
    assert h.close_connection


def test_a_pass_writes_first_events_before_later_ones():
    """Streams fed in one pass of the loop are written in one pass too,
    those that have not sent their first event ahead of the rest."""
    pairs = [socket.socketpair() for _ in range(3)]
    flushes = FlushQueue()
    order = []
    replies = []
    for k, (a, _) in enumerate(pairs):
        h = _Handler(a)
        h.state.flushes = flushes
        reply = ChatStream(h, f"id-{k}", 7)
        send = reply._send
        reply._send = lambda obj, k=k, send=send: (order.append(k),
                                                   send(obj))[1]
        replies.append(reply)

    async def run():
        loop = asyncio.get_running_loop()
        # streams 0 and 1 have spoken; 2 has not
        loop.call_soon(lambda: [replies[0].feed(_partial("a", [97])),
                                replies[1].feed(_partial("b", [98]))])
        for _ in range(4):
            await asyncio.sleep(0)
        order.clear()
        loop.call_soon(lambda: [replies[0].feed(_partial("c", [99])),
                                replies[1].feed(_partial("d", [100])),
                                replies[2].feed(_partial("e", [101]))])
        for _ in range(4):
            await asyncio.sleep(0)

    asyncio.run(run())
    assert order == [2, 0, 1]
    for a, b in pairs:
        a.close()
        b.close()
