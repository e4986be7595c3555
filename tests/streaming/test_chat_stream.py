"""A streamed chat reply leaves the server a step at a time
(entrypoints/openai/chat_stream.py): the first content event carries the
first token's text and arrives while the reply is being made, the events'
contents joined are the non-streamed reply, ``finish_reason`` and
``usage`` stand on the last event alone, a client that goes away frees
its request, and deltas that wait share an event."""

import json
import socket
import threading
import time

import httpx
import pytest

from vllm_omni_tpu.config.stage import StageConfig
from vllm_omni_tpu.entrypoints.openai.api_server import build_server
from vllm_omni_tpu.resilience.faults import FaultPlan, set_fault_plan

STEP_DELAY_MS = 60


def _llm_stage(**engine):
    return StageConfig(
        stage_id=0,
        stage_type="llm",
        engine_args={
            "model_factory": "tests.helpers:tiny_lm_factory",
            "num_pages": 64, "page_size": 4, "max_model_len": 128,
            **engine,
        },
        engine_input_source=[-1],
        final_output=True,
        final_output_type="text",
        default_sampling_params={"temperature": 0.0, "max_tokens": 4},
    )


@pytest.fixture(scope="module")
def served():
    server, state = build_server(
        model="tiny-lm", stage_configs=[_llm_stage()],
        host="127.0.0.1", port=0,
    )
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{port}"
    # compile what the tests run before any of them reads a clock
    httpx.post(f"{url}/v1/chat/completions", json=_body(max_tokens=8),
               timeout=300)
    yield url, state
    server.shutdown()
    state.shutdown()


@pytest.fixture()
def slow_steps():
    """Every engine step takes STEP_DELAY_MS longer (the ``step`` site of
    resilience/faults.py): a reply is then seconds long, its steps far
    enough apart to tell on a clock."""
    set_fault_plan(FaultPlan.parse(f"step:delay_ms={STEP_DELAY_MS}"))
    yield STEP_DELAY_MS / 1e3
    set_fault_plan(None)


def _body(**kw):
    return {"messages": [{"role": "user", "content": "hi"}],
            "temperature": 0, **kw}


def _events(url, **kw):
    """A streamed reply: [(arrival instant, raw line, parsed or None)]."""
    out = []
    with httpx.stream("POST", f"{url}/v1/chat/completions",
                      json=_body(stream=True, **kw), timeout=120) as r:
        assert r.status_code == 200
        assert "text/event-stream" in r.headers["content-type"]
        for line in r.iter_lines():
            if line.startswith("data: "):
                raw = line[6:]
                out.append((time.monotonic(), raw,
                            None if raw == "[DONE]" else json.loads(raw)))
    return out


def _contents(events):
    return [e["choices"][0]["delta"].get("content")
            for _, _, e in events if e is not None]


def _engine(state):
    return state.omni._omni.stages[0].engine


def _stream_counts(url):
    s = httpx.get(f"{url}/metrics?format=json", timeout=30).json()["stream"]
    return s["requests_total"], s["events_total"], s["tokens_total"]


# ------------------------------------------------------ the first event
def test_the_first_content_event_holds_the_first_token_and_comes_early(
        served, slow_steps):
    url, _ = served
    n = 8
    events = _events(url, max_tokens=n)
    first_t, first_raw, first = events[0]
    done_t = events[-1][0]
    assert events[-1][1] == "[DONE]"
    # no line that holds "content" stands before generated text exists:
    # the first one carries the role AND text, of no more than the first
    # step's tokens (the tiny model's tokens are a character each; the
    # two-slot pipeline retires a prompt's first token and the next
    # together at most)
    assert '"content"' in first_raw
    delta = first["choices"][0]["delta"]
    assert delta["role"] == "assistant"
    assert 1 <= len(delta["content"]) <= 2
    assert first["choices"][0]["finish_reason"] is None
    # it arrived while the reply was being made, steps before its end
    assert done_t - first_t >= (n - 4) * slow_steps
    # and the reply took its steps: nothing was buffered to the end
    arrivals = [t for t, _, e in events if e is not None]
    assert arrivals[-1] - arrivals[0] >= (n - 4) * slow_steps
    assert len(arrivals) >= n - 2


def test_later_events_carry_content_alone(served):
    url, _ = served
    events = [e for _, _, e in _events(url, max_tokens=6) if e is not None]
    assert len(events) >= 2
    for e in events[1:]:
        assert set(e["choices"][0]["delta"]) == {"content"}
        assert e["object"] == "chat.completion.chunk"
        assert e["id"] == events[0]["id"]


# --------------------------------------------- the reply, whole and same
@pytest.mark.parametrize("prompt,n", [("hi", 12), ("a longer question", 7),
                                      ("x", 2)])
def test_the_deltas_joined_are_the_reply_that_is_not_streamed(
        served, prompt, n):
    # greedy: this engine's seeded draws depend on what it served before
    # (two non-streamed requests of one seed differ too), so only the
    # greedy reply is the same reply twice
    url, _ = served
    sampling = {"messages": [{"role": "user", "content": prompt}]}
    whole = httpx.post(f"{url}/v1/chat/completions",
                       json=_body(max_tokens=n, **sampling),
                       timeout=120).json()
    events = _events(url, max_tokens=n, **sampling,
                     stream_options={"include_usage": True})
    assert events[-1][1] == "[DONE]"
    chunks = [e for _, _, e in events if e is not None]
    assert "".join(_contents(events)) == \
        whole["choices"][0]["message"]["content"]
    # finish_reason and usage stand on the last chunk only
    assert [c["choices"][0]["finish_reason"] for c in chunks[:-1]] == \
        [None] * (len(chunks) - 1)
    assert chunks[-1]["choices"][0]["finish_reason"] == \
        whole["choices"][0]["finish_reason"] == "length"
    assert all("usage" not in c for c in chunks[:-1])
    assert chunks[-1]["usage"] == whole["usage"]


def test_usage_is_sent_only_where_asked(served):
    url, _ = served
    chunks = [e for _, _, e in _events(url, max_tokens=3) if e is not None]
    assert all("usage" not in c for c in chunks)


def test_logprobs_ride_the_events_of_their_tokens(served):
    url, _ = served
    kw = dict(max_tokens=6, logprobs=True, top_logprobs=2)
    whole = httpx.post(f"{url}/v1/chat/completions", json=_body(**kw),
                       timeout=120).json()["choices"][0]
    chunks = [e for _, _, e in _events(url, **kw) if e is not None]
    assert len(chunks) >= 4
    got = [entry for c in chunks
           for entry in c["choices"][0]["logprobs"]["content"]]
    assert got == whole["logprobs"]["content"]
    for c in chunks:
        # an event's entries are its own tokens', a character each here
        assert len(c["choices"][0]["logprobs"]["content"]) == \
            len(c["choices"][0]["delta"]["content"])


def test_a_reply_of_one_token_is_one_event_with_everything(served):
    url, _ = served
    events = _events(url, max_tokens=1)
    assert [raw for _, raw, _ in events][-1] == "[DONE]"
    (chunk,) = [e for _, _, e in events if e is not None]
    choice = chunk["choices"][0]
    assert choice["delta"]["role"] == "assistant"
    assert len(choice["delta"]["content"]) == 1
    assert choice["finish_reason"] == "length"


# ----------------------------------------------------- what is refused
def test_a_request_refused_at_admission_keeps_its_http_status(served):
    url, _ = served
    # media for a stage with no multimodal front end: the engine's
    # intake refuses it, and the stream's first item is that refusal
    png = ("iVBORw0KGgoAAAANSUhEUgAAAAEAAAABCAYAAAAfFcSJAAAADUlEQVR42mNk"
           "YPhfDwAChwGA60e6kgAAAABJRU5ErkJggg==")
    r = httpx.post(f"{url}/v1/chat/completions", json={
        "messages": [{"role": "user", "content": [
            {"type": "text", "text": "what is this"},
            {"type": "image_url",
             "image_url": {"url": f"data:image/png;base64,{png}"}}]}],
        "stream": True, "max_tokens": 4}, timeout=60)
    assert r.status_code == 400
    assert "content-type" in r.headers
    assert "event-stream" not in r.headers["content-type"]
    assert r.json()["error"]["type"] == "invalid_request_error"


def test_n_above_one_with_stream_stays_refused(served):
    url, _ = served
    r = httpx.post(f"{url}/v1/chat/completions",
                   json=_body(stream=True, n=2), timeout=30)
    assert r.status_code == 400


# ------------------------------------------------ a client that goes away
def test_a_client_that_closes_mid_stream_frees_its_request(
        served, slow_steps):
    url, state = served
    eng = _engine(state)
    host, port = url.rsplit("/", 1)[1].split(":")
    body = json.dumps(_body(stream=True, max_tokens=100,
                            ignore_eos=True)).encode()
    sock = socket.create_connection((host, int(port)), timeout=30)
    sock.sendall(b"POST /v1/chat/completions HTTP/1.1\r\n"
                 b"Host: x\r\nContent-Type: application/json\r\n"
                 + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    seen = b""
    while b'"content"' not in seen:
        seen += sock.recv(65536)
    assert eng.scheduler.running, "the request should still be running"
    sock.close()
    # 100 tokens at the slowed step are six seconds: the request is
    # gone, and its pages are back, long before it would have finished
    deadline = time.monotonic() + 3.0
    kv = eng.scheduler.kv
    while time.monotonic() < deadline and (
            eng.has_unfinished_requests
            or kv.num_free_pages != kv.num_pages):
        time.sleep(0.02)
    assert not eng.has_unfinished_requests
    assert kv.num_free_pages == kv.num_pages
    assert not state.omni._streams
    # and the server goes on serving
    assert _contents(_events(url, max_tokens=2))


def test_pause_generation_ends_the_streams_in_flight(served, slow_steps):
    import asyncio

    url, state = served
    got = {}

    def client():
        got["events"] = _events(url, max_tokens=100, ignore_eos=True)

    t = threading.Thread(target=client)
    t.start()
    deadline = time.monotonic() + 30
    while not state.omni._streams and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(4 * slow_steps)
    asyncio.run_coroutine_threadsafe(
        state.omni.pause_generation(), state.loop).result(timeout=30)
    t.join(timeout=30)
    assert not t.is_alive()
    try:
        # the stream was cut short and closed cleanly: some content, no
        # finish_reason, [DONE]; the engine holds nothing
        events = got["events"]
        assert events[-1][1] == "[DONE]"
        text = "".join(c for c in _contents(events) if c)
        assert 0 < len(text) < 100
        assert all(e["choices"][0]["finish_reason"] is None
                   for _, _, e in events if e is not None)
        eng = _engine(state)
        assert not eng.has_unfinished_requests
        assert eng.scheduler.kv.num_free_pages == eng.scheduler.kv.num_pages
    finally:
        asyncio.run_coroutine_threadsafe(
            state.omni.resume_generation(), state.loop).result(timeout=30)
    assert "".join(_contents(_events(url, max_tokens=3)))


# ------------------------------------------------------------ coalescing
def test_deltas_that_wait_share_an_event_and_the_counters_say_so(served):
    """While the writer's thread is held, the engine goes on stepping;
    what has gathered for the stream when the writer gets to it leaves as
    ONE event, and tokens over events reads above 1."""
    url, state = served
    n = 40
    r0, e0, k0 = _stream_counts(url)
    held = threading.Event()

    def hold():
        held.set()
        time.sleep(1.0)

    events = []
    with httpx.stream("POST", f"{url}/v1/chat/completions",
                      json=_body(stream=True, max_tokens=n,
                                 ignore_eos=True), timeout=120) as r:
        for line in r.iter_lines():
            if not line.startswith("data: ") or line[6:] == "[DONE]":
                continue
            events.append(json.loads(line[6:]))
            if not held.is_set():
                # the first event is out: hold the loop's thread for
                # longer than the rest of the reply takes to make
                state.loop.call_soon_threadsafe(hold)
                held.wait(10)
    contents = [e["choices"][0]["delta"]["content"] for e in events]
    assert sum(len(c) for c in contents) == n
    assert max(len(c) for c in contents) >= 10
    assert len(events) <= n // 2
    r1, e1, k1 = _stream_counts(url)
    assert r1 - r0 == 1
    assert e1 - e0 == len(events)
    assert k1 - k0 == n
    assert (k1 - k0) / (e1 - e0) >= 2


def test_a_token_an_event_while_the_writer_keeps_up(served, slow_steps):
    url, _ = served
    _, e0, k0 = _stream_counts(url)
    events = _events(url, max_tokens=6)
    _, e1, k1 = _stream_counts(url)
    assert k1 - k0 == 6
    # at most the first step's two tokens share an event
    assert 5 <= e1 - e0 <= 6
    assert len([e for _, _, e in events if e is not None]) == e1 - e0


def test_the_stream_metrics_are_on_both_faces(served):
    url, _ = served
    _events(url, max_tokens=3)
    snap = httpx.get(f"{url}/metrics?format=json", timeout=30).json()
    hist = snap["stream"]["first_event_ms"]
    assert hist["count"] >= 1 and hist["sum"] > 0
    # engine's first token to the first event's write: milliseconds
    assert hist["p50"] < 500
    text = httpx.get(f"{url}/metrics", timeout=30).text
    for name in ("stream_requests_total", "stream_events_total",
                 "stream_tokens_total", "stream_first_event_ms_count"):
        assert f"vllm_omni_tpu_{name}" in text
    from vllm_omni_tpu.metrics.prometheus import validate_exposition

    assert validate_exposition(text) == []
