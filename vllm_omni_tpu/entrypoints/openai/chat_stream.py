"""One streamed chat reply, written as it is made.

``/v1/chat/completions`` with ``"stream": true`` answers with server-sent
events.  The request's outputs arrive on the server's event-loop thread
(``ServerState.stream``): a partial output for every engine step that
added tokens to the reply, then the final one.  That thread is the ONE
writer of every stream's events while the reply is being made: it
formats an event and offers it to the socket without waiting, so no
thread is woken for a token and a slow reader holds up nobody (what its
socket does not take waits in ``pending``).  The handler's own thread
sleeps until the reply ends, and then writes what is left: bytes the
socket had refused, finals that are no text (audio is large, and is
formatted on the thread that owns the connection, as it always was), and
``[DONE]``.

The events of one reply:

- nothing with a ``content`` key leaves before a generated token's text
  exists; the first content event carries ``role`` beside it;
- later events carry ``delta.content`` alone, each with the text of the
  tokens that arrived since the last one.  What waits when the writer
  gets to a stream goes as ONE event: a delta's flush is put behind
  whatever the loop has in hand, so where the loop falls behind the
  steps, several steps' tokens share an event and the event rate stops
  following the token rate;
- a delta whose text is still empty (the bytes of an unfinished
  character) sends nothing; its tokens and logprobs ride the next event;
- the last text event carries what remains of the reply (the final
  output's text behind the characters already sent, so that the events'
  contents joined are ``tokenizer.decode`` of the whole reply) with
  ``finish_reason``, and ``usage`` where
  ``stream_options.include_usage`` asked for it; then ``[DONE]``.
"""

from __future__ import annotations

import asyncio
import json
import socket
import threading
import time

from vllm_omni_tpu.logger import init_logger

logger = init_logger(__name__)


def sse_frame(obj) -> bytes:
    """One server-sent event as one HTTP chunk."""
    payload = ("data: " + (obj if isinstance(obj, str)
                           else json.dumps(obj)) + "\n\n").encode()
    return f"{len(payload):x}\r\n".encode() + payload + b"\r\n"


#: stands where the text goes in a stream's pre-built delta event
_MARK = "\x00"


class FlushQueue:
    """The streams that hold a delta to write, written in ONE pass of the
    event loop behind whatever the loop had in hand when the first of
    them was fed: first events first (a request's time to first token is
    what its user waits for; a later token's event is one of many).
    The loop's thread alone touches it."""

    def __init__(self):
        self._waiting: list[ChatStream] = []

    def add(self, stream: "ChatStream") -> None:
        if not self._waiting:
            asyncio.get_running_loop().call_soon(self._run)
        self._waiting.append(stream)

    def _run(self) -> None:
        waiting, self._waiting = self._waiting, []
        waiting.sort(key=_has_spoken)
        for stream in waiting:
            stream._queued_flush()


def _has_spoken(stream: "ChatStream") -> bool:
    return stream._role_sent


class ChatStream:
    def __init__(self, handler, rid: str, created: int,
                 include_usage: bool = False):
        self.handler = handler
        self.rid = rid
        self.include_usage = include_usage
        self.stats = handler.state.omni.metrics.stream
        self._flushes: FlushQueue = handler.state.flushes
        self._base = {"id": rid, "object": "chat.completion.chunk",
                      "created": created,
                      "model": handler.state.model_name}
        #: set when no further output will come (event-loop thread)
        self.done = threading.Event()
        #: a first item that cannot start a stream: an exception or an
        #: errored output, which the handler answers with its HTTP status
        self.refused = None
        self.started = False   # the SSE response's headers are written
        self.lost = False      # the client went away
        self._ended = False    # an error event was the stream's last
        # items kept for the handler's thread (``finish``); None once
        # that thread writes
        self._deferred: list | None = []
        self._blocking = False
        self.pending = bytearray()  # bytes the socket has not taken yet
        # the delta that waits for its flush
        self._text: list[str] = []
        self._ids: list[int] = []
        self._logprobs: list = []
        self._final = None
        self._flush_queued = False
        # of the reply so far: what partial outputs have brought
        self._chars = 0
        self._tokens = 0
        self._n_logprobs = 0
        self._role_sent = False
        self._first_token_mono = None
        # the event of a plain delta (content alone), built once: the
        # text of all but a reply's first and last event goes between
        self._delta_json: tuple[str, str] | None = None

    # ------------------------------------------------ event-loop thread
    def feed(self, item) -> None:
        """The request's next output (or the exception that ended it)."""
        try:
            self._feed(item)
        except OSError:
            self._client_lost()
        except Exception:
            # a fault of this writer must not end the loop's task with
            # the handler's thread still asleep: the stream ends here
            logger.exception("stream %s: writer failed", self.rid)
            self._client_lost()

    def close(self) -> None:
        """No further output: write what waits, wake the handler (after
        which a flush still queued on the loop finds ``done`` set, and
        leaves the stream to the handler's thread)."""
        try:
            self._flush()
        except OSError:
            self._client_lost()
        self.done.set()

    def _feed(self, item) -> None:
        if self.lost or self._ended or self.refused is not None:
            return
        failed = isinstance(item, Exception) or item.is_error
        if not self.started:
            if failed:
                # before any output the status line is still ours to
                # choose: a 429 buried inside a 200 stream would hide the
                # back-off contract from every streaming client
                self.refused = item
                return
            self._start()
        if failed:
            self._flush()
            self._send(self._error_event(item))
            self._ended = True
            return
        is_text = item.final_output_type == "text" and bool(item.outputs)
        if self._deferred is not None and (self._deferred or not is_text):
            self._deferred.append(item)
            return
        if not is_text:
            for chunk in self.handler._chat_chunks(item, self.rid,
                                                   self._base["created"]):
                self._send(chunk)
            return
        if item.finished:
            self._final = item
        else:
            c = item.outputs[0]
            if c.text:
                self._text.append(c.text)
                self._chars += len(c.text)
            self._ids.extend(c.token_ids)
            self._tokens += len(c.token_ids)
            if c.logprobs:
                self._logprobs.extend(c.logprobs)
                self._n_logprobs += len(c.logprobs)
            t = item.metrics.get("first_token_mono")
            if t is not None:
                self._first_token_mono = t
        if self._blocking:
            self._flush()
        elif not self._flush_queued:
            # behind whatever the loop has in hand: deltas of this stream
            # that are already waiting there join this one
            self._flush_queued = True
            self._flushes.add(self)

    def _queued_flush(self) -> None:
        if self.done.is_set():
            return
        try:
            self._flush()
        except OSError:
            self._client_lost()

    def _start(self) -> None:
        self.started = True
        self.stats.requests_total += 1
        self.handler._sse_start()

    def _flush(self) -> None:
        self._flush_queued = False
        final, self._final = self._final, None
        if self.lost or self._ended or not (self._text or final):
            return
        text = "".join(self._text)
        self._text.clear()
        if final is None and self._role_sent and not self._logprobs:
            n = len(self._ids)
            self._ids.clear()
            self._send(self._plain_delta(text))
            self._sent(n)
            return
        ids, logprobs = self._ids, self._logprobs
        self._ids, self._logprobs = [], []
        choice = {"index": 0, "delta": {}, "finish_reason": None}
        if final is not None:
            whole = final.outputs[0]
            text += (whole.text or "")[self._chars:]
            ids = ids + list(whole.token_ids[self._tokens:])
            if whole.logprobs is not None:
                logprobs = logprobs + list(
                    whole.logprobs[self._n_logprobs:])
            choice["finish_reason"] = whole.finish_reason
        if not self._role_sent:
            self._role_sent = True
            choice["delta"]["role"] = "assistant"
        choice["delta"]["content"] = text
        if logprobs or (final is not None
                        and final.outputs[0].logprobs is not None):
            choice["logprobs"] = {"content": self.handler._logprob_content(
                ids, logprobs)}
        chunk = {**self._base, "choices": [choice]}
        if final is not None and self.include_usage:
            n_in = len(final.prompt_token_ids)
            n_out = len(final.outputs[0].token_ids)
            chunk["usage"] = {"prompt_tokens": n_in,
                              "completion_tokens": n_out,
                              "total_tokens": n_in + n_out}
        self._send(chunk)
        self._sent(len(ids))

    def _plain_delta(self, text: str) -> str:
        if self._delta_json is None:
            whole = json.dumps({**self._base, "choices": [{
                "index": 0, "delta": {"content": _MARK},
                "finish_reason": None}]})
            head, tail = whole.split(json.dumps(_MARK))
            self._delta_json = head, tail
        head, tail = self._delta_json
        return head + json.dumps(text) + tail

    def _sent(self, n_tokens: int) -> None:
        stats = self.stats
        stats.events_total += 1
        stats.tokens_total += n_tokens
        if self._first_token_mono is not None:
            stats.first_event_ms.observe(
                (time.monotonic() - self._first_token_mono) * 1e3)
            self._first_token_mono = None

    def _error_event(self, item) -> dict:
        # mid-stream failure: the status line is long gone, so the SSE
        # error event carries the taxonomy (type + would-be HTTP code)
        # for clients to act on
        if isinstance(item, Exception):
            return {"error": {"message": str(item),
                              "type": "internal_error", "code": 500}}
        code, etype = self.handler._ERROR_KIND_HTTP.get(
            item.error_kind, (500, "internal_error"))
        return {"error": {"message": item.error_message
                          or "request failed",
                          "type": etype, "code": code}}

    def _send(self, obj) -> None:
        """An event to the socket: all of it on the handler's thread,
        as much as the socket takes at once on the loop's."""
        self.pending += sse_frame(obj)
        sock = self.handler.connection
        if self._blocking:
            sock.sendall(self.pending)
            self.pending.clear()
            return
        try:
            n = sock.send(self.pending, socket.MSG_DONTWAIT)
        except BlockingIOError:
            return
        del self.pending[:n]

    def _client_lost(self) -> None:
        """The peer closed the connection: nobody reads this reply, so
        the engine stops making it and frees its pages."""
        if not self.lost:
            self.lost = True
            self.handler.state.omni.abort(self.rid)

    # ---------------------------------------------------- handler thread
    def finish(self) -> None:
        """After ``done``: what the loop's thread left for this one."""
        handler = self.handler
        if self.refused is not None:
            if isinstance(self.refused, Exception):
                handler._error(500, str(self.refused), "internal_error")
            else:
                handler._surface_error([self.refused])
            return
        self._blocking = True
        try:
            if not self.started:
                self._start()
            deferred, self._deferred = self._deferred, None
            if not self.lost:
                if self.pending:
                    handler.connection.sendall(self.pending)
                    self.pending.clear()
                for item in deferred:
                    self._feed(item)
                self._send("[DONE]")
                handler._sse_end()
        except OSError:
            self._client_lost()
        if self.lost:
            handler.close_connection = True
