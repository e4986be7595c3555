"""What a step hands over: partial outputs for the requests that stream.

A request whose ``stream`` field is set (``Request.stream``, which the
serving layer sets where the client sent ``"stream": true``) gets, from
every retire that appended tokens to it, one ``OmniRequestOutput`` with
``finished=False`` whose one ``CompletionOutput`` holds ONLY the new
token ids (and their logprob entries where the request asked for them).
Not ``from_pipeline``: that copies the prompt's ids and every output id,
which at a 16k-token prompt would be 16k integers a row a step.  A
finished request's output is the whole one it always was, so everything
downstream of a final (forwarding, decoding, the non-streamed reply) is
untouched, and a request that does not stream costs one attribute test.

The module stands apart from ``llm_engine.py`` so that no line of that
file moves (the persistent compile cache's key holds source positions).
"""

from __future__ import annotations

import time

from vllm_omni_tpu.outputs import CompletionOutput, OmniRequestOutput

# What a partial output has nothing of, shared by every one and never
# written: at 256 streaming rows a step the five containers a default
# ``OmniRequestOutput`` builds for itself were 1 300 allocations a step,
# and the collector ran four times as often as without them (2.7 s of a
# 45 s window against 0.6; PERF.md, PR 56).
_NO_LIST: list = []
_NO_DICT: dict = {}


def partial_output(req, now: float) -> OmniRequestOutput:
    """The tokens ``req`` gained since its last hand-over, as an output
    that is not finished.  ``metrics["first_token_mono"]`` rides the
    first one: the instant the engine had the first token, from which
    the serving layer measures its own hand-over
    (``stream_first_event_ms``)."""
    n0, req.num_streamed = req.num_streamed, len(req.output_token_ids)
    logprobs = None
    if req.output_logprobs:
        l0 = req.num_streamed_logprobs
        req.num_streamed_logprobs = len(req.output_logprobs)
        logprobs = req.output_logprobs[l0:]
    return OmniRequestOutput(
        request_id=req.request_id,
        prompt_token_ids=_NO_LIST,
        outputs=[CompletionOutput(
            index=0, token_ids=req.output_token_ids[n0:],
            logprobs=logprobs)],
        images=_NO_LIST,
        multimodal_output=_NO_DICT,
        metrics={"first_token_mono": now} if n0 == 0 else _NO_DICT,
        additional_information=_NO_DICT,
    )


def step_outputs(rows, finished) -> list[OmniRequestOutput]:
    """A step's outputs: a partial for each live row that streams and
    gained tokens, then the whole output of each request that finished.
    ``rows`` are the step's scheduled rows (a row that sampled nothing,
    as a prefill chunk short of its prompt's end, gained nothing)."""
    outs = []
    now = 0.0
    for s in rows:
        req = s.request
        if (req.stream and not req.status.is_finished
                and len(req.output_token_ids) > req.num_streamed):
            now = now or time.monotonic()
            outs.append(partial_output(req, now))
    outs.extend(OmniRequestOutput.from_pipeline(r) for r in finished)
    return outs
