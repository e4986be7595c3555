"""Self-contained tokenizers.

``ByteTokenizer`` is the zero-dependency default used when no HF tokenizer
files ship with a model (random-weight pipelines, tests, benches): UTF-8
bytes + special tokens.  When a model directory carries a real HF
tokenizer, ``load_tokenizer`` prefers it (transformers is in the image).
"""

from __future__ import annotations

import codecs
import os
from typing import Optional

import numpy as np


class ByteTokenizer:
    PAD, BOS, EOS = 0, 1, 2
    _SPECIALS = 3

    def __init__(self, vocab_size: int = 512):
        if vocab_size < 256 + self._SPECIALS:
            # byte values collapse modulo the usable range
            self.byte_span = vocab_size - self._SPECIALS
        else:
            self.byte_span = 256
        self.vocab_size = vocab_size
        self.pad_token_id = self.PAD
        self.bos_token_id = self.BOS
        self.eos_token_id = self.EOS

    def encode(self, text: str, add_bos: bool = True) -> list[int]:
        ids = [self._SPECIALS + (b % self.byte_span) for b in text.encode()]
        return ([self.BOS] if add_bos else []) + ids

    def decode(self, ids) -> str:
        bs = bytes(
            (int(i) - self._SPECIALS) % max(1, self.byte_span)
            for i in ids
            if int(i) >= self._SPECIALS
        )
        return bs.decode(errors="replace")

    def stream_decoder(self) -> "_ByteStreamDecoder":
        """An incremental ``decode`` for one reply (``stream_decoder``
        below)."""
        return _ByteStreamDecoder(self)

    def batch_encode(
        self, texts: list[str], max_len: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """Right-padded [B, max_len] ids + lengths."""
        out = np.full((len(texts), max_len), self.PAD, np.int32)
        lens = np.zeros((len(texts),), np.int32)
        for i, t in enumerate(texts):
            ids = self.encode(t)[:max_len]
            out[i, : len(ids)] = ids
            lens[i] = len(ids)
        return out, lens


class _ByteStreamDecoder:
    """``ByteTokenizer.decode`` a few ids at a time: the codec's own
    incremental decoder keeps an incomplete trailing UTF-8 sequence until
    the bytes that complete it (or show it invalid) arrive, so the pieces
    joined are a prefix of ``decode`` of all the ids, character for
    character, ``errors="replace"`` included."""

    def __init__(self, tok: ByteTokenizer):
        self._first, self._span = tok._SPECIALS, max(1, tok.byte_span)
        self._dec = codecs.getincrementaldecoder("utf-8")("replace")

    def feed(self, ids) -> str:
        first, span = self._first, self._span
        return self._dec.decode(bytes(
            (int(i) - first) % span for i in ids if int(i) >= first))


class _WindowStreamDecoder:
    """Incremental decoding for a tokenizer that offers ``decode(ids)``
    alone (an HF tokenizer): the new ids are decoded behind the ids of
    the piece before them, since a token's text can depend on what stands
    to its left (a leading space), and a piece that ends in U+FFFD is
    held back, since the bytes of a character can be split over tokens.
    It holds the ids of the last piece and those not yet turned into
    text, never the whole reply."""

    def __init__(self, tokenizer):
        self._decode = tokenizer.decode
        self._ids: list[int] = []
        self._n_context = 0

    def feed(self, ids) -> str:
        self._ids.extend(int(i) for i in ids)
        context = self._decode(self._ids[:self._n_context])
        text = self._decode(self._ids)
        if len(text) <= len(context) or text.endswith("\ufffd"):
            return ""
        del self._ids[:self._n_context]
        self._n_context = len(self._ids)
        return text[len(context):]


def stream_decoder(tokenizer):
    """An object whose ``feed(new_ids)`` returns the text those ids add
    to a reply: the pieces, joined, are a prefix of
    ``tokenizer.decode(all ids)``, and what an incomplete trailing
    character withholds is the rest of it.  A tokenizer that knows how to
    decode itself incrementally says so (``stream_decoder()``)."""
    own = getattr(tokenizer, "stream_decoder", None)
    return own() if own is not None else _WindowStreamDecoder(tokenizer)


def load_tokenizer(model_path: Optional[str], vocab_size: int = 512):
    """HF tokenizer when available, byte fallback otherwise."""
    if model_path and os.path.isdir(model_path):
        for f in ("tokenizer.json", "tokenizer_config.json"):
            if os.path.exists(os.path.join(model_path, f)):
                try:
                    from transformers import AutoTokenizer

                    return AutoTokenizer.from_pretrained(model_path)
                except Exception:
                    break
    return ByteTokenizer(vocab_size)
