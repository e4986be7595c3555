"""Incremental decoding (utils/tokenizer.py ``stream_decoder``): the
pieces of a reply, joined, are a prefix of ``decode`` of all its ids, and
what is held back is an unfinished trailing character and nothing else.
Seeded random ids: multi-byte sequences split over tokens at every
point, bytes that are no UTF-8 at all, ids far above the byte range."""

import random

import pytest

from vllm_omni_tpu.utils.tokenizer import (
    ByteTokenizer,
    _WindowStreamDecoder,
    stream_decoder,
)

# bytes from which the draws are weighted: leads of 2-, 3- and 4-byte
# sequences, continuations, ASCII, and bytes no UTF-8 text holds
_BYTES = [0xE2, 0x82, 0xAC, 0xF0, 0x9F, 0x98, 0x80, 0x41, 0x20, 0xC3, 0xA9,
          0xFF, 0xC0, 0xED, 0xA0, 0x80, 0xF4, 0x90]


def _ids(rng, tok, n):
    out = []
    for _ in range(n):
        if rng.random() < 0.4:
            out.append(rng.randrange(tok.vocab_size))
        else:
            out.append(tok._SPECIALS + rng.choice(_BYTES))
    return out


def _stream(decoder, ids, rng, most=3):
    pieces, i = [], 0
    while i < len(ids):
        k = rng.randrange(1, most + 1)
        pieces.append(decoder.feed(ids[i:i + k]))
        i += k
    return pieces


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("vocab", [512, 152064])
def test_the_pieces_joined_are_decode_of_the_whole(seed, vocab):
    tok = ByteTokenizer(vocab)
    rng = random.Random(seed)
    for _ in range(200):
        ids = _ids(rng, tok, rng.randrange(1, 48))
        whole = tok.decode(ids)
        sent = "".join(_stream(stream_decoder(tok), ids, rng))
        assert whole.startswith(sent)
        # held back: at most the replacement of one unfinished character
        # (up to three bytes of a four-byte sequence)
        rest = whole[len(sent):]
        assert len(rest) <= 3 and set(rest) <= {"�"}


def test_a_split_character_leaves_whole():
    tok = ByteTokenizer(512)
    ids = tok.encode("a€😀é", add_bos=False)
    dec = stream_decoder(tok)
    pieces = [dec.feed([i]) for i in ids]
    assert "".join(pieces) == "a€😀é"
    # one piece a character, at the token that completes it
    assert [p for p in pieces if p] == ["a", "€", "😀", "é"]
    assert pieces[1] == pieces[2] == "" and pieces[3] == "€"


def test_an_invalid_byte_is_replaced_at_once_not_held():
    tok = ByteTokenizer(512)
    dec = stream_decoder(tok)
    # 0xFF can start nothing: its replacement leaves with it
    assert dec.feed([tok._SPECIALS + 0xFF]) == "�"
    # a lead byte waits; a byte that cannot continue it settles both
    assert dec.feed([tok._SPECIALS + 0xE2]) == ""
    assert dec.feed([tok._SPECIALS + 0x41]) == "�A"


def test_special_ids_add_no_text():
    tok = ByteTokenizer(512)
    dec = stream_decoder(tok)
    assert dec.feed([tok.BOS, tok.EOS, tok.PAD]) == ""
    assert dec.feed(tok.encode("ok", add_bos=False)) == "ok"


class _Pieces:
    """A tokenizer that offers ``decode`` alone, as an HF one does, with
    tokens that split characters (byte-level pieces) and a first token
    whose leading space ``decode`` drops (as sentencepiece does)."""

    def __init__(self):
        self._bytes = ByteTokenizer(512)

    def decode(self, ids):
        text = self._bytes.decode(ids)
        return text[1:] if text.startswith(" ") else text


@pytest.mark.parametrize("seed", range(4))
def test_a_tokenizer_with_decode_alone_streams_a_prefix(seed):
    tok = _Pieces()
    assert isinstance(stream_decoder(tok), _WindowStreamDecoder)
    rng = random.Random(seed)
    for _ in range(100):
        ids = _ids(rng, tok._bytes, rng.randrange(1, 40))
        whole = tok.decode(ids)
        sent = "".join(_stream(stream_decoder(tok), ids, rng))
        assert whole.startswith(sent)


def test_a_tokenizer_with_decode_alone_streams_plain_text_whole():
    tok = _Pieces()
    ids = tok._bytes.encode(" hello wörld €", add_bos=False)
    dec = stream_decoder(tok)
    pieces = [dec.feed([i]) for i in ids]
    assert "".join(pieces) == "hello wörld €" == tok.decode(ids)
    # the split characters came whole, at their last token
    assert "ö" in pieces and "€" in pieces
