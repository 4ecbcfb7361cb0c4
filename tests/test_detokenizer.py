"""The streaming detokeniser keeps its place (PR 47).

`StreamDetokenizer` (models/llama/generator.py) is handed a stream's new
ids only and decodes the few ids since the text it last sent, where
`incremental_decode` decoded the whole output at every token. What is
pinned here, for every kind of tokenizer a user serves (the byte
fallback, a byte-level BPE, a Metaspace BPE with byte fallback, the
benchmark's word-level form; all built here, nothing is downloaded):

  * the concatenated deltas equal `tokenizer.decode` of the same ids,
    over encoded text and over seeded random ids, flushed or not;
  * no delta ends in U+FFFD before the flush, and a held token's text
    arrives in a later delta;
  * the ids handed to `decode` a token are a small constant however
    long the output is (the deterministic form of the speed claim).
"""

import json
import os
import random
import sys

import pytest

from cake_tpu.models.llama.generator import (
    ByteTokenizer, StreamDetokenizer, encode_text,
)

FFFD = "�"
KINDS = ["byte", "byte_level_bpe", "metaspace_bpe", "word_level"]
# multi-byte characters, and an emoji that every byte-capable kind
# splits across tokens
TEXTS = {
    "ascii": "Hello world, this is a test of streaming text.",
    "accents_cjk": "Grüße aus München — naïve café, 東京は大きい都市です。",
    "emoji": "so: 😀🎉👍🏽 and a family 👨‍👩‍👧‍👦 done",
    "spaces": "  double  spaces and\nnewlines\t tabs  ",
}
SENTENCES = list(TEXTS.values()) + [
    "the quick brown fox jumps over the lazy dog " * 3]


def _byte_level_bpe():
    from tokenizers import (Tokenizer, decoders, models, pre_tokenizers,
                            trainers)
    tok = Tokenizer(models.BPE())
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    tok.train_from_iterator(SENTENCES, trainers.BpeTrainer(
        vocab_size=330, special_tokens=["<eos>"], show_progress=False,
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet()))
    return tok


def _metaspace_bpe():
    """The Llama-2 / Mistral layout: a space is U+2581, the first one of
    a sequence is stripped, a character outside the vocabulary is its
    UTF-8 bytes as `<0xNN>` tokens (trained on ASCII only, so every
    multi-byte character here is)."""
    from tokenizers import (Tokenizer, decoders, models, pre_tokenizers,
                            trainers)
    pre = pre_tokenizers.Metaspace(replacement="▁", prepend_scheme="first")
    tok = Tokenizer(models.BPE(unk_token="<unk>"))
    tok.pre_tokenizer = pre
    tok.train_from_iterator(
        [TEXTS["ascii"], SENTENCES[-1]], trainers.BpeTrainer(
            vocab_size=140, show_progress=False,
            special_tokens=["<unk>", "<s>", "</s>"]))
    # the byte tokens are plain vocabulary (a special token is skipped
    # by decode), so the trained model is rebuilt with them added
    model = json.loads(tok.to_str())["model"]
    vocab = dict(model["vocab"])
    for i in range(256):
        vocab[f"<0x{i:02X}>"] = len(vocab)
    tok = Tokenizer(models.BPE(
        vocab=vocab, merges=[tuple(m) for m in model["merges"]],
        unk_token="<unk>", byte_fallback=True))
    tok.pre_tokenizer = pre
    tok.decoder = decoders.Sequence(
        [decoders.Replace("▁", " "), decoders.ByteFallback(),
         decoders.Fuse(), decoders.Strip(" ", 1, 0)])
    return tok


def _word_level(tmp):
    """The benchmark's tokenizer (benchmarks/harness/server.py) at a
    small vocabulary: id i is the word `w<i>`."""
    from tokenizers import Tokenizer
    bench = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    sys.path.insert(0, bench)
    try:
        from harness.server import write_tokenizer
    finally:
        sys.path.remove(bench)
    write_tokenizer(str(tmp), 300)
    with open(os.path.join(tmp, "tokenizer.json")) as f:
        assert json.load(f)["model"]["type"] == "WordLevel"
    return Tokenizer.from_file(os.path.join(tmp, "tokenizer.json"))


@pytest.fixture(scope="module")
def tokenizers_by_kind(tmp_path_factory):
    return {"byte": ByteTokenizer(300),
            "byte_level_bpe": _byte_level_bpe(),
            "metaspace_bpe": _metaspace_bpe(),
            "word_level": _word_level(tmp_path_factory.mktemp("word"))}


@pytest.fixture(params=KINDS)
def tok(request, tokenizers_by_kind):
    return tokenizers_by_kind[request.param]


def vocab_size(tok):
    return getattr(tok, "vocab_size", None) or tok.get_vocab_size()


def text_ids(tok, text):
    if not isinstance(tok, ByteTokenizer) and tok.decoder is None:
        # the word-level form knows no real word: a sentence of its own
        r = random.Random(len(text))
        text = " ".join(f"w{r.randrange(1, 300)}" for _ in text.split())
    return encode_text(tok, text)


def random_ids(tok, seed, n=None):
    """Ids no text encodes to: stray continuation bytes, specials that
    decode to nothing, a word beside half a character. Where the
    vocabulary has `<0xNN>` byte tokens they come as the bytes of whole
    characters only: the `ByteFallback` decoder turns EVERY byte of a
    run that is not UTF-8 into U+FFFD, the ones before the stray byte
    too, so a later token changes text that was complete and sent, and
    no stream of deltas (this one, or the whole-output form before it:
    both miss the same 1,802 of 2,000 streams of 30 uniform ids) can
    equal the whole decode. No encoder emits such a run."""
    r = random.Random(seed)
    n = n or r.randrange(1, 120)
    if isinstance(tok, ByteTokenizer) or tok.token_to_id("<0x00>") is None:
        return [r.randrange(vocab_size(tok)) for _ in range(n)]
    byte_id = [tok.token_to_id(f"<0x{b:02X}>") for b in range(256)]
    plain = sorted(set(range(vocab_size(tok))) - set(byte_id))
    ids = []
    while len(ids) < n:
        if r.random() < 0.5:
            ids.append(r.choice(plain))
        else:
            ids += [byte_id[b] for b in r.choice("éß—東😀ж€").encode()]
    return ids


def stream(tok, ids):
    """A delta a token, as the engine and the generators feed it, and
    the flush apart."""
    det = StreamDetokenizer(tok)
    return [det.add((t,)) for t in ids], det.add(final=True), det


# -- the deltas are the whole decode --------------------------------------------


@pytest.mark.parametrize("text", list(TEXTS))
def test_encoded_text_streams_to_its_whole_decode(tok, text):
    ids = text_ids(tok, TEXTS[text])
    deltas, tail, _ = stream(tok, ids)
    assert "".join(deltas) + tail == tok.decode(ids)
    # the text ends on a whole character: nothing was left to flush
    assert tail == ""
    assert not any(d.endswith(FFFD) for d in deltas)


@pytest.mark.parametrize("seed", range(6))
def test_random_ids_stream_to_their_whole_decode(tok, seed):
    for k in range(40):
        ids = random_ids(tok, 1000 * seed + k)
        deltas, tail, _ = stream(tok, ids)
        whole = tok.decode(ids)
        assert "".join(deltas) + tail == whole, ids
        # (b) what is not final never ends in a replacement character
        assert not any(d.endswith(FFFD) for d in deltas), ids
        # without the flush the stream is the whole decode short of an
        # incomplete tail, and the flush is that tail
        assert whole.startswith("".join(deltas))


@pytest.mark.parametrize("kind", KINDS[:3])
def test_incomplete_tail_waits_for_the_flush(tokenizers_by_kind, kind):
    """A stream cut inside a character: nothing of the character is
    sent before `final`, and the flush sends what the buffered decode
    of the same ids ends with."""
    tok = tokenizers_by_kind[kind]
    ids = encode_text(tok, "ok 😀")
    assert len(encode_text(tok, "😀")) > 1   # split across tokens
    cut = ids[:-1]
    deltas, tail, _ = stream(tok, cut)
    whole = tok.decode(cut)
    assert whole.endswith(FFFD)
    assert "".join(deltas) == "ok " and tail == whole[len("ok "):]
    # a second flush has nothing left
    det = StreamDetokenizer(tok)
    det.add(cut, final=True)
    assert det.add(final=True) == ""


@pytest.mark.parametrize("kind", KINDS[:3])
def test_held_token_arrives_with_the_token_that_completes_it(
        tokenizers_by_kind, kind):
    tok = tokenizers_by_kind[kind]
    lead, emoji = encode_text(tok, "a"), encode_text(tok, "a😀")
    emoji = emoji[len(lead):]
    assert len(emoji) > 1
    deltas, tail, _ = stream(tok, lead + emoji + lead)
    # every token of the character but its last is held, the last one
    # carries the whole character, and the next token its own text
    again = tok.decode(lead + emoji + lead)[len("a😀"):]
    assert again.strip() == "a"
    assert deltas[len(lead):] == [""] * (len(emoji) - 1) + ["😀", again]
    assert tail == ""


def test_every_token_with_text_is_a_delta_of_its_own(tok):
    """Whole characters a token: no token waits for the next one."""
    ids = text_ids(tok, TEXTS["ascii"])
    deltas, _, _ = stream(tok, ids)
    assert all(deltas) and len(deltas) == len(ids)


def test_first_token_of_a_sequence_is_treated_once(tokenizers_by_kind):
    """Metaspace strips the space before a sequence's first word: the
    window's first id is never the stream's first, and no delta after
    the first loses its space."""
    tok = tokenizers_by_kind["metaspace_bpe"]
    ids = encode_text(tok, "hello world this is a test")
    deltas, _, _ = stream(tok, ids)
    assert "".join(deltas) == "hello world this is a test"
    assert tok.decode(ids[-1:]) == "test" and deltas[-1].endswith("test")
    assert not deltas[0].startswith(" ")
    words = [d for d in deltas if d.startswith(" ")]
    assert len(words) == 5


def test_several_ids_at_once_are_their_deltas_joined(tok):
    """A stream attached to a request under way hands over what it
    missed in one call."""
    ids = text_ids(tok, TEXTS["accents_cjk"]) + random_ids(tok, 3, 40)
    deltas, tail, _ = stream(tok, ids)
    det = StreamDetokenizer(tok)
    got = det.add(ids[:7]) + det.add(ids[7:30]) + det.add(ids[30:])
    assert got == "".join(deltas)
    assert det.add(final=True) == tail
    assert StreamDetokenizer(tok).add(ids, final=True) == tok.decode(ids)


def test_nothing_new_is_nothing_decoded(tok):
    det = StreamDetokenizer(tok)
    assert det.add() == "" and det.add(final=True) == ""
    assert det.decoded_ids == 0


# -- and cost the same at token 1,000 as at token 1 -------------------------------


class Counting:
    """A tokenizer whose `decode` counts the ids it is handed."""

    def __init__(self, tok):
        self.tok, self.calls, self.ids = tok, 0, 0

    def decode(self, ids):
        self.calls += 1
        self.ids += len(ids)
        return self.tok.decode(ids)


def test_ids_decoded_a_token_do_not_grow_with_the_output(tok):
    """1,024 tokens of whole characters: the window is the last delta's
    ids and the new one, decoded twice. The whole-output form handed
    `decode` 1 + 2 + ... + 1,024 = 524,800 ids for the same stream."""
    ids = text_ids(tok, " ".join([TEXTS["ascii"]] * 120))[:1024]
    assert len(ids) == 1024
    counting = Counting(tok)
    det = StreamDetokenizer(counting)
    first = [det.add((t,)) for t in ids[:512]]
    at_512 = counting.ids
    rest = [det.add((t,)) for t in ids[512:]]
    assert "".join(first + rest) == tok.decode(ids)
    assert counting.ids <= 4 * 1024
    # the second half costs what the first did
    assert counting.ids - at_512 <= at_512 + 4
    assert counting.calls <= 2 * 1024
    # the count the step records carry is the count of the wrapper
    assert det.decoded_ids == counting.ids


def test_a_held_token_widens_the_window_by_itself_only(tokenizers_by_kind):
    """A character of four byte tokens: the window grows by one id a
    held token and closes again with the character."""
    counting = Counting(tokenizers_by_kind["byte"])
    det = StreamDetokenizer(counting)
    seen = []
    for t in encode_text(counting.tok, "ab😀cd"):
        before = counting.ids
        det.add((t,))
        seen.append(counting.ids - before)
    # a: itself. b: a, then a b. the emoji: b, then b and one to four
    # of its bytes (the prefix is not decoded again while a token is
    # held). c: the emoji, then the emoji and c. d: c, then c d.
    assert seen == [1, 1 + 2, 1 + 2, 3, 4, 5, 4 + 5, 1 + 2]
