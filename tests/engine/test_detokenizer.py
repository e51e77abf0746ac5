"""IncrementalDetokenizer: the one-decode stream path against the window
path it stands in for (engine/detokenizer.py), on tokenizers made here
(no download): the benchmark's word-level tokenizer as
``benchmarks/run.py`` ``write_model_dir`` builds it, and a byte-level BPE
trained in memory whose multi-byte characters split across tokens."""

import pytest
from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers
from transformers import AutoTokenizer

from vllm_distributed_tpu.engine.detokenizer import IncrementalDetokenizer
from vllm_distributed_tpu.sampling_params import SamplingParams

WORD_VOCAB = 4096
# 2-, 3- and 4-byte characters between ASCII, so that byte-level tokens
# end inside a character.
BPE_TEXT = ("the café naïve über straße 中文"
            "字符 日本語 \U0001f600 ok \U0001f680"
            "\U0001f30d done €5 é中\U0001f600 end")


class OnlyDecode:
    """A tokenizer double with ``decode`` and nothing else: it shows no
    fast backend, so the window path takes it."""

    def __init__(self, tokenizer) -> None:
        self._tokenizer = tokenizer

    def decode(self, ids, skip_special_tokens=True):
        return self._tokenizer.decode(
            ids, skip_special_tokens=skip_special_tokens)


@pytest.fixture(scope="module")
def word_level(tmp_path_factory):
    from benchmarks import run
    work = run.WORK
    run.WORK = str(tmp_path_factory.mktemp("bench_work"))
    try:
        path = run.write_model_dir("words", {"vocab_size": WORD_VOCAB})
    finally:
        run.WORK = work
    return AutoTokenizer.from_pretrained(path, local_files_only=True)


@pytest.fixture(scope="module")
def byte_bpe():
    tok = Tokenizer(models.BPE())
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    tok.train_from_iterator(
        [BPE_TEXT, "the end is ok"], trainers.BpeTrainer(
            vocab_size=280, special_tokens=["<s>", "</s>"],
            initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
            show_progress=False))
    return tok


def _ids(name, word_level, byte_bpe):
    if name == "word_level":
        return word_level, [(i * 37 + 5) % WORD_VOCAB for i in range(40)]
    ids = byte_bpe.encode(BPE_TEXT).ids
    # A character's bytes really do split across tokens.
    assert any(byte_bpe.decode([t]).endswith("�") for t in ids)
    return byte_bpe, ids


def _pair(tokenizer, **params):
    params = SamplingParams(**params)
    stream = IncrementalDetokenizer(tokenizer, params)
    window = IncrementalDetokenizer(OnlyDecode(tokenizer), params)
    assert (stream.path, window.path) == ("stream", "window")
    return stream, window


def _feed(detok, ids, burst):
    """Updates of ``burst`` tokens; the deltas and whole texts seen."""
    deltas, texts = [], []
    for i in range(0, len(ids), burst):
        assert detok.update(ids[i:i + burst]) is None
        deltas.append(detok.new_text)
        texts.append(detok.output_text)
    return deltas, texts


@pytest.mark.parametrize("name", ["word_level", "byte_bpe"])
def test_stream_equals_window_token_by_token(name, word_level, byte_bpe):
    tokenizer, ids = _ids(name, word_level, byte_bpe)
    stream, window = _pair(tokenizer)
    got = _feed(stream, ids, 1)
    want = _feed(window, ids, 1)
    assert got == want
    assert "".join(got[0]) == stream.output_text
    assert stream.output_text == tokenizer.decode(ids)
    if name == "byte_bpe":
        assert "" in got[0], "a split character is held back"
        assert "�" not in stream.output_text
    else:
        assert all(got[0]), "one piece a token, separators kept"


@pytest.mark.parametrize("burst", [2, 3, 7])
@pytest.mark.parametrize("name", ["word_level", "byte_bpe"])
def test_stream_equals_window_in_bursts(name, burst, word_level, byte_bpe):
    """Several tokens an update (multi-step, speculation): the stream is
    stepped once a token inside the burst, so at a burst's end it stands
    where token-by-token feeding stands; the window path, decoding once
    a burst, may hold back more but ends on the same text."""
    tokenizer, ids = _ids(name, word_level, byte_bpe)
    stream, window = _pair(tokenizer)
    single, _ = _pair(tokenizer)
    deltas, texts = _feed(stream, ids, burst)
    _, single_texts = _feed(single, ids, 1)
    assert texts == single_texts[burst - 1::burst] + (
        [single_texts[-1]] if len(ids) % burst else [])
    assert "".join(deltas) == stream.output_text
    _feed(window, ids, burst)
    for detok in (stream, window):
        detok.flush()
    assert stream.output_text == window.output_text == tokenizer.decode(ids)


@pytest.mark.parametrize("stop,text,last_delta", [
    # The stop spans two emissions: " t42" went out before " t79" came.
    ("t42 t79", "t5 ", ""),
    # Inside one emission: the piece is cut, its head still goes out.
    ("79", "t5 t42 t", " t"),
    ("t5", "", ""),
])
def test_stop_strings_truncate_alike(word_level, stop, text, last_delta):
    ids = [5, 42, 79, 116]
    for detok in _pair(word_level, stop=[stop, "never"]):
        hit, deltas = None, []
        for token_id in ids:
            hit = detok.update([token_id])
            deltas.append(detok.new_text)
            if hit is not None:
                break
        assert hit == stop
        assert detok.output_text == text
        assert deltas[-1] == last_delta
        # What went out before the stop was seen stays out (the server
        # never takes text back): the deltas cover the text.
        assert "".join(deltas).startswith(text)


@pytest.mark.parametrize("kind", ["wrapper", "bare"])
@pytest.mark.parametrize("skip", [True, False])
def test_skip_special_tokens_both_ways(skip, kind):
    tok = Tokenizer(models.WordLevel(
        {"<eos>": 0, "<unk>": 1, "a": 2, "b": 3, "c": 4},
        unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    tok.add_special_tokens(["<eos>"])
    if kind == "wrapper":
        from transformers import PreTrainedTokenizerFast
        tok = PreTrainedTokenizerFast(tokenizer_object=tok,
                                      eos_token="<eos>")
    ids = [2, 0, 3, 0, 0, 4]
    stream, window = _pair(tok, skip_special_tokens=skip)
    assert _feed(stream, ids, 1) == _feed(window, ids, 1)
    assert stream.output_text == ("a b c" if skip
                                  else "a <eos> b <eos> <eos> c")


def test_flush_emits_a_held_back_tail(byte_bpe):
    """A generation cut inside a character: update() holds the bytes
    back, flush() at the finish emits what they decode to."""
    ids = byte_bpe.encode("ok \U0001f600").ids
    while not byte_bpe.decode(ids).endswith("�"):
        ids.pop()
    for detok in _pair(byte_bpe):
        _feed(detok, ids, 1)
        held = detok.output_text
        assert held in ("ok", "ok ") and "�" not in held
        detok.flush()
        assert detok.output_text == byte_bpe.decode(ids)
        assert detok.new_text == detok.output_text[len(held):] != ""
        detok.flush()  # nothing held: nothing more
        assert detok.output_text == byte_bpe.decode(ids)


@pytest.mark.parametrize("fails_at", [0, 3, 4])
def test_a_stream_that_refuses_a_token_hands_over_to_the_window(
        word_level, fails_at):
    """``DecodeStream.step`` raises where a decode does not extend what
    it gave ("invalid prefix"): the request goes on through the window
    algorithm from the offsets kept, mid-burst too, and loses nothing."""
    ids = [5, 42, 79, 116, 153, 190, 227]

    class Refusing:
        def __init__(self, stream):
            self.stream, self.calls = stream, 0

        def step(self, backend, token_id):
            self.calls += 1
            if self.calls > fails_at:
                raise Exception("Invalid prefix encountered")
            return self.stream.step(backend, token_id)

    detok = IncrementalDetokenizer(word_level, SamplingParams())
    detok._stream = Refusing(detok._stream)
    deltas, _ = _feed(detok, ids, 3)
    assert detok.path == "window"
    assert "".join(deltas) == detok.output_text == word_level.decode(ids)


def test_cleanup_of_tokenization_spaces_takes_the_window(word_level):
    """The wrapper cleans each decoded window up after its backend; a
    stream of pieces cannot, so such a tokenizer keeps the window."""
    import copy
    cleaning = copy.copy(word_level)
    cleaning.clean_up_tokenization_spaces = True
    params = SamplingParams()
    assert IncrementalDetokenizer(cleaning, params).path == "window"
    assert IncrementalDetokenizer(word_level, params).path == "stream"
    assert IncrementalDetokenizer(word_level.backend_tokenizer,
                                  params).path == "stream"


@pytest.mark.parametrize("double,path", [(False, "stream"),
                                         (True, "window")])
def test_counter_names_the_path(word_level, double, path):
    """vdt:frontend_detokenized_tokens_total{path}: a tokenizer double
    with only ``decode`` takes the window path, and the counter says
    so."""
    from tests.conftest import make_config
    from vllm_distributed_tpu.core.sched.scheduler import EngineCoreOutput
    from vllm_distributed_tpu.engine.output_processor import OutputProcessor
    from vllm_distributed_tpu.request import EngineCoreRequest
    tokenizer = OnlyDecode(word_level) if double else word_level
    proc = OutputProcessor(make_config(), tokenizer)
    proc.add_request(EngineCoreRequest(
        request_id="r", prompt_token_ids=[1, 2],
        sampling_params=SamplingParams(max_tokens=8)))
    out = None
    for step in ([7], [8, 9], [10]):
        out = proc.process_outputs([EngineCoreOutput("r", step)])
    assert out.request_outputs[0].outputs[0].text == "t7 t8 t9 t10"
    other = "window" if path == "stream" else "stream"
    assert proc.stats.detokenized_tokens == {path: 4, other: 0}
    text = proc.stats.render()
    assert (f'vdt:frontend_detokenized_tokens_total{{path="{path}"}} 4'
            in text)
    assert (f'vdt:frontend_detokenized_tokens_total{{path="{other}"}} 0'
            in text)
