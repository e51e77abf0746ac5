"""Incremental detokenization.

Reference: vllm/v1/engine/detokenizer.py (per-request incremental decode
with stable-prefix emission and stop-string scanning back-off; its
``FastIncrementalDetokenizer`` over ``tokenizers.decoders.DecodeStream``).

The incremental algorithm keeps a small suffix window of token ids: a
token's text is only emitted once decoding a longer suffix no longer
changes it (byte-level BPE can merge with following tokens; multi-byte
unicode may be split across tokens). Two carriers of that one algorithm:

- ``stream``: where the tokenizer has a fast backend, a ``DecodeStream``
  is stepped once a token and holds the window itself, in Rust;
- ``window``: everywhere else (a slow Python tokenizer, a test double with
  only ``decode``), two ``tokenizer.decode`` calls an update.

What selects is what the tokenizer object shows (``_fast_backend``).
"""

from typing import Optional

from tokenizers import Tokenizer
from tokenizers.decoders import DecodeStream

from vllm_distributed_tpu.sampling_params import SamplingParams


def _fast_backend(tokenizer) -> Optional[Tokenizer]:
    """The ``tokenizers.Tokenizer`` behind ``tokenizer`` where decoding
    through it gives the text ``tokenizer.decode`` gives: a bare
    ``Tokenizer``, or a ``PreTrainedTokenizerFast`` that does not clean
    up tokenization spaces (the wrapper applies that to each decoded
    window after the backend; a stream of pieces cannot)."""
    if isinstance(tokenizer, Tokenizer):
        return tokenizer
    backend = getattr(tokenizer, "backend_tokenizer", None)
    if isinstance(backend, Tokenizer) and not getattr(
            tokenizer, "clean_up_tokenization_spaces", False):
        return backend
    return None


class IncrementalDetokenizer:
    """Per-request detokenizer state."""

    def __init__(self, tokenizer, params: SamplingParams) -> None:
        self.tokenizer = tokenizer
        self.skip_special_tokens = params.skip_special_tokens
        self.stop_strings = params.stop or []
        # Longest stop string bounds how much emitted text we must retain
        # to detect a stop spanning an emission boundary.
        self._max_stop_len = max((len(s) for s in self.stop_strings),
                                 default=0)
        self._tail = ""
        self.token_ids: list[int] = []
        # Emitted text, piece by piece: joined where someone reads the
        # whole (``output_text``), never per token otherwise.
        self._pieces: list[str] = []
        self._len = 0
        # What the last update() (and a flush() after it) emitted: the
        # delta output kind's read.
        self.new_text = ""
        # Prefix/read-offset incremental decode (reference:
        # detokenize_incrementally): text is emitted as the difference
        # between decoding [prefix:] and [prefix:read] — decoding tail
        # segments independently would drop the separators a tokenizer
        # inserts BETWEEN tokens (spaces in word-level/SentencePiece).
        # The stream path keeps the same two offsets beside its
        # DecodeStream (which holds them too), for flush().
        self._prefix_offset = 0
        self._read_offset = 0
        self._backend = _fast_backend(tokenizer)
        self._stream = (DecodeStream(
            skip_special_tokens=self.skip_special_tokens)
                        if self._backend is not None else None)

    @property
    def path(self) -> str:
        """Which carrier decodes this request's next token
        (vdt:frontend_detokenized_tokens_total's label)."""
        return "stream" if self._stream is not None else "window"

    @property
    def output_text(self) -> str:
        if len(self._pieces) != 1:
            self._pieces = ["".join(self._pieces)]
        return self._pieces[0]

    def update(self, new_token_ids: list[int]) -> Optional[str]:
        """Append tokens; returns the stop string hit, if any."""
        self.new_text = ""
        if self.tokenizer is None:
            return None
        self.token_ids.extend(new_token_ids)
        if self._stream is not None:
            new_text = self._step_stream(new_token_ids)
        else:
            new_text = self._decode_window(hold_back=True)
        if not new_text:
            return None
        before = self._len
        self._emit(new_text)
        if not self.stop_strings:
            return None
        # Scan only the recently-produced region.
        window = self._tail + new_text
        window_start = self._len - len(window)
        for stop in self.stop_strings:
            idx = window.find(stop)
            if idx != -1:
                # Truncate at the stop string (excluded from output).
                text = self.output_text[:window_start + idx]
                self._pieces = [text]
                self._len = len(text)
                self.new_text = text[before:]
                return stop
        self._tail = window[-self._max_stop_len:]
        return None

    def flush(self) -> None:
        """Emit any held-back tail at end of generation (text withheld by
        update() because it ended in a split multi-byte sequence)."""
        if self.tokenizer is None or self._read_offset >= len(
                self.token_ids):
            return
        new_text = self._decode_window(hold_back=False)
        if new_text:
            self._emit(new_text)
        self._prefix_offset = self._read_offset = len(self.token_ids)

    def _emit(self, new_text: str) -> None:
        self._pieces.append(new_text)
        self._len += len(new_text)
        self.new_text += new_text

    def _decode_window(self, hold_back: bool) -> str:
        """The window algorithm: what decoding [prefix:] adds to decoding
        [prefix:read], and the offsets moved on behind it."""
        prefix_text = self.tokenizer.decode(
            self.token_ids[self._prefix_offset:self._read_offset],
            skip_special_tokens=self.skip_special_tokens)
        full_text = self.tokenizer.decode(
            self.token_ids[self._prefix_offset:],
            skip_special_tokens=self.skip_special_tokens)
        # A window ending in the unicode replacement char may be a split
        # multi-byte sequence: hold it back until completed.
        if len(full_text) <= len(prefix_text) or (
                hold_back and full_text.endswith("�")):
            return ""
        self._prefix_offset = self._read_offset
        self._read_offset = len(self.token_ids)
        return full_text[len(prefix_text):]

    def _step_stream(self, new_token_ids: list[int]) -> str:
        """One ``DecodeStream.step`` a token: the same window, the same
        hold-back, each token decoded once and in Rust."""
        step, backend = self._stream.step, self._backend
        at = len(self.token_ids) - len(new_token_ids)
        pieces = []
        try:
            for token_id in new_token_ids:
                at += 1
                piece = step(backend, token_id)
                if piece is not None:
                    pieces.append(piece)
                    self._prefix_offset = self._read_offset
                    self._read_offset = at
        except Exception:  # noqa: BLE001 - tokenizers raises a bare one
            # The stream refuses a decode that does not extend what it
            # gave ("invalid prefix"); the window algorithm slices by
            # length and takes the request on from the offsets kept.
            self._stream = None
            pieces.append(self._decode_window(hold_back=True))
        return "".join(pieces)
