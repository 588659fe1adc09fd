"""Tokenizer wrapper with incremental (streaming) detokenization.

Capability parity with reference lib/llm/src/tokenizers.rs: Encoder/Decoder
traits over HF ``tokenizers`` (tokenizers.rs:33-300), a ``DecodeStream`` that
emits UTF-8-safe text deltas token by token (tokenizers.rs:214), and a
``Sequence`` accumulating ids+text. Incremental decode keeps prefix/read
offsets so multi-token unicode graphemes and sentencepiece prefix-space
handling produce exact concatenation-equal output.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Sequence as Seq

from tokenizers import Tokenizer as HFTokenizer


class Tokenizer:
    """Thread-safe wrapper over a HF tokenizers.Tokenizer."""

    def __init__(self, hf: HFTokenizer):
        self._hf = hf
        self._lock = threading.Lock()
        # Explicit EOS ids (e.g. from GGUF metadata) override the
        # name-convention discovery in eos_token_ids().
        self.eos_override: list[int] | None = None

    @classmethod
    def from_file(cls, path: str) -> "Tokenizer":
        if path.endswith(".gguf"):
            from dynamo_tpu.llm.gguf import tokenizer_from_gguf
            return tokenizer_from_gguf(path)
        return cls(HFTokenizer.from_file(path))

    @classmethod
    def from_bytes(cls, blob: bytes) -> "Tokenizer":
        return cls(HFTokenizer.from_str(blob.decode("utf-8")))

    @classmethod
    def from_pretrained_dir(cls, model_dir: str) -> "Tokenizer":
        """Load from a local model directory containing tokenizer.json."""
        path = os.path.join(model_dir, "tokenizer.json")
        if not os.path.exists(path):
            raise FileNotFoundError(f"no tokenizer.json under {model_dir}")
        return cls.from_file(path)

    def to_bytes(self) -> bytes:
        return self._hf.to_str().encode("utf-8")

    @property
    def vocab_size(self) -> int:
        return self._hf.get_vocab_size()

    def encode(self, text: str, add_special_tokens: bool = False) -> list[int]:
        with self._lock:
            return self._hf.encode(text, add_special_tokens=add_special_tokens).ids

    def decode(self, ids: Seq[int], skip_special_tokens: bool = True) -> str:
        with self._lock:
            return self._hf.decode(list(ids), skip_special_tokens=skip_special_tokens)

    def token_to_id(self, token: str) -> int | None:
        return self._hf.token_to_id(token)

    def eos_token_ids(self) -> list[int]:
        """Best-effort EOS discovery from common conventions."""
        if self.eos_override is not None:
            return list(self.eos_override)
        ids = []
        for tok in ("</s>", "<|endoftext|>", "<|eot_id|>", "<|end_of_text|>",
                    "<|im_end|>", "<eos>"):
            tid = self._hf.token_to_id(tok)
            if tid is not None:
                ids.append(tid)
        return ids


class DecodeStream:
    """Incremental detokenizer (reference tokenizers.rs DecodeStream :214).

    ``step(token_id)`` returns the new text produced by appending the token, or
    None when the bytes so far don't yet form valid complete text (e.g. half of
    a multi-byte grapheme). The offsets approach matches HF's streaming decode:
    decode(all_ids[prefix:]) vs decode(all_ids[prefix:read]) and emit the
    suffix only when it's complete and doesn't end in a replacement char.
    """

    def __init__(self, tokenizer: Tokenizer, skip_special_tokens: bool = True):
        self._tok = tokenizer
        self._skip = skip_special_tokens
        self.ids: list[int] = []
        self._prefix_offset = 0
        self._read_offset = 0

    def step(self, token_id: int) -> str | None:
        self.ids.append(token_id)
        prefix_text = self._tok.decode(self.ids[self._prefix_offset:self._read_offset],
                                       self._skip)
        new_text = self._tok.decode(self.ids[self._prefix_offset:], self._skip)
        if new_text.endswith("�"):
            # Incomplete UTF-8 sequence: wait for more tokens.
            return None
        if len(new_text) <= len(prefix_text):
            return None
        delta = new_text[len(prefix_text):]
        self._prefix_offset = self._read_offset
        self._read_offset = len(self.ids)
        return delta


class StopSequenceChecker:
    """Streaming stop-string detection over appended text deltas.

    Holds back a tail of ``max_stop_len - 1`` chars so a stop string split
    across deltas is still caught (reference backend.rs stop-sequence
    handling). ``append`` returns (emit_text, matched) where emit_text is the
    safe-to-emit portion.
    """

    def __init__(self, stops: list[str]):
        self.stops = [s for s in stops if s]
        self._held = ""
        self._max = max((len(s) for s in self.stops), default=0)

    def append(self, delta: str) -> tuple[str, bool]:
        if not self.stops:
            return delta, False
        buf = self._held + delta
        # Earliest match across all stop strings wins, so no text past an
        # earlier stop leaks when a later-listed stop also matches.
        best = -1
        for stop in self.stops:
            idx = buf.find(stop)
            if idx != -1 and (best == -1 or idx < best):
                best = idx
        if best != -1:
            self._held = ""
            return buf[:best], True
        keep = min(self._max - 1, len(buf))
        # Only hold back a tail that is a prefix of some stop string.
        hold = 0
        for k in range(keep, 0, -1):
            tail = buf[-k:]
            if any(s.startswith(tail) for s in self.stops):
                hold = k
                break
        self._held = buf[len(buf) - hold:] if hold else ""
        emit = buf[:len(buf) - hold] if hold else buf
        return emit, False

    def flush(self) -> str:
        held, self._held = self._held, ""
        return held


def make_test_tokenizer(vocab_texts: list[str] | None = None) -> Tokenizer:
    """Build a small self-contained byte-level BPE tokenizer (no hub access).
    Used by tests and the mocker; NOT for real models."""
    from tokenizers import models, pre_tokenizers, decoders, trainers

    hf = HFTokenizer(models.BPE(unk_token=None))
    hf.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    hf.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=512, special_tokens=["<|endoftext|>", "<|im_end|>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
        show_progress=False)  # the bar writes blank lines to fd 1
    corpus = vocab_texts or [
        "hello world this is a test of the tpu native serving framework",
        "the quick brown fox jumps over the lazy dog 0123456789",
        "def main(): return [i for i in range(10)]",
    ]
    hf.train_from_iterator(corpus, trainer)
    return Tokenizer(hf)
