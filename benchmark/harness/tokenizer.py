"""Token ids on the wire as decimal text: ``"17 905 3"`` <-> ``[17, 905, 3]``.

The serving path tokenizes text; the benchmark sizes its traffic in
tokens over the whole vocabulary, so it injects this one-to-one
tokenizer through ``create_app(tokenizer=...)``.
"""

from __future__ import annotations

from typing import List


class IntTokenizer:
    eos_token_id = None

    def __init__(self, vocab_size: int):
        self.vocab_size = vocab_size

    def encode(self, text: str) -> List[int]:
        ids = [int(t) for t in text.split()]
        if any(not 0 <= i < self.vocab_size for i in ids):
            raise ValueError("token id outside the vocabulary")
        return ids

    def decode(self, ids, skip_special_tokens: bool = True) -> str:
        return " ".join(str(int(i)) for i in ids)
