"""The benchmark's own tokenizer: one word per id of the model's vocabulary.

The launcher's fallback tokenizer has 512 entries; a model with 152,064
logits then emits ids that decode to nothing and the detokenizer idles.
This WordLevel ``tokenizer.json`` has an entry for every id (``w<id>``;
the first ids are the words of the chat template), splits on whitespace,
and is handed to the launcher as ``--tokenizer``. One word is one token, so
a prompt has exactly the length drawn, every sampled id decodes to a word,
and the client can count the tokens of a streamed chunk by counting words.
"""

from __future__ import annotations

import os

#: Ids 0..RESERVED-1 are not drawn for prompts.
RESERVED = 16
TEMPLATE_WORDS = ("<unk>", "<|im_start|>", "<|im_end|>", "user",
                  "assistant", "system")


def word(token_id: int) -> str:
    return (TEMPLATE_WORDS[token_id] if token_id < len(TEMPLATE_WORDS)
            else f"w{token_id}")


def write_tokenizer(path: str, vocab_size: int) -> str:
    """Write the tokenizer file (atomically) and return its path."""
    from tokenizers import AddedToken, Tokenizer, models, pre_tokenizers
    vocab = {word(i): i for i in range(vocab_size)}
    tok = Tokenizer(models.WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    # The chat template glues its markers to their neighbours
    # ("<|im_start|>user", "...<|im_end|>"). Added tokens are cut out of the
    # text before the whitespace split; they are ordinary words otherwise
    # (never skipped when decoding), so a sampled marker id is still a word.
    tok.add_tokens([AddedToken(w, special=False, normalized=False)
                    for w in ("<|im_start|>", "<|im_end|>")])
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    tok.save(tmp)
    os.replace(tmp, path)
    return path


def text_of(ids) -> str:
    return " ".join(word(i) for i in ids)


def template_overhead(tokenizer_path: str, chat_template: str) -> int:
    """Tokens the chat template adds around a one-message prompt, counted
    with the tokenizer itself."""
    import jinja2
    from tokenizers import Tokenizer
    tok = Tokenizer.from_file(tokenizer_path)
    text = jinja2.Template(chat_template).render(
        messages=[{"role": "user", "content": word(RESERVED)}],
        add_generation_prompt=True)
    return len(tok.encode(text, add_special_tokens=False).ids) - 1
