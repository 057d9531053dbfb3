"""The one traffic generator: a mix file's parameters plus a seed give the
requests of a closed loop.

Every seed gets the same set of sizes: ``DECK`` prompt lengths and as many
answer lengths, evenly spaced over the mix's ranges, dealt again and again.
The seed draws the order of the prompt lengths and every token id. The order
of the answer lengths is the same for every seed: it decides on which step
each slot falls free, so when each prefill call comes, and with it the phase
in which the window closes (PERF.md, section 2, says what that does to
``tokens_per_s``). So a seed changes what is asked and never how much work
there is or when it is due. Token ids are uniform over the vocabulary, so
no two prompts share a prefix.
"""

from __future__ import annotations

import itertools

import numpy as np

DECK = 16


def _grid(lo: int, hi: int) -> np.ndarray:
    return np.round(np.linspace(lo, hi, DECK)).astype(np.int64)


def requests(mix: dict, seed: int, vocab: int):
    """Endless ``(prompt ids as a list, tokens to generate)``."""
    rng = np.random.default_rng(int(seed))
    prompts = rng.permutation(_grid(*mix["prompt_tokens"]))
    answers = np.random.default_rng(0).permutation(
        _grid(*mix["answer_tokens"]))
    for i in itertools.count():
        ids = rng.integers(0, vocab, int(prompts[i % DECK]))
        yield ids.astype(np.int64).tolist(), int(answers[i % DECK])
