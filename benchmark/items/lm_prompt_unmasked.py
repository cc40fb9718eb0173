"""`lm_prompt` items for a block-diffusion LM: the same prompt files
(whitespace-separated token ids, a leading `# max_new_tokens: N`), with
ids drawn from the whole vocabulary LESS the mask id, which marks the
positions still to generate and may not stand in a prompt; and results
that carry, beside the tokens, the denoising step that fixed each
(`fixed_at`) and what the last block held past the budget
(`beyond_budget`), which `checks/lm_block_diffusion.py` rebuilds every
block from.
"""

from __future__ import annotations

import random
from typing import Any, Dict, List

import numpy as np

from benchmark.harness import manifest as mf
from benchmark.harness.loadgen import Request, int_draws

_lm_prompt = mf.load_module("items", "lm_prompt")


class Served(list):
    """A request's output tokens (a plain list to everything that counts
    or compares them), with the record of how they were generated."""

    fixed_at: List[int]
    beyond_tokens: List[int]
    beyond_fixed_at: List[int]


def make(items: Dict[str, Any], n: int, order: random.Random, start: int,
         seed: int, config: Dict[str, Any]) -> List[Request]:
    """`n` prompts whose sizes are the mix's fixed sequence from point
    `start`, and whose token ids come from `seed`."""
    spec = config["lm_spec"]
    vocab, max_len = int(spec["vocab_size"]), int(spec["max_len"])
    mask_id = int(spec["mask_token_id"])
    lengths = int_draws(items["prompt_tokens"], n, order, start)
    budgets = int_draws(items["output_tokens"], n, order, start)
    tok = np.random.RandomState(seed % (2 ** 32))
    out = []
    for i, (length, budget) in enumerate(zip(lengths, budgets)):
        if length + budget > max_len:
            raise ValueError(
                f"prompt of {length} + budget {budget} exceeds max_len "
                f"{max_len}: choose traffic on which no operation fails")
        ids = tok.randint(0, vocab - 1, length).astype(np.int32)
        ids += ids >= mask_id  # every id but the mask's, uniformly
        out.append(Request(
            index=i, name=f"p{i:05d}.tokens.txt",
            size={"prompt_tokens": length, "output_tokens": budget},
            payload=ids,
        ))
    return out


# the prompt file's format and the streamed chunks' are `lm_prompt`'s
write = _lm_prompt.write
parse_streamed = _lm_prompt.parse_streamed
count_items = _lm_prompt.count_items


def result_items(result: Any) -> List[int]:
    """The output tokens of a terminal's or a job output's result."""
    out = Served(int(t) for t in result["tokens"])
    out.fixed_at = [int(f) for f in result.get("fixed_at", ())]
    beyond = result.get("beyond_budget") or {}
    out.beyond_tokens = [int(t) for t in beyond.get("tokens", ())]
    out.beyond_fixed_at = [int(f) for f in beyond.get("fixed_at", ())]
    return out
