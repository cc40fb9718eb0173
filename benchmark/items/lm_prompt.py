"""Items of LM traffic: token prompts with an output budget each, as
prompt files in the program's input format (whitespace-separated token
ids, a leading `# max_new_tokens: N` directive).

Lengths and budgets are the quantiles of the traffic file's
distributions in the mix's fixed order from the seed's point
(`loadgen.int_draws`); token ids are seeded uniform draws over the
vocabulary. No two prompts share a prefix
except by chance, and no prompt is sent twice.
"""

from __future__ import annotations

import random
from typing import Any, Dict, List

import numpy as np

from benchmark.harness.loadgen import Request, int_draws


def make(items: Dict[str, Any], n: int, order: random.Random, start: int,
         seed: int, config: Dict[str, Any]) -> List[Request]:
    """`n` prompts whose sizes are the mix's fixed sequence from point
    `start`, and whose token ids come from `seed`."""
    vocab = int(config["lm_spec"]["vocab_size"])
    max_len = int(config["lm_spec"]["max_len"])
    lengths = int_draws(items["prompt_tokens"], n, order, start)
    budgets = int_draws(items["output_tokens"], n, order, start)
    tok = np.random.RandomState(seed % (2 ** 32))
    out = []
    for i, (length, budget) in enumerate(zip(lengths, budgets)):
        if length + budget > max_len:
            raise ValueError(
                f"prompt of {length} + budget {budget} exceeds max_len "
                f"{max_len}: choose traffic on which no operation fails")
        out.append(Request(
            index=i, name=f"p{i:05d}.tokens.txt",
            size={"prompt_tokens": length, "output_tokens": budget},
            payload=tok.randint(0, vocab, length).astype(np.int32),
        ))
    return out


def write(req: Request, path: str) -> None:
    with open(path, "w") as f:
        f.write(f"# max_new_tokens: {req.size['output_tokens']}\n")
        f.write(" ".join(str(int(t)) for t in req.payload))


def parse_streamed(chunks: List[str]) -> List[int]:
    return [int(t) for t in "".join(chunks).split()]


def count_items(chunk: str) -> int:
    return len(chunk.split())


def result_items(result: Any) -> List[int]:
    """The output tokens of a terminal's or a job output's result."""
    return [int(t) for t in result["tokens"]]
