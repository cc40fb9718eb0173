"""`correct` for a served LM whose reference is too slow for
`lm_served_tokens.py`'s sample of twice the grid's slots: the SAME
comparison (the served tokens' gap below the plain reference's best
logit, at every served position, given the prompt and the served tokens
before it; `served_gap_mean` and `served_gap_max`; tokens missing and
streams that differ over EVERY finished request, limit 0) over the
configuration's `correct.sample` requests, chosen the same way: those
live together when the most were (they hold distinct slots), the
longest, and seeded picks spread over the prompt buckets.

The reference of a state-space model runs a plain scan over every
position of a sequence and a plain loop over the held experts: the
configuration says what one request costs it (`correct.sample_why`).
"""

from __future__ import annotations

from typing import Any, Dict, List

from benchmark.harness import manifest as mf

_whole = mf.load_module("checks", "lm_served_tokens")


def check(run: Dict[str, Any], reference, seed: int, *,
          control: bool = False) -> List[Dict[str, Any]]:
    """`lm_served_tokens.check` over `correct.sample` requests. That
    check sizes its sample from the one key `max_slots` of the run's
    spec, and hands the spec on to the reference, whose model has no
    such key: so it is given the run with that key set to half the
    sample, and everything else as it was."""
    k = int(run["config"]["correct"]["sample"])
    if k < 2 or k % 2:
        raise ValueError(f"correct.sample {k}: a whole number of pairs")
    system = run["system"]
    return _whole.check(
        {**run, "system": {**system, "spec": {
            **system["spec"], "max_slots": k // 2}}},
        reference, seed, control=control)
