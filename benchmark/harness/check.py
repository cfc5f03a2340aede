"""The comparisons that decide ``correct``.

Serving: after the window has closed, a sample of the requests it
finished — drawn from the seed, the longest always in it — is run
through the plain reference, once over each prompt with its served
tokens.  At every served position the reference's logit of the served
token is compared with the reference's best logit.  A bf16 engine picks
the reference's first choice or a near-tie; a wrong cache row, a wrong
position or a lower precision picks tokens the reference ranks well
below its best.  The numbers compared, each with its own limit from the
traffic file's ``check.limits``:

- ``served_gap_max``: the widest such gap over the sample;
- ``served_gap_mean``: the mean gap over the sample (steadier).

Valid for greedy tokens only, which is all this traffic sends.
"""

from __future__ import annotations

import time

import numpy as np


def pick_sample(finished, k: int, seed: int) -> list:
    """``k`` of the finished requests, drawn from the seed, the one
    with the longest sequence always among them."""
    if not finished:
        return []
    def size(r):
        return r.planned.prompt_len + len(r.tokens)
    longest = max(range(len(finished)), key=lambda i: size(finished[i]))
    rng = np.random.default_rng([int(seed), 4])
    rest = [i for i in rng.permutation(len(finished)) if i != longest]
    return [finished[i] for i in [longest] + rest[:max(k - 1, 0)]]


def compare(values: dict, limits: dict) -> dict:
    """Each number beside its limit; ``correct`` only if every number
    that has a limit is within it (a missing limit fails: a number
    nobody bounded proves nothing)."""
    rows, ok = [], True
    for name, value in values.items():
        limit = limits.get(name)
        within = (limit is not None and np.isfinite(value)
                  and value <= limit)
        ok &= bool(within)
        rows.append({"number": name, "value": float(value),
                     "limit": limit, "within": bool(within)})
    return {"correct": bool(ok and rows), "compared": rows}


def longest(spec: dict) -> int:
    """The longest length a traffic file's length spec can draw."""
    return int(spec["value"] if spec["dist"] == "fixed" else spec["max"])


def served_tokens(params, cfg_file: dict, finished, traffic: dict, seed: int,
                  log, compiles=None) -> dict:
    from benchmark.references import decoder

    spec = traffic.get("check", {})
    # One padded length for every request of every run of this traffic,
    # so the reference compiles once and is found in the cache after.
    rows_to = longest(traffic["output_len"])
    pad_to = -(-(longest(traffic["prompt_len"]) + rows_to)
               // decoder.PAD) * decoder.PAD
    n0 = len(compiles.events) if compiles is not None else 0
    sample = pick_sample(finished, int(spec.get("sample", 4)), seed)
    if not sample:
        log(phase="check", error="no finished request to compare")
        return {"correct": False, "compared": []}
    t0 = time.monotonic()
    gaps = []
    for r in sample:
        gaps.append(decoder.served_gaps(
            params, cfg_file, r.prompt, r.tokens, pad_to=pad_to,
            rows_to=rows_to).astype(np.float64))
    allg = np.concatenate(gaps)
    values = {"served_gap_max": float(allg.max()),
              "served_gap_mean": float(allg.mean())}
    out = compare(values, spec.get("limits", {}))
    log(phase="check", seconds=time.monotonic() - t0,
        requests=len(sample), served_tokens=int(allg.size), pad_to=pad_to,
        compiles=(len(compiles.events) - n0
                  if compiles is not None else None),
        longest=int(max(r.planned.prompt_len + len(r.tokens)
                        for r in sample)),
        not_first_choice=int((allg > 0).sum()), **out)
    return out
