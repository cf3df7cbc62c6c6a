"""The one traffic generator: reads a mix's data file, gives the requests.
Stdlib only.

A serving mix (``kind: open_loop``) names distributions for prompt and output
lengths and an arrival rate. Every seed gets the same set of lengths and the
same set of gaps between arrivals (the distributions' quantiles at evenly
spaced probabilities), in the order drawn from the mix's ``order_seed``: every
seed sends the same schedule, and differs in weights and token ids."""

from __future__ import annotations

import math
import random
import statistics


def _quantiles(n):
    return [(i + 0.5) / n for i in range(n)]


def _lengths(spec, n):
    """``n`` token counts at the lognormal's evenly spaced quantiles, clipped to the mix's range."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    inv = statistics.NormalDist().inv_cdf
    vals = [spec["median"] * math.exp(spec["sigma"] * inv(q)) for q in _quantiles(n)]
    return [min(max(int(round(v)), spec["min"]), spec["max"]) for v in vals]


def _phase(mix, rng, n, start, length, phase, first_index):
    prompts = _lengths(mix["prompt_tokens"], n)
    outputs = _lengths(mix["output_tokens"], n)
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    gaps = [-math.log(1.0 - q) for q in _quantiles(n)]  # the exponential's quantiles
    for seq in (prompts, outputs, gaps):
        rng.shuffle(seq)
    scale = length * n / (n + 0.5) / sum(gaps)  # the last arrival falls inside the phase
    due, reqs = start, []
    for i in range(n):
        due += gaps[i] * scale
        reqs.append({"index": first_index + i, "due": due, "prompt_tokens": prompts[i],
                     "max_tokens": outputs[i], "phase": phase})
    return reqs


def open_loop_plan(mix, seconds, rate=None):
    """Warm-up arrivals for ``mix["warmup_s"]`` seconds, then the window's."""
    rate = rate if rate is not None else mix["rate"]
    if not rate:
        raise ValueError("the mix has no rate yet: pass one")
    rng = random.Random(mix["order_seed"])
    n_warm = max(1, round(rate * mix["warmup_s"]))
    n_win = max(1, round(rate * seconds))
    reqs = _phase(mix, rng, n_warm, 0.0, mix["warmup_s"], "warmup", 0)
    reqs += _phase(mix, rng, n_win, mix["warmup_s"], seconds, "window", n_warm)
    return {"rate": rate, "warmup_s": mix["warmup_s"], "seconds": seconds, "requests": reqs}


def describe(values):
    values = sorted(values)
    pick = lambda q: values[min(len(values) - 1, int(q * len(values)))]
    return {"n": len(values), "min": values[0], "p50": pick(0.5), "p90": pick(0.9), "max": values[-1],
            "sum": sum(values)}


def prefill_buckets(mix, minimum=16):
    """The power-of-two prompt-length buckets the mix can produce (the engine
    pads a prefill to the next power of two, at least ``minimum``)."""
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    out, b = [], minimum
    while True:
        if b >= lo:
            out.append(b)
        if b >= hi:
            return out
        b *= 2
