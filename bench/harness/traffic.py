"""The one traffic generator: bursts ("waves") of requests from a mix file.

A copy of the paper's workload generator (``src/repro/serving/workload.py``
``generate_trace``): adapter popularity is a power law P(i) ~ i^-alpha,
prompt and output lengths are uniform on the mix's ranges, optional
per-adapter system prompts open a share of the prompts, and an optional
share of prompts carries a long extra tail. Two things differ, both so
that every seed and every wave asks for the same work:

* arrivals are waves: ``wave_requests`` requests all due at once
  (``arrival_time`` 0), one ``serve()`` call each;
* lengths and the shares are stratified: a wave's prompt lengths are its
  ``wave_requests`` evenly spaced quantiles of the uniform range, and so
  are its output lengths, in an order drawn from the wave's index alone. A
  share f of N requests is exactly round(f N) of them. Every seed thus
  asks for the same sizes in the same order (on the chip, shuffling them
  by the seed moved the median time to first token of a 3-wave window by
  8% from seed to seed); the seed draws the adapters and the tokens.

Wave ``w`` of seed ``s`` depends on (s, w) alone.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

SALT_WAVE = 0x3A7E
SALT_SYSTEM = 0x5157


def _quantiles(lo: int, hi: int, n: int) -> np.ndarray:
    """n evenly spaced quantiles of the uniform integers in [lo, hi]."""
    k = np.arange(n, dtype=np.float64)
    return (lo + np.floor((k + 0.5) / n * (hi - lo + 1))).astype(np.int64)


def _share(rng: np.random.Generator, n: int, frac: float) -> np.ndarray:
    """A boolean mask with exactly round(frac * n) entries set."""
    mask = np.zeros(n, bool)
    mask[rng.permutation(n)[:int(round(frac * n))]] = True
    return mask


def popularity(n_adapters: int, alpha: float) -> np.ndarray:
    w = np.arange(1, n_adapters + 1, dtype=np.float64) ** (-alpha)
    return w / w.sum()


def system_prompts(mix: Dict, n_adapters: int, vocab: int, seed: int
                   ) -> Dict[int, np.ndarray]:
    n = int(mix.get("system_prompt_len", 0))
    if n <= 0:
        return {}
    rng = np.random.default_rng([seed, SALT_SYSTEM])
    return {a: rng.integers(0, vocab, n, dtype=np.int32)
            for a in range(n_adapters)}


def wave(mix: Dict, n_adapters: int, vocab: int, seed: int, index: int,
         first_id: int, prompts: Optional[Dict[int, np.ndarray]] = None
         ) -> List[Dict]:
    """The requests of wave ``index``, as plain dicts (the harness turns
    them into the program's ``Request``): request_id, prompt_tokens,
    prompt_len, output_len, true_adapter, adapter_id (None unless
    explicit)."""
    n = int(mix["wave_requests"])
    # sizes and their order depend on the wave's index alone; the seed
    # draws the adapters, the explicit share and the tokens
    sizes = np.random.default_rng([SALT_WAVE, index])
    rng = np.random.default_rng([seed, SALT_WAVE, index])
    plen = sizes.permutation(_quantiles(*mix["input_range"], n))
    olen = sizes.permutation(_quantiles(*mix["output_range"], n))
    long_mask = _share(sizes, n, float(mix.get("long_prompt_frac", 0.0)))
    extra = np.zeros(n, np.int64)
    if long_mask.any():
        extra[long_mask] = sizes.permutation(
            _quantiles(*mix["long_input_range"], int(long_mask.sum())))
    prompts = prompts or {}
    shared = (_share(sizes, n, float(mix.get("shared_prefix_frac", 1.0)))
              if prompts else np.zeros(n, bool))
    adapters = rng.choice(n_adapters, size=n,
                          p=popularity(n_adapters, float(mix["alpha"])))
    explicit = _share(rng, n, float(mix.get("explicit_adapter_frac", 0.0)))
    out = []
    for k in range(n):
        aid = int(adapters[k])
        toks = rng.integers(0, vocab, int(plen[k] + extra[k]), dtype=np.int32)
        if shared[k]:
            toks = np.concatenate([prompts[aid], toks])
        out.append({"request_id": first_id + k, "prompt_tokens": toks,
                    "prompt_len": int(len(toks)), "output_len": int(olen[k]),
                    "true_adapter": aid,
                    "adapter_id": aid if explicit[k] else None})
    return out


def max_prompt_len(mix: Dict) -> int:
    return (int(mix["input_range"][1]) + int(mix.get("system_prompt_len", 0))
            + (int(mix["long_input_range"][1])
               if mix.get("long_prompt_frac", 0.0) > 0 else 0))


def buckets_used(mix: Dict, buckets: Sequence[int]) -> List[int]:
    """The prompt buckets a prompt of this mix can land in (the engine
    pads each prompt to the smallest bucket that holds it)."""
    top = max_prompt_len(mix)
    lo = int(mix["input_range"][0]) + int(mix.get("system_prompt_len", 0))
    out = []
    prev = 0
    for b in sorted(buckets):
        if b >= lo and prev < top:
            out.append(b)
        prev = b
    return out
