"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished (drawn from the seed; it always holds the
longest one and, where the window loaded adapters, one request served by
an adapter loaded inside the window) is run through the plain reference of
``bench/models/<model>.py``: one float32 forward over each prompt followed
by the tokens the program served, with weights and adapters made again
from the seed. For every served token the reference's logits give a gap:
how far the served token's logit lies below the reference's best at that
position. A greedy server that computes what the configuration states
serves tokens whose gap is at most a rounding-sized amount. The numbers
compared (``numbers``), each with the limit the configuration file gives it
under ``correct``: the widest gap of the sample, and the mean gap over all
its served tokens (how often rounding flips a near-tie, times how far).

The control (``gaps(..., control=True)``) puts the reference, computed with
int8 weights and activations, in the program's place: at each position of
the same sequences it takes the token the int8 forward ranks first.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

SAMPLE = 12
SALT_SAMPLE = 0x5A3


def sample(done: Sequence[Dict], seed: int, loaded: Sequence[int],
           k: int = SAMPLE) -> List[Dict]:
    """k finished requests: the longest (prompt plus output), one whose
    adapter the pool loaded during the window (if any), the rest drawn
    from the seed."""
    if not done:
        return []
    rng = np.random.default_rng([seed, SALT_SAMPLE])
    order = list(rng.permutation(len(done)))
    longest = max(range(len(done)), key=lambda i: (
        done[i]["prompt_len"] + len(done[i]["tokens"]), -i))
    picked = [longest]
    loaded_set = set(loaded)
    for i in order:
        if i != longest and done[i]["adapter"] in loaded_set:
            picked.append(i)
            break
    for i in order:
        if len(picked) >= k:
            break
        if i not in picked:
            picked.append(i)
    return [done[i] for i in picked]


def gaps(model, cfg: Dict, seed: int, rows: Sequence[Dict],
         control: bool = False) -> Dict[str, np.ndarray]:
    """``served``: one gap per served token of every row, in the float32
    reference's logits. With ``control``, also ``int8``: the gaps of the
    tokens that the int8 control ranks first at the same positions."""
    params = model.make_params(cfg, seed)
    adapter_of = model.adapter_fn(cfg, seed)
    adapters = {a: adapter_of(a) for a in sorted({r["adapter"] for r in rows})}
    seqs = [{"tokens": np.concatenate([np.asarray(r["prompt_tokens"]),
                                       np.asarray(r["tokens"][:-1],
                                                  np.int32)]),
             "prompt_len": r["prompt_len"], "n_out": len(r["tokens"]),
             "adapter": r["adapter"]} for r in rows]
    ref = model.forward_logits(cfg, params, adapters, seqs)
    picks = {"served": [np.asarray(r["tokens"], np.int64) for r in rows]}
    if control:
        ctl = model.forward_logits(cfg, params, adapters, seqs, int8=True)
        picks["int8"] = [np.argmax(c, axis=-1) for c in ctl]
    del params, adapters
    return {name: np.concatenate(
        [lg.max(-1) - np.take_along_axis(lg, p[:, None], -1)[:, 0]
         for lg, p in zip(ref, ps)]) for name, ps in picks.items()}


def numbers(gaps_: np.ndarray) -> Dict[str, float]:
    """The numbers ``correct`` compares, from one sample's gaps."""
    return {"logit_gap_max": float(gaps_.max()),
            "logit_gap_mean": float(gaps_.mean())}
