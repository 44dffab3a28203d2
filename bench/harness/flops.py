"""Operations and bytes that the served work needs, from the shapes of the
configuration and of the requests alone (never from the program).

Counts are of real work: real prompt tokens in a prefill group (not the
padded bucket or replica rows), rows that hold a request in a decode step,
and only the LoRA adapters that those rows use. A multiply-add is two
operations.

What depends on the block (the LoRA targets' widths, the weights of a
layer, one token's operations through every layer) comes from the
configuration's model module, ``bench/models/<model>.py``
(``lora_modules``, ``layer_weight_params``, ``token_flops``); what is
here holds for any block.
"""
from __future__ import annotations

from typing import Dict, Tuple

from bench.models import module_of

BF16 = 2


def head_flops(cfg: Dict) -> int:
    """One row of logits."""
    return 2 * cfg["d_model"] * cfg["vocab_size"]


def prefill_flops(cfg: Dict, prompt_len: int) -> int:
    """A prompt's prefill: every prompt token under causal attention, and
    the logits of its last position only (the step reads no others)."""
    token_flops = module_of(cfg).token_flops
    return sum(token_flops(cfg, i + 1) for i in range(prompt_len)) \
        + head_flops(cfg)


def decode_flops(cfg: Dict, position: int) -> int:
    """One decode row feeding the token at ``position``."""
    return module_of(cfg).token_flops(cfg, position + 1) + head_flops(cfg)


def sgmv_need(tokens: int, n_adapters: int, d_in: int, d_out: int,
              rank: int) -> Tuple[int, int]:
    """(operations, bytes) of one SGMV call: shrink then expand for
    ``tokens`` rows; it must read x, the A and B of each adapter the rows
    use, and write y, all in bf16."""
    flops = 2 * tokens * rank * (d_in + d_out)
    nbytes = BF16 * (tokens * d_in + n_adapters * rank * (d_in + d_out)
                     + tokens * d_out)
    return flops, nbytes


def sgmv_step_bound(cfg: Dict, tokens: int, n_adapters: int,
                    peak_flops: float, peak_bw: float) -> float:
    """Least time the chip could take for one step's SGMV calls (every
    layer, every LoRA target, each call bound by the larger of its
    operations over the peak rate and its bytes over the bandwidth)."""
    r = cfg["lora"]["rank"]
    t = 0.0
    for d_in, d_out in module_of(cfg).lora_modules(cfg).values():
        fl, nb = sgmv_need(tokens, n_adapters, d_in, d_out, r)
        t += max(fl / peak_flops, nb / peak_bw)
    return cfg["n_layers"] * t
