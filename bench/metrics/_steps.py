"""What the traced wave's steps served, rebuilt from the wall-clock
observer's step list (request ids per step) and the wave's requests."""

from typing import Dict, Iterator, Tuple

from bench.harness import flops


def _requests(wave: Dict) -> Dict[int, Dict]:
    return {d["request_id"]: d for d in wave["requests"]}


def _adapters(wave: Dict) -> Dict[int, int]:
    return {r.request_id: r.selected_adapter for r in wave["reqs"]}


def prompt_tokens(wave: Dict) -> int:
    reqs = _requests(wave)
    return sum(reqs[rid]["prompt_len"]
               for kind, _, _, rids in wave["observer"].steps
               if kind.startswith("prefill") for rid in rids)


def sgmv_calls(wave: Dict) -> Iterator[Tuple[int, int]]:
    """(real tokens, distinct adapters) of each prefill or decode step."""
    reqs, adapters = _requests(wave), _adapters(wave)
    for kind, _, _, rids in wave["observer"].steps:
        if not rids:
            continue
        n = len({adapters[r] for r in rids})
        if kind.startswith("prefill"):
            yield sum(reqs[r]["prompt_len"] for r in rids), n
        elif kind.startswith("decode"):
            yield len(rids), n


def model_flops(cfg: Dict, wave: Dict) -> int:
    reqs = _requests(wave)
    made: Dict[int, int] = {}
    total = 0
    for kind, _, _, rids in wave["observer"].steps:
        for rid in rids:
            plen = reqs[rid]["prompt_len"]
            if kind.startswith("prefill"):
                total += flops.prefill_flops(cfg, plen)
                made[rid] = 1
            elif kind.startswith("decode"):
                total += flops.decode_flops(cfg, plen + made[rid] - 1)
                made[rid] += 1
    return total
