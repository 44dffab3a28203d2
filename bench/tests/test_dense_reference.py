"""The dense decoder's reference refuses, naming the key, every option it
does not compute, and computes what it does compute bit for bit as it did.

``data/tiny_reference_logits.npz`` holds the float32 reference's logits
and its int8 control's for three rows of the tiny configuration (seed
2**33 + 5), as the reference gave them before its counts and its refusal
were added to the module, on this CPU backend.
"""
import copy
import os

import numpy as np
import pytest

from bench.models import dense_decoder as dd
from bench.tests import tiny

SEED = 2 ** 33 + 5
DATA = os.path.join(os.path.dirname(__file__), "data",
                    "tiny_reference_logits.npz")


def _rows():
    rng = np.random.default_rng(7)
    out = []
    for plen, n_out, adapter in ((9, 7, 3), (5, 11, 12), (14, 4, 3)):
        toks = rng.integers(0, tiny.CONFIG["vocab_size"], plen + n_out - 1,
                            dtype=np.int32)
        out.append({"tokens": toks, "prompt_len": plen, "n_out": n_out,
                    "adapter": adapter})
    return out


def test_reference_logits_unchanged():
    cfg = tiny.CONFIG
    params = dd.make_params(cfg, SEED)
    adapter_of = dd.adapter_fn(cfg, SEED)
    rows = _rows()
    adapters = {a: adapter_of(a) for a in sorted({r["adapter"] for r in rows})}
    want = np.load(DATA)
    for kind, int8 in (("reference", False), ("int8", True)):
        got = dd.forward_logits(cfg, params, adapters, rows, int8=int8)
        for i, logits in enumerate(got):
            assert logits.dtype == np.float32
            np.testing.assert_array_equal(logits, want[f"{kind}_{i}"])


def _set(key, value):
    cfg = copy.deepcopy(tiny.CONFIG)
    if key.startswith("attn."):
        cfg["attn"][key[len("attn."):]] = value
    else:
        cfg[key] = value
    return cfg


NOT_COMPUTED = [
    ("attn.layer_pattern", ["local", "global"]),
    ("attn.layer_pattern", ["none"]),
    ("attn.qk_norm", True),
    ("attn.attn_logit_softcap", 50.0),
    ("attn.chunked_local", True),
    ("attn.rope", False),
    ("act", "gelu"),
    ("post_norm", True),
    ("final_logit_softcap", 30.0),
    ("scale_embeddings", True),
    ("moe", {"n_experts": 16, "top_k": 4}),
    ("ssm", {"d_state": 128}),
    ("encoder", {"n_layers": 24}),
    ("shared_attn_every", 6),
]


@pytest.mark.parametrize("key,value", NOT_COMPUTED,
                         ids=[k if k != "attn.layer_pattern"
                              else k + "=" + "+".join(v)
                              for k, v in NOT_COMPUTED])
def test_refuses_what_it_does_not_compute(key, value):
    cfg = _set(key, value)
    with pytest.raises(ValueError, match=f"'{key}'"):
        dd.check(cfg)
    with pytest.raises(ValueError, match=f"'{key}'"):
        dd.make_params(cfg, SEED)
    with pytest.raises(ValueError, match=f"'{key}'"):
        dd.forward_logits(cfg, {}, {}, _rows())


@pytest.mark.parametrize("key", ["norm_kind", "attn.rope_scaling",
                                 "lora.use_rslora"])
def test_refuses_a_key_it_does_not_know(key):
    """A field that the program adds later reaches the engine from the file
    (``test_config_file``); this reference refuses it until it computes it."""
    cfg = copy.deepcopy(tiny.CONFIG)
    group, _, name = key.rpartition(".")
    (cfg[group] if group else cfg)[name] = "layer"
    with pytest.raises(ValueError, match=f"does not know .*'{key}'"):
        dd.check(cfg)


@pytest.mark.parametrize("key,value", [
    ("attn.layer_pattern", ["local"]), ("attn.kv_cache_quant", True),
    ("attn.qk_norm", False), ("attn.rope", True), ("moe", None),
    ("final_logit_softcap", None), ("shared_attn_every", 0)])
def test_accepts_what_it_computes(key, value):
    dd.check(_set(key, value))


def test_benchmark_configurations_pass():
    import json

    for name in ("qwen2-0.5b", "starcoder2-7b-l16"):
        with open(os.path.join(tiny.BENCH, "configs", name + ".json")) as f:
            dd.check(json.load(f))
