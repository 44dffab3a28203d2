"""A configuration file reaches the program's ModelConfig whole: every key
but the benchmark's own sets the field of that name, nested groups become
their dataclasses and lists their tuples, and a key that names nothing
stops the run."""
import dataclasses
import json
import os
from typing import Dict, Optional

import pytest

from bench.harness import engine
from bench.harness.engine import HARNESS_KEYS, model_config
from repro.configs.base import (AttentionConfig, LoRAConfig, ModelConfig,
                                get_config)

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "configs")

# the program's own configurations (src/repro/configs), by CLI id
PROGRAM = ("qwen2-0.5b", "starcoder2-7b", "llama3-8b", "llama3.2-3b",
           "qwen1.5-110b", "openelm-1.1b", "chameleon-34b", "gemma2-9b",
           "dbrx-132b", "llama4-maverick-400b-a17b", "mamba2-130m",
           "zamba2-2.7b", "whisper-medium")

OWN = {"source": "test", "reduced": [], "model": "dense_decoder",
       "deployment": "test", "departures": [], "published": {},
       "assumed": {}, "correct": {"logit_gap_mean": 0.001}}


def _file(name: str) -> Dict:
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def _as_file(cfg: ModelConfig) -> Dict:
    """``cfg`` written as a configuration file's keys, through JSON."""
    return json.loads(json.dumps({**dataclasses.asdict(cfg), **OWN}))


def _fifteen_keys(cfg: Dict, n_adapters: Optional[int] = None
                  ) -> ModelConfig:
    """How the harness built the ModelConfig before it passed every field:
    fifteen named top-level keys, plus ``attn`` and ``lora``."""
    keys = ("name", "family", "citation", "n_layers", "d_model", "n_heads",
            "n_kv_heads", "d_ff", "vocab_size", "head_dim", "norm_eps",
            "tie_embeddings", "act", "glu", "dtype")
    lora = dict(cfg["lora"])
    lora["target_modules"] = tuple(lora["target_modules"])
    if n_adapters is not None:
        lora["n_adapters"] = int(n_adapters)
    attn = dict(cfg["attn"])
    attn["layer_pattern"] = tuple(attn["layer_pattern"])
    return ModelConfig(**{k: cfg[k] for k in keys},
                       attn=AttentionConfig(**attn), lora=LoRAConfig(**lora))


def test_harness_keys():
    assert HARNESS_KEYS == {"source", "reduced", "model", "deployment",
                            "departures", "published", "assumed", "correct"}
    assert not HARNESS_KEYS & {f.name for f in dataclasses.fields(ModelConfig)}


@pytest.mark.parametrize("arch", PROGRAM)
def test_program_config_round_trips(arch):
    want = get_config(arch)
    got = model_config(_as_file(want))
    assert got == want
    assert type(got.attn.layer_pattern) is tuple
    assert type(got.lora.target_modules) is tuple
    if want.ssm is not None:
        assert type(got.ssm) is type(want.ssm)
        assert type(got.ssm.a_init_range) is tuple


def test_round_trips_cover_every_block_option():
    """The program's configurations set moe, ssm, encoder, post_norm, the
    soft-caps and the hybrid period somewhere, so the round trips above
    carry each of them."""
    cfgs = [get_config(a) for a in PROGRAM]
    assert any(c.moe is not None for c in cfgs)
    assert any(c.ssm is not None for c in cfgs)
    assert any(c.encoder is not None for c in cfgs)
    assert any(c.post_norm for c in cfgs)
    assert any(c.scale_embeddings for c in cfgs)
    assert any(c.final_logit_softcap is not None for c in cfgs)
    assert any(c.attn.attn_logit_softcap is not None for c in cfgs)
    assert any(c.shared_attn_every for c in cfgs)


@pytest.mark.parametrize("name", ["qwen2-0.5b", "starcoder2-7b-l16"])
@pytest.mark.parametrize("n_adapters", [None, 7])
def test_benchmark_files_build_what_fifteen_keys_built(name, n_adapters):
    cfg = _file(name)
    got, want = model_config(cfg, n_adapters), _fifteen_keys(cfg, n_adapters)
    for f in dataclasses.fields(ModelConfig):
        assert getattr(got, f.name) == getattr(want, f.name), f.name
    assert got == want


def test_benchmark_files_pinned():
    q = model_config(_file("qwen2-0.5b"))
    assert (q.n_layers, q.d_model, q.n_heads, q.n_kv_heads, q.head_dim,
            q.d_ff, q.vocab_size) == (24, 896, 14, 2, 64, 4864, 151936)
    assert q.attn == AttentionConfig(layer_pattern=("global",),
                                     sliding_window=4096, qkv_bias=True,
                                     rope_theta=1000000.0)
    assert q.lora == LoRAConfig(rank=16, alpha=32.0, target_modules=(
        "q", "k", "v", "o", "up", "gate", "down"), max_resident=32,
        n_adapters=1024)
    assert (q.moe, q.ssm, q.encoder, q.post_norm, q.final_logit_softcap,
            q.lora_backend, q.kv_backend) == (None, None, None, False, None,
                                              "auto", "dense")
    s = model_config(_file("starcoder2-7b-l16"), 64)
    assert (s.n_layers, s.d_model, s.d_ff, s.act, s.glu, s.tie_embeddings,
            s.attn.layer_pattern) == (16, 4608, 18432, "gelu", False, False,
                                      ("local",))


@pytest.mark.parametrize("key,where", [
    ("d_modle", None), ("window", "attn"), ("rnak", "lora")],
    ids=["top", "attn", "lora"])
def test_unknown_key_stops_the_run(key, where):
    cfg = _file("qwen2-0.5b")
    if where is None:
        cfg[key] = 1
        name = key
    else:
        cfg[where] = dict(cfg[where], **{key: 1})
        name = f"{where}.{key}"
    with pytest.raises(ValueError, match=f"'{name}'"):
        model_config(cfg)


@dataclasses.dataclass(frozen=True)
class _Later(ModelConfig):
    """A ModelConfig with a field that a later version of the program
    adds: the harness passes it with no edit."""

    norm_kind: str = "rms"


def test_a_field_the_program_adds_later_passes(monkeypatch):
    base = model_config(_file("qwen2-0.5b"))
    cfg = dict(_file("qwen2-0.5b"), norm_kind="layer")
    with pytest.raises(ValueError, match="'norm_kind'"):
        model_config(cfg)
    monkeypatch.setattr(engine, "ModelConfig", _Later)
    got = model_config(cfg)
    assert type(got) is _Later and got.norm_kind == "layer"
    for f in dataclasses.fields(ModelConfig):
        assert getattr(got, f.name) == getattr(base, f.name), f.name
