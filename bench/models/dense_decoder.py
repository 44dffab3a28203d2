"""Weights and plain reference for the dense decoder configurations.

A dense decoder here is what ``bench/configs/<config>.json`` states: token
embedding, ``n_layers`` pre-norm blocks (RMSNorm with weight ``1 + s``,
grouped-query attention with rotate-half RoPE and optional q/k/v biases,
then a SwiGLU or a tanh-GELU MLP), a final RMSNorm and a tied or untied
output head. LoRA pairs (A [r, d_in], B [d_out, r], delta
``alpha / r * x A^T B^T``) sit on the projections the file lists.

This file imports nothing of the program under test. ``check`` refuses,
naming the key, a configuration that sets an option it does not compute
(more than one kind of layer, query/key norms, soft-caps, chunked or
position-free attention, post-norms, scaled embeddings, experts, state-space
or encoder layers), or any key it does not know; ``make_params`` and
``forward_logits`` call it. It holds the block's counts that
``bench/harness/flops.py`` builds on (``lora_modules``,
``layer_weight_params``, ``token_flops``), and makes:

* ``make_params``: every base weight, in bf16, on the device, in one jitted
  call from the seed. The tree has the layout the serving engine takes as
  ``params`` (layers stacked on a leading axis under ``layers/slot0``);
  ``bench/harness/engine.py`` checks that layout against the program's own
  before the engine is built.
* ``adapter_fn``: one adapter (nonzero A and B), bf16, from the seed and
  the adapter id, stacked over layers the same way.
* ``forward_logits``: a float32 forward at ``highest`` matmul precision
  over whole sequences, layer by layer, and with ``int8=True`` its int8
  twin (weights per output channel, activations per token): the control
  that the comparison deciding ``correct`` must reject.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# standard deviations of the random weights (uniform draws): unit-scale
# projections, small biases and norm offsets, embeddings and head scaled so
# that logits have a standard deviation near 1 (flat enough that near-ties,
# where rounding shows, are common), and B sized so that each
# LoRA delta is about a third of its base projection
NORM_STD = 0.1
BIAS_STD = 0.1
LOGIT_STD = 1.0
LORA_B_STD = 0.15 / math.sqrt(16.0)

SALT_PARAMS = 0xB01
SALT_ADAPTERS = 0xADA


def key_from(seed: int, salt: int) -> jax.Array:
    """A jax PRNG key from any non-negative seed (jax's own PRNGKey drops
    the bits above 32, so the seed goes through numpy's SeedSequence)."""
    word = np.random.SeedSequence([int(seed), salt]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def _u(key: jax.Array, shape: Sequence[int], std: float) -> jax.Array:
    x = jax.random.uniform(key, tuple(shape), jnp.float32, -1.0, 1.0)
    return (x * (std * math.sqrt(3.0))).astype(jnp.bfloat16)


def dims(cfg: Dict) -> Dict[str, int]:
    hd = cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]
    return {"d": cfg["d_model"], "f": cfg["d_ff"], "v": cfg["vocab_size"],
            "hd": hd, "h": cfg["n_heads"], "kh": cfg["n_kv_heads"],
            "qs": cfg["n_heads"] * hd, "kvs": cfg["n_kv_heads"] * hd,
            "L": cfg["n_layers"], "r": cfg["lora"]["rank"]}


# options of a configuration file that this reference does not compute,
# each with the test that the file sets it: a block that needs one is
# another model module's
_NOT_COMPUTED = {
    "attn.layer_pattern": lambda c: len(set(c["attn"]["layer_pattern"])) != 1
    or c["attn"]["layer_pattern"][0] not in ("global", "local"),
    "attn.qk_norm": lambda c: c["attn"].get("qk_norm", False),
    "attn.attn_logit_softcap":
        lambda c: c["attn"].get("attn_logit_softcap") is not None,
    "attn.chunked_local": lambda c: c["attn"].get("chunked_local", False),
    "attn.rope": lambda c: not c["attn"].get("rope", True),
    "act": lambda c: c["act"] != ("silu" if c["glu"] else "gelu"),
    "post_norm": lambda c: c.get("post_norm", False),
    "final_logit_softcap": lambda c: c.get("final_logit_softcap") is not None,
    "scale_embeddings": lambda c: c.get("scale_embeddings", False),
    "moe": lambda c: c.get("moe") is not None,
    "ssm": lambda c: c.get("ssm") is not None,
    "encoder": lambda c: c.get("encoder") is not None,
    "shared_attn_every": lambda c: c.get("shared_attn_every", 0),
}


# every other key of a configuration file that this module knows, by group:
# what the block reads, the benchmark's own keys, and the program's backend
# choices, which change no logit the reference computes. A key outside these
# and ``_NOT_COMPUTED`` (a field the program adds later) is refused too.
_KNOWN = {
    "": {"name", "family", "citation", "n_layers", "d_model", "n_heads",
         "n_kv_heads", "d_ff", "vocab_size", "head_dim", "norm_eps",
         "tie_embeddings", "glu", "dtype", "attn", "lora", "lora_backend",
         "kv_backend", "source", "reduced", "model", "deployment",
         "departures", "published", "assumed", "correct"},
    "attn.": {"sliding_window", "qkv_bias", "rope_theta", "kv_cache_quant"},
    "lora.": {"rank", "alpha", "dropout", "target_modules", "max_resident",
              "n_adapters"},
}


def _refuse(key: str, what: str) -> None:
    raise ValueError(f"bench/models/dense_decoder.py {what} the "
                     f"configuration's {key!r}; a block with it needs a "
                     f"model module of its own")


def check(cfg: Dict) -> None:
    """Refuse a configuration that sets an option this reference does not
    compute, or a key it does not know, naming the key."""
    for prefix, known in _KNOWN.items():
        group = cfg[prefix[:-1]] if prefix else cfg
        for key in group:
            if key not in known and prefix + key not in _NOT_COMPUTED:
                _refuse(prefix + key, "does not know")
    for key, is_set in _NOT_COMPUTED.items():
        if is_set(cfg):
            _refuse(key, "does not compute")


# ---------------------------------------------------------------------------
# counts of the block (bench/harness/flops.py builds on them)
# ---------------------------------------------------------------------------


def lora_modules(cfg: Dict) -> Dict[str, Tuple[int, int]]:
    """(d_in, d_out) of each LoRA target of one layer, in the config's
    order (``gate`` only in a gated MLP)."""
    m = dims(cfg)
    full = {"q": (m["d"], m["qs"]), "k": (m["d"], m["kvs"]),
            "v": (m["d"], m["kvs"]), "o": (m["qs"], m["d"]),
            "up": (m["d"], m["f"]), "gate": (m["d"], m["f"]),
            "down": (m["f"], m["d"])}
    return {name: full[name] for name in cfg["lora"]["target_modules"]
            if name != "gate" or cfg["glu"]}


def layer_weight_params(cfg: Dict) -> int:
    """Weights of one layer's matrix multiplications."""
    m = dims(cfg)
    return 2 * m["d"] * m["qs"] + 2 * m["d"] * m["kvs"] \
        + (3 if cfg["glu"] else 2) * m["d"] * m["f"]


def token_flops(cfg: Dict, context: int) -> int:
    """One token through every layer, attending over ``context`` keys
    (itself included): weights, LoRA and attention (scores and values).
    A multiply-add is two operations."""
    m = dims(cfg)
    lora = sum(2 * m["r"] * (i + o) for i, o in lora_modules(cfg).values())
    attn = 4 * context * m["h"] * m["hd"]
    return m["L"] * (2 * layer_weight_params(cfg) + lora + attn)


def _layer(key: jax.Array, cfg: Dict) -> Dict:
    m = dims(cfg)
    d, f = m["d"], m["f"]
    ks = jax.random.split(key, 12)
    attn = {"wq": _u(ks[0], (d, m["qs"]), d ** -0.5),
            "wk": _u(ks[1], (d, m["kvs"]), d ** -0.5),
            "wv": _u(ks[2], (d, m["kvs"]), d ** -0.5),
            "wo": _u(ks[3], (m["qs"], d), m["qs"] ** -0.5)}
    if cfg["attn"]["qkv_bias"]:
        attn["bq"] = _u(ks[4], (m["qs"],), BIAS_STD)
        attn["bk"] = _u(ks[5], (m["kvs"],), BIAS_STD)
        attn["bv"] = _u(ks[6], (m["kvs"],), BIAS_STD)
    mlp = {"up": _u(ks[7], (d, f), d ** -0.5),
           "down": _u(ks[8], (f, d), f ** -0.5)}
    if cfg["glu"]:
        mlp["gate"] = _u(ks[9], (d, f), d ** -0.5)
    return {"ln1": {"scale": _u(ks[10], (d,), NORM_STD)}, "attn": attn,
            "ln2": {"scale": _u(ks[11], (d,), NORM_STD)}, "mlp": mlp}


def make_params(cfg: Dict, seed: int) -> Dict:
    """Every base weight, bf16, on the default device, in one jitted call."""
    check(cfg)
    m = dims(cfg)
    emb_std = LOGIT_STD / math.sqrt(m["d"])

    def build(key: jax.Array) -> Dict:
        k_emb, k_head, k_fin, k_layers = jax.random.split(key, 4)
        layers = jax.lax.map(
            lambda i: _layer(jax.random.fold_in(k_layers, i), cfg),
            jnp.arange(m["L"], dtype=jnp.int32))
        out = {"embed": _u(k_emb, (m["v"], m["d"]), emb_std),
               "final_norm": {"scale": _u(k_fin, (m["d"],), NORM_STD)},
               "layers": {"slot0": layers}}
        if not cfg["tie_embeddings"]:
            out["lm_head"] = _u(k_head, (m["d"], m["v"]), emb_std)
        return out

    return jax.jit(build)(key_from(seed, SALT_PARAMS))


def adapter_fn(cfg: Dict, seed: int):
    """A jitted ``adapter_id -> adapter`` for this seed: one compile, any
    id. Leaves are bf16, A [L, r, d_in] and B [L, d_out, r]."""
    m = dims(cfg)
    shapes = lora_modules(cfg)
    base = key_from(seed, SALT_ADAPTERS)

    def build(adapter_id: jax.Array) -> Dict:
        key = jax.random.fold_in(base, adapter_id)
        tree = {}
        for j, (name, (d_in, d_out)) in enumerate(shapes.items()):
            ka, kb = jax.random.split(jax.random.fold_in(key, j))
            tree[name] = {"A": _u(ka, (m["L"], m["r"], d_in), d_in ** -0.5),
                          "B": _u(kb, (m["L"], d_out, m["r"]), LORA_B_STD)}
        return {"layers": {"slot0": tree}}

    jitted = jax.jit(build)
    return lambda adapter_id: jitted(jnp.int32(adapter_id))


# ---------------------------------------------------------------------------
# plain reference (float32, highest precision) and its int8 control
# ---------------------------------------------------------------------------


def _fake_int8(x: jax.Array, axis: int) -> jax.Array:
    """Symmetric int8 rounding with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(x: jax.Array, w: jax.Array, int8: bool) -> jax.Array:
    """x [..., d_in] @ w [d_in, d_out]."""
    if int8:
        x = _fake_int8(x, -1)
        w = _fake_int8(w, 0)
    return jnp.einsum("...d,do->...o", x, w)


def _lora(x: jax.Array, pair: Dict, scale: float, int8: bool) -> jax.Array:
    """Per-row adapters: x [N, S, d_in], A [N, r, d_in], B [N, d_out, r]."""
    a, b = pair["A"], pair["B"]
    if int8:
        x = _fake_int8(x, -1)
        a = _fake_int8(a, -1)
        b = _fake_int8(b, -1)
    s = jnp.einsum("nsd,nrd->nsr", x, a)
    if int8:
        s = _fake_int8(s, -1)
    return scale * jnp.einsum("nsr,nor->nso", s, b)


def _rmsnorm(x: jax.Array, s: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + s)


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotate-half RoPE. x [N, S, H, hd]; pos [S]."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu_tanh(x: jax.Array) -> jax.Array:
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _block(cfg: Dict, int8: bool, lp: Dict, la: Dict, h: jax.Array
           ) -> jax.Array:
    """One decoder layer over whole sequences. h [N, S, d] float32."""
    m = dims(cfg)
    n, s, _ = h.shape
    eps = cfg["norm_eps"]
    scale = cfg["lora"]["alpha"] / cfg["lora"]["rank"]
    at = lp["attn"]

    def proj(x, w, name, bias=None):
        y = _mm(x, w, int8)
        if bias is not None:
            y = y + bias
        if name in la:
            y = y + _lora(x, la[name], scale, int8)
        return y

    x = _rmsnorm(h, lp["ln1"]["scale"], eps)
    q = proj(x, at["wq"], "q", at.get("bq")).reshape(n, s, m["h"], m["hd"])
    k = proj(x, at["wk"], "k", at.get("bk")).reshape(n, s, m["kh"], m["hd"])
    v = proj(x, at["wv"], "v", at.get("bv")).reshape(n, s, m["kh"], m["hd"])
    pos = jnp.arange(s)
    theta = cfg["attn"]["rope_theta"]
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    g = m["h"] // m["kh"]
    qg = q.reshape(n, s, m["kh"], g, m["hd"])
    sc = jnp.einsum("nqkgd,nskd->nkgqs", qg, k) * m["hd"] ** -0.5
    allowed = pos[None, :] <= pos[:, None]
    if cfg["attn"]["layer_pattern"][0] == "local":
        allowed &= (pos[:, None] - pos[None, :]) < cfg["attn"]["sliding_window"]
    sc = jnp.where(allowed, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("nkgqs,nskd->nqkgd", p, v).reshape(n, s, m["qs"])
    h = h + proj(o, at["wo"], "o")
    x = _rmsnorm(h, lp["ln2"]["scale"], eps)
    mp = lp["mlp"]
    up = proj(x, mp["up"], "up")
    if cfg["glu"]:
        gate = proj(x, mp["gate"], "gate")
        y = jax.nn.sigmoid(gate) * gate * up
    else:
        y = _gelu_tanh(up)
    return h + proj(y, mp["down"], "down")


def _logits_fn(cfg: Dict, int8: bool):
    eps = cfg["norm_eps"]

    def fn(head: jax.Array, fin: jax.Array, h: jax.Array) -> jax.Array:
        x = _rmsnorm(h, fin, eps)
        if cfg["tie_embeddings"]:
            return _mm(x, head.T, int8)
        return _mm(x, head, int8)

    return fn


def forward_logits(cfg: Dict, params: Dict, adapters: Dict[int, Dict],
                   rows: List[Dict], int8: bool = False) -> List[np.ndarray]:
    """Logits at the positions that predict each row's served tokens.

    rows: dicts with ``tokens`` (prompt then served tokens, int32),
    ``prompt_len``, ``n_out`` and ``adapter``. Runs all rows together,
    right-padded (padding is after every position read, so causal attention
    never sees it), one layer at a time in float32 from the bf16 weights.
    Returns one [n_out, vocab] float32 array per row.
    """
    check(cfg)
    m = dims(cfg)
    n = len(rows)
    s = max(len(r["tokens"]) for r in rows)
    toks = np.zeros((n, s), np.int32)
    for i, r in enumerate(rows):
        toks[i, :len(r["tokens"])] = r["tokens"]
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)  # noqa: E731
    block = jax.jit(lambda lp, la, h: _block(cfg, int8, lp, la, h))
    logits = _logits_fn(cfg, int8)
    out: List[np.ndarray] = []
    with jax.default_matmul_precision("highest"):
        h = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
        stacked = params["layers"]["slot0"]
        for layer in range(m["L"]):
            lp = f32(jax.tree.map(lambda a: a[layer], stacked))
            la = {}
            for name in lora_modules(cfg):
                la[name] = {
                    ab: jnp.stack([adapters[r["adapter"]]["layers"]["slot0"]
                                   [name][ab][layer] for r in rows]
                                  ).astype(jnp.float32)
                    for ab in ("A", "B")}
            h = block(lp, la, h)
        head = params["embed"] if cfg["tie_embeddings"] else params["lm_head"]
        head = head.astype(jnp.float32)
        fin = params["final_norm"]["scale"].astype(jnp.float32)
        # one program for every row: a fixed-size slice of each row's
        # hidden states, starting at the position of its first served token
        width = max(r["n_out"] for r in rows)
        h = jnp.pad(h, ((0, 0), (0, width), (0, 0)))
        take = jax.jit(lambda hd, fn, hr, p0: logits(
            hd, fn, jax.lax.dynamic_slice_in_dim(hr, p0, width, 0)))
        for i, r in enumerate(rows):
            full = np.asarray(take(head, fin, h[i],
                                   jnp.int32(r["prompt_len"] - 1)))
            out.append(full[:r["n_out"]])
    return out
