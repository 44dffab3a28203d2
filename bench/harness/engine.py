"""The system under test, as the benchmark drives it.

``make_engine`` builds the program's ``EdgeLoRAEngine`` through a subclass
that overrides only ``_adapter_host``: adapter ``i`` is the benchmark's
seeded pair with nonzero A and B (the program's own adapters have B = 0,
so a served stream could never show a wrong LoRA delta). ``WallObserver``
is the ``tracer`` each wave gets: its hooks stamp the host clock per
request at every step and count compiles, and do nothing else.
"""
from __future__ import annotations

import dataclasses
import time
import types
import typing
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from repro.configs.base import ModelConfig
from repro.serving.engine import EdgeLoRAEngine, EngineConfig
from repro.serving.trace import EngineTracer

# keys of a configuration file that the benchmark reads itself; every other
# key names a field of the program's ModelConfig
HARNESS_KEYS = frozenset({"source", "reduced", "model", "deployment",
                          "departures", "published", "assumed", "correct"})


def _typed(hint: Any, value: Any, key: str) -> Any:
    """A value as JSON gives it, in the type of a field annotated ``hint``:
    an object becomes the field's dataclass, a list a tuple."""
    if typing.get_origin(hint) in (typing.Union, types.UnionType):
        args = [a for a in typing.get_args(hint) if a is not type(None)]
        if value is None or len(args) != 1:
            return value
        hint = args[0]
    if dataclasses.is_dataclass(hint) and isinstance(value, dict):
        return _dataclass(hint, value, key + ".")
    if (typing.get_origin(hint) or hint) is tuple and isinstance(value, list):
        return tuple(value)
    return value


def _dataclass(cls: type, values: Dict, prefix: str = "") -> Any:
    """``cls`` from a configuration file's keys; a key that names none of
    its fields stops the run."""
    hints = typing.get_type_hints(cls)
    names = {f.name for f in dataclasses.fields(cls) if f.init}
    for key in values:
        if key not in names:
            raise ValueError(f"configuration key {prefix + key!r} is neither "
                             f"one of the benchmark's own "
                             f"({', '.join(sorted(HARNESS_KEYS))}) nor a "
                             f"field of the program's {cls.__name__}")
    return cls(**{k: _typed(hints[k], v, prefix + k)
                  for k, v in values.items()})


def model_config(cfg: Dict, n_adapters: Optional[int] = None) -> ModelConfig:
    """The program's ModelConfig for a configuration file: every key but
    the benchmark's own (``HARNESS_KEYS``) sets the field of that name, as
    ``dataclasses.fields`` lists them. ``n_adapters`` (from the traffic mix)
    overrides how many adapters are registered."""
    mcfg = _dataclass(ModelConfig, {k: v for k, v in cfg.items()
                                    if k not in HARNESS_KEYS})
    if n_adapters is not None:
        mcfg = dataclasses.replace(mcfg, lora=dataclasses.replace(
            mcfg.lora, n_adapters=int(n_adapters)))
    return mcfg


def engine_config(mix: Dict, seed: int) -> EngineConfig:
    kw = dict(mix["engine"])
    kw["prompt_buckets"] = tuple(kw["prompt_buckets"])
    return EngineConfig(seed=seed, **kw)


class WallObserver(EngineTracer):
    """Host-clock stamps of one ``serve()`` call.

    ``compute`` fires after each step's ``block_until_ready`` with the real
    request ids the step served: a prefill kind gives those requests their
    first token, a decode kind one more token each. ``steps`` keeps
    (kind, key, stamp, request ids) in order; ``compiles`` counts first
    sightings of a step shape.
    """

    def __init__(self, annotate: bool = False) -> None:
        super().__init__(strict_watchdog=False)
        self.steps: List[Tuple[str, Tuple, float, Tuple[int, ...]]] = []
        self.prefill: Dict[int, float] = {}
        self.decode: Dict[int, List[float]] = {}
        self.compiles = 0
        self.annotate = annotate

    def compute(self, t: float, dt: float, key: Tuple,
                requests: Optional[List[int]] = None) -> None:
        stamp = time.perf_counter()
        kind = key[0]
        rids = tuple(requests or ())
        self.steps.append((kind, key, stamp, rids))
        if kind.startswith("prefill"):
            for rid in rids:
                self.prefill[rid] = stamp
        else:
            for rid in rids:
                self.decode.setdefault(rid, []).append(stamp)
        if self.annotate:
            with jax.profiler.TraceAnnotation(f"bench.after {kind}"):
                pass

    def compile(self, t: float, key: Tuple) -> None:
        self.compiles += 1

    def token_stamps(self, rid: int) -> List[float]:
        first = self.prefill.get(rid)
        if first is None:
            return []
        return [first] + self.decode.get(rid, [])

    # the engine's other hooks record nothing here
    def begin(self, now: float, n_slots: int, meta: Any = None) -> None:
        self.meta = {"n_slots": n_slots}

    def finish(self, now: float) -> Dict:
        return {}

    def transition(self, *args: Any, **kwargs: Any) -> None:
        pass

    def sched(self, *args: Any, **kwargs: Any) -> None:
        pass

    def channel_hook(self, *args: Any, **kwargs: Any) -> None:
        pass

    def arena_hook(self, *args: Any, **kwargs: Any) -> None:
        pass

    def sample(self, *args: Any, **kwargs: Any) -> None:
        pass

    def clock(self, now: float) -> None:
        pass

    def breakdown_summary(self) -> None:
        return None


def _tree_sig(tree: Any) -> Any:
    return jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), tree)


def check_layout(cfg: ModelConfig, params: Any, adapter: Any) -> None:
    """The benchmark's weight trees must have exactly the structure,
    shapes and dtypes of the program's own (``Model.init`` /
    ``Model.init_lora``); fail loudly otherwise."""
    from repro.models import build_model

    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    want_p = _tree_sig(jax.eval_shape(model.init, key))
    want_a = jax.tree.map(lambda a: tuple(a.shape),
                          jax.eval_shape(model.init_lora, key))
    have_p = _tree_sig(jax.eval_shape(lambda: params))
    have_a = jax.tree.map(lambda a: tuple(a.shape),
                          jax.eval_shape(lambda: adapter))
    if want_p != have_p:
        raise RuntimeError(f"benchmark weights do not match the program's "
                           f"parameter layout:\n{want_p}\nvs\n{have_p}")
    if want_a != have_a:
        raise RuntimeError(f"benchmark adapter does not match the program's "
                           f"LoRA layout:\n{want_a}\nvs\n{have_a}")


def make_engine(cfg: ModelConfig, ecfg: EngineConfig, params: Any,
                adapter_of: Callable[[int], Any]) -> EdgeLoRAEngine:
    """The program's engine, serving the benchmark's weights and adapters.
    ``loaded`` lists every adapter id the pool loaded, in order."""

    class BenchEngine(EdgeLoRAEngine):
        loaded: List[int] = []

        def _adapter_host(self, adapter_id: int) -> Any:
            BenchEngine.loaded.append(int(adapter_id))
            return adapter_of(int(adapter_id))

    BenchEngine.loaded = []
    return BenchEngine(cfg, ecfg, params=params)
