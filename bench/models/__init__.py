"""Weights and plain references, one module per model family.

A configuration file names its module under ``model``. The module brings
everything of the benchmark that depends on the block: ``make_params``,
``adapter_fn`` and ``forward_logits`` (weights and the plain reference),
and the counts ``lora_modules``, ``layer_weight_params`` and
``token_flops`` that ``bench/harness/flops.py`` builds on.
"""
import importlib
from types import ModuleType
from typing import Dict


def module_of(cfg: Dict) -> ModuleType:
    """The model module that a configuration file names."""
    return importlib.import_module(f"bench.models.{cfg['model']}")
