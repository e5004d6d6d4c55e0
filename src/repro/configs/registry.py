"""--arch registry: id -> config.

Two tables, one per workload class:

* ``_MODULES`` — autoregressive LMs (``ModelConfig``; the 10 assigned
  architectures).  ``get_config`` / ``ARCH_IDS`` / ``all_configs``.
* ``_DIT_MODULES`` — diffusion transformers (``DiTConfig``).
  ``get_dit_config`` / ``DIT_ARCH_IDS`` / ``all_dit_configs``.

EVERY runnable config module in this package must appear in one of the
tables: ``REGISTERED_CONFIG_MODULES`` is the union the docs-check tool
(tools/check_docs.py, `make docs-check`) compares against the package
directory, so an unregistered config module fails the pre-push gate.
"""
from __future__ import annotations

import dataclasses
import importlib

from .base import ModelConfig

_MODULES = {
    "command-r-plus-104b": "command_r_plus_104b",
    "gemma3-4b": "gemma3_4b",
    "gemma-2b": "gemma_2b",
    "deepseek-67b": "deepseek_67b",
    "musicgen-medium": "musicgen_medium",
    "zamba2-1.2b": "zamba2_1p2b",
    "xlstm-350m": "xlstm_350m",
    "qwen2-moe-a2.7b": "qwen2_moe_a2p7b",
    "deepseek-v3-671b": "deepseek_v3_671b",
    "paligemma-3b": "paligemma_3b",
}

_DIT_MODULES = {
    "dit-xl-2": "dit_xl_2",
    "dit-test": "dit_test",
}

ARCH_IDS = tuple(_MODULES)
DIT_ARCH_IDS = tuple(_DIT_MODULES)

# Non-config support modules in this package (everything else must be a
# registered config module — enforced by `make docs-check`).
_SUPPORT_MODULES = frozenset({"__init__", "base", "registry", "shapes"})
REGISTERED_CONFIG_MODULES = (frozenset(_MODULES.values())
                             | frozenset(_DIT_MODULES.values()))


def _load(table: dict, arch: str, what: str):
    try:
        mod = table[arch]
    except KeyError:
        raise KeyError(f"unknown {what} {arch!r}; options: {list(table)}")
    return importlib.import_module(f"repro.configs.{mod}").CONFIG


def get_config(arch: str) -> ModelConfig:
    """``arch`` is a registered id, or ``<id>:ep<n>.<i>``: the model
    whose MoE layers hold shard ``i`` of ``n`` of their routed experts,
    one chip's share under n-way expert parallelism (the router keeps
    every expert)."""
    if arch in _DIT_MODULES:
        raise KeyError(f"{arch!r} is a diffusion config; use "
                       f"get_dit_config({arch!r})")
    name, _, share = arch.partition(":ep")
    cfg = _load(_MODULES, name, "arch")
    if share:
        n, _, i = share.partition(".")
        cfg = expert_share(cfg, int(n), int(i))
    return cfg


def expert_share(cfg: ModelConfig, n_shards: int, shard: int) -> ModelConfig:
    """``cfg`` with its MoE layers holding shard ``shard`` of
    ``n_shards`` equal shares of the routed experts."""
    if cfg.moe is None:
        raise ValueError(f"{cfg.name} has no experts to share")
    if cfg.moe.n_routed_experts % n_shards or not 0 <= shard < n_shards:
        raise ValueError(f"no expert shard {shard} of {n_shards} for "
                         f"{cfg.moe.n_routed_experts} experts")
    return dataclasses.replace(
        cfg, name=f"{cfg.name}:ep{n_shards}.{shard}",
        moe=dataclasses.replace(cfg.moe, n_expert_shards=n_shards,
                                expert_shard=shard))


def get_dit_config(arch: str):
    """DiT architecture id -> :class:`repro.models.dit.DiTConfig`."""
    return _load(_DIT_MODULES, arch, "dit arch")


def all_configs() -> dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}


def all_dit_configs() -> dict:
    return {a: get_dit_config(a) for a in DIT_ARCH_IDS}
