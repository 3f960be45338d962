"""Logical-axis sharding rules → PartitionSpecs, and the DeploymentConfig.

Every parameter in the model carries logical axis names (see
``models/common.py``); the rule table below maps logical names to mesh axes.
The rule table is PART OF THE DEPLOYMENT CONFIGURATION — i.e. it is a
dimension of the deployment Discovery Space and searchable by the paper's
machinery (see ``tuning/deployment.py``).

Default strategy (2-D "FSDP × TP", MaxText-style):
  * ``embed``  → ``data``   (ZeRO-3: parameters+optimizer sharded over DP)
  * ``heads`` / ``mlp`` / ``vocab`` / ``lru`` → ``model`` (tensor parallel)
  * batch     → (``pod``, ``data``); pod axis is pure DP over DCN
  * divisibility fallbacks per architecture (e.g. kv_heads=1 replicates KV;
    40 experts don't divide a 16-way model axis → experts replicated and the
    expert hidden dim TP-sharded instead).
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.attention import AttnOptions
from ..models.blocks import ModelOptions
from ..models.common import DTypePolicy
from ..models.config import ModelConfig
from ..models.moe import MoEOptions
from ..models.rglru import RGLRUOptions
from ..models.xlstm import XLSTMOptions

__all__ = ["DeploymentConfig", "default_deployment", "param_specs",
           "batch_specs", "cache_specs", "named_sharding_tree"]

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
           "float16": jnp.float16}


@dataclass(frozen=True)
class DeploymentConfig:
    """The deployment configuration — every field is a potential Discovery
    Space dimension."""

    rules: Tuple[Tuple[str, Optional[str]], ...]
    batch_axes: Tuple[str, ...] = ("data",)
    seq_axis: Optional[str] = None       # sequence sharding for prefill (SP)
    remat: str = "dots"                  # none | full | dots
    microbatches: int = 1
    attn_impl: str = "xla"
    attn_q_chunk: int = 512
    attn_kv_chunk: int = 512
    band_skip: bool = True
    moe_impl: str = "capacity"
    moe_capacity_factor: float = 1.25
    mlstm_chunk: int = 128
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    grad_compression: str = "none"       # none | int8_ef
    # cast fp32 params to compute dtype ONCE per step instead of inside
    # every microbatch (beyond-paper optimization found in §Perf: cuts
    # weight-stream traffic ~2.5× at microbatches=16)
    cast_params_once: bool = False
    # force query-head sharding inside attention even when heads don't
    # divide the model axis (GSPMD pads) — §Perf beyond-paper change that
    # un-replicates attention for llama4's 40 heads on a 16-way axis
    attn_shard_heads: Optional[str] = None

    # -- derived ---------------------------------------------------------------

    def rule(self, logical: Optional[str]) -> Optional[str]:
        if logical is None:
            return None
        for name, axis in self.rules:
            if name == logical:
                return axis
        return None

    def with_rule(self, logical: str, axis: Optional[str]) -> "DeploymentConfig":
        new = tuple((n, axis if n == logical else a) for n, a in self.rules)
        if logical not in [n for n, _ in self.rules]:
            new = new + ((logical, axis),)
        return replace(self, rules=new)

    def model_options(self) -> ModelOptions:
        return ModelOptions(
            attn=AttnOptions(impl=self.attn_impl, q_chunk=self.attn_q_chunk,
                             kv_chunk=self.attn_kv_chunk,
                             band_skip=self.band_skip,
                             shard_heads=self.attn_shard_heads,
                             shard_batch=tuple(self.batch_axes)),
            moe=MoEOptions(impl=self.moe_impl,
                           capacity_factor=self.moe_capacity_factor),
            rglru=RGLRUOptions(impl="xla"),
            xlstm=XLSTMOptions(chunk=self.mlstm_chunk),
            remat=self.remat,
            policy=DTypePolicy(param_dtype=_DTYPES[self.param_dtype],
                               compute_dtype=_DTYPES[self.compute_dtype]),
            act_sharding=(tuple(self.batch_axes), self.seq_axis),
        )

    def spec_for(self, logical_axes: Sequence[Optional[str]]) -> P:
        return P(*[self.rule(a) for a in logical_axes])


def default_deployment(cfg: ModelConfig, mesh: Mesh,
                       shape_kind: str = "train",
                       global_batch: int = 256, seq_len: int = 4096,
                       hbm_budget: float = 10e9) -> DeploymentConfig:
    """Architecture- and mesh-aware default deployment (the paper-faithful
    baseline configuration; the starting point of every deployment search).

    Microbatch count is chosen so the stacked per-layer activation residuals
    (carry bf16 + the fp32 copy XLA:CPU keeps for emulated-bf16 modules —
    6 B/elem worst case) fit the HBM budget alongside params+optimizer.
    """
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_n = axis_sizes.get("model", 1)
    data_n = axis_sizes.get("data", 1)
    dp = data_n * axis_sizes.get("pod", 1)

    def fits(n: int) -> bool:
        return n % model_n == 0

    rules = {
        "layers": None,
        "embed": "data" if cfg.d_model % data_n == 0 else None,
        "heads": "model" if fits(cfg.num_heads) else None,
        "kv_heads": "model" if fits(cfg.num_kv_heads) else None,
        "head_dim": None,
        "mlp": "model" if (cfg.d_ff == 0 or fits(cfg.d_ff)) else None,
        "mlp_in": None,
        "vocab": "model" if fits(cfg.vocab_size) else None,
        "experts": "model" if (cfg.num_experts and fits(cfg.num_experts)) else None,
        "experts_router": None,
        "moe_mlp": None,
        "lru": "model" if fits(cfg.resolved_lru_dim) else None,
        "lru_in": None,
        "heads_gate": None,
        "frontend": None,
    }
    # MoE fallback: if experts can't shard, TP the expert hidden dim.
    if cfg.num_experts and rules["experts"] is None:
        f = cfg.moe_d_ff or cfg.d_ff
        rules["moe_mlp"] = "model" if fits(f) else None
    # xLSTM blocks put their projections on 'mlp': 2d/4d/f widths
    if cfg.family == "ssm":
        rules["mlp"] = "model" if fits(2 * cfg.d_model) else None

    # batch axes: only mesh axes whose combined size divides the global
    # batch (long_500k has global_batch=1: batch replicated, parallelism
    # comes from the model axis alone)
    batch_axes = []
    prod = 1
    for a in ("pod", "data"):
        if a in axis_sizes and global_batch % (prod * axis_sizes[a]) == 0:
            batch_axes.append(a)
            prod *= axis_sizes[a]
    batch_axes = tuple(batch_axes)

    microbatches = 1
    if shape_kind == "train":
        local_batch = max(global_batch // dp, 1)
        tokens_local = local_batch * seq_len
        # stacked residual-stream carries: L × tokens × d × 6 B (bf16+fp32)
        resid = cfg.num_layers * tokens_local * cfg.d_model * 6
        microbatches = 1
        while resid / microbatches > hbm_budget and microbatches < local_batch:
            microbatches *= 2
        microbatches = min(microbatches, local_batch)

    return DeploymentConfig(
        rules=tuple(sorted(rules.items())),
        batch_axes=batch_axes,
        microbatches=microbatches,
    )


# ---------------------------------------------------------------------------
# spec trees
# ---------------------------------------------------------------------------


def param_specs(logical_tree, deployment: DeploymentConfig):
    """Map the model's logical-axes tree to a PartitionSpec tree."""
    if isinstance(logical_tree, tuple):
        return deployment.spec_for(logical_tree)
    return {k: param_specs(v, deployment) for k, v in logical_tree.items()}


def batch_specs(cfg: ModelConfig, deployment: DeploymentConfig,
                kind: str = "train") -> dict:
    """PartitionSpecs for a training/prefill/decode input batch."""
    b = P(deployment.batch_axes if len(deployment.batch_axes) != 1
          else deployment.batch_axes[0])
    bt = tuple(deployment.batch_axes)
    s = deployment.seq_axis
    out = {}
    if cfg.uses_tokens:
        out["tokens"] = P(bt, s)
    else:
        out["embeds"] = P(bt, s, None)
    if kind == "train":
        out["labels"] = P(bt, s)
    return out


def _cache_leaf_specs(kind: str, cfg: ModelConfig, deployment: DeploymentConfig,
                      stacked: bool = True):
    bt = tuple(deployment.batch_axes)
    kv_axis = deployment.rule("kv_heads")
    cache_seq_axis = None
    if kv_axis is None:
        # heads won't shard: split the cache length instead (flash-decode
        # style split-KV) so decode attention parallelizes over the model axis
        cache_seq_axis = deployment.rule("heads") or "model"
    lru = deployment.rule("lru")
    mlp = deployment.rule("mlp")
    if kind in ("attn", "moe"):
        spec = {"k": P(bt, cache_seq_axis, kv_axis, None),
                "v": P(bt, cache_seq_axis, kv_axis, None)}
    elif kind == "rglru":
        spec = {"h": P(bt, lru), "conv": P(bt, None, lru)}
    elif kind == "mlstm":
        h = deployment.rule("heads")
        spec = {"C": P(bt, h, None, None), "n": P(bt, h, None), "m": P(bt, h)}
    elif kind == "slstm":
        spec = {k: P(bt, None) for k in ("c", "n", "m", "h")}
    else:
        raise ValueError(kind)
    if stacked:
        spec = jax.tree.map(lambda p: P(None, *p), spec,
                            is_leaf=lambda x: isinstance(x, P))
    return spec


def cache_specs(cfg: ModelConfig, deployment: DeploymentConfig) -> dict:
    """PartitionSpec tree matching ``LMModel.init_cache`` structure."""
    out = {}
    for si, stage in enumerate(cfg.stages):
        stage_spec = {}
        for i, spec in enumerate(stage.superblock):
            stage_spec[f"l{i}"] = _cache_leaf_specs(spec.kind, cfg, deployment)
        out[f"stage{si}"] = stage_spec
    return out


def named_sharding_tree(spec_tree, mesh: Mesh):
    return jax.tree.map(lambda p: NamedSharding(mesh, p), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
