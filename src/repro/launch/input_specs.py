"""ShapeDtypeStruct stand-ins + shardings for every (arch x shape x step).

Nothing here allocates device memory: parameters, optimizer states, and KV
caches are built with ``jax.eval_shape`` over the real init functions, so a
program lowered from them is the exact production program.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import InputShape
from repro.core import peft as peft_lib
from repro.launch.mesh import data_axes
from repro.models import encdec
from repro.models.registry import init_params
from repro.models.transformer import init_caches
from repro.optim import adamw_init
from repro.sharding import specs as sharding_specs


def _struct(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def eval_param_shapes(cfg):
    return jax.eval_shape(partial(init_params, cfg=cfg), jax.random.PRNGKey(0))


def eval_peft_shapes(cfg, peft_cfg):
    return jax.eval_shape(partial(peft_lib.init_peft, cfg=cfg, peft_cfg=peft_cfg), jax.random.PRNGKey(0))


def eval_cache_shapes(cfg, batch: int, max_len: int):
    return jax.eval_shape(lambda: init_caches(cfg, batch, max_len))


def _modality_extras(cfg, batch: int):
    extras = {}
    if cfg.modality == "vision":
        extras["patches"] = jax.ShapeDtypeStruct(
            (batch, cfg.frontend_seq, cfg.d_model), jnp.dtype(cfg.dtype)
        )
    if cfg.modality == "audio":
        extras["frames"] = jax.ShapeDtypeStruct(
            (batch, cfg.frontend_seq, cfg.d_model), jnp.dtype(cfg.dtype)
        )
    return extras


def _batch_axes_size(mesh) -> int:
    n = 1
    for a in data_axes(mesh):
        n *= mesh.shape[a]
    return n


def train_inputs(cfg, peft_cfg, shape: InputShape, mesh, *, fsdp: bool = False) -> Tuple[tuple, tuple]:
    """(arg structs, in_shardings specs) for ``train_step``."""
    sharding_specs.set_mesh_axis_sizes(mesh)
    tp = mesh.shape["model"]
    b_axes = data_axes(mesh)

    base = eval_param_shapes(cfg)
    peft = eval_peft_shapes(cfg, peft_cfg)
    opt = jax.eval_shape(adamw_init, peft)
    batch = {
        "tokens": jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len + 1), jnp.int32),
        **_modality_extras(cfg, shape.global_batch),
    }
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)

    base_s = sharding_specs.param_specs(base, tp, fsdp_axes=b_axes if fsdp else ())
    peft_s = sharding_specs.peft_specs(peft)
    opt_s = {"m": peft_s, "v": peft_s, "count": P()}
    bspec = sharding_specs.batch_spec(b_axes, 2)
    batch_s = {k: (bspec if v.ndim == 2 else sharding_specs.batch_spec(b_axes, v.ndim)) for k, v in batch.items()}
    rng_s = P()
    args = (base, peft, opt, batch, rng)
    shardings = (base_s, peft_s, opt_s, batch_s, rng_s)
    return args, shardings


def _cast_params(params, dtype):
    """Serving weights dtype (bf16 deployment: halves resident bytes)."""
    import numpy as np

    def cast(x):
        if np.issubdtype(x.dtype, np.floating):
            return jax.ShapeDtypeStruct(x.shape, jnp.dtype(dtype))
        return x

    return jax.tree.map(cast, params)


def prefill_inputs(cfg, shape: InputShape, mesh, *, weights_dtype: str = "float32") -> Tuple[tuple, tuple]:
    sharding_specs.set_mesh_axis_sizes(mesh)
    tp = mesh.shape["model"]
    b_axes = data_axes(mesh)
    b = shape.global_batch

    cache_len = shape.seq_len + (cfg.frontend_seq if cfg.modality == "vision" else 0)
    params = _cast_params(eval_param_shapes(cfg), weights_dtype)
    caches = eval_cache_shapes(cfg, b, cache_len)
    batch = {
        "tokens": jax.ShapeDtypeStruct((b, shape.seq_len), jnp.int32),
        **_modality_extras(cfg, b),
    }
    params_s = sharding_specs.param_specs(params, tp)
    caches_s = sharding_specs.cache_specs(caches, b_axes, tp)
    batch_s = {
        k: sharding_specs.batch_spec(b_axes, v.ndim) for k, v in batch.items()
    }
    return (params, batch, caches), (params_s, batch_s, caches_s)


def serve_inputs(cfg, shape: InputShape, mesh, *, weights_dtype: str = "float32", expert_shard: str = "auto") -> Tuple[tuple, tuple]:
    """Decode: ONE new token against a cache of ``seq_len``."""
    sharding_specs.set_mesh_axis_sizes(mesh)
    tp = mesh.shape["model"]
    b_axes = data_axes(mesh)
    b = shape.global_batch
    n_data = _batch_axes_size(mesh)
    shard_seq = b < n_data  # long_500k: B=1 -> sequence-shard the cache

    # SWA archs hold only a window-sized ring buffer (init_layer_cache caps);
    # VLM caches cover the patch prefix too
    cache_len = shape.seq_len + (cfg.frontend_seq if cfg.modality == "vision" else 0)
    params = _cast_params(eval_param_shapes(cfg), weights_dtype)
    caches = eval_cache_shapes(cfg, b, cache_len)
    token = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    params_s = sharding_specs.param_specs(params, tp, expert_shard=expert_shard)
    caches_s = sharding_specs.cache_specs(
        caches, b_axes, tp, shard_seq_on_data=shard_seq
    )
    token_s = sharding_specs.batch_spec(b_axes, 2) if not shard_seq else P()
    args = [params, token, pos, caches]
    shardings = [params_s, token_s, P(), caches_s]

    if cfg.is_encoder_decoder:
        enc_kvs = jax.eval_shape(
            lambda p, e: encdec.encoder_cross_kvs(p, cfg, e),
            params,
            jax.ShapeDtypeStruct((b, cfg.frontend_seq, cfg.d_model), jnp.dtype(cfg.dtype)),
        )
        args.append(enc_kvs)
        shardings.append(sharding_specs.cache_specs(enc_kvs, b_axes, tp))
    return tuple(args), tuple(shardings)
