"""Builds the jit-able entry point + arg structures + shardings for every
(architecture x input-shape x mesh) combination.

Everything returns ShapeDtypeStruct stand-ins (no device allocation) so the
dry-run can .lower().compile() the production meshes on CPU placeholders.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs.base import (CommConfig, FedConfig, INPUT_SHAPES,
                                ModelConfig, ShapeConfig)
from repro.core.fed import FedEngine
from repro.launch.mesh import client_axes, data_axes
from repro.models import transformer as T
from repro.sharding import specs as S

FULL_ATTENTION_ARCHS = {
    "qwen3-moe-235b-a22b", "minicpm-2b", "qwen3-14b",
    "deepseek-v2-lite-16b", "qwen2-vl-2b", "chatglm3-6b",
}
ENCODER_ONLY_ARCHS = {"hubert-xlarge"}


def applicable(arch_id: str, shape_name: str) -> Tuple[bool, str]:
    """Shape/arch skip rules (recorded in DESIGN.md §5)."""
    shape = INPUT_SHAPES[shape_name]
    if arch_id in ENCODER_ONLY_ARCHS and shape.kind == "decode":
        return False, "encoder-only: no decode step"
    if shape_name == "long_500k" and arch_id in FULL_ATTENTION_ARCHS:
        return False, "pure full-attention arch: 500k decode needs sub-quadratic mixing"
    return True, ""


@dataclass
class Bundle:
    """Everything the dry-run / launcher needs for one combination."""
    fn: Callable
    args: tuple                 # ShapeDtypeStructs (or concrete arrays)
    in_shardings: tuple
    out_shardings: Any
    meta: Dict[str, Any]


def _sds(tree, shardings=None):
    """pytree -> ShapeDtypeStruct pytree (optionally sharding-annotated)."""
    if shardings is None:
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shardings)


def _replicated(mesh, tree):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


def resolve_fed(arch_id: str, mesh, *, local_iters: int = 10) -> FedConfig:
    over = dict(configs.get_fed_overrides(arch_id))
    strategy = over.pop("strategy", "parallel")
    caxes = client_axes(mesh)
    csize = 1
    for a in caxes:
        csize *= mesh.shape[a]
    if strategy == "parallel":
        num_clients = csize
    else:
        num_clients = 8
    # sequential runs give each client the full mesh; persistent per-client
    # EMAs would cost C x 2|theta| HBM -> stateless mode (DESIGN.md §4)
    persistent = over.pop("persistent_client_state",
                          strategy != "sequential")
    return FedConfig(num_clients=num_clients, local_iters=local_iters,
                     optimizer="fed_sophia", strategy=strategy,
                     persistent_client_state=persistent,
                     tau=10, **over)


# --------------------------------------------------------------------------
# builders
# --------------------------------------------------------------------------

def _batch_struct(cfg: ModelConfig, lead_dims: tuple, seq: int):
    dtype = T.param_dtype(cfg)
    out = {}
    if cfg.embedding_inputs:
        out["embeds"] = jnp.zeros(lead_dims + (seq, cfg.d_model), dtype)
    else:
        out["tokens"] = jnp.zeros(lead_dims + (seq,), jnp.int32)
    return out


def _apply_overrides(cfg: ModelConfig, over: Optional[dict]) -> ModelConfig:
    if not over:
        return cfg
    typed = {}
    for k, v in over.items():
        cur = getattr(cfg, k)
        if isinstance(v, str) and cur is not None:
            if isinstance(cur, bool):
                v = v.lower() in ("1", "true", "yes")
            elif isinstance(cur, (int, float, str)):
                v = type(cur)(v)
        typed[k] = v
    return dataclasses.replace(cfg, **typed)


def build_train(arch_id: str, mesh, *, reduced: bool = False,
                local_iters: int = 10, optimizer: str = "fed_sophia",
                use_pallas: bool = False, fsdp_gather: bool = True,
                cfg_overrides: Optional[dict] = None,
                fed_overrides: Optional[dict] = None,
                comm: Optional[CommConfig] = None,
                packed_state: bool = False) -> Bundle:
    cfg = _apply_overrides(configs.get_model_config(arch_id), cfg_overrides)
    shape = INPUT_SHAPES["train_4k"]
    seq, gbatch = shape.seq_len, shape.global_batch
    if reduced:
        cfg = cfg.reduced(d_model=128)
        seq, gbatch = 32, 16
    fed = resolve_fed(arch_id, mesh, local_iters=local_iters)
    if optimizer != "fed_sophia":
        fed = dataclasses.replace(fed, optimizer=optimizer)
    if fed_overrides:
        typed = {k: (type(getattr(fed, k))(v)
                     if isinstance(v, str) and not isinstance(
                         getattr(fed, k), (bool, str)) else v)
                 for k, v in fed_overrides.items()}
        fed = dataclasses.replace(fed, **typed)
    if use_pallas:
        fed = dataclasses.replace(fed, use_pallas=True)
    if comm is not None:
        fed = dataclasses.replace(fed, comm=comm)
    task = T.LMTask(cfg)
    seq_fed0 = fed.strategy == "sequential"
    p_struct = jax.eval_shape(lambda k: T.init_lm(k, cfg),
                              jax.random.PRNGKey(0))
    gather_sh = None
    if seq_fed0 and fsdp_gather:
        # FSDP storage sharding is (model x data); every USE of the params
        # must see the model-only sharding or GSPMD replicates the
        # batch-sharded activations over data instead (see FedEngine).
        gather_sh = S.param_shardings(cfg, mesh, p_struct, fsdp_axes=None)
    # the local loops carry each client's model and Sophia m/h as leaves,
    # stored as the params are: over the model axes, and under FSDP the
    # data axes too
    carry_sh = S.param_shardings(
        cfg, mesh, p_struct,
        fsdp_axes=data_axes(mesh) if seq_fed0 else None)
    engine = FedEngine(task, fed, gather_shardings=gather_sh,
                       carry_shardings=carry_sh)

    C = fed.num_clients
    caxes = client_axes(mesh)
    daxes = data_axes(mesh)
    seq_fed = fed.strategy == "sequential"
    dsize = 1
    for a in daxes:
        dsize *= mesh.shape[a]
    # sequential shards the per-client batch over the data axes
    b = max(gbatch // C, dsize if seq_fed else 1)

    state = jax.eval_shape(engine.init, jax.random.PRNGKey(0))
    p_sh = S.param_shardings(cfg, mesh, state["params"],
                             fsdp_axes=daxes if seq_fed else None)
    if packed_state:
        # packed-resident mode: the state ships to the device with
        # params (and FedOpt m/v) already in wire layout — the round
        # neither packs nor unpacks them
        state = jax.eval_shape(engine.pack_state, state)
    st_sh = {"params": p_sh,
             "round": NamedSharding(mesh, P())}
    # ALL per-client engine state lives in wire layout (C, rows, cols)
    # — the Sophia m/h EMAs, the uplink EF residuals, the per-client
    # downlink model replicas and the server-side downlink EF — so one
    # sharding rule covers everything: clients over the client axes,
    # and the cols axis over the remaining (model) axes in parallel
    # mode — the wire-layout analogue of the old per-leaf param
    # shardings, so the 2 x C x |theta| optimizer state is never
    # replicated across the model axes.  cols (= quant_block, a power
    # of two) is the divisible axis; rows = ceil(n/cols) generally is
    # not.  Under sequential/FSDP, cols shard over the data axes
    # instead (ZeRO-style, mirroring the params' fsdp_axes — note
    # resolve_fed disables persistent client state for sequential, so
    # client_opt only exists there under an explicit override).
    maxes = tuple(n for n in mesh.axis_names if n not in daxes)
    wire_sh = NamedSharding(
        mesh, P(caxes, None, maxes or None) if not seq_fed
        else P(None, None, daxes))
    if "client_opt" in state:
        from repro.core.sophia import SophiaState
        st_sh["client_opt"] = SophiaState(m=wire_sh, h=wire_sh)
    from repro.comm.downlink import EF_KEY, MODEL_KEY
    for k in ("comm_ef", MODEL_KEY, EF_KEY):
        if k in state:
            st_sh[k] = wire_sh
    if packed_state:
        # the flat analogue of the per-leaf param shardings: the 2D
        # (rows, cols) buffer shards its cols (= quant_block, a power
        # of two) over the model axes in parallel mode, or over the
        # data axes under sequential/FSDP (ZeRO-style) — one rule for
        # params and the FedOpt server state alike
        flat_sh = NamedSharding(
            mesh, P(None, maxes or None) if not seq_fed
            else P(None, daxes))
        st_sh["params"] = flat_sh
        if "server_opt" in state:
            st_sh["server_opt"] = {k: flat_sh
                                   for k in state["server_opt"]}

    batch = _batch_struct(cfg, (C, b), seq)
    batch["labels"] = jnp.zeros((C, b, seq), jnp.int32)
    if seq_fed:
        b_sh = jax.tree.map(
            lambda x: NamedSharding(
                mesh, P(None, daxes, *([None] * (x.ndim - 2)))), batch)
    else:
        b_sh = jax.tree.map(
            lambda x: NamedSharding(
                mesh, P(caxes, *([None] * (x.ndim - 1)))), batch)

    rng = jax.random.PRNGKey(0)
    args = (_sds(state), _sds(batch), _sds(rng))
    in_sh = (st_sh, b_sh, NamedSharding(mesh, P()))
    out_sh = (st_sh, None)
    meta = dict(arch=arch_id, shape="train_4k", entry="train_round",
                num_clients=C, per_client_batch=b, strategy=fed.strategy,
                seq=seq, cfg=cfg, fed=fed, packed_state=packed_state)
    return Bundle(engine.round, args, in_sh, out_sh, meta)


def _serve_cfg(arch_id: str, shape_name: str, reduced: bool,
               cfg_overrides: Optional[dict] = None) -> ModelConfig:
    cfg = _apply_overrides(configs.get_model_config(arch_id), cfg_overrides)
    if reduced:
        cfg = cfg.reduced(d_model=128)
    if shape_name == "long_500k" and "global" in cfg.block_pattern:
        cfg = dataclasses.replace(cfg, long_mode_swa_only=True)
    return cfg


def _serve_param_shardings(arch_id, cfg, mesh):
    # qwen3-moe's 470GB of bf16 experts exceed model-axis-only sharding ->
    # 2D weight sharding for serving. Everything else: pure TP.
    fsdp = data_axes(mesh) if arch_id == "qwen3-moe-235b-a22b" else None
    params = jax.eval_shape(lambda k: T.init_lm(k, cfg),
                            jax.random.PRNGKey(0))
    return params, S.param_shardings(cfg, mesh, params, fsdp_axes=fsdp)


def build_prefill(arch_id: str, mesh, *, reduced: bool = False,
                  cfg_overrides: Optional[dict] = None) -> Bundle:
    cfg = _serve_cfg(arch_id, "prefill_32k", reduced, cfg_overrides)
    shape = INPUT_SHAPES["prefill_32k"]
    B, seq = shape.global_batch, shape.seq_len
    if reduced:
        B, seq = 4, 64
    params, p_sh = _serve_param_shardings(arch_id, cfg, mesh)
    daxes = data_axes(mesh)
    batch = _batch_struct(cfg, (B,), seq)
    b_sh = jax.tree.map(
        lambda x: NamedSharding(mesh, P(daxes, *([None] * (x.ndim - 1)))),
        batch)

    def prefill(params, batch):
        logits, cache, _ = T.forward(params, cfg, batch, want_cache=True,
                                     remat=False)
        return logits, cache

    cache_struct = jax.eval_shape(
        lambda p, b: prefill(p, b)[1], params, batch)
    c_sh = S.cache_shardings(cfg, mesh, cache_struct, batch_axes=daxes)
    out_sh = (NamedSharding(mesh, P(daxes, None, "model")), c_sh)
    args = (_sds(params), _sds(batch))
    meta = dict(arch=arch_id, shape="prefill_32k", entry="serve_prefill",
                batch=B, seq=seq, cfg=cfg)
    return Bundle(prefill, args, (p_sh, b_sh), out_sh, meta)


def build_decode(arch_id: str, shape_name: str, mesh, *,
                 reduced: bool = False,
                 cfg_overrides: Optional[dict] = None) -> Bundle:
    cfg = _serve_cfg(arch_id, shape_name, reduced, cfg_overrides)
    shape = INPUT_SHAPES[shape_name]
    B, seq = shape.global_batch, shape.seq_len
    if reduced:
        B, seq = 4, 64
    params, p_sh = _serve_param_shardings(arch_id, cfg, mesh)
    daxes = data_axes(mesh)
    batch = _batch_struct(cfg, (B,), 1)
    dsize = 1
    for a in daxes:
        dsize *= mesh.shape[a]
    batch_entry = daxes if (B % dsize == 0 and B >= dsize) else None
    b_sh = jax.tree.map(
        lambda x: NamedSharding(mesh,
                                P(batch_entry, *([None] * (x.ndim - 1)))),
        batch)
    cache = jax.eval_shape(lambda: T.init_cache(cfg, B, seq))
    c_sh = S.cache_shardings(cfg, mesh, cache, batch_axes=daxes)
    pos = jnp.zeros((), jnp.int32)

    def step(params, batch, cache, pos):
        return T.decode_step(params, cfg, batch, cache, pos)

    logit_sh = NamedSharding(mesh, P(batch_entry, None, "model"))
    args = (_sds(params), _sds(batch), _sds(cache), _sds(pos))
    in_sh = (p_sh, b_sh, c_sh, NamedSharding(mesh, P()))
    out_sh = (logit_sh, c_sh)
    meta = dict(arch=arch_id, shape=shape_name, entry="serve_step",
                batch=B, cache_len=seq, cfg=cfg)
    return Bundle(step, args, in_sh, out_sh, meta)


def build(arch_id: str, shape_name: str, mesh, *, reduced: bool = False,
          **kw) -> Bundle:
    ok, reason = applicable(arch_id, shape_name)
    if not ok:
        raise ValueError(f"skip {arch_id} x {shape_name}: {reason}")
    kind = INPUT_SHAPES[shape_name].kind
    if kind == "train":
        return build_train(arch_id, mesh, reduced=reduced, **kw)
    cfg_overrides = kw.pop("cfg_overrides", None)
    if kind == "prefill":
        return build_prefill(arch_id, mesh, reduced=reduced,
                             cfg_overrides=cfg_overrides)
    return build_decode(arch_id, shape_name, mesh, reduced=reduced,
                        cfg_overrides=cfg_overrides)
