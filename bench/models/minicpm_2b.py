"""MiniCPM-2B (openbmb/MiniCPM-2B-sft-bf16, arXiv:2404.06395): the
program's mapping, the plain reference of the model, its FLOP count and
its CPU test cut (the interface is in ``bench/models/__init__.py``).

The reference is the decoder-only model written from the configuration
file: scaled embedding, RMSNorm, rotary multi-head attention, SwiGLU,
depth-scaled residuals, tied head.  Every matrix product runs at
``Precision.HIGHEST`` in float32; the initial weights are drawn in the
model's ``torch_dtype``.
"""
from __future__ import annotations

import dataclasses
import inspect
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
#: logit of a padded vocabulary row (never sampled, never a label)
MASK = -1e9

#: configuration-file key -> `ModelConfig` field
MODEL_KEYS = {
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "scale_emb": "scale_emb",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
    "torch_dtype": "dtype",
}
#: what the CPU tests cut: widths, with the vocabulary cut by `tiny`
TINY = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=4,
            intermediate_size=256, dim_model_base=128)


# -------------------------------------------------------------- program
def program_config(cfg: Dict[str, Any]):
    """The program's `ModelConfig` for a configuration file: the
    architecture's preset, cut with ``with_depth``, then every mapped
    key of the file applied."""
    from repro import configs
    mc = configs.get_model_config(cfg["arch"]).with_depth(
        cfg["num_hidden_layers"])
    if mc.num_layers != cfg["num_hidden_layers"]:
        raise ValueError(f"{cfg['name']}: depth {cfg['num_hidden_layers']} "
                         f"is not a whole number of layer periods")
    over = {f: cfg[k] for k, f in MODEL_KEYS.items()}
    over["residual_scale"] = cfg["scale_depth"] / math.sqrt(
        cfg["assumed"]["residual_scale_layers"])
    if cfg["hidden_act"] != "silu" or mc.ffn_kind != "swiglu":
        raise ValueError(f"{cfg['name']}: the program's {cfg['arch']} is "
                         f"{mc.ffn_kind}, the file says {cfg['hidden_act']}")
    if cfg["hidden_size"] != cfg["dim_model_base"]:
        raise ValueError("the program does not divide the logits by "
                         "hidden_size / dim_model_base")
    if norm_eps() != cfg["rms_norm_eps"]:
        raise ValueError(f"{cfg['name']}: the program's RMSNorm eps is "
                         f"{norm_eps()}, the file says {cfg['rms_norm_eps']}")
    return dataclasses.replace(mc, **over)


def norm_eps() -> float:
    """The RMSNorm epsilon the program runs (it has no setting for it)."""
    from repro.models import layers
    return inspect.signature(layers.rms_norm).parameters["eps"].default


# ------------------------------------------------------------ reference
@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes of a configuration file, as the reference reads them."""
    D: int
    H: int
    KV: int
    F: int
    V: int
    L: int
    scale_emb: float
    residual_scale: float
    logit_div: float
    eps: float
    rope_theta: float
    dtype: Any
    pad_multiple: int
    #: where set, every matrix product reads its operands rounded to
    #: this dtype (the lower-precision control of the output check)
    compute: Any = None

    @property
    def hd(self) -> int:
        return self.D // self.H

    @property
    def Vp(self) -> int:
        m = self.pad_multiple
        return -(-self.V // m) * m

    @classmethod
    def from_config(cls, cfg: Dict[str, Any]) -> "Model":
        if cfg["hidden_act"] != "silu" or not cfg["tie_word_embeddings"]:
            raise ValueError("the reference models SwiGLU with a tied head")
        if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
            raise ValueError("the reference models multi-head attention")
        a = cfg["assumed"]
        return cls(
            D=cfg["hidden_size"], H=cfg["num_attention_heads"],
            KV=cfg["num_key_value_heads"], F=cfg["intermediate_size"],
            V=cfg["vocab_size"], L=cfg["num_hidden_layers"],
            scale_emb=float(cfg["scale_emb"]),
            residual_scale=cfg["scale_depth"] / math.sqrt(
                a["residual_scale_layers"]),
            logit_div=cfg["hidden_size"] / cfg["dim_model_base"],
            eps=float(cfg["rms_norm_eps"]),
            rope_theta=float(cfg["rope_theta"]),
            dtype=jnp.dtype(cfg["torch_dtype"]),
            pad_multiple=a["vocab_pad_multiple"])


def param_shapes(m: Model) -> Dict[str, Any]:
    """The parameter tree: per-layer weights stacked on a leading layer
    axis, (in, out) matrices."""
    D, F, L = m.D, m.F, m.L
    return {
        "blocks_0": {
            "ffn": {"w_down": (L, F, D), "w_gate": (L, D, F),
                    "w_up": (L, D, F)},
            "ln1": (L, D), "ln2": (L, D),
            "mixer": {"wk": (L, D, m.KV * m.hd), "wo": (L, m.H * m.hd, D),
                      "wq": (L, D, m.H * m.hd), "wv": (L, D, m.KV * m.hd)},
        },
        "embed": (m.Vp, D),
        "final_norm": (D,),
    }


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, F32) * scale).astype(dtype)


def init_params(key, m: Model):
    """Embedding N(0, 0.02); each matrix N(0, 1/fan_in); norms 1; the
    draws in ``m.dtype`` and then held as float32 values.  Keys: the
    init key splits into (embedding, unused head, layer stack); each
    layer's key splits into (attention, ffn, unused), attention's into
    (q, k, v, o) and the ffn's into (gate, up, down)."""
    D, F, dt = m.D, m.F, m.dtype
    k_embed, _, k_layers = jax.random.split(key, 3)

    def layer(k):
        k_att, k_ffn, _ = jax.random.split(k, 3)
        kq, kk, kv, ko = jax.random.split(k_att, 4)
        kg, ku, kd = jax.random.split(k_ffn, 3)
        sd, sf = 1.0 / math.sqrt(D), 1.0 / math.sqrt(m.H * m.hd)
        return {
            "ffn": {"w_down": _normal(kd, (F, D), 1.0 / math.sqrt(F), dt),
                    "w_gate": _normal(kg, (D, F), sd, dt),
                    "w_up": _normal(ku, (D, F), sd, dt)},
            "ln1": jnp.ones((D,), dt), "ln2": jnp.ones((D,), dt),
            "mixer": {"wk": _normal(kk, (D, m.KV * m.hd), sd, dt),
                      "wo": _normal(ko, (m.H * m.hd, D), sf, dt),
                      "wq": _normal(kq, (D, m.H * m.hd), sd, dt),
                      "wv": _normal(kv, (D, m.KV * m.hd), sd, dt)},
        }

    tree = {"blocks_0": jax.vmap(layer)(jax.random.split(k_layers, m.L)),
            "embed": _normal(k_embed, (m.Vp, D), 0.02, dt),
            "final_norm": jnp.ones((D,), dt)}
    return jax.tree.map(lambda x: x.astype(F32), tree)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate the two halves of each head (rotary on every dimension)."""
    S, hd = x.shape[-3], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _in(m: Model, x):
    return x if m.compute is None else x.astype(m.compute).astype(F32)


def mm(m: Model, a, b):
    """A matrix product as the reference runs it: float32 at HIGHEST,
    the operands rounded to ``m.compute`` first where it is set."""
    return jnp.matmul(_in(m, a), _in(m, b), precision=HI)


def _layer(m: Model, x, w):
    B, S = x.shape[:2]
    h = _rms(x, w["ln1"], m.eps)
    q, k, v = (mm(m, h, w["mixer"][n]).reshape(B, S, m.H, m.hd)
               for n in ("wq", "wk", "wv"))
    q, k = _rope(q, m.rope_theta), _rope(k, m.rope_theta)
    s = jnp.einsum("bqhd,bkhd->bhqk", _in(m, q), _in(m, k),
                   precision=HI) / math.sqrt(m.hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, MASK)
    o = jnp.einsum("bhqk,bkhd->bqhd", _in(m, jax.nn.softmax(s, -1)),
                   _in(m, v), precision=HI).reshape(B, S, m.H * m.hd)
    x = x + m.residual_scale * mm(m, o, w["mixer"]["wo"])
    h = _rms(x, w["ln2"], m.eps)
    f = jax.nn.silu(mm(m, h, w["ffn"]["w_gate"])) * mm(m, h,
                                                       w["ffn"]["w_up"])
    return x + m.residual_scale * mm(m, f, w["ffn"]["w_down"])


def hidden(p, m: Model, tokens):
    """tokens (B, S) -> the final normed hidden state (B, S, D) that
    the head reads.  Each layer is recomputed in the backward pass
    (memory only)."""
    x = p["embed"][tokens] * m.scale_emb
    layer = jax.checkpoint(lambda x, w: _layer(m, x, w))
    for li in range(m.L):
        x = layer(x, jax.tree.map(lambda a: a[li], p["blocks_0"]))
    return _rms(x, p["final_norm"], m.eps) / m.logit_div


def masked(m: Model, logits):
    """Padded vocabulary rows at ``MASK``."""
    return jnp.where(jnp.arange(m.Vp) < m.V, logits, MASK)


def logits_and_aux(p, m: Model, tokens):
    """tokens (B, S) -> (logits (B, S, Vp) through the tied head, padded
    rows at ``MASK``; auxiliary loss 0.0: the model has none)."""
    return masked(m, mm(m, hidden(p, m, tokens), p["embed"].T)), 0.0


# ----------------------------------------------------------------- work
def matmul_params(cfg) -> int:
    """Parameters that take part in a matrix product per token: every
    layer's attention and SwiGLU matrices, and the tied output head
    (the embedding lookup is not a product)."""
    D, F, L, V = (cfg["hidden_size"], cfg["intermediate_size"],
                  cfg["num_hidden_layers"], cfg["vocab_size"])
    hd = D // cfg["num_attention_heads"]
    att = D * hd * (2 * cfg["num_attention_heads"]
                    + 2 * cfg["num_key_value_heads"])
    return L * (att + 3 * D * F) + V * D


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward and backward FLOPs of one token at sequence length
    ``seq``: 6 per matrix parameter, plus causal attention's scores and
    weighted sum (2 x 2 x seq/2 x D per layer forward, times 3)."""
    D = cfg["num_attention_heads"] * (cfg["hidden_size"]
                                      // cfg["num_attention_heads"])
    return (6.0 * matmul_params(cfg)
            + 6.0 * cfg["num_hidden_layers"] * seq * D)


# ----------------------------------------------------------------- tiny
def tiny(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration at `TINY` widths, with at most 500 vocabulary
    rows, half that where the file holds a slice of the vocabulary."""
    sliced = "vocab_size" in cfg["reduced"]
    return dict(cfg, **TINY,
                vocab_size=min(cfg["vocab_size"], 500) // (2 if sliced
                                                           else 1))
