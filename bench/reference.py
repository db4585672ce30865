"""Plain float32 reference of the benchmark's federated Fed-Sophia rounds.

Written from the configuration files and the algorithm, and importing
nothing of the program: the decoder-only model (MiniCPM style: scaled
embedding, RMSNorm, rotary multi-head attention, SwiGLU, depth-scaled
residuals, tied head), its cross-entropy loss, the Gauss-Newton-Bartlett
curvature estimate, the clipped Sophia step, and int8 stochastic
quantization of the downlink broadcast and the uplink delta over the
packed wire layout (``docs/wire-format.md``: leaves flattened in pytree
order, concatenated, zero-padded, one scale per row of ``quant_block``
coordinates).  Every matrix product runs at ``Precision.HIGHEST``.

What the configuration states is kept: resident parameters and client
replicas are stored in ``state_dtype``, the Sophia moments in
``moment_dtype`` / ``hessian_dtype`` after every local step, and the
initial weights are drawn in the model's ``torch_dtype``.  The random
streams follow the benchmark's key schedule: the round key is folded
with the client index; the downlink, uplink and curvature-sampling keys
fold that client key with 0xD0, 0xC0 and the local step.  Departures
from a bit-for-bit replay of the program are deliberate and belong to
the comparison's tolerance: the forward and backward run in float32
where the program runs bfloat16, and the quantization scales are
float32.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
#: logit of a padded vocabulary row (never sampled, never a label)
MASK = -1e9
#: client-key salts of the two wire streams
DOWNLINK_SALT = 0xD0
UPLINK_SALT = 0xC0


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


@dataclasses.dataclass(frozen=True)
class Hyper:
    """The optimizer, the round and the wire, from the files."""
    lr: float
    beta1: float
    beta2: float
    rho: float
    eps: float
    weight_decay: float
    schedule: str
    total_rounds: int
    decay_frac: float
    local_iters: int
    tau: int
    cols: int
    qmax: int
    state_dtype: Any
    moment_dtype: Any
    hessian_dtype: Any

    @classmethod
    def from_files(cls, cfg, traffic) -> "Hyper":
        e = cfg["engine"]
        for k in ("compressor", "downlink_compressor"):
            if e[k] != "int8":
                raise ValueError(f"the reference models int8 wires, not "
                                 f"{k}={e[k]!r}")
        if e["optimizer"] != "fed_sophia":
            raise ValueError("the reference models fed_sophia")
        return cls(lr=e["lr"], beta1=e["beta1"], beta2=e["beta2"],
                   rho=e["rho"], eps=e["eps"],
                   weight_decay=e["weight_decay"], schedule=e["schedule"],
                   total_rounds=e["total_rounds"],
                   decay_frac=e["decay_frac"],
                   local_iters=traffic["local_iters"], tau=traffic["tau"],
                   cols=e["quant_block"], qmax=127,
                   state_dtype=jnp.dtype(e["state_dtype"]),
                   moment_dtype=jnp.dtype(e["moment_dtype"]),
                   hessian_dtype=jnp.dtype(e["hessian_dtype"]))

    def lr_at(self, r: int) -> float:
        """Warmup-stable-decay (MiniCPM): constant, then a linear decay
        to a tenth over the last ``decay_frac`` of the rounds."""
        if self.schedule == "const":
            return self.lr
        if self.schedule != "wsd":
            raise ValueError(self.schedule)
        start = self.total_rounds * (1.0 - self.decay_frac)
        t = min(max((r - start) / max(self.total_rounds * self.decay_frac,
                                      1), 0.0), 1.0)
        return self.lr * (1.0 - t * 0.9)


# --------------------------------------------------------------- layout
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


def layout(m: Model) -> Tuple[List[str], List[Tuple[int, ...]], Any]:
    """Leaf names and shapes in wire order, and the tree definition."""
    tree = param_shapes(m)
    is_shape = lambda x: isinstance(x, tuple)
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree,
                                                         is_leaf=is_shape)
    names = [jax.tree_util.keystr(p) for p, _ in flat]
    return names, [s for _, s in flat], treedef


@dataclasses.dataclass(frozen=True)
class Wire:
    """The packed (rows, cols) layout of one model."""
    names: Tuple[str, ...]
    shapes: Tuple[Tuple[int, ...], ...]
    treedef: Any
    cols: int

    @property
    def sizes(self) -> Tuple[int, ...]:
        return tuple(int(np.prod(s)) for s in self.shapes)

    @property
    def total(self) -> int:
        return sum(self.sizes)

    @property
    def rows(self) -> int:
        return -(-self.total // self.cols)

    @classmethod
    def of(cls, m: Model, cols: int) -> "Wire":
        names, shapes, treedef = layout(m)
        return cls(tuple(names), tuple(shapes), treedef, cols)

    def pack(self, tree):
        leaves = jax.tree_util.tree_leaves(tree)
        v = jnp.concatenate([x.reshape(-1).astype(F32) for x in leaves])
        v = jnp.pad(v, (0, self.rows * self.cols - self.total))
        return v.reshape(self.rows, self.cols)

    def unpack(self, flat):
        v = flat.reshape(-1)
        out, off = [], 0
        for sz, shp in zip(self.sizes, self.shapes):
            out.append(v[off:off + sz].reshape(shp))
            off += sz
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def leaf_sq(self, flat):
        """Per-leaf sum of squares of a packed buffer (float32), summed
        over any leading axes."""
        v = flat.astype(F32).reshape(flat.shape[:-2] + (-1,))
        out, off = [], 0
        for sz in self.sizes:
            out.append(jnp.sum(jnp.square(v[..., off:off + sz])))
            off += sz
        return jnp.stack(out)


# ---------------------------------------------------------------- model
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


def _mm(m: Model, a, b):
    return jnp.matmul(_in(m, a), _in(m, b), precision=HI)


def _layer(m: Model, x, w):
    B, S = x.shape[:2]
    h = _rms(x, w["ln1"], m.eps)
    q, k, v = (_mm(m, h, w["mixer"][n]).reshape(B, S, m.H, m.hd)
               for n in ("wq", "wk", "wv"))
    q, k = _rope(q, m.rope_theta), _rope(k, m.rope_theta)
    s = jnp.einsum("bqhd,bkhd->bhqk", _in(m, q), _in(m, k),
                   precision=HI) / math.sqrt(m.hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, MASK)
    o = jnp.einsum("bhqk,bkhd->bqhd", _in(m, jax.nn.softmax(s, -1)),
                   _in(m, v), precision=HI).reshape(B, S, m.H * m.hd)
    x = x + m.residual_scale * _mm(m, o, w["mixer"]["wo"])
    h = _rms(x, w["ln2"], m.eps)
    f = jax.nn.silu(_mm(m, h, w["ffn"]["w_gate"])) * _mm(m, h,
                                                         w["ffn"]["w_up"])
    return x + m.residual_scale * _mm(m, f, w["ffn"]["w_down"])


def logits_fn(p, m: Model, tokens):
    """tokens (B, S) -> logits (B, S, Vp), padded rows at ``MASK``.
    Each layer is recomputed in the backward pass (memory only)."""
    x = p["embed"][tokens] * m.scale_emb
    layer = jax.checkpoint(lambda x, w: _layer(m, x, w))
    for li in range(m.L):
        x = layer(x, jax.tree.map(lambda a: a[li], p["blocks_0"]))
    x = _rms(x, p["final_norm"], m.eps) / m.logit_div
    logits = _mm(m, x, p["embed"].T)
    return jnp.where(jnp.arange(m.Vp) < m.V, logits, MASK)


def cross_entropy(logits, labels):
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


def loss_fn(p, m: Model, batch):
    return cross_entropy(logits_fn(p, m, batch["tokens"]), batch["labels"])


def sampled_loss_fn(p, m: Model, batch, key):
    """The GNB inner loss: cross-entropy against labels drawn from the
    model's own (stopped) distribution."""
    logits = logits_fn(p, m, batch["tokens"])
    y = jax.random.categorical(key, jax.lax.stop_gradient(logits), axis=-1)
    return cross_entropy(logits, y)


# ---------------------------------------------------------------- round
def _up(x):
    return x.astype(F32)


def quantize(x, key, qmax):
    """Unbiased int8 stochastic quantization, one scale per row."""
    s = jnp.max(jnp.abs(x), axis=1, keepdims=True) / qmax
    safe = jnp.where(s > 0, s, 1.0)
    u = jax.random.uniform(key, x.shape)
    return jnp.clip(jnp.floor(x / safe + u), -qmax, qmax) * s


def client_round(m: Model, hp: Hyper, wire: Wire):
    """jitted (theta, replica, mom, curv, batch, client key, r, lr) ->
    (start, delta_hat, mom, curv, mean loss, first-gradient leaf sq).
    ``theta`` is the server model, ``replica`` what this client last
    received; both packed, in the dtypes they are stored in."""
    def grad(t, batch):
        return jax.value_and_grad(
            lambda f: loss_fn(wire.unpack(f), m, batch))(t)

    def gnb(t, batch, key):
        n = batch["labels"].size
        g = jax.grad(
            lambda f: sampled_loss_fn(wire.unpack(f), m, batch, key))(t)
        return n * g * g

    def run(theta, replica, mom, curv, batch, ckey, r, lr):
        theta, replica = _up(theta), _up(replica)
        start = replica + quantize(theta - replica,
                                   jax.random.fold_in(ckey, DOWNLINK_SALT),
                                   hp.qmax)

        def step(carry, j):
            t, mo, cu = carry
            loss, g = grad(t, batch)
            do_h = (r * hp.local_iters + j) % hp.tau == 0
            hh = jax.lax.cond(do_h,
                              lambda: gnb(t, batch,
                                          jax.random.fold_in(ckey, j)),
                              lambda: jnp.zeros_like(t))
            mo = hp.beta1 * _up(mo) + (1.0 - hp.beta1) * g
            cu = jnp.where(do_h, hp.beta2 * _up(cu) + (1.0 - hp.beta2) * hh,
                           _up(cu))
            t = t - lr * hp.weight_decay * t
            t = t - lr * jnp.clip(mo / jnp.maximum(cu, hp.eps), -hp.rho,
                                  hp.rho)
            return ((t, mo.astype(hp.moment_dtype),
                     cu.astype(hp.hessian_dtype)),
                    (loss, wire.leaf_sq(g)))

        (t, mom, curv), (losses, gsq) = jax.lax.scan(
            step, (start, mom, curv), jnp.arange(hp.local_iters))
        up = quantize(t - start, jax.random.fold_in(ckey, UPLINK_SALT),
                      hp.qmax)
        return start, up, mom, curv, jnp.mean(losses), gsq[0]

    return jax.jit(run)


class Reference:
    """The reference run of one cell: ``init`` then ``round`` r = 0, 1,
    ... over the same batches and keys the program was given."""

    def __init__(self, cfg, traffic, lower=None):
        """``lower``: a dtype name; the control then stores the model in
        it and reads every matrix product's operands rounded to it."""
        self.m = Model.from_config(cfg)
        self.hp = Hyper.from_files(cfg, traffic)
        if lower is not None:
            self.m = dataclasses.replace(self.m, compute=jnp.dtype(lower))
            self.hp = dataclasses.replace(self.hp,
                                          state_dtype=jnp.dtype(lower))
        self.wire = Wire.of(self.m, self.hp.cols)
        self.clients = traffic["clients"]
        self._client = client_round(self.m, self.hp, self.wire)
        C, dt = self.clients, self.hp.state_dtype

        @jax.jit
        def server(theta, ups, starts):
            """The server model moves by the mean decoded uplink plus
            the mean of what the clients received less the model."""
            theta = _up(theta)
            agg = sum(ups) / C + (sum(starts) / C - theta)
            return (theta + agg).astype(dt)
        self._server = server

    def init(self, key):
        hp, C = self.hp, self.clients
        theta = jax.jit(lambda k: self.wire.pack(init_params(k, self.m))
                        .astype(hp.state_dtype))(key)
        shape = theta.shape
        return {"theta": theta, "replica": [theta] * C,
                "mom": [jnp.zeros(shape, hp.moment_dtype)] * C,
                "curv": [jnp.zeros(shape, hp.hessian_dtype)] * C}

    def round(self, state, batches, key, r):
        """One synchronous round of every client; ``batches`` leaves
        carry the client axis first.  Returns (state, mean loss, the
        first client's first-step gradient leaf sq)."""
        hp, C = self.hp, self.clients
        lr = hp.lr_at(r)
        theta = state["theta"]
        starts, ups, moms, curvs, losses, gsq0 = [], [], [], [], [], None
        for i in range(C):
            batch = jax.tree.map(lambda a: a[i], batches)
            start, up, mo, cu, loss, gsq = self._client(
                theta, state["replica"][i], state["mom"][i],
                state["curv"][i], batch, jax.random.fold_in(key, i), r, lr)
            starts.append(start)
            ups.append(up)
            moms.append(mo)
            curvs.append(cu)
            losses.append(loss)
            gsq0 = gsq if gsq0 is None else gsq0
        new = {"theta": self._server(theta, ups, starts),
               "replica": [s.astype(hp.state_dtype) for s in starts],
               "mom": moms, "curv": curvs}
        return new, float(np.mean([float(x) for x in losses])), gsq0
