"""Plain float32 reference of the benchmark's federated Fed-Sophia rounds.

Written from the configuration files and the algorithm, and importing
nothing of the program.  The model is the architecture's module
(``bench/models/<arch>.py``: its parameter tree, initial weights,
logits and auxiliary loss); this module is the round around it: the
cross-entropy loss plus the model's auxiliary term, the
Gauss-Newton-Bartlett curvature estimate, the clipped Sophia step, and
int8 stochastic quantization of the downlink broadcast and the uplink
delta over the packed wire layout (``docs/wire-format.md``: leaves
flattened in pytree order, concatenated, zero-padded, one scale per row
of ``quant_block`` coordinates).

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
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: client-key salts of the two wire streams
DOWNLINK_SALT = 0xD0
UPLINK_SALT = 0xC0


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
    def of(cls, shapes_tree, cols: int) -> "Wire":
        """The layout of a tree of leaf shapes (a model module's
        ``param_shapes``), leaves in pytree order."""
        is_shape = lambda x: isinstance(x, tuple)
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            shapes_tree, is_leaf=is_shape)
        return cls(tuple(jax.tree_util.keystr(p) for p, _ in flat),
                   tuple(s for _, s in flat), treedef, cols)

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


def wire_of(model, cfg, m=None) -> Wire:
    """The wire of a configuration file under its model module, for the
    module's ``Model`` ``m`` (by default the file's)."""
    m = model.Model.from_config(cfg) if m is None else m
    return Wire.of(model.param_shapes(m), cfg["engine"]["quant_block"])


# ----------------------------------------------------------------- loss
def cross_entropy(logits, labels):
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


def loss_fn(model, p, m, batch):
    """The training loss: cross-entropy plus the model's auxiliary
    term, as the program's task adds it."""
    logits, aux = model.logits_and_aux(p, m, batch["tokens"])
    return cross_entropy(logits, batch["labels"]) + aux


def sampled_loss_fn(model, p, m, batch, key):
    """The GNB inner loss: cross-entropy against labels drawn from the
    model's own (stopped) distribution, plus the auxiliary term."""
    logits, aux = model.logits_and_aux(p, m, batch["tokens"])
    y = jax.random.categorical(key, jax.lax.stop_gradient(logits), axis=-1)
    return cross_entropy(logits, y) + aux


# ---------------------------------------------------------------- round
def _up(x):
    return x.astype(F32)


def quantize(x, key, qmax):
    """Unbiased int8 stochastic quantization, one scale per row."""
    s = jnp.max(jnp.abs(x), axis=1, keepdims=True) / qmax
    safe = jnp.where(s > 0, s, 1.0)
    u = jax.random.uniform(key, x.shape)
    return jnp.clip(jnp.floor(x / safe + u), -qmax, qmax) * s


def client_round(model, m, hp: Hyper, wire: Wire):
    """For the model module ``model`` and its ``Model`` ``m``: jitted
    (theta, replica, mom, curv, batch, client key, r, lr) -> (start,
    delta_hat, mom, curv, mean loss, first-gradient leaf sq).
    ``theta`` is the server model, ``replica`` what this client last
    received; both packed, in the dtypes they are stored in."""
    def grad(t, batch):
        return jax.value_and_grad(
            lambda f: loss_fn(model, wire.unpack(f), m, batch))(t)

    def gnb(t, batch, key):
        n = batch["labels"].size
        g = jax.grad(
            lambda f: sampled_loss_fn(model, wire.unpack(f), m, batch,
                                     key))(t)
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

    def __init__(self, model, cfg, traffic, lower=None):
        """``model``: the architecture's module.  ``lower``: a dtype
        name; the control then stores the model in it and reads every
        matrix product's operands rounded to it."""
        self.model = model
        self.m = model.Model.from_config(cfg)
        self.hp = Hyper.from_files(cfg, traffic)
        if lower is not None:
            self.m = dataclasses.replace(self.m, compute=jnp.dtype(lower))
            self.hp = dataclasses.replace(self.hp,
                                          state_dtype=jnp.dtype(lower))
        self.wire = wire_of(model, cfg, self.m)
        self.clients = traffic["clients"]
        self._client = client_round(model, self.m, self.hp, self.wire)
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
        theta = jax.jit(lambda k: self.wire.pack(
            self.model.init_params(k, self.m))
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
