"""A test fixture: MiniCPM-2B with an untied output head, as an
architecture of its own (``arch`` ``minicpm-2b-untied``).

``bench/tests/test_bench_spec.py`` copies it to
``bench/models/minicpm_2b_untied.py`` of a temporary checkout, to show
that a new architecture needs only new files.  It takes MiniCPM-2B's
module from the same checkout and changes what an untied head changes:
a leaf ``lm_head`` of (D, Vp) drawn N(0, 1/D) in the model's dtype from
the init key's second split (the program's key schedule), the logits
through it, and the program's ``tie_embeddings``.
"""
from __future__ import annotations

import dataclasses
import math
import os

from bench import models

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
base = models.load("minicpm-2b", ROOT)

tiny = base.tiny
# the untied head makes the same V x D products a token as the tied one
train_flops_per_token = base.train_flops_per_token


def program_config(cfg):
    return base.program_config(dict(cfg, arch="minicpm-2b"))


@dataclasses.dataclass(frozen=True)
class Model(base.Model):
    @classmethod
    def from_config(cls, cfg) -> "Model":
        m = base.Model.from_config(dict(cfg, tie_word_embeddings=True))
        return cls(**{f.name: getattr(m, f.name)
                      for f in dataclasses.fields(m)})


def param_shapes(m: Model):
    return dict(base.param_shapes(m), lm_head=(m.D, m.Vp))


def init_params(key, m: Model):
    import jax
    _, k_head, _ = jax.random.split(key, 3)
    head = base._normal(k_head, (m.D, m.Vp), 1.0 / math.sqrt(m.D), m.dtype)
    return dict(base.init_params(key, m), lm_head=head.astype(base.F32))


def logits_and_aux(p, m: Model, tokens):
    logits = base.mm(m, base.hidden(p, m, tokens), p["lm_head"])
    return base.masked(m, logits), 0.0
