"""The local loop's leaf-layout carry keeps the packed state's sharding
(tier-1, compile-only): the launcher's packed train round, compiled on
a simulated 4 x 2 (data x model) mesh, must hold each client's carried
theta/m/h with no more bytes per device than the wire-layout buffers
it was unpacked from.  Unpacking a cols-sharded (rows, cols) buffer
into leaves whose width does not divide the shard (here 192 against
512) hands GSPMD no sharding to keep, so without the engine's
`carry_shardings` the carry would be replicated over the model axis
(or, under FSDP, the data axes).  Runs in a subprocess: the placeholder
device count must be set before jax initializes."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r'''
import dataclasses, re, sys
import jax
from repro.configs.base import INPUT_SHAPES
from repro.launch import api
from repro.launch.mesh import make_small_mesh

strategy = sys.argv[1]
INPUT_SHAPES["train_4k"] = dataclasses.replace(
    INPUT_SHAPES["train_4k"], seq_len=32, global_batch=8)
mesh = make_small_mesh()
fed = ({"strategy": "sequential", "num_clients": 8,
        "persistent_client_state": False}
       if strategy == "sequential" else None)
b = api.build_train(
    "minicpm-2b", mesh, local_iters=3, packed_state=True,
    cfg_overrides={"num_layers": 2, "d_model": 192, "num_heads": 4,
                   "num_kv_heads": 4, "head_dim": 48, "d_ff": 480,
                   "vocab_size": 1000},
    fed_overrides=fed)
with mesh:
    txt = jax.jit(b.fn, in_shardings=b.in_shardings,
                  out_shardings=b.out_shardings,
                  donate_argnums=(0,)).lower(*b.args).compile().as_text()
# the local scan: the innermost of the round's own scans (the model's
# layer scans and the GNB's run inside a fed.* phase, the rng's loops
# inside a vmap)
own = re.compile(r"jit\(round\)(/while/body/closed_call)*/while")
loops = [(m.group(2), m.group(1)) for m in re.finditer(
    r"= \((.*?)\) while\(.*?op_name=\"([^\"]*)\"", txt)
    if own.fullmatch(m.group(2))]
shape = max(loops, key=lambda l: len(l[0]))[1]
carry = 0
for dims in re.findall(r"\bf32\[([\d,]+)\]", shape):
    n = 1
    for d in dims.split(","):
        n *= int(d)
    carry += 4 * n
spec = b.fn.__self__.runtime_for(b.args[0]["params"]).spec
shards = mesh.shape["model"] * (
    mesh.shape["data"] if strategy == "sequential" else 1)
print("carry_bytes", carry, "wire_bytes", 3 * 4 * spec.padded // shards)
'''


@pytest.mark.parametrize("strategy", ["parallel", "sequential"])
def test_local_carry_stays_sharded(strategy):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT, strategy],
                       capture_output=True, text=True, timeout=600,
                       env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
    words = r.stdout.split()
    carry = int(words[words.index("carry_bytes") + 1])
    wire = int(words[words.index("wire_bytes") + 1])
    # the norms' vectors are stored replicated: a few KiB over
    assert carry <= 1.05 * wire, r.stdout
