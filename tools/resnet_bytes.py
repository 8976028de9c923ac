"""Where do the ResNet step's HBM bytes go? Aggregates hlo_stats rows
(bytes ~= measured bw x self-time) by op-name bucket.

Usage: python tools/resnet_bytes.py [fused|pallas|plain]

``pallas`` additionally routes the fused units through the Pallas conv
kernel family (FLAGS_pallas_conv — ops/_pallas/conv.py). The top-3
byte-dominant conv shape classes this profile identified (r5, batch 256)
are recorded as ``RESNET50_TOP3_SHAPES`` in that module.
"""
import functools
import glob
import json
import os
import re
import shutil
import sys
import tempfile

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as paddle
from paddle_tpu.core import flags as _flags
from paddle_tpu.framework.functional import (functional_call, get_buffers,
                                             get_params)
from paddle_tpu.nn import functional as F
from paddle_tpu.nn import fused_conv_bn  # noqa: F401  (define flag)
from paddle_tpu.optimizer import Momentum
from paddle_tpu.vision.models import resnet50

mode = sys.argv[1] if len(sys.argv) > 1 else "plain"
_flags.set_flags({"fused_conv_bn": 1 if mode in ("fused", "pallas") else 0})
if mode == "pallas":
    from paddle_tpu.ops._pallas import conv as _pconv  # noqa: F401
    _flags.set_flags({"pallas_conv": 1})

batch, img, steps = 256, 224, 6
paddle.seed(0)
model = resnet50(data_format="NHWC")
model.train()
model.astype(paddle.bfloat16)
opt = Momentum(learning_rate=0.1, momentum=0.9, multi_precision=True)
params = get_params(model)
buffers = get_buffers(model)
opt_state = opt.init(params)


def loss_of(p, buf, x, y):
    out, new_buf = functional_call(model, p, x, buffers=buf, mutable=True,
                                   training=True)
    return F.cross_entropy(out.astype(jnp.float32), y,
                           reduction="mean"), new_buf


@functools.partial(jax.jit, donate_argnums=(0,))
def step(state, x, y):
    p, buf, st = state
    (loss, new_buf), grads = jax.value_and_grad(
        loss_of, has_aux=True)(p, buf, x, y)
    new_p, new_st = opt.apply_gradients(p, grads, st, 0.1)
    return loss, (new_p, new_buf, new_st)


rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((batch, img, img, 3)), jnp.bfloat16)
y = jnp.asarray(rng.integers(0, 1000, (batch,)), jnp.int32)
state = (params, buffers, opt_state)
loss, state = step(state, x, y)
loss, state = step(state, x, y)
float(loss)

tracedir = tempfile.mkdtemp(prefix="rn_bytes_")
with jax.profiler.trace(tracedir):
    for _ in range(steps):
        loss, state = step(state, x, y)
    float(loss)

from xprof.convert import raw_to_tool_data as rtd  # noqa: E402
xplane = glob.glob(os.path.join(
    sorted(glob.glob(os.path.join(tracedir, "plugins/profile/*")))[-1],
    "*.xplane.pb"))
data, _ = rtd.xspace_to_tool_data(xplane, "hlo_stats", {})
d = json.loads(data.decode() if isinstance(data, bytes) else data)
shutil.rmtree(tracedir, ignore_errors=True)
cols = [c["id"] for c in d["cols"]]
print("columns:", cols)
rows = [[c.get("v") for c in r["c"]] for r in d["rows"]]
i = {c: cols.index(c) for c in cols}

def g(r, name, default=0.0):
    idx = i.get(name)
    return r[idx] if idx is not None and r[idx] is not None else default

# shape-class bucket: the widest output tensor shape mentioned in the expr
SHAPE_RE = re.compile(r"(bf16|f32)\[([0-9,]+)\]")

def bucket(expr, cat):
    shapes = SHAPE_RE.findall(expr or "")
    best, bestn = "", 0
    for dt, s in shapes:
        dims = [int(v) for v in s.split(",") if v]
        n = int(np.prod(dims)) if dims else 0
        if n > bestn:
            bestn, best = n, f"{dt}[{s}]"
    return f"{cat:22s} {best}"

tot_ms = tot_gb = 0.0
agg = {}
for r in rows:
    ms = g(r, "total_self_time") / 1e3
    bw = g(r, "measured_memory_bw")      # GiB/s? assume GB/s
    gb = bw * (ms / 1e3)
    tot_ms += ms
    tot_gb += gb
    key = bucket(str(g(r, "hlo_op_expression", "")), str(g(r, "category", "")))
    a = agg.setdefault(key, [0.0, 0.0, 0])
    a[0] += ms; a[1] += gb; a[2] += int(g(r, "occurrences", 0))
print(f"mode={mode} total {tot_ms/steps:.2f} ms/step, "
      f"~{tot_gb/steps:.1f} GB/step (bw-derived)")
for key, (ms, gb, occ) in sorted(agg.items(), key=lambda kv: -kv[1][1])[:35]:
    print(f"  {gb/steps:7.2f} GB  {ms/steps:8.3f} ms  x{occ/steps:5.1f}  {key}")
