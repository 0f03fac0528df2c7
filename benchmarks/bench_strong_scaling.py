"""Paper Fig. 3/4: strong scaling — fixed problem size, growing shard count.

Each shard count runs in a SUBPROCESS (XLA pins the device count at init),
generating the same graph on nb = 1, 2, 4, 8 fake devices and timing the
total + per-phase cost.  The paper's observation that small scales stop
scaling early (scale-16 saturates at 2 nodes) reproduces as fixed per-shard
overheads dominating.

The children run with JAX_PLATFORMS=cpu: a CPU emulation of multi-node
scaling that never contends for an accelerator, and whose rows name the
platform."""

from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import print_table, save_json

_CHILD = r"""
import os, sys, json, time
import jax
import jax.numpy as jnp
from repro.core.types import GraphConfig
from repro.core.pipeline import generate_edges
from repro.core.shuffle import distributed_shuffle
from repro.core.relabel import relabel_ring
from repro.core.redistribute import redistribute_sorted
from repro.core.csr import build_csr_sorted
from repro.distributed.collectives import flat_mesh

scale, nb = int(sys.argv[1]), int(sys.argv[2])
cfg = GraphConfig(scale=scale, nb=nb, capacity_factor=4.0)
mesh = flat_mesh(nb)

def t(fn):
    fn_out = fn()
    jax.block_until_ready(fn_out)   # includes compile; then time warm
    best = 1e9
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best

res = {}
res["shuffle"] = t(lambda: distributed_shuffle(cfg, mesh))
pv = distributed_shuffle(cfg, mesh)
res["edge_gen"] = t(lambda: generate_edges(cfg, mesh))
src, dst = generate_edges(cfg, mesh)
res["relabel"] = t(lambda: relabel_ring(cfg, mesh, jnp.copy(src), jnp.copy(dst), pv))
ns, nd = relabel_ring(cfg, mesh, src, dst, pv)
res["redistribute"] = t(lambda: redistribute_sorted(cfg, mesh, ns, nd))
owned = redistribute_sorted(cfg, mesh, ns, nd)
res["csr"] = t(lambda: build_csr_sorted(cfg, mesh, owned))
res["total"] = sum(res.values())
res["platform"] = jax.devices()[0].platform
print("RESULT " + json.dumps(res))
"""


def run(scales=(12, 14), shard_counts=(1, 2, 4, 8)):
    rows = []
    for s in scales:
        for nb in shard_counts:
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       XLA_FLAGS=f"--xla_force_host_platform_device_count={nb}",
                       PYTHONPATH="src")
            r = subprocess.run([sys.executable, "-c", _CHILD, str(s), str(nb)],
                               env=env, capture_output=True, text=True,
                               timeout=1200)
            assert r.returncode == 0, r.stderr[-2000:]
            line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][0]
            res = json.loads(line[len("RESULT "):])
            platform = res.pop("platform")
            norm = 2.0 ** (s - 16)
            rows.append({"scale": s, "nb": nb, "platform": platform,
                         **{k: v / norm for k, v in res.items()}})
    print_table("Fig.3/4: strong scaling, per-phase time / 2^(s-16) [s]",
                rows, ["scale", "nb", "platform", "total", "shuffle", "edge_gen",
                       "relabel", "redistribute", "csr"])
    save_json("strong_scaling", rows)
    return rows


if __name__ == "__main__":
    run()
