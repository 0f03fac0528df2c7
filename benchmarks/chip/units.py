"""The general traffic driver: one kind of unit of work per traffic file.

A traffic file (`traffic/<name>.json`) names its `kind` and the parameters
of that kind; the configuration file gives the sizes.  Each kind sets up
once, then runs units back to back for the window:

    gen     one `repro.core.pipeline.generate()`, seed to finished CSR.
            Parameters: `shuffle_variant`.  Work: m edges.
    walks   one `repro.data.walks.distributed_walks()` call over a graph
            that set-up generates once: `walkers` walkers of the
            configuration's `walk_length` hops.  Parameters: `walkers`,
            `shuffle_variant` (the graph's).  Work: walkers x length hops.

After each unit a small compiled check runs on the device and returns only
scalars: whether the unit kept its invariants (nothing dropped, m edges,
monotone offsets; every walker back and valid) and an order-free
fingerprint of its output.  Set-up compiles everything the window calls,
the checks included, without running a unit.
"""

from __future__ import annotations

import hashlib
import sys
from functools import partial
from types import ModuleType
from typing import Callable, Dict, Iterable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import reference as ref
import reference_walks as ref_walks
import scopes

U32 = jnp.uint32


def derive_seed(seed: int, purpose: str) -> int:
    """A 32-bit seed for one purpose, drawn from the run's --seed."""
    digest = hashlib.blake2b(f"{purpose}:{seed}".encode(), digest_size=4).digest()
    return int.from_bytes(digest, "little")


def _is_jitted(obj) -> bool:
    return callable(obj) and hasattr(obj, "lower") and hasattr(obj, "trace")


def compile_only(fn: Callable, modules: Iterable[ModuleType]):
    """Run `fn` with every jitted function of `modules` compiled for its
    arguments (through the persistent cache) and not run: such a call made
    from plain Python returns its output shapes instead.  Calls made while
    tracing another program are left alone.  Returns `fn`'s output as
    shapes; the window's own calls then find every program compiled."""
    saved, busy = [], []

    def shim(jitted):
        def call(*args, **kwargs):
            if busy:
                return jitted(*args, **kwargs)
            busy.append(jitted)
            try:
                return jitted.lower(*args, **kwargs).compile().out_info
            finally:
                busy.pop()
        return call

    for mod in modules:
        for name, obj in list(vars(mod).items()):
            if _is_jitted(obj):
                saved.append((mod, name, obj))
                setattr(mod, name, shim(obj))
    try:
        return fn()
    finally:
        for mod, name, obj in saved:
            setattr(mod, name, obj)


# ---------------------------------------------------------------------------
# device-side checks: scalars only come back
# ---------------------------------------------------------------------------


def _fold(x):
    return jnp.sum(ref.mix32(x), dtype=U32)


@partial(jax.jit, static_argnames=("spec",))
def gen_check(spec: ref.GraphSpec, pv, offv, adjv, num_edges, dropped_relabel,
              dropped_redistribute):
    """[ok, pv fingerprint, edge-multiset fingerprint] as uint32."""
    nb, B, n = spec.nb, spec.bucket, spec.n
    o = offv.reshape(nb, B + 1)
    monotone = jnp.all(jnp.diff(o, axis=1) >= 0) & jnp.all(o[:, 0] == 0)
    ends = jnp.all(o[:, -1] == num_edges.reshape(-1))
    hits = jnp.zeros((n,), jnp.int32).at[jnp.clip(pv, 0, n - 1)].add(1)
    is_perm = jnp.all(hits == 1) & jnp.all((pv >= 0) & (pv < n))
    ok = ((dropped_relabel == 0) & (dropped_redistribute == 0)
          & (jnp.sum(num_edges) == spec.m) & monotone & ends & is_perm)
    _, rows, cols, _ = ref.csr_pairs(spec, offv, adjv, num_edges)
    used = rows < n
    fp_pv = _fold(pv.astype(U32) ^ ref.mix32(jnp.arange(n, dtype=U32)))
    fp_edges = jnp.sum(jnp.where(used, ref.mix32(ref.mix32(rows) ^ cols.astype(U32)), 0),
                       dtype=U32)
    return jnp.stack([ok.astype(U32), fp_pv, fp_edges])


@partial(jax.jit, static_argnames=("spec",))
def walk_check(spec: ref_walks.WalkSpec, hist, valid, wid, dropped):
    """[ok, fingerprint of every valid (walker id, history)] as uint32."""
    n = spec.graph.n
    in_graph = jnp.all((hist >= 0) & (hist < n), axis=1)
    ids = jnp.zeros((spec.walkers,), jnp.int32).at[jnp.clip(wid, 0, spec.walkers - 1)].add(
        (valid & (wid >= 0) & (wid < spec.walkers)).astype(jnp.int32))
    ok = (dropped == 0) & jnp.all(ids == 1) & jnp.all(in_graph | ~valid)
    t = jnp.arange(hist.shape[1], dtype=U32)
    salt = ref.mix32(wid.astype(U32)[:, None] * U32(hist.shape[1]) + t)
    fp = jnp.sum(jnp.where(valid[:, None], ref.mix32(hist.astype(U32) ^ salt), 0), dtype=U32)
    return jnp.stack([ok.astype(U32), fp])


# ---------------------------------------------------------------------------
# traffic kinds
# ---------------------------------------------------------------------------


class Check(NamedTuple):
    ok: bool
    fingerprint: tuple


def graph_spec(config: dict, shuffle: str) -> ref.GraphSpec:
    return ref.GraphSpec(scale=int(config["scale"]), edge_factor=int(config["edge_factor"]),
                         a=float(config["a"]), b=float(config["b"]), c=float(config["c"]),
                         d=float(config["d"]), nb=int(config["nb"]), shuffle=shuffle)


def graph_config(config: dict, graph_seed: int):
    from repro.core.types import GraphConfig

    return GraphConfig(scale=int(config["scale"]), edge_factor=int(config["edge_factor"]),
                       a=float(config["a"]), b=float(config["b"]), c=float(config["c"]),
                       d=float(config["d"]), nb=int(config["nb"]),
                       capacity_factor=float(config["capacity_factor"]), seed=graph_seed)


class GenTraffic:
    """Units of `generate()`: the paper's pipeline from seed to CSR."""

    rate_metric = "gen_edges_per_s"
    walk = None

    def __init__(self, config: dict, traffic: dict, seed: int, mesh):
        from repro.core import pipeline

        self.pipeline = pipeline
        self.variant = traffic["shuffle_variant"]
        self.graph_seed = derive_seed(seed, "graph")
        self.config = config
        self.cfg = graph_config(config, self.graph_seed)
        self.spec = graph_spec(config, self.variant)
        self.mesh = mesh
        self.work_per_unit = self.spec.m

    def _generate(self):
        return self.pipeline.generate(self.cfg, self.mesh, shuffle_variant=self.variant)

    def setup(self) -> None:
        res = compile_only(self._generate, [self.pipeline])
        gen_check.lower(self.spec, *self._check_args(res)).compile()

    def _check_args(self, res):
        return (res.pv, res.csr.offv, res.csr.adjv, res.csr.num_edges,
                res.dropped_relabel, res.dropped_redistribute)

    def unit(self):
        return self._generate()

    def check(self, res) -> Check:
        vals = np.asarray(gen_check(self.spec, *self._check_args(res)))
        return Check(bool(vals[0]), tuple(int(v) for v in vals[1:]))

    def keep(self, res):
        """What the comparison reads; the rest of the unit's state is freed."""
        return res.pv, res.csr.offv, res.csr.adjv, res.csr.num_edges

    def compare(self, kept) -> Dict[str, int]:
        out = ref.compare_graph(self.spec, U32(self.graph_seed), *kept)
        return {k: int(v) for k, v in out.items()}

    def program_texts(self) -> Optional[List[str]]:
        """The optimised HLO of every phase program, for a stand-in seed."""
        return scopes.program_texts(graph_config(self.config, scopes.STAND_IN_SEED),
                                    self.mesh, self.variant)


class WalkTraffic:
    """Units of `distributed_walks()`: one call of the walk corpus over the
    CSR that set-up generated and keeps on the device."""

    rate_metric = "walk_hops_per_s"

    def __init__(self, config: dict, traffic: dict, seed: int, mesh):
        from repro.core import pipeline
        from repro.data import walks

        self.pipeline, self.walks = pipeline, walks
        self.variant = traffic["shuffle_variant"]
        self.graph_seed = derive_seed(seed, "graph")
        self.walk_seed = derive_seed(seed, "walks")
        self.config = config
        self.cfg = graph_config(config, self.graph_seed)
        self.spec = graph_spec(config, self.variant)
        nb = self.spec.nb
        if int(traffic["walkers"]) % nb:
            raise ValueError(f"{traffic['walkers']} walkers do not split over {nb} shards")
        self.walk = ref_walks.WalkSpec(self.spec, int(traffic["walkers"]) // nb,
                                       int(config["walk_length"]))
        self.capacity_factor = float(config["walk_capacity_factor"])
        self.mesh = mesh
        self.work_per_unit = self.walk.hops
        self.offv = self.adjv = None

    def _call(self, fn, cfg, offv, adjv, seed):
        return fn(cfg, self.mesh, offv, adjv, length=self.walk.length, seed=seed,
                  walkers_per_shard=self.walk.walkers_per_shard,
                  capacity_factor=self.capacity_factor)

    def setup(self) -> None:
        csr = self.pipeline.generate(self.cfg, self.mesh, shuffle_variant=self.variant).csr
        self.offv, self.adjv = jax.block_until_ready((csr.offv, csr.adjv))
        del csr
        compiled = self._call(self.walks.distributed_walks.lower, self.cfg, self.offv,
                              self.adjv, self.walk_seed).compile()
        mem = compiled.memory_analysis()
        if mem is not None:
            print(f"walk program bytes: argument {mem.argument_size_in_bytes} "
                  f"output {mem.output_size_in_bytes} temp {mem.temp_size_in_bytes}",
                  file=sys.stderr, flush=True)
        walk_check.lower(self.walk, *compiled.out_info).compile()

    def unit(self):
        return self._call(self.walks.distributed_walks, self.cfg, self.offv, self.adjv,
                          self.walk_seed)

    def check(self, out) -> Check:
        vals = np.asarray(walk_check(self.walk, *out))
        return Check(bool(vals[0]), (int(vals[1]),))

    def keep(self, out):
        """The last call's rows; the graph and the rest are freed."""
        self.offv = self.adjv = None
        hist, valid, wid, _ = out
        return hist, valid, wid

    def compare(self, kept) -> Dict[str, int]:
        out = ref_walks.compare_walks(self.walk, U32(self.graph_seed), U32(self.walk_seed),
                                      *kept)
        return {k: int(v) for k, v in out.items()}

    def program_texts(self) -> List[str]:
        """The optimised HLO of the walk program, for a stand-in graph and
        walk seed and the CSR's shapes, which tracing generate() gives."""
        cfg = graph_config(self.config, scopes.STAND_IN_SEED)
        csr = jax.eval_shape(lambda: self.pipeline.generate(
            cfg, self.mesh, shuffle_variant=self.variant).csr)
        sharded = NamedSharding(self.mesh, P("shards"))
        offv, adjv = (jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharded)
                      for a in (csr.offv, csr.adjv))
        with scopes.metadata_in_key():
            lowered = self._call(self.walks.distributed_walks.lower, cfg, offv, adjv,
                                 scopes.STAND_IN_SEED)
            return [lowered.compile().as_text()]


KINDS = {"gen": GenTraffic, "walks": WalkTraffic}


def make(config: dict, traffic: dict, seed: int, mesh):
    kind = traffic["kind"]
    if kind not in KINDS:
        raise ValueError(f"unknown traffic kind {kind!r}; known: {sorted(KINDS)}")
    return KINDS[kind](config, traffic, seed, mesh)
