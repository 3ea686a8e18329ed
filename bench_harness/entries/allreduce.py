"""Rounds of node-histogram allreduces through
``rabit_tpu.parallel.collectives.device_allreduce``.

The configuration gives the histogram of one node (features x max_bin
bins of f32 (grad, hess)); the traffic mix gives how many nodes each
level of a tree allreduces together (``level_nodes``). A round is one
tree: its levels one after another, each result waited for before the
next, as split finding requires. Payloads come from a pool of
``tree_pool`` trees made on the device from the seed.
"""

from __future__ import annotations

import functools
import time

import numpy as np

from bench_harness import reference

def _gen(key, sizes, p, pool):
    """One jitted call: ``pool`` trees, each a [p, n] f32 payload per
    level, histogram-like: interleaved (grad, hess) pairs with grad
    ~ 16 N(0, 1) and hess ~ 16 |N(0, 1)|. (Interleaving by a stack and
    reshape took the TPU compiler four minutes at this size.)"""
    import jax
    import jax.numpy as jnp
    trees = []
    for t in range(pool):
        levels = []
        for i, n in enumerate(sizes):
            x = 16.0 * jax.random.normal(
                jax.random.fold_in(key, t * len(sizes) + i), (p, n),
                jnp.float32)
            odd = jax.lax.broadcasted_iota(jnp.int32, (p, n), 1) % 2 == 1
            levels.append(jnp.where(odd, jnp.abs(x), x))
        trees.append(levels)
    return trees


class Cell:
    def __init__(self, cfg, traffic, key, rng, mesh, control):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from rabit_tpu.parallel import collectives as C

        self.C, self.mesh = C, mesh
        self.p = mesh.shape[mesh.axis_names[0]]
        self.method = cfg["method"]
        self.wire = cfg["control"]["wire"] if control else cfg["wire"]
        node = cfg["features"] * cfg["max_bin"] * 2   # f32 per node
        self.sizes = [k * node for k in traffic["level_nodes"]]
        pool = traffic["tree_pool"]
        sh = NamedSharding(mesh, P(mesh.axis_names[0]))
        gen = jax.jit(functools.partial(_gen, sizes=tuple(self.sizes),
                                        p=self.p, pool=pool),
                      out_shardings=sh)
        self.trees = gen(key)
        self.schedule = lambda r: r % pool

    def warm(self):
        """A round holds every level's size: the harness's warm rounds
        compile them."""

    def round(self, r, rec, annotate):
        t = self.schedule(r)
        outs, disp = [], []
        for xs in self.trees[t]:
            with annotate("bench.dispatch"):
                t0 = time.perf_counter()
                out = self.C.device_allreduce(xs, self.mesh, self.C.SUM,
                                              method=self.method,
                                              wire=self.wire)
                disp.append(time.perf_counter() - t0)
            with annotate("bench.wait"):
                out.block_until_ready()
            outs.append(out)
        rec["bytes"] = [4 * n for n in self.sizes]
        rec["dispatch_s"] = disp
        return [(t, outs)]

    def check(self, items):
        """items: (tree, level outputs) of the sampled rounds. Every shard
        of every level, on every device, against the float64 numpy sum of
        the ranks' payloads: widest gap over the largest magnitude."""
        got = [(t, [[np.asarray(s.data) for s in o.addressable_shards]
                    for o in outs]) for t, outs in items]
        want = {}
        for t in sorted({t for t, _ in got}):
            want[t] = [reference.allreduce_sum(list(np.asarray(xs)))
                       for xs in self.trees[t]]
        self.trees = None
        errs = []
        for t, levels in got:
            # a replicated result has one shard on each of the p devices
            if any(len(shards) != self.p for shards in levels):
                errs.append(float("inf"))
                continue
            errs.append(max(reference.rel_err(s, w)
                            for shards, w in zip(levels, want[t])
                            for s in shards))
        return {"allreduce_rel_err": errs}


def build(cfg, traffic, key, rng, mesh, control=False):
    return Cell(cfg, traffic, key, rng, mesh, control)
