"""Rounds of gradient-histogram builds through
``rabit_tpu.models.histogram.distributed_histogram``.

The configuration gives the dataset (rows of this chip, features, bins,
the logistic gradients); the traffic mix's ``tree`` gives which nodes a
round builds: level ``level`` of ``trees`` fixed trees over the shard,
with the subtraction trick (the smaller of each pair of siblings; level
0 is the whole shard). Each split sends ``k / max_bin`` of its node's
rows to the left, ``k`` drawn uniform from ``split_bin`` by numpy from
the mix's fixed ``split_seed``: a threshold on a feature whose quantile
bins are equal-frequency. So node sizes are arbitrary row counts, the
same for every run seed (every program is warmed in set-up), and the
run seed orders the trees and the nodes and makes the data.

The configuration's ``layout`` says in what form a node's ``n`` rows of
``F`` features are handed, p ranks (``p`` = 1 here) on the leading axis:

- ``"entries"`` (the default): flat per-entry triples. ``bins`` int32
  ``[p, n*F]`` of global bin ids ``f*max_bin + u``, row-major, and
  ``grad``, ``hess`` f32 ``[p, n*F]``, each row's value on every one of
  its entries.
- ``"dense"``: XGBoost's dense form. ``bins`` int32 ``[p, n, F]`` of the
  same global ids, row-major, and ``grad``, ``hess`` f32 ``[p, n]``, one
  value per row. For one seed these are the entries form's arrays
  reshaped and un-broadcast.

The call is the same in both: ``distributed_histogram(grad, hess, bins,
nbins, mesh, axis, method, precision=...)``. That ``bins`` has one axis
more than ``grad`` is the whole of the dense contract: the result is the
``[nbins, 2]`` (sum of grad, sum of hess) histogram of the equivalent
entries form, each row's weights counted on every one of its F entries.
"""

from __future__ import annotations

import functools
import time

import numpy as np

from bench_harness import reference


def tree_nodes(rows: int, tree: dict, max_bin: int) -> list:
    """[[rows of each node a round builds] per tree]."""
    rng = np.random.default_rng(tree.get("split_seed", 0))
    lo, hi = tree.get("split_bin", (1, max_bin - 1))
    out = []
    for _ in range(tree["trees"]):
        nodes = [rows]
        for _ in range(tree["level"]):
            nxt = []
            for n in nodes:
                left = round(n * int(rng.integers(lo, hi + 1)) / max_bin)
                left = min(max(left, 1), n - 1)
                nxt += [left, n - left]
            nodes = nxt
        out.append(nodes if tree["level"] == 0 else
                   [min(nodes[i], nodes[i + 1])
                    for i in range(0, len(nodes), 2)])
    return out


LAYOUTS = ("entries", "dense")


def _gen(key, sizes, features, max_bin, label_rate, margin_std, pool,
         layout):
    """One jitted call: HIGGS-like rows for every node, each node's rows
    contiguous as a row partitioner leaves them, in the ``layout`` form
    (module docstring). Returns [bins per node], [[grad per node] per
    set], [[hess ...]] with ``pool`` gradient sets over the same bins.
    Bins are f*max_bin + u with u uniform; the gradients are the logistic
    loss's at margins ~ N(0, margin_std), one per row."""
    import jax
    import jax.numpy as jnp
    total = sum(sizes)
    kb, ky, km = jax.random.split(key, 3)
    u = jax.random.randint(kb, (total, features), 0, max_bin, jnp.int32)
    bins = jnp.arange(features, dtype=jnp.int32) * max_bin + u
    y = jax.random.bernoulli(ky, label_rate, (pool, total))
    p = jax.nn.sigmoid(margin_std * jax.random.normal(
        km, (pool, total), jnp.float32))
    gh = [p - y.astype(jnp.float32), p * (1 - p)]
    width = 1
    if layout == "entries":
        bins, width = bins.reshape(-1), features
        gh = [jnp.broadcast_to(v[:, :, None], (pool, total, features))
              .reshape(pool, -1) for v in gh]
    offs = np.concatenate([[0], np.cumsum(sizes)]) * width
    cut = [slice(int(a), int(b)) for a, b in zip(offs[:-1], offs[1:])]
    gs, hs = ([[v[k, None, c] for c in cut] for k in range(pool)]
              for v in gh)
    return [bins[None, c] for c in cut], gs, hs


class Cell:
    def __init__(self, cfg, traffic, key, rng, mesh, control):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from rabit_tpu.models import histogram as H

        self.H, self.mesh = H, mesh
        self.axis = mesh.axis_names[0]
        self.features, self.max_bin = cfg["features"], cfg["max_bin"]
        self.nbins = self.features * self.max_bin
        self.method = cfg["method"]
        self.precision = (cfg["control"]["precision"] if control
                          else cfg["precision"])
        layout = cfg.get("layout", "entries")
        if layout not in LAYOUTS:
            raise ValueError(f"layout {layout!r} is not one of {LAYOUTS}")
        trees = tree_nodes(cfg["rows"], traffic["tree"], self.max_bin)
        sizes = [n for t in trees for n in t]
        pool = traffic["grad_pool"]
        gen = jax.jit(functools.partial(
            _gen, sizes=tuple(sizes), features=self.features,
            max_bin=self.max_bin, label_rate=cfg["label_rate"],
            margin_std=cfg["margin_std"], pool=pool, layout=layout),
            out_shardings=NamedSharding(mesh, P(self.axis)))
        bins, gs, hs = gen(key)
        # node id k * len(sizes) + j: node j with gradient set k
        self.nodes = [(bins[j], gs[k][j], hs[k][j])
                      for k in range(pool) for j in range(len(sizes))]
        self.rows = sizes * pool
        # the bytes each call hands to the program, for the roofline
        self.nbytes = [sum(a.nbytes for a in node) for node in self.nodes]
        firsts = np.cumsum([0] + [len(t) for t in trees])
        self.trees = [list(range(a, b)) for a, b in zip(firsts[:-1],
                                                        firsts[1:])]
        first = {}
        for j, n in enumerate(sizes):
            first.setdefault(n, j)
        self.first_of_size = list(first.values())
        self.rng, self.pool, self.order = rng, pool, []

    def schedule(self, r):
        """Round r's node ids: each cycle of len(trees) rounds builds every
        tree once, in an order drawn from the seed, each tree's nodes in
        an order of their own, with gradient set r % pool."""
        t = len(self.trees)
        while len(self.order) <= r:
            self.order += [int(i) for i in self.rng.permutation(t)]
        base = (r % self.pool) * (len(self.rows) // self.pool)
        return [base + int(j)
                for j in self.rng.permutation(self.trees[self.order[r]])]

    def _call(self, node):
        b, g, h = self.nodes[node]
        return self.H.distributed_histogram(
            g, h, b, self.nbins, self.mesh, self.axis, self.method,
            precision=self.precision)

    def warm(self):
        """Compile (or load) the program of every node size."""
        import jax
        jax.block_until_ready([self._call(i) for i in self.first_of_size])

    def round(self, r, rec, annotate):
        import jax
        nodes = self.schedule(r)
        outs, disp = [], []
        for i in nodes:
            with annotate("bench.dispatch"):
                t = time.perf_counter()
                outs.append(self._call(i))
                disp.append(time.perf_counter() - t)
        with annotate("bench.wait"):
            jax.block_until_ready(outs)
        rec["rows"] = sum(self.rows[i] for i in nodes)
        rec["bytes"] = [self.nbytes[i] for i in nodes]
        rec["nbins"] = self.nbins
        rec["dispatch_s"] = disp
        return list(zip(nodes, outs))

    def check(self, items):
        """items: the (node, output) pairs of the sampled rounds. For each,
        the widest gap of either column over that column's largest
        magnitude, against the float64 numpy histogram of the node's
        rows, in either layout."""
        got = [(i, np.asarray(o)) for i, o in items]
        want = {}
        for i in sorted({i for i, _ in got}):
            b, g, h = (np.asarray(a) for a in self.nodes[i])
            want[i] = reference.histogram(b, g, h, self.nbins)
        self.nodes = None
        errs = []
        for i, o in got:
            if o.shape != want[i].shape:
                errs.append(float("inf"))
                continue
            errs.append(max(reference.rel_err(o[:, c], want[i][:, c])
                            for c in range(2)))
        return {"hist_rel_err": errs}


def build(cfg, traffic, key, rng, mesh, control=False):
    return Cell(cfg, traffic, key, rng, mesh, control)
