#!/usr/bin/env python
"""Bring-up smoke of rabit_tpu's main path on a TPU, through the entry
points a user calls. It claims no speed: the times it prints are set-up
and compile times, labelled as such.

``python chip_smoke.py`` (one chip, one process):

1. place the compile cache (``rabit_tpu.utils.compile_cache``) and
   refuse to run anywhere but on a TPU;
2. ``rabit_tpu.init(engine="xla")``;
3. the gradient-histogram allreduce (``models.histogram``) at bench.py's
   cell (2,097,152 rows, 1024 bins) and the HIGGS ``gpu_hist`` shape
   (28 features x 256 bins = 7168 bins): ``method="auto"`` must resolve
   to the compiled Pallas kernel, and the result must match the numpy
   ``host_histogram`` on the same rows;
4. ``rabit.allreduce`` / ``broadcast`` / ``checkpoint`` on the reduced
   histogram (host no-ops at world 1);
5. three training steps of the flagship transformer with the Pallas
   flash kernels, checked against the same step with flash off.

``python chip_smoke.py --chips 4`` runs only the path that exists
across chips, in one process driving all four: the histogram allreduce
on a 4-device mesh and ``device_allreduce`` with each explicit schedule.

Any failed phase or comparison raises, so the process exits non-zero and
the last line is not ``"ok": true``. On success the last line of stdout
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))

ROWS = 1 << 21                  # rows per worker (bench.py's cell)
NBINS = (1024, 28 * 256)        # bench.py's cell; HIGGS 28 x 256 bins
ROUNDS = 3
ALLREDUCE_SIZES = (4096, 1 << 21)   # f32 elements per rank
# the flagship transformer at FLAGSHIP_HW_20260730T013742Z's size
TF = dict(vocab=256, n_layers=2, d_model=256, n_heads=8, d_head=32,
          d_ff=1024, seq=512, batch=8)
TF_STEPS = 3


@contextlib.contextmanager
def _phase(name: str):
    t0 = time.perf_counter()
    yield
    print(f"# phase {name}: {time.perf_counter() - t0:.3f} s wall "
          "(set-up + compile + run; not a metric)", flush=True)


def _check(ok, what) -> None:
    """A failed comparison raises (``assert`` would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _assert_kernel(compiled_text: str, what: str) -> None:
    """The compiled program holds a Mosaic kernel: the Pallas path ran
    compiled, not interpreted and not replaced by an XLA op."""
    _check("tpu_custom_call" in compiled_text,
           f"{what}: no tpu_custom_call in the compiled program")


def _assert_pallas_resolved(H, PK) -> None:
    resolved = H.resolve_method("auto")
    print(f"# histogram: method='auto' resolves to {resolved!r}, "
          f"interpret={PK._interpret()}")
    _check(resolved == "pallas" and not PK._interpret(),
           "method='auto' is not the compiled Pallas kernel")


def _gen_inputs(mesh, p: int, n: int, nbins: int, seed: int):
    """(grad, hess, bins) [p, n] made on the device from ``seed``, the
    leading axis sharded over the mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = NamedSharding(mesh, P(mesh.axis_names[0]))

    @functools.partial(jax.jit, out_shardings=(sh, sh, sh))
    def gen(key):
        kg, kh, kb = jax.random.split(key, 3)
        return (jax.random.normal(kg, (p, n), jnp.float32),
                jax.random.uniform(kh, (p, n), jnp.float32),
                jax.random.randint(kb, (p, n), 0, nbins, jnp.int32))

    return gen(jax.random.PRNGKey(seed))


def _check_placement(arr, p: int, what: str) -> None:
    """Every shard of ``arr`` lives on its own device (code that never
    ran on several chips can put them all on the first one)."""
    devs = {s.device for s in arr.addressable_shards}
    _check(len(devs) == p,
           f"{what}: shards on {len(devs)} devices, want {p}")


def histogram_phase(p: int) -> "np.ndarray":
    """distributed_histogram on make_mesh(p) at every shape in NBINS,
    ROUNDS rounds each, against the sum of the workers' host_histogram.
    Returns the last reduced histogram."""
    import numpy as np
    from rabit_tpu.models import histogram as H
    from rabit_tpu.ops import pallas_kernels as PK
    from rabit_tpu.parallel import make_mesh

    _assert_pallas_resolved(H, PK)
    mesh = make_mesh(p)
    got = None
    for nbins in NBINS:
        for r in range(ROUNDS):
            g, h, b = _gen_inputs(mesh, p, ROWS, nbins, seed=100 * nbins + r)
            _check_placement(g, p, "histogram inputs")
            if r == 0:
                text = H.distributed_histogram.lower(
                    g, h, b, nbins=nbins, mesh=mesh, method="auto",
                    precision="high").compile().as_text()
                _assert_kernel(text, f"histogram nbins={nbins}")
            out = H.distributed_histogram(g, h, b, nbins, mesh, "workers",
                                          "auto", precision="high")
            got = np.asarray(out)
            gh, hh, bh = (np.asarray(a) for a in (g, h, b))
            want = np.zeros((nbins, 2), np.float64)
            for i in range(p):
                want += H.host_histogram(gh[i], hh[i], bh[i], nbins)
            # bench.py's tolerance: the bf16 hi/lo split's ~2e-6 rel
            # error with slack for f32 accumulation
            atol = 4e-3 * float(np.sqrt(p * ROWS / nbins))
            err = float(np.abs(got - want).max())
            _check(got.shape == (nbins, 2) and np.isfinite(got).all(),
                   f"nbins={nbins}: shape {got.shape} or non-finite")
            _check(np.allclose(got, want, rtol=1e-3, atol=atol),
                   f"high nbins={nbins} round={r}: max|err|={err}")
            print(f"# histogram p={p} rows/worker={ROWS} nbins={nbins} "
                  f"round={r} precision=high: max|err|={err:.3e} "
                  f"(atol {atol:.3e}, rtol 1e-3) ok")
            if r == 0 and nbins == NBINS[0]:
                _check_fast(H, g, h, b, gh, hh, bh, nbins, mesh, want)
    return got.astype(np.float32)


def _check_fast(H, g, h, b, gh, hh, bh, nbins, mesh, want) -> None:
    """precision="fast" once, against its own error model: each
    component is rounded to bf16 (relative error <= 2^-9), so a bin's
    error is at most 2^-8 of the sum of its |components| (the factor 2
    covers f32 accumulation). The docstring's "~2e-4" is the max error
    over the largest bin; it is printed beside the bound."""
    import numpy as np
    p = gh.shape[0]
    got = np.asarray(H.distributed_histogram(
        g, h, b, nbins, mesh, "workers", "auto", precision="fast"))
    mag = np.zeros((nbins, 2), np.float64)
    for i in range(p):
        mag += H.host_histogram(np.abs(gh[i]), np.abs(hh[i]), bh[i], nbins)
    err = np.abs(got - want)
    rel = float(err.max() / np.abs(want).max())
    _check(np.isfinite(got).all() and (err <= 2.0 ** -8 * mag + 1e-3).all(),
           f"fast nbins={nbins}: error beyond the bf16 bound (rel {rel:.3e})")
    print(f"# histogram nbins={nbins} precision=fast: "
          f"max|err|/max|want|={rel:.3e} (documented ~2e-4) ok")


def public_api_phase(hist) -> None:
    """allreduce / broadcast / checkpoint round-trip through the public
    API, each returning exactly what it was given."""
    import numpy as np
    import rabit_tpu as rabit

    _check(rabit.get_world_size() == 1, "world size is not 1")
    out = rabit.allreduce(hist.copy(), rabit.SUM)
    _check(out.dtype == hist.dtype and np.array_equal(out, hist),
           "allreduce changed its input")
    got = rabit.broadcast({"hist": hist, "round": 3}, 0)
    _check(got["round"] == 3 and np.array_equal(got["hist"], hist),
           "broadcast changed its input")
    rabit.checkpoint({"hist": hist})
    version, model = rabit.load_checkpoint()
    _check(version == 1 and np.array_equal(model["hist"], hist),
           f"checkpoint round-trip failed (version {version})")
    print("# public api: allreduce/broadcast/checkpoint returned their "
          "input; at world 1 the xla engine answers them on the host "
          "(engine/xla.py), so no device collective ran here")


def transformer_phase() -> None:
    """TF_STEPS steps of the flagship transformer with RABIT_FLASH_ATTN=1
    (the Pallas flash kernels, forward and fused backward): losses
    finite and falling, first-step loss within 1e-3 relative of the
    same step with flash off."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from rabit_tpu.models import transformer as tf
    from rabit_tpu.parallel import make_mesh

    mesh = make_mesh(1, ("dp", "tp", "sp"), (1, 1, 1))
    params0 = tf.init_params(
        jax.random.PRNGKey(0), vocab=TF["vocab"], n_layers=TF["n_layers"],
        d_model=TF["d_model"], n_heads=TF["n_heads"], d_head=TF["d_head"],
        d_ff=TF["d_ff"], max_t=TF["seq"])
    # learnable structure: next token = (token + 1) % vocab
    seq = np.arange(TF["seq"] + TF["vocab"] + 1) % TF["vocab"]
    phase = np.random.default_rng(0).integers(0, TF["vocab"], TF["batch"])
    toks = np.stack([np.roll(seq, -int(s))[:TF["seq"] + 1] for s in phase])
    x = jnp.asarray(toks[:, :-1].astype(np.int32))
    y = jnp.asarray(toks[:, 1:].astype(np.int32))

    def first_loss(flash: bool):
        os.environ["RABIT_FLASH_ATTN"] = "1" if flash else "0"
        step = tf.make_train_step(mesh, lr=0.1)
        return step, step.lower(params0, x, y).compile()

    step, exe = first_loss(True)
    _assert_kernel(exe.as_text(), "flash train step")
    params, losses = params0, []
    for _ in range(TF_STEPS):
        params, loss = step(params, x, y)
        losses.append(float(loss))
    _, exe_ref = first_loss(False)
    ref = float(exe_ref(params0, x, y)[1])
    os.environ.pop("RABIT_FLASH_ATTN")
    print(f"# transformer flash losses {losses}; flash-off first-step "
          f"loss {ref}")
    _check(all(np.isfinite(losses)), f"non-finite losses {losses}")
    _check(all(b < a for a, b in zip(losses, losses[1:])),
           f"losses do not fall: {losses}")
    rel = abs(losses[0] - ref) / abs(ref)
    _check(rel < 1e-3, f"flash vs flash-off first-step loss: rel {rel:.3e}")


def allreduce_phase(p: int) -> None:
    """device_allreduce with each explicit schedule over make_mesh(p),
    against the numpy sum, every shard identical."""
    import numpy as np
    from rabit_tpu.ops.reducers import SUM
    from rabit_tpu.parallel import dispatch, make_mesh
    from rabit_tpu.parallel.collectives import device_allreduce, shard_over

    mesh = make_mesh(p, ("proc",))
    rng = np.random.default_rng(3)
    for n in ALLREDUCE_SIZES:
        xs = rng.standard_normal((p, n)).astype(np.float32)
        want = xs.astype(np.float64).sum(axis=0)
        dx = shard_over(mesh, xs)
        _check_placement(dx, p, "allreduce input")
        for method in ("tree", "ring", "bidir", "swing"):
            for wire in ((None, "bf16", "int8") if method == "ring"
                         else (None,)):
                out = device_allreduce(dx, mesh, SUM, method=method,
                                       wire=wire)
                _check_placement(out, p, f"{method}/{wire} output")
                shards = [np.asarray(out.addressable_data(i))
                          for i in range(p)]
                _check(all(np.array_equal(shards[0], s) for s in shards),
                       f"{method}/{wire} n={n}: shards differ")
                got = shards[0].astype(np.float64)
                rel = float(np.abs(got - want).max() / np.abs(want).max())
                # exact schedules: f32 reassociation only; quantized
                # wire: __graft_entry__'s envelope over p-1 ring hops
                tol = 1e-5 if wire is None else 2e-2 * np.sqrt(p)
                _check(rel < tol, f"{method}/{wire} n={n}: rel {rel:.3e}")
                print(f"# allreduce p={p} n={n} method={method} "
                      f"wire={wire}: rel err {rel:.3e} ok")
        auto = dispatch.resolve(n, np.float32, SUM, p)
        print(f"# allreduce n={n}: method='auto' resolves to {auto} "
              f"from {dispatch.table_source()}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip phase on 4 chips")
    args = ap.parse_args()

    sys.path.insert(0, _REPO)
    from rabit_tpu.utils import compile_cache

    with _phase("setup"):
        print(f"# compile cache: {compile_cache.setup(_REPO)}")
        import jax
        devs = jax.devices()
        dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
               "count": len(devs)}
        print(f"# devices: {dev}; order {[d.id for d in devs]}")
        if dev["platform"] != "tpu":
            sys.exit(f"chip_smoke: no TPU (JAX found {dev['platform']}); "
                     "refusing to run the smoke anywhere else")
        if dev["count"] < args.chips:
            sys.exit(f"chip_smoke: --chips {args.chips} needs "
                     f"{args.chips} devices, JAX found {dev['count']}")

    if args.chips == 4:
        with _phase("histogram_4chip"):
            histogram_phase(4)
        with _phase("device_allreduce_4chip"):
            allreduce_phase(4)
    else:
        import rabit_tpu as rabit
        with _phase("init"):
            rabit.init([], engine="xla")
        with _phase("histogram"):
            hist = histogram_phase(1)
        with _phase("public_api"):
            public_api_phase(hist)
        rabit.finalize()
        with _phase("transformer"):
            transformer_phase()
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
