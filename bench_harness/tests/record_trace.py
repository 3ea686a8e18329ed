"""Record the small trace that tests/test_harness.py reduces: three
rounds of a tiny distributed_histogram and, on several chips, a tiny
device_allreduce, inside the harness's own ``bench.*`` host spans.

    python3 bench_harness/tests/record_trace.py --chips 4 --out DIR

Run on the chip (it refuses a CPU); the ``.xplane.pb`` it leaves in DIR
is committed as ``bench_harness/tests/trace_<chips>chip.xplane.pb``.
"""

from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: no TPU")
    from rabit_tpu.models import histogram as H
    from rabit_tpu.parallel import make_mesh
    from rabit_tpu.parallel import collectives as C
    from jax.sharding import NamedSharding, PartitionSpec as P

    p = args.chips
    mesh = make_mesh(p)
    sh = NamedSharding(mesh, P(mesh.axis_names[0]))
    n, nbins = 16384, 7168
    key = jax.random.PRNGKey(0)
    b = jax.device_put(jax.random.randint(key, (p, n), 0, nbins), sh)
    g = jax.device_put(jax.random.normal(key, (p, n)), sh)
    h = jax.device_put(jax.random.uniform(key, (p, n)), sh)
    x = jax.device_put(jax.random.normal(key, (p, 1 << 16)), sh)

    def round_():
        with jax.profiler.TraceAnnotation("bench.round"):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                out = [H.distributed_histogram(g, h, b, nbins, mesh)]
                if p > 1:
                    out.append(C.device_allreduce(x, mesh, C.SUM))
            with jax.profiler.TraceAnnotation("bench.wait"):
                jax.block_until_ready(out)

    round_()
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            round_()
    jax.profiler.stop_trace()
    (f,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(args.out, exist_ok=True)
    dst = os.path.join(args.out, f"trace_{p}chip.xplane.pb")
    shutil.copy(f, dst)
    shutil.rmtree(tdir)
    print(dst, os.path.getsize(dst))


if __name__ == "__main__":
    main()
