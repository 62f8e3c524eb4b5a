#!/usr/bin/env python3
"""Record ``tpu_window.xplane.pb``, the small chip trace that
``bench/test_trace_reduce.py`` reduces.

Inside one ``bench.window`` annotation, with the harness's profiler
options: a jitted function named ``step`` runs three times on a small
f64 grid, then the program's Pallas GF(256) parity kernel once, with a
host sleep of 50 ms after each call so the window holds idle gaps.
Run on one TPU:

    python3 bench/testdata/record_trace.py OUT_DIR
"""
from __future__ import annotations

import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(out_dir: str) -> int:
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_enable_x64", True)
    from bench import trace_reduce
    from repro.kernels import gf256_encode

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2

    @jax.jit
    def step(u):
        return 6.0 * u - jnp.roll(u, 1, axis=0) - jnp.roll(u, -1, axis=0)

    u = jnp.ones((8, 64, 128), jnp.float64)
    words = jnp.ones((6, 256, 128), jnp.uint32)
    step(u).block_until_ready()
    jax.block_until_ready(gf256_encode._encode_tiles(
        words, nparity=2, bm=256, interpret=False))

    log_dir = os.path.join(out_dir, "profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        for _ in range(3):
            u = step(u)
            u.block_until_ready()
            time.sleep(0.05)
        jax.block_until_ready(gf256_encode._encode_tiles(
            words, nparity=2, bm=256, interpret=False))
        time.sleep(0.05)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(log_dir)
    shutil.copy(path, os.path.join(out_dir, "tpu_window.xplane.pb"))
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print(plane.name, [(line.name, sum(1 for _ in line.events))
                           for line in plane.lines])
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                for e in line.events:
                    print("   ", line.name, "|", e.name, e.duration_ns)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
