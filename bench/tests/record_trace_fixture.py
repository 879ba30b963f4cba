"""Record the small trace that ``test_bench_trace.py`` reduces (run on a TPU).

    python3 bench/tests/record_trace_fixture.py OUT.xplane.pb

Inside one ``bench.window`` span: five runs of a jitted matmul program
(``probe_step``), each in a ``bench.step`` span and followed by a 20 ms
``bench.wait`` sleep, then two runs of a second program (``probe_other``).
Prints the planes and lines of the trace and a few events of each.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace_fixture: needs a TPU")

    @jax.jit
    def probe_step(a, b):
        return jnp.tanh(a @ b) @ b

    @jax.jit
    def probe_other(a):
        return (a * 2.0).sum()

    a = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready(probe_step(a, a))
    jax.block_until_ready(probe_other(a))
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(5):
            with jax.profiler.TraceAnnotation("bench.step"):
                jax.block_until_ready(probe_step(a, a))
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.02)
        for _ in range(2):
            jax.block_until_ready(probe_other(a))
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True))[-1]
    shutil.copy(path, out)
    print(f"wrote {out}: {os.path.getsize(out)} bytes")
    pd = jax.profiler.ProfileData.from_file(out)
    for plane in pd.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            ev = list(line.events)
            print("  line", repr(line.name), len(ev))
            for e in ev[:4]:
                print("    ", repr(e.name), e.start_ns, e.duration_ns,
                      {k: v for k, v in list(e.stats)[:8]})


if __name__ == "__main__":
    main(sys.argv[1])
