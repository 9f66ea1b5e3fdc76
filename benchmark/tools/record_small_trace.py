"""Records the small capture that ``benchmark/tests/test_xplane.py`` checks
the reduction on: a few matrix products with host sleeps between them, so
the device is idle for a known part.  Run on the chip:

    python benchmark/tools/record_small_trace.py chiprun_out/small_trace
"""
import glob
import os
import shutil
import sys
import time


def main(out_dir):
    import jax
    import jax.numpy as jnp
    tmp = os.path.join(out_dir, "capture")
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)

    @jax.jit
    def work(a, b):
        return jnp.tanh(a @ b) @ b

    a = jnp.ones((1024, 1024), jnp.bfloat16)
    b = jnp.ones((1024, 1024), jnp.bfloat16)
    work(a, b).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(3):
        work(a, b).block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    shutil.copy(path, os.path.join(out_dir, "small_trace.xplane.pb"))
    shutil.rmtree(tmp)
    print(os.path.getsize(os.path.join(out_dir, "small_trace.xplane.pb")),
          "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
