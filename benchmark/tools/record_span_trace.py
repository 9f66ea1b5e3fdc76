"""Records the capture that ``benchmark/tests/test_hostspans.py`` checks the
gap attribution on: jitted calls inside ``obs.span("jit.a")`` with known
sleeps between them — two inside ``obs.span("io.b")``, one inside the parent
``serving.step`` alone, one outside every span.  Run on the chip:

    python benchmark/tools/record_span_trace.py chiprun_out/span_trace

Writes ``span_trace.xplane.pb`` and ``span_trace.json`` (the sleeps asked
for, in order, with the innermost span each lay in) into that directory.
"""
import glob
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

# (seconds slept, innermost span open meanwhile), one between every two calls
SLEEPS = [(0.003, "io.b"), (0.005, "io.b"), (0.004, "serving.step"),
          (0.006, "outside")]


def main(out_dir):
    import jax
    import jax.numpy as jnp
    from paddle_tpu import observability as obs
    tmp = os.path.join(out_dir, "capture")
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)

    @jax.jit
    def work(a, b):
        return jnp.tanh(a @ b) @ b

    a = jnp.ones((2048, 2048), jnp.bfloat16)
    b = jnp.ones((2048, 2048), jnp.bfloat16)
    work(a, b).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)

    def call():
        with obs.span("jit.a", hit=True):
            work(a, b).block_until_ready()

    with obs.span("serving.step", running=1, waiting=0):
        for seconds, inside in SLEEPS[:3]:
            call()
            if inside == "io.b":
                with obs.span("io.b", asked_ms=1e3 * seconds):
                    time.sleep(seconds)
            else:
                time.sleep(seconds)
        call()
    time.sleep(SLEEPS[3][0])
    call()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    shutil.copy(path, os.path.join(out_dir, "span_trace.xplane.pb"))
    shutil.rmtree(tmp)
    with open(os.path.join(out_dir, "span_trace.json"), "w") as f:
        json.dump({"sleeps": SLEEPS, "device": jax.devices()[0].device_kind},
                  f)
    print(os.path.getsize(os.path.join(out_dir, "span_trace.xplane.pb")),
          "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
