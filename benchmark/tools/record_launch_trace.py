"""Records the capture that ``benchmark/tests/test_launches.py`` checks the
linked placement on: a tiny two-program engine loop — a "decode" program
launched in ``serving.launch``, a "sample" program launched behind it inside
``serving.sample`` and fetched in ``serving.fetch`` — with known host sleeps
in ``serving.capacity`` (before the decode's launch) and ``serving.deliver``
(after the fetch), one sleep between two decode launches.  Run on the chip:

    python benchmark/tools/record_launch_trace.py chiprun_out/launch_trace

Writes ``launch_trace.xplane.pb`` and ``launch_trace.json`` (the sleeps
asked for, in order, each with the span it lay in) into that directory.
"""
import glob
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

# (span that sleeps, seconds) for each gap between two decode launches, in
# order: each gap holds exactly one sleep
SLEEPS = [("serving.capacity", 0.003), ("serving.deliver", 0.002),
          ("serving.capacity", 0.001), ("serving.deliver", 0.004),
          ("serving.capacity", 0.002), ("serving.deliver", 0.003)]


def main(out_dir):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu import observability as obs
    tmp = os.path.join(out_dir, "capture")
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)

    @jax.jit
    def decode(a, b):
        # ~3 ms on a v5e: the sampler, launched right behind it, waits
        # past ``launches.QUEUED_MS`` as a real pass's does
        for _ in range(4):
            a = jnp.tanh(a @ b)
        return a

    @jax.jit
    def sample(x):
        return jnp.argmax(x, axis=-1)

    a = jnp.ones((4096, 4096), jnp.bfloat16)
    b = jnp.full((4096, 4096), 1e-4, jnp.bfloat16)
    np.asarray(sample(decode(a, b)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    def sleep_in(name, gap):
        if 0 <= gap < len(SLEEPS) and SLEEPS[gap][0] == name:
            time.sleep(SLEEPS[gap][1])

    # gap k lies between step k's decode and step k + 1's: step k sleeps
    # in its deliver, or step k + 1 in its capacity pass
    for k in range(len(SLEEPS) + 1):
        with obs.span("serving.step"), obs.span("serving.decode"):
            with obs.span("serving.capacity"):
                sleep_in("serving.capacity", k - 1)
            with obs.span("serving.launch", program="decode"):
                logits = decode(a, b)
            with obs.span("serving.sample"):
                with obs.span("serving.launch", program="sample"):
                    toks = sample(logits)
                with obs.span("serving.fetch"):
                    np.asarray(toks)
            with obs.span("serving.deliver"):
                sleep_in("serving.deliver", k)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    shutil.copy(path, os.path.join(out_dir, "launch_trace.xplane.pb"))
    shutil.rmtree(tmp)
    with open(os.path.join(out_dir, "launch_trace.json"), "w") as f:
        json.dump({"sleeps": SLEEPS, "device": jax.devices()[0].device_kind},
                  f)
    print(os.path.getsize(os.path.join(out_dir, "launch_trace.xplane.pb")),
          "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
