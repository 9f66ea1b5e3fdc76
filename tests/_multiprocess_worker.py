"""Worker script for the real multi-process collective test.

Launched (twice, as separate OS processes) by
tests/test_distributed_multiprocess.py through
``python -m paddle_tpu.distributed.launch --master ... --nnodes 2
--rank R`` — so by the time this runs, ``launch()`` has already called
``jax.distributed.initialize`` against the coordinator and installed
the global mesh.  The worker proves the multi-host path end to end:

- ``jax.process_count() == 2`` (real DCN-style bootstrap, not a
  single-process virtual mesh);
- a ``paddle_tpu.distributed.all_reduce`` across the two processes
  produces the cross-process sum on BOTH ranks (the eager multi-host
  path: ``multihost_utils.process_allgather`` + reduce).

Results are written as one JSON file per rank (argv[1] is the output
directory); the parent asserts on them — a crashed or hung worker
simply never writes its file.
"""
import json
import os
import sys

import numpy as np


def main():
    out_dir = sys.argv[1]
    import jax

    import paddle_tpu as P
    from paddle_tpu import distributed as dist
    from paddle_tpu.analysis import kv_tracer

    kv_tracer.arm_from_env()   # no-op unless PTPU_KV_TRACE_DIR is set
    rank = jax.process_index()
    from paddle_tpu.observability import fleettrace
    fleettrace.arm_from_env(rank=rank)    # needs PTPU_OBS_SPOOL_DIR
    nprocs = jax.process_count()

    t = P.to_tensor(np.array([float(rank + 1), 10.0 * (rank + 1)],
                             np.float32))
    dist.all_reduce(t)                       # SUM over processes
    reduced = [float(x) for x in np.asarray(t.numpy())]

    gathered = []
    dist.all_gather(gathered,
                    P.to_tensor(np.array([rank], np.int32)))
    ranks_seen = sorted(int(np.asarray(g.numpy())[0]) for g in gathered)

    b = P.to_tensor(np.array([100.0 + rank], np.float32))
    dist.broadcast(b, src=1)                 # rank 1's value everywhere
    broadcast_val = float(np.asarray(b.numpy())[0])

    payload = {
        "rank": rank,
        "nprocs": nprocs,
        "reduced": reduced,
        "ranks_seen": ranks_seen,
        "broadcast": broadcast_val,
    }
    path = os.path.join(out_dir, f"rank{rank}.json")
    with open(path + ".tmp", "w") as fh:
        json.dump(payload, fh)
    os.replace(path + ".tmp", path)


if __name__ == "__main__":
    main()
