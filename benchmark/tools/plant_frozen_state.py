"""One run of a cell with a fault planted in the PROGRAM: decode hands every
slot's recurrent state back as it got it (the prefill's state, never
advanced).  The run goes through ``run.main``, so the line's ``compared`` is
the harness's own comparison against the cell's limits; a cell whose
``correct`` sees the state prints ``correct: false``.  The faulty programs
are compiled into, and loaded from, a directory of their own, never the
checkout's program cache (whose fingerprint covers no pool code).

    python benchmark/tools/plant_frozen_state.py --workload <cell> --seed 1 \
        --seconds 30 --trace 0
"""
from __future__ import annotations

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def frozen(advance):
    """``SlotState.recur`` with decode's advance of the state dropped."""
    def recur(self, fn, conv, ssm, lens, slot, values):
        if slot is not None:
            return advance(self, fn, conv, ssm, lens, slot, values)
        out, _conv, _ssm = fn(conv, ssm, lens, *values)
        return out, conv, ssm
    return recur


def main(argv=None):
    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
        prefix="frozen_state_")
    from benchmark import run
    from paddle_tpu.serving import kv_pool
    kv_pool.SlotState.recur = frozen(kv_pool.SlotState.recur)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
