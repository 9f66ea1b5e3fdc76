"""One run of a cell with a fault planted in the PROGRAM: a block's commit
pass is skipped — the pass that fixes its last position also moves the
length and opens the next block, so the pages keep the K/V of a block that
still held a mask (the rows its last denoising pass wrote).  The run goes
through ``run.main``, so the line's ``compared`` is the harness's own
comparison against the cell's limits; a cell whose ``correct`` sees the
stored blocks prints ``correct: false``.  The compiled programs are the
sound engine's (the fault is in what the host does between them).

    python benchmark/tools/plant_skipped_commit.py --workload <cell> \
        --seed 1 --seconds 30 --trace 0
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def skipping(decoded):
    """``BlockDiffusion.decoded`` that commits without the commit pass."""
    def faulty(self, eng, live, *rest):
        decoded(self, eng, live, *rest)
        for s, r in live:
            if eng._slots[s] is r and not self.masked[s].any():
                eng._lens[s] += self.rows
                self.open_block(s)
    return faulty


def main(argv=None):
    from benchmark import run
    from paddle_tpu.serving import generation
    generation.BlockDiffusion.decoded = skipping(
        generation.BlockDiffusion.decoded)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
