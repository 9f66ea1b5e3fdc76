"""One run of a cell with a fault planted in the PROGRAM: the chunked delta
rule of a prompt hands its state on to the next chunk WITHOUT the chunk's
decay (``S <- S + K^T U`` in place of ``S <- e^G S + K^T U``), while
everything inside a chunk and the one-token decode stay sound.  A checked
request whose prompt spans more than one chunk then starts decoding from a
state that has kept every earlier chunk's writes.  The run goes through
``run.main``, so the line's ``compared`` is the harness's own comparison
against the cell's limits; a cell whose ``correct`` sees the carried state
prints ``correct: false``.  The faulty programs get a fingerprint of their
own, so the serving AOT cache never hands back the sound ones.

    python benchmark/tools/plant_delta_fault.py --workload <cell> \
        --seed 1 --seconds 30 --trace 0
"""
from __future__ import annotations

import inspect
import os
import sys
import textwrap

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

SOUND = "S = S * ho[..., None, None] + "
FAULTY = "S = S + "


def plant(program, kv_pool):
    """Plants the fault in ``models.qwen3_next``'s chunked delta rule, for
    the rest of the process."""
    src = textwrap.dedent(inspect.getsource(program.gated_delta_chunked))
    if src.count(SOUND) != 1:
        raise SystemExit("plant_delta_fault: the chunked rule no longer "
                         "hands its state on as this tool expects")
    space = dict(vars(program))
    exec(compile(src.replace(SOUND, FAULTY), program.__file__, "exec"),
         space)
    program.gated_delta_chunked = space["gated_delta_chunked"]
    init = kv_pool.LayeredPool.__init__

    def marked(self, *args, **kw):
        init(self, *args, **kw)
        self.attention_path += "+planted:no_decay_between_chunks"

    kv_pool.LayeredPool.__init__ = marked


def main(argv=None):
    from benchmark import run
    from paddle_tpu.models import qwen3_next
    from paddle_tpu.serving import kv_pool
    plant(qwen3_next, kv_pool)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
