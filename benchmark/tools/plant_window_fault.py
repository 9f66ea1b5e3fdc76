"""One run of a cell with a fault planted in the PROGRAM: the prefill of a
window layer attends past its window — over every earlier position of the
prompt, as a full layer does — while its ring and its decode stay sound.
A checked request whose prompt is longer than the window then carries the
wrong hidden states into every later layer.  The run goes through
``run.main``, so the line's ``compared`` is the harness's own comparison
against the cell's limits; a cell whose ``correct`` sees the window prints
``correct: false``.  The faulty programs get a fingerprint of their own, so
the serving AOT cache never hands back the sound ones.

    python benchmark/tools/plant_window_fault.py --workload <cell> \
        --seed 1 --seconds 30 --trace 0
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def past_the_window(attention):
    """``attention`` with its ``window`` dropped: causal over the whole
    prompt."""
    def faulty(*args, window=None, **kw):
        return attention(*args, **kw)
    return faulty


def plant(kv_pool):
    """Plants the fault in ``serving.kv_pool``'s window layers, for the
    rest of the process."""
    init = kv_pool.WindowKV.__init__

    def marked(self, cfg, spec):
        init(self, cfg, spec)
        self.attention_path += "+planted:past_the_window"

    kv_pool.WindowKV.__init__ = marked
    for name in ("flash_attention_bshd", "grouped_causal_attention"):
        setattr(kv_pool, name, past_the_window(getattr(kv_pool, name)))


def main(argv=None):
    from benchmark import run
    from paddle_tpu.serving import kv_pool
    plant(kv_pool)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
