"""Median host wall of the ``engine.step()`` calls inside the window that
admitted nothing (a pure decode step), from the benchmark's own stamps."""
from benchmark import stats


def read(run):
    walls = run.get("decode_only_steps_s")
    if not walls:
        return None
    return 1e3 * stats.median(walls)
