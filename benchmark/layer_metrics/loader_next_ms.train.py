"""Mean duration of the program's own ``io.next`` spans in the traced
window: from the train loop asking ``io.DataLoader`` for a batch to the
batch being ready — the inside twin of ``data_wait_ms.train``."""
from benchmark import hostspans


def read(run):
    spans = hostspans.durations_ms("io.next")
    return sum(spans) / len(spans) if spans else None
