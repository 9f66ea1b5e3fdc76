"""Mean host time per step inside ``next(loader)``, from the benchmark's
own span around it."""


def read(run):
    waits = run["spans"].get("data.next")
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
