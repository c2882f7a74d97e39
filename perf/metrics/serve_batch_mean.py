"""Requests completed per batch dispatched over the window, from the
service's own counts (``stats()['counts']``)."""

name = 'serve_batch_mean'
unit = 'req'
layer = 'serve'
moves = 'amps_per_s'
workloads = None  # every cell that reports `moves`


def read(run):
    stats = run.window.get("stats") or {}
    if not stats.get("batches"):
        return None
    return stats["completed"] / stats["batches"]
