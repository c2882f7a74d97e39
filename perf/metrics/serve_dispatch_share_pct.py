"""Seconds the service spent inside batch dispatches during the window
(``stats()['by_tier']['exact']['dispatch']['total_s']``, the service's own
clock) over the window; the rest of a request's life is queue and bind."""

name = 'serve_dispatch_share_pct'
unit = '%'
layer = 'serve'
moves = 'amps_per_s'
workloads = None  # every cell that reports `moves`


def read(run):
    stats = run.window.get("stats") or {}
    if not stats.get("batches"):
        return None
    return 100.0 * stats["dispatch_s"] / run.window["window_s"]
