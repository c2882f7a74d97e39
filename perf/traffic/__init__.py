"""Traffic kinds: one module per way of driving an entry point of the
program; a cell's ``perf/workloads/<name>.json`` names one by ``kind``
and gives its parameters. Each module has ``prepare(run)`` (set-up and
warm-up), ``window(run)`` (the measured window), ``summary(run)``,
``check(run)`` -> ``(numbers, attempted, failed)`` and
``end_to_end(run)`` -> ``{metric: value}``."""
