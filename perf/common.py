"""Small shared pieces of the harness: output lines, digests, the table
of peaks, the count of compilations, quantiles. No JAX at import time."""

from __future__ import annotations

import hashlib
import json
import os
import sys
import threading
import time

PERF_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(PERF_DIR)


_DEVICE: dict = {}  # platform, kind, count: named in every printed record


def name_device(record: dict) -> None:
    """The device this process runs on, for every line ``emit`` prints."""
    _DEVICE.clear()
    _DEVICE.update(record)


def emit(record: dict) -> None:
    print(json.dumps({**record, **_DEVICE}, default=str), flush=True)


def progress(phase: str, step: str, t0: float, **info) -> None:
    """One line as a step ends, so that a run that is cut says where."""
    emit({"phase": phase, "step": step,
          "seconds": round(time.monotonic() - t0, 3), **info})


def span(name: str):
    """A host span of the benchmark in the profiler's own trace; the
    trace reduction attributes idle gaps of the device to these."""
    import jax

    return jax.profiler.TraceAnnotation(f"perf:{name}")


def digest(obj) -> str:
    blob = json.dumps(obj, sort_keys=True, default=list).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def load_json(*parts: str):
    with open(os.path.join(PERF_DIR, *parts), encoding="utf-8") as fh:
        return json.load(fh)


def peaks_for(device_kind: str) -> dict:
    """The chip's published peaks. A kind not in the table is an error;
    nothing in the environment overrides it."""
    table = load_json("peaks.json")
    if device_kind not in table:
        raise SystemExit(
            f"perf/peaks.json has no entry for device kind {device_kind!r}"
        )
    return table[device_kind]


class CompileCounter:
    """Programs built (compiled, or fetched from the persistent cache)
    since ``install()``; ``mark()``/``since_mark()`` bracket the window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self._n = 0
        self._mark = 0
        self._lock = threading.Lock()

    def install(self) -> "CompileCounter":
        from jax import monitoring

        def listener(event: str, duration: float, **_kw) -> None:
            if event == self.EVENT:
                with self._lock:
                    self._n += 1

        monitoring.register_event_duration_secs_listener(listener)
        return self

    @property
    def total(self) -> int:
        return self._n

    def mark(self) -> None:
        self._mark = self._n

    def since_mark(self) -> int:
        return self._n - self._mark


def device_record(jax, chips: int) -> dict:
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": chips,
    }


def memory_peak_bytes(jax, chips: int) -> int:
    """Peak bytes in use on the fullest of the chips used."""
    peaks = [
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.devices()[:chips]
    ]
    return max(peaks)


def fail(message: str) -> "NoReturn":  # noqa: F821
    print(message, file=sys.stderr, flush=True)
    raise SystemExit(2)
