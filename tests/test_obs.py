"""tnc_tpu.obs: spans, metrics, exporters, and the disabled fast path.

Pins the subsystem's contracts: span nesting/timing and counter
aggregation when enabled; near-zero overhead (shared no-op singleton)
when disabled; Chrome-trace schema validity (required ``ph``/``ts``/
``pid``/``tid`` keys, balanced ``B``/``E`` events); JSONL round-trip;
the ``JsonFormatter`` ``extra=`` serialization and additive
``setup_logging`` the metric sink depends on; and the executor
integration (distinct prelude vs residual spans from a hoisted sliced
run).
"""

import json
import logging
import subprocess
import sys
import time

import numpy as np
import pytest

import tnc_tpu.obs as obs
from tnc_tpu.obs.core import MetricsRegistry


@pytest.fixture
def enabled_obs():
    """Fresh enabled registry; restores the disabled default afterwards."""
    reg = obs.configure(enabled=True, registry=MetricsRegistry())
    try:
        yield reg
    finally:
        obs.configure(enabled=False, registry=MetricsRegistry())


@pytest.fixture
def disabled_obs():
    obs.configure(enabled=False, registry=MetricsRegistry())
    yield obs.get_registry()
    obs.configure(enabled=False, registry=MetricsRegistry())


# -- disabled fast path -------------------------------------------------


def test_disabled_span_is_shared_noop(disabled_obs):
    s1 = obs.span("anything", big=list(range(10)))
    s2 = obs.span("else")
    assert s1 is s2 is obs.NULL_SPAN
    with s1 as sp:
        assert sp.add(flops=1) is sp
        assert sp.set(x=2) is sp
    obs.counter_add("c")
    obs.gauge_set("g", 1.0)
    obs.observe("h", 1.0)
    assert disabled_obs.span_records() == []
    assert disabled_obs.counters() == {}
    assert disabled_obs.gauges() == {}
    assert disabled_obs.histograms() == {}


def test_disabled_span_overhead(disabled_obs):
    """Disabled-path call cost — no profiler session, ``TNC_TPU_TRACE``
    unset: one bool check and one ``TraceMe.is_enabled()``, then the
    shared no-op span. The acceptance bound for leaving instrumentation
    in production paths, as a ratio against a no-op context manager
    timed in the SAME loop (baseline and span back to back, round after
    round, the best round's ratio taken): whatever else loads the
    machine — five other xdist workers in the driver's run — slows both
    sides of a round alike, where an absolute bound in microseconds read
    the load. The ratio catches any accidental allocation or registry
    touch on the disabled path; that it allocates nothing is also
    asserted outright."""
    import jax  # noqa: F401 — loaded: the profiler sink is reachable

    assert not obs.profiler_recording()
    # the disabled path hands out the one shared no-op span
    assert obs.span("stage", steps=3) is obs.core.NULL_SPAN

    class Null:
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    null = Null()
    n = 20_000

    def run_baseline():
        t0 = time.perf_counter()
        for _ in range(n):
            with null:
                pass
        return time.perf_counter() - t0

    def run_disabled():
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("stage", steps=3):
                pass
        return time.perf_counter() - t0

    rounds = [(run_baseline(), run_disabled()) for _ in range(9)]
    ratio, base, disabled = min(
        (d / max(b, 1e-9), b, d) for b, d in rounds
    )
    assert ratio < 10, (
        f"disabled span {disabled:.4f}s vs no-op baseline {base:.4f}s "
        f"in the same round: {ratio:.1f}x"
    )


# -- enabled recording --------------------------------------------------


def test_span_nesting_and_timing(enabled_obs):
    with obs.span("outer", kind="test"):
        time.sleep(0.002)
        with obs.span("inner"):
            time.sleep(0.002)
    recs = {r.name: r for r in enabled_obs.span_records()}
    assert set(recs) == {"outer", "inner"}
    outer, inner = recs["outer"], recs["inner"]
    assert outer.depth == 0 and inner.depth == 1
    assert inner.dur_ns >= 1_000_000
    assert outer.dur_ns >= inner.dur_ns
    # child runs inside the parent's window
    assert outer.start_ns <= inner.start_ns
    assert inner.start_ns + inner.dur_ns <= outer.start_ns + outer.dur_ns
    assert outer.args["kind"] == "test"
    assert outer.pid > 0 and outer.tid > 0


def test_counter_gauge_histogram_aggregation(enabled_obs):
    obs.counter_add("slices", 4)
    obs.counter_add("slices", 2)
    obs.counter_add("cache", 1, kind="hit")
    obs.counter_add("cache", 1, kind="hit")
    obs.counter_add("cache", 1, kind="miss")
    obs.gauge_set("peak", 10.0)
    obs.gauge_set("peak", 20.0)  # gauges overwrite
    obs.observe("ms", 1.0)
    obs.observe("ms", 3.0)
    c = enabled_obs.counters()
    assert c[("slices", ())] == 6.0
    assert c[("cache", (("kind", "hit"),))] == 2.0
    assert c[("cache", (("kind", "miss"),))] == 1.0
    assert enabled_obs.gauges()[("peak", ())] == 20.0
    h = enabled_obs.histograms()[("ms", ())]
    assert (h["count"], h["sum"], h["min"], h["max"]) == (2, 4.0, 1.0, 3.0)


def test_span_add_feeds_registry_counters(enabled_obs):
    with obs.span("stage") as sp:
        sp.add(flops=100, slices=2)
        sp.add(flops=50)
    rec = enabled_obs.span_records()[0]
    assert rec.args["flops"] == 150 and rec.args["slices"] == 2
    c = enabled_obs.counters()
    assert c[("stage.flops", ())] == 150.0
    assert c[("stage.slices", ())] == 2.0


def test_span_stats_depth_filter(enabled_obs):
    with obs.span("phase"):
        with obs.span("child"):
            pass
    with obs.span("phase"):
        pass
    top = enabled_obs.span_stats(max_depth=0)
    assert top["phase"]["count"] == 2 and "child" not in top
    assert enabled_obs.span_stats()["child"]["count"] == 1


def test_span_stats_tid_filter(enabled_obs):
    """Depth is per-thread: a worker-thread span starts at depth 0, so a
    per-phase breakdown must be able to pin the coordinating thread."""
    import threading

    def worker():
        with obs.span("worker.stage"):
            pass

    with obs.span("main.phase"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    main_tid = threading.get_ident()
    worker_rec = next(
        r for r in enabled_obs.span_records() if r.name == "worker.stage"
    )
    assert worker_rec.depth == 0 and worker_rec.tid != main_tid
    pinned = enabled_obs.span_stats(max_depth=1, tid=main_tid)
    assert "main.phase" in pinned and "worker.stage" not in pinned


def test_traced_decorator(enabled_obs):
    @obs.traced("plan.demo", kind="unit")
    def work(x):
        return x + 1

    assert work(1) == 2
    rec = enabled_obs.span_records()[0]
    assert rec.name == "plan.demo" and rec.args["kind"] == "unit"


def test_refresh_from_env(monkeypatch):
    monkeypatch.setenv("TNC_TPU_TRACE", "1")
    assert obs.refresh_from_env() is True
    assert obs.enabled()
    monkeypatch.setenv("TNC_TPU_TRACE", "0")
    assert obs.refresh_from_env() is False
    assert not obs.enabled()


# -- Chrome trace export ------------------------------------------------


def _make_trace(tmp_path):
    with obs.span("bench.config", config="t"):
        with obs.span("sliced.prelude") as sp:
            sp.add(flops=10)
        for _ in range(3):
            with obs.span("sliced.residual") as sp:
                sp.add(flops=40, slices=4)
    path = str(tmp_path / "trace.json")
    obs.export_chrome_trace(path)
    return path


def test_chrome_trace_schema(enabled_obs, tmp_path):
    path = _make_trace(tmp_path)
    doc = json.load(open(path))
    events = doc["traceEvents"]
    assert events, "trace must contain events"
    slices = [e for e in events if e["ph"] in ("B", "E")]
    for ev in slices:
        for key in ("name", "ph", "ts", "pid", "tid"):
            assert key in ev, f"{key} missing from {ev}"
        assert isinstance(ev["ts"], (int, float))
    # balanced B/E per (pid, tid), stack-disciplined
    stacks: dict[tuple, list] = {}
    for ev in slices:
        stack = stacks.setdefault((ev["pid"], ev["tid"]), [])
        if ev["ph"] == "B":
            stack.append(ev["name"])
        else:
            assert stack and stack[-1] == ev["name"], "unbalanced B/E"
            stack.pop()
    assert all(not s for s in stacks.values()), "unclosed B events"
    names = {e["name"] for e in slices}
    assert {"bench.config", "sliced.prelude", "sliced.residual"} <= names


def test_open_spans_appear_in_export(enabled_obs, tmp_path):
    path = str(tmp_path / "open.json")
    with obs.span("whole.run"):
        obs.export_chrome_trace(path)
    events = json.load(open(path))["traceEvents"]
    assert any(
        e["name"] == "whole.run" and e["ph"] == "B" for e in events
    ), "still-open wrapper span missing from the export"


def test_trace_summary_and_table(enabled_obs, tmp_path):
    path = _make_trace(tmp_path)
    from tnc_tpu.obs.export import load_trace_events

    rows = obs.trace_summary(load_trace_events(path))
    by_name = {r["name"]: r for r in rows}
    assert by_name["sliced.residual"]["count"] == 3
    assert by_name["sliced.residual"]["flops"] == 120.0
    assert by_name["sliced.residual"]["slices"] == 12.0
    assert by_name["sliced.prelude"]["count"] == 1
    table = obs.format_summary_table(rows)
    assert "sliced.residual" in table and "share" in table


def test_trace_summarize_cli(enabled_obs, tmp_path):
    path = _make_trace(tmp_path)
    r = subprocess.run(
        [sys.executable, "scripts/trace_summarize.py", path],
        capture_output=True,
        text=True,
    )
    assert r.returncode == 0, r.stderr
    assert "sliced.prelude" in r.stdout


# -- JSONL + logging sink -----------------------------------------------


def test_jsonl_roundtrip(enabled_obs, tmp_path):
    with obs.span("stage", n=1) as sp:
        sp.add(flops=7)
    obs.counter_add("hits", 3)
    obs.gauge_set("peak", 9.0)
    obs.observe("ms", 2.0)
    path = str(tmp_path / "metrics.jsonl")
    obs.export_jsonl(path)
    records = [json.loads(line) for line in open(path)]
    by_type: dict = {}
    for rec in records:
        by_type.setdefault(rec["type"], []).append(rec)
    span_rec = by_type["span"][0]
    assert span_rec["name"] == "stage" and span_rec["args"]["flops"] == 7
    assert span_rec["dur_s"] >= 0
    counters = {r["name"]: r["value"] for r in by_type["counter"]}
    assert counters["hits"] == 3.0 and counters["stage.flops"] == 7.0
    assert by_type["gauge"][0] == {
        "type": "gauge", "name": "peak", "value": 9.0
    }
    hist = by_type["histogram"][0]
    assert hist["name"] == "ms" and hist["count"] == 1


def test_json_formatter_serializes_extra_fields():
    from tnc_tpu.benchmark.logging_util import JsonFormatter

    record = logging.LogRecord(
        "tnc_tpu.obs", logging.INFO, __file__, 1, "metric", (), None
    )
    record.metric = "jit_cache.hit"
    record.value = 4.0
    record.metric_type = "counter"
    record.weird = object()  # non-JSON values degrade to str, not a crash
    payload = json.loads(JsonFormatter().format(record))
    assert payload["metric"] == "jit_cache.hit"
    assert payload["value"] == 4.0
    assert payload["metric_type"] == "counter"
    assert isinstance(payload["weird"], str)
    assert payload["msg"] == "metric"


def test_setup_logging_is_additive_and_idempotent(tmp_path):
    from tnc_tpu.benchmark.logging_util import setup_logging

    root = logging.getLogger("tnc_tpu")
    # bench-tagged handlers from earlier tests are setup_logging's OWN —
    # it replaces those by contract; only foreign handlers must survive
    before = [
        h for h in root.handlers if not getattr(h, "_tnc_tpu_bench", False)
    ]
    app_handler = logging.NullHandler()  # the application's own handler
    root.addHandler(app_handler)
    env_handler = logging.NullHandler()  # the TNC_TPU_LOG import handler
    env_handler._tnc_tpu_env = True
    root.addHandler(env_handler)
    try:
        setup_logging(tmp_path)
        setup_logging(tmp_path)  # idempotent: no duplicate handlers
        assert app_handler in root.handlers, "application handler clobbered"
        # the library's own env stderr handler is replaced, not kept —
        # keeping it would emit every record to stderr twice
        assert env_handler not in root.handlers
        bench = [
            h for h in root.handlers
            if getattr(h, "_tnc_tpu_bench", False)
        ]
        assert len(bench) == 2  # one stderr stream + one JSONL file
        for h in before:
            assert h in root.handlers, "pre-existing handler clobbered"
    finally:
        for h in root.handlers[:]:
            if getattr(h, "_tnc_tpu_bench", False) or h is app_handler:
                root.removeHandler(h)
                h.close()


def test_emit_metrics_lands_in_json_sink(enabled_obs, tmp_path):
    from tnc_tpu.benchmark.logging_util import setup_logging

    root = logging.getLogger("tnc_tpu")
    try:
        setup_logging(tmp_path)
        obs.counter_add("jit_cache.hit", 2)
        with obs.span("stage"):
            pass
        n = obs.emit_metrics()
        assert n >= 2
        files = list(tmp_path.glob("benchmark_*.jsonl"))
        assert len(files) == 1
        for h in root.handlers:
            h.flush()
        records = [json.loads(line) for line in open(files[0])]
        metrics = {
            r["metric"]: r for r in records if r.get("metric_type")
        }
        assert metrics["jit_cache.hit"]["value"] == 2.0
        assert metrics["stage"]["metric_type"] == "span"
    finally:
        for h in root.handlers[:]:
            if getattr(h, "_tnc_tpu_bench", False):
                root.removeHandler(h)
                h.close()


# -- executor integration -----------------------------------------------


def _ring_sliced_program():
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.slicing import Slicing
    from tnc_tpu.ops.sliced import build_sliced_program
    from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
    from tnc_tpu.tensornetwork.tensordata import TensorData

    rng = np.random.default_rng(0)

    def mk(legs):
        return LeafTensor(
            legs, [4] * len(legs),
            TensorData.matrix(rng.standard_normal([4] * len(legs))),
        )

    ring = CompositeTensor([mk([0, 1]), mk([1, 2]), mk([2, 3]), mk([3, 0])])
    path = ContractionPath.simple([(0, 3), (0, 1), (0, 2)])
    sp = build_sliced_program(ring, path, Slicing((2,), (4,)))
    arrays = [t.data.into_data() for t in ring.tensors]
    return sp, arrays


def test_numpy_hoisted_run_emits_prelude_and_residual_spans(enabled_obs):
    from tnc_tpu.ops.sliced import execute_sliced_numpy

    sp, arrays = _ring_sliced_program()
    want = execute_sliced_numpy(sp, arrays, hoist=False)
    got = execute_sliced_numpy(sp, arrays, hoist=True)
    assert np.allclose(got, want)
    names = [r.name for r in enabled_obs.span_records()]
    assert "sliced.prelude" in names
    assert names.count("sliced.residual") == 2  # naive + hoisted runs
    c = enabled_obs.counters()
    assert c[("sliced.residual.slices", ())] == 8.0  # 4 slices x 2 runs
    assert c[("sliced.prelude.flops", ())] > 0


def test_chunked_jax_run_emits_prelude_and_residual_spans(enabled_obs):
    from tnc_tpu.ops.backends import JaxBackend, NumpyBackend

    sp, arrays = _ring_sliced_program()
    want = NumpyBackend().execute_sliced(sp, arrays)
    got = JaxBackend(
        dtype="complex64"
    ).execute_sliced(sp, arrays, hoist=True)
    assert np.allclose(got, want, atol=1e-4)
    names = {r.name for r in enabled_obs.span_records()}
    assert {"sliced.prelude", "sliced.residual",
            "backend.place_buffers"} <= names


def test_disabled_executor_records_nothing(disabled_obs):
    from tnc_tpu.ops.sliced import execute_sliced_numpy

    sp, arrays = _ring_sliced_program()
    execute_sliced_numpy(sp, arrays, hoist=True)
    assert disabled_obs.span_records() == []
    assert disabled_obs.counters() == {}
