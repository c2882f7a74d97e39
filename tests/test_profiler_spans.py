"""The program's spans in the profiler's own trace, and the names of the
programs it runs.

Pins: while a ``jax.profiler`` session records — and ``TNC_TPU_TRACE``
is unset — every ``obs.span`` is a host annotation ``perf:tnc.<name>``
on the calling thread of the ``.xplane.pb``; with no session nothing is
written anywhere. The three measured hot paths (chunked
``execute_sliced`` with hoisting, the service with a batch of two,
slice-SPMD on virtual devices) emit the spans of their layer
boundaries, children inside parents, none per step or per slice, and
run jitted programs whose module names are the ``tnc_*`` names by role.
The service's ``stats()`` carries the same boundaries as always-on
totals.
"""

import glob
import os
import threading
import time

import numpy as np
import pytest

import tnc_tpu.obs as obs
from tnc_tpu.obs.core import PROFILER_SPAN_PREFIX, MetricsRegistry


@pytest.fixture
def disabled_obs():
    obs.configure(enabled=False, registry=MetricsRegistry())
    yield obs.get_registry()
    obs.configure(enabled=False, registry=MetricsRegistry())


class Trace:
    """What a profiler session wrote: the program's host spans per
    thread line, and the names of the XLA modules that ran."""

    def __init__(self, trace_dir: str):
        from jax.profiler import ProfileData

        (path,) = glob.glob(
            os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
        )
        self.spans = []  # (line id, name, start_ns, end_ns, stats)
        self.host_events = []  # (name, start_ns, end_ns), any host event
        self.modules = set()
        self.ops = set()  # (module, op) of every XLA op event
        for plane in ProfileData.from_file(path).planes:
            for line_id, line in enumerate(plane.lines):
                for ev in line.events:
                    start = float(ev.start_ns)
                    end = start + float(ev.duration_ns)
                    stats = dict(ev.stats)
                    if "hlo_module" in stats:
                        self.modules.add(stats["hlo_module"])
                        if "hlo_op" in stats:
                            self.ops.add((stats["hlo_module"], stats["hlo_op"]))
                    if not plane.name.startswith("/host:"):
                        continue
                    self.host_events.append((ev.name, start, end))
                    if ev.name.startswith(PROFILER_SPAN_PREFIX):
                        name = ev.name[len(PROFILER_SPAN_PREFIX):]
                        self.spans.append((line_id, name, start, end, stats))

    def names(self) -> list:
        return [s[1] for s in self.spans]

    def named(self, name: str) -> list:
        return [s for s in self.spans if s[1] == name]

    def inside(self, child: str, parent: str) -> bool:
        """Every ``child`` span lies within a ``parent`` span of its
        own thread."""
        parents = self.named(parent)
        return all(
            any(p[0] == c[0] and p[2] <= c[2] and c[3] <= p[3] for p in parents)
            for c in self.named(child)
        )


def traced(tmp_path, fn) -> Trace:
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return Trace(trace_dir)


# -- the span API ---------------------------------------------------------


def test_span_on_worker_thread_lands_in_profiler_trace(disabled_obs, tmp_path):
    import jax

    def work():
        with obs.span("x", n=3, riders="r1,r2") as sp:
            time.sleep(0.02)
            sp.set(late=1)

    def session():
        # the benchmark's own spans bracket the worker's on this clock
        with jax.profiler.TraceAnnotation("perf:before"):
            pass
        worker = threading.Thread(target=work)
        worker.start()
        worker.join()
        with jax.profiler.TraceAnnotation("perf:after"):
            pass

    assert not obs.profiler_recording()
    trace = traced(tmp_path, session)
    assert not obs.profiler_recording()
    assert trace.names() == ["x"]
    (_, _, start, end, stats) = trace.spans[0]
    (before,) = [e for e in trace.host_events if e[0] == "perf:before"]
    (after,) = [e for e in trace.host_events if e[0] == "perf:after"]
    assert before[2] <= start and end <= after[1]
    assert end - start >= 0.02e9
    assert stats["n"] == 3 and stats["late"] == 1
    assert stats["riders"] == "r1 r2"  # commas are the encoding's own
    # the profiler's trace was the only sink
    assert disabled_obs.span_records() == []
    assert disabled_obs.counters() == {}


def test_no_session_writes_nothing(disabled_obs, tmp_path):
    import jax  # noqa: F401 — loaded, so the profiler sink is reachable

    assert obs.span("x", n=3) is obs.NULL_SPAN

    @obs.traced("plan.demo")
    def plan():
        return 7

    assert plan() == 7
    with obs.phase("backend.lookup") as sp:
        assert sp is obs.NULL_SPAN
    assert disabled_obs.span_records() == []
    assert not glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)


def test_registry_span_is_also_an_annotation(tmp_path):
    reg = obs.configure(enabled=True, registry=MetricsRegistry())
    try:
        def work():
            with obs.span("outer", k=1):
                with obs.span("inner"):
                    pass

        trace = traced(tmp_path, work)
        assert sorted(trace.names()) == ["inner", "outer"]
        assert trace.inside("inner", "outer")
        assert [r.name for r in reg.span_records()] == ["inner", "outer"]
    finally:
        obs.configure(enabled=False, registry=MetricsRegistry())


def test_collect_phases_totals_without_any_tracing(disabled_obs):
    with obs.collect_phases() as totals:
        with obs.phase("backend.place_buffers", n=2) as sp:
            time.sleep(0.002)
            sp.add(bytes=64)
            sp.add(bytes=36)
        with obs.collect_phases() as inner:
            with obs.phase("backend.lookup"):
                pass
        with obs.phase("backend.lookup"):
            pass
    assert totals["backend.place_buffers"] >= 0.002
    assert totals["backend.place_buffers.bytes"] == 100
    assert set(inner) == {"backend.lookup"}
    assert set(totals) == {
        "backend.place_buffers", "backend.place_buffers.bytes",
        "backend.lookup",
    }
    with obs.phase("backend.lookup") as sp:  # no collector: a plain span
        assert sp is obs.NULL_SPAN
    assert disabled_obs.span_records() == []


# -- the three measured paths ---------------------------------------------


def _ring(seed):
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
    from tnc_tpu.tensornetwork.tensordata import TensorData

    rng = np.random.default_rng(seed)

    def mk(legs):
        shape = [4] * len(legs)
        data = rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
        return LeafTensor(legs, shape, TensorData.matrix(data))

    # legs 3 and 4 are sliced; the (0, 1) pair touches neither, so the
    # hoisting pass has a prelude to take out
    ts = [mk([0, 1]), mk([1, 2]), mk([2, 3]), mk([3, 4]), mk([4, 0])]
    path = ContractionPath.simple([(0, 1), (0, 2), (0, 3), (0, 4)])
    return ts, CompositeTensor([t.copy() for t in ts]), path


def _run_chunked():
    from tnc_tpu.contractionpath.slicing import Slicing
    from tnc_tpu.ops.backends import JaxBackend, NumpyBackend
    from tnc_tpu.ops.sliced import build_sliced_program

    ts, tn, path = _ring(3)
    sp = build_sliced_program(tn, path, Slicing((3, 4), (4, 4)))
    arrays = [t.data.into_data() for t in ts]
    backend = JaxBackend(
        dtype="complex128", split_complex=True, slice_batch=4, chunk_steps=2
    )
    out = {}

    def run():
        out["got"] = backend.execute_sliced(sp, arrays, hoist=True)

    def check():
        want = NumpyBackend().execute_sliced(sp, arrays)
        np.testing.assert_allclose(out["got"], want, rtol=1e-9, atol=1e-9)

    return run, check


def _run_serve():
    from tests.test_serve import make_circuit, oracle_amplitude
    from tnc_tpu.ops.backends import JaxBackend
    from tnc_tpu.serve import ContractionService

    svc = ContractionService.from_circuit(
        make_circuit(),
        backend=JaxBackend(dtype="complex128", split_complex=True),
        max_batch=2, max_wait_ms=2000.0,
    )
    bits = ["01101", "11010"]
    out = {}

    def run():
        try:
            futures = [svc.submit(b) for b in bits]
            out["got"] = [f.result(timeout=120) for f in futures]
            out["stats"] = svc.stats()
        finally:
            svc.stop()

    def check():
        for b, got in zip(bits, out["got"]):
            want = complex(oracle_amplitude(b).reshape(()))
            assert abs(got - want) < 1e-9
        assert out["stats"]["batch_size"]["max"] == 2

    return run, check, out


def _run_spmd():
    from tnc_tpu.contractionpath.slicing import Slicing
    from tnc_tpu.ops.sliced import build_sliced_program, execute_sliced_numpy
    from tnc_tpu.parallel.sliced_parallel import (
        distributed_sliced_contraction,
        make_mesh,
    )

    ts, tn, path = _ring(5)
    slicing = Slicing((3, 4), (4, 4))
    mesh = make_mesh(4)
    out = {}

    def run():
        out["got"] = distributed_sliced_contraction(
            tn, path, slicing, mesh=mesh, dtype="complex128",
            split_complex=True, hoist=True,
        )

    def check():
        sp = build_sliced_program(tn, path, slicing)
        want = execute_sliced_numpy(sp, [t.data.into_data() for t in ts])
        got = out["got"].data.into_data().reshape(sp.program.result_shape)
        np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9)

    return run, check


# path -> (spans that must appear, (child, parent) nestings, jit modules)
EXPECTED = {
    "chunked": (
        {"backend.lookup", "backend.place_buffers", "sliced.prelude",
         "sliced.residual", "backend.fetch"},
        [],
        {"jit_tnc_prelude", "jit_tnc_residual_c00", "jit_tnc_residual_last"},
    ),
    "serve": (
        {"serve.collect", "serve.dispatch", "serve.bind", "backend.lookup",
         "backend.place_buffers", "backend.execute", "backend.fetch",
         "serve.reply"},
        [(child, "serve.dispatch") for child in (
            "serve.bind", "backend.lookup", "backend.place_buffers",
            "backend.execute", "backend.fetch")],
        {"jit_tnc_program_batched"},
    ),
    "spmd": (
        {"spmd.contract", "spmd.build", "spmd.place", "spmd.execute",
         "spmd.fetch"},
        [(child, "spmd.contract") for child in (
            "spmd.build", "spmd.place", "spmd.execute", "spmd.fetch")],
        {"jit_tnc_spmd_slices"},
    ),
}

_TRACES: dict = {}


@pytest.fixture
def path_trace(request, tmp_path, disabled_obs):
    """One traced run of a path, shared by the tests that read it."""
    path = request.param
    if path not in _TRACES:
        extra = None
        if path == "chunked":
            run, check = _run_chunked()
        elif path == "serve":
            run, check, extra = _run_serve()
        else:
            run, check = _run_spmd()
        trace = traced(tmp_path, run)
        check()
        _TRACES[path] = (trace, extra)
    return (path,) + _TRACES[path]


@pytest.mark.parametrize("path_trace", sorted(EXPECTED), indirect=True)
def test_path_emits_its_layer_spans(path_trace):
    path, trace, _ = path_trace
    want, nestings, _ = EXPECTED[path]
    names = trace.names()
    assert want <= set(names), sorted(want - set(names))
    for child, parent in nestings:
        assert trace.inside(child, parent), (child, parent)
    # one call, one batch: no name fires per step, per slice or per
    # chunk dispatch (16 slices, 4 steps, 2-3 chunks here)
    for name in set(names):
        assert names.count(name) <= 3, (name, names.count(name))
    assert len(names) <= 16, names


@pytest.mark.parametrize("path_trace", sorted(EXPECTED), indirect=True)
def test_path_runs_programs_named_by_role(path_trace):
    path, trace, _ = path_trace
    want = EXPECTED[path][2]
    assert want <= trace.modules, sorted(trace.modules)
    closures = {"jit_run", "jit_last_fn", "jit__lambda", "jit_device_fn",
                "jit_fn", "jit_leaf_sum", "jit_chunk_fn"}
    assert not closures & trace.modules, sorted(closures & trace.modules)


@pytest.mark.parametrize("path_trace", sorted(EXPECTED), indirect=True)
def test_every_traced_op_of_the_programs_is_a_key_of_the_op_table(path_trace):
    """The text asked for afterwards is the text of the executable that
    ran: every ``module/op`` the session saw of a ``jit_tnc_*`` program
    is a key of the table, and no module reads as stale."""
    path, trace, _ = path_trace
    ran = {(m, op) for m, op in trace.ops if m.startswith("jit_tnc_")}
    assert {m for m, _ in ran} >= EXPECTED[path][2]
    table = obs.device_op_table({m for m, _ in ran})
    for module, op in sorted(ran):
        variants = table[module]
        assert all(v["status"] == "ok" for v in variants), (module, variants[0]["why"])
        assert any(op in v["ops"] for v in variants), (module, op)
    ops = [(f"{m}/{op}", 1.0) for m, op in sorted(ran)]
    joined = obs.step_seconds(ops, table)
    assert joined["unknown_ops"] == []
    # a second asking gives the same names: two lowerings agree
    again = obs.device_op_table({m for m, _ in ran})
    assert {m: [sorted(v["ops"]) for v in vs] for m, vs in again.items()} == {
        m: [sorted(v["ops"]) for v in vs] for m, vs in table.items()}


@pytest.mark.parametrize("path_trace", ["chunked"], indirect=True)
def test_residual_span_says_how_the_rows_ran(path_trace):
    _, trace, _ = path_trace
    (residual,) = trace.named("sliced.residual")
    assert residual[4]["rows"] == "loop"
    assert residual[4]["batch"] == 4
    assert residual[4]["chunks"] >= 2


@pytest.mark.parametrize("path_trace", ["serve"], indirect=True)
def test_service_stats_total_the_dispatch_boundaries(path_trace):
    _, trace, out = path_trace
    row = out["stats"]["by_tier"]["exact"]["dispatch"]
    assert row["count"] == 1
    phases = ("bind_s", "lookup_s", "place_s", "execute_s", "fetch_s")
    for key in phases:
        assert row[key] > 0, key
    assert sum(row[key] for key in phases) <= row["total_s"] * 1.001
    # the bytes copied, not the bytes looked at: a leaf already resident
    # behind place_buffers is a hit and counts nothing
    (place,) = trace.named("backend.place_buffers")
    assert row["h2d_bytes"] == place[4]["bytes"] > 0
    assert row["leaves_placed"] == place[4]["placed"] >= 5  # the bras
    assert row["leaf_hits"] == place[4]["hits"]
    assert place[4]["placed"] + place[4]["hits"] == place[4]["n"]


_ALIVE: list = []


def _lowered(program_kind: str):
    """The lowered form of one jitted program of the measured paths, on
    shapes alone: what a compile for a described chip sees."""
    import jax
    import jax.numpy as jnp

    from tnc_tpu.contractionpath.slicing import Slicing
    from tnc_tpu.ops.backends import jit_program
    from tnc_tpu.ops.chunked import _compiled_plan, _prelude_fn
    from tnc_tpu.ops.hoist import hoist_sliced_program
    from tnc_tpu.ops.program import build_program
    from tnc_tpu.ops.sliced import build_sliced_program
    from tnc_tpu.parallel.sliced_parallel import _make_spmd_fn, make_mesh

    def pair(*shape):
        return (jax.ShapeDtypeStruct(shape, jnp.float32),) * 2

    _, tn, path = _ring(7)
    full = [pair(4, 4)] * 5
    if program_kind == "tnc_program":
        fn = jit_program(build_program(tn, path), True, "float32", donate=False)
        return fn.jitted.lower(full)
    if program_kind == "tnc_program_batched":
        fn = jit_program(
            build_program(tn, path), True, "float32", donate=False,
            batched=frozenset({0}),
        )
        return fn.jitted.lower([pair(2, 4, 4)] + full[1:])
    sp = build_sliced_program(tn, path, Slicing((3, 4), (4, 4)))
    if program_kind == "tnc_spmd_slices":
        fn = _make_spmd_fn(
            sp, make_mesh(4), "slices", "complex64", True, "float32",
            hoist=True,
        )
        _ALIVE[:] = [fn]  # no cache holds it, and the op table holds it weakly
        return fn.lower(*full)
    hp = hoist_sliced_program(sp)
    assert not hp.is_noop
    prelude = _prelude_fn(hp, True, "float32")
    pins = tuple(full[orig] for _, orig in hp.prelude_inputs)
    if program_kind == "tnc_prelude":
        return prelude.lower(pins)
    chunks, chunk_fns, _ = _compiled_plan(hp.residual, 4, 2, True, "float32")
    assert len(chunks) > 1  # the first is not the accumulating last one
    cached = iter(jax.eval_shape(prelude, pins))
    state = dict(enumerate(
        full[ref] if kind == "leaf" else next(cached)
        for kind, ref in hp.residual_sources
    ))
    idx = jax.ShapeDtypeStruct((4, 2), jnp.int32)
    for chunk, fn in zip(chunks[:-1], chunk_fns):
        ins = tuple(state[slot] for slot in chunk.in_slots)
        if program_kind == "tnc_residual_c00":
            return fn.lower(ins, idx)
        state.update(zip(chunk.out_slots, jax.eval_shape(fn, ins, idx)))
    assert program_kind == "tnc_residual_last"
    ins = tuple(state[slot] for slot in chunks[-1].in_slots)
    acc = (pair(*hp.residual.program.stored_result_shape),) * 2
    return chunk_fns[-1].lower(ins, idx, acc)


PROGRAM_KINDS = [
    "tnc_program", "tnc_program_batched", "tnc_prelude", "tnc_residual_c00",
    "tnc_residual_last", "tnc_spmd_slices",
]


@pytest.mark.parametrize("program_kind", PROGRAM_KINDS)
def test_lowered_module_name_and_step_scopes(program_kind):
    text = _lowered(program_kind).as_text(debug_info=True)
    assert f"module @jit_{program_kind} " in text
    assert f"jit({program_kind})/" in text
    # every step's ops sit under the step's own named scope (the ring's
    # 4x4 steps are all small one-dot steps in the matrix form), with
    # the three sub-scopes; "vmap(tnc.step....)/" when batched
    assert "tnc.step.0000.small.block.matrix" in text
    for part in ("prep", "dot", "out"):
        assert f".small.block.matrix/{part}/" in text or (
            f".small.block.matrix)/{part}/" in text
        ), part
    # the shape buckets name no scope any more
    for bucket in ("tnc.small", "tnc.medium", "tnc.stem"):
        assert bucket + "/" not in text and bucket + ")" not in text
    # the non-step work of a slice is under scopes of its own
    if program_kind in ("tnc_residual_c00", "tnc_residual_last"):
        assert "tnc.chunk.io/" in text and "tnc.slice.index/" in text
    if program_kind in ("tnc_residual_last", "tnc_spmd_slices"):
        assert "tnc.slice.sum/" in text


def _dot_holding_ops(text: str) -> set:
    """Instructions of an optimized text that are a dot or convolution,
    or a fusion whose computation holds one: read off the text with
    nothing of the program's parser."""
    import re

    holding, current, ops = set(), None, set()
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$", line)
        if head:
            current = head.group(1)
        elif re.search(r"\s(dot|convolution)\(", line):
            holding.add(current)
            ops.add(re.match(r"\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=", line).group(1))
    for line in text.splitlines():
        called = re.search(r"calls=%?([\w.\-]+)", line)
        if called and called.group(1) in holding:
            ops.add(re.match(r"\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=", line).group(1))
    return ops


@pytest.mark.parametrize("program_kind", PROGRAM_KINDS)
def test_compiled_dots_are_in_the_op_table_under_one_step(program_kind):
    """Each program kind compiled on the CPU: the table asked for
    afterwards is ``ok``, holds every op of the optimized text, and puts
    every dot-holding op under exactly one step, part ``dot``."""
    from tnc_tpu.obs import op_table

    lowered = _lowered(program_kind)
    text = lowered.compile().as_text()
    variants = obs.device_op_table([f"jit_{program_kind}"])[f"jit_{program_kind}"]
    ops = op_table.parse_hlo_ops(text)["ops"]
    variant = next(v for v in variants if set(v["ops"]) == set(ops))
    assert variant["status"] == "ok", variant["why"]
    dots = _dot_holding_ops(text) & set(ops)  # those that run as ops
    assert dots
    for op in dots:
        entry = variant["ops"][op]
        assert len(entry["steps"]) == 1 and entry["part"] == "dot", (op, entry)
    # the static facts beside it: one row a step, in scope order
    facts = variant["steps"]
    assert [f["number"] for f in facts] == list(range(len(facts)))
    seen = {n for e in variant["ops"].values() for n in e["steps"]}
    assert seen == {f["number"] for f in facts}
    for f in facts:
        assert f["scope"] == f"tnc.step.{f['number']:04d}.small.block.matrix"
        assert f["elements"] > 0 and f["macs"] > 0 and f["k"] >= 1
    runs = {f["runs"] for f in facts}
    want = {
        "tnc_residual_c00": {"row"}, "tnc_residual_last": {"row"},
        "tnc_spmd_slices": {"once", "row"},
    }.get(program_kind, {"once"})
    assert runs == want
    # the ring's four steps, whichever program runs them: the prelude
    # takes plan step 0, the residual the rest
    plan = sorted(f["plan_index"] for f in facts)
    assert plan == {
        "tnc_program": [0, 1, 2, 3], "tnc_program_batched": [0, 1, 2, 3],
        "tnc_prelude": [0], "tnc_spmd_slices": [0, 1, 2, 3],
    }.get(program_kind, plan)
    assert set(plan) <= {0, 1, 2, 3}


def _forget_traced_programs():
    """Drop every cache that would hand back a function traced before:
    the next `_lowered` traces anew."""
    from tnc_tpu.ops import backends, chunked

    backends._PROGRAM_JIT_CACHE.clear()
    chunked._PLAN_CACHE.clear()
    chunked._PRELUDE_CACHE.clear()


@pytest.mark.parametrize("program_kind", PROGRAM_KINDS)
def test_the_program_is_the_same_without_the_scopes(program_kind, monkeypatch):
    """The scopes are metadata: with every one patched to a no-op the
    lowered text without debug info is byte for byte the same."""
    import contextlib

    from tnc_tpu.obs import op_table

    _forget_traced_programs()
    with_scopes = _lowered(program_kind)
    assert "tnc.step." in with_scopes.as_text(debug_info=True)
    monkeypatch.setattr(
        op_table, "named_scope", lambda name: contextlib.nullcontext()
    )
    _forget_traced_programs()
    without = _lowered(program_kind)
    assert "tnc.step." not in without.as_text(debug_info=True)
    assert "tnc.slice." not in without.as_text(debug_info=True)
    assert without.as_text() == with_scopes.as_text()
    _forget_traced_programs()
