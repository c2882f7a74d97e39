"""The resident-leaf store behind ``place_buffers``: each leaf is placed
on the device once per content, and a stored buffer is never donated."""

import sys
import threading

import numpy as np
import pytest

from tnc_tpu import obs
from tnc_tpu.ops import backends
from tnc_tpu.ops.backends import (
    JaxBackend,
    NumpyBackend,
    ResidentLeaves,
    place_buffers,
)
from tnc_tpu.ops.split_complex import combine_array

SPLIT = pytest.mark.parametrize("split", [False, True], ids=["complex", "split"])


@pytest.fixture(autouse=True)
def store(monkeypatch):
    """A store of this test's own: the process-wide one is shared with
    every other test of the worker."""
    fresh = ResidentLeaves()
    monkeypatch.setattr(backends, "RESIDENT_LEAVES", fresh)
    return fresh


def placing(fn):
    """``fn()`` and what ``backend.place_buffers`` totalled inside it."""
    with obs.collect_phases() as phases:
        out = fn()
    return out, {
        key.rsplit(".", 1)[1]: value
        for key, value in phases.items()
        if key.startswith("backend.place_buffers.")
    }


def gates(seed, n=6, shape=(2, 2)):
    rng = np.random.default_rng(seed)
    return [
        rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
        for _ in range(n)
    ]


def host(buffer, split):
    return combine_array(*buffer) if split else np.asarray(buffer)


def same_buffer(a, b, split):
    return (a[0] is b[0] and a[1] is b[1]) if split else a is b


def bound_circuit(seed=0):
    from tests.test_serve import make_circuit
    from tnc_tpu.serve import bind_circuit

    return bind_circuit(make_circuit(seed=seed))


def ring_sliced(seed):
    from tests.test_profiler_spans import _ring
    from tnc_tpu.contractionpath.slicing import Slicing

    ts, tn, path = _ring(seed)
    return ts, tn, path, Slicing((3, 4), (4, 4))


# -- the store's rule ------------------------------------------------------


@SPLIT
def test_equal_content_from_another_object_hits(split, store):
    arrays = gates(1)
    first, counts = placing(lambda: place_buffers(arrays, "complex128", split))
    assert counts == {
        "placed": 6, "hits": 0, "bytes": sum(a.nbytes for a in arrays)
    }
    copies = [a.copy() for a in arrays]
    second, counts = placing(lambda: place_buffers(copies, "complex128", split))
    assert counts == {"placed": 0, "hits": 6, "bytes": 0}
    for a, b, want in zip(first, second, arrays):
        assert same_buffer(a, b, split)
        np.testing.assert_array_equal(host(b, split), want)
    assert len(store) == 6
    assert store.total_bytes == sum(a.nbytes for a in arrays)


@SPLIT
def test_equal_leaves_of_one_call_share_a_buffer(split, store):
    gate = gates(2, n=1)[0]
    out = place_buffers([gate, gate.copy(), gate.copy()], "complex128", split)
    assert same_buffer(out[0], out[1], split)
    assert same_buffer(out[0], out[2], split)
    assert len(store) == 1


@SPLIT
def test_key_covers_dtype_split_flag_and_target(split, store):
    import jax

    gate = gates(3, n=1)
    place_buffers(gate, "complex128", split)
    for dtype, flag, device in [
        ("complex64", split, None),
        ("complex128", not split, None),
        ("complex128", split, jax.devices()[1]),
    ]:
        out, counts = placing(
            lambda: place_buffers(gate, dtype, flag, device)
        )
        assert counts["placed"] == 1, (dtype, flag, device)
        np.testing.assert_allclose(host(out[0], flag), gate[0], rtol=1e-6)
    assert len(store) == 4
    (on_one,) = place_buffers(gate, "complex128", split, jax.devices()[1])
    part = on_one[0] if split else on_one
    assert part.devices() == {jax.devices()[1]}


@SPLIT
def test_in_place_edit_misses_and_the_result_follows(split):
    bp = bound_circuit()
    backend = JaxBackend(dtype="complex128", split_complex=split, donate=False)
    arrays = [np.array(a) for a in bp.arrays]
    before = backend.execute(bp.program, arrays)
    slot = next(
        s for s in range(len(arrays))
        if s not in bp.bra_slots and arrays[s].size == 4
    )
    arrays[slot] *= 0.5  # same object, other bytes
    after, counts = placing(lambda: backend.execute(bp.program, arrays))
    assert counts["placed"] == 1 and counts["hits"] == len(arrays) - 1
    np.testing.assert_allclose(after, 0.5 * before, rtol=1e-12)
    np.testing.assert_allclose(
        after, NumpyBackend().execute(bp.program, arrays), atol=1e-12
    )


@SPLIT
def test_transient_slots_never_enter_the_store(split, store):
    arrays = gates(4)
    for _ in range(2):
        out, counts = placing(
            lambda: place_buffers(arrays, "complex128", split, None, [1, 4])
        )
    assert counts["placed"] == 2 and counts["hits"] == 4
    assert counts["bytes"] == arrays[1].nbytes + arrays[4].nbytes
    assert len(store) == 4
    stored = [
        part for buf, _ in store._entries.values()
        for part in (buf if split else (buf,))
    ]
    for slot in (1, 4):
        for part in out[slot] if split else (out[slot],):
            assert not any(part is s for s in stored)
        np.testing.assert_array_equal(host(out[slot], split), arrays[slot])


@SPLIT
def test_leaf_over_the_size_limit_bypasses(split, monkeypatch):
    small = ResidentLeaves(max_leaf_bytes=4 * 16)
    monkeypatch.setattr(backends, "RESIDENT_LEAVES", small)
    arrays = gates(5, n=2) + gates(5, n=1, shape=(2, 4))
    for _ in range(2):
        out, counts = placing(lambda: place_buffers(arrays, "complex128", split))
    assert counts == {"placed": 1, "hits": 2, "bytes": arrays[2].nbytes}
    assert len(small) == 2
    np.testing.assert_array_equal(host(out[2], split), arrays[2])
    # the process-wide limits: a megabyte a leaf, far under 1 % of HBM
    assert ResidentLeaves.MAX_LEAF_BYTES == 1 << 20
    assert ResidentLeaves.MAX_TOTAL_BYTES <= 0.01 * 16e9


@SPLIT
def test_digest_keys_above_the_inline_size(split, store):
    big = gates(6, n=1, shape=(8, 8))  # 1 KiB: keyed by digest
    place_buffers(big, "complex128", split)
    (key,) = store._entries
    assert len(key[2]) == 16 < big[0].nbytes
    edited = [big[0].copy()]
    _, counts = placing(lambda: place_buffers(edited, "complex128", split))
    assert counts["hits"] == 1
    edited[0][7, 7] += 1e-9
    out, counts = placing(lambda: place_buffers(edited, "complex128", split))
    assert counts["placed"] == 1
    np.testing.assert_array_equal(host(out[0], split), edited[0])


@SPLIT
def test_eviction_keeps_the_total_under_the_bound(split, monkeypatch):
    arrays = gates(7, n=8)
    size = arrays[0].nbytes
    small = ResidentLeaves(max_total_bytes=3 * size)
    monkeypatch.setattr(backends, "RESIDENT_LEAVES", small)
    for a in arrays:
        place_buffers([a], "complex128", split)
        assert small.total_bytes <= 3 * size
    assert len(small) == 3 and small.total_bytes == 3 * size
    # the newest three stayed; the oldest was evicted and is placed
    # again, correctly, on its next use
    _, counts = placing(lambda: place_buffers(arrays[5:], "complex128", split))
    assert counts == {"placed": 0, "hits": 3, "bytes": 0}
    out, counts = placing(lambda: place_buffers(arrays[:1], "complex128", split))
    assert counts == {"placed": 1, "hits": 0, "bytes": size}
    np.testing.assert_array_equal(host(out[0], split), arrays[0])
    assert small.total_bytes == 3 * size


@SPLIT
def test_concurrent_place_buffers_from_two_threads(split, store):
    arrays = gates(8, n=40)
    results, errors = {}, []

    def work(tid):
        try:
            for _ in range(25):
                results[tid] = place_buffers(
                    [a.copy() for a in arrays], "complex128", split
                )
        except Exception as exc:  # noqa: BLE001 — reported below
            errors.append(exc)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(t,)) for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert not errors, errors
    # one entry a content, every thread ended on the stored buffers
    assert len(store) == len(arrays)
    assert store.total_bytes == sum(a.nbytes for a in arrays)
    for tid in range(4):
        for got, ref, want in zip(results[tid], results[0], arrays):
            assert same_buffer(got, ref, split)
            np.testing.assert_array_equal(host(got, split), want)


# -- the call sites ---------------------------------------------------------


@SPLIT
@pytest.mark.parametrize("donate", [True, False], ids=["donate", "keep"])
def test_batched_twice_then_execute_on_one_backend(split, donate):
    """The donation rule: the batched executable donates its stacked
    slots only, and a donating ``execute`` places buffers of its own, so
    no call finds a resident leaf deleted."""
    bp = bound_circuit(seed=1)
    backend = JaxBackend(dtype="complex128", split_complex=split, donate=donate)
    bits = ["01101", "11010", "00000"]
    want = bp.amplitudes(bits)
    n, bras = len(bp.arrays), len(bp.bra_slots)
    first, counts = placing(lambda: bp.amplitudes(bits, backend))
    assert counts["hits"] == 0
    second, counts = placing(lambda: bp.amplitudes(bits, backend))
    assert counts["placed"] == bras and counts["hits"] == n - bras
    np.testing.assert_array_equal(first, second)
    np.testing.assert_allclose(second, want, atol=1e-12)
    # execute on the same leaves, request 0's bras bound in
    per = bp._batch_buffers(bits[:1], bp.arrays)
    for slot in bp.bra_slots:
        per[slot] = per[slot][0]
    for _ in range(2):
        single, counts = placing(lambda: backend.execute(bp.program, per))
        np.testing.assert_allclose(single, want[0], atol=1e-12)
    assert counts["placed"] == (n if donate else 0)
    third = bp.amplitudes(bits, backend)  # the resident leaves survived
    np.testing.assert_array_equal(third, second)


@SPLIT
def test_execute_sliced_range_twice_places_nothing_again(split):
    from tnc_tpu.ops.sliced import build_sliced_program

    ts, tn, path, slicing = ring_sliced(11)
    sp = build_sliced_program(tn, path, slicing)
    backend = JaxBackend(
        dtype="complex128", split_complex=split, slice_batch=4, chunk_steps=2
    )

    def call():
        # fresh host arrays each call, as TensorData.into_data() builds
        arrays = [np.array(t.data.into_data()) for t in ts]
        return backend.execute_sliced(sp, arrays, slice_range=(4, 12))

    first, counts = placing(call)
    assert counts["placed"] == len(ts) and counts["hits"] == 0
    second, counts = placing(call)
    assert counts == {"placed": 0, "hits": len(ts), "bytes": 0}
    np.testing.assert_array_equal(first, second)
    want = NumpyBackend().execute_sliced(
        sp, [t.data.into_data() for t in ts], slice_range=(4, 12)
    )
    np.testing.assert_allclose(second, want, rtol=1e-9, atol=1e-9)


@SPLIT
def test_spmd_contraction_twice_places_nothing_again(split):
    from tnc_tpu.ops.sliced import build_sliced_program, execute_sliced_numpy
    from tnc_tpu.parallel.sliced_parallel import (
        distributed_sliced_contraction,
        make_mesh,
    )

    ts, tn, path, slicing = ring_sliced(12)
    mesh = make_mesh(4)

    def call():
        out = distributed_sliced_contraction(
            tn, path, slicing, mesh=mesh, dtype="complex128",
            split_complex=split, hoist=True,
        )
        return out.data.into_data()

    first, counts = placing(call)
    assert counts["placed"] == len(ts)
    second, counts = placing(call)
    assert counts == {"placed": 0, "hits": len(ts), "bytes": 0}
    np.testing.assert_array_equal(first, second)
    sp = build_sliced_program(tn, path, slicing)
    want = execute_sliced_numpy(sp, [t.data.into_data() for t in ts])
    np.testing.assert_allclose(
        second.reshape(sp.program.result_shape), want, rtol=1e-9, atol=1e-9
    )
    # committed and replicated: every leaf lives on all four devices
    leaf, _ = next(iter(backends.RESIDENT_LEAVES._entries.values()))
    part = leaf[0] if split else leaf
    assert part.devices() == set(mesh.devices.flat)


@SPLIT
def test_partitioned_leaves_are_placed_once(split, store):
    """No partition program donates its inputs (PR 28): the leaves go
    through the store, a second call copies nothing host to device and
    gives the same answer, bit for bit."""
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.parallel.partitioned import (
        distributed_partitioned_contraction,
    )
    from tnc_tpu.tensornetwork.tensor import CompositeTensor

    ts, _, _, _ = ring_sliced(13)
    tn = CompositeTensor([
        CompositeTensor([t.copy() for t in ts[:3]]),
        CompositeTensor([t.copy() for t in ts[3:]]),
    ])
    path = ContractionPath(
        {0: ContractionPath.simple([(0, 1), (0, 2)]),
         1: ContractionPath.simple([(0, 1)])},
        [(0, 1)],
    )

    def call():
        return distributed_partitioned_contraction(
            tn, path, n_devices=2, dtype="complex128", split_complex=split
        ).data.into_data()

    first, counts = placing(call)
    second, again = placing(call)
    assert counts["hits"] == 0 and counts["placed"] == len(ts)
    assert again["hits"] == len(ts) and again["placed"] == 0
    np.testing.assert_array_equal(first, second)
    assert 0 < len(store) <= len(ts)


# -- what the service counts -------------------------------------------------


@SPLIT
def test_stats_h2d_bytes_of_a_second_batch_count_the_bras_only(split):
    from tests.test_serve import make_circuit, oracle_amplitude
    from tnc_tpu.serve import ContractionService

    svc = ContractionService.from_circuit(
        make_circuit(),
        backend=JaxBackend(dtype="complex128", split_complex=split),
        max_batch=2, max_wait_ms=2000.0,
    )
    bits = ["01101", "11010"]
    rows = []
    try:
        for _ in range(2):
            futures = [svc.submit(b) for b in bits]
            got = [f.result(timeout=120) for f in futures]
            rows.append(dict(svc.stats()["by_tier"]["exact"]["dispatch"]))
    finally:
        svc.stop()
    for b, amp in zip(bits, got):
        assert abs(amp - complex(oracle_amplitude(b).reshape(()))) < 1e-9
    first, both = rows
    assert (first["count"], both["count"]) == (1, 2)
    # 2 riders x 5 bras of (2,) complex128
    bras, bra_bytes = 5, 2 * 5 * 2 * 16
    n = first["leaves_placed"]
    assert first["leaf_hits"] == 0 and n > bras
    assert first["h2d_bytes"] > bra_bytes
    assert both["h2d_bytes"] - first["h2d_bytes"] == bra_bytes
    assert both["leaves_placed"] - n == bras
    assert both["leaf_hits"] == n - bras


def test_phase_and_counters_carry_placed_and_hits():
    reg = obs.configure(enabled=True, registry=obs.MetricsRegistry())
    try:
        arrays = gates(9)
        place_buffers(arrays, "complex128", True)
        place_buffers(arrays[:4], "complex128", True, None, [0])
        records = [
            r for r in reg.span_records()
            if r.name == "backend.place_buffers"
        ]
        counters = obs.counters_by_prefix("resident_leaves.")
    finally:
        obs.configure(enabled=False, registry=obs.MetricsRegistry())
    assert [r.args["n"] for r in records] == [6, 4]
    assert [r.args["placed"] for r in records] == [6, 1]
    assert [r.args["hits"] for r in records] == [0, 3]
    assert [r.args["bytes"] for r in records] == [6 * 64, 64]
    assert counters == {"resident_leaves.hit": 3.0, "resident_leaves.miss": 7.0}
