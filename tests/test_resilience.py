"""tnc_tpu.resilience: classification, retry, fault injection,
slice-range checkpoint/resume, and the OOM degradation ladder.

Pins the subsystem's contracts:

- exception classification (TRANSIENT / RESOURCE / FATAL) including the
  injected-fault types and wrapped causes;
- RetryPolicy semantics — transient retried, resource/fatal re-raised,
  exhaustion raises :class:`RetryExhaustedError` carrying the attempt
  count and chaining the original error;
- a chunked run killed mid-range and restarted with a checkpoint is
  **bit-identical** to an uninterrupted run (same for the numpy oracle);
- injected RESOURCE_EXHAUSTED walks the degradation ladder (batch
  shrink → finer slicing) and still returns the correct amplitude, with
  every rung visible as obs counters;
- a failed partition raises an error naming the partition and device;
- with all resilience env vars unset, the fault-point and checkpoint
  hooks cost nothing measurable on the hot path (overhead pin, like
  ``test_obs.py``'s disabled-span bound).
"""

import os
import time

import numpy as np
import pytest

import tnc_tpu.obs as obs
from tnc_tpu.obs.core import MetricsRegistry
from tnc_tpu.resilience import (
    FailureClass,
    RetryExhaustedError,
    RetryPolicy,
    SliceCheckpoint,
    classify_exception,
    classify_pool_failure,
    configure_retry,
    execute_sliced_resilient,
    resolve_ckpt,
    signature_hash,
)
from tnc_tpu.resilience import faultinject as fi


@pytest.fixture
def fast_retry():
    """Zero-backoff default policy; restores the env-derived default."""
    configure_retry(RetryPolicy(max_attempts=3, base_delay_s=0.0))
    yield
    configure_retry(None)


@pytest.fixture
def enabled_obs():
    reg = obs.configure(enabled=True, registry=MetricsRegistry())
    try:
        yield reg
    finally:
        obs.configure(enabled=False, registry=MetricsRegistry())


def _ring_sliced_program(dims=(2, 2), slice_dims=(4, 4), seed=0):
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.slicing import Slicing
    from tnc_tpu.ops.sliced import build_sliced_program
    from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
    from tnc_tpu.tensornetwork.tensordata import TensorData

    rng = np.random.default_rng(seed)

    def mk(legs):
        return LeafTensor(
            legs, [4] * len(legs),
            TensorData.matrix(rng.standard_normal([4] * len(legs))),
        )

    ring = CompositeTensor([mk([0, 1]), mk([1, 2]), mk([2, 3]), mk([3, 0])])
    path = ContractionPath.simple([(0, 3), (0, 1), (0, 2)])
    sp = build_sliced_program(ring, path, Slicing(dims, slice_dims))
    arrays = [t.data.into_data() for t in ring.tensors]
    return ring, path, sp, arrays


_CHUNK_KW = dict(
    batch=4, chunk_steps=2, split_complex=False, precision=None,
    dtype="complex64",
)


# -- classification -----------------------------------------------------


@pytest.mark.parametrize(
    "exc,want",
    [
        (RuntimeError("RESOURCE_EXHAUSTED: out of memory on device"),
         FailureClass.RESOURCE),
        (RuntimeError("Failed to allocate 2.1G"), FailureClass.RESOURCE),
        (RuntimeError("UNAVAILABLE: TPU worker preempted"),
         FailureClass.TRANSIENT),
        (RuntimeError("DEADLINE_EXCEEDED: rpc timed out"),
         FailureClass.TRANSIENT),
        (ConnectionResetError("socket closed"), FailureClass.TRANSIENT),
        (TimeoutError(), FailureClass.TRANSIENT),
        (ValueError("shape mismatch"), FailureClass.FATAL),
        (RuntimeError("INTERNAL: compiler bug"), FailureClass.FATAL),
    ],
)
def test_classify_exception(exc, want):
    assert classify_exception(exc) is want


def test_classify_oom_needs_word_boundary():
    """'oom' must not match inside 'room'/'zoom' — a fatal error whose
    message merely contains such a word must not walk the ladder."""
    assert classify_exception(
        FileNotFoundError("/tmp/zoom_cfg.json missing")
    ) is FailureClass.FATAL
    assert classify_exception(
        ValueError("no room in layout")
    ) is FailureClass.FATAL
    assert classify_exception(
        RuntimeError("OOM while allocating 2G")
    ) is FailureClass.RESOURCE


def test_classify_retry_exhausted_is_fatal():
    """Spent retry ladders must not be retried again by an outer
    boundary — nested policies would stack to max_attempts² dispatches.
    Holds for a bare exhausted error AND one wrapped by another boundary
    (its message embeds the transient text, which must not re-match)."""
    exhausted = RetryExhaustedError(
        "backend.dispatch", 3, RuntimeError("UNAVAILABLE: preempted")
    )
    assert classify_exception(exhausted) is FailureClass.FATAL
    try:
        raise RuntimeError("partition 1 on device 1 failed") from exhausted
    except RuntimeError as wrapped:
        assert classify_exception(wrapped) is FailureClass.FATAL


def test_classify_walks_cause_chain():
    try:
        try:
            raise RuntimeError("RESOURCE_EXHAUSTED: oom")
        except RuntimeError as inner:
            raise RuntimeError("wrapper") from inner
    except RuntimeError as wrapped:
        assert classify_exception(wrapped) is FailureClass.RESOURCE


def test_injected_fault_types_classify():
    assert classify_exception(
        fi.InjectedOOM("RESOURCE_EXHAUSTED: injected")
    ) is FailureClass.RESOURCE
    assert classify_exception(
        fi.InjectedTransient("UNAVAILABLE: injected")
    ) is FailureClass.TRANSIENT
    assert classify_exception(
        fi.InjectedFatal("INTERNAL: injected")
    ) is FailureClass.FATAL


# -- retry policy -------------------------------------------------------


def test_retry_transient_then_success():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionResetError("blip")
        return 42

    assert RetryPolicy(max_attempts=3, base_delay_s=0.0).run(flaky) == 42
    assert len(calls) == 3


def test_retry_exhaustion_reraises_with_attempt_count():
    orig = RuntimeError("UNAVAILABLE: preempted")

    def always():
        raise orig

    with pytest.raises(RetryExhaustedError) as ei:
        RetryPolicy(max_attempts=2, base_delay_s=0.0).run(
            always, label="unit"
        )
    assert ei.value.attempts == 2
    assert ei.value.__cause__ is orig
    assert "UNAVAILABLE: preempted" in str(ei.value)
    assert "2 attempts" in str(ei.value)


def test_retry_fatal_and_resource_reraise_immediately():
    calls = []

    def fatal():
        calls.append(1)
        raise ValueError("bug")

    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=5, base_delay_s=0.0).run(fatal)
    assert len(calls) == 1

    calls.clear()

    def oom():
        calls.append(1)
        raise RuntimeError("RESOURCE_EXHAUSTED: oom")

    with pytest.raises(RuntimeError):
        RetryPolicy(max_attempts=5, base_delay_s=0.0).run(oom)
    assert len(calls) == 1  # degrading is the caller's job, not retrying


def test_retry_counters_visible(enabled_obs):
    def flaky(calls=[]):
        calls.append(1)
        if len(calls) < 2:
            raise ConnectionResetError("blip")
        return 1

    RetryPolicy(max_attempts=2, base_delay_s=0.0).run(flaky, label="unit")
    c = obs.counters_by_prefix("resilience.retry")
    assert c["resilience.retry.attempts{site=unit}"] == 1.0


def test_classify_pool_failure_decisions(caplog):
    import logging

    log = logging.getLogger("test.pool")
    with caplog.at_level(logging.WARNING, logger="test.pool"):
        assert classify_pool_failure(
            TimeoutError("worker hung"), log, "test pool", can_retry=True
        ) is True
        assert classify_pool_failure(
            ValueError("bad pickle"), log, "test pool", can_retry=True
        ) is False
        assert classify_pool_failure(
            TimeoutError("again"), log, "test pool", can_retry=False
        ) is False
    text = caplog.text
    assert "recreating the pool and retrying once" in text
    assert "falling back to serial evaluation" in text
    assert "bad pickle" in text  # the real worker error is logged


# -- fault injection ----------------------------------------------------


def test_faultinject_dsl_parse_and_fire():
    rules = fi.parse_spec(
        "chunked.batch(start=8, batch=4)=oom*2; partition.local=fatal"
    )
    assert rules[0].site == "chunked.batch"
    assert rules[0].conds == {"start": "8", "batch": "4"}
    assert rules[0].kind == "oom" and rules[0].remaining == 2
    assert rules[1].remaining == 1

    with fi.faults("x.y(k=1)=transient*1"):
        fi.fault_point("x.y", k=2)  # condition mismatch: no fire
        with pytest.raises(fi.InjectedTransient):
            fi.fault_point("x.y", k=1)
        fi.fault_point("x.y", k=1)  # count exhausted


def test_faultinject_bad_specs_raise():
    for bad in ("site-only", "a.b=frobnicate", "(x=1)=oom", "a.b(x)=oom"):
        with pytest.raises(ValueError):
            fi.parse_spec(bad)


def test_faultinject_disabled_is_noop():
    assert not fi.enabled()
    fi.fault_point("anything", x=1)  # must not raise


# -- checkpoint ---------------------------------------------------------


def test_checkpoint_roundtrip_and_signature_check(tmp_path, caplog):
    ck = SliceCheckpoint(tmp_path, "sig-a", every=1)
    assert ck.load() is None
    arrays = [np.arange(6.0).reshape(2, 3),
              np.ones(2, dtype=np.complex128) * (1 + 2j)]
    assert ck.maybe_save(5, lambda: arrays) is True
    cursor, got = SliceCheckpoint(tmp_path, "sig-a").load()
    assert cursor == 5
    assert np.array_equal(got[0], arrays[0])
    assert np.array_equal(got[1], arrays[1])
    # signature mismatch: fresh start, not a crash
    assert SliceCheckpoint(tmp_path, "sig-OTHER").load() is None
    # corrupt file: fresh start
    files = list(tmp_path.glob("ckpt_*.npz"))
    files[0].write_bytes(b"garbage")
    assert SliceCheckpoint(tmp_path, "sig-a").load() is None


def test_checkpoint_finalize_removes_file(tmp_path):
    ck = SliceCheckpoint(tmp_path, "sig", every=1)
    ck.save(1, [np.zeros(2)])
    assert list(tmp_path.glob("ckpt_*.npz"))
    ck.finalize()
    assert not list(tmp_path.glob("ckpt_*.npz"))
    ck.finalize()  # idempotent


def test_checkpoint_cadence(tmp_path):
    ck = SliceCheckpoint(tmp_path, "sig", every=4)
    materialized = []

    def arrays():
        materialized.append(1)
        return [np.zeros(1)]

    assert ck.maybe_save(2, arrays) is False
    assert not materialized  # accumulator not fetched off-cadence
    assert ck.maybe_save(4, arrays) is True
    assert ck.maybe_save(6, arrays) is False
    assert ck.maybe_save(8, arrays) is True


def test_resolve_ckpt_env_and_arg(monkeypatch):
    monkeypatch.delenv("TNC_TPU_CKPT", raising=False)
    assert resolve_ckpt(None) is None
    assert resolve_ckpt("/x") == "/x"
    monkeypatch.setenv("TNC_TPU_CKPT", "/env")
    assert resolve_ckpt(None) == "/env"
    assert resolve_ckpt("/arg") == "/arg"
    assert signature_hash("a", 1) != signature_hash("a", 2)


# -- chunked executor: kill/resume bit-identical ------------------------


def test_chunked_checkpoint_resume_bit_identical(tmp_path, monkeypatch):
    from tnc_tpu.ops.chunked import execute_sliced_batched_jax

    monkeypatch.setenv("TNC_TPU_CKPT_EVERY", "1")
    _, _, sp, arrays = _ring_sliced_program()
    golden = execute_sliced_batched_jax(sp, arrays, **_CHUNK_KW)

    ckpt = str(tmp_path / "ck")
    with fi.faults("chunked.batch(start=8)=fatal"):
        with pytest.raises(fi.InjectedFatal):
            execute_sliced_batched_jax(sp, arrays, ckpt=ckpt, **_CHUNK_KW)
    assert list((tmp_path / "ck").glob("ckpt_*.npz")), "no checkpoint left"

    resumed = execute_sliced_batched_jax(sp, arrays, ckpt=ckpt, **_CHUNK_KW)
    assert np.array_equal(np.asarray(resumed), np.asarray(golden)), (
        "resumed run must be bit-identical to uninterrupted"
    )
    # completed run deletes its checkpoint
    assert not list((tmp_path / "ck").glob("ckpt_*.npz"))


def test_chunked_checkpoint_resume_split_complex(tmp_path, monkeypatch):
    from tnc_tpu.ops.chunked import execute_sliced_batched_jax

    monkeypatch.setenv("TNC_TPU_CKPT_EVERY", "1")
    _, _, sp, arrays = _ring_sliced_program()
    kw = dict(batch=4, chunk_steps=2, split_complex=True,
              precision="float32", dtype="complex64")
    golden = execute_sliced_batched_jax(sp, arrays, **kw)
    ckpt = str(tmp_path / "ck")
    with fi.faults("chunked.batch(start=4)=fatal"):
        with pytest.raises(fi.InjectedFatal):
            execute_sliced_batched_jax(sp, arrays, ckpt=ckpt, **kw)
    resumed = execute_sliced_batched_jax(sp, arrays, ckpt=ckpt, **kw)
    assert np.array_equal(np.asarray(resumed), np.asarray(golden))


def test_chunked_env_gated_checkpoint(tmp_path, monkeypatch):
    from tnc_tpu.ops.chunked import execute_sliced_batched_jax

    monkeypatch.setenv("TNC_TPU_CKPT", str(tmp_path / "envck"))
    monkeypatch.setenv("TNC_TPU_CKPT_EVERY", "1")
    _, _, sp, arrays = _ring_sliced_program()
    with fi.faults("chunked.batch(start=12)=fatal"):
        with pytest.raises(fi.InjectedFatal):
            execute_sliced_batched_jax(sp, arrays, **_CHUNK_KW)
    assert list((tmp_path / "envck").glob("ckpt_*.npz"))


def test_chunked_resume_from_unaligned_cursor(tmp_path, monkeypatch):
    """A run that degraded its batch mid-range can leave a cursor that
    is not a multiple of the original batch; the resume must keep the
    requested batch and handle the odd head/tail ranges correctly."""
    from tnc_tpu.ops.chunked import execute_sliced_batched_jax
    from tnc_tpu.ops.sliced import execute_sliced_numpy

    monkeypatch.setenv("TNC_TPU_CKPT_EVERY", "1")
    _, _, sp, arrays = _ring_sliced_program()
    oracle = execute_sliced_numpy(sp, arrays)
    ckpt = str(tmp_path / "ck")
    # OOM at the first batch degrades 4 -> 2, then a fatal at cursor 10
    # (unaligned to batch 4) kills the run mid-range
    with fi.faults("chunked.batch(start=0)=oom; chunked.batch(start=10)=fatal"):
        with pytest.raises(fi.InjectedFatal):
            execute_sliced_batched_jax(sp, arrays, ckpt=ckpt, **_CHUNK_KW)
    resumed = execute_sliced_batched_jax(sp, arrays, ckpt=ckpt, **_CHUNK_KW)
    assert np.allclose(np.asarray(resumed), oracle, atol=1e-4)


def test_checkpoint_not_resumed_across_different_input_data(
    tmp_path, monkeypatch
):
    """The program signature is structural — the same circuit contracted
    over different leaf data (e.g. another bitstring) shares it. The
    data digest in the checkpoint signature must keep run B from
    resuming run A's accumulator."""
    from tnc_tpu.ops.chunked import execute_sliced_batched_jax
    from tnc_tpu.ops.sliced import execute_sliced_numpy

    monkeypatch.setenv("TNC_TPU_CKPT_EVERY", "1")
    _, _, sp_a, arrays_a = _ring_sliced_program(seed=0)
    _, _, sp_b, arrays_b = _ring_sliced_program(seed=1)  # same structure
    assert sp_a.signature() == sp_b.signature()
    ckpt = str(tmp_path / "ck")
    with fi.faults("chunked.batch(start=8)=fatal"):
        with pytest.raises(fi.InjectedFatal):
            execute_sliced_batched_jax(sp_a, arrays_a, ckpt=ckpt, **_CHUNK_KW)
    assert list((tmp_path / "ck").glob("ckpt_*.npz"))
    # run B with A's checkpoint present: must start fresh and be correct
    out_b = execute_sliced_batched_jax(sp_b, arrays_b, ckpt=ckpt, **_CHUNK_KW)
    oracle_b = execute_sliced_numpy(sp_b, arrays_b)
    assert np.allclose(np.asarray(out_b), oracle_b, atol=1e-4)


def test_sync_dispatch_env_keeps_results_correct(monkeypatch):
    """TNC_TPU_SYNC_DISPATCH=1 (surface async device errors inside the
    retry scope) must not change results."""
    from tnc_tpu.ops.chunked import execute_sliced_batched_jax
    from tnc_tpu.ops.sliced import execute_sliced_numpy

    _, _, sp, arrays = _ring_sliced_program()
    oracle = execute_sliced_numpy(sp, arrays)
    monkeypatch.setenv("TNC_TPU_SYNC_DISPATCH", "1")
    out = execute_sliced_batched_jax(sp, arrays, **_CHUNK_KW)
    assert np.allclose(np.asarray(out), oracle, atol=1e-4)


def test_numpy_checkpoint_resume_bit_identical(tmp_path, monkeypatch):
    from tnc_tpu.ops.sliced import execute_sliced_numpy

    monkeypatch.setenv("TNC_TPU_CKPT_EVERY", "1")
    _, _, sp, arrays = _ring_sliced_program()
    golden = execute_sliced_numpy(sp, arrays)
    ckpt = str(tmp_path / "ck")
    with fi.faults("sliced.slice(s=9)=fatal"):
        with pytest.raises(fi.InjectedFatal):
            execute_sliced_numpy(sp, arrays, ckpt=ckpt)
    resumed = execute_sliced_numpy(sp, arrays, ckpt=ckpt)
    assert np.array_equal(resumed, golden)


# -- degradation ladder -------------------------------------------------


def test_injected_oom_shrinks_batch_and_completes(enabled_obs):
    from tnc_tpu.ops.chunked import execute_sliced_batched_jax
    from tnc_tpu.ops.sliced import execute_sliced_numpy

    _, _, sp, arrays = _ring_sliced_program()
    oracle = execute_sliced_numpy(sp, arrays)
    with fi.faults("chunked.batch=oom*2"):
        out = execute_sliced_batched_jax(sp, arrays, **_CHUNK_KW)
    assert np.allclose(np.asarray(out), oracle, atol=1e-4)
    c = enabled_obs.counters()
    assert c[("resilience.degrade.batch_shrink", ())] == 2.0
    assert enabled_obs.gauges()[("resilience.degrade.batch", ())] == 1.0
    assert obs.counters_by_prefix("resilience.faults")


def test_full_ladder_replans_and_returns_correct_amplitude(
    enabled_obs, fast_retry
):
    from tnc_tpu.ops.backends import JaxBackend
    from tnc_tpu.ops.sliced import execute_sliced_numpy

    ring, path, sp, arrays = _ring_sliced_program(dims=(2,), slice_dims=(4,))
    oracle = execute_sliced_numpy(sp, arrays)
    backend = JaxBackend(
        dtype="complex64", slice_batch=2,
        split_complex=False,
    )
    # exhaust the batch-shrink rung (2 -> 1 -> raise), then the replan
    # rung executes a re-sliced program and the fault budget is spent
    with fi.faults("chunked.batch=oom*3"):
        out, used_slicing = execute_sliced_resilient(
            ring, path, sp.slicing, backend=backend
        )
    got = complex(np.asarray(out).reshape(-1)[0])
    want = complex(np.asarray(oracle).reshape(-1)[0])
    assert abs(got - want) <= 1e-4 * max(abs(want), 1.0)
    c = enabled_obs.counters()
    assert c[("resilience.degrade.batch_shrink", ())] >= 1.0
    assert c[("resilience.ladder.replans", ())] == 1.0


def test_ladder_spent_reraises_the_resource_error(enabled_obs, fast_retry):
    """Both rungs spent (every batch size of every re-sliced program
    fails): the resource error reaches the caller, after exactly
    ``max_replans`` re-slicings and no other executor."""
    from tnc_tpu.ops.backends import JaxBackend

    ring, path, sp, _ = _ring_sliced_program(dims=(2,), slice_dims=(4,))
    backend = JaxBackend(dtype="complex64", slice_batch=2, split_complex=False)
    # a re-sliced program may come back unsliced: it then runs whole
    with fi.faults("chunked.batch=oom*-1;backend.dispatch=oom*-1"):
        with pytest.raises(fi.InjectedOOM):
            execute_sliced_resilient(
                ring, path, sp.slicing, backend=backend, max_replans=1
            )
    c = enabled_obs.counters()
    assert c[("resilience.ladder.replans", ())] == 1.0
    assert not obs.counters_by_prefix("resilience.ladder.fallback")


def test_ladder_reraises_fatal_untouched(fast_retry):
    from tnc_tpu.ops.backends import JaxBackend

    ring, path, sp, _ = _ring_sliced_program(dims=(2,), slice_dims=(4,))
    backend = JaxBackend(
        dtype="complex64", slice_batch=2,
        split_complex=False,
    )
    with fi.faults("chunked.batch=fatal*99"):
        with pytest.raises(fi.InjectedFatal):
            execute_sliced_resilient(ring, path, sp.slicing, backend=backend)


def test_transient_retry_exhaustion_in_chunked(fast_retry):
    from tnc_tpu.ops.chunked import execute_sliced_batched_jax

    _, _, sp, arrays = _ring_sliced_program()
    configure_retry(RetryPolicy(max_attempts=2, base_delay_s=0.0))
    with fi.faults("chunked.batch=transient*99"):
        with pytest.raises(RetryExhaustedError) as ei:
            execute_sliced_batched_jax(sp, arrays, **_CHUNK_KW)
    assert ei.value.attempts == 2
    assert "UNAVAILABLE" in str(ei.value.__cause__)


def test_no_retry_once_donated_buffers_are_consumed(
    enabled_obs, fast_retry
):
    """A transient failure after a donating dispatch consumed its inputs
    must NOT be retried — re-dispatching deleted arrays would mask the
    original error with 'Array has been deleted'."""
    import jax.numpy as jnp

    from tnc_tpu.ops.backends import jit_program
    from tnc_tpu.ops.program import build_program

    ring, path, _, arrays = _ring_sliced_program()
    program = build_program(ring, path)
    fn = jit_program(program, split_complex=False, precision=None,
                     donate=True)
    bufs = [jnp.asarray(a, dtype="complex64") for a in arrays]
    fn(list(bufs))
    # whether XLA found the donation usable is shape-dependent; force
    # the consumed state the guard protects against
    bufs[0].delete()
    assert bufs[0].is_deleted()
    with fi.faults("backend.dispatch=transient*5"):
        with pytest.raises(fi.InjectedTransient):
            fn(list(bufs))
    assert not obs.counters_by_prefix("resilience.retry.attempts"), (
        "must not retry a dispatch whose donated inputs are gone"
    )


# -- partitioned executor -----------------------------------------------


def _partitioned_network():
    import random

    from tnc_tpu.contractionpath.repartitioning import compute_solution
    from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
    from tnc_tpu.tensornetwork.tensordata import TensorData

    rng = np.random.default_rng(3)

    def mk(legs):
        return LeafTensor(
            legs, [4] * len(legs),
            TensorData.matrix(rng.standard_normal([4] * len(legs))),
        )

    tn = CompositeTensor([mk([0, 1]), mk([1, 2]), mk([2, 3]), mk([3, 0])])
    ptn, ppath, _, _ = compute_solution(
        tn, [0, 0, 1, 1], rng=random.Random(0)
    )
    return ptn, ppath


def test_partition_failure_names_partition_and_device(fast_retry):
    from tnc_tpu.parallel import (
        PartitionExecutionError,
        distributed_partitioned_contraction,
    )

    ptn, ppath = _partitioned_network()
    with fi.faults("partition.local(partition=1)=fatal*99"):
        with pytest.raises(PartitionExecutionError) as ei:
            distributed_partitioned_contraction(ptn, ppath, n_devices=2)
    assert ei.value.partition == 1
    assert "partition 1" in str(ei.value)
    assert "device" in str(ei.value)
    assert ei.value.__cause__ is ei.value.original


def test_partition_transient_is_retried_in_place(fast_retry, enabled_obs):
    from tnc_tpu.parallel import distributed_partitioned_contraction

    ptn, ppath = _partitioned_network()
    golden = distributed_partitioned_contraction(ptn, ppath, n_devices=2)
    with fi.faults("partition.local(partition=0)=transient*1"):
        out = distributed_partitioned_contraction(ptn, ppath, n_devices=2)
    assert np.allclose(
        out.data.into_data(), golden.data.into_data(), atol=1e-5
    )
    c = obs.counters_by_prefix("resilience.retry.attempts")
    assert c["resilience.retry.attempts{site=partition.local}"] == 1.0


def test_spmd_transient_is_retried(fast_retry):
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.slicing import find_slicing
    from tnc_tpu.parallel import distributed_sliced_contraction
    from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
    from tnc_tpu.tensornetwork.tensordata import TensorData

    rng = np.random.default_rng(0)
    ts = [
        LeafTensor([0, 1], [4, 4],
                   TensorData.matrix(rng.standard_normal((4, 4)))),
        LeafTensor([1, 2], [4, 4],
                   TensorData.matrix(rng.standard_normal((4, 4)))),
        LeafTensor([2, 0], [4, 4],
                   TensorData.matrix(rng.standard_normal((4, 4)))),
    ]
    tn = CompositeTensor([t.copy() for t in ts])
    path = ContractionPath.simple([(0, 1), (0, 2)])
    slicing = find_slicing(ts, path.toplevel, target_size=12)
    with fi.faults("spmd.dispatch=transient*1"):
        out = distributed_sliced_contraction(tn, path, slicing, n_devices=1)
    a, b, c = (t.data.into_data() for t in ts)
    want = np.einsum("ab,bc,ca->", a, b, c)
    got = complex(np.asarray(out.data.into_data()).reshape(-1)[0])
    assert abs(got - want) <= 1e-5 * abs(want)


# -- protocol: within-cell resume ---------------------------------------


def test_protocol_requeues_crashed_cell_with_checkpoint(tmp_path):
    from tnc_tpu.benchmark.protocol import Protocol, cell_checkpoint_dir

    journal = tmp_path / "protocol.jsonl"
    ckroot = tmp_path / "ckpt"
    proto = Protocol(journal, checkpoint_dir=ckroot)
    proto.trying("run-jax/cell-a")
    proto.trying("run-jax/cell-b")
    # cell-a crashed mid-range leaving a checkpoint; cell-b left nothing
    cell = cell_checkpoint_dir(ckroot, "run-jax/cell-a")
    cell.mkdir(parents=True)
    (cell / "ckpt_0123.npz").write_bytes(b"x")

    back = Protocol(journal, checkpoint_dir=ckroot)
    assert back.should_run("run-jax/cell-a"), "checkpointed cell requeued"
    assert back.resumable == {"run-jax/cell-a"}
    assert not back.should_run("run-jax/cell-b")
    assert "run-jax/cell-b" in back.failed

    # finishing the resumed cell clears it
    back.trying("run-jax/cell-a")
    back.done("run-jax/cell-a")
    final = Protocol(journal, checkpoint_dir=ckroot)
    assert not final.should_run("run-jax/cell-a")
    assert "run-jax/cell-a" in final.completed


def test_protocol_resume_budget_bounds_requeues(tmp_path):
    """A cell that crashes deterministically after its first checkpoint
    must eventually land in `failed` — not be requeued on every restart
    forever (the journal's original anti-wedge invariant)."""
    from tnc_tpu.benchmark.protocol import Protocol, cell_checkpoint_dir

    journal = tmp_path / "protocol.jsonl"
    ckroot = tmp_path / "ckpt"
    cell = cell_checkpoint_dir(ckroot, "run-jax/crasher")
    cell.mkdir(parents=True)
    (cell / "ckpt_0123.npz").write_bytes(b"x")

    Protocol(journal, checkpoint_dir=ckroot).trying("run-jax/crasher")
    for _ in range(2):  # two crash/restart cycles within the budget
        p = Protocol(journal, checkpoint_dir=ckroot, max_resumes=2)
        assert p.should_run("run-jax/crasher")
        p.trying("run-jax/crasher")  # ... crashes again
    spent = Protocol(journal, checkpoint_dir=ckroot, max_resumes=2)
    assert not spent.should_run("run-jax/crasher")
    assert "run-jax/crasher" in spent.failed


def test_protocol_loads_alone_do_not_burn_resume_budget(tmp_path):
    """Constructing the Protocol (e.g. sweeps filtered to other cells)
    must not spend the resume budget — only an actual re-run attempt
    (`trying` on a resumable cell) does."""
    from tnc_tpu.benchmark.protocol import Protocol, cell_checkpoint_dir

    journal = tmp_path / "protocol.jsonl"
    ckroot = tmp_path / "ckpt"
    cell = cell_checkpoint_dir(ckroot, "cell-y")
    cell.mkdir(parents=True)
    (cell / "ckpt_0.npz").write_bytes(b"x")
    Protocol(journal, checkpoint_dir=ckroot).trying("cell-y")
    for _ in range(5):  # unrelated loads, no re-run
        p = Protocol(journal, checkpoint_dir=ckroot, max_resumes=2)
        assert p.should_run("cell-y")
    assert "cell-y" in p.resumable


def test_pool_map_with_retry_rebuilds_once_then_serial(caplog):
    import logging

    from tnc_tpu.resilience import pool_map_with_retry

    class FakePool:
        def __init__(self, fail):
            self.fail = fail
            self.terminated = False

        def terminate(self):
            self.terminated = True

    log = logging.getLogger("test.poolmap")
    built = []

    def rebuild():
        built.append(1)
        return FakePool(fail=False)

    def submit(pool):
        if pool.fail:
            raise TimeoutError("worker hung")
        return [1, 2, 3]

    # transient failure: old pool terminated, fresh pool retried once
    first = FakePool(fail=True)
    results, pool = pool_map_with_retry(
        first, submit, rebuild, log, "test pool"
    )
    assert results == [1, 2, 3] and first.terminated and len(built) == 1
    assert pool is not first

    # fatal failure: straight to serial, no rebuild
    built.clear()
    results, pool = pool_map_with_retry(
        FakePool(fail=False),
        lambda p: (_ for _ in ()).throw(ValueError("bad pickle")),
        rebuild, log, "test pool",
    )
    assert results is None and pool is None and not built


def test_pool_map_with_retry_rebuild_failure_degrades_to_serial(caplog):
    """A pool respawn failing (fork/fd exhaustion — the same pressure
    that wedged the first pool) must fall back to serial, not crash."""
    import logging

    from tnc_tpu.resilience import pool_map_with_retry

    class FakePool:
        def terminate(self):
            pass

    def submit(pool):
        raise TimeoutError("worker hung")

    def rebuild():
        raise OSError("fork failed")

    log = logging.getLogger("test.poolmap")
    with caplog.at_level(logging.WARNING, logger="test.poolmap"):
        results, pool = pool_map_with_retry(
            FakePool(), submit, rebuild, log, "test pool"
        )
    assert results is None and pool is None
    assert "rebuild failed" in caplog.text


def test_protocol_without_checkpoint_dir_keeps_old_semantics(tmp_path):
    from tnc_tpu.benchmark.protocol import Protocol

    journal = tmp_path / "p.jsonl"
    proto = Protocol(journal)
    proto.trying("cell-1")
    back = Protocol(journal)
    assert not back.should_run("cell-1")
    assert "cell-1" in back.failed


# -- disabled-path overhead ---------------------------------------------


def test_disabled_resilience_hooks_overhead(monkeypatch):
    """With all resilience env vars unset, the fault-point hook on the
    hot path and the checkpoint gate must cost nothing measurable —
    the same acceptance bound as obs' disabled-span pin."""
    monkeypatch.delenv("TNC_TPU_FAULTS", raising=False)
    monkeypatch.delenv("TNC_TPU_CKPT", raising=False)
    fi.refresh_from_env()
    assert not fi.enabled()

    n = 20_000

    def timed(fn):
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    def run_fault_points():
        for i in range(n):
            fi.fault_point("hot.site", start=i)

    def run_ckpt_gate():
        for _ in range(n):
            resolve_ckpt(None)

    per_fault = timed(run_fault_points) / n
    per_gate = timed(run_ckpt_gate) / n
    assert per_fault < 10e-6, f"fault_point costs {per_fault*1e9:.0f} ns"
    assert per_gate < 10e-6, f"resolve_ckpt costs {per_gate*1e9:.0f} ns"


def test_no_checkpoint_files_written_when_unset(tmp_path, monkeypatch):
    from tnc_tpu.ops.chunked import execute_sliced_batched_jax

    monkeypatch.delenv("TNC_TPU_CKPT", raising=False)
    monkeypatch.chdir(tmp_path)
    _, _, sp, arrays = _ring_sliced_program()
    execute_sliced_batched_jax(sp, arrays, **_CHUNK_KW)
    assert not list(tmp_path.rglob("ckpt_*.npz"))
