"""tnc_tpu.serve: rebinding, plan cache, and the serving front end.

Pins the subsystem's contracts:

- rebind-vs-oracle **bit**-equality on the numpy path: a batch of B
  bitstrings through one bound program equals B independent
  plan+compile+contract runs, bit for bit (incl. ``*`` open legs);
  split-complex serving agrees with the oracle to f32 parity;
- a plan-cache hit performs zero pathfinding (no ``plan.find_path``
  span) and zero retracing (jit cache-hit counter) for a second,
  structurally identical circuit;
- LRU eviction and corrupted-entry recovery in the on-disk plan cache;
- micro-batching, admission control, deadline expiry, and
  batch-failure → singleton degradation in :class:`ContractionService`;
- the shared digest helper is stable across Python hash seeds and dict
  orderings (subprocess-pinned).
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import tnc_tpu.obs as obs
from tnc_tpu.builders.circuit_builder import Circuit, normalize_bitstring
from tnc_tpu.contractionpath.paths import Greedy, OptMethod
from tnc_tpu.obs.core import MetricsRegistry
from tnc_tpu.ops.backends import JaxBackend, NumpyBackend
from tnc_tpu.ops.program import build_program, flat_leaf_tensors
from tnc_tpu.resilience.retry import RetryPolicy
from tnc_tpu.serve import (
    ContractionService,
    DeadlineExceededError,
    PlanCache,
    QueueFullError,
    ServiceClosedError,
    bind_circuit,
    thread_batch,
)
from tnc_tpu.tensornetwork.tensordata import TensorData


@pytest.fixture
def enabled_obs():
    reg = obs.configure(enabled=True, registry=MetricsRegistry())
    try:
        yield reg
    finally:
        obs.configure(enabled=False, registry=MetricsRegistry())


def make_circuit(n=5, depth=4, seed=0):
    """Random-ish circuit; same (n, depth, seed) → identical structure
    AND identical gate values."""
    rng = np.random.default_rng(seed)
    c = Circuit()
    reg = c.allocate_register(n)
    for q in range(n):
        c.append_gate(TensorData.gate("h"), [reg.qubit(q)])
    for d in range(depth):
        for q in range(n):
            gate = TensorData.gate(
                "rz" if (d + q) % 2 else "rx", (float(rng.uniform(0, 3)),)
            )
            c.append_gate(gate, [reg.qubit(q)])
        for q in range(d % 2, n - 1, 2):
            c.append_gate(
                TensorData.gate("cx"), [reg.qubit(q), reg.qubit(q + 1)]
            )
    return c


def oracle_amplitude(bits, n=5, depth=4, seed=0):
    """The sequential oracle: full pipeline per bitstring — fresh
    network, fresh plan, fresh program, numpy complex128 contraction."""
    tn, _ = make_circuit(n, depth, seed).into_amplitude_network(bits)
    program = build_program(
        tn, Greedy(OptMethod.GREEDY).find_path(tn).replace_path()
    )
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    return np.asarray(NumpyBackend().execute(program, arrays))


def random_bits(n, b, seed):
    rng = np.random.default_rng(seed)
    return ["".join(rng.choice(["0", "1"], n)) for _ in range(b)]


# ---------------------------------------------------------------------------
# rebinding


class TestRebind:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_batched_rebind_bitcompares_to_sequential_oracle(self, seed):
        bp = bind_circuit(make_circuit(seed=seed))
        bits = random_bits(5, 7, seed)
        amps = bp.amplitudes(bits)
        want = np.array(
            [complex(oracle_amplitude(b, seed=seed).reshape(())) for b in bits]
        )
        # bit-equality, not allclose: same operands, same GEMMs, same
        # summation order per batch entry
        assert np.array_equal(
            amps.view(np.float64), want.view(np.float64)
        )

    def test_open_legs_bitcompare(self):
        bp = bind_circuit(make_circuit(seed=1), mask="0*0*0")
        reqs = ["0*1*0", "1*0*1"]
        out = bp.amplitudes(reqs)
        assert out.shape == (2, 2, 2)
        for i, bits in enumerate(reqs):
            want = oracle_amplitude(bits, seed=1)
            assert np.array_equal(out[i], want)

    def test_batch_of_b_equals_b_singletons(self):
        bp = bind_circuit(make_circuit(seed=2))
        bits = random_bits(5, 6, 3)
        batched = bp.amplitudes(bits)
        singles = np.concatenate([bp.amplitudes([b]) for b in bits])
        assert np.array_equal(
            batched.view(np.float64), singles.view(np.float64)
        )

    def test_thread_batch_marks_only_bra_descendants(self):
        bp = bind_circuit(make_circuit(seed=0))
        flags, feasible = thread_batch(bp.program, bp.bra_slots)
        assert feasible
        # at least one step carries the leg, and the result-producing
        # step must (every bra feeds the final amplitude)
        assert any(ab or bb for ab, bb in flags)
        assert flags[-1][0] or flags[-1][1]

    def test_rebind_reuses_one_program(self):
        """Rebinding never rebuilds/replans: the program object is
        shared across queries."""
        bp = bind_circuit(make_circuit(seed=0))
        prog_before = bp.program
        bp.amplitudes(["00000"])
        bp.amplitudes(["11111", "10101"])
        assert bp.program is prog_before

    def test_jax_threaded_matches_numpy(self):
        bp = bind_circuit(make_circuit(seed=0))
        bits = random_bits(5, 4, 5)
        want = bp.amplitudes(bits)
        backend = JaxBackend(dtype="complex128", donate=False)
        got = bp.amplitudes(bits, backend)
        assert np.allclose(got, want, atol=1e-12)
        # the gate leaves were placed on the device once and are reused
        # (only the bras transfer per dispatch)
        with obs.collect_phases() as phases:
            again = bp.amplitudes(bits, backend)
        assert np.allclose(again, want, atol=1e-12)
        assert phases["backend.place_buffers.placed"] == len(bp.bra_slots)
        assert phases["backend.place_buffers.hits"] == (
            len(bp.arrays) - len(bp.bra_slots)
        )

    def test_empty_batched_slots_is_explicit_error(self):
        bp = bind_circuit(make_circuit(seed=0))
        with pytest.raises(ValueError, match="at least one batched slot"):
            NumpyBackend().execute_batched(bp.program, bp.arrays, [])

    def test_split_complex_vmap_fallback_hits_f32_parity(self):
        bp = bind_circuit(make_circuit(seed=0))
        bits = random_bits(5, 4, 6)
        want = bp.amplitudes(bits)
        backend = JaxBackend(
            dtype="complex64", split_complex=True, donate=False
        )
        got = bp.amplitudes(bits, backend)
        assert np.allclose(got, want, atol=1e-5)

    def test_fully_open_template_serves_statevector(self):
        bp = bind_circuit(make_circuit(n=3, depth=2, seed=4), mask="***")
        out = bp.amplitudes(["***", "***"])
        assert out.shape[0] == 2
        assert np.array_equal(out[0], out[1])

    @pytest.mark.parametrize(
        "mask,target",
        [("******", None), ("0*0**0", None), ("*0000*", None),
         ("0*0**0", 2.0**5), ("*0000*", 2.0**4)],
    )
    def test_open_axes_come_back_in_result_legs_order(self, mask, target):
        """Fully open or with bras, sliced or not: one order, the
        executable's result legs (the plan's, not the qubits'); the
        template's permutor names the same legs in qubit order."""
        from tnc_tpu.queries.statevector import statevector

        circuit = make_circuit(n=6, depth=3, seed=7)
        state = statevector(circuit)
        bp = bind_circuit(circuit, mask=mask, target_size=target)
        assert (bp.sliced is not None) == (target is not None)
        executable = bp.program if bp.sliced is None else bp.sliced.program
        assert bp.result_legs == tuple(executable.result_legs)
        by_qubit = bp.template.permutor.target_leg_order
        assert sorted(bp.result_legs) == sorted(by_qubit)
        request = mask.replace("0", "1")
        out = bp.amplitudes([request, mask])
        assert out.shape == (2,) + (2,) * mask.count("*")
        axes = [bp.result_legs.index(leg) for leg in by_qubit]
        for row, bits in zip(out, (request, mask)):
            index = tuple(slice(None) if c == "*" else int(c) for c in bits)
            assert np.allclose(np.transpose(row, axes), state[index], atol=1e-12)

    def test_invalid_request_names_position(self):
        bp = bind_circuit(make_circuit(seed=0))
        with pytest.raises(ValueError, match="position 2"):
            bp.amplitudes(["01x01"])
        # determined template rejects '*' requests
        with pytest.raises(ValueError, match="position 1 is determined"):
            bp.amplitudes(["0*000"])

    def test_sliced_plan_serves_and_roundtrips(self, tmp_path):
        cache = PlanCache(tmp_path)
        bp = bind_circuit(
            make_circuit(n=6, depth=3, seed=7),
            plan_cache=cache,
            target_size=2.0**5,
        )
        assert bp.sliced is not None and bp.sliced.slicing.num_slices > 1
        assert bp.plan["slicing"] is not None
        assert bp.plan["hoist"]["residual_steps"] > 0
        bits = random_bits(6, 3, 8)
        got = bp.amplitudes(bits)
        want = np.array(
            [
                complex(oracle_amplitude(b, n=6, depth=3, seed=7).reshape(()))
                for b in bits
            ]
        )
        assert np.allclose(got, want, atol=1e-10)
        # cache round-trip rebuilds the same sliced plan
        bp2 = bind_circuit(
            make_circuit(n=6, depth=3, seed=7),
            plan_cache=cache,
            target_size=2.0**5,
        )
        assert bp2.sliced is not None
        assert bp2.sliced.slicing == bp.sliced.slicing
        assert np.allclose(bp2.amplitudes(bits), got)


# ---------------------------------------------------------------------------
# plan cache


class TestPlanCache:
    def test_hit_skips_planner(self, tmp_path, enabled_obs):
        cache = PlanCache(tmp_path)

        def find_path_spans():
            return sum(
                1
                for r in obs.get_registry().span_records()
                if r.name == "plan.find_path"
            )

        bind_circuit(make_circuit(seed=0), plan_cache=cache)
        after_first = find_path_spans()
        assert after_first >= 1
        bp2 = bind_circuit(make_circuit(seed=0), plan_cache=cache)
        assert find_path_spans() == after_first  # ZERO new pathfinding
        assert bp2.plan["pairs"]
        hits = obs.counters_by_prefix("serve.plan_cache.hit")
        assert sum(hits.values()) >= 1

    def test_second_structural_circuit_hits_jit_cache(
        self, tmp_path, enabled_obs
    ):
        """The acceptance criterion: repeat structure → no pathfinding
        AND no recompilation (jit cache hit on the first dispatch)."""
        cache = PlanCache(tmp_path)
        backend = JaxBackend(dtype="complex64", donate=False)
        bp = bind_circuit(make_circuit(seed=0), plan_cache=cache)
        bp.amplitudes(["00000", "11111"], backend)
        before = obs.counters_by_prefix("jit_cache")
        bp2 = bind_circuit(make_circuit(seed=0), plan_cache=cache)
        bp2.amplitudes(["00000", "11111"], backend)
        after = obs.counters_by_prefix("jit_cache")
        assert after.get("jit_cache.hit", 0) > before.get("jit_cache.hit", 0)
        assert after.get("jit_cache.miss", 0) == before.get(
            "jit_cache.miss", 0
        )

    def test_structure_key_is_bitstring_independent(self):
        tn0, _ = make_circuit(seed=0).into_amplitude_network("00000")
        tn1, _ = make_circuit(seed=0).into_amplitude_network("10110")
        from tnc_tpu.serve import network_structure_digest

        assert network_structure_digest(tn0) == network_structure_digest(tn1)

    def test_lru_eviction(self, tmp_path):
        cache = PlanCache(tmp_path, max_entries=2)
        plan = {"version": 1, "pairs": [[0, 1]], "program_sig": "x"}
        cache.store("k1", plan)
        time.sleep(0.02)
        cache.store("k2", plan)
        time.sleep(0.02)
        cache.load("k1")  # touch: k1 becomes most recently used
        time.sleep(0.02)
        cache.store("k3", plan)  # evicts k2 (LRU), not k1
        assert cache.load("k1") is not None
        assert cache.load("k2") is None
        assert cache.load("k3") is not None
        assert len(cache) == 2

    def test_corrupted_entry_recovers(self, tmp_path):
        cache = PlanCache(tmp_path)
        key = cache.key_for_network(
            make_circuit(seed=0).into_amplitude_network("00000")[0]
        )
        (tmp_path / f"{key}.json").write_text("{not json!!")
        # load: corrupt → dropped, miss
        assert cache.load(key) is None
        assert not (tmp_path / f"{key}.json").exists()
        # bind through the corrupt entry: replans and re-stores
        bp = bind_circuit(make_circuit(seed=0), plan_cache=cache)
        assert bp.plan["pairs"]
        assert cache.load(key) is not None

    def test_semantically_corrupt_plan_replans(self, tmp_path):
        """Valid JSON whose pairs don't rebuild (out-of-range slots)
        must degrade to a replan and purge the entry — never raise out
        of bind, never leave a poison pill on disk."""
        cache = PlanCache(tmp_path)
        bind_circuit(make_circuit(seed=0), plan_cache=cache)
        key = cache.key_for_network(
            make_circuit(seed=0).into_amplitude_network("0" * 5)[0]
        )
        plan = cache.load(key)
        plan["pairs"] = [[0, 999]]  # rebuilds nowhere
        cache.store(key, plan)
        bp = bind_circuit(make_circuit(seed=0), plan_cache=cache)
        assert np.asarray(bp.amplitudes(["00000"])).shape == (1,)
        healed = cache.load(key)
        assert healed is not None and healed["pairs"] != [[0, 999]]

    def test_store_failure_is_best_effort(self, tmp_path):
        """A cache write failure must never fail the caller — the plan
        is already in memory; the cache is an optimization."""
        import shutil

        cache = PlanCache(tmp_path / "plans")
        shutil.rmtree(tmp_path / "plans")
        cache.store("k", {"version": 1, "pairs": [[0, 1]]})  # no raise
        # bind through the broken cache: plans and serves anyway
        bp = bind_circuit(make_circuit(seed=0), plan_cache=cache)
        assert np.asarray(bp.amplitudes(["00000"])).shape == (1,)

    def test_wrong_version_is_a_miss(self, tmp_path):
        cache = PlanCache(tmp_path)
        (tmp_path / "k.json").write_text(
            json.dumps({"version": 999, "pairs": [[0, 1]]})
        )
        assert cache.load("k") is None

    def test_stale_program_sig_replans(self, tmp_path, enabled_obs):
        cache = PlanCache(tmp_path)
        bp = bind_circuit(make_circuit(seed=0), plan_cache=cache)
        key = cache.key_for_network(bp.template.network)
        plan = cache.load(key)
        plan["program_sig"] = "deadbeef"  # foreign/stale plan
        cache.store(key, plan)
        before = sum(
            1
            for r in obs.get_registry().span_records()
            if r.name == "plan.find_path"
        )
        bp2 = bind_circuit(make_circuit(seed=0), plan_cache=cache)
        after = sum(
            1
            for r in obs.get_registry().span_records()
            if r.name == "plan.find_path"
        )
        assert after == before + 1  # invalid entry → honest replan
        assert cache.validate(bp2.plan, bp2.program)


# ---------------------------------------------------------------------------
# digest satellite


class TestStableDigest:
    def test_dict_and_set_order_independent(self):
        from tnc_tpu.utils.digest import stable_digest

        assert stable_digest({"a": 1, "b": [2, 3]}) == stable_digest(
            {"b": [2, 3], "a": 1}
        )
        assert stable_digest({3, 1, 2}) == stable_digest({2, 3, 1})
        assert stable_digest((1, 2)) != stable_digest([1, 2])

    def test_stable_across_hash_seeds(self):
        """The digest of a program signature (nested dataclass tuples)
        must not depend on PYTHONHASHSEED — on-disk plan/checkpoint
        keys cross process boundaries."""
        code = (
            "from tnc_tpu.utils.digest import stable_digest\n"
            "from tnc_tpu.ops.program import PairStep\n"
            "st = PairStep(0, 1, (2, 2), None, (2, 2), True, (2,), None,"
            " (2,), True, False, (2,))\n"
            "print(stable_digest({'step': st, 'z': {1, 2, 3}}, 'tag'))\n"
        )
        digests = set()
        for seed in ("0", "424242"):
            env = dict(os.environ)
            env["PYTHONHASHSEED"] = seed
            env["JAX_PLATFORMS"] = "cpu"
            r = subprocess.run(
                [sys.executable, "-c", code],
                capture_output=True,
                text=True,
                env=env,
                check=True,
            )
            digests.add(r.stdout.strip())
        assert len(digests) == 1

    def test_checkpoint_signature_routed_through_shared_helper(self):
        from tnc_tpu.resilience.checkpoint import signature_hash
        from tnc_tpu.utils.digest import stable_digest

        assert signature_hash("a", 1, (2, 3)) == stable_digest("a", 1, (2, 3))

    def test_numeric_kind_not_arrival_type(self):
        """Same value, different numeric arrival type: numpy scalars
        fold by KIND (Integral→int, Real→float), so np.float32(2.0)
        digests like 2.0, never like the int 2."""
        from tnc_tpu.utils.digest import stable_digest

        assert stable_digest(np.float32(2.0)) == stable_digest(2.0)
        assert stable_digest(np.float64(2.0)) == stable_digest(2.0)
        assert stable_digest(np.int32(2)) == stable_digest(2)
        assert stable_digest(2.0) != stable_digest(2)

    def test_benchmark_cache_key_unchanged_format(self):
        from tnc_tpu.benchmark.cache import cache_key

        key = cache_key("greedy", "OPENQASM 2.0;", 7, 4, "sa")
        assert key.startswith("greedy_") and key.endswith("_7_4_sa")
        assert key == cache_key("greedy", "OPENQASM 2.0;", 7, 4, "sa")


# ---------------------------------------------------------------------------
# bitstring normalization satellite


class TestNormalizeBitstring:
    def test_iterable_states(self):
        assert normalize_bitstring([0, 1, None, "*", "1"]) == "01**1"

    def test_error_names_char_and_position(self):
        with pytest.raises(ValueError, match=r"character '2' at position 3"):
            normalize_bitstring("0112")
        with pytest.raises(ValueError, match=r"state 7 at position 1"):
            normalize_bitstring([0, 7])
        with pytest.raises(ValueError, match="position 0"):
            normalize_bitstring([True, 0])

    def test_amplitude_network_accepts_iterable(self):
        tn_str, _ = make_circuit(n=3, depth=2, seed=0).into_amplitude_network(
            "010"
        )
        tn_it, _ = make_circuit(n=3, depth=2, seed=0).into_amplitude_network(
            [0, 1, 0]
        )
        assert len(tn_str) == len(tn_it)

    def test_length_mismatch(self):
        c = make_circuit(n=3, depth=1, seed=0)
        with pytest.raises(ValueError, match="length 2 != qubit count 3"):
            c.into_amplitude_network("01")


# ---------------------------------------------------------------------------
# service front end


class SlowBackend(NumpyBackend):
    """Oracle backend with a configurable dispatch delay (and optional
    scripted failures) — deterministic service-timing tests."""

    def __init__(self, delay_s=0.0, fail_batches=0, fail_with=None):
        super().__init__()
        self.delay_s = delay_s
        self.fail_batches = fail_batches
        self.fail_with = fail_with or (lambda: ConnectionResetError("blip"))
        self.calls = []

    def execute_batched(self, program, arrays, batched):
        b = int(np.asarray(arrays[list(batched)[0]]).shape[0])
        self.calls.append(b)
        if self.delay_s:
            time.sleep(self.delay_s)
        if b > 1 and self.fail_batches > 0:
            self.fail_batches -= 1
            raise self.fail_with()
        return super().execute_batched(program, arrays, batched)


class PoisonBackend(NumpyBackend):
    """Fails any dispatch whose batch contains the poisoned bra
    pattern — a deterministic 'bad input at dispatch time' the
    admission-time validation cannot catch."""

    def __init__(self, poison_bits):
        super().__init__()
        self.poison = poison_bits

    def execute_batched(self, program, arrays, batched):
        slots = list(batched)
        rows = np.stack([np.asarray(arrays[s]) for s in slots], axis=1)
        for row in rows:  # row: (n_det, 2) one-hot bras, qubit order
            bits = "".join("0" if abs(r[0]) > 0.5 else "1" for r in row)
            if bits == self.poison:
                raise ValueError(f"poisoned request {bits}")
        return super().execute_batched(program, arrays, batched)


class TestService:
    def _service(self, backend=None, **kw):
        bound = bind_circuit(make_circuit(seed=0))
        kw.setdefault("max_wait_ms", 20.0)
        kw.setdefault(
            "retry_policy", RetryPolicy(max_attempts=2, base_delay_s=0.0)
        )
        return ContractionService(bound, backend=backend, **kw).start()

    def test_concurrent_queries_match_oracle(self):
        svc = self._service(max_batch=4)
        try:
            bits = random_bits(5, 10, 11)
            futs = [svc.submit(b) for b in bits]
            got = np.array([f.result(timeout=30) for f in futs])
        finally:
            svc.stop()
        want = np.array(
            [complex(oracle_amplitude(b).reshape(())) for b in bits]
        )
        assert np.array_equal(got.view(np.float64), want.view(np.float64))
        stats = svc.stats()
        assert stats["counts"]["completed"] == 10
        assert stats["batch_size"]["max"] >= 1

    def test_micro_batching_batches_riders(self):
        backend = SlowBackend(delay_s=0.05)
        svc = self._service(backend=backend, max_batch=8, max_wait_ms=100.0)
        try:
            # distinct bits: identical riders would collapse via queue
            # dedup and never grow the dispatched batch
            bits = random_bits(5, 6, 23)
            futs = [svc.submit(b) for b in bits]
            [f.result(timeout=30) for f in futs]
        finally:
            svc.stop()
        # the waiting window must have merged riders into shared batches
        assert max(backend.calls) >= 2

    def test_deadline_expiry(self):
        backend = SlowBackend(delay_s=0.5)
        svc = self._service(backend=backend, max_batch=1, max_wait_ms=0.0)
        try:
            first = svc.submit("00000")  # occupies the dispatcher ~0.5 s
            time.sleep(0.1)
            doomed = svc.submit("11111", timeout_s=0.05)
            with pytest.raises(DeadlineExceededError):
                doomed.result(timeout=30)
            assert complex(first.result(timeout=30)) is not None
        finally:
            svc.stop()
        assert svc.stats()["counts"]["expired"] == 1

    def test_admission_control_rejects_when_full(self):
        backend = SlowBackend(delay_s=0.5)
        svc = self._service(
            backend=backend, max_batch=1, max_wait_ms=0.0, max_queue=1
        )
        try:
            ok1 = svc.submit("00000")
            time.sleep(0.1)  # dispatcher now busy with ok1
            ok2 = svc.submit("00001")  # fills the queue
            with pytest.raises(QueueFullError):
                svc.submit("00010")
            ok1.result(timeout=30)
            ok2.result(timeout=30)
        finally:
            svc.stop()
        assert svc.stats()["counts"]["rejected"] == 1

    def test_transient_batch_failure_retries_in_place(self):
        backend = SlowBackend(fail_batches=1)  # first batch dispatch blips
        svc = self._service(backend=backend, max_batch=4, max_wait_ms=50.0)
        try:
            futs = [svc.submit(b) for b in random_bits(5, 3, 12)]
            got = [f.result(timeout=30) for f in futs]
        finally:
            svc.stop()
        assert all(isinstance(a, complex) for a in got)
        assert svc.stats()["counts"]["degraded_batches"] == 0  # retry, not degrade

    def test_batch_failure_degrades_to_singletons(self):
        """A request that poisons the whole batch (fatal at dispatch)
        fails alone; its co-riders still complete."""
        svc = self._service(
            backend=PoisonBackend("10101"), max_batch=4, max_wait_ms=100.0
        )
        try:
            good1 = svc.submit("00000")
            bad = svc.submit("10101")  # fails any dispatch containing it
            good2 = svc.submit("11111")
            a1 = good1.result(timeout=30)
            a2 = good2.result(timeout=30)
            with pytest.raises(ValueError, match="poisoned"):
                bad.result(timeout=30)
        finally:
            svc.stop()
        assert a1 == complex(oracle_amplitude("00000").reshape(()))
        assert a2 == complex(oracle_amplitude("11111").reshape(()))
        assert svc.stats()["counts"]["degraded_batches"] >= 1
        assert svc.stats()["counts"]["failed"] == 1

    def test_malformed_request_rejected_at_submit(self):
        """Validation happens at admission: a typo'd bitstring never
        enters the queue (and never poisons a batch)."""
        svc = self._service(max_batch=4)
        try:
            with pytest.raises(ValueError, match="position 2"):
                svc.submit("00x00")
            amp = svc.amplitude("00000", timeout_s=30)
        finally:
            svc.stop()
        assert amp == complex(oracle_amplitude("00000").reshape(()))
        assert svc.stats()["counts"]["degraded_batches"] == 0

    def test_cancelled_future_does_not_kill_dispatcher(self):
        """A caller-cancelled future (fut.cancel(), or an abandoned
        asyncio await) must not kill the dispatcher thread — later
        requests still complete."""
        backend = SlowBackend(delay_s=0.3)
        svc = self._service(backend=backend, max_batch=1, max_wait_ms=0.0)
        try:
            first = svc.submit("00000")  # occupies the dispatcher
            time.sleep(0.1)
            doomed = svc.submit("11111")
            assert doomed.cancel()
            first.result(timeout=30)
            after = svc.submit("01010")  # dispatcher must still be alive
            assert isinstance(after.result(timeout=30), complex)
        finally:
            svc.stop()
        assert svc.stats()["counts"]["cancelled"] == 1

    # -- per-type terminal-outcome accounting (one regression test per
    # outcome: every terminal state must land in its by_type row, not
    # just the global counters) --------------------------------------

    def test_by_type_counts_completed(self):
        svc = self._service(max_batch=4)
        try:
            [svc.submit(b).result(timeout=30) for b in random_bits(5, 3, 21)]
        finally:
            svc.stop()
        row = svc.stats()["by_type"]["amplitude"]["counts"]
        assert row["submitted"] == 3 and row["completed"] == 3

    def test_by_type_counts_expired(self):
        backend = SlowBackend(delay_s=0.5)
        svc = self._service(backend=backend, max_batch=1, max_wait_ms=0.0)
        try:
            first = svc.submit("00000")
            time.sleep(0.1)
            doomed = svc.submit("11111", timeout_s=0.05)
            with pytest.raises(DeadlineExceededError):
                doomed.result(timeout=30)
            first.result(timeout=30)
        finally:
            svc.stop()
        row = svc.stats()["by_type"]["amplitude"]["counts"]
        assert row["expired"] == 1
        assert row["completed"] == 1

    def test_by_type_counts_rejected(self):
        backend = SlowBackend(delay_s=0.5)
        svc = self._service(
            backend=backend, max_batch=1, max_wait_ms=0.0, max_queue=1
        )
        try:
            ok1 = svc.submit("00000")
            time.sleep(0.1)
            ok2 = svc.submit("00001")
            with pytest.raises(QueueFullError):
                svc.submit("00010")
            ok1.result(timeout=30)
            ok2.result(timeout=30)
        finally:
            svc.stop()
        row = svc.stats()["by_type"]["amplitude"]["counts"]
        assert row["rejected"] == 1

    def test_by_type_counts_cancelled(self):
        backend = SlowBackend(delay_s=0.3)
        svc = self._service(backend=backend, max_batch=1, max_wait_ms=0.0)
        try:
            first = svc.submit("00000")
            time.sleep(0.1)
            doomed = svc.submit("11111")
            assert doomed.cancel()
            first.result(timeout=30)
            svc.submit("01010").result(timeout=30)
        finally:
            svc.stop()
        row = svc.stats()["by_type"]["amplitude"]["counts"]
        assert row["cancelled"] == 1

    def test_by_type_counts_failed(self):
        svc = self._service(
            backend=PoisonBackend("10101"), max_batch=4, max_wait_ms=100.0
        )
        try:
            good = svc.submit("00000")
            bad = svc.submit("10101")
            good.result(timeout=30)
            with pytest.raises(ValueError, match="poisoned"):
                bad.result(timeout=30)
        finally:
            svc.stop()
        row = svc.stats()["by_type"]["amplitude"]["counts"]
        assert row["failed"] == 1
        assert row["completed"] == 1

    def test_request_timeline_spans(self, enabled_obs):
        """Every request's terminal serve.request span carries its
        timeline; serve.dispatch spans carry the rider id list."""
        svc = self._service(max_batch=4)
        try:
            futs = [svc.submit(b) for b in random_bits(5, 4, 22)]
            [f.result(timeout=30) for f in futs]
        finally:
            svc.stop()
        recs = enabled_obs.span_records()
        req_spans = [r for r in recs if r.name == "serve.request"]
        assert len(req_spans) == 4
        rids = {r.args["rid"] for r in req_spans}
        assert len(rids) == 4  # unique ids
        for r in req_spans:
            assert r.args["outcome"] == "completed"
            assert r.args["latency_s"] >= r.args["dispatch_s"] >= 0.0
            assert r.args["queue_age_s"] >= 0.0
        dispatch = [r for r in recs if r.name == "serve.dispatch"]
        carried = set()
        for d in dispatch:
            carried.update(d.args["riders"].split(","))
        assert rids <= carried  # every request attributed to a dispatch

    def test_one_shot_iterable_request(self):
        """A generator request is consumed exactly once (at admission
        validation) — the normalized string is what gets dispatched."""
        svc = self._service(max_batch=4)
        try:
            amp = svc.submit(iter([0, 1, 0, 1, 0])).result(timeout=30)
        finally:
            svc.stop()
        assert amp == complex(oracle_amplitude("01010").reshape(()))

    def test_submit_after_stop_raises(self):
        svc = self._service()
        svc.stop()
        with pytest.raises(ServiceClosedError):
            svc.submit("00000")

    def test_asyncio_facade(self):
        import asyncio

        svc = self._service(max_batch=4)

        async def run():
            return await asyncio.gather(
                *(svc.amplitude_async(b) for b in ["00000", "11111"])
            )

        try:
            got = asyncio.run(run())
        finally:
            svc.stop()
        assert got[0] == complex(oracle_amplitude("00000").reshape(()))
        assert got[1] == complex(oracle_amplitude("11111").reshape(()))

    def test_obs_wiring(self, enabled_obs):
        svc = self._service(max_batch=4)
        try:
            futs = [svc.submit(b) for b in random_bits(5, 5, 13)]
            [f.result(timeout=30) for f in futs]
        finally:
            svc.stop()
        counters = obs.counters_by_prefix("serve.requests.")
        assert counters.get("serve.requests.submitted", 0) == 5
        assert counters.get("serve.requests.completed", 0) == 5
        hists = obs.get_registry().histograms()
        names = {name for (name, _labels) in hists}
        assert "serve.batch_size" in names
        assert "serve.latency_s" in names
        gauges = obs.get_registry().gauges()
        assert any(k[0] == "serve.queue_depth" for k in gauges)


# ---------------------------------------------------------------------------
# background replanner (anytime plan improvement + atomic swap)


def exact_circuit(n=6):
    """X/CX-only circuit: every amplitude is EXACTLY 0.0 or 1.0 (the
    gates are permutation matrices), so any two contraction orders
    produce bit-identical results — the property the swap pin needs."""
    c = Circuit()
    reg = c.allocate_register(n)
    for q in range(n):
        c.append_gate(TensorData.gate("x"), [reg.qubit(q)])
    for q in range(n - 1):
        c.append_gate(TensorData.gate("cx"), [reg.qubit(q), reg.qubit(q + 1)])
    return c


class _SlowerNamedGreedy(Greedy):
    """Greedy under another name: produces the SAME plan, but the
    finder marker differs — lets the tests force deterministic
    candidate == incumbent comparisons without hyper-optimizer cost."""


def _wait_for(predicate, timeout_s=60.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.02)
    return False


class TestBackgroundReplanner:
    def _service_with_cache(self, tmp_path, circuit=None, **kwargs):
        cache = PlanCache(tmp_path / "plans")
        svc = ContractionService.from_circuit(
            circuit if circuit is not None else make_circuit(),
            plan_cache=cache,
            **kwargs,
        )
        return svc, cache

    def test_swap_preserves_amplitudes_bitwise(self, tmp_path, enabled_obs):
        """THE pin: amplitudes before and after a real hyper-optimizer
        swap are bit-identical (exact-permutation circuit), the swap
        goes through the plan cache's atomic-write path, and the
        serve.replan.* counters record it."""
        from tnc_tpu.serve import BackgroundReplanner

        n = 6
        svc, cache = self._service_with_cache(
            tmp_path, circuit=exact_circuit(n)
        )
        bits = ["1" * n, "0" * n, "10" * (n // 2)]
        before = [svc.amplitude(b) for b in bits]
        assert svc.bound.plan.get("finder") == "Greedy"

        rp = BackgroundReplanner(svc, cache, margin=100.0).start()
        try:
            assert _wait_for(lambda: rp.stats["swaps"] == 1)
            # adoption happens at the next batch boundary
            after = [svc.amplitude(b) for b in bits]
        finally:
            svc.stop()
        assert svc.stats()["counts"]["plan_swaps"] == 1
        for b, a in zip(before, after):
            # bit-identical: the amplitudes are exact 0.0 / 1.0
            assert a == b
            assert a in (0.0 + 0.0j, 1.0 + 0.0j, -1.0 - 0.0j, 1.0 - 0.0j)
        # the improved plan is the cache's entry now (atomic store path)
        key = cache.key_for_network(svc.bound.template.network, None)
        plan = cache.load(key)
        assert plan["finder"] == "Hyperoptimizer"
        counters = obs.counters_by_prefix("serve.replan.")
        assert counters.get("serve.replan.attempt", 0) == 1
        assert counters.get("serve.replan.swap", 0) == 1
        assert counters.get("serve.replan.adopted", 0) == 1

    def test_reject_keeps_incumbent(self, tmp_path, enabled_obs):
        """A candidate that does not beat the margin is rejected: no
        cache rewrite, no bound swap, reject counter bumped."""
        from tnc_tpu.serve import BackgroundReplanner

        svc, cache = self._service_with_cache(tmp_path)
        svc.amplitude("00000")
        incumbent_plan = dict(svc.bound.plan)
        # same-path candidate (equal predicted cost) under a strict
        # margin can never win
        rp = BackgroundReplanner(
            svc, cache, optimizer=_SlowerNamedGreedy(), margin=0.95
        ).start()
        try:
            assert _wait_for(lambda: rp.stats["rejects"] == 1)
            assert rp.stats["swaps"] == 0
        finally:
            svc.stop()
        assert svc.stats()["counts"]["plan_swaps"] == 0
        assert svc.bound.plan.get("pairs") == incumbent_plan.get("pairs")
        assert svc.bound.plan.get("finder") == "Greedy"
        counters = obs.counters_by_prefix("serve.replan.")
        assert counters.get("serve.replan.reject", 0) == 1
        assert "serve.replan.swap" not in counters

    def test_swap_mechanics_without_search(self, tmp_path):
        """Deterministic swap through the full store → rebuild →
        adopt pipeline using a same-plan candidate and a permissive
        margin (no hyper-optimizer nondeterminism in the loop)."""
        from tnc_tpu.serve import BackgroundReplanner

        svc, cache = self._service_with_cache(tmp_path)
        want = complex(oracle_amplitude("00000").reshape(()))
        assert svc.amplitude("00000") == want
        rp = BackgroundReplanner(
            svc, cache, optimizer=_SlowerNamedGreedy(), margin=2.0
        ).start()
        try:
            assert _wait_for(lambda: rp.stats["swaps"] == 1)
            # bit-identical trivially: the candidate IS the same path
            assert svc.amplitude("00000") == want
        finally:
            svc.stop()
        assert svc.stats()["counts"]["plan_swaps"] == 1
        assert svc.bound.plan.get("finder") == "_SlowerNamedGreedy"

    def test_inflight_requests_survive_swap(self, tmp_path):
        """Requests streaming through the service while the replanner
        swaps all complete with oracle-exact results — no drops, no
        corruption (each batch runs wholly under one bound)."""
        from tnc_tpu.serve import BackgroundReplanner

        svc, cache = self._service_with_cache(
            tmp_path, max_batch=4, max_wait_ms=1.0
        )
        rp = BackgroundReplanner(
            svc, cache, optimizer=_SlowerNamedGreedy(), margin=2.0,
            poll_interval_s=0.001,
        ).start()
        bits = random_bits(5, 40, seed=7)
        want = {b: complex(oracle_amplitude(b).reshape(())) for b in set(bits)}
        try:
            futs = [svc.submit(b) for b in bits]
            got = [f.result(timeout=60) for f in futs]
            assert _wait_for(lambda: rp.stats["swaps"] == 1)
            futs2 = [svc.submit(b) for b in bits]
            got2 = [f.result(timeout=60) for f in futs2]
        finally:
            svc.stop()
        for b, g in zip(bits + bits, got + got2):
            assert g == want[b]
        counts = svc.stats()["counts"]
        assert counts["failed"] == 0
        assert counts["completed"] == 2 * len(bits)
        assert counts["plan_swaps"] == 1

    def test_swap_bound_rejects_other_structure(self, tmp_path):
        svc, _cache = self._service_with_cache(tmp_path)
        other = bind_circuit(make_circuit(n=4))
        try:
            with pytest.raises(ValueError, match="not a plan"):
                svc.swap_bound(other)
        finally:
            svc.stop()

    def test_service_stop_stops_replanner(self, tmp_path):
        from tnc_tpu.serve import BackgroundReplanner

        svc, cache = self._service_with_cache(tmp_path)
        rp = BackgroundReplanner(
            svc, cache, optimizer=_SlowerNamedGreedy(), margin=0.95
        ).start()
        assert svc._replanner is rp
        svc.stop()
        assert rp._thread is None

    def test_replanner_skips_hyper_planned_entries(self, tmp_path):
        """A structure whose cached plan already came from a search
        finder is left alone (no attempt counter motion)."""
        from tnc_tpu.serve import BackgroundReplanner

        cache = PlanCache(tmp_path / "plans")
        svc = ContractionService.from_circuit(
            make_circuit(),
            pathfinder=Greedy(OptMethod.RANDOM_GREEDY),
            plan_cache=cache,
        )
        rp = BackgroundReplanner(svc, cache, margin=100.0)
        try:
            # RANDOM_GREEDY is still Greedy by class name — simulate a
            # hyper-provenance entry instead
            svc.bound.plan["finder"] = "Hyperoptimizer"
            assert rp._attempt_once() is False
            assert rp.stats["attempts"] == 0
        finally:
            svc.stop()

    def test_min_hits_defers_replanning(self, tmp_path):
        from tnc_tpu.serve import BackgroundReplanner

        svc, cache = self._service_with_cache(tmp_path)
        rp = BackgroundReplanner(
            svc, cache, optimizer=_SlowerNamedGreedy(), margin=2.0,
            min_hits=3,
        )
        try:
            assert rp._attempt_once() is False  # 0 hits < 3
            key = cache.key_for_network(svc.bound.template.network, None)
            for _ in range(3):
                cache.load(key)
            assert rp._attempt_once() is True
        finally:
            svc.stop()

    def test_store_failure_abandons_swap(self, tmp_path, enabled_obs):
        """When the best-effort cache store doesn't stick, the rebuilt
        bound is NOT the priced improvement — the swap is abandoned
        (no stale/greedy plan silently counted as a hyper swap)."""
        from tnc_tpu.serve import BackgroundReplanner

        class _ReversedChain(Greedy):
            """A valid but different path (left-deep chain over the
            reversed leaf order) so the candidate program's signature
            genuinely differs from the incumbent's."""

            def _solve_toplevel(self, inputs):
                n = len(inputs)
                pairs, cur, nxt = [], n - 1, n
                for i in range(n - 2, -1, -1):
                    pairs.append((cur, i))
                    cur = nxt
                    nxt += 1
                return pairs

        svc, cache = self._service_with_cache(tmp_path)
        key = cache.key_for_network(svc.bound.template.network, None)
        cache.invalidate(key)  # and the store never lands either:
        cache.store = lambda key, plan: None  # simulate disk-full no-op
        rp = BackgroundReplanner(
            svc, cache, optimizer=_ReversedChain(), margin=1e9
        )
        try:
            assert rp._attempt_once() is False
            assert rp.stats["swaps"] == 0
            assert rp.stats["rejects"] == 1
        finally:
            svc.stop()
        assert svc.stats()["counts"]["plan_swaps"] == 0
        counters = obs.counters_by_prefix("serve.replan.")
        assert counters.get("serve.replan.store_lost", 0) == 1

    def test_swap_bound_rejects_same_size_other_circuit(self, tmp_path):
        """Same qubit count + same bra layout but a different circuit:
        the structure-digest guard must still reject it."""
        svc, _cache = self._service_with_cache(tmp_path)
        other = bind_circuit(make_circuit(seed=99))
        try:
            with pytest.raises(ValueError, match="different structure"):
                svc.swap_bound(other)
        finally:
            svc.stop()

    def test_from_circuit_replan_requires_cache_before_start(self):
        with pytest.raises(ValueError, match="requires a plan_cache"):
            ContractionService.from_circuit(
                make_circuit(), background_replan=True
            )

    def test_from_circuit_bad_replan_options_no_thread_leak(self, tmp_path):
        import threading

        before = {t.name for t in threading.enumerate()}
        with pytest.raises(TypeError):
            ContractionService.from_circuit(
                make_circuit(),
                plan_cache=PlanCache(tmp_path / "plans"),
                background_replan=True,
                replan_options={"bogus_kwarg": 1},
            )
        time.sleep(0.1)
        after = {t.name for t in threading.enumerate()}
        assert "tnc-serve-dispatch" not in (after - before)

    def test_failing_attempt_abandons_key(self, tmp_path):
        """A persistently failing optimizer stops being retried (no
        hot-loop full-search retries every poll interval)."""
        from tnc_tpu.serve import BackgroundReplanner

        class _Boom:
            def find_path(self, tn):
                raise RuntimeError("planner exploded")

        svc, cache = self._service_with_cache(tmp_path)
        rp = BackgroundReplanner(
            svc, cache, optimizer=_Boom(), margin=2.0,
            poll_interval_s=0.005,
        ).start()
        try:
            assert _wait_for(lambda: rp.stats["attempts"] == 1, 20.0)
            time.sleep(0.2)  # many poll intervals
            assert rp.stats["attempts"] == 1  # abandoned, not hot-looped
        finally:
            svc.stop()


class TestPlanCacheHits:
    def test_hits_and_hot_keys(self, tmp_path):
        cache = PlanCache(tmp_path)
        cache.store("a", {"version": 1, "pairs": []})
        cache.store("b", {"version": 1, "pairs": []})
        assert cache.hits("a") == 0
        cache.load("a")
        cache.load("a")
        cache.load("b")
        cache.load("missing")  # misses never count as hits
        assert cache.hits("a") == 2
        assert cache.hits("b") == 1
        assert cache.hot_keys() == ["a", "b"]
        assert cache.hot_keys(limit=1) == ["a"]

    def test_corrupt_load_not_counted(self, tmp_path):
        cache = PlanCache(tmp_path)
        (tmp_path / "bad.json").write_text("{nope")
        assert cache.load("bad") is None

    def test_eviction_and_invalidation_prune_heat(self, tmp_path):
        # hits()/hot_keys() must not rank keys the cache no longer
        # holds, and _hits must not grow per structure ever served
        cache = PlanCache(tmp_path, max_entries=2)
        plan = {"version": 1, "pairs": []}
        cache.store("k1", plan)
        time.sleep(0.02)
        cache.store("k2", plan)
        cache.load("k1")
        time.sleep(0.02)
        cache.load("k2")
        time.sleep(0.02)
        cache.store("k3", plan)  # evicts k1 (k2's load touched it last)
        assert cache.load("k1") is None
        assert cache.hits("k1") == 0
        assert "k1" not in cache.hot_keys()
        cache.invalidate("k2")
        assert cache.hits("k2") == 0
        assert cache.hot_keys() == []
        assert cache.hits("bad") == 0



# ---------------------------------------------------------------------------
# multi-host serving building blocks (single-process contracts; the
# 2-process cluster pins live in tests/test_multihost_serve.py)


class TestShardRanges:
    def test_even_and_remainder(self):
        from tnc_tpu.serve import shard_ranges

        assert shard_ranges(8, 2) == [(0, 4), (4, 8)]
        assert shard_ranges(7, 3) == [(0, 3), (3, 5), (5, 7)]

    def test_covers_exactly_once(self):
        from tnc_tpu.serve import shard_ranges

        for n, p in [(0, 3), (1, 4), (5, 5), (13, 4), (16, 1)]:
            ranges = shard_ranges(n, p)
            assert len(ranges) == p
            ids = [i for lo, hi in ranges for i in range(lo, hi)]
            assert ids == list(range(n))

    def test_empty_shards_are_legal(self):
        from tnc_tpu.serve import shard_ranges

        ranges = shard_ranges(2, 4)
        assert ranges == [(0, 1), (1, 2), (2, 2), (2, 2)]


class TestSliceRangeSharding:
    def _sliced_bound(self, tmp_path):
        from tnc_tpu.builders.random_circuit import brickwork_circuit

        c = brickwork_circuit(8, 6, np.random.default_rng(9))
        bound = bind_circuit(c, target_size=64)
        assert bound.sliced is not None
        return bound

    def test_whole_range_bitwise_equals_full_loop(self, tmp_path):
        bound = self._sliced_bound(tmp_path)
        num = bound.sliced.slicing.num_slices
        det = [bound.template.request_bits("10101010")]
        full = bound.amplitudes_det(det)
        whole = bound.amplitudes_det(det, slice_range=(0, num))
        assert np.array_equal(full, whole)

    def test_range_partials_sum_to_full(self, tmp_path):
        from tnc_tpu.serve import shard_ranges

        bound = self._sliced_bound(tmp_path)
        num = bound.sliced.slicing.num_slices
        det = [
            bound.template.request_bits(b)
            for b in ("00000000", "11111111", "01100110")
        ]
        full = bound.amplitudes_det(det)
        acc = None
        for lo, hi in shard_ranges(num, 2):
            part = bound.amplitudes_det(det, slice_range=(lo, hi))
            acc = part if acc is None else acc + part
        assert np.allclose(acc, full, rtol=1e-12, atol=1e-14)

    def test_slice_range_rejected_on_unsliced_bound(self):
        bound = bind_circuit(make_circuit(seed=0))
        det = [bound.template.request_bits("0" * 5)]
        with pytest.raises(ValueError, match="slice_range"):
            bound.amplitudes_det(det, slice_range=(0, 1))

    def test_numpy_backend_range_is_contiguous_partial(self, tmp_path):
        bound = self._sliced_bound(tmp_path)
        backend = NumpyBackend()
        arrays = list(bound.arrays)
        full = backend.execute_sliced(bound.sliced, arrays)
        num = bound.sliced.slicing.num_slices
        a = backend.execute_sliced(bound.sliced, arrays, slice_range=(0, num))
        assert np.array_equal(full, a)
        with pytest.raises(ValueError, match="exclusive"):
            backend.execute_sliced(
                bound.sliced, arrays, max_slices=1, slice_range=(0, 1)
            )

    def test_jax_chunked_strategy_serves_range_partials(
        self, tmp_path, enabled_obs
    ):
        """The chunked executor honors ``slice_range`` with the
        programs it has: partials sum to the whole and the chunked
        residual span proves which executor ran."""
        bound = self._sliced_bound(tmp_path)
        num = bound.sliced.slicing.num_slices
        det = [bound.template.request_bits("10101010")]
        backend = JaxBackend(donate=False)
        full = np.asarray(bound.amplitudes_det(det, backend))
        lo = np.asarray(
            bound.amplitudes_det(det, backend, slice_range=(0, num // 2))
        )
        hi = np.asarray(
            bound.amplitudes_det(det, backend, slice_range=(num // 2, num))
        )
        assert np.allclose(lo + hi, full, rtol=1e-5, atol=1e-8)
        chunked_spans = [
            r
            for r in obs.get_registry().span_records()
            if r.name == "sliced.residual"
            and r.args.get("executor") == "chunked"
        ]
        assert chunked_spans, "range shards bypassed the chunked executor"

    def test_concat_rows_empty_shard_keeps_dtype(self):
        """Idle hosts of a fleet larger than the batch gather EMPTY
        shards, and ``amplitudes_det([])`` hardcodes complex128 — the
        root's concatenation must not upcast the filled rows' dtype."""
        from tnc_tpu.serve.multihost import _concat_rows

        rows = np.ones((3, 1), dtype=np.complex64)
        empty = np.zeros((0, 1), dtype=np.complex128)
        out = _concat_rows([rows, empty, empty])
        assert out.dtype == np.complex64
        assert np.array_equal(out, rows)
        assert _concat_rows([empty, empty]).shape[0] == 0


class TestClusterSingleProcess:
    """Degenerate (1-process) contracts of the fleet entry points: they
    must fall through to plain local execution bit-identically."""

    def test_cluster_amplitudes_local(self):
        from tnc_tpu.serve import cluster_amplitudes

        bound = bind_circuit(make_circuit(seed=3))
        det = [bound.template.request_bits("1" * 5)]
        assert np.array_equal(
            cluster_amplitudes(bound, det), bound.amplitudes_det(det)
        )

    def test_cluster_sliced_requires_sliced_bound(self):
        from tnc_tpu.serve import cluster_amplitudes_sliced

        bound = bind_circuit(make_circuit(seed=3))
        det = [bound.template.request_bits("1" * 5)]
        # single-process fall-through executes locally even unsliced
        assert np.array_equal(
            cluster_amplitudes_sliced(bound, det),
            bound.amplitudes_det(det),
        )

    def test_dispatcher_mode_validation_and_stop(self):
        from tnc_tpu.serve import ClusterDispatcher

        with pytest.raises(ValueError):
            ClusterDispatcher(mode="nope")
        d = ClusterDispatcher()
        bound = bind_circuit(make_circuit(seed=4))
        det = [bound.template.request_bits("0" * 5)]
        got = d(bound, det)
        assert np.array_equal(got, bound.amplitudes_det(det))
        d.stop()
        d.stop()  # idempotent
        with pytest.raises(RuntimeError, match="stopped"):
            d(bound, det)

    def test_shard_failure_named_and_raised(self):
        """A failed shard gathers as a failure marker (lockstep — no
        skipped collective) and the root's raise names the process."""
        from tnc_tpu.serve.multihost import (
            _raise_shard_failures,
            _ShardFailure,
        )

        f = _ShardFailure(2, RuntimeError("boom"))
        with pytest.raises(
            RuntimeError, match=r"process 2: RuntimeError: boom"
        ):
            _raise_shard_failures([np.zeros(2), f])
        _raise_shard_failures([np.zeros(2)])  # clean gather: no raise

    def test_legacy_backend_without_slice_range_kw(self):
        """A Backend subclass written before ``slice_range`` existed
        keeps serving whole-range sliced requests — the kwarg is only
        forwarded when a shard is actually requested."""
        from tnc_tpu.builders.random_circuit import brickwork_circuit

        class LegacyBackend(NumpyBackend):
            def execute_sliced(
                self, sp, arrays, max_slices=None, host=True, hoist=None
            ):
                return NumpyBackend.execute_sliced(
                    self, sp, arrays, max_slices=max_slices, host=host,
                    hoist=hoist,
                )

        bound = bind_circuit(
            brickwork_circuit(8, 6, np.random.default_rng(9)),
            target_size=64,
        )
        assert bound.sliced is not None
        det = [bound.template.request_bits("10101010")]
        got = bound.amplitudes_det(det, LegacyBackend())
        assert np.array_equal(got, bound.amplitudes_det(det))

    def test_service_uses_custom_dispatcher(self):
        """The ContractionService dispatcher hook: batches flow through
        the pluggable callable (the multi-host fan-out point) and the
        results are oracle-exact."""
        calls = []
        bound = bind_circuit(make_circuit(seed=5))

        def dispatcher(b, bits, backend):
            calls.append(len(bits))
            return b.amplitudes_det(bits, backend)

        with ContractionService(
            bound, dispatcher=dispatcher, max_batch=8, max_wait_ms=20.0
        ) as svc:
            bits = ["00000", "10101", "11111"]
            futs = [svc.submit(b) for b in bits]
            got = np.asarray([f.result(timeout=60) for f in futs])
        want = bound.amplitudes_det(
            [bound.template.request_bits(b) for b in bits]
        )
        assert np.array_equal(got, want)
        assert sum(calls) == 3


class TestSharedCacheWatcher:
    def _service(self, tmp_path, **kw):
        cache = PlanCache(tmp_path)
        svc = ContractionService.from_circuit(
            make_circuit(seed=7), plan_cache=cache, **kw
        )
        return svc, cache

    def test_adopts_foreign_publish(self, tmp_path):
        """Replica A's (simulated) replanner publish lands in replica
        B's running service: the watcher notices the fingerprint
        change, rebuilds through the cache-hit path, and stages the
        swap — amplitudes stay oracle-exact across it."""
        from tnc_tpu.contractionpath.paths.hyper import Hyperoptimizer
        from tnc_tpu.serve import SharedCacheWatcher
        from tnc_tpu.serve.rebind import plan_structure

        svc, cache = self._service(tmp_path)
        try:
            bound = svc.bound
            key = cache.key_for_network(
                bound.template.network, bound.target_size
            )
            watcher = SharedCacheWatcher(svc, cache)
            assert watcher.poll_once() is False  # nothing new yet

            # replica A publishes an improved plan (different finder →
            # different path with high probability; force a distinct
            # program by replanning with a hyper search)
            tn = bound.template.network
            path, slicing, program, sliced, result = plan_structure(
                tn, Hyperoptimizer(ntrials=2, polish_rounds=1)
            )
            plan = cache.record_for(
                path, program, slicing=slicing, sliced_program=sliced,
                finder="Hyperoptimizer",
            )
            cache.store(key, plan)

            before = svc.bound
            adopted = watcher.poll_once()
            if program.signature_digest() == before.program.signature_digest():
                # hyper found the same plan: the watcher must SKIP
                assert adopted is False
                assert watcher.stats["skips"] == 1
            else:
                assert adopted is True
                assert watcher.stats["adopts"] == 1
                # the staged bound adopts at the next batch boundary;
                # both plans contract the same network, so the value
                # agrees to accumulation rounding (a different path
                # re-associates the float sums)
                amp = svc.amplitude("00000", timeout_s=30)
                oracle = before.amplitudes_det(
                    [before.template.request_bits("00000")]
                )[0]
                assert amp == pytest.approx(oracle, rel=1e-10)
                assert svc.stats()["counts"]["plan_swaps"] == 1
        finally:
            svc.stop()

    def test_same_plan_republish_is_skipped(self, tmp_path):
        from tnc_tpu.serve import SharedCacheWatcher

        svc, cache = self._service(tmp_path)
        try:
            bound = svc.bound
            key = cache.key_for_network(
                bound.template.network, bound.target_size
            )
            watcher = SharedCacheWatcher(svc, cache)
            # touch the entry with the SAME plan content but new bytes
            plan = json.loads((tmp_path / f"{key}.json").read_text())
            plan["created_at"] = plan["created_at"] + 1.0
            cache.store(key, plan)
            assert watcher.poll_once() is False
            assert watcher.stats["skips"] == 1
        finally:
            svc.stop()

    def test_failed_adoption_retried_next_poll(self, tmp_path, monkeypatch):
        """A publish whose adoption fails (transient I/O on the shared
        volume) is retried on the next poll — the fingerprint only
        advances after the publish is fully handled."""
        from tnc_tpu.serve import SharedCacheWatcher
        from tnc_tpu.serve import replan as replan_mod

        svc, cache = self._service(tmp_path)
        try:
            bound = svc.bound
            key = cache.key_for_network(
                bound.template.network, bound.target_size
            )
            watcher = SharedCacheWatcher(svc, cache)
            plan = json.loads((tmp_path / f"{key}.json").read_text())
            plan["created_at"] = plan["created_at"] + 1.0
            cache.store(key, plan)

            real = replan_mod.bind_template
            monkeypatch.setattr(
                replan_mod, "bind_template",
                lambda *a, **k: (_ for _ in ()).throw(
                    OSError("shared volume hiccup")
                ),
            )
            with pytest.raises(OSError):
                watcher.poll_once()
            monkeypatch.setattr(replan_mod, "bind_template", real)
            # _seen did NOT advance: the same publish is seen again and
            # (being a same-plan re-publish) now deliberately skipped
            assert watcher.poll_once() is False
            assert watcher.stats["skips"] == 1
        finally:
            svc.stop()

    def test_from_circuit_watch_lifecycle(self, tmp_path):
        svc, cache = self._service(
            tmp_path, shared_cache_watch=True,
            watch_options={"poll_interval_s": 0.01},
        )
        assert len(svc._watchers) == 1
        watcher = svc._watchers[0]
        assert watcher._thread is not None
        svc.stop()
        assert watcher._thread is None  # stop() stopped the watcher

    def test_watch_requires_cache(self):
        with pytest.raises(ValueError, match="shared_cache_watch"):
            ContractionService.from_circuit(
                make_circuit(seed=7), shared_cache_watch=True
            )


class TestSharedStoreConcurrency:
    def test_entry_fingerprint_tracks_content(self, tmp_path):
        cache = PlanCache(tmp_path)
        assert cache.entry_fingerprint("k") is None
        cache.store("k", {"version": 1, "pairs": [[0, 1]]})
        fp1 = cache.entry_fingerprint("k")
        assert fp1
        assert cache.entry_fingerprint("k") == fp1  # stable read
        cache.store("k", {"version": 1, "pairs": [[1, 2]]})
        assert cache.entry_fingerprint("k") != fp1

    def test_concurrent_writers_never_interleave(self, tmp_path):
        """N threads racing store() on one key (the replica-fleet
        shape): every observed on-disk state must be one writer's
        COMPLETE entry, never a byte mix."""
        import threading

        cache = PlanCache(tmp_path)
        plans = [
            {"version": 1, "pairs": [[i, i + 1]] * 50, "writer": i}
            for i in range(8)
        ]
        stop = threading.Event()
        bad: list = []

        def reader():
            while not stop.is_set():
                plan = cache.load("k")
                if plan is not None and plan["pairs"] != (
                    [[plan["writer"], plan["writer"] + 1]] * 50
                ):
                    bad.append(plan)

        threads = [threading.Thread(target=reader) for _ in range(2)]
        for t in threads:
            t.start()
        writers = [
            threading.Thread(
                target=lambda p=p: [cache.store("k", p) for _ in range(20)]
            )
            for p in plans
        ]
        for w in writers:
            w.start()
        for w in writers:
            w.join()
        stop.set()
        for t in threads:
            t.join()
        assert not bad, f"interleaved reads observed: {bad[:1]}"
        # no stranded temp files beyond the published entry
        leftovers = list(tmp_path.glob("*.json.tmp"))
        assert leftovers == []
