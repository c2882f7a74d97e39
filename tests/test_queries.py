"""Query engine (tnc_tpu.queries): chain-rule sampling, Pauli
expectation values and marginal sweeps, pinned against the dense
statevector oracle — and all three as first-class query types on a
mixed ContractionService queue with plan-cache reuse.

Exactness tiers: on circuits whose gate entries are exactly
representable (X/CX/Z permutation-and-phase circuits, and GHZ — whose
contraction sums mix only exact zeros into the H-roundoff products)
the tensor-network answers BIT-compare to the dense oracle on the
numpy backend; on generic rotation circuits they agree to 1e-12.
"""

from __future__ import annotations

import math
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import tnc_tpu.obs as obs
from tnc_tpu.builders.circuit_builder import Circuit
from tnc_tpu.obs.core import MetricsRegistry
from tnc_tpu.queries import statevector as sv
from tnc_tpu.queries.expectation import (
    bind_expectation,
    pauli_expectation,
    pauli_expectation_value_and_grad,
    pauli_sum_expectation,
)
from tnc_tpu.queries.marginal import marginal_sweep
from tnc_tpu.queries.sampling import ChainSampler, sample_bitstrings
from tnc_tpu.tensornetwork.tensordata import TensorData


def _ghz(n: int) -> Circuit:
    c = Circuit()
    reg = c.allocate_register(n)
    c.append_gate(TensorData.gate("h"), [reg.qubit(0)])
    for i in range(n - 1):
        c.append_gate(TensorData.gate("cx"), [reg.qubit(i), reg.qubit(i + 1)])
    return c


def _exact(n: int = 3) -> Circuit:
    """X/CX only — every amplitude is exactly 0 or 1 (all arithmetic
    exact in float64), the bitwise-pin workhorse."""
    c = Circuit()
    reg = c.allocate_register(n)
    c.append_gate(TensorData.gate("x"), [reg.qubit(0)])
    for i in range(n - 1):
        c.append_gate(TensorData.gate("cx"), [reg.qubit(i), reg.qubit(i + 1)])
    c.append_gate(TensorData.gate("x"), [reg.qubit(n - 1)])
    return c


def _rotations(n: int = 4, depth: int = 3, seed: int = 5) -> Circuit:
    """Generic parameterized circuit (rx/ry/rz + cx brick)."""
    rng = np.random.default_rng(seed)
    c = Circuit()
    reg = c.allocate_register(n)
    names = ["rx", "ry", "rz"]
    for layer in range(depth):
        for q in range(n):
            name = names[int(rng.integers(len(names)))]
            c.append_gate(
                TensorData.gate(name, [float(rng.uniform(0, 2 * math.pi))]),
                [reg.qubit(q)],
            )
        for q in range(layer % 2, n - 1, 2):
            c.append_gate(
                TensorData.gate("cx"), [reg.qubit(q), reg.qubit(q + 1)]
            )
    return c


# ---------------------------------------------------------------------------
# dense statevector oracle self-checks


class TestStatevectorOracle:
    def test_matches_tnc_amplitudes(self):
        from tnc_tpu.contractionpath.paths import Greedy, OptMethod
        from tnc_tpu.ops.backends import NumpyBackend
        from tnc_tpu.ops.program import build_program, flat_leaf_tensors

        circuit = _rotations()
        state = sv.statevector(circuit)
        for bits in ["0000", "1010", "1111", "0110"]:
            tn, _ = circuit.copy().into_amplitude_network(bits)
            res = Greedy(OptMethod.GREEDY).find_path(tn)
            program = build_program(tn, res.replace_path())
            arrays = [
                leaf.data.into_data() for leaf in flat_leaf_tensors(tn)
            ]
            want = complex(
                np.asarray(NumpyBackend().execute(program, arrays)).reshape(())
            )
            assert abs(sv.amplitude(state, bits) - want) < 1e-12

    def test_norm_and_marginals(self):
        state = sv.statevector(_rotations())
        assert abs(np.sum(sv.probabilities(state)) - 1.0) < 1e-12
        p = sv.marginal_probability(state, "0***")
        p0, p1 = sv.conditional_distribution(state, "")
        assert abs(p - p0) < 1e-15 and abs(p0 + p1 - 1.0) < 1e-12

    def test_pauli_expectation_vs_dense_matrix(self):
        state = sv.statevector(_rotations(3, 2))
        flat = state.reshape(-1)
        for pauli in ["zxy", "iyz", "xxx"]:
            want = complex(
                np.vdot(flat, sv.pauli_string_matrix(pauli) @ flat)
            )
            assert abs(sv.pauli_expectation(state, pauli) - want) < 1e-12

    def test_rejects_finalized_circuit(self):
        c = _ghz(2)
        c.into_statevector_network()
        with pytest.raises(ValueError, match="un-finalized"):
            sv.statevector(c)


# ---------------------------------------------------------------------------
# chain-rule sampling


class TestSampling:
    def test_conditionals_bitwise_on_ghz12(self):
        """Per-qubit conditional marginals bit-compare to the dense
        oracle on a 12-qubit GHZ chain, every prefix length."""
        n = 12
        circuit = _ghz(n)
        state = sv.statevector(circuit)
        sampler = ChainSampler(circuit)
        for prefix in ["", "0", "1", "01", "00", "0" * 11, "1" * 11]:
            got = sampler.marginals([prefix])[0]
            want = sv.conditional_distribution(state, prefix)
            assert got[0] == want[0] and got[1] == want[1], (
                prefix, got, want
            )

    def test_conditionals_bitwise_on_exact_circuit(self):
        circuit = _exact(5)
        state = sv.statevector(circuit)
        sampler = ChainSampler(circuit)
        got = sampler.marginals([""])[0]
        want = sv.conditional_distribution(state, "")
        assert got[0] == want[0] and got[1] == want[1]
        assert set(np.asarray(got).tolist()) <= {0.0, 1.0}

    def test_conditionals_allclose_on_rotation_circuit(self):
        circuit = _rotations(5, 3)
        state = sv.statevector(circuit)
        sampler = ChainSampler(circuit)
        for prefix in ["", "0", "10", "110", "0101"]:
            got = sampler.marginals([prefix])[0]
            want = sv.conditional_distribution(state, prefix)
            np.testing.assert_allclose(got, want, rtol=0, atol=1e-12)

    def test_sampled_stream_matches_oracle_sampler(self):
        """A seeded sampler run equals the dense oracle's chain-rule
        sampler run (same draw discipline, same RNG) on a circuit with
        exact conditionals — the strongest end-to-end exactness pin."""
        circuit = _ghz(6)
        state = sv.statevector(circuit)
        got = ChainSampler(circuit).sample(16, seed=20260804)
        want = sv.sample_oracle(
            state, 16, np.random.default_rng(20260804)
        )
        assert got == want

    def test_sample_distribution_roughly_uniform_on_ghz(self):
        samples = sample_bitstrings(_ghz(4), 200, seed=7)
        assert set(samples) == {"0000", "1111"}
        ones = sum(1 for s in samples if s[0] == "1")
        assert 60 <= ones <= 140  # ~Binomial(200, .5), generous bounds

    def test_corider_independence(self):
        """A request's sampled stream is identical whether dispatched
        alone or co-batched with other requests."""
        solo = ChainSampler(_rotations(4, 2)).sample(8, seed=11)
        groups = ChainSampler(_rotations(4, 2)).sample_groups(
            [(3, 99), (8, 11), (5, 123)]
        )
        assert groups[1] == solo

    def test_prefix_dedup_batches_conditionals(self):
        """The frozen-bits fast path dispatches one conditional per
        DISTINCT prefix: on GHZ there are at most 2 live prefixes per
        step, however many samples are in flight."""
        obs.configure(enabled=True, registry=MetricsRegistry())
        try:
            ChainSampler(_ghz(5)).sample(64, seed=3)
            counters = obs.counters_by_prefix("queries.sample.")
            steps = counters["queries.sample.steps"]
            conditionals = counters["queries.sample.conditionals"]
            assert steps == 5
            assert conditionals <= 2 * 5  # ≤ 2 distinct prefixes per step
        finally:
            obs.configure(enabled=False)

    def test_deterministic_across_hash_seeds(self):
        """A seeded sampler stream is reproducible across processes
        with different PYTHONHASHSEED (nothing on the sampling path
        iterates a hash-ordered container)."""
        code = (
            "import numpy as np\n"
            "from tnc_tpu.builders.circuit_builder import Circuit\n"
            "from tnc_tpu.tensornetwork.tensordata import TensorData\n"
            "from tnc_tpu.queries.sampling import ChainSampler\n"
            "c = Circuit(); reg = c.allocate_register(5)\n"
            "c.append_gate(TensorData.gate('h'), [reg.qubit(0)])\n"
            "c.append_gate(TensorData.gate('ry', [0.8]), [reg.qubit(2)])\n"
            "for i in range(4):\n"
            "    c.append_gate(TensorData.gate('cx'),"
            " [reg.qubit(i), reg.qubit(i + 1)])\n"
            "print(' '.join(ChainSampler(c).sample(12, seed=42)))\n"
        )
        streams = set()
        for seed in ("0", "424242"):
            env = dict(os.environ)
            env["PYTHONHASHSEED"] = seed
            env["JAX_PLATFORMS"] = "cpu"
            r = subprocess.run(
                [sys.executable, "-c", code],
                capture_output=True, text=True, env=env, check=True,
            )
            streams.add(r.stdout.strip())
        assert len(streams) == 1

    def test_circuit_not_consumed(self):
        circuit = _ghz(3)
        ChainSampler(circuit).sample(2, seed=0)
        # still usable: another finalizer works
        circuit.into_statevector_network()

    def test_rejects_bad_args(self):
        with pytest.raises(ValueError):
            ChainSampler(_ghz(2)).sample(0, seed=0)
        with pytest.raises(ValueError):
            ChainSampler(Circuit())


# ---------------------------------------------------------------------------
# expectation values


class TestExpectation:
    def test_identity_norm_exact(self):
        assert pauli_expectation(_exact(3), "iii") == (1 + 0j)

    def test_values_bitwise_on_exact_circuit(self):
        """⟨ψ|P|ψ⟩ BIT-compares to the dense oracle on the numpy
        backend for exact-arithmetic circuits."""
        state = sv.statevector(_exact(3))
        for pauli in ["zii", "izi", "iiz", "zzz", "xxi", "iii"]:
            got = pauli_expectation(_exact(3), pauli)
            want = sv.pauli_expectation(state, pauli)
            assert got == want, (pauli, got, want)

    def test_values_allclose_on_rotation_circuit(self):
        state = sv.statevector(_rotations(3, 2))
        for pauli in ["zzi", "xyz", "yix", "yyy", "izx"]:
            got = pauli_expectation(_rotations(3, 2), pauli)
            want = sv.pauli_expectation(state, pauli)
            assert abs(got - want) < 1e-12, (pauli, got, want)

    def test_y_transpose_convention(self):
        """The observable leaf stores Pᵀ; Y (antisymmetric) is where
        the convention shows: rx(θ)|0⟩ has ⟨Y⟩ = -sin(θ) ≠ 0."""
        theta = 0.9

        def mk():
            c = Circuit()
            reg = c.allocate_register(1)
            c.append_gate(TensorData.gate("rx", [theta]), [reg.qubit(0)])
            return c

        got = pauli_expectation(mk(), "y")
        want = sv.pauli_expectation(sv.statevector(mk()), "y")
        assert abs(got - want) < 1e-12
        assert abs(got.real - (-math.sin(theta))) < 1e-12

    def test_pauli_sum_batches_one_structure(self):
        """Terms of a Pauli sum share one planned sandwich: the batched
        total bit-compares to the per-term singleton dispatches, and
        only ONE find_path span is recorded for all terms."""
        terms = [(0.5, "zzi"), (-1.25, "xxi"), (2.0, "iyy"), (0.75, "iii")]
        obs.configure(enabled=True, registry=MetricsRegistry())
        try:
            prog = bind_expectation(_rotations(3, 2))
            total, vals = prog.pauli_sum(terms)
            spans = [
                r for r in obs.get_registry().span_records()
                if r.name == "plan.find_path"
            ]
            assert len(spans) == 1
        finally:
            obs.configure(enabled=False)
        singles = [
            complex(bind_expectation(_rotations(3, 2)).values([p])[0])
            for _, p in terms
        ]
        for got, want in zip(vals, singles):
            assert got == want  # same program, same arithmetic: bitwise
        # pauli_expectation contracts each term's own lightcone: another
        # network, the same value to rounding
        for (_, p), want in zip(terms, singles):
            assert abs(pauli_expectation(_rotations(3, 2), p) - want) < 1e-12
        assert total == complex(
            sum(c * v for (c, _), v in zip(terms, singles))
        )

    def test_pauli_sum_expectation_value(self):
        state = sv.statevector(_rotations(3, 2))
        terms = [(0.5, "zii"), (1.5, "ixi")]
        got = pauli_sum_expectation(_rotations(3, 2), terms)
        want = sum(c * sv.pauli_expectation(state, p) for c, p in terms)
        assert abs(got - want) < 1e-12

    def test_invalid_pauli_rejected(self):
        with pytest.raises(ValueError, match="position 1"):
            pauli_expectation(_ghz(3), "zqz")
        with pytest.raises(ValueError, match="length"):
            pauli_expectation(_ghz(3), "zz")
        with pytest.raises(ValueError, match="at least one term"):
            pauli_sum_expectation(_ghz(3), [])


class TestExpectationGradients:
    def test_grads_match_finite_differences(self):
        """Cotangents of Re(Σ c_t ⟨P_t⟩) w.r.t. sandwich leaves vs
        entrywise finite differences through the dense oracle forward
        (perturbing the SAME leaf the cotangent belongs to)."""
        jax = pytest.importorskip("jax")
        del jax
        terms = [(1.0, "zz"), (0.5, "xi")]

        def mk(delta=None, slot=None):
            c = Circuit()
            reg = c.allocate_register(2)
            c.append_gate(TensorData.gate("ry", [0.8]), [reg.qubit(0)])
            c.append_gate(
                TensorData.gate("cx"), [reg.qubit(0), reg.qubit(1)]
            )
            c.append_gate(TensorData.gate("rx", [0.3]), [reg.qubit(1)])
            return c

        # slot 2 = the ry gate leaf (kets are slots 0-1), ket layer
        val, _vals, grads = pauli_expectation_value_and_grad(
            mk(), terms, wrt=[2], dtype="complex64"
        )
        g = grads[0]

        # dense-oracle forward with the ket-layer ry leaf perturbed
        # (adjoint layer held fixed): build the sandwich value by hand
        def forward(leaf):
            # ⟨ψ_adj| P |ψ_ket⟩ with ψ_ket using `leaf`, ψ_adj the
            # unperturbed circuit — matches differentiating only the
            # ket-layer slot
            base = sv.statevector(mk())

            c = Circuit()
            reg = c.allocate_register(2)
            c.append_gate(TensorData.matrix(leaf), [reg.qubit(0)])
            c.append_gate(
                TensorData.gate("cx"), [reg.qubit(0), reg.qubit(1)]
            )
            c.append_gate(TensorData.gate("rx", [0.3]), [reg.qubit(1)])
            ket = sv.statevector(c)
            out = 0.0
            for coeff, pauli in terms:
                out += (
                    coeff
                    * np.vdot(
                        base.reshape(-1),
                        sv.apply_paulis(ket, pauli).reshape(-1),
                    )
                ).real
            return out

        leaf0 = TensorData.gate("ry", (0.8,)).into_data()
        eps = 1e-4
        for idx in np.ndindex(2, 2):
            d = np.zeros((2, 2), dtype=complex)
            d[idx] = eps
            fd_re = (forward(leaf0 + d) - forward(leaf0 - d)) / (2 * eps)
            fd_im = (
                forward(leaf0 + 1j * d) - forward(leaf0 - 1j * d)
            ) / (2 * eps)
            # df = Re(sum(g * dT)): real perturbation picks Re(g),
            # imaginary picks -Im(g)
            assert abs(g[idx].real - fd_re) < 1e-3, idx
            assert abs(-g[idx].imag - fd_im) < 1e-3, idx
        assert isinstance(val, float)

    def test_theta_chain_rule_both_layers(self):
        """df/dθ composes the ket-layer AND adjoint-layer cotangents;
        checked against finite differences of the dense expectation."""
        pytest.importorskip("jax")
        theta = 0.7
        terms = [(1.0, "zi"), (0.5, "xx")]

        def mk(t=theta):
            c = Circuit()
            reg = c.allocate_register(2)
            c.append_gate(TensorData.gate("rx", [t]), [reg.qubit(0)])
            c.append_gate(
                TensorData.gate("cx"), [reg.qubit(0), reg.qubit(1)]
            )
            return c

        # sandwich flat leaves: [ket, ket, rx, cx, adj-ket, adj-ket,
        # adj-rx, adj-cx, obs, obs] → rx is slot 2, its mirror slot 6
        _val, _vals, grads = pauli_expectation_value_and_grad(
            mk(), terms, wrt=[2, 6], dtype="complex64"
        )
        g_ket, g_adj = grads
        s, c_ = math.sin(theta / 2) / 2, math.cos(theta / 2) / 2
        dG = np.array([[-s, -1j * c_], [-1j * c_, -s]])
        # adjoint leaf stores G† (conj-transpose for a 1-qubit gate)
        dfdth = float(
            np.sum(g_ket * dG).real + np.sum(g_adj * np.conj(dG).T).real
        )

        def f(t):
            state = sv.statevector(mk(t))
            return sum(
                coeff * sv.pauli_expectation(state, p).real
                for coeff, p in terms
            )

        eps = 1e-5
        fd = (f(theta + eps) - f(theta - eps)) / (2 * eps)
        assert abs(dfdth - fd) < 1e-3

    def test_batched_sum_grads_match_singletons(self):
        """The batched Pauli-sum reverse sweep equals the
        coefficient-weighted sum of single-term gradients."""
        pytest.importorskip("jax")
        terms = [(1.0, "zzi"), (-0.5, "xix")]
        _v, _vals, grads_sum = pauli_expectation_value_and_grad(
            _rotations(3, 2), terms, wrt=[3, 4]
        )
        singles = [
            pauli_expectation_value_and_grad(
                _rotations(3, 2), [(coeff, p)], wrt=[3, 4]
            )[2]
            for coeff, p in terms
        ]
        for i in range(2):
            want = singles[0][i] + singles[1][i]
            np.testing.assert_allclose(
                grads_sum[i], want, rtol=0, atol=1e-5
            )


# ---------------------------------------------------------------------------
# marginal sweeps


class TestMarginalSweep:
    def test_matches_dense_oracle(self):
        circuit = _rotations(5, 2)
        state = sv.statevector(circuit)
        patterns = ["0*1*0", "1*0*1", "0*0*0", "1*1*1"]
        got = marginal_sweep(circuit.copy(), patterns)
        want = [sv.marginal_probability(state, p) for p in patterns]
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-12)

    def test_bitwise_on_exact_circuit(self):
        circuit = _exact(4)
        state = sv.statevector(circuit)
        got = marginal_sweep(circuit.copy(), ["1*1*", "0*0*"])
        want = [
            sv.marginal_probability(state, "1*1*"),
            sv.marginal_probability(state, "0*0*"),
        ]
        assert got.tolist() == want

    def test_fully_determined_pattern_is_probability(self):
        circuit = _ghz(3)
        state = sv.statevector(circuit)
        got = marginal_sweep(circuit.copy(), ["000", "111", "010"])
        want = [abs(sv.amplitude(state, b)) ** 2 for b in ["000", "111", "010"]]
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-12)

    def test_mask_mismatch_raises(self):
        with pytest.raises(ValueError, match="wildcard mask"):
            marginal_sweep(_ghz(3), ["0*0", "00*"])

    def test_results_clipped_nonnegative(self):
        out = marginal_sweep(_rotations(4, 2), ["00**", "11**"])
        assert np.all(out >= 0.0)


# ---------------------------------------------------------------------------
# the mixed service queue


class TestMixedServiceQueue:
    def _mk(self, n=4):
        return _rotations(n, 2, seed=17)

    def test_mixed_queue_serves_all_types(self):
        state = sv.statevector(self._mk())
        from tnc_tpu.serve import ContractionService

        with ContractionService.from_circuit(
            self._mk(), queries=True, max_batch=8, max_wait_ms=5.0
        ) as svc:
            futs = {
                "amp": svc.submit("0110"),
                "sample": svc.submit_sample(6, seed=9),
                "exp": svc.submit_expectation([(1.0, "zzii"), (0.5, "xiix")]),
                "marg": svc.submit_marginal("01**"),
            }
            res = {k: f.result(timeout=60) for k, f in futs.items()}
            stats = svc.stats()

        assert abs(res["amp"] - sv.amplitude(state, "0110")) < 1e-12
        assert res["sample"] == ChainSampler(self._mk()).sample(6, seed=9)
        want_exp = 1.0 * sv.pauli_expectation(state, "zzii") + (
            0.5 * sv.pauli_expectation(state, "xiix")
        )
        assert abs(res["exp"] - want_exp) < 1e-12
        assert abs(res["marg"] - sv.marginal_probability(state, "01**")) < 1e-12

        by_type = stats["by_type"]
        for kind in ("amplitude", "sample", "expectation", "marginal"):
            assert by_type[kind]["counts"]["completed"] == 1, by_type
            assert by_type[kind]["counts"]["batches"] >= 1

    def test_batches_never_mix_types(self):
        """One submission burst of mixed kinds: every dispatched batch
        carries exactly one kind (span kind= attribute)."""
        from tnc_tpu.serve import ContractionService

        obs.configure(enabled=True, registry=MetricsRegistry())
        try:
            with ContractionService.from_circuit(
                self._mk(), queries=True, max_batch=32, max_wait_ms=20.0
            ) as svc:
                futs = []
                for _ in range(4):
                    futs.append(svc.submit("0000"))
                    futs.append(svc.submit_expectation("zzii"))
                    futs.append(svc.submit_marginal("0***"))
                for f in futs:
                    f.result(timeout=60)
            spans = [
                r for r in obs.get_registry().span_records()
                if r.name == "serve.dispatch"
            ]
            kinds = [r.args.get("kind") for r in spans]
            assert all(k in ("amplitude", "expectation", "marginal")
                       for k in kinds)
            # grouped: fewer dispatches than requests, and at least one
            # batch per kind present
            assert {"amplitude", "expectation", "marginal"} <= set(kinds)
            assert len(spans) < 12
        finally:
            obs.configure(enabled=False)

    def test_repeat_round_zero_pathfinding_with_plan_cache(self):
        """Acceptance pin: a mixed queue served twice — round 2 through
        a FRESH service over the same plan cache — performs ZERO
        pathfinding (no plan.find_path spans) and hits the cache."""
        from tnc_tpu.serve import ContractionService, PlanCache

        def round_trip(svc):
            futs = [
                svc.submit("0000"),
                svc.submit_sample(3, seed=1),
                svc.submit_expectation("zzii"),
                svc.submit_marginal("00**"),
            ]
            return [f.result(timeout=60) for f in futs]

        def find_path_spans():
            return sum(
                1 for r in obs.get_registry().span_records()
                if r.name == "plan.find_path"
            )

        obs.configure(enabled=True, registry=MetricsRegistry())
        try:
            with tempfile.TemporaryDirectory() as cache_dir:
                cache = PlanCache(cache_dir)
                with ContractionService.from_circuit(
                    self._mk(), queries=True, plan_cache=cache,
                    max_batch=8, max_wait_ms=2.0,
                ) as svc:
                    first = round_trip(svc)
                spans_after_first = find_path_spans()
                assert spans_after_first > 0

                with ContractionService.from_circuit(
                    self._mk(), queries=True, plan_cache=cache,
                    max_batch=8, max_wait_ms=2.0,
                ) as svc2:
                    second = round_trip(svc2)
                assert find_path_spans() == spans_after_first, (
                    "second round re-ran the pathfinder"
                )
                hits = obs.counters_by_prefix("serve.plan_cache.hit")
                assert sum(hits.values()) >= 4  # amp + sample ks + exp + marg
            # identical answers across rounds (same plans, same values)
            assert first[0] == second[0]
            assert first[1] == second[1]
            assert first[2] == second[2]
            assert first[3] == second[3]
        finally:
            obs.configure(enabled=False)

    def test_invalid_payloads_fail_at_submit(self):
        from tnc_tpu.serve import ContractionService

        with ContractionService.from_circuit(
            self._mk(), queries=True
        ) as svc:
            with pytest.raises(ValueError):
                svc.submit_expectation("zz")  # wrong length
            with pytest.raises(ValueError):
                svc.submit_sample(0)
            with pytest.raises(ValueError):
                svc.submit_marginal("012*")
            with pytest.raises(ValueError, match="no handler"):
                svc.submit_query("nope", 1)
            # the queue survives all of the above
            assert svc.marginal("****") == pytest.approx(1.0)

    def test_unregistered_kinds_raise_without_queries(self):
        from tnc_tpu.serve import ContractionService

        with ContractionService.from_circuit(self._mk()) as svc:
            with pytest.raises(ValueError, match="no handler"):
                svc.submit_sample(1)

    def test_per_type_obs_counters(self):
        from tnc_tpu.serve import ContractionService

        obs.configure(enabled=True, registry=MetricsRegistry())
        try:
            with ContractionService.from_circuit(
                self._mk(), queries=True, max_batch=4, max_wait_ms=2.0
            ) as svc:
                svc.amplitude("0000")
                svc.sample(2, seed=0)
                svc.expectation("ziii")
            counters = obs.get_registry().counters()
            submitted = {
                dict(k[1]).get("type"): v
                for k, v in counters.items()
                if k[0] == "serve.query.submitted"
            }
            assert submitted.get("amplitude") == 1
            assert submitted.get("sample") == 1
            assert submitted.get("expectation") == 1
            hist = {
                dict(k[1]).get("type")
                for k, v in obs.get_registry().histograms().items()
                if k[0] == "serve.query.latency_s"
            }
            assert {"amplitude", "sample", "expectation"} <= hist
        finally:
            obs.configure(enabled=False)

    def test_expired_query_requests_counted_per_type(self):
        from tnc_tpu.serve import ContractionService, DeadlineExceededError

        svc = ContractionService.from_circuit(
            self._mk(), queries=True, max_batch=4, max_wait_ms=1.0
        )
        try:
            fut = svc.submit_marginal("00**", timeout_s=-0.001)
            with pytest.raises(DeadlineExceededError):
                fut.result(timeout=60)
            stats = svc.stats()
            assert stats["by_type"]["marginal"]["counts"]["expired"] == 1
        finally:
            svc.stop()
