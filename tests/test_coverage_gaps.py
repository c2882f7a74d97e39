"""Targeted tests for thin coverage spots:
benchmark CLI scenario enumeration and end-to-end modes, FM-refinement
rollback in the native-oracle bisection, GA operator paths, and the
benchmark logging/entry plumbing."""

import json
import logging
import random
import subprocess
import sys

import numpy as np
import pytest

GHZ3 = (
    'OPENQASM 2.0;\ninclude "qelib1.inc";\nqreg q[3];\n'
    "h q[0];\ncx q[0], q[1];\ncx q[1], q[2];\n"
)


@pytest.fixture()
def circuits_dir(tmp_path):
    d = tmp_path / "circuits"
    d.mkdir()
    (d / "ghz3.qasm").write_text(GHZ3)
    (d / "ghz3b.qasm").write_text(GHZ3)
    return d


def _args(circuits_dir, tmp_path, *extra):
    from tnc_tpu.benchmark.cli import build_parser

    return build_parser().parse_args(
        [
            "sweep",
            "--circuits-dir",
            str(circuits_dir),
            "--cache-dir",
            str(tmp_path / "cache"),
            "--out",
            str(tmp_path / "out.jsonl"),
            "--protocol",
            str(tmp_path / "protocol.jsonl"),
            *extra,
        ]
    )


def test_enumerate_scenarios_product_and_filters(circuits_dir, tmp_path):
    from tnc_tpu.benchmark.cli import enumerate_scenarios

    args = _args(
        circuits_dir, tmp_path, "--partitions", "2", "4", "--seeds", "0", "1"
    )
    scenarios = enumerate_scenarios(args)
    assert len(scenarios) == 2 * 2 * 2  # circuits x partitions x seeds

    args = _args(circuits_dir, tmp_path, "--include", "0", "3")
    assert len(enumerate_scenarios(args)) == 2  # 2 scenarios, [0,3) keeps both
    args = _args(circuits_dir, tmp_path, "--exclude", "0", "1")
    assert len(enumerate_scenarios(args)) == 1


def test_enumerate_scenarios_empty_dir_exits(tmp_path):
    from tnc_tpu.benchmark.cli import enumerate_scenarios

    empty = tmp_path / "none"
    empty.mkdir()
    with pytest.raises(SystemExit):
        enumerate_scenarios(_args(empty, tmp_path))


def test_cli_sweep_then_run_end_to_end(circuits_dir, tmp_path):
    """Full sweep→run round trip through main() (reference modes,
    ``benchmark/src/main.rs:195-219``), numpy backend, one scenario."""
    from tnc_tpu.benchmark.cli import main

    common = [
        "--circuits-dir",
        str(circuits_dir),
        "--cache-dir",
        str(tmp_path / "cache"),
        "--out",
        str(tmp_path / "out.jsonl"),
        "--protocol",
        str(tmp_path / "protocol.jsonl"),
        "--partitions",
        "2",
        "--include",
        "0",
        "1",
        "--time-budget",
        "2",
    ]
    assert main(["sweep", *common]) == 0
    assert main(["run", *common, "--backend", "numpy"]) == 0
    lines = [
        json.loads(l)
        for l in (tmp_path / "out.jsonl").read_text().splitlines()
    ]
    kinds = {l.get("kind") or l.get("type") or ("run" if "time_to_solution" in l else "sweep") for l in lines}
    assert len(lines) >= 2 and len(kinds) >= 1


def test_benchmark_module_entry_help():
    r = subprocess.run(
        [sys.executable, "-m", "tnc_tpu.benchmark", "--help"],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert r.returncode == 0
    assert "sweep" in r.stdout and "run" in r.stdout


def test_json_logging_writes_per_host_file(tmp_path):
    from tnc_tpu.benchmark.logging_util import setup_logging

    setup_logging(tmp_path, level=logging.INFO)
    logging.getLogger("tnc_tpu.test").info("hello %s", "world")
    for h in logging.getLogger().handlers:
        h.flush()
    files = list(tmp_path.glob("*.jsonl")) + list(tmp_path.glob("*.log"))
    assert files, "no per-host log file created"
    text = "".join(f.read_text() for f in files)
    assert "hello world" in text
    # restore a quiet root logger for the rest of the suite
    for h in list(logging.getLogger().handlers):
        logging.getLogger().removeHandler(h)


def test_fm_refine_rollback_keeps_best_prefix():
    """A move sequence whose tail worsens the cut must roll back to the
    best prefix (``_fm_refine`` rollback branch)."""
    from tnc_tpu.partitioning.bisect import Hypergraph, _fm_refine

    # path graph 0-1-2-3 with a heavy middle edge: initial alternating
    # partition has cut 3; the optimum [0,0,1,1] has cut 1.
    hg = Hypergraph(
        num_vertices=4,
        edge_pins=[[0, 1], [1, 2], [2, 3]],
        edge_weights=[1.0, 5.0, 1.0],
        vertex_weights=[1.0, 1.0, 1.0, 1.0],
    )
    part = [0, 1, 0, 1]
    _fm_refine(hg, part, target0=2.0, imbalance=0.6)

    def cut(p):
        return sum(
            w
            for pins, w in zip(hg.edge_pins, hg.edge_weights)
            if len({p[v] for v in pins}) > 1
        )

    assert cut(part) <= 2.0  # strictly better than the initial cut of 7
    assert len(set(part)) == 2  # still a 2-way partition


def test_fm_refine_respects_balance():
    from tnc_tpu.partitioning.bisect import Hypergraph, _fm_refine

    # star: all vertices want to join vertex 0's block, balance forbids it
    hg = Hypergraph(
        num_vertices=4,
        edge_pins=[[0, 1], [0, 2], [0, 3]],
        edge_weights=[1.0, 1.0, 1.0],
        vertex_weights=[1.0, 1.0, 1.0, 1.0],
    )
    part = [0, 0, 1, 1]
    _fm_refine(hg, part, target0=2.0, imbalance=0.1)
    w0 = sum(1 for p in part if p == 0)
    assert 1 <= w0 <= 3  # never collapses to one side


def test_genetic_balance_partitions_improves_or_matches():
    from tnc_tpu.builders.connectivity import ConnectivityLayout
    from tnc_tpu.builders.random_circuit import random_circuit
    from tnc_tpu.contractionpath.repartitioning.genetic import (
        GeneticSettings,
        balance_partitions,
    )
    from tnc_tpu.tensornetwork.partitioning import find_partitioning
    from tnc_tpu.tensornetwork.simplify import simplify_network

    rng = np.random.default_rng(2)
    tn = simplify_network(
        random_circuit(
            10, 6, 0.5, 0.5, rng, ConnectivityLayout.LINE, bitstring="0" * 10
        )
    )
    init = find_partitioning(tn, 2)
    settings = GeneticSettings(
        population_size=8, max_generations=4, stale_limit=3
    )
    best, score = balance_partitions(
        tn, init, 2, rng=random.Random(0), settings=settings, max_time=20
    )
    assert len(best) == len(init)
    assert np.isfinite(score) and score > 0
