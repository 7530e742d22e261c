"""Fixtures of the benchmark's own tests: tiny cells that run on the CPU."""
from __future__ import annotations

import dataclasses
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
# bandwidth and compute peaks of the v5e, for readers run on the CPU
TEST_PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def shrink(cell, **params):
    """``cell`` with its configuration's generator parameters replaced."""
    config = dict(cell.config)
    config["params"] = dict(config["params"], **params)
    return dataclasses.replace(cell, config=config)


@pytest.fixture(scope="module")
def bench_spec():
    from bench.spec import Benchmark

    return Benchmark(ROOT)


@pytest.fixture(scope="module")
def tiny_protein(bench_spec):
    """The protein expansion cell at n = 2^10 with families of 64 (~32
    nonzeros per column, so that the CPU multiplies quickly)."""
    return shrink(bench_spec.cell("protein-2e18.square-sync"), log2_n=10,
                  family=64, intra_p=0.32)


@pytest.fixture(scope="module")
def tiny_rmat(bench_spec):
    """The R-MAT squaring cell at scale 9."""
    return shrink(bench_spec.cell("rmat-s20.square-sync"), scale=9)
