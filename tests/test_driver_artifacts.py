"""The two artifacts the round driver consumes must always work:
bench.py (one JSON line) and __graft_entry__ (entry + dryrun_multichip)."""

import io
import json
import sys

import jax
import pytest


def test_bench_main_emits_one_json_line(monkeypatch, capsys):
    import bench

    monkeypatch.setattr(bench, "BATCH", 1 << 14)
    monkeypatch.setattr(bench, "NUM_METRICS", 64)
    monkeypatch.setattr(bench, "BUCKET_LIMIT", 256)
    bench.main()
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1
    payload = json.loads(out[0])
    for key in ("metric", "value", "unit", "vs_baseline", "ingest_path"):
        assert key in payload
    assert payload["value"] > 0
    assert payload["unit"] == "samples/s"


def test_bench_plausibility_guard_refuses_impossible_rates(
    monkeypatch, capsys
):
    import bench
    import benchmarks.h2d_bench as h2d

    monkeypatch.setattr(
        h2d, "run", lambda **kw: {"value": 1.0, "transport": "stub"}
    )
    # the 31T samples/s class of broken timing (async backend acking
    # before execution) must be withheld, not reported as the headline
    monkeypatch.setattr(bench, "measure_headline", lambda *a, **k: {
        "samples_per_s": 3.1e13, "elapsed_s": 1e-4, "samples": 1,
        "ingest_path": "stub", "percentile_query_p99_us": 1.0,
        "percentile_query_median_us": 1.0,
    })
    bench.main()
    payload = json.loads(capsys.readouterr().out.strip())
    assert payload["suspect"] is True
    assert payload["value"] is None
    assert payload["vs_baseline"] is None
    assert payload["measured_samples_per_s"] == pytest.approx(3.1e13)
    assert payload["plausibility_cap_samples_per_s"] > 0


def test_plausibility_cap_scales_with_accumulator():
    import bench

    vmem = 128 * 1024 * 1024
    v5e = 819e9  # Google Cloud, "TPU v5e"
    assert bench.plausibility_cap_samples_per_s("TPU v5 lite", vmem) == v5e / 8
    assert bench.plausibility_cap_samples_per_s(
        "TPU v5 lite", vmem + 1) == v5e / 16
    assert bench.plausibility_cap_samples_per_s("cpu", 1 << 30) == 4e11 / 16
    # a device kind with no peak on record is an error, not a default
    with pytest.raises(ValueError, match="no peak memory bandwidth"):
        bench.plausibility_cap_samples_per_s("TPU v6 lite", 1 << 10)


def test_graft_entry_compiles_and_runs():
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    acc, stats = out
    assert acc.shape[0] == 64
    assert "percentiles" in stats


@pytest.mark.parametrize("n", [1, 2, 4, 8])
def test_dryrun_multichip(n):
    import __graft_entry__ as g

    # headline-shape validation once (the driver's own n=8 call); the
    # smaller device counts exercise mesh construction + sharding on
    # cheap shapes so the sweep doesn't pay 4x the 10k x 8193 compile
    g.dryrun_multichip(n, headline=(n == 8))
