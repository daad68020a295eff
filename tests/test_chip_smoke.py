"""chip_smoke.py on the CPU: it refuses to run without a TPU, and its
checks pass on a tiny run and fail when a device dispatch raises."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Workload(
    num_metrics=256, intervals=2, samples_per_interval=1 << 14,
    host_writes_per_interval=400, retention=((4, 1), (2, 4)),
)


def test_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_checks_pass_on_a_tiny_cpu_run():
    res = chip_smoke.run_system(TINY, seed=3, snapshot_acc=True)
    assert res["paths"]["commit"] == "fused"
    assert res["fused_intervals"] >= TINY.intervals
    assert res["errors_logged"] == 0 and res["shed_samples"] == 0
    assert res["worst_percentile_rel_err"] <= chip_smoke.TOLERANCE


def test_fails_when_a_device_dispatch_raises():
    """An injected device failure in the fused commit is recovered by the
    pipeline (counts stay exact through the host spill) — and the smoke
    must still fail, because the device did not do the work."""
    from loghisto_tpu.resilience import FaultInjector, ResilienceConfig

    inj = FaultInjector(seed=0).plan("commit.dispatch", on_call=3)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.run_system(
            TINY, seed=3, resilience=ResilienceConfig(fault_injector=inj)
        )
    assert inj.fires_at("commit.dispatch") == 1


def test_reference_rank_rule_is_np_quantile():
    import numpy as np

    rng = np.random.default_rng(0)
    ids = rng.integers(0, 5, 2000).astype(np.int32)
    values = rng.lognormal(3, 1, 2000)
    ps = list(chip_smoke.INTERVAL_PERCENTILES.values())
    counts, q = chip_smoke.rank_quantiles(ids, values, 6, ps)
    assert counts[5] == 0 and np.isnan(q[5]).all()
    for i in range(5):
        want = np.quantile(values[ids == i], ps, method="inverted_cdf")
        np.testing.assert_array_equal(q[i], want)


@pytest.mark.parametrize("env_dir", [None, "/tmp/somewhere/jax-cache"])
def test_compile_cache_location(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins when set (and no other cache is
    set); otherwise the cache is the fixed <repo>/.jax_cache/."""
    import jax

    from loghisto_tpu.utils.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        path = enable_compile_cache()
        if env_dir is None:
            assert path == os.path.join(ROOT, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        else:
            assert path == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
